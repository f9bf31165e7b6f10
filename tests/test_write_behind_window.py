"""performance/write-behind: a pressure drain no longer holds the
write that set it off (ISSUE 33).

A write that fills the window is answered while its drain is still
below; ``window-size`` bounds the bytes answered and not yet landed
(absorbed and in flight) by upstream's rule: the write waits, in
``wb.wait``, only while the bytes in flight *before its own* exceed
it.  Drains of one fd whose byte ranges do not touch run side by side,
an overwrite lands after what it overwrites, and whatever has to see
the file as written (fsync, flush, readv, fstat, ftruncate, release)
returns only when everything in flight has landed.  The child here
parks every ``writev`` on an event the test sets: nothing is timed.
The last test serves a 4+2 volume in this process and shows
``cluster/ec`` two ``writev`` of one inode at once."""

import asyncio
import errno

import numpy as np
import pytest

from glusterfs_tpu.api.glfs import SyncClient
from glusterfs_tpu.core.fops import FopError
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.core.iatt import Iatt
from glusterfs_tpu.core.layer import FdObj
from glusterfs_tpu.performance.write_behind import WriteBehindLayer
from glusterfs_tpu.utils.volspec import ec_volfile

W = 4096  # window-size of these tests, and the size of a full write


class Parked:
    """A child whose ``writev`` parks until the test lets it land
    (``land(i)``: the i-th call in the order of arrival)."""

    def __init__(self, free: bool = False):
        self.type_name = self.name = "parked"
        self.children, self.parents = [], []
        self.free = free            # land at once
        self.calls: list[tuple[int, int]] = []  # (offset, length)
        self.gates: list[asyncio.Event] = []
        self.landed: list[int] = []
        self.fail: set[int] = set()
        self.data = bytearray()
        self.inside = self.most = 0
        self.seen: list[str] = []   # every other fop, as it arrives

    async def writev(self, fd, data, offset, xdata=None):
        i = len(self.calls)
        self.calls.append((offset, len(data)))
        self.gates.append(asyncio.Event())
        self.inside += 1
        self.most = max(self.most, self.inside)
        try:
            if not self.free:
                await self.gates[i].wait()
            if i in self.fail:
                raise FopError(errno.EIO)
            if len(self.data) < offset + len(data):
                self.data.extend(bytes(offset + len(data) - len(self.data)))
            self.data[offset:offset + len(data)] = data
            self.landed.append(i)
            return Iatt(size=len(self.data))
        finally:
            self.inside -= 1

    def land(self, *calls: int) -> None:
        for i in calls:
            self.gates[i].set()

    async def readv(self, fd, size, offset, xdata=None):
        self.seen.append("readv")
        return bytes(self.data[offset:offset + size])

    async def fstat(self, fd, xdata=None):
        self.seen.append("fstat")
        return Iatt(size=len(self.data))

    async def flush(self, fd, xdata=None):
        self.seen.append("flush")

    async def fsync(self, fd, datasync=0, xdata=None):
        self.seen.append("fsync")

    async def ftruncate(self, fd, size, xdata=None):
        self.seen.append("ftruncate")
        del self.data[size:]
        return Iatt(size=len(self.data))

    async def release(self, fd):
        self.seen.append("release")


def _layer(child, **options) -> WriteBehindLayer:
    return WriteBehindLayer("wb", {"window-size": W, **options},
                            children=[child])


def _fd(wb, n: int) -> FdObj:
    """An fd as ``create`` through the layer leaves it: with a postbuf,
    so that no write asks the child for one."""
    fd = FdObj(bytes([n]) * 16)
    wb._ctx(fd).last_iatt = Iatt()
    return fd


def _fill(i: int) -> bytes:
    return bytes([65 + i]) * W


async def _settle(n: int = 20) -> None:
    for _ in range(n):
        await asyncio.sleep(0)


def _run(coro):
    return asyncio.run(asyncio.wait_for(coro, 30))


async def _write(wb, fd, i: int, offset: int | None = None):
    """A window-filling write that has to be answered at once."""
    return await asyncio.wait_for(
        wb.writev(fd, _fill(i), i * W if offset is None else offset), 5)


def test_a_write_that_fills_the_window_returns_while_its_drain_is_parked():
    async def run():
        child = Parked()
        wb = _layer(child)
        fd = _fd(wb, 1)
        ia = await _write(wb, fd, 0)
        await _settle()
        assert child.calls == [(0, W)] and child.landed == []
        assert ia.size == W  # the postbuf counts what is in flight
        st = wb.dump_private()
        assert (st["window_bytes"], st["in_flight_bytes"]) == (W, W)
        assert (st["answered_behind"], st["waited_on_window"],
                st["drains_overlapped"]) == (1, 0, 0)
        child.land(0)
        await wb.fsync(fd)
        st = wb.dump_private()
        assert (st["window_bytes"], st["in_flight_bytes"]) == (0, 0)
        assert bytes(child.data) == _fill(0)

    _run(run())


def test_two_drains_side_by_side_and_the_third_write_waits_for_the_first():
    async def run():
        child = Parked()
        wb = _layer(child)
        fd = _fd(wb, 2)
        await _write(wb, fd, 0)
        await _write(wb, fd, 1)  # W in flight does not exceed W
        await _settle()
        assert child.calls == [(0, W), (W, W)] and child.inside == 2
        third = asyncio.create_task(wb.writev(fd, _fill(2), 2 * W))
        await _settle()
        assert not third.done() and len(child.calls) == 2
        assert wb.dump_private()["window_bytes"] == 3 * W
        child.land(1)  # not the oldest: the third still waits
        await _settle()
        assert not third.done() and len(child.calls) == 2
        child.land(0)
        await asyncio.wait_for(third, 5)
        await _settle()
        assert child.calls[2] == (2 * W, W) and child.landed == [1, 0]
        st = wb.dump_private()
        assert (st["answered_behind"], st["waited_on_window"],
                st["drains_overlapped"]) == (3, 1, 1)
        assert st["phases"]["wb.wait"]["count"] == 1
        child.land(2)
        await wb.flush(fd)
        assert bytes(child.data) == _fill(0) + _fill(1) + _fill(2)
        assert child.most == 2

    _run(run())


def test_an_overwrite_reaches_the_child_after_what_it_overwrites():
    async def run():
        child = Parked()
        wb = _layer(child)
        fd = _fd(wb, 3)
        await _write(wb, fd, 0)
        await _write(wb, fd, 1, offset=W // 2)  # touches [0, W) in flight
        await _settle()
        assert child.calls == [(0, W)]  # the overwrite is held back
        assert wb.dump_private()["in_flight_bytes"] == 2 * W
        child.land(0)
        await _settle()
        assert child.calls == [(0, W), (W // 2, W)]
        child.land(1)
        await wb.fsync(fd)
        assert bytes(child.data) == _fill(0)[:W // 2] + _fill(1)

    _run(run())


@pytest.mark.parametrize(
    "fop", ["fsync", "flush", "readv", "fstat", "ftruncate", "release"])
def test_a_full_drain_site_waits_for_everything_in_flight(fop):
    """Each returns only after both drains in flight and the window's
    own tail have landed, and what it does below sees their bytes."""
    async def run():
        child = Parked()
        wb = _layer(child)
        fd = _fd(wb, 4)
        await _write(wb, fd, 0)
        await _write(wb, fd, 1)
        await wb.writev(fd, b"tail", 2 * W)  # below the window: absorbed
        call = {"fsync": lambda: wb.fsync(fd), "flush": lambda: wb.flush(fd),
                "readv": lambda: wb.readv(fd, 2 * W + 4, 0),
                "fstat": lambda: wb.fstat(fd),
                "ftruncate": lambda: wb.ftruncate(fd, 2 * W + 2),
                "release": lambda: wb.release(fd)}[fop]
        task = asyncio.create_task(call())
        await _settle()
        assert not task.done() and child.seen == []
        child.land(1)
        await _settle()
        assert not task.done() and child.seen == []
        assert len(child.calls) == 2  # the tail goes after what is in flight
        child.land(0)
        await _settle()
        assert child.calls[2] == (2 * W, 4) and child.seen == []
        child.land(2)
        got = await asyncio.wait_for(task, 5)
        assert child.seen == [fop] and child.landed == [1, 0, 2]
        whole = _fill(0) + _fill(1) + b"tail"
        if fop == "readv":
            assert got == whole
        elif fop == "fstat":
            assert got.size == len(whole)
        elif fop == "ftruncate":
            assert got.size == 2 * W + 2
        else:
            assert bytes(child.data) == whole
        st = wb.dump_private()
        assert (st["window_bytes"], st["in_flight_bytes"]) == (0, 0)

    _run(run())


def test_a_failed_background_drain_is_raised_by_the_next_fop():
    """And it starts no further drain: the one held back behind it is
    dropped, the write parked on the window is refused."""
    async def run():
        child = Parked()
        child.fail = {0}
        wb = _layer(child)
        fd = _fd(wb, 5)
        await _write(wb, fd, 0)
        await _write(wb, fd, 1, offset=0)  # waits behind the first
        third = asyncio.create_task(wb.writev(fd, _fill(2), 2 * W))
        await _settle()
        assert child.calls == [(0, W)] and not third.done()
        child.land(0)
        with pytest.raises(FopError) as e:
            await asyncio.wait_for(third, 5)
        assert e.value.err == errno.EIO
        await _settle()
        assert child.calls == [(0, W)] and child.landed == []
        assert wb.dump_private()["in_flight_bytes"] == 0
        # raised once; the fd then works again
        child.free = True
        await wb.fsync(fd)
        assert child.calls[1:] == [(2 * W, W)]

    _run(run())


def test_the_error_of_a_drain_nobody_waited_for_comes_on_the_next_fop():
    async def run():
        child = Parked()
        child.fail = {0}
        wb = _layer(child)
        fd = _fd(wb, 6)
        await _write(wb, fd, 0)
        await _settle()
        child.land(0)
        await _settle()
        with pytest.raises(FopError):
            await wb.writev(fd, b"x", W)
        assert len(child.calls) == 1

    _run(run())


def test_strict_write_ordering_never_has_two_under_way():
    async def run():
        child = Parked()
        wb = _layer(child, **{"strict-write-ordering": "on"})
        fd = _fd(wb, 7)
        await _write(wb, fd, 0)
        second = asyncio.create_task(wb.writev(fd, _fill(1), W))
        await _settle()
        assert not second.done() and child.calls == [(0, W)]
        child.land(0)
        await asyncio.wait_for(second, 5)
        await _settle()
        assert child.calls == [(0, W), (W, W)]
        child.land(1)
        await wb.fsync(fd)
        assert child.most == 1 and wb.dump_private()["drains_overlapped"] == 0

    _run(run())


def test_a_write_below_the_window_followed_by_a_read_drains_as_before():
    async def run():
        child = Parked(free=True)
        wb = _layer(child)
        fd = _fd(wb, 8)
        for i in range(3):
            await wb.writev(fd, b"s" * 1000, i * 1000)
        assert child.calls == [] and wb.window_bytes == 3000
        assert await wb.readv(fd, 3000, 0) == b"s" * 3000
        assert child.calls == [(0, 3000)] and child.seen == ["readv"]
        st = wb.dump_private()
        assert (st["answered_behind"], st["waited_on_window"],
                st["drains_overlapped"], st["in_flight_bytes"],
                st["window_bytes"]) == (0, 0, 0, 0, 0)
        assert st["phases"] == {}

    _run(run())


def test_the_stripe_cut_keeps_its_tail_when_the_drain_is_behind():
    """A pressure drain cuts exactly as an awaited one: whole stripes
    go below, the sub-stripe tail stays absorbed."""
    async def run():
        child = Parked()
        wb = _layer(child, **{"stripe-size": 1024})
        fd = _fd(wb, 9)
        await asyncio.wait_for(wb.writev(fd, b"a" * (W + 300), 0), 5)
        await _settle()
        assert child.calls == [(0, W)]
        st = wb.dump_private()
        assert (st["window_bytes"], st["in_flight_bytes"]) == (W + 300, W)
        child.free = True
        child.land(0)
        await wb.release(fd)
        assert child.calls == [(0, W), (W, 300)]
        assert bytes(child.data) == b"a" * (W + 300)

    _run(run())


def test_random_overlapping_writers_land_as_written():
    """Writes of every size at random offsets with the child landing
    its calls late and out of order: the file is what the writes say,
    and no two calls whose bytes touch were ever below at once."""
    async def run(seed):
        rng = np.random.default_rng(seed)
        child = Parked()
        wb = _layer(child)
        fd = _fd(wb, 10)
        model = bytearray()
        live: dict[int, tuple[int, int]] = {}
        clash = []
        orig = child.writev

        async def watched(fd, data, offset, xdata=None):
            i = len(child.calls)
            for o, n in live.values():
                if offset < o + n and o < offset + len(data):
                    clash.append((offset, len(data), o, n))
            live[i] = (offset, len(data))
            try:
                return await orig(fd, data, offset, xdata)
            finally:
                del live[i]

        child.writev = watched

        async def lander():
            while True:
                await asyncio.sleep(0)
                parked = [i for i, g in enumerate(child.gates)
                          if not g.is_set()]
                if parked and rng.random() < 0.3:
                    child.land(int(rng.choice(parked)))

        bg = asyncio.create_task(lander())
        for step in range(120):
            size = int(rng.choice([100, W // 2, W, W + 700]))
            off = int(rng.integers(0, 6 * W))
            data = bytes([step % 251]) * size
            await asyncio.wait_for(wb.writev(fd, data, off), 20)
            if len(model) < off + size:
                model.extend(bytes(off + size - len(model)))
            model[off:off + size] = data
            if step % 40 == 39:
                got = await asyncio.wait_for(wb.readv(fd, len(model), 0), 20)
                assert got == bytes(model)
        await asyncio.wait_for(wb.fsync(fd), 20)
        bg.cancel()
        assert bytes(child.data) == bytes(model)
        assert clash == []
        assert wb.dump_private()["drains_overlapped"] > 0

    for seed in (33, 34):
        _run(run(seed))


# -- served: a 4+2 volume under the layer ----------------------------------

def test_a_sequential_writer_has_two_writes_of_its_file_in_cluster_ec(
        tmp_path):
    """A 16 MiB sequential 1 MiB writer and its fsync through
    write-behind (1 MB window, the volume's stripe as its cut) over a
    systematic 4+2 volume: ``cluster/ec`` has two ``writev`` of the
    inode under way, and the file reads back byte-exact."""
    k, r = 4, 2
    vol = ec_volfile(tmp_path, k + r, r,
                     brick_layers=[("features/locks", {})],
                     options={"systematic": "on"}) + (
        f"\nvolume wb\n    type performance/write-behind\n"
        f"    option stripe-size {k * 512}\n    subvolumes disp\n"
        f"end-volume\n")
    g = Graph.construct(vol)
    ec, wb = g.by_name["disp"], g.top
    assert isinstance(wb, WriteBehindLayer)
    state = {"inside": 0, "most": 0, "calls": 0}
    orig = ec.writev

    async def counted(fd, data, offset, xdata=None):
        state["calls"] += 1
        state["inside"] += 1
        state["most"] = max(state["most"], state["inside"])
        try:
            return await orig(fd, data, offset, xdata)
        finally:
            state["inside"] -= 1

    ec.writev = counted
    c = SyncClient(g)
    c.mount()
    try:
        mib = 1 << 20
        data = np.random.default_rng(33).integers(
            0, 256, 16 * mib, dtype=np.uint8).tobytes()
        f = c.create("/f")
        for i in range(16):
            f.write(data[i * mib:(i + 1) * mib], i * mib)
        f.fsync()
        st = wb.dump_private()
        assert (st["window_bytes"], st["in_flight_bytes"]) == (0, 0)
        assert st["answered_behind"] == 16 == state["calls"]
        assert st["drains_overlapped"] >= 8 and state["most"] == 2
        assert f.read(16 * mib, 0) == data
        f.close()
        assert c.read_file("/f") == data
    finally:
        c.close()
