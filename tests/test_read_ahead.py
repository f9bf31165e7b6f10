"""performance/read-ahead: which pages an fd keeps (ISSUE 27).

One parametrised test over a stub child that logs every ``readv`` as
``(size, offset)``: no brick, no wire, nothing timed.  The layer is
driven directly, as ``io-cache`` above it drives it."""

import asyncio

import pytest

from glusterfs_tpu.core.layer import FdObj, Layer, register
from glusterfs_tpu.performance.read_ahead import ReadAheadLayer

PSZ = 4096
COUNT = 8
WINDOW = COUNT * PSZ


@register("test/ra-stub")
class StubChild(Layer):
    """A file in memory.  A ``readv`` whose offset is in ``held`` takes
    its bytes at once, as a brick would, and answers only when
    ``gate`` is set: a fetch in flight across whatever the case does
    meanwhile."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.data = bytearray()
        self.log: list[tuple[int, int]] = []
        self.held: set[int] = set()
        self.gate = asyncio.Event()

    async def readv(self, fd, size, offset, xdata=None):
        self.log.append((size, offset))
        data = bytes(self.data[offset:offset + size])
        if offset in self.held:
            await self.gate.wait()
        else:
            await asyncio.sleep(0)
        return data

    async def writev(self, fd, data, offset, xdata=None):
        self.data[offset:offset + len(data)] = bytes(data)
        return len(data)

    async def ftruncate(self, fd, size, xdata=None):
        del self.data[size:]
        self.data.extend(bytes(size - len(self.data)))


class Rig:
    """The layer over the stub, one fd, and the invariant of every
    case: what a read returns is the file's bytes, and the fd never
    holds more than one window plus the pages of the read in hand."""

    def __init__(self, file_bytes: int, **opts):
        self.stub = StubChild("stub")
        self.stub.data = bytearray(
            (i // PSZ * 31 + i) % 251 for i in range(file_bytes))
        self.ra = ReadAheadLayer(
            "ra", {"page-size": str(PSZ), "page-count": str(COUNT),
                   **opts}, [self.stub])
        self.fd = FdObj(b"g" * 16)
        self.door_bytes = 0

    @property
    def ctx(self):
        return self.fd.ctx_get(self.ra)

    async def settle(self):
        """Let a fetch in flight land (not a held one)."""
        task = self.ctx.task
        if task is not None and not self.stub.held:
            await asyncio.wait_for(asyncio.shield(task), 5)

    async def read(self, size: int, offset: int, settle: bool = True):
        got = bytes(await asyncio.wait_for(
            self.ra.readv(self.fd, size, offset), 5))
        assert got == bytes(self.stub.data[offset:offset + size]), offset
        self.door_bytes += len(got)
        if settle:
            await self.settle()
        held = len(self.ctx.pages)
        assert held <= COUNT + -(-size // PSZ), (held, size, offset)
        return got

    async def stream(self, size: int, start: int, stop: int):
        for off in range(start, stop, size):
            await self.read(size, off)

    def child_bytes(self, since: int = 0) -> int:
        """What the child's answers since then carried (a request
        beyond the end of the file carries nothing)."""
        end = len(self.stub.data)
        return sum(max(0, min(size, end - off))
                   for size, off in self.stub.log[since:])

    def dropped(self, *causes) -> int:
        d = self.ra.dump_private()["dropped_unread_pages"]
        return sum(d[c] for c in causes)


async def _wrap(rig: Rig):
    """(a) a sequential reader of one window a read wraps to offset 0:
    every later pass costs the child the file and less than a window
    more (the ramp's partial fetches), not the file twice."""
    file_bytes = len(rig.stub.data)
    for passes in range(3):
        mark = len(rig.stub.log)
        await rig.stream(WINDOW, 0, file_bytes)
        assert rig.child_bytes(mark) <= file_bytes + WINDOW, passes
    # the window beyond the last read of a pass is all that a wrap
    # throws away; a pass wastes the ramp (1 + 2 + 4 pages) besides
    assert rig.dropped("seek", "stale_fetch") <= 3
    assert rig.dropped("passed") <= 3 * (COUNT - 1)
    st = rig.ra.dump_private()
    assert st["served_from_pages_bytes"] + st["demand_bytes"] \
        == rig.door_bytes
    assert st["demand_bytes"] <= 3 * 5 * WINDOW
    assert st["waited_on_prefetch"] == 0  # every fetch had landed


async def _seek(rig: Rig, to: int):
    """(b), (c) a seek drops what was fetched for the old place, the
    stream goes on from the new one at the price of a new ramp."""
    await rig.stream(WINDOW, 0, 8 * WINDOW)
    assert rig.ctx.unread  # a window ahead of the stream
    before = rig.dropped("seek")
    await rig.read(WINDOW, to)
    assert rig.dropped("seek") == before + COUNT
    assert rig.ctx.window == 1 and not rig.ctx.pages
    mark = len(rig.stub.log)
    await rig.stream(WINDOW, to + WINDOW, to + 9 * WINDOW)
    # eight reads, the ramp's 1 + 2 + 4 pages, the window now ahead
    assert rig.child_bytes(mark) <= 10 * WINDOW
    assert rig.ctx.window == COUNT  # the ramp is back at the ceiling


async def _write_between(rig: Rig):
    """(d) a write between two reads: the second read has the new
    bytes though its pages had been fetched before the write."""
    await rig.stream(WINDOW, 0, 6 * WINDOW)
    assert 6 * COUNT in rig.ctx.pages
    await rig.ra.writev(rig.fd, b"\xff" * PSZ, 6 * WINDOW + PSZ)
    assert not rig.ctx.pages and rig.dropped("write") == COUNT
    got = await rig.read(WINDOW, 6 * WINDOW)
    assert got[PSZ:2 * PSZ] == b"\xff" * PSZ
    await rig.ra.ftruncate(rig.fd, 7 * WINDOW + 5)
    assert not rig.ctx.pages
    assert len(await rig.read(WINDOW, 7 * WINDOW)) == 5


async def _stale_fetch(rig: Rig, then: str):
    """(e) a fetch in flight when a write or a seek arrives stores
    nothing when it lands, and the read after a write is served the
    new bytes."""
    await rig.stream(WINDOW, 0, 5 * WINDOW)
    rig.stub.held.add(6 * WINDOW)
    await rig.read(WINDOW, 5 * WINDOW)  # starts the fetch of window 6
    task = rig.ctx.task
    await asyncio.sleep(0)  # the child has taken the bytes it will answer
    assert not task.done() and rig.stub.log[-1] == (WINDOW, 6 * WINDOW)
    if then == "write":
        await rig.ra.writev(rig.fd, b"\xee" * WINDOW, 6 * WINDOW)
    else:
        await rig.read(WINDOW, 20 * WINDOW, settle=False)
    rig.stub.held.clear()
    rig.stub.gate.set()
    await asyncio.wait_for(task, 5)
    assert not rig.ctx.pages
    assert rig.dropped("stale_fetch") == COUNT
    if then == "write":
        assert await rig.read(WINDOW, 6 * WINDOW) == b"\xee" * WINDOW
    else:
        await rig.stream(WINDOW, 21 * WINDOW, 24 * WINDOW)


async def _eof(rig: Rig):
    """(f) a file that ends inside a page: the short page is served,
    the read at the end is short, the one beyond it empty."""
    size = len(rig.stub.data)
    assert size % PSZ and size % WINDOW
    await rig.stream(WINDOW, 0, size - size % WINDOW)
    assert len(await rig.read(WINDOW, size - size % WINDOW)) \
        == size % WINDOW
    assert await rig.read(WINDOW, size + WINDOW - size % WINDOW) == b""
    await rig.stream(3 * PSZ, 0, size + 3 * PSZ)


async def _two_readers(rig: Rig):
    """(g) two readers inside the window in flight park on its one
    task: the child sees that range once."""
    half = WINDOW // 2
    await rig.stream(WINDOW, 0, 5 * WINDOW)
    rig.stub.held.add(6 * WINDOW)
    await rig.read(WINDOW, 5 * WINDOW)
    before = rig.dropped("seek", "stale_fetch", "passed")
    readers = [asyncio.create_task(rig.read(half, 6 * WINDOW + i * half,
                                            settle=False))
               for i in range(2)]
    await asyncio.sleep(0.01)
    assert not any(r.done() for r in readers)
    assert rig.ra.dump_private()["waited_on_prefetch"] == 2
    rig.stub.held.clear()
    rig.stub.gate.set()
    await asyncio.gather(*readers)
    assert [off // WINDOW for _, off in rig.stub.log].count(6) == 1
    assert rig.dropped("seek", "stale_fetch", "passed") == before


async def _page_bound(rig: Rig):
    """(h) reads smaller and larger than the window, aligned or not:
    ``Rig.read`` holds every one to a window plus its own pages, and
    a stream of one-page reads asks the child once per half window
    at most, not once per page."""
    size = len(rig.stub.data)
    for step in (PSZ, 3 * PSZ, PSZ + 512, WINDOW, 11 * PSZ):
        mark = len(rig.stub.log)
        await rig.stream(step, 0, size)
        if step <= WINDOW:  # a larger read re-reads what was fetched
            assert rig.child_bytes(mark) <= size + 2 * WINDOW, step
        if step == PSZ:
            assert len(rig.stub.log) - mark <= size // PSZ // 4 + 8
    assert rig.ra.dump_private()["hits"] > 0


CASES = {
    "a-wrap": (_wrap, 40 * WINDOW, {}),
    "b-seek-back-one-window": (lambda r: _seek(r, 7 * WINDOW),
                               40 * WINDOW, {}),
    "c-seek-forward": (lambda r: _seek(r, 20 * WINDOW), 40 * WINDOW, {}),
    "d-write-between-reads": (_write_between, 40 * WINDOW, {}),
    "e-fetch-in-flight-across-write": (
        lambda r: _stale_fetch(r, "write"), 40 * WINDOW, {}),
    "e-fetch-in-flight-across-seek": (
        lambda r: _stale_fetch(r, "seek"), 40 * WINDOW, {}),
    "f-eof-short-page": (_eof, 9 * WINDOW + 3 * PSZ + 100, {}),
    "g-two-readers-one-task": (_two_readers, 40 * WINDOW, {}),
    "h-page-bound": (_page_bound, 24 * WINDOW, {}),
    "i-wrap-compound-chain": (_wrap, 40 * WINDOW,
                              {"compound-fops": "on"}),
    "j-wrap-fixed-window": (_wrap, 40 * WINDOW,
                            {"adaptive-window": "off"}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_retention(case):
    body, file_bytes, opts = CASES[case]

    async def run():
        rig = Rig(file_bytes, **opts)
        await body(rig)
        await rig.ra.release(rig.fd)
        assert rig.ctx is None

    asyncio.run(run())
