"""performance/read-ahead: which pages an fd keeps (ISSUE 27) and how
many fetches a stream has in flight (ISSUE 31).

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
    meanwhile.  With ``slow`` every ``readv`` waits in ``pending``
    until the case answers it (:func:`_drive`)."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.data = bytearray()
        self.log: list[tuple[int, int]] = []
        self.held: set[int] = set()
        self.gate = asyncio.Event()
        self.slow = False
        self.pending: list[asyncio.Future] = []

    async def readv(self, fd, size, offset, xdata=None):
        self.log.append((size, offset))
        data = bytes(self.data[offset:offset + size])
        if self.slow:
            self.pending.append(asyncio.get_running_loop().create_future())
            await self.pending[-1]
        elif offset in self.held:
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
        """Let the fetches in flight land (not held ones)."""
        if not self.stub.held and not self.stub.slow:
            for f in list(self.ctx.fetches):
                await asyncio.wait_for(asyncio.shield(f.task), 5)

    def ahead(self) -> int:
        """Pages held or on their way beyond the read in hand."""
        nxt = -(-self.ctx.next_offset // PSZ)
        pages = set(self.ctx.pages)
        for f in self.ctx.fetches:
            if f.live:
                pages.update(range(f.first, f.first + f.pages))
        return sum(1 for i in pages if i >= nxt)

    async def read(self, size: int, offset: int, settle: bool = True):
        got = bytes(await asyncio.wait_for(
            self.ra.readv(self.fd, size, offset), 5))
        assert got == bytes(self.stub.data[offset:offset + size]), offset
        self.door_bytes += len(got)
        if settle:
            await self.settle()
        held = len(self.ctx.pages) if self.ctx else 0  # released under it
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
    task = rig.ctx.fetches[-1].task
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


async def _drive(rig: Rig, body, each=lambda: None) -> int:
    """Run ``body`` over a child that answers one ``readv`` at a time
    and only when nothing else can move: every fetch stays in flight
    as long as the stream lets it.  ``each`` runs before every answer.
    Returns the most fetches the fd had in flight at once, after
    holding every moment to the bound: never more than one window held
    or on its way beyond the read in hand."""
    rig.stub.slow = True
    task = asyncio.create_task(body)
    most = 0
    while not task.done():
        for _ in range(8):
            await asyncio.sleep(0)
        ctx = rig.ctx
        if ctx is not None:
            most = max(most, len(ctx.fetches))
            assert rig.ahead() <= COUNT, (rig.ahead(), ctx.next_offset)
        each()
        if rig.stub.pending:
            rig.stub.pending.pop(0).set_result(None)
    await task
    rig.stub.slow = False
    return most


def _asked_twice(log, start: int = 0) -> list:
    """The child reads from ``start`` on that ask for bytes an earlier
    one of them asked for."""
    seen: list[tuple[int, int]] = []
    twice = []
    for size, off in log:
        if off < start:
            continue
        if any(off < e and o < off + size for o, e in seen):
            twice.append((size, off))
        seen.append((off, off + size))
    return twice


async def _two_in_flight(rig: Rig, ramp: int):
    """(k), (l) a sequential stream of one window a read over a slow
    child: two fetches in flight (the one the read is parked on and
    the one ahead), never three; past the ramp's ``ramp`` windows no
    range is asked of the child twice and the ranges tile the file."""
    size = len(rig.stub.data)
    most = await _drive(rig, rig.stream(WINDOW, 0, size))
    assert most == 2
    st = rig.ra.dump_private()
    assert st["fetches_overlapped"] >= size // WINDOW - ramp - 2
    assert st["phases"]["ra.wait"]["count"] == st["waited_on_prefetch"] \
        >= size // WINDOW - ramp - 1
    log = rig.stub.log
    assert not _asked_twice(log, ramp * WINDOW)
    tiles = sorted((off, off + sz) for sz, off in log
                   if off >= ramp * WINDOW)
    assert tiles[0][0] == ramp * WINDOW and all(
        a[1] == b[0] for a, b in zip(tiles, tiles[1:]))
    assert tiles[-1][1] >= size
    assert rig.dropped("seek", "stale_fetch", "passed") <= 7  # the ramp


async def _small_reads_slow_child(rig: Rig):
    """(m) a stream of one-page reads over a slow child: pages on
    their way count as ahead, so the child is still asked once per
    half window, and no range twice."""
    size = len(rig.stub.data)
    most = await _drive(rig, rig.stream(PSZ, 0, size))
    assert most <= 2
    assert len(rig.stub.log) <= size // PSZ // (COUNT // 2) + 4
    assert not _asked_twice(rig.stub.log, 4 * WINDOW)


async def _park_with_one_ahead(rig: Rig) -> asyncio.Task:
    """A reader of window 5 parked on its fetch, the fetch of window 6
    in flight beside it, the child answering neither."""
    await rig.stream(WINDOW, 0, 4 * WINDOW)
    rig.stub.slow = True
    await rig.read(WINDOW, 4 * WINDOW)  # served; starts the fetch of 5
    reader = asyncio.create_task(rig.read(WINDOW, 5 * WINDOW,
                                          settle=False))
    for _ in range(8):
        await asyncio.sleep(0)
    assert not reader.done()
    return reader


async def _two_stale(rig: Rig, then: str):
    """(n) a seek, a write or a truncate while two fetches fly: both
    land, both are discarded, no page of theirs is left behind."""
    reader = await _park_with_one_ahead(rig)
    # parked on the fetch of window 5, the fetch of window 6 beside it
    assert [(f.first, f.pages) for f in rig.ctx.fetches] == [
        (5 * COUNT, COUNT), (6 * COUNT, COUNT)]
    tasks = [f.task for f in rig.ctx.fetches]
    before = rig.dropped("stale_fetch")
    if then == "seek":
        other = asyncio.create_task(rig.read(WINDOW, 20 * WINDOW,
                                             settle=False))
    elif then == "write":
        other = asyncio.create_task(
            rig.ra.writev(rig.fd, b"\xee" * WINDOW, 6 * WINDOW))
    else:
        other = asyncio.create_task(
            rig.ra.ftruncate(rig.fd, 6 * WINDOW + 5))
    for _ in range(8):
        await asyncio.sleep(0)
    assert not any(f.live for f in rig.ctx.fetches)
    rig.stub.slow = False
    for fut in rig.stub.pending:
        fut.set_result(None)
    rig.stub.pending.clear()
    await asyncio.gather(reader, other, *tasks)
    assert rig.dropped("stale_fetch") == before + 2 * COUNT
    # the reader that was parked was answered by a demand of its own;
    # nothing the two fetches brought is held
    assert not rig.ctx.fetches
    assert all(i >= 21 * COUNT for i in rig.ctx.pages) if then == "seek" \
        else not rig.ctx.pages
    if then == "write":
        assert await rig.read(WINDOW, 6 * WINDOW) == b"\xee" * WINDOW
    elif then == "truncate":
        assert len(await rig.read(WINDOW, 6 * WINDOW)) == 5


async def _release_cancels(rig: Rig):
    """(o) ``release`` cancels every fetch in flight."""
    reader = await _park_with_one_ahead(rig)
    tasks = [f.task for f in rig.ctx.fetches]
    assert len(tasks) == 2
    ctx = rig.ctx
    await rig.ra.release(rig.fd)
    rig.stub.slow = False
    await asyncio.wait_for(reader, 5)  # answered by a demand of its own
    assert all(t.cancelled() for t in tasks) and not ctx.fetches
    rig.fd.ctx_del(rig.ra)  # the late demand's


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
    "k-two-in-flight-fixed-window": (
        lambda r: _two_in_flight(r, 0), 40 * WINDOW,
        {"adaptive-window": "off"}),
    "l-two-in-flight-after-ramp": (
        lambda r: _two_in_flight(r, 5), 40 * WINDOW, {}),
    "l-two-in-flight-compound": (
        lambda r: _two_in_flight(r, 5), 40 * WINDOW,
        {"compound-fops": "on"}),
    "m-small-reads-slow-child": (_small_reads_slow_child,
                                 24 * WINDOW, {}),
    "n-two-in-flight-across-seek": (
        lambda r: _two_stale(r, "seek"), 40 * WINDOW, {}),
    "n-two-in-flight-across-write": (
        lambda r: _two_stale(r, "write"), 40 * WINDOW, {}),
    "n-two-in-flight-across-truncate": (
        lambda r: _two_stale(r, "truncate"), 40 * WINDOW, {}),
    "o-release-cancels-every-fetch": (_release_cancels, 40 * WINDOW, {}),
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
