"""cluster/ec: reads of one inode run side by side (ISSUE 31).

Under the eager window a ``readv`` registers its stripe range as
*shared* and leaves the local gfid lock before its fan-out and decode,
as a ``writev`` under ``parallel-writes`` does with an exclusive one
(beside ``tests/test_ec_read_mask_parallel.py``): read against read
never conflicts (upstream ``EC_FLAG_LOCK_SHARED``), read against write
conflicts both ways, and whatever settles the window waits for the
reads in flight.  The fan-outs are held and logged by a spy on
``_dispatch``: no brick process, no wire, nothing timed."""

import asyncio
import contextlib
import random

import numpy as np
import pytest

from glusterfs_tpu.api.glfs import SyncClient
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.utils.volspec import ec_volfile

K, R = 4, 2
N = K + R
STRIPE = K * 512
BRICK_LAYERS = [("features/locks", {})]


def _rand(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


class Spy:
    """Every fan-out of ``ec`` by op, in the order it began; the ops
    in ``hold`` wait at the door of their fan-out until ``open`` is
    set; ``delay`` (seeded) widens the others."""

    live: list["Spy"] = []

    def __init__(self, ec, hold=(), delay=None):
        self.ec, self.hold, self.delay = ec, set(hold), delay
        self.open = asyncio.Event()
        self.live.append(self)
        self.began: list[str] = []
        self.active = dict.fromkeys(("readv", "writev"), 0)
        self.most = dict(self.active)
        orig = ec._dispatch

        async def spy(idxs, op, argfn, **meta):
            self.began.append(op)
            if op in self.active:
                self.active[op] += 1
                self.most[op] = max(self.most[op], self.active[op])
            try:
                if op in self.hold:
                    await self.open.wait()
                elif self.delay is not None:
                    await asyncio.sleep(self.delay())
                return await orig(idxs, op, argfn, **meta)
            finally:
                if op in self.active:
                    self.active[op] -= 1

        ec._dispatch = spy


@contextlib.contextmanager
def _volume(base, degraded: bool, **options):
    """A systematic 4+2 volume (the benchmark's layout) with one
    64-stripe file written and settled; ``degraded``: data brick 1 is
    down, so every read decodes (healthy, none does)."""
    g = Graph.construct(ec_volfile(
        base, N, R, brick_layers=BRICK_LAYERS,
        options={"systematic": "on", "eager-lock-timeout": 30,
                 "other-eager-lock-timeout": 30,
                 "eager-lock-max-hold": 60, **options}))
    c = SyncClient(g)
    c.mount()
    try:
        data = _rand(64 * STRIPE, seed=31)
        c.write_file("/f", data)
        f = c.open("/f")
        f.fsync()
        f.close()
        if degraded:
            g.top.up[1] = False
        yield c, g.top, data
    finally:
        c.close()


@pytest.fixture(params=["healthy", "degraded"])
def vol(request, tmp_path):
    with _volume(tmp_path, request.param == "degraded") as v:
        yield v


def _run(c, coro):
    """On the client's loop, and never for ever: a wait that nothing
    ends is the fault these tests look for.  Whatever a failed case
    left held is let go, or the unmount would wait for it."""
    async def guarded():
        try:
            return await asyncio.wait_for(coro, 60)
        finally:
            while Spy.live:
                Spy.live.pop().open.set()

    return c._run(guarded())


async def _after(seconds: float, coro):
    await asyncio.sleep(seconds)
    return await coro


async def _until(cond):
    """What passes through the codec's pool thread takes time, not
    loop passes."""
    for _ in range(2000):
        if cond():
            return
        await asyncio.sleep(0.005)
    raise AssertionError("never came true")


async def _yield(n: int = 20):
    for _ in range(n):
        await asyncio.sleep(0)


@pytest.mark.parametrize("degraded", [False, True],
                         ids=["healthy", "degraded"])
def test_two_reads_of_one_inode_overlap(tmp_path, degraded):
    """The later reads' fan-outs begin before the first has answered,
    and all answer the file's bytes; degraded, their decodes (one mask:
    ``first-k``; a batching window wider than the fan-outs' spread)
    share a flush of the batcher."""
    with _volume(tmp_path, degraded, **{
            "read-policy": "first-k",
            "stripe-cache-window": 20000}) as (c, ec, data):

        async def drive():
            f = await c._client.open("/f")
            await f.read(STRIPE, 0)  # the window is open
            spy = Spy(ec, hold={"readv"})
            reads = [asyncio.create_task(
                f.read(16 * STRIPE, i * 16 * STRIPE)) for i in range(3)]
            await _yield()
            assert spy.active["readv"] == 3 and not any(
                r.done() for r in reads)
            assert len(ec._eager[f.fd.gfid].ranges) == 3
            before = ec.codec.dump_stats()
            spy.open.set()
            got = await asyncio.gather(*reads)
            after = ec.codec.dump_stats()
            await f.close()
            return got, {k: after[k] - before[k]
                         for k in ("batched_fops", "flushes")}

        got, codec = _run(c, drive())
        assert b"".join(bytes(g) for g in got) == data[:48 * STRIPE]
        assert ec.dump_private()["read_fanout"]["overlapped"] == 2
        if degraded:  # each read decoded, and not each in its own flush
            assert codec["batched_fops"] == 3 > codec["flushes"] >= 1
        else:
            assert codec["batched_fops"] == 0


def test_write_waits_for_read_over_its_stripes(vol):
    c, ec, data = vol
    new = _rand(4 * STRIPE, seed=32)

    async def drive():
        f = await c._client.open("/f")
        await f.write(data[:STRIPE], 0)  # the window's pre-op has landed
        spy = Spy(ec, hold={"readv"})
        read = asyncio.create_task(f.read(8 * STRIPE, 8 * STRIPE))
        await _yield()
        over = asyncio.create_task(f.write(new, 10 * STRIPE))
        await _yield()
        assert "writev" not in spy.began  # parked on the read's range
        await f.write(new, 32 * STRIPE)  # a disjoint write does not wait
        waves = spy.began.count("writev")  # one, or a wave's two parts
        assert waves and not over.done()
        spy.open.set()
        got = await read
        await over
        assert spy.began.count("writev") > waves
        again = await f.read(8 * STRIPE, 8 * STRIPE)
        await f.close()
        return bytes(got), bytes(again)

    got, again = _run(c, drive())
    assert got == data[8 * STRIPE:16 * STRIPE]  # whole, from before
    assert again == data[8 * STRIPE:10 * STRIPE] + new \
        + data[14 * STRIPE:16 * STRIPE]


def test_read_waits_for_write_over_its_stripes(vol):
    c, ec, data = vol
    new = _rand(4 * STRIPE, seed=33)

    async def drive():
        f = await c._client.open("/f")
        await f.write(data[:STRIPE], 0)
        spy = Spy(ec, hold={"writev"})
        write = asyncio.create_task(f.write(new, 10 * STRIPE))
        await _until(lambda: spy.active["writev"])  # encoded, and held
        over = asyncio.create_task(f.read(8 * STRIPE, 8 * STRIPE))
        await _yield()
        assert "readv" not in spy.began  # parked on the write's range
        other = await f.read(8 * STRIPE, 32 * STRIPE)  # disjoint: served
        assert not over.done()
        spy.open.set()
        await write
        got = await over
        await f.close()
        return bytes(got), bytes(other)

    got, other = _run(c, drive())
    assert other == data[32 * STRIPE:40 * STRIPE]
    assert got == data[8 * STRIPE:10 * STRIPE] + new \
        + data[14 * STRIPE:16 * STRIPE]  # whole, from after


def test_window_stays_held_under_a_read_in_flight(vol):
    """The window's timer fires while a read is in flight: neither the
    post-op nor the unlock goes out before the read has answered."""
    c, ec, data = vol

    async def drive():
        f = await c._client.open("/f")
        await f.write(data[:STRIPE], 0)  # a post-op is owed
        ec.opts["eager-lock-timeout"] = 0.01
        spy = Spy(ec, hold={"readv"})
        read = asyncio.create_task(f.read(8 * STRIPE, 8 * STRIPE))
        await _yield()
        other = asyncio.create_task(f.read(STRIPE, 40 * STRIPE))
        await asyncio.sleep(0.1)  # ten timeouts
        assert f.fd.gfid in ec._eager
        assert not {"xattrop", "inodelk"} & set(spy.began)
        spy.open.set()
        got = await read
        await other
        for _ in range(100):
            if f.fd.gfid not in ec._eager:
                break
            await asyncio.sleep(0.01)
        assert f.fd.gfid not in ec._eager
        assert "xattrop" in spy.began  # the post-op, with its unlock
        await f.close()
        return bytes(got)

    assert _run(c, drive()) == data[8 * STRIPE:16 * STRIPE]


def test_ftruncate_settles_reads_first(vol):
    c, ec, data = vol

    async def drive():
        f = await c._client.open("/f")
        await f.read(STRIPE, 0)
        spy = Spy(ec, hold={"readv"})
        read = asyncio.create_task(f.read(8 * STRIPE, 56 * STRIPE))
        await _yield()
        cut = asyncio.create_task(f.ftruncate(8 * STRIPE))
        await _yield()
        assert "ftruncate" not in spy.began and not cut.done()
        spy.open.set()
        got = await read
        await cut
        size = (await f.fstat()).size
        await f.close()
        return bytes(got), size

    got, size = _run(c, drive())
    assert got == data[56 * STRIPE:]  # whole, from before the cut
    assert size == 8 * STRIPE


@pytest.mark.parametrize("seed", [31, 3100031])
def test_no_torn_stripe_under_seeded_interleavings(vol, seed):
    """Six jobs read and write stripe ranges of one file side by side,
    every fan-out widened by a seeded delay.  Each write fills its
    stripes with its own tag; ``last`` (stripe -> tag of the last
    acknowledged write) and ``open_`` (stripe -> tags of writes begun
    and not yet acknowledged) are the dict a read is held to: every
    stripe it returns is one tag whole, and that tag is the last
    acknowledged when the read began or a write's that was open while
    the read was."""
    c, ec, data = vol
    rng = random.Random(seed)
    stripes = 64
    last = {s: None for s in range(stripes)}  # None: the fixture's bytes
    open_: dict[int, set] = {s: set() for s in range(stripes)}
    watchers: list[dict[int, set]] = []
    tags = iter(range(1, 256))

    def stripe_tag(got: bytes, s: int, first: int):
        cut = got[(s - first) * STRIPE:(s - first + 1) * STRIPE]
        if cut == data[s * STRIPE:(s + 1) * STRIPE]:
            return None
        assert len(set(cut)) == 1, f"torn stripe {s}"
        return cut[0]

    async def job(f, ops: int):
        for _ in range(ops):
            first = rng.randrange(stripes - 8)
            count = rng.randrange(1, 9)
            span = range(first, first + count)
            if rng.random() < 0.4:
                tag = next(tags)
                for s in span:
                    open_[s].add(tag)
                    for w in watchers:
                        if s in w:
                            w[s].add(tag)
                await f.write(bytes([tag]) * (count * STRIPE),
                              first * STRIPE)
                for s in span:
                    open_[s].discard(tag)
                    last[s] = tag
            else:
                allowed = {s: {last[s]} | open_[s] for s in span}
                watchers.append(allowed)
                got = bytes(await f.read(count * STRIPE, first * STRIPE))
                watchers.remove(allowed)
                assert len(got) == count * STRIPE
                for s in span:
                    assert stripe_tag(got, s, first) in allowed[s], s

    async def drive():
        f = await c._client.open("/f")
        await f.write(data[:STRIPE], 0)
        delay = lambda: rng.choice((0, 0, 0.001, 0.003))  # noqa: E731
        spy = Spy(ec, delay=delay)
        # each child's call too, side by side as over a wire: a wave's
        # fragments land one by one, with other waves' between them
        ec._local_cached = False
        for child in ec.children:
            for op in ("readv", "writev"):
                def slow(*a, _fop=getattr(child, op), **kw):
                    return _after(delay(), _fop(*a, **kw))
                setattr(child, op, slow)
        await asyncio.gather(*(job(f, 12) for _ in range(6)))
        await f.close()
        return spy

    spy = _run(c, drive())
    assert spy.most["readv"] >= 2  # reads did run side by side
    assert spy.most["writev"] >= 1
    final = c.read_file("/f")
    for s in range(stripes):
        assert stripe_tag(final, s, 0) == last[s], s
