"""Eager-lock reuse + delayed combined post-op: consecutive writes on
one inode share a single inodelk + pre-op + post-op (ec-common.c:2176
ec_lock_reuse, :2377 delayed xattrop), the post-op commits version+size+
dirty in ONE atomic mixed xattrop, and a client crash between data write
and post-op heals correctly."""

import asyncio
import os

import numpy as np
import pytest

from glusterfs_tpu.api.glfs import SyncClient
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.core.layer import Loc
from glusterfs_tpu.mgmt.shd import crawl_once
from glusterfs_tpu.utils.volspec import ec_volfile

K, R = 4, 2
N = K + R
STRIPE = K * 512

BRICK_LAYERS = [("features/locks", {}), ("features/index", {})]


def _rand(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8)


def _index_entries(base, i):
    d = os.path.join(str(base), f"brick{i}", ".glusterfs_tpu", "indices",
                     "xattrop")
    return sorted(os.listdir(d)) if os.path.isdir(d) else []


@pytest.fixture
def vol(tmp_path):
    g = Graph.construct(ec_volfile(
        tmp_path, N, R, brick_layers=BRICK_LAYERS,
        # long timeout: windows close deterministically via fd close /
        # drain points, never via a racing timer
        options={"eager-lock-timeout": 30}))
    c = SyncClient(g)
    c.mount()
    yield c, g.top, tmp_path
    c.close()


def _ctrl_counts(brick_top):
    """Control-plane fop counts as seen by the brick (EC-issued waves)."""
    return {op: (brick_top.stats[op].count if op in brick_top.stats else 0)
            for op in ("inodelk", "getxattr", "xattrop", "setxattr",
                       "writev")}


def test_sequential_writes_amortize_to_one_wave(vol):
    """20 sequential stripe writes: 1 inodelk pair + 1 metadata fetch +
    1 pre-op + 1 combined post-op for the WHOLE window — ~1.25 waves per
    write, vs 6 with per-fop transactions (VERDICT weak #6)."""
    c, ec, base = vol
    f = c.create("/seq")
    brick0 = ec.children[0]
    before = _ctrl_counts(brick0)
    chunk = _rand(STRIPE, seed=1).tobytes()
    for i in range(20):
        f.write(chunk, i * STRIPE)
    f.close()
    after = _ctrl_counts(brick0)
    d = {op: after[op] - before[op] for op in after}
    assert d["writev"] == 20
    ctrl = d["inodelk"] + d["getxattr"] + d["xattrop"] + d["setxattr"]
    # lock+unlock (2 inodelk) + 1 getxattr + pre-op + combined post-op
    assert ctrl <= 8, f"control waves too high: {d}"
    # the data is committed and consistent
    assert c.read_file("/seq") == chunk * 20
    assert c.stat("/seq").size == 20 * STRIPE
    info = c._run(ec.heal_info(Loc("/seq")))
    assert info["bad"] == [] and not info["dirty"]
    for i in range(N):
        assert _index_entries(base, i) == []


def test_stat_and_read_during_open_window(vol):
    """Deferred size commit must not be observable: stat/read mid-window
    serve from the cached window metadata."""
    c, ec, base = vol
    f = c.create("/win")
    data = _rand(2 * STRIPE, seed=2).tobytes()
    f.write(data, 0)
    # window still open (no close): stat sees the new size, read sees
    # the new bytes
    assert c.stat("/win").size == 2 * STRIPE
    assert c.read_file("/win") == data
    f.write(data, 2 * STRIPE)
    assert c.stat("/win").size == 4 * STRIPE
    f.close()
    assert c.stat("/win").size == 4 * STRIPE


def test_crash_between_write_and_postop_heals(vol):
    """Client dies after fragment writes but before the delayed post-op:
    bricks hold new data + dirty marks + old counters.  The index feeds
    the shd, which reconverges the file (VERDICT next-round #5 done
    criterion)."""
    c, ec, base = vol
    data = _rand(2 * STRIPE, seed=3).tobytes()
    c.write_file("/cr", data)
    newstripe = _rand(STRIPE, seed=4).tobytes()
    f = c.open("/cr")
    f.fsync()  # durability point: commit the baseline post-op (close
    # alone defers it, reference post-op-delay semantics)
    f.write(newstripe, 0)

    async def crash():
        # simulate process death: the window state evaporates without a
        # post-op; the server releases a dead client's locks, which
        # _inodelk_unwind stands in for here
        gfid = (await ec.lookup(Loc("/cr")))[0].gfid
        st = ec._eager.pop(gfid)
        if st.timer is not None:
            st.timer.cancel()
        await ec._inodelk_unwind(Loc("/cr", gfid=gfid), st.locked, st.owner)
        return gfid

    gfid = c._run(crash())
    # dirty stuck on every brick -> pending index holds the gfid
    for i in range(N):
        assert _index_entries(base, i) == [gfid.hex()], f"brick {i}"
    report = c._run(crawl_once(c._client))
    assert [h["path"] for h in report["healed"]] == ["/cr"]
    for i in range(N):
        assert _index_entries(base, i) == []
    # all bricks agree afterwards: any K decode identically
    seen = set()
    for drop in ((4, 5), (0, 1)):
        for i in drop:
            ec.set_child_up(i, False)
        got = c.read_file("/cr")
        assert got[STRIPE:] == data[STRIPE:]
        seen.add(got[:STRIPE])
        for i in drop:
            ec.set_child_up(i, True)
    assert len(seen) == 1, "bricks diverge after crash heal"
    info = c._run(ec.heal_info(Loc("/cr")))
    assert info["bad"] == [] and not info["dirty"]


def test_window_survives_interleaved_read(vol):
    """A read between writes keeps the window open (lock reuse), stays
    correct, and adds no extra lock/pre-op waves."""
    c, ec, base = vol
    f = c.create("/rw")
    brick0 = ec.children[0]
    before = _ctrl_counts(brick0)
    a = _rand(STRIPE, seed=5).tobytes()
    b = _rand(STRIPE, seed=6).tobytes()
    f.write(a, 0)
    assert f.read(STRIPE, 0) == a
    f.write(b, STRIPE)
    assert f.read(2 * STRIPE, 0) == a + b
    f.close()
    after = _ctrl_counts(brick0)
    d = {op: after[op] - before[op] for op in after}
    ctrl = d["inodelk"] + d["getxattr"] + d["xattrop"] + d["setxattr"]
    assert ctrl <= 8, f"interleaved read broke the window: {d}"
    assert c.read_file("/rw") == a + b


def test_concurrent_write_and_truncate_no_inversion(vol):
    """ftruncate inside an open eager window must not deadlock: _Txn
    flushes the window under the local lock before winding its own
    inodelk (the drain needs the local lock the txn holds — waiting on
    the brick lock instead would stall until the lock timeout)."""
    c, ec, base = vol
    data = _rand(4 * STRIPE, seed=9).tobytes()

    async def drive():
        cl = c._client
        f = await cl.create("/ci")
        await f.write(data, 0)          # window open (timeout 30)

        async def trunc():
            await ec.truncate(Loc("/ci"), 2 * STRIPE)

        async def more_writes():
            for i in range(3):
                await ec.writev(f.fd, data[:STRIPE], i * STRIPE)

        await asyncio.wait_for(
            asyncio.gather(trunc(), more_writes()), timeout=10)
        await f.close()

    c._run(drive())
    assert c.stat("/ci").size in (2 * STRIPE, 3 * STRIPE)
    info = c._run(ec.heal_info(Loc("/ci")))
    assert info["bad"] == []


def test_max_hold_caps_continuous_writer(tmp_path):
    """A continuous writer must not hold the cluster lock forever: the
    window force-flushes at eager-lock-max-hold so FIFO brick locks let
    other clients in (contention-yield bound)."""
    g = Graph.construct(ec_volfile(
        tmp_path, N, R, brick_layers=BRICK_LAYERS,
        options={"eager-lock-timeout": 5, "eager-lock-max-hold": 0.2}))
    c = SyncClient(g)
    c.mount()
    try:
        ec = g.top
        chunk = _rand(STRIPE, seed=10).tobytes()

        async def stream():
            cl = c._client
            f = await cl.create("/hold")
            flushes = 0
            loop = asyncio.get_running_loop()
            t0 = loop.time()
            while loop.time() - t0 < 0.8:
                await f.write(chunk, 0)
                if f.fd.gfid not in ec._eager:
                    flushes += 1
                await asyncio.sleep(0.01)
            await f.close()
            return flushes

        flushes = c._run(stream())
        # the window was force-released at least twice in 0.8s despite
        # uninterrupted writes with a 5s idle timeout
        assert flushes >= 2, f"window never yielded ({flushes})"
        info = c._run(ec.heal_info(Loc("/hold")))
        assert info["bad"] == [] and not info["dirty"]
    finally:
        c.close()


def test_degraded_window_keeps_dirty_for_shd(vol):
    """Brick dies mid-window: post-op bumps versions on survivors only,
    dirty stays, index retains the entry until healed."""
    c, ec, base = vol
    f = c.create("/deg")
    a = _rand(STRIPE, seed=7).tobytes()
    f.write(a, 0)
    ec.set_child_up(2, False)
    b = _rand(STRIPE, seed=8).tobytes()
    f.write(b, STRIPE)
    ec.set_child_up(2, True)
    f.close()
    # brick 2 missed a write inside the window -> excluded from post-op
    info = c._run(ec.heal_info(Loc("/deg")))
    assert info["bad"] == [2] and info["dirty"]
    assert _index_entries(base, 0) != []
    report = c._run(crawl_once(c._client))
    assert [h["path"] for h in report["healed"]] == ["/deg"]
    ec.set_child_up(0, False)
    ec.set_child_up(1, False)
    assert c.read_file("/deg") == a + b
    ec.set_child_up(0, True)
    ec.set_child_up(1, True)


# -- the write wave in two parts (ISSUE 25): a systematic write sends its
# data fragments while the codec computes parity; to the transaction the
# two parts are ONE wave ------------------------------------------------


@pytest.fixture
def sysvol(tmp_path):
    g = Graph.construct(ec_volfile(
        tmp_path, N, R, brick_layers=BRICK_LAYERS,
        options={"eager-lock-timeout": 30, "systematic": "on"}))
    c = SyncClient(g)
    c.mount()
    yield c, g.top, tmp_path
    c.close()


def _spy_writev(ec, log, fail=None):
    """Every child's writev as it arrives: ``(child, carried the
    pre-xattrop)`` appended to ``log``; children in ``fail`` (child ->
    errno) refuse theirs."""
    from glusterfs_tpu.core.fops import FopError

    for i, ch in enumerate(ec.children):
        async def writev(fd, data, offset, xdata=None, _i=i,
                         _real=ch.writev):
            log.append((_i, "pre-xattrop" in (xdata or {})))
            if fail and _i in fail:
                raise FopError(fail[_i], f"brick {_i} refuses")
            return await _real(fd, data, offset, xdata)

        ch.writev = writev


def _hold_codec(ec, gate, error=None):
    """The flush says its launch is out, and its answer waits for
    ``gate`` (and then raises ``error``, if given): the parity future
    of a write, held."""
    real = ec.codec.encode_async

    async def held(buf, origin="serve", launched=None):
        if launched is not None:
            launched.set_result(None)
        await gate.wait()
        if error is not None:
            raise error
        return await real(buf, origin=origin)

    ec.codec.encode_async = held


async def _until(cond, what):
    for _ in range(400):
        if cond():
            return
        await asyncio.sleep(0.005)
    raise AssertionError(f"never happened: {what}")


def _any_k_agree(c, ec, path, allowed):
    """Every choice of lost bricks tried reads the same bytes, one of
    ``allowed``."""
    seen = set()
    for drop in ((4, 5), (0, 1), (2, 4), (1, 5)):
        for i in drop:
            ec.set_child_up(i, False)
        seen.add(c.read_file(path))
        for i in drop:
            ec.set_child_up(i, True)
    assert len(seen) == 1, "bricks diverge"
    assert seen.pop() in allowed


def test_first_write_carries_the_preop_on_both_parts(sysvol):
    """A window's first write: every brick sees pre-xattrop dirty+1 on
    its own call, data part and parity part alike, and ``pre_landed``
    waits for the sixth answer; the next write of the window carries
    none."""
    c, ec, base = sysvol
    data = _rand(2 * STRIPE, seed=30).tobytes()
    log: list = []

    async def drive():
        gate = asyncio.Event()
        f = await c._client.create("/pre")
        _spy_writev(ec, log)
        _hold_codec(ec, gate)
        w = asyncio.ensure_future(ec.writev(f.fd, data, 0))
        await _until(lambda: len(log) == K, "the data part")
        await asyncio.sleep(0.02)
        st = ec._eager[f.fd.gfid]
        assert log == [(i, True) for i in range(K)]
        assert not w.done() and st.inflight == 1
        assert not st.pre_landed.is_set(), \
            "pre_landed before the parity bricks answered"
        gate.set()
        await w
        assert sorted(log) == [(i, True) for i in range(N)]
        assert st.pre_landed.is_set() and st.inflight == 0
        assert st.good == set(range(N)) and st.delta == 1
        del log[:]
        await ec.writev(f.fd, data, 2 * STRIPE)
        assert sorted(log) == [(i, False) for i in range(N)]
        assert st.delta == 2
        await f.close()

    c._run(drive())
    assert ec.dump_private()["write_path"]["split"] == 2
    assert c.read_file("/pre") == data * 2
    info = c._run(ec.heal_info(Loc("/pre")))
    assert info["bad"] == [] and not info["dirty"]


@pytest.mark.parametrize("how", ["codec-raises", "cancelled"])
def test_torn_between_the_parts_poisons_the_whole_wave(sysvol, how):
    """The data part is on the bricks and the parity never comes (the
    codec fails, or the write is cancelled between the parts): the
    caller gets EIO (or its cancel), the wave's whole target set leaves
    the good set, nothing is committed, dirty stays, and heal
    reconverges the file."""
    from glusterfs_tpu.core.fops import FopError
    import errno

    c, ec, base = sysvol
    old = _rand(2 * STRIPE, seed=31).tobytes()
    new = _rand(2 * STRIPE, seed=32).tobytes()
    c.write_file("/torn", old)
    log: list = []

    async def drive():
        f = await c._client.open("/torn")
        await f.fsync()  # the baseline's post-op is committed
        gate = asyncio.Event()
        _spy_writev(ec, log)
        real = ec.codec.encode_async
        _hold_codec(ec, gate, RuntimeError("device lost")
                    if how == "codec-raises" else None)
        w = asyncio.ensure_future(ec.writev(f.fd, new, 0))
        await _until(lambda: len(log) == K, "the data part")
        await asyncio.sleep(0.02)
        if how == "codec-raises":
            gate.set()
            with pytest.raises(FopError) as ei:
                await w
            assert ei.value.err == errno.EIO
        else:
            w.cancel()
            with pytest.raises(asyncio.CancelledError):
                await w
        assert sorted(i for i, _ in log) == list(range(K)), \
            "a parity brick was written without its parity"
        st = ec._eager[f.fd.gfid]
        assert st.good == set() and st.delta == 0 and st.inflight == 0
        ec.codec.encode_async = real
        await f.close()

    c._run(drive())
    info = c._run(ec.heal_info(Loc("/torn")))
    assert info["dirty"], "a torn wave released dirty"
    assert all(_index_entries(base, i) for i in range(K))
    report = c._run(crawl_once(c._client))
    assert [h["path"] for h in report["healed"]] == ["/torn"]
    for i in range(N):
        assert _index_entries(base, i) == []
    _any_k_agree(c, ec, "/torn", (old, new))
    info = c._run(ec.heal_info(Loc("/torn")))
    assert info["bad"] == [] and not info["dirty"]


@pytest.mark.parametrize("fail, lost", [
    ({1: 28, 4: 28}, False),          # ENOSPC on a data and a parity brick
    ({1: 122, 2: 122, 4: 122}, True),  # EDQUOT on three: below quorum
], ids=["two-fail", "quorum-lost"])
@pytest.mark.parametrize("systematic", ["on", "off"])
def test_brick_failures_judged_once_over_both_parts(tmp_path, systematic,
                                                    fail, lost):
    """A data brick failing in the first part and a parity brick in
    the second give the good set, the quorum verdict and the errno that
    the one wave (``systematic off``) gives."""
    from glusterfs_tpu.core.fops import FopError

    g = Graph.construct(ec_volfile(
        tmp_path, N, R, brick_layers=BRICK_LAYERS,
        options={"eager-lock-timeout": 30, "systematic": systematic}))
    c = SyncClient(g)
    c.mount()
    ec = g.top
    data = _rand(2 * STRIPE, seed=33).tobytes()
    log: list = []

    async def drive():
        f = await c._client.create("/ef")
        _spy_writev(ec, log, fail)
        gate = asyncio.Event()
        gate.set()  # nothing held: the codec answers in its own time
        _hold_codec(ec, gate)
        try:
            await ec.writev(f.fd, data, 0)
            err = None
        except FopError as e:
            err = e.err
        st = ec._eager[f.fd.gfid]
        out = (err, set(st.good), st.delta, st.pre_landed.is_set())
        await f.close()
        return out

    try:
        err, good, delta, landed = c._run(drive())
    finally:
        c.close()
    assert sorted(i for i, _ in log) == list(range(N))
    assert all(pre for _i, pre in log)
    assert good == set(range(N)) - set(fail)
    if lost:
        assert err == 122 and delta == 0 and not landed
    else:
        assert err is None and delta == 1 and landed
    assert ec.write_path["split"] == (1 if systematic == "on" else 0)
