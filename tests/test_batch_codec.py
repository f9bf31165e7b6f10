"""Batching codec (stripe-cache analog): concurrent fop codec work must
coalesce into one device batch per tick, with a CPU-ladder cutoff for
small batches (reference ec.c:286 stripe-cache + north-star
"HBM-resident batches" requirement)."""

import asyncio

import numpy as np
import pytest

from glusterfs_tpu.ops import gf256
from glusterfs_tpu.ops.batch import BatchingCodec

K, R = 4, 2
STRIPE = K * 512


def _rand(n, seed):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8)


def test_concurrent_encodes_one_launch():
    codec = BatchingCodec(K, R, "xla", window=0.005, min_batch=0)

    async def run():
        datas = [_rand(STRIPE * (i + 1), i) for i in range(8)]
        outs = await asyncio.gather(
            *(codec.encode_async(d) for d in datas))
        return datas, outs

    datas, outs = asyncio.run(run())
    assert codec.launches == 1, "8 concurrent encodes must share 1 launch"
    assert codec.max_batch == 8
    for d, o in zip(datas, outs):
        assert np.array_equal(o, gf256.ref_encode(d, K, K + R))


def test_concurrent_decodes_group_by_mask():
    codec = BatchingCodec(K, R, "xla", window=0.005, min_batch=0)
    rng_rows = [(0, 1, 2, 3), (1, 3, 4, 5), (0, 1, 2, 3)]
    datas = [_rand(STRIPE * 2, 10 + i) for i in range(3)]
    frag_sets = [gf256.ref_encode(d, K, K + R) for d in datas]

    async def run():
        return await asyncio.gather(*(
            codec.decode_async(fr[np.asarray(rows)], rows)
            for fr, rows in zip(frag_sets, rng_rows)))

    outs = asyncio.run(run())
    # two distinct masks -> exactly two launches
    assert codec.launches == 2
    for d, o in zip(datas, outs):
        assert np.array_equal(o, d)


def test_small_batch_falls_back_to_cpu_ladder():
    codec = BatchingCodec(K, R, "xla", window=0.002,
                          min_batch=1 << 20)  # everything is "small"

    async def run():
        d = _rand(STRIPE, 3)
        return d, await codec.encode_async(d)

    d, out = asyncio.run(run())
    assert codec.launches == 0, "small batch must not hit the device path"
    assert codec.cpu_launches == 1
    assert np.array_equal(out, gf256.ref_encode(d, K, K + R))


def test_sequential_calls_do_not_starve():
    codec = BatchingCodec(K, R, "xla", window=0.001, min_batch=0)

    async def run():
        outs = []
        for i in range(3):  # strictly sequential: each waits its window
            d = _rand(STRIPE, 20 + i)
            outs.append((d, await codec.encode_async(d)))
        return outs

    for d, o in asyncio.run(run()):
        assert np.array_equal(o, gf256.ref_encode(d, K, K + R))


class _SlowDeviceCodec(BatchingCodec):
    """Device launches take a fixed wall time (a slow-device stand-in)."""

    DELAY = 0.25

    def encode(self, data):
        import time as _t

        _t.sleep(self.DELAY)
        return super().encode(data)


def test_flushes_pipeline_do_not_serialize():
    """Batch N+1 must fill and dispatch while batch N is on the device:
    two flushes with a 0.25 s device round trip must finish in well under
    the 0.5 s a serialized (on-loop, blocking) flush design would take,
    and the event loop must keep ticking during a flush (VERDICT r2
    weak #1: every flush was a blocking round trip on the loop)."""
    import time as _t

    codec = _SlowDeviceCodec(K, R, "xla", window=0.001, min_batch=0)
    ticks = 0

    async def ticker():
        nonlocal ticks
        while True:
            await asyncio.sleep(0.01)
            ticks += 1

    async def run():
        d = _rand(STRIPE * 4, 1)
        # warm the jit cache for the bucket shape OFF the clock (the
        # waves below pad to the same 16-stripe bucket)
        await codec.encode_async(d)
        tick_task = asyncio.ensure_future(ticker())
        t0 = _t.perf_counter()
        wave_a = [asyncio.ensure_future(codec.encode_async(d))
                  for _ in range(4)]
        await asyncio.sleep(0.005)  # window expires -> flush A in flight
        wave_b = [asyncio.ensure_future(codec.encode_async(d))
                  for _ in range(4)]
        outs = await asyncio.gather(*wave_a, *wave_b)
        dt = _t.perf_counter() - t0
        tick_task.cancel()
        return outs, dt

    outs, dt = asyncio.run(run())
    assert codec.launches == 3, "warmup + two timed flushes expected"
    assert dt < 2 * _SlowDeviceCodec.DELAY * 0.9, (
        f"flushes serialized: {dt:.3f}s for two overlappable "
        f"{_SlowDeviceCodec.DELAY}s launches")
    assert ticks >= 10, f"event loop starved during flushes ({ticks} ticks)"
    want = gf256.ref_encode(_rand(STRIPE * 4, 1), K, K + R)
    for o in outs:
        assert np.array_equal(o, want)


def test_failed_calibration_is_loud_and_never_reroutes_a_named_backend(
        monkeypatch):
    """A calibration that raises is logged once at ERROR with its
    reason kept in dump_stats.  Under ``auto`` flushes stay on the CPU
    ladder; under an explicitly named device backend they go to the
    device and the fop fails — served nowhere the operator did not ask
    for."""
    from glusterfs_tpu.core import gflog
    from glusterfs_tpu.ops import gf256_xla

    def refuse(*_a, **_kw):  # a compile the chip rejects
        raise RuntimeError("Mosaic says no")

    monkeypatch.setattr(gf256_xla, "encode", refuse)
    codec = BatchingCodec(K, R, "xla", window=0.001, min_batch=1)
    d = _rand(STRIPE * 2, 9)

    async def run():
        assert not await codec.ensure_calibrated()
        with pytest.raises(RuntimeError, match="Mosaic says no"):
            await codec.encode_async(d)
        codec._auto = True  # what backend="auto" would have set
        return await codec.encode_async(d)

    out = asyncio.run(run())
    assert np.array_equal(out, gf256.ref_encode(d, K, K + R))
    st = codec.dump_stats()
    assert st["calibration"] == "failed"
    assert "Mosaic says no" in st["calibration_error"]
    assert st["flushes"] == 2 and st["cpu_launches"] == 1
    logged = [m for m in gflog.recent_messages(1000)
              if "MSGID: 110041" in m and "Mosaic says no" in m]
    assert len(logged) == 1 and logged[0].startswith("ERROR")
    codec.close()


def test_measured_break_even_routing():
    """With calibrated models, each flush goes to the predicted-faster
    path: a high-overhead device model routes small flushes to the CPU
    ladder; a near-zero-overhead device model routes them to the device."""
    codec = BatchingCodec(K, R, "xla", window=0.001, min_batch=1)
    # hand-calibrate: device = 1 s overhead + fast rate; native = fast
    codec._dev.overhead, codec._dev.rate, codec._dev.samples = 1.0, 1e12, 2
    codec._nat.overhead, codec._nat.rate, codec._nat.samples = 0.0, 1e9, 2
    codec._cal_state = "done"

    async def one(d):
        return await codec.encode_async(d)

    d = _rand(STRIPE * 2, 7)
    out = asyncio.run(one(d))
    assert np.array_equal(out, gf256.ref_encode(d, K, K + R))
    assert codec.cpu_launches == 1 and codec.launches == 0, \
        "slow-device model must route to the CPU ladder"
    be = codec.break_even_bytes()
    assert be is not None and be > STRIPE * 2

    # flip: device is effectively free -> device path wins
    codec._dev.overhead, codec._dev.rate = 0.0, 1e12
    codec._nat.rate = 1e6
    out = asyncio.run(one(d))
    assert np.array_equal(out, gf256.ref_encode(d, K, K + R))
    assert codec.launches == 1, "fast-device model must route to the device"


def test_ensure_calibrated_measures_both_paths():
    codec = BatchingCodec(K, R, "xla", window=0.001)

    async def run():
        return await codec.ensure_calibrated()

    assert asyncio.run(run()) is True
    stats = codec.dump_stats()
    assert stats["calibration"] == "done"
    assert stats["device_model"] is not None
    assert stats["native_model"] is not None
    assert stats["device_model"]["rate_MiB_s"] > 0


def test_ec_volume_concurrent_writes_coalesce(tmp_path):
    """N concurrent client writes on an EC volume must be served by fewer
    codec launches than fops (the served-data-path coalescing the north
    star asks for), and every byte must round-trip."""
    from glusterfs_tpu.api.glfs import Client
    from glusterfs_tpu.core.graph import Graph
    from glusterfs_tpu.utils.volspec import ec_volfile

    volspec = ec_volfile(tmp_path, K + R, R, options={
        "cpu-extensions": "xla", "stripe-cache": "on",
        "stripe-cache-window": 2000, "stripe-cache-min-batch": 0})

    datas = {f"/f{i}": bytes(_rand(4 * STRIPE, 40 + i)) for i in range(12)}

    async def run():
        c = Client(Graph.construct(volspec))
        await c.mount()
        ec = c.graph.top
        await asyncio.gather(*(
            c.write_file(p, d) for p, d in datas.items()))
        writes_launches = ec.codec.launches
        reads = await asyncio.gather(*(
            c.read_file(p) for p in datas))
        await c.unmount()
        return writes_launches, ec.codec.launches, reads

    wl, total_l, reads = asyncio.run(run())
    assert wl < 12, f"12 concurrent writes took {wl} launches (no coalescing)"
    for (p, d), got in zip(datas.items(), reads):
        assert got == d, p


def test_small_codec_lazy_build_is_race_free():
    """graft-race GL09 regression (ISSUE 14): _small()'s lazy native
    codec used to be built with an UNLOCKED check-then-assign, and the
    routing path (event loop) races the calibration path (flush-pool
    thread) into it — two racers must converge on ONE codec instance,
    built under the codec lock."""
    import threading

    codec = BatchingCodec(K, R, "xla", min_batch=1 << 20)
    assert codec._cpu is None  # device backend: still lazy

    built = []
    start = threading.Barrier(8)

    def race():
        start.wait()
        built.append(codec._small())

    threads = [threading.Thread(target=race) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert len(built) == 8
    assert all(b is built[0] for b in built), \
        "racing _small() calls built more than one small codec"
    assert built[0] is not codec  # device backend got a CPU sibling
    # CPU-ladder backends alias self at construction (pre-publication):
    # no lazy cross-context write exists at all
    cpu = BatchingCodec(K, R, "native", min_batch=1 << 20)
    assert cpu._cpu is cpu


def test_calibration_schedule_check_is_locked():
    """graft-race GL09 regression (ISSUE 14): the debounce check read
    _cal_state WITHOUT the lock while _calibrate (pool thread) writes
    it under the lock; the locked read must still debounce — exactly
    one timer per idle gap, and a non-idle state schedules nothing."""
    codec = BatchingCodec(K, R, "xla", min_batch=1 << 20)

    async def run():
        codec._maybe_schedule_calibration()
        t1 = codec._cal_timer
        codec._maybe_schedule_calibration()  # debounced: same timer
        t2 = codec._cal_timer
        with codec._lock:
            codec._cal_state = "done"
        t1.cancel()
        codec._cal_timer = None
        codec._maybe_schedule_calibration()  # non-idle: no new timer
        t3 = codec._cal_timer
        return t1, t2, t3

    t1, t2, t3 = asyncio.run(run())
    assert t1 is t2 and t1 is not None
    assert t3 is None


@pytest.mark.parametrize("route", ["device", "cpu", "fails"])
def test_launched_future_resolves_on_every_route(monkeypatch, route):
    """``encode_async(launched=...)``: on the device route the future
    resolves after the jitted call was dispatched and before the answer
    is fetched; on the CPU ladder when the coding call begins; and a
    flush that fails before any launch still resolves it (ISSUE 25:
    cluster/ec marshals a write's data part from then on)."""
    from glusterfs_tpu.ops import _device, gf256_xla

    order: list = []
    codec = BatchingCodec(K, R, "xla", systematic=True,
                          min_batch=(1 << 30) if route == "cpu" else 0)
    if route == "fails":
        def refuse(*_a, **_kw):
            order.append("refused")
            raise RuntimeError("no device")

        monkeypatch.setattr(gf256_xla, "encode", refuse)
    else:
        real_launched = _device.launched

        def launched():
            order.append("launch dispatched")
            real_launched()

        monkeypatch.setattr(_device, "launched", launched)
    d = _rand(STRIPE * 4, 21)

    async def run():
        fut = asyncio.get_running_loop().create_future()
        fut.add_done_callback(lambda _f: order.append("told"))
        job = asyncio.ensure_future(codec.encode_async(d, launched=fut))
        await asyncio.wait_for(fut, 10)
        try:
            return await job
        except RuntimeError as e:
            return e

    out = asyncio.run(run())
    codec.close()
    if route == "fails":
        assert isinstance(out, RuntimeError) and order == ["refused", "told"]
        return
    assert np.array_equal(out, gf256.ref_encode(d, K, K + R,
                                                systematic=True))
    if route == "device":
        assert order == ["launch dispatched", "told"] and codec.launches == 1
    else:
        assert order == ["told"] and codec.cpu_launches == 1


# -- the one lane, under each of its three ops (ISSUE 28) -------------------

from tests.test_batch_fill import recorder  # noqa: E402,F401 (fixture)

LANES = ["encode", "delta", "decode"]
ENTRY = {"encode": "encode", "delta": "encode_delta", "decode": "decode"}
MASK = (1, 3, 4, 5)  # data rows 0 and 2 lost


def _lane_codec(**kw):
    kw.setdefault("min_batch", 0)  # the device route
    return BatchingCodec(K, R, "xla", systematic=True, **kw)


def _lane_fop(codec, lane, seed, stripes=2):
    """One fop of ``lane`` -> (a call that starts it, its right answer)."""
    data = _rand(STRIPE * stripes, seed)
    if lane == "encode":
        return (lambda: codec.encode_async(data),
                gf256.ref_encode(data, K, K + R, systematic=True))
    if lane == "delta":
        return (lambda: codec.encode_delta_async(data),
                gf256.ref_parity(data, K, K + R))
    frags = gf256.ref_encode(data, K, K + R, systematic=True)[list(MASK)]
    return lambda: codec.decode_async(frags, MASK), data


def _phase_counts(codec):
    return {name: row["count"]
            for name, row in codec.dump_stats()["phases"].items()}


@pytest.mark.parametrize("lane", LANES)
def test_lane_past_max_batch_bytes_flushes_before_the_timer(lane):
    """The blow-up guard: with a window nobody would wait for, the fop
    that takes the queue to ``max_batch_bytes`` flushes it on the spot
    and the timer is gone."""
    codec = _lane_codec(window=60.0, max_batch_bytes=2 * 2 * STRIPE)
    fops = [_lane_fop(codec, lane, 30 + i) for i in range(2)]

    async def run():
        outs = await asyncio.wait_for(
            asyncio.gather(*(start() for start, _want in fops)), 20)
        assert codec._lanes[lane].timer is None
        return outs

    outs = asyncio.run(run())
    codec.close()
    assert codec.flushes == 1 and codec.batched_fops == 2
    for (_start, want), out in zip(fops, outs):
        assert np.array_equal(out, want)


@pytest.mark.parametrize("lane", LANES)
def test_lane_batch_queued_at_close_runs_inline(lane):
    """``close()`` with fops still in the window: their flush runs on
    the loop's own thread (the pool is shut) and every waiter gets its
    answer."""
    import threading

    codec = _lane_codec(window=0.05)
    fops = [_lane_fop(codec, lane, 40 + i) for i in range(3)]
    ran_on: list = []
    run_flush = codec._run

    def spy(*a):
        ran_on.append(threading.current_thread())
        return run_flush(*a)

    codec._run = spy

    async def run():
        jobs = [asyncio.ensure_future(start()) for start, _want in fops]
        await asyncio.sleep(0)  # queued, the timer not yet due
        assert sum(map(len, codec._lanes[lane].queues.values())) == 3
        codec.close()
        return await asyncio.wait_for(asyncio.gather(*jobs), 20)

    outs = asyncio.run(run())
    assert ran_on == [threading.main_thread()]
    for (_start, want), out in zip(fops, outs):
        assert np.array_equal(out, want)


@pytest.mark.parametrize("lane", LANES)
def test_lane_failed_launch_fails_its_waiters_and_no_others(lane):
    """A launch that raises: every waiter of that flush gets the error,
    each of their ``codec.queue`` and ``codec.resume`` phases is closed,
    and the next flush of the lane works."""
    codec = _lane_codec(window=0.005)
    fops = [_lane_fop(codec, lane, 50 + i) for i in range(3)]

    def refuse(*_a):
        raise RuntimeError("launch refused")

    async def run():
        setattr(codec, ENTRY[lane], refuse)
        failed = await asyncio.gather(
            *(start() for start, _want in fops[:2]),
            return_exceptions=True)
        delattr(codec, ENTRY[lane])  # the class's own again
        return failed, await fops[2][0]()

    failed, out = asyncio.run(run())
    codec.close()
    assert [str(e) for e in failed] == ["launch refused"] * 2
    assert all(isinstance(e, RuntimeError) for e in failed)
    counts = _phase_counts(codec)
    assert counts["codec.queue"] == counts["codec.resume"] == 3
    assert counts["codec.flush"] == 2 and codec.flushes == 2
    assert np.array_equal(out, fops[2][1])


@pytest.mark.parametrize("lane", LANES)
def test_lane_flush_counts_and_names_itself(lane, recorder):
    """A flush of two fops (2 + 3 stripes, launched in the 16-stripe
    bucket): the dump's counters, and the ``codec.flush`` span's own
    account under the lane's name."""
    from glusterfs_tpu.core import tracing

    codec = _lane_codec(window=0.005)
    fops = [_lane_fop(codec, lane, 60 + i, stripes=2 + i) for i in range(2)]

    async def run():
        tracing.ANNOTATE = recorder
        return await asyncio.gather(*(start() for start, _want in fops))

    outs = asyncio.run(run())
    codec.close()
    for (_start, want), out in zip(fops, outs):
        assert np.array_equal(out, want)
    st = codec.dump_stats()
    assert (st["flushes"], st["batched_fops"], st["max_batch"],
            st["launches"], st["cpu_launches"]) == (1, 2, 2, 1, 0)
    assert (st["stripes"], st["padded_stripes"]) == (5, 16)
    flushes = [m for name, m in recorder.log if name == "gftpu:codec.flush"]
    assert len(flushes) == 1
    assert {key: flushes[0][key] for key in (
        "op", "route", "fops", "bytes", "stripes", "bucket_stripes")} == {
            "op": lane, "route": "device", "fops": 2, "bytes": 5 * STRIPE,
            "stripes": 5, "bucket_stripes": 16}
    counts = _phase_counts(codec)
    assert counts["codec.gather"] == counts["codec.scatter"] == 1


@pytest.mark.parametrize("lane", LANES)
def test_lane_device_flush_calls_the_entry_point_on_the_instance(lane):
    """The seam benchmarks/control.py stands on: an entry point replaced
    ON THE INSTANCE is what a device-route flush calls."""
    codec = _lane_codec(window=0.005)
    start, want = _lane_fop(codec, lane, 70)
    entry = getattr(codec, ENTRY[lane])
    calls: list = []

    def altered(*a):
        calls.append(len(a))
        out = entry(*a).copy()
        out.flat[0] ^= 1
        return out

    setattr(codec, ENTRY[lane], altered)
    out = asyncio.run(start())
    codec.close()
    assert calls == [2 if lane == "decode" else 1]
    assert codec.flushes == 1 and codec.cpu_launches == 0
    want = want.copy()
    want.flat[0] ^= 1
    assert np.array_equal(out, want)
