"""Batching codec: coalesce concurrent fop codec work into one device batch.

The reference amortizes per-write stripe work with a stripe-cache
(reference xlators/cluster/ec/src/ec.c:286 option ``stripe-cache``); the
TPU analog — and the north star's "stripe fragments from concurrent fops
coalesced into HBM-resident batches" — is a batching window:

* concurrent ``encode_async`` / ``encode_delta_async`` / ``decode_async``
  calls within one event-loop tick (plus ``window`` seconds) queue into
  the pending list of their lane (:class:`_Lane`: the three ops differ
  by a few values, and the queue, the blow-up guard, the timer, the
  flush and the pool run are one code for all of them);
* one flush concatenates the queued stripe-aligned payloads and makes ONE
  kernel launch for the whole batch (encode; decodes group by surviving
  mask — one launch per mask, the same ``(k, rows)`` keying as the
  per-mask compiled-program LRU every backend decodes through
  (gf256.DECODE_PROGRAMS), so a flush group always lands on one cached
  program/kernel);
* flushes run OFF the event loop in a small thread pool, so batch N+1
  keeps filling (and can dispatch) while batch N is on the device — fop
  latency never serializes on a device round trip;
* device launches are shape-bucketed: the concatenated batch is padded
  with zero stripes up to the next power-of-two stripe count, so the
  jitted kernel cache sees a bounded set of shapes instead of recompiling
  for every distinct batch size (correct because stripes are independent,
  ec-method.c:393-408, and the codec is linear so zero stripes encode to
  zero fragments that we slice off);
* routing between the device and the CPU ladder is MEASURED, not assumed:
  a background calibration times the device at two bucket sizes (fitting
  ``t = overhead + bytes/rate``) and the native ladder on the same data;
  each flush then goes to whichever path predicts faster for its size.
  Until calibration completes, flushes run on the CPU ladder — a served
  volume is never slower than the native path while the device warms up.
  Production flush timings keep updating the models (EMA), so a drifting
  transfer latency re-routes automatically.  A calibration that fails
  (a kernel the compiler refuses, a lost device) is logged once at
  ERROR with its reason kept for ``dump_stats()``: ``auto`` then stays
  on the CPU ladder, an explicitly requested device backend sends the
  flush to the device anyway so the fop fails instead of being served
  somewhere the operator did not ask for.

* the **mesh tier** (ISSUE 8, ``cluster.mesh-codec``): when the volume
  key is on and the warm-up saw >1 jax device, flushes
  at/above ``stripe-cache-min-batch`` skip the single-device ladder and
  land in ONE pjit'd ``NamedSharding(Mesh(dp, frag))`` launch
  (parallel/mesh_codec) — many concurrent fops' stripes sharded over
  ``dp``, the fragment dimension over ``frag``, so the encode IS the
  scatter.  Decodes past ``MESH_RING_DECODE_BYTES`` ride the
  ring-pipelined ppermute reduce instead of the all-gather plane.
  Systematic volumes joined the tier in ISSUE 12: encodes (and
  parity deltas) take the PARITY-ROWS-ONLY sharded program — the k
  data fragments are host reshapes, the mesh computes just the r
  parity rows — while degraded decodes keep the single-device
  ladder (healthy systematic reads never decode at all).
  Launches are counted per (op, origin) on the
  ``gftpu_mesh_{launches,batch_stripes}_total`` families ("serve" =
  fop traffic, "heal" = shd re-encode) and each opens a ``mesh-codec``
  span joined to the first queued fop's trace.

* every flush is a span tree (core/tracing.py ``phase``): per fop
  ``codec.queue`` (enqueue to the start of the flush in the pool: the
  same-tick timer and the thread hop) and ``codec.resume`` (end of the
  flush to the fop running again on the loop); per flush, on the pool
  thread, ``codec.flush`` under the first fop's span, with
  ``codec.gather`` (concatenate, pad to the bucket), the launch's
  ``codec.h2d`` / ``codec.launch`` / ``codec.d2h`` (ops/_device
  ``device_call``) and ``codec.scatter`` (per-fop copies) inside it;
  a flush of one fop that fills its bucket gathers and scatters
  nothing and has neither span.
  ``dump_stats()["phases"]`` has their sums.

Correctness leans on fragment-stream concatenation: fragment ``f`` of
``concat(stripes_a, stripes_b)`` is ``concat(frag_f(a), frag_f(b))`` —
stripes are independent (ec-method.c:393-408 loops stripes).
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import threading
import time

import numpy as np

from ..core import gflog as _gflog
from ..core import metrics as _metrics
from ..core import tracing as _tracing
from . import gf256
from .codec import Codec

_log = _gflog.get_logger("ec")

_DEVICE_BACKENDS = ("pallas-xor", "xla", "xla-xor", "mesh")

#: live BatchingCodecs, scraped (not owned) by the unified registry —
#: the mesh data-plane families (ISSUE 8): launches prove coalesced
#: traffic really lands on the (dp, frag) mesh, batch_stripes sizes it,
#: and the origin label separates the serving path from shd heal
_LIVE_BATCHERS = _metrics.REGISTRY.register_objects(
    "gftpu_mesh_launches_total", "counter",
    "pjit'd (dp, frag) mesh codec launches by owning codec, op, and "
    "traffic origin (serve = BatchingCodec flushes from fops, heal = "
    "shd re-encode)",
    lambda c: [({"codec": c.name, "op": op, "origin": o}, v)
               for (op, o), v in list(c.mesh_launches.items())])
_metrics.REGISTRY.register_objects(
    "gftpu_mesh_batch_stripes_total", "counter",
    "stripes carried by mesh codec launches (post-bucket-padding) by "
    "owning codec, op, and origin",
    lambda c: [({"codec": c.name, "op": op, "origin": o}, v)
               for (op, o), v in list(c.mesh_stripes.items())],
    live=_LIVE_BATCHERS)

# Shape buckets: power-of-two stripe counts with this floor.  Bounded
# distinct shapes -> bounded jit compiles per (k, n) / (k, mask).
_BUCKET_FLOOR_STRIPES = 16

# Calibration bucket sizes (in stripes): a small and a large point to fit
# t(n) = overhead + n / rate.  The large point also warms the kernel cache
# for the bucket real traffic most often lands in.
_CAL_SMALL = 64
_CAL_LARGE = 2048

_EMA = 0.3  # weight of a new production sample in the online models


def _bucket_stripes(s: int) -> int:
    b = _BUCKET_FLOOR_STRIPES
    while b < s:
        b <<= 1
    return b


class _PathModel:
    """Online ``t(bytes) = overhead + bytes / rate`` timing model."""

    def __init__(self) -> None:
        self.overhead = 0.0
        self.rate = 0.0  # bytes/s; 0 -> uncalibrated
        self.samples = 0

    @property
    def ready(self) -> bool:
        return self.rate > 0.0

    def fit_two_points(self, n1: int, t1: float, n2: int, t2: float) -> None:
        """Exact fit from calibration at two sizes (n2 > n1)."""
        slope = max((t2 - t1) / max(n2 - n1, 1), 1e-15)
        self.rate = 1.0 / slope
        self.overhead = max(t1 - n1 * slope, 0.0)
        self.samples = 2

    def observe(self, nbytes: int, secs: float) -> None:
        """EMA update from a production flush (overhead held, rate tracked)."""
        if not self.ready:
            return
        span = secs - self.overhead
        if span <= 0:
            # faster than the modeled overhead: overhead was overestimated
            self.overhead = (1 - _EMA) * self.overhead + _EMA * secs * 0.5
            span = max(secs - self.overhead, 1e-9)
        implied = nbytes / span
        self.rate = (1 - _EMA) * self.rate + _EMA * implied
        self.samples += 1

    def predict(self, nbytes: int) -> float:
        return self.overhead + nbytes / self.rate if self.ready else float("inf")


class _Lane:
    """One op of the batcher, as data: what ``encode``, ``delta`` and
    ``decode`` do not share.  ``op`` names the lane on spans and
    counters.  ``entry`` names the codec's entry point that codes a
    flush, looked up on the instance WHEN THE FLUSH RUNS: the
    batcher's own (counted) one on the device route, the small codec's
    on the CPU ladder (benchmarks/control.py replaces ``encode`` and
    ``decode`` on the instance and must be what a flush calls).  A
    fop's share of a flush's answer is its own size over ``shrink``
    along the last axis (fragments of bytes: k; bytes of fragments:
    1).  ``modelled``: its timings feed the router's models (they
    track full-generator work; parity-only deltas would skew them
    low).  ``mesh_systematic``: the mesh tier codes it on a systematic
    volume (it is encode-only there: a degraded decode reconstructs
    the missing rows on the single-device route).

    Fops queue by ``key``, the entry point's arguments after the data:
    ``()``, or ``(rows,)`` for a decode, one queue a surviving mask
    (the keying of the per-mask program LRU, so a flush lands on one
    cached kernel).  One timer serves every queue of the lane and a
    flush empties them all.  Loop-side state only."""

    __slots__ = ("op", "entry", "shrink", "modelled", "mesh_systematic",
                 "queues", "timer")

    def __init__(self, op: str, entry: str, shrink: int,
                 modelled: bool = True, mesh_systematic: bool = True):
        self.op, self.entry, self.shrink = op, entry, shrink
        self.modelled, self.mesh_systematic = modelled, mesh_systematic
        # key -> [(data, fut, origin, the fop's ``launched`` future or
        # None, the fop's open codec.queue phase)]
        self.queues: dict[tuple, list[tuple]] = {}
        self.timer: asyncio.Task | None = None


class BatchingCodec(Codec):
    """Codec with an async batching window for the served data path.

    The sync ``encode``/``decode`` API stays available (heal tooling,
    tests); the data path awaits ``encode_async``/``decode_async``.

    Stats: ``flushes`` counts coalesced batches handed to a path,
    ``launches`` counts device batch launches (sync calls included),
    ``cpu_launches`` counts flushes routed to the CPU ladder,
    ``batched_fops`` total fops served, ``max_batch`` the largest
    coalesced batch in fops, ``stripes`` the stripes coded for fops and
    ``padded_stripes`` the stripes launched for them (on the device and
    mesh routes the power-of-two bucket, so the zero padding with it).

    ``min_batch`` is a hard floor below which flushes never go to the
    device; ``min_batch=0`` disables routing entirely (every flush takes
    the device path — tests and kernel benches use this to pin the path).
    Between the floor and the measured break-even, the calibrated models
    decide per flush.
    """

    def __init__(self, k: int, r: int, backend: str = "auto", *,
                 window: float = 0.0, min_batch: int = 256 * 1024,
                 max_batch_bytes: int = 256 << 20,
                 systematic: bool = False, mesh: bool = False,
                 name: str = ""):
        super().__init__(k, r, backend, systematic=systematic)
        # instance label on the mesh families: the owning layer's name
        # (a distribute-over-disperse volume has one codec PER group —
        # identical label sets would collide in the exposition)
        self.name = name or f"{k}+{r}"
        self.window = window
        self.min_batch = min_batch
        self.max_batch_bytes = max_batch_bytes
        # parity deltas (ISSUE 10) ride the same flush ladder as full
        # encodes: one parity-rows-only launch per flush
        self._lanes = {
            "encode": _Lane("encode", "encode", k),
            "delta": _Lane("delta", "encode_delta", k, modelled=False),
            "decode": _Lane("decode", "decode", 1, mesh_systematic=False),
        }
        # lazy small-batch codec; CPU-ladder backends alias self HERE
        # (pre-publication, against self.backend as RESOLVED by the
        # base init) so _small()'s lazy build is the only
        # cross-context write left — and that one is lock-serialized
        self._cpu = None if self.backend in _DEVICE_BACKENDS else self
        self.flushes = 0
        self.launches = 0
        self.cpu_launches = 0
        self.batched_fops = 0
        self.max_batch = 0
        # over every flush: the stripes coded for fops, and the stripes
        # launched (the zero padding up to the bucket included)
        self.stripes = 0
        self.padded_stripes = 0
        # two workers: batch N's device round trip overlaps batch N+1's
        # dispatch/host work (jax serializes on-device execution itself)
        # sink one of this codec's phases (core/tracing.py): rows per
        # (phase, thread), each written by its own thread alone.  The
        # pool's threads work for this codec only, so the device
        # entries' ownerless phases (ops/_device.py) land here too
        self.phases: dict = {}
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=2, thread_name_prefix=f"ec-codec-{k}+{r}",
            initializer=_tracing.adopt, initargs=(self.name, self.phases))
        self._lock = threading.Lock()
        self._dev = _PathModel()
        self._nat = _PathModel()
        self._cal_state = "idle"  # idle -> running -> done/failed
        self._cal_error = ""      # why, when failed
        # mesh data plane (ISSUE 8, cluster.mesh-codec): when the key is
        # on AND >1 device is visible, flushes at/above min_batch land
        # in ONE pjit'd NamedSharding(Mesh(dp, frag)) launch.  Counting
        # devices is a backend init (about 10 s on a TPU host), so it
        # warms OFF the event loop; until it answers "ready", flushes
        # take the existing ladder unchanged.  Systematic volumes ride
        # the tier too (ISSUE 12): encodes take the parity-rows-only
        # sharded launch; degraded DECODES keep the single-device
        # ladder (healthy systematic reads never decode at all).
        self.mesh_requested = mesh
        self._mesh = None
        self._mesh_state = "off"  # off -> warming -> ready/unavailable
        self._mesh_error = ""     # why, when the warm-up raised
        self.mesh_launches: dict[tuple[str, str], int] = {}
        self.mesh_stripes: dict[tuple[str, str], int] = {}
        if mesh:
            self._mesh_state = "warming"
            # a dedicated daemon thread, NOT the flush pool: backend
            # init holds its thread for seconds, and with calibration
            # on the other pool worker that would queue production
            # flushes behind it — the stall the ladder promises away
            threading.Thread(target=self._mesh_warm, daemon=True,
                             name=f"gftpu-mesh-warm-{k}+{r}").start()
        _LIVE_BATCHERS.add(self)  # unified-registry scrape target
        # calibration is DEFERRED to an idle gap: the first device
        # encode pays jax imports + kernel compiles that monopolize the
        # GIL for seconds — run that while production flushes are
        # arriving and every in-flight fop (and the event loop's own
        # heartbeats) stalls behind it.  Flushes stamp _last_flush; a
        # debounce task starts calibrating only after _CAL_IDLE_S of
        # quiet.  ensure_calibrated() (benches) still forces it NOW.
        # Seeded with NOW, not 0: a zero seed would make the first
        # flush see an "infinite" idle gap and fire calibration under
        # the cold-start burst.
        self._last_flush = time.monotonic()
        self._cal_timer: asyncio.Task | None = None
        if self.backend in _DEVICE_BACKENDS and _tracing.ANNOTATE is None:
            # a jax backend: this is the process that owns the chip, so
            # whoever starts jax.profiler here finds the program's
            # spans beside the device ops (core/tracing.py, sink three)
            import jax.profiler

            _tracing.ANNOTATE = jax.profiler.TraceAnnotation

    _CAL_IDLE_S = 0.3

    # -- stats hooks (count every device launch, sync path included) ------

    def encode(self, data: np.ndarray) -> np.ndarray:
        with self._lock:
            self.launches += 1
        return super().encode(data)

    def decode(self, frags: np.ndarray, rows) -> np.ndarray:
        with self._lock:
            self.launches += 1
        return super().decode(frags, rows)

    def encode_delta(self, delta: np.ndarray) -> np.ndarray:
        with self._lock:
            self.launches += 1
        return super().encode_delta(delta)

    def _small(self) -> Codec:
        # double-checked under the codec lock: _route (loop) and
        # _calibrate (flush-pool thread) race the first call, and an
        # unserialized lazy build constructs the native codec twice —
        # graft-race GL09 caught the unlocked cross-context write
        if self._cpu is None:
            with self._lock:
                if self._cpu is None:
                    try:
                        self._cpu = Codec(self.k, self.r, "native",
                                          systematic=self.systematic)
                    except RuntimeError as e:
                        _log.warning(43, "%s: small flushes take the "
                                     "NumPy oracle, not native: %s",
                                     self.name, e)
                        self._cpu = Codec(self.k, self.r, "ref",
                                          systematic=self.systematic)
        return self._cpu

    # -- mesh data plane ---------------------------------------------------

    def _mesh_warm(self) -> None:
        """Runs on its own daemon thread (NEVER the flush pool — see
        the spawn site in __init__): count the devices, then build
        (cache) the process mesh.  A single device parks the codec on
        the existing ladder; a warm-up that raises does too, after one
        ERROR line, with the reason kept for ``dump_stats()``."""
        try:
            from ..parallel import mesh_codec

            if mesh_codec.device_count() > 1:
                self._mesh = mesh_codec.default_mesh()
                self._mesh_state = "ready"
                return
        except Exception as e:
            self._mesh_error = f"{type(e).__name__}: {e}"
            _log.error(42, "%s: mesh tier unavailable, flushes stay on "
                       "the %s ladder: %s", self.name, self.backend,
                       self._mesh_error)
        self._mesh_state = "unavailable"

    async def ensure_mesh(self) -> bool:
        """Await the mesh warm probe (tests/benches/dryrun — daemons
        never wait); True when the mesh plane is routable."""
        while self._mesh_state == "warming":
            await asyncio.sleep(0.01)
        return self._mesh_state == "ready"

    def _mesh_launch(self, op: str, cat: np.ndarray, batch, rows=None):
        """ONE pjit'd NamedSharding launch over the (dp, frag) mesh for
        a whole coalesced flush (runs in the pool).  Pads to the stripe
        bucket so the jit cache stays bounded (zero stripes encode to
        zero fragments — sliced back off), records the launch on the
        mesh counters, and opens a ``mesh-codec`` span (under the
        flush's, so in the first queued fop's trace) so slow-fop trees
        show the dispatch."""
        from . import codec as codec_mod
        from ..parallel import mesh_codec

        origins = {o for _d, _f, o, *_ in batch}
        origin = origins.pop() if len(origins) == 1 else "mixed"
        unit = self.fragment_chunk if op == "decode" else self.stripe_size
        s = cat.shape[-1] // unit
        sb = 0
        try:
            with _tracing.phase("mesh-codec", op):
                cat = self._pad_bucket(cat)
                sb = cat.shape[-1] // unit
                if op == "delta":
                    out = mesh_codec.sharded_parity(
                        self.k, self.r, cat, self._mesh)
                elif op == "encode":
                    out = mesh_codec.sharded_encode(
                        self.k, self.r, cat, self._mesh,
                        systematic=self.systematic)
                elif cat.size > codec_mod.MESH_RING_DECODE_BYTES:
                    # the memory-bounded alternative: fragments stay
                    # ring-sharded, an XOR accumulator ppermutes
                    from ..parallel import ring_codec

                    out = ring_codec.ring_decode(
                        self.k, rows, cat, self._mesh)
                else:
                    out = mesh_codec.sharded_decode(
                        self.k, rows, cat, self._mesh)
                if op == "decode":
                    return out[: s * self.stripe_size]
                return out[:, : s * self.fragment_chunk]
        finally:
            with self._lock:
                self.launches += 1
                key = (op, origin)
                self.mesh_launches[key] = \
                    self.mesh_launches.get(key, 0) + 1
                self.mesh_stripes[key] = \
                    self.mesh_stripes.get(key, 0) + sb

    # -- measured break-even routing --------------------------------------

    def _calibrate(self) -> None:
        """Time device + native at two bucket sizes; fit both models.

        Runs in the pool.  Each size gets a warmup launch (pays the jit
        compile, which production flushes to that bucket then reuse) and a
        timed launch.
        """
        try:
            small = self._small()
            pts_dev, pts_nat = [], []
            for stripes in (_CAL_SMALL, _CAL_LARGE):
                data = np.frombuffer(
                    np.random.default_rng(stripes).bytes(
                        stripes * self.stripe_size), dtype=np.uint8)
                super().encode(data)  # warmup: compile + cache
                t0 = time.perf_counter()
                super().encode(data)
                pts_dev.append((data.size, time.perf_counter() - t0))
                t0 = time.perf_counter()
                small.encode(data)
                pts_nat.append((data.size, time.perf_counter() - t0))
            with self._lock:
                self._dev.fit_two_points(*pts_dev[0], *pts_dev[1])
                self._nat.fit_two_points(*pts_nat[0], *pts_nat[1])
                self._cal_state = "done"
        except Exception as e:  # device unusable: say so, once
            with self._lock:
                self._cal_state = "failed"
                self._cal_error = f"{type(e).__name__}: {e}"
            _log.error(41, "%s: %s calibration failed, flushes %s: %s",
                       self.name, self.backend,
                       "stay on the CPU ladder" if self._auto
                       else "go to the device and fail", self._cal_error)

    def _maybe_start_calibration(self) -> None:
        with self._lock:
            if self._cal_state != "idle":
                return
            self._cal_state = "running"
        if self._cal_timer is not None:
            self._cal_timer.cancel()
            self._cal_timer = None
        self._pool.submit(self._calibrate)

    def _maybe_schedule_calibration(self) -> None:
        """Debounced: start calibration after an idle gap, not under load."""
        # _cal_state is written by the pool thread (_calibrate) under
        # the lock; this loop-side read takes it too (graft-race GL09:
        # an unlocked read beside a cross-context writer) — one
        # uncontended acquire on a path that already locks in _route
        with self._lock:
            if self._cal_state != "idle" or self._cal_timer is not None:
                return
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            return

        async def when_idle():
            while True:
                gap = time.monotonic() - self._last_flush
                if gap >= self._CAL_IDLE_S:
                    break
                await asyncio.sleep(self._CAL_IDLE_S - gap)
            self._cal_timer = None
            self._maybe_start_calibration()

        self._cal_timer = loop.create_task(when_idle())

    async def ensure_calibrated(self) -> bool:
        """Run (or await) calibration; True if the device model is ready.

        Benches call this so routing decisions in the measured window are
        model-driven rather than 'calibrating -> CPU'.  Daemons never wait.
        """
        if self._small() is self:
            return False
        self._maybe_start_calibration()
        while True:
            with self._lock:
                st = self._cal_state
            if st in ("done", "failed"):
                return st == "done"
            await asyncio.sleep(0.01)

    def _route(self, total: int) -> tuple[Codec, str]:
        """Pick the path for a flush of ``total`` bytes ->
        ``(codec, kind)`` with kind in {"mesh", "device", "cpu"}.

        The mesh tier outranks the calibrated single-device ladder when
        the volume key armed it AND the warm probe saw >1 device AND
        the flush clears min_batch (min_batch <= 0 pins the path for
        tests) — below that, the pre-mesh ladder is untouched."""
        if self._mesh_state == "ready" and \
                (self.min_batch <= 0 or total >= self.min_batch):
            return self, "mesh"
        small = self._small()
        if small is self:
            return self, "cpu"  # CPU-ladder backend: nothing to route
        if self.min_batch <= 0:
            return self, "device"  # routing disabled: force the device
        if total < self.min_batch:
            return small, "cpu"
        with self._lock:
            st, dev, nat = self._cal_state, self._dev, self._nat
            if st == "failed" and not self._auto:
                # the operator named this device backend: a device that
                # cannot code fails the fop, it is not served elsewhere
                return self, "device"
            if st != "done":
                pass
            elif dev.predict(self._padded(total)) <= nat.predict(total):
                return self, "device"
            else:
                return small, "cpu"
        self._maybe_schedule_calibration()
        return small, "cpu"

    def _padded(self, total: int) -> int:
        return _bucket_stripes(total // self.stripe_size) * self.stripe_size

    def break_even_bytes(self) -> int | None:
        """Bytes past which the device model predicts a win (None if flat)."""
        with self._lock:
            if not (self._dev.ready and self._nat.ready):
                return None
            inv = 1.0 / self._nat.rate - 1.0 / self._dev.rate
            if inv <= 0:
                return None
            # 0 when the device model wins at every size (overhead
            # below native's): never report a negative byte count
            return max(0, int((self._dev.overhead - self._nat.overhead)
                              / inv))

    def _observe(self, device: bool, nbytes: int, secs: float) -> None:
        with self._lock:
            (self._dev if device else self._nat).observe(nbytes, secs)

    # -- bucketed device launches ------------------------------------------

    def _pad_bucket(self, cat: np.ndarray) -> np.ndarray:
        """Zero-pad a flush to its power-of-two stripe bucket, along
        the one axis of stripe-major bytes or the second of ``(k, w)``
        fragments (zero stripes code to zeros, sliced back off)."""
        unit = self.stripe_size if cat.ndim == 1 else self.fragment_chunk
        s = cat.shape[-1] // unit
        sb = _bucket_stripes(s)
        if sb == s:
            return cat
        pad = np.zeros(cat.shape[:-1] + ((sb - s) * unit,), dtype=np.uint8)
        return np.concatenate([cat, pad], axis=-1)

    # -- one lane: queue, guard, timer, flush, pool run --------------------

    async def _enqueue(self, lane: _Lane, item: np.ndarray, key: tuple,
                       origin: str, launched=None) -> np.ndarray:
        """One fop's work joins its lane and waits for its share of the
        flush's answer.  Its ``codec.queue`` phase opens here and ends
        on the pool thread, when the flush starts."""
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        q = lane.queues.setdefault(key, [])
        q.append((item, fut, origin, launched,
                  _tracing.phase(self.name, "codec.queue",
                                 self.phases).start(push=False)))
        if sum(d.size for d, *_ in q) >= self.max_batch_bytes:
            self._flush(lane)  # blow-up guard: do not wait for the timer
        elif lane.timer is None:
            lane.timer = asyncio.ensure_future(self._timer(lane))
        out, resume = await fut
        resume.stop()
        return out

    async def _timer(self, lane: _Lane):
        # window 0 = same-tick coalescing: sleep(0) runs after every
        # already-scheduled callback, so fops made concurrent in this
        # loop pass still land in one batch, while a lone sequential
        # writer pays no idle wait (a fixed window poll costs ~0.3 ms
        # of epoll timeout per flush on the smallfile path)
        await asyncio.sleep(self.window)
        self._flush(lane)

    def _flush(self, lane: _Lane) -> None:
        """Every queue of the lane leaves for the pool, on the loop:
        each is routed and counted as one flush."""
        if lane.timer is not None:
            lane.timer.cancel()
            lane.timer = None
        queues, lane.queues = lane.queues, {}
        loop = asyncio.get_running_loop()
        for key, batch in queues.items():
            self._last_flush = time.monotonic()
            total = sum(d.size for d, *_ in batch)
            codec, kind = self._route(total)
            if kind == "mesh" and self.systematic \
                    and not lane.mesh_systematic:
                codec, kind = self, "device"
            if kind == "cpu" and codec is not self:
                self.cpu_launches += 1
            self.flushes += 1
            self.batched_fops += len(batch)
            self.max_batch = max(self.max_batch, len(batch))
            coded, padded = self._launch_stripes(kind, total)
            self.stripes += coded
            self.padded_stripes += padded
            self._submit(self._run, loop, lane, key, batch, codec, kind,
                         total)

    def _submit(self, fn, loop, *args) -> None:
        """Pool submit with an inline fallback: a batch still pending in
        the window when close() shuts the pool (live reconfigure swaps
        the codec) must NOT strand its awaiting fops — run the flush on
        the loop thread instead."""
        try:
            self._pool.submit(fn, loop, *args)
        except RuntimeError:  # pool shut down after close()
            fn(loop, *args)

    def _launch_stripes(self, kind: str, total: int) -> tuple[int, int]:
        """(stripes coded for fops, stripes launched) of a flush of
        ``total`` bytes: the device and mesh routes pad to the bucket."""
        s = total // self.stripe_size
        return s, s if kind == "cpu" else _bucket_stripes(s)

    def _run(self, loop, lane: _Lane, key: tuple, batch, codec: Codec,
             kind: str, total: int) -> None:
        """One flush, in the pool: gather, code, time, scatter, hand
        back.  The fops that gave a ``launched`` future
        (:meth:`encode_async`) hear once, at the latest when the flush
        ends, however it ends."""
        results = err = None
        waiting = [w for _d, _f, _o, w, _q in batch if w is not None]

        def tell():
            if waiting:
                loop.call_soon_threadsafe(self._tell_launched, waiting[:])
                waiting.clear()

        try:
            # every fop's codec.queue ends; codec.flush opens under the
            # span the first fop waits in, naming the others' spans
            for *_, q in batch:
                q.stop()
            stripes, bucket = self._launch_stripes(kind, total)
            meta = {"op": lane.op, "route": kind, "fops": len(batch),
                    "bytes": total, "stripes": stripes,
                    "bucket_stripes": bucket}
            if key:  # a decode: the rows its launch reads and rebuilds
                meta["rows_in"] = len(key[0])
                meta["rows_out"] = self.rebuilt_rows(key[0])
            others = [str(q.origin[2]) for *_, q in batch[1:] if q.origin]
            if others:
                meta["others"] = ",".join(others)
            with _tracing.phase(self.name, "codec.flush", self.phases,
                                batch[0][-1].origin, cpu=True, **meta):
                t0 = time.perf_counter()
                cat = self._gather(batch, kind)
                if kind == "device" and waiting:
                    from . import _device

                    _device.after_launch(tell)
                else:
                    tell()
                if kind == "mesh":
                    out = self._mesh_launch(lane.op, cat, batch, *key)
                else:
                    # ``codec`` is this one on the device route
                    out = getattr(codec, lane.entry)(cat, *key)
                    if kind == "device":  # the bucket's zero padding
                        out = out[..., : total // lane.shrink]
                    if lane.modelled:
                        # device samples observe the PADDED size — the
                        # launch did that much work, and _route predicts
                        # padded too.  Mesh launches are key-routed, not
                        # model-routed: their timings must not skew the
                        # single-device model.
                        self._observe(kind == "device",
                                      self._padded(total)
                                      if kind == "device" else total,
                                      time.perf_counter() - t0)
                results = self._scatter(batch, out, lane.shrink)
        except Exception as e:
            results, err = None, e
        tell()  # a flush that ended before its launch
        # each fop's codec.resume opens here and ends when the fop
        # runs again on the loop
        resumes = [_tracing.phase(self.name, "codec.resume", self.phases,
                                  q.origin).start(push=False)
                   for *_, q in batch]
        loop.call_soon_threadsafe(self._resolve, batch, results, resumes,
                                  err)

    def _gather(self, batch, kind: str) -> np.ndarray:
        """The batch as one array, padded to its stripe bucket where it
        goes to the device: ``codec.gather``.  One fop that fills its
        bucket is passed on as it is, and has no such span."""
        if len(batch) == 1:
            cat = batch[0][0]
            unit = self.stripe_size if cat.ndim == 1 \
                else self.fragment_chunk
            s = cat.shape[-1] // unit
            if kind != "device" or _bucket_stripes(s) == s:
                return cat
        with _tracing.phase(self.name, "codec.gather", self.phases,
                            cpu=True):
            if len(batch) > 1:
                cat = np.concatenate([d for d, *_ in batch], axis=-1)
            return self._pad_bucket(cat) if kind == "device" else cat

    def _scatter(self, batch, out: np.ndarray, shrink: int) -> list:
        """Each fop's own copy of its part of a flush's answer, along
        the last axis: ``codec.scatter``.  One fop takes the answer as
        it is, with no span."""
        if len(batch) == 1:
            return [out]
        with _tracing.phase(self.name, "codec.scatter", self.phases,
                            cpu=True):
            results, off = [], 0
            for item, *_ in batch:
                n = item.size // shrink
                results.append(out[..., off:off + n].copy())
                off += n
            return results

    @staticmethod
    def _resolve(batch, results, resumes, err) -> None:
        for i, (_d, fut, *_rest) in enumerate(batch):
            if err is None and not fut.done():
                fut.set_result((results[i], resumes[i]))
                continue
            resumes[i].stop(err is not None)  # nobody awaits it
            if not fut.done():
                fut.set_exception(err)

    @staticmethod
    def _tell_launched(waiting) -> None:
        for fut in waiting:
            if not fut.done():
                fut.set_result(None)

    # -- the three callers -------------------------------------------------

    async def encode_async(self, data: np.ndarray, origin: str = "serve",
                           launched: asyncio.Future | None = None
                           ) -> np.ndarray:
        """Encode stripe-aligned bytes; coalesced with concurrent calls.

        ``origin`` labels the traffic source on the mesh counters
        ("serve" = fop data path, "heal" = shd re-encode) and rides the
        queue so a flush can attribute its launch.

        ``launched`` (a future of the caller's loop) is resolved when
        the flush that carries this fop has got past the part of its
        work that holds the interpreter: on the device route when the
        launch is dispatched (ops/_device ``after_launch``), on the
        others when the coding call begins; at the latest when the
        flush ends, however it ends.  A caller with work of its own for
        the loop does it from then on, beside the pool thread's wait,
        instead of fighting it for the interpreter."""
        data = np.ascontiguousarray(data, dtype=np.uint8).ravel()
        if data.size % self.stripe_size:
            raise ValueError("data length not a multiple of the stripe")
        return await self._enqueue(self._lanes["encode"], data, (),
                                   origin, launched)

    async def encode_delta_async(self, delta: np.ndarray,
                                 origin: str = "serve") -> np.ndarray:
        """Parity deltas for a stripe-aligned XOR delta; coalesced with
        concurrent calls exactly like ``encode_async`` (fragment-stream
        concatenation holds for the parity submatrix too — stripes are
        independent).  Deltas ride the measured flush ladder, and on a
        mesh-armed codec a routed flush lands on the same
        parity-rows-only sharded program as the systematic mesh encode
        (``mesh_codec.sharded_parity``, a ``delta`` launch on the mesh
        counters)."""
        delta = np.ascontiguousarray(delta, dtype=np.uint8).ravel()
        if delta.size % self.stripe_size:
            raise ValueError("delta length not a multiple of the stripe")
        return await self._enqueue(self._lanes["delta"], delta, (), origin)

    async def decode_async(self, frags: np.ndarray, rows,
                           origin: str = "serve") -> np.ndarray:
        """Decode k fragments; coalesced with concurrent same-mask calls."""
        rows = tuple(int(x) for x in rows)
        frags = np.ascontiguousarray(frags, dtype=np.uint8)
        return await self._enqueue(self._lanes["decode"], frags, (rows,),
                                   origin)

    def close(self) -> None:
        """Release the flush pool.  The EC layer calls this when a
        reconfigure replaces the codec and at graph fini — without it
        every rebuild leaks the two worker threads.  Queued flushes
        still run (their awaiters must resolve); threads exit after."""
        if self._cal_timer is not None:
            self._cal_timer.cancel()
            self._cal_timer = None
        self._pool.shutdown(wait=False)

    def dump_stats(self) -> dict:
        with self._lock:
            dev_ready = self._dev.ready
            dev = {"overhead_s": round(self._dev.overhead, 6),
                   "rate_MiB_s": round(self._dev.rate / 2**20, 1),
                   "samples": self._dev.samples} if dev_ready else None
            nat = {"overhead_s": round(self._nat.overhead, 6),
                   "rate_MiB_s": round(self._nat.rate / 2**20, 1),
                   "samples": self._nat.samples} if self._nat.ready else None
            cal, cal_err = self._cal_state, self._cal_error
        return {
            "backend": self.backend,
            "flushes": self.flushes,
            "launches": self.launches,
            "cpu_launches": self.cpu_launches,
            "batched_fops": self.batched_fops,
            "max_batch": self.max_batch,
            "stripes": self.stripes,
            "padded_stripes": self.padded_stripes,
            "window_s": self.window,
            "min_batch_bytes": self.min_batch,
            "calibration": cal,
            "calibration_error": cal_err,
            "device_model": dev,
            "native_model": nat,
            "break_even_bytes": self.break_even_bytes(),
            # per phase of a flush: count, seconds, slowest (the summed
            # wait at the codec; core/tracing.py sink one)
            "phases": _tracing.phase_sums(self.phases),
            "mesh": {
                "requested": self.mesh_requested,
                "state": self._mesh_state,
                "error": self._mesh_error,
                "launches": {f"{op}:{o}": v for (op, o), v
                             in self.mesh_launches.items()},
                "stripes": {f"{op}:{o}": v for (op, o), v
                            in self.mesh_stripes.items()},
            },
        }
