"""Unified erasure codec with runtime backend dispatch.

The analog of the reference's ``disperse.cpu-extensions`` option and
``ec_code_detect()`` runtime backend selection (reference
xlators/cluster/ec/src/ec-code.c:59-69, 977-1059): the option values
``{none, auto, x64, sse, avx}`` become

=============  =================================================
backend        implementation
=============  =================================================
``ref``        pure-NumPy bit-sliced oracle (ops/gf256.py)
``native``     C++ AVX2 XOR kernels via ctypes (native/)
``xla``        MXU binary matmul via jitted XLA (ops/gf256_xla.py)
``xla-xor``    VPU XOR chains via jitted XLA
``pallas-xor`` Pallas TPU kernels, the CSE'd XOR program unrolled
               in VMEM over the wire layouts (ops/gf256_pallas.py)
``mesh``       multi-chip: stripes sharded over the device mesh's
               ``dp`` axis, fragments over ``frag`` (parallel/
               mesh_codec shard_map plane); decodes past a memory
               threshold ride the ring-pipelined ppermute reduce
``auto``       mesh on a multi-chip TPU host; pallas-xor on one
               chip; else native, else xla
=============  =================================================

Each name is one object with the same four operations (:class:`_Backend`:
``encode``, ``decode``, ``parity``, ``reconstruct``); :data:`_IMPLS` is
the one table from name to object, :func:`detect` the one place that
picks a name, and :class:`Codec` holds what they resolved to.

Orthogonally to the backend, the ``cluster.mesh-codec`` volume key
(op-version 10) arms a mesh TIER in ops/batch.BatchingCodec: coalesced
stripe-cache flushes at/above ``stripe-cache-min-batch`` take the
(dp, frag) sharded launch regardless of which ladder backend serves
the small/fallback path — see docs/mesh_codec.md.

All backends are byte-exact against ``ref`` (the ``ec-cpu-extensions.t``
oracle, reproduced by tests/test_codec.py).  Decode work is cached per
surviving-fragment mask exactly like the reference's LRU of inverted
matrices (ec-method.c:200-245) — but one level further compiled: the
shared LRU (gf256.DECODE_PROGRAMS) holds the CSE'd straight-line XOR
*program* per mask, which the pallas/xla kernels unroll into their
traces and the native ladder executes directly (gf_decode_prog).
"""

from __future__ import annotations

import functools
import os
import threading
import weakref
from collections import Counter

import numpy as np

from ..core import gflog, metrics
from . import gf256

# mesh decodes larger than this ride the ring-pipelined ppermute path
# (streaming reduce over the frag axis instead of one all-gather whose
# gathered operand must fit each device)
MESH_RING_DECODE_BYTES = 64 << 20


_log = gflog.get_logger("ec")

# -- unified-registry scrape (core/metrics.py): which backends the
# live codecs resolved to, and what the device probe last said --------
_LIVE_CODECS: "weakref.WeakSet" = weakref.WeakSet()

# what tpu_devices() learned, once per process: state is one of
# unprobed / present / absent / error; "error" keeps the exception text
_PROBE_LOCK = threading.Lock()
_probe: dict = {"state": "unprobed", "devices": (), "error": ""}


def _codec_backend_samples():
    counts = Counter(c.backend for c in list(_LIVE_CODECS))
    return [({"backend": b}, n) for b, n in counts.items()]


def probe_state() -> dict:
    """``{"state", "devices", "error"}`` as :func:`tpu_devices` left it
    (never touches jax — safe on the event loop and in a scrape)."""
    return {"state": _probe["state"], "devices": len(_probe["devices"]),
            "error": _probe["error"]}


def _probe_samples():
    return [({"state": s}, 1 if s == _probe["state"] else 0)
            for s in ("unprobed", "present", "absent", "error")]


metrics.REGISTRY.register(
    "gftpu_codec_instances", "gauge",
    "live Codec objects by resolved backend", _codec_backend_samples)
metrics.REGISTRY.register(
    "gftpu_codec_device_probe", "gauge",
    "device-probe state (1 on the active row)", _probe_samples)


def virtual_mesh_env(n_devices: int | None = None,
                     env: dict | None = None) -> dict:
    """A child-process environment pinned to the VIRTUAL CPU mesh:
    CPU platform only (the child can never open the chip its parent
    may own) and — when ``n_devices`` is given — exactly that many
    forced host devices.  The one copy of the rules every subprocess
    spawner shares (``__graft_entry__.dryrun_multichip``)."""
    out = dict(os.environ if env is None else env)
    out["JAX_PLATFORMS"] = "cpu"
    flags = " ".join(
        f for f in out.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    if n_devices is not None:
        flags = (f"{flags} "
                 f"--xla_force_host_platform_device_count={n_devices}")
    out["XLA_FLAGS"] = flags.strip()
    return out


def compile_cache_dir() -> str:
    """Where this process keeps jax's persistent compilation cache:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment places it (jax
    reads that itself; nothing is set in code), else ``.jax_cache``
    beside the package — a path fixed by the checkout, because the
    path is part of what makes a later process find the entries."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def tpu_devices() -> tuple:
    """The TPU devices of this process — THE one place that asks jax
    for the accelerator (``platform == "tpu"``); ``()`` on a host
    without one.  Asked once; every later call answers from
    :data:`_probe`.  Before the first backend init it settles a
    configured ``jax.distributed`` join (parallel/meshd); with a TPU in
    hand, and before anything is jitted, it places the persistent
    compilation cache (:func:`compile_cache_dir`) with the write
    thresholds at zero, so every kernel one process compiled is a cache
    hit for the next.  A CPU-only process is left without one: XLA:CPU
    reloads cached executables with a page of machine-feature errors
    and nothing it compiles here is slow.

    A backend that fails to initialize raises ``RuntimeError`` (here
    and on every later call), after one ERROR log line; the probe state
    is then ``error``.  What ``jax.devices()`` does on the local v5e
    (jax 0.9.0, libtpu 0.0.34; measured through the chip tool, PR 21):

    * chip free: the TPU list, after 9-12 s of runtime start-up;
    * chip held by another process: ``RuntimeError: Unable to
      initialize backend 'tpu': ABORTED: Internal error when accessing
      libtpu multi-process lockfile`` after about 3 s — it never hangs,
      so there is no deadline here.  Only ``JAX_PLATFORMS=''`` turns
      that into a quiet CPU list; no entry point of this repo sets it;
    * ``JAX_PLATFORMS=cpu``: the CPU list in about 3 s; the chip is
      never opened (what :func:`glusterfs_tpu.pin_cpu` relies on)."""
    with _PROBE_LOCK:
        if _probe["state"] == "error":
            raise RuntimeError(_probe["error"])
        if _probe["state"] != "unprobed":
            return _probe["devices"]
        from glusterfs_tpu.parallel import meshd

        meshd.settle_before_backend_init()
        import jax

        try:
            devs = tuple(d for d in jax.devices() if d.platform == "tpu")
        except Exception as e:  # backend init: the chip is held, or broken
            _probe.update(state="error", error=f"{type(e).__name__}: {e}")
            _log.error(40, "accelerator backend failed to initialize "
                       "(JAX_PLATFORMS=%r): %s",
                       os.environ.get("JAX_PLATFORMS"), _probe["error"])
            raise RuntimeError(_probe["error"]) from e
        if devs:
            # nothing has been jitted yet: backend init compiles nothing
            if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
                jax.config.update("jax_compilation_cache_dir",
                                  compile_cache_dir())
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", -1)
        _probe.update(state="present" if devs else "absent", devices=devs)
        return devs


def detect(requested: str = "auto") -> str:
    """Resolve a requested backend name to an available one.

    An unavailable explicit request raises (the reference's
    ec_code_detect logs and falls back; we prefer loud): ``native``
    without a toolchain, and — on a host whose accelerator backend
    failed to initialize — any backend that runs through jax, since it
    could only run somewhere the operator did not ask for.  ``auto``
    walks mesh (multi-chip) -> pallas-xor (one chip) -> native -> xla;
    a failed backend init there is one ERROR line (:func:`tpu_devices`)
    and the CPU ladder.
    """
    if requested != "auto":
        if requested not in BACKENDS:
            raise ValueError(f"unknown backend {requested!r}; one of {BACKENDS}")
        if requested == "native":
            from glusterfs_tpu import native

            if not native.available():
                raise RuntimeError("native backend unavailable (no toolchain?)")
        elif requested != "ref":
            tpu_devices()  # raises when the backend cannot initialize
        return requested
    try:
        devs = tpu_devices()
    except RuntimeError:
        devs = ()
    if devs:
        # multi-chip host: the mesh data plane (stripes over dp,
        # fragments over frag) IS the scale-out path; one chip keeps
        # the single-device pallas kernels
        return "mesh" if len(devs) > 1 else "pallas-xor"
    from glusterfs_tpu import native

    if native.available():
        return "native"
    return "xla"


@functools.cache
def _generator_bits(k: int, n: int, systematic: bool) -> np.ndarray:
    return gf256.expand_bitmatrix(gf256.generator_matrix(k, n, systematic))


def _data_rows(data: np.ndarray, k: int) -> np.ndarray:
    """Data fragments of the systematic code: a pure host reshape of
    the stripe-major bytes (fragment j = chunk j of every stripe)."""
    c = gf256.CHUNK_SIZE
    s = data.size // (k * c)
    return np.ascontiguousarray(
        data.reshape(s, k, c).transpose(1, 0, 2)).reshape(k, s * c)


def _interleave(k: int, pieces) -> np.ndarray:
    """Stripe-major bytes from the k data rows of the systematic code,
    handed over as ``(row, fragment)`` pairs: pure host assembly."""
    pieces = list(pieces)
    c = gf256.CHUNK_SIZE
    s = pieces[0][1].size // c
    out = np.empty((s, k, c), dtype=np.uint8)
    for row, frag in pieces:
        out[:, row, :] = frag.reshape(s, c)
    return out.reshape(-1)


class _Backend:
    """A backend over one ``(k, r)`` geometry: the same four operations
    on each.  ``encode(data, systematic)`` and ``decode(frags, rows,
    systematic)`` apply the whole generator of either code;
    ``parity(data)`` gives only the r parity rows of the systematic
    code (a parity delta, and a systematic encode on a device);
    ``reconstruct(frags, rows, missing)`` only its ``missing`` data
    rows.  Arguments arrive checked and contiguous from :class:`Codec`."""

    def __init__(self, k: int, r: int):
        self.k, self.r, self.n = k, r, k + r

    def reconstruct(self, frags: np.ndarray, rows, missing) -> np.ndarray:
        # no kernel of its own: the rows out of the whole decode
        return _data_rows(self.decode(frags, rows, True),
                          self.k)[list(missing)]


class _Ref(_Backend):
    """The NumPy oracle every other backend is held to."""

    def encode(self, data, systematic):
        return gf256.ref_encode(data, self.k, self.n, systematic=systematic)

    def decode(self, frags, rows, systematic):
        return gf256.ref_decode(frags, rows, self.k, systematic=systematic)

    def parity(self, data):
        return gf256.ref_parity(data, self.k, self.n)


class _Native(_Backend):
    """The CPU platform: AVX2 XOR kernels, compiled programs per mask."""

    def encode(self, data, systematic):
        from glusterfs_tpu import native

        return native.encode(data, self.k, self.n,
                             _generator_bits(self.k, self.n, systematic))

    def decode(self, frags, rows, systematic):
        from glusterfs_tpu import native

        return native.decode_program(
            frags, self.k,
            gf256.decode_program(self.k, tuple(rows), systematic))

    def parity(self, data):
        from glusterfs_tpu import native

        # gf_encode walks whatever (rows, k*8) bit-matrix it is handed:
        # the parity submatrix with n-k output fragments
        return native.encode(data, self.k, self.r,
                             gf256.parity_bits_cached(self.k, self.n))


class _Xla(_Backend):
    """Jitted XLA, ``form`` ``matmul`` (the MXU) or ``xor`` (VPU
    chains): the names ``xla`` and ``xla-xor``."""

    def __init__(self, k: int, r: int, form: str):
        super().__init__(k, r)
        self.form = form

    def encode(self, data, systematic):
        from . import gf256_xla

        return gf256_xla.encode(data, self.k, self.n, self.form,
                                systematic=systematic)

    def decode(self, frags, rows, systematic):
        from . import gf256_xla

        return gf256_xla.decode(frags, rows, self.k, self.form,
                                systematic=systematic)

    def parity(self, data):
        from . import gf256_xla

        return gf256_xla.parity(data, self.k, self.n, self.form)


class _Pallas(_Backend):
    """One chip.  On the systematic code the device computes, and the
    link carries, only what the host cannot reshape for itself: parity
    rows on encode, the missing data rows on a degraded decode."""

    def __init__(self, k: int, r: int, interpret: bool = False):
        super().__init__(k, r)
        self.interpret = interpret

    def encode(self, data, systematic):
        from . import gf256_pallas

        if not systematic:
            return gf256_pallas.encode(data, self.k, self.n, self.interpret)
        out = np.empty((self.n, data.size // self.k), dtype=np.uint8)
        out[: self.k] = _data_rows(data, self.k)
        out[self.k:] = self.parity(data)
        return out

    def decode(self, frags, rows, systematic):
        if not systematic:
            from . import gf256_pallas

            return gf256_pallas.decode(frags, rows, self.k, self.interpret)
        missing = [j for j in range(self.k) if j not in rows]
        have = [(row, frags[i]) for i, row in enumerate(rows)
                if row < self.k]
        if missing:
            have += zip(missing, self.reconstruct(frags, rows, missing))
        return _interleave(self.k, have)

    def parity(self, data):
        from . import gf256_pallas

        return gf256_pallas.parity(data, self.k, self.n, self.interpret)

    def reconstruct(self, frags, rows, missing):
        from . import gf256_pallas

        return gf256_pallas.reconstruct(frags, rows, missing, self.k,
                                        self.interpret)


class _Mesh(_Backend):
    """Several chips: stripes over ``dp``, fragments over ``frag``.  A
    systematic encode is the parity-rows-only sharded launch (data rows
    are host reshapes); the systematic mesh is encode-only, so a
    degraded systematic decode rides the single-device XLA matmul —
    there on every host the mesh resolves on, and orders of magnitude
    over the bit-sliced oracle."""

    def encode(self, data, systematic):
        from glusterfs_tpu.parallel import mesh_codec

        return mesh_codec.sharded_encode(self.k, self.r, data,
                                         systematic=systematic)

    def decode(self, frags, rows, systematic):
        if systematic:
            from . import gf256_xla

            return gf256_xla.decode(frags, rows, self.k, "matmul",
                                    systematic=True)
        from glusterfs_tpu.parallel import mesh_codec, ring_codec

        if frags.size > MESH_RING_DECODE_BYTES:
            return ring_codec.ring_decode(self.k, tuple(rows), frags)
        return mesh_codec.sharded_decode(self.k, tuple(rows), frags)

    def parity(self, data):
        from glusterfs_tpu.parallel import mesh_codec

        return mesh_codec.sharded_parity(self.k, self.r, data)


#: THE table from a backend's name to its object, built ``(k, r)``
_IMPLS = {
    "ref": _Ref,
    "native": _Native,
    "xla": functools.partial(_Xla, form="matmul"),
    "xla-xor": functools.partial(_Xla, form="xor"),
    "pallas-xor": _Pallas,
    "mesh": _Mesh,
}
BACKENDS = tuple(_IMPLS)


class Codec:
    """Erasure codec for a (k data + r redundancy) dispersal.

    ``encode`` takes stripe-aligned bytes (length a multiple of
    ``stripe_size = k*512``) and returns ``(n, len/k)`` fragments;
    ``decode`` takes any k fragments + their indices and returns the bytes.
    Padding/RMW of unaligned user I/O belongs to the EC layer above
    (cluster/ec), not the codec — same split as ec-method.c vs
    ec-inode-write.c in the reference.
    """

    def __init__(self, k: int, r: int, backend: str = "auto",
                 systematic: bool = False):
        if k < 1 or r < 0 or k > gf256.MAX_FRAGMENTS:
            raise ValueError(f"bad k={k}, r={r} (k <= {gf256.MAX_FRAGMENTS})")
        self.k = k
        self.r = r
        self.n = k + r
        if self.n > 255:
            raise ValueError("k + r must be <= 255")
        self.fragment_chunk = gf256.CHUNK_SIZE
        self.stripe_size = k * gf256.CHUNK_SIZE
        # whether the operator named the backend (ops/batch: a named
        # device backend that cannot code fails the fop, ``auto`` is
        # served from the CPU ladder)
        self._auto = backend == "auto"
        self.backend = detect(backend)
        self._impl = _IMPLS[self.backend](k, r)
        # systematic generator (gf256.systematic_matrix): data rows are
        # raw stripe chunks — healthy reads need no math, encode ships
        # only parity off-device, degraded reads reconstruct only the
        # missing rows.  Incompatible fragment format with the default
        # (reference-parity) code: fixed per volume at create.
        self.systematic = systematic
        _LIVE_CODECS.add(self)  # unified-registry scrape target

    def encode(self, data: np.ndarray) -> np.ndarray:
        data = np.ascontiguousarray(data, dtype=np.uint8).ravel()
        if data.size % self.stripe_size:
            raise ValueError(
                f"data length {data.size} not a multiple of stripe "
                f"{self.stripe_size}")
        return self._impl.encode(data, self.systematic)

    def decode(self, frags: np.ndarray, rows) -> np.ndarray:
        """Reconstruct from the k fragments ``frags`` with indices ``rows``."""
        rows = [int(x) for x in rows]
        if len(rows) != self.k or len(set(rows)) != self.k:
            raise ValueError(f"need {self.k} distinct fragment indices")
        if any(x < 0 or x >= self.n for x in rows):
            raise ValueError("fragment index out of range")
        frags = np.ascontiguousarray(frags, dtype=np.uint8)
        if self.systematic and max(rows) < self.k:
            # healthy read: every data row survived — pure host assembly
            return _interleave(self.k, zip(rows, frags))
        return self._impl.decode(frags, rows, self.systematic)

    def rebuilt_rows(self, rows) -> int:
        """How many of the k data rows a decode from the fragments
        ``rows`` computes: on the systematic code those that are not
        among them, on the reference's all k (none of its fragments is
        the stripe's own bytes)."""
        if not self.systematic:
            return self.k
        return self.k - sum(1 for r in rows if r < self.k)

    def encode_delta(self, delta: np.ndarray) -> np.ndarray:
        """Parity-fragment deltas ((n-k), len/k) of a stripe-aligned
        XOR delta — the sub-stripe write primitive (parity-delta /
        parity-logging): linearity gives ``frag_i(old ⊕ Δ) =
        frag_i(old) ⊕ frag_i(Δ)``, and on a systematic volume the data
        rows of Δ are the overwritten bytes themselves, so a small
        write ships only the touched data slices plus these parity
        deltas (brick-side ``xorv`` applies them in place).  Only the
        parity submatrix of the generator is applied — no backend
        touches the k identity rows."""
        if not self.systematic:
            raise ValueError("delta encode needs the systematic layout "
                             "(non-systematic fragments are all "
                             "codewords; there is no verbatim data row "
                             "to delta against)")
        delta = np.ascontiguousarray(delta, dtype=np.uint8).ravel()
        if delta.size % self.stripe_size:
            raise ValueError(
                f"delta length {delta.size} not a multiple of stripe "
                f"{self.stripe_size}")
        return self._impl.parity(delta)

    def reassemble(self, bufs, rows, frag_len: int) -> np.ndarray | None:
        """Healthy systematic fast path straight from fragment BUFFERS
        (the zero-staging lane of the read fan-out, ISSUE 3): when every
        data row survived, the answer is a pure interleave — each
        received buffer is written once, directly into its chunk
        positions of the output, with no intermediate ``frags`` staging
        array.  Buffers shorter than ``frag_len`` zero-fill (sparse
        tails, mirroring the EC layer's staging semantics).

        Returns the assembled stripe-major bytes, or None when this
        codec/row-set doesn't qualify (non-systematic, or a data row is
        missing) — the caller then stages and decodes."""
        if not self.systematic or sorted(int(r) for r in rows) != \
                list(range(self.k)):
            return None
        k, c = self.k, self.fragment_chunk
        if frag_len % c:
            raise ValueError(f"frag_len {frag_len} not a multiple of {c}")
        s = frag_len // c
        out = np.empty((s, k, c), dtype=np.uint8)
        for row, buf in zip(rows, bufs):
            a = np.frombuffer(buf, dtype=np.uint8)
            dst = out[:, int(row), :]
            whole = a.size // c
            rem = a.size % c
            if whole:
                dst[:whole] = a[: whole * c].reshape(whole, c)
            if rem:
                dst[whole, :rem] = a[whole * c:]
                dst[whole, rem:] = 0
            dst[whole + (1 if rem else 0):] = 0
        return out.reshape(-1)

    # -- convenience -------------------------------------------------------

    def pad_length(self, nbytes: int) -> int:
        """Bytes after zero-padding up to a whole stripe (reference pads
        the tail stripe with zeros, ec-inode-write.c)."""
        s = self.stripe_size
        return (nbytes + s - 1) // s * s

    def encode_padded(self, data: np.ndarray) -> tuple[np.ndarray, int]:
        """Zero-pad to a stripe multiple and encode; returns (frags, nbytes)."""
        data = np.ascontiguousarray(data, dtype=np.uint8).ravel()
        orig = data.size
        padded = self.pad_length(orig)
        if padded != orig:
            data = np.concatenate(
                [data, np.zeros(padded - orig, dtype=np.uint8)])
        return self.encode(data), orig

    def decode_padded(self, frags: np.ndarray, rows, nbytes: int) -> np.ndarray:
        """Decode and trim zero-padding back to ``nbytes``."""
        return self.decode(frags, rows)[:nbytes]
