"""Sharded erasure-codec data plane over a TPU device mesh.

The reference's scale-out data plane is socket fan-out: a write scatters N
encoded fragments to N bricks, a degraded read gathers any K and decodes
(reference xlators/cluster/ec/src/ec-common.c:816-900 dispatch_all /
dispatch_min).  On a TPU pod the same dataflow is mesh-sharded compute:

* mesh axis ``dp`` — stripe batches (many concurrent fops coalesced), the
  data-parallel axis;
* mesh axis ``frag`` — the fragment dimension: each device computes/holds
  the fragments bound for its bricks, so the encode *is* the scatter (the
  tensor-parallel analog; XLA inserts the collectives that replace the
  reference's per-brick socket writes).

Decode reads fragments sharded over ``frag`` and reduces across them —
an all-gather over ICI replacing ``ec_dispatch_min`` network reads.

Everything is jit + NamedSharding; no data-dependent control flow.
"""

from __future__ import annotations

import functools
import threading as _threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import metrics as _metrics
from ..ops import gf256


def make_mesh(devices=None) -> Mesh:
    """Factor the device list into a (dp, frag) mesh."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    frag = 2 if n % 2 == 0 and n > 1 else 1
    dp = n // frag
    return Mesh(np.asarray(devices).reshape(dp, frag), ("dp", "frag"))


# -- device discovery + the process mesh ---------------------------------
#
# The mesh tier counts ALL jax devices, whatever the platform: the
# virtual CPU mesh is how every test and the dryrun exercise it.  Asking
# takes a backend init (about 10 s on a TPU host), so the serving path
# asks once, off the event loop (BatchingCodec._mesh_warm), and the
# loop-side checks and the registry scrape read the remembered answer.

_count: list = []  # [n] once device_count() has asked


def device_count() -> int:
    """All jax devices; asked once.  Under a ``jax.distributed`` job
    (``cluster.mesh-distributed`` / parallel/meshd.py) that is the
    GLOBAL list across every member process — what the mesh tier sizes
    its (dp, frag) plane over; a configured join is settled first,
    because it must precede the process's first backend init.  Raises
    what ``jax.devices()`` raises."""
    if not _count:
        from . import meshd

        meshd.settle_before_backend_init()
        _count.append(len(jax.devices()))
    return _count[0]


def local_device_count() -> int:
    """Devices bound to THIS process (``jax.local_devices()``) — under
    a distributed mesh, one brick's share of the global plane; equal to
    :func:`device_count` in a single-process runtime."""
    from . import meshd

    meshd.settle_before_backend_init()
    return len(jax.local_devices())


def device_count_cached() -> int:
    """What :func:`device_count` answered, 0 if it was never asked.
    Never touches jax — safe on the event loop."""
    return _count[0] if _count else 0


_process_mesh: list = []  # [Mesh] once built


def default_mesh() -> Mesh:
    """The process-wide (dp, frag) mesh over every visible device.

    Only call after ``device_count()`` answered (jax is then already
    initialized, so this never pays a backend init on the event loop)
    — the BatchingCodec orders its calls exactly that way."""
    if not _process_mesh:
        _process_mesh.append(make_mesh())
    return _process_mesh[0]


def _mesh_device_samples():
    """gftpu_mesh_devices scrape: cached state only — a registry scrape
    must never trigger a jax probe."""
    if _process_mesh:
        dp, frag = _process_mesh[0].devices.shape
        return [({"axis": "total"}, dp * frag), ({"axis": "dp"}, dp),
                ({"axis": "frag"}, frag)]
    return [({"axis": "total"}, device_count_cached())]


_metrics.REGISTRY.register(
    "gftpu_mesh_devices", "gauge",
    "devices in the (dp, frag) codec mesh (total/dp/frag; total only "
    "until the mesh is built)", _mesh_device_samples)

# Serializes the jitted mesh-program CALLS, not just their
# construction: jax.jit is LAZY — the real trace + compile happens at
# the first call (and again per new input shape), so a lock released
# before ``fn(...)`` would still let the BatchingCodec's two flush
# workers race an encode and a decode first-compile (observed once as
# a pybind11 instance-allocation failure under e2e load).  Holding the
# lock across the call costs little: the backend serializes on-device
# execution anyway, and shape bucketing (ops/batch) bounds how often a
# call is a compile at all.
# graft-race GL07 machine-checks this extent now: the jit factories
# are tables.KNOWN_LAZY rows and every lock-spans-the-call site below
# is a declared tables.LAZY_UNDER_LOCK_OK row — shrinking the lock
# back off the call fails lint instead of reintroducing the empty
# critical region.
_BUILD_LOCK = _threading.Lock()


def _unpack_bits(x: jnp.ndarray) -> jnp.ndarray:
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (x[..., None] >> shifts) & jnp.uint8(1)
    return bits.reshape(*x.shape[:-1], x.shape[-1] * 8)


def _pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    w8 = bits.shape[-1]
    b = bits.reshape(*bits.shape[:-1], w8 // 8, 8)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    return (b * weights).sum(axis=-1, dtype=jnp.uint8)


def _apply(abits: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """(R*8, C*8) bitmatrix applied to batched chunks (B, C*8, 64)
    -> (B, R*8, 64); int8 matmul mod 2 (MXU)."""
    bits = _unpack_bits(x).astype(jnp.int8)  # (B, C8, 512)
    y = jnp.einsum("rc,bcw->brw", abits.astype(jnp.int8), bits,
                   preferred_element_type=jnp.int32)
    return _pack_bits((y & 1).astype(jnp.uint8))


@functools.lru_cache(maxsize=32)
def sharded_step_fn(k: int, r: int, mesh: Mesh):
    """One full data-plane step, jitted over the mesh.

    step(batch) with batch (B, k*8, 64) uint8 (B stripes, sharded over dp):
      1. encode -> fragments (n*8, B, 64), sharded over (frag, dp) — the
         scatter-to-bricks layout;
      2. degraded decode: reconstruct from the LAST k fragments (i.e. the
         k data fragments 0..r-1 all lost — worst-case reconstruction);
      3. parity: count mismatched bytes vs the input (must be 0).

    Returns (fragments, mismatches).  The decode forces an all-gather of
    fragment shards across ``frag``; the mismatch reduce crosses ``dp`` —
    both ride ICI like the reference's fan-in rides sockets.
    """
    n = k + r
    abits = jnp.asarray(gf256.expand_bitmatrix(gf256.encode_matrix(k, n)))
    rows = tuple(range(r, r + k))
    bbits = jnp.asarray(gf256.decode_bits_cached(k, rows))

    def step(batch):
        frags = _apply(abits, batch)              # (B, n*8, 64)
        frags = jnp.transpose(frags, (1, 0, 2))   # (n*8, B, 64) frag-major
        surv = frags.reshape(n, 8, *frags.shape[1:])[np.asarray(rows)]
        surv = surv.reshape(k * 8, *frags.shape[1:])
        surv = jnp.transpose(surv, (1, 0, 2))     # (B, k*8, 64)
        out = _apply(bbits, surv)                 # (B, k*8, 64)
        mism = jnp.sum((out != batch).astype(jnp.int32))
        return frags, mism

    in_s = NamedSharding(mesh, P("dp", None, None))
    out_s = (NamedSharding(mesh, P("frag", "dp", None)),
             NamedSharding(mesh, P()))
    return jax.jit(step, in_shardings=in_s, out_shardings=out_s)


def run_step(k: int, r: int, batch: np.ndarray, mesh: Mesh | None = None):
    """Convenience wrapper: shard, run, return (frags, mismatches)."""
    if mesh is None:
        mesh = make_mesh()
    with _BUILD_LOCK:
        fn = sharded_step_fn(k, r, mesh)
        frags, mism = fn(jnp.asarray(batch))
    return frags, int(mism)


@functools.lru_cache(maxsize=32)
def _encode_fn(k: int, n: int, mesh: Mesh):
    """Jitted encode, stripes sharded over ``dp``, fragments laid out
    over ``frag`` — the encode IS the scatter-to-bricks step."""
    abits = jnp.asarray(gf256.expand_bitmatrix(gf256.encode_matrix(k, n)))
    in_s = NamedSharding(mesh, P("dp", None, None))
    out_s = NamedSharding(mesh, P("frag", "dp", None))
    return jax.jit(
        lambda x: jnp.transpose(_apply(abits, x), (1, 0, 2)),
        in_shardings=in_s, out_shardings=out_s)


@functools.lru_cache(maxsize=32)
def _parity_fn(k: int, n: int, mesh: Mesh):
    """Jitted PARITY-ROWS-ONLY encode for the systematic layout
    (ISSUE 12 / ROADMAP item 5): the k data rows of a systematic code
    are verbatim stripe chunks — a host reshape, no math — so the mesh
    computes (and the interconnect carries) only the r parity
    fragments, sharded exactly like the full encode: stripes over
    ``dp``, the (parity) fragment dimension over ``frag``."""
    pbits = jnp.asarray(gf256.parity_bits_cached(k, n))
    in_s = NamedSharding(mesh, P("dp", None, None))
    out_s = NamedSharding(mesh, P("frag", "dp", None))
    return jax.jit(
        lambda x: jnp.transpose(_apply(pbits, x), (1, 0, 2)),
        in_shardings=in_s, out_shardings=out_s)


def _planes_to_wire(y: np.ndarray, rows: int, s: int) -> np.ndarray:
    """Plane-major (rows*8, S, 64) -> wire fragment-major
    (rows, S*512): fragment f's chunk for stripe s' interleaves its 8
    planes."""
    return (y.reshape(rows, 8, s, gf256.WORD_SIZE)
             .transpose(0, 2, 1, 3)
             .reshape(rows, s * gf256.CHUNK_SIZE))


def sharded_encode(k: int, r: int, data: np.ndarray,
                   mesh: Mesh | None = None,
                   systematic: bool = False) -> np.ndarray:
    """Encode stripe-aligned bytes into wire-layout fragments
    ``(n, S*512)`` with stripes sharded over the mesh's ``dp`` axis and
    the fragment dimension over ``frag`` (the served-volume entry point
    the BatchingCodec's ``mesh`` backend feeds).

    ``systematic=True`` is the parity-rows-only lane: the mesh launch
    computes just the r parity fragments and the k data fragments are
    assembled host-side as pure reshapes — fragment-identical to the
    single-device systematic encode (property-pinned in
    tests/test_process_plane.py)."""
    if mesh is None:
        mesh = make_mesh()
    n = k + r
    data = np.ascontiguousarray(data, dtype=np.uint8).ravel()
    s = data.size // (k * gf256.CHUNK_SIZE)
    x = data.reshape(s, k * 8, gf256.WORD_SIZE)
    dp = mesh.devices.shape[0]
    pad = (-s) % dp
    if pad:
        x = np.concatenate(
            [x, np.zeros((pad, *x.shape[1:]), dtype=np.uint8)], axis=0)
    if systematic:
        with _BUILD_LOCK:
            y = np.asarray(_parity_fn(k, n, mesh)(jnp.asarray(x)))
        y = y[:, :s, :]  # (r*8, S, 64) parity planes
        out = np.empty((n, s * gf256.CHUNK_SIZE), dtype=np.uint8)
        # data rows: verbatim stripe chunks (ops/codec ``_data_rows``)
        out[:k] = np.ascontiguousarray(
            data.reshape(s, k, gf256.CHUNK_SIZE)
                .transpose(1, 0, 2)).reshape(k, s * gf256.CHUNK_SIZE)
        out[k:] = _planes_to_wire(y, r, s)
        return out
    with _BUILD_LOCK:
        y = np.asarray(_encode_fn(k, n, mesh)(jnp.asarray(x)))
    # y: (n*8, S', 64)
    y = y[:, :s, :]
    return _planes_to_wire(y, n, s)


def sharded_parity(k: int, r: int, delta: np.ndarray,
                   mesh: Mesh | None = None) -> np.ndarray:
    """Parity-fragment deltas ``(r, S*512)`` of a stripe-aligned XOR
    delta over the mesh — the sub-stripe-write primitive
    (ops/codec.Codec.encode_delta) on the (dp, frag) plane.  Same
    parity-rows-only program as the systematic encode: linearity makes
    the parity of Δ exactly the parity delta."""
    if mesh is None:
        mesh = make_mesh()
    n = k + r
    delta = np.ascontiguousarray(delta, dtype=np.uint8).ravel()
    s = delta.size // (k * gf256.CHUNK_SIZE)
    x = delta.reshape(s, k * 8, gf256.WORD_SIZE)
    dp = mesh.devices.shape[0]
    pad = (-s) % dp
    if pad:
        x = np.concatenate(
            [x, np.zeros((pad, *x.shape[1:]), dtype=np.uint8)], axis=0)
    with _BUILD_LOCK:
        y = np.asarray(_parity_fn(k, n, mesh)(jnp.asarray(x)))
    return _planes_to_wire(y[:, :s, :], r, s)


@functools.lru_cache(maxsize=256)
def _decode_fn(k: int, rows: tuple[int, ...], mesh: Mesh):
    """Jitted degraded decode for one surviving mask, stripes sharded
    over ``dp`` (the LRU of per-mask jitted decoders mirrors the
    reference's LRU of inverted matrices, ec-method.c:200-245)."""
    bbits = jnp.asarray(gf256.decode_bits_cached(k, rows))
    sharding = NamedSharding(mesh, P("dp", None, None))
    return jax.jit(
        lambda x: _apply(bbits, x),
        in_shardings=sharding, out_shardings=sharding)


def sharded_decode(
    k: int,
    rows,
    frags: np.ndarray,
    mesh: Mesh | None = None,
) -> np.ndarray:
    """Decode k surviving fragments (fragment-major, (k, S*512)) into the
    original (S*k*512,) bytes, sharded over the mesh's ``dp`` axis.

    ``rows`` are the surviving fragment indices (any order-preserving
    k-subset of 0..n-1) — the ``ec_dispatch_min`` answer set.
    """
    if mesh is None:
        mesh = make_mesh()
    rows = tuple(int(x) for x in rows)
    x = gf256.frags_to_planes(frags, k)  # (S, k*8, 64), validates shape
    s = x.shape[0]
    dp = mesh.devices.shape[0]
    pad = (-s) % dp  # dp-sharded input must divide evenly; pad + trim
    if pad:
        x = np.concatenate(
            [x, np.zeros((pad, *x.shape[1:]), dtype=np.uint8)], axis=0)
    with _BUILD_LOCK:
        y = _decode_fn(k, rows, mesh)(jnp.asarray(x))
    return np.asarray(y)[:s].reshape(s * k * gf256.CHUNK_SIZE)
