"""Pallas TPU kernels for the GF(256) erasure codec.

The contraction ``y[r, :] = XOR_j { x[j, :] : abits[r, j] == 1 }`` over
plane-major byte data (see ops/gf256.py) is computed entirely in VMEM so the
8x bit-expanded intermediates of the XLA path never touch HBM.  Two kernel
bodies (reference analog: the JIT'd XOR-chain kernels of
xlators/cluster/ec/src/ec-code.c, selected by disperse.cpu-extensions):

* ``xor``: statically unrolled per-row XOR chains on the VPU — the direct
  TPU analog of the reference's AVX chains.  Coefficients are baked into the
  trace (per-matrix specialization, like the reference's per-matrix JIT with
  its LRU cache, ec-method.c:200-245).
* ``mxu``: in-kernel unpack -> int8 binary matmul on the MXU (mod 2) ->
  repack.  Coefficient bit-matrix arrives as a kernel operand, so decode
  does not recompile per surviving-fragment mask.

Data layout in/out of the kernels is plane-major ``(planes, W)``: plane row
``j`` of the input holds byte ``w`` of plane ``j & 7`` of chunk-column
``j >> 3``, across all stripes.  ``ops/codec.py`` wraps the stripe-major <->
plane-major transposes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import tracing
from . import gf256
from ._device import device_call, launched

# Lane tile for uint8 is (32, 128); keep W tiles big to amortize grid overhead.
_TILE_W = 8192

# xor3 kernel geometry: plane rows viewed as (M, 128) so every row slice is
# a full (BM, 128) vreg tile (8 sublanes x 128 lanes fully used; the 2-D
# kernel's (1, W) slices waste 7/8 of each vreg).
_TILE3_M = 256  # measured best on v5e (probe: 44 GiB/s e2e encode 4+2)
_TILE3_W = _TILE3_M * 128  # bytes per plane row per grid step (32 KiB)

# VMEM working-set budget for the mxu kernel (the int32 matmul output
# dominates at R rows x 8*tile int32); stay well under the ~16 MiB more
# conservative TPU VMEM sizes.
_MXU_VMEM_BUDGET = 8 << 20


def _mxu_tile_w(r: int, c: int) -> int:
    """Largest power-of-two tile (dividing _TILE_W) whose mxu working set
    fits the VMEM budget: y (r, 8t) i32 + bits (c, 8t) i8 + x (c, t) i32."""
    t = _TILE_W
    while t > 512:
        working = r * 8 * t * 4 + c * 8 * t + c * t * 4 + (r + c) * t
        if working <= _MXU_VMEM_BUDGET:
            break
        t //= 2
    return t


def _xor_kernel_body(sels: tuple[tuple[int, ...], ...]):
    """Build a kernel computing out[r] = XOR of x[j] for j in sels[r]."""

    def kernel(x_ref, o_ref):
        x = x_ref[:]
        for r, sel in enumerate(sels):
            if not sel:
                o_ref[r : r + 1, :] = jnp.zeros_like(o_ref[r : r + 1, :])
                continue
            acc = x[sel[0] : sel[0] + 1, :]
            for j in sel[1:]:
                acc = acc ^ x[j : j + 1, :]
            o_ref[r : r + 1, :] = acc

    return kernel


def _mxu_kernel(a_ref, x_ref, o_ref):
    """Unpack -> binary matmul (mod 2) -> pack, all in VMEM.

    Bit positions use grouped order (all bit-0 columns, then all bit-1
    columns, ...) so everything stays rank-2: Mosaic can't insert minor dims
    on int8.  The bit dim is a free dim of the matmul, so any consistent
    order is valid as long as pack mirrors unpack.
    """
    x = x_ref[:].astype(jnp.int32)  # (C, TW); int8 shifts don't legalize
    tw = x.shape[1]
    bits = jnp.concatenate(
        [((x >> b) & 1).astype(jnp.int8) for b in range(8)], axis=1
    )  # (C, 8*TW)
    y = jax.lax.dot_general(
        a_ref[:],
        bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (R, 8*TW)
    acc = y[:, 0:tw] & 1
    for b in range(1, 8):
        acc = acc | ((y[:, b * tw : (b + 1) * tw] & 1) << b)
    o_ref[:] = acc.astype(jnp.uint8)


def _xor3_kernel_body(sels: tuple[tuple[int, ...], ...]):
    """out[r] = XOR of x[j] for j in sels[r], on (BM, 128) row tiles."""

    def kernel(x_ref, o_ref):
        x = x_ref[:]
        for r, sel in enumerate(sels):
            if not sel:
                o_ref[r] = jnp.zeros_like(o_ref[r])
                continue
            acc = x[sel[0]]
            for j in sel[1:]:
                acc = acc ^ x[j]
            o_ref[r] = acc

    return kernel


@functools.lru_cache(maxsize=256)
def _xor3_apply_fn(sels: tuple[tuple[int, ...], ...], c: int,
                   interpret: bool):
    """(C, W) uint8 -> (R, W) uint8; W % _TILE3_W == 0; 3-D tiled."""
    r = len(sels)
    kernel = _xor3_kernel_body(sels)

    @jax.jit
    def run(x):
        w = x.shape[1]
        m = w // 128
        x3 = x.reshape(c, m, 128)
        grid = (m // _TILE3_M,)
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((r, m, 128), jnp.uint8),
            grid=grid,
            in_specs=[
                pl.BlockSpec((c, _TILE3_M, 128), lambda i: (0, i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((r, _TILE3_M, 128), lambda i: (0, i, 0),
                                   memory_space=pltpu.VMEM),
            interpret=interpret,
            name="gf256_xor3",
        )(x3)
        return out.reshape(r, w)

    return run


@functools.lru_cache(maxsize=256)
def _xor_apply_fn(sels: tuple[tuple[int, ...], ...], c: int, interpret: bool):
    """(C, W) uint8 -> (R, W) uint8 via static XOR chains; W % _TILE_W == 0."""
    r = len(sels)
    kernel = _xor_kernel_body(sels)

    @jax.jit
    def run(x):
        w = x.shape[1]
        grid = (w // _TILE_W,)
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((r, w), jnp.uint8),
            grid=grid,
            in_specs=[
                pl.BlockSpec((c, _TILE_W), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((r, _TILE_W), lambda i: (0, i),
                                   memory_space=pltpu.VMEM),
            interpret=interpret,
            name="gf256_xor",
        )(x)

    return run


@functools.lru_cache(maxsize=16)
def _mxu_apply_fn(r: int, c: int, interpret: bool):
    """(R*8, C*8) bitmatrix (int8), (C*8, W) bytes -> (R*8, W) bytes."""

    tile_w = _mxu_tile_w(r, c)

    @jax.jit
    def run(abits, x):
        w = x.shape[1]
        grid = (w // tile_w,)
        return pl.pallas_call(
            _mxu_kernel,
            out_shape=jax.ShapeDtypeStruct((r, w), jnp.uint8),
            grid=grid,
            in_specs=[
                pl.BlockSpec((r, c), lambda i: (0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((c, tile_w), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((r, tile_w), lambda i: (0, i),
                                   memory_space=pltpu.VMEM),
            interpret=interpret,
            name="gf256_mxu",
        )(abits, x)

    return run


def _sels_from_bits(abits: np.ndarray) -> tuple[tuple[int, ...], ...]:
    return tuple(tuple(int(j) for j in np.nonzero(row)[0]) for row in abits)


def apply_bitmatrix(
    abits: np.ndarray,
    x: jnp.ndarray,
    formulation: str = "xor",
    interpret: bool = False,
) -> jnp.ndarray:
    """Apply an (R, C) GF(2) bit-matrix to plane-major bytes (C, W) -> (R, W).

    W must be a multiple of _TILE_W (callers pad stripes accordingly).
    """
    if formulation not in ("xor", "xor3", "mxu"):
        raise ValueError(
            f"formulation must be 'xor', 'xor3' or 'mxu', got {formulation!r}")
    r, c = abits.shape
    if x.shape[0] != c:
        raise ValueError(f"plane rows {x.shape[0]} != bitmatrix columns {c}")
    if x.shape[1] % _TILE_W:
        raise ValueError(f"W must be a multiple of {_TILE_W}")
    if formulation == "xor3":
        if x.shape[1] % _TILE3_W:
            raise ValueError(f"W must be a multiple of {_TILE3_W} for xor3")
        return _xor3_apply_fn(_sels_from_bits(abits), c, interpret)(x)
    if formulation == "xor":
        return _xor_apply_fn(_sels_from_bits(abits), c, interpret)(x)
    return _mxu_apply_fn(r, c, interpret)(jnp.asarray(abits, jnp.int8), x)


# ---------------------------------------------------------------------------
# Fused wire-layout kernels (the production path).
#
# The transpose-sandwich wrappers below pay 3-4 extra HBM passes (XLA
# materializes the u8 fragment-major <-> plane-major transposes at ~1/6 of
# copy speed).  The fused kernels read and write the wire layouts directly
# and do the plane relayout in VMEM via 64-byte lane slices:
#
# * encode: stripe-major (S, k*512) blocks in, per-fragment (n, TS, 512)
#   blocks out — measured 98 GiB/s e2e on v5e (4+2, 64 MiB).
# * decode: per-fragment (k, TS, 512) blocks in, concatenated to one wide
#   (TS, k*512) VMEM value FIRST (slicing planes from k separate block
#   values is 25% slower), stripe-major out — measured 92 GiB/s e2e.
#
# Both keep fragments byte-exact with the reference layout
# (ec-method.c:393-433): fragment f = its 512-byte chunk from every stripe.
# ---------------------------------------------------------------------------

_FUSED_TS = 256  # stripes per grid step (measured best on v5e)

# Per-config tiles from an on-chip sweep of the TRANSPOSED program
# kernels (v5e, best of ts in {64,128,256,512}): encode/decode 4+2
# 109-118 GiB/s @256-512, 8+4 111/123 @256; k=16's larger per-step
# working set needs ts=128 (256 exceeded scoped VMEM).  That sweep ran
# under an earlier libtpu and is not repeated on the local chip; with
# these tiles every program compiles there (jax 0.9.0, libtpu 0.0.34).


def _enc_ts(k: int) -> int:
    return 128 if k >= 16 else _FUSED_TS


_dec_ts = _enc_ts


def _program_encode_kernel(ops: tuple, outs: tuple, k: int, n: int):
    """Straight-line XOR program body (gf256.xor_program): shared
    subexpressions are computed ONCE per grid step instead of once per
    output plane — these kernels are VPU-throughput-bound, so the
    ~2.7x XOR-count cut is ~the speedup.

    Transposed geometry: the wire layout's 64-byte bit-plane words
    sliced stripe-major are (ts, 64) values — HALF of every 128-lane
    vreg idle.  One in-VMEM transpose per block turns every program
    variable into a (64, ts) full-lane tile, doubling VPU utilization
    (measured: 16+4 encode 38 -> 79 GiB/s)."""

    def kernel(x_ref, o_ref):
        xt = x_ref[:].T  # (k*512, ts): planes are (64, ts) full tiles
        t = [xt[j * 64:(j + 1) * 64, :] for j in range(k * 8)]
        for dst, a, b in ops:
            t.append(t[a] ^ t[b])  # dst ids are dense: dst == len(t)
        for f in range(n):
            accs = []
            for b in range(8):
                o = outs[f * 8 + b]
                acc = t[o[0]]
                for v in o[1:]:
                    acc = acc ^ t[v]
                accs.append(acc)
            o_ref[f] = jnp.concatenate(accs, axis=0).T  # (ts, 512)

    return kernel


def _program_decode_kernel(ops: tuple, outs: tuple, k: int):
    """Decode body, same transposed program geometry as encode."""

    def kernel(x_ref, o_ref):
        # one wide value first: lane-slicing from k separate (ts, 512)
        # block values generates markedly slower code
        xt = jnp.concatenate([x_ref[f] for f in range(k)], axis=1).T
        t = [xt[j * 64:(j + 1) * 64, :] for j in range(k * 8)]
        for dst, a, b in ops:
            t.append(t[a] ^ t[b])
        cols = []
        for c in range(k):
            for b in range(8):
                o = outs[c * 8 + b]
                acc = t[o[0]]
                for v in o[1:]:
                    acc = acc ^ t[v]
                cols.append(acc)
        o_ref[:] = jnp.concatenate(cols, axis=0).T  # (ts, k*512)

    return kernel


@functools.lru_cache(maxsize=64)
def _fused_encode_fn(k: int, n: int, interpret: bool):
    """jitted: flat stripe-major bytes (S*k*512,) -> fragments (n, S*512).

    The kernel body executes the CSE'd straight-line XOR program
    (gf256.xor_program, ~0.4x the naive chain count) in ONE pallas
    call: shared intermediates span every output fragment, so the old
    wide-k group split (one call per fragment group, each re-reading
    the input because the naive unroll blew the compiler's appetite)
    would forfeit most of the sharing."""
    prog = gf256.encode_program(k, n)
    ts = _enc_ts(k)
    kernel = _program_encode_kernel(prog.ops, prog.outs, k, n)

    @jax.jit
    def run(flat):
        s = flat.shape[0] // (k * gf256.CHUNK_SIZE)
        sp = (s + ts - 1) // ts * ts
        x = flat.reshape(s, k * gf256.CHUNK_SIZE)
        if sp != s:
            x = jnp.pad(x, ((0, sp - s), (0, 0)))
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n, sp, 512), jnp.uint8),
            grid=(sp // ts,),
            in_specs=[pl.BlockSpec((ts, k * 512), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((n, ts, 512),
                                   lambda i: (0, i, 0),
                                   memory_space=pltpu.VMEM),
            interpret=interpret,
            name="gf256_encode",
        )(x)
        return out[:, :s, :].reshape(n, s * gf256.CHUNK_SIZE)

    return run


@functools.lru_cache(maxsize=256)
def _fused_decode_fn(k: int, rows: tuple[int, ...], interpret: bool):
    """jitted: survivors (k, S*512) fragment-major -> flat bytes (S*k*512,).

    One jitted decoder per surviving mask (this LRU of compiled kernels
    sits on top of gf256.DECODE_PROGRAMS, the shared per-mask LRU of
    compiled XOR programs — together the compiled-program analog of the
    reference's inverted-matrix LRU, ec-method.c:200-245); the body runs
    the CSE'd XOR program in one pallas call (see _fused_encode_fn)."""
    prog = gf256.decode_program(k, rows)
    ts = _dec_ts(k)
    kernel = _program_decode_kernel(prog.ops, prog.outs, k)

    @jax.jit
    def run(frags):
        s = frags.shape[1] // gf256.CHUNK_SIZE
        sp = (s + ts - 1) // ts * ts
        x = frags.reshape(k, s, 512)
        if sp != s:
            x = jnp.pad(x, ((0, 0), (0, sp - s), (0, 0)))
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((sp, k * 512), jnp.uint8),
            grid=(sp // ts,),
            in_specs=[pl.BlockSpec((k, ts, 512),
                                   lambda i: (0, i, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((ts, k * 512), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            interpret=interpret,
            name="gf256_decode",
        )(x)
        return out[:s].reshape(s * k * gf256.CHUNK_SIZE)

    return run


# ---------------------------------------------------------------------------
# Systematic serving kernels (disperse.systematic): the device computes
# and ships ONLY what the host cannot reshape for itself — parity rows
# on encode (r/k of the data back over the host<->device link instead
# of n/k), missing data rows on degraded decode.  What that link costs
# next to the XOR math is not yet measured on a local chip.
# gf256.systematic_matrix documents the design choice vs the
# reference's non-systematic code.
# ---------------------------------------------------------------------------


def _program_reconstruct_kernel(ops: tuple, outs: tuple, k: int, m: int):
    """Fragment-major survivors in -> fragment-major wanted rows out
    (decode-style input, encode-style output; same transposed CSE'd
    program geometry as _program_encode_kernel)."""

    def kernel(x_ref, o_ref):
        xt = jnp.concatenate([x_ref[f] for f in range(k)], axis=1).T
        t = [xt[j * 64:(j + 1) * 64, :] for j in range(k * 8)]
        for dst, a, b in ops:
            t.append(t[a] ^ t[b])
        for f in range(m):
            accs = []
            for b in range(8):
                o = outs[f * 8 + b]
                acc = t[o[0]]
                for v in o[1:]:
                    acc = acc ^ t[v]
                accs.append(acc)
            o_ref[f] = jnp.concatenate(accs, axis=0).T  # (ts, 512)

    return kernel


@functools.lru_cache(maxsize=64)
def _fused_parity_fn(k: int, n: int, interpret: bool):
    """jitted: flat stripe-major bytes (S*k*512,) -> parity fragments
    ONLY ((n-k), S*512) of the systematic code — D2H is r/k of the data
    instead of n/k."""
    prog = gf256.parity_program(k, n)
    ts = _enc_ts(k)
    r = n - k
    kernel = _program_encode_kernel(prog.ops, prog.outs, k, r)

    @jax.jit
    def run(flat):
        s = flat.shape[0] // (k * gf256.CHUNK_SIZE)
        sp = (s + ts - 1) // ts * ts
        x = flat.reshape(s, k * gf256.CHUNK_SIZE)
        if sp != s:
            x = jnp.pad(x, ((0, sp - s), (0, 0)))
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((r, sp, 512), jnp.uint8),
            grid=(sp // ts,),
            in_specs=[pl.BlockSpec((ts, k * 512), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((r, ts, 512), lambda i: (0, i, 0),
                                   memory_space=pltpu.VMEM),
            interpret=interpret,
            name="gf256_parity",
        )(x)
        return out[:, :s, :].reshape(r, s * gf256.CHUNK_SIZE)

    return run


@functools.lru_cache(maxsize=256)
def _fused_reconstruct_fn(k: int, rows: tuple[int, ...],
                          wanted: tuple[int, ...], interpret: bool):
    """jitted: systematic survivors (k, S*512) fragment-major ->
    ONLY the ``wanted`` missing data rows (len(wanted), S*512) — D2H is
    missing/k of the data instead of all of it."""
    prog = gf256.reconstruct_program(k, rows, wanted)
    ts = _dec_ts(k)
    m = len(wanted)
    kernel = _program_reconstruct_kernel(prog.ops, prog.outs, k, m)

    @jax.jit
    def run(frags):
        s = frags.shape[1] // gf256.CHUNK_SIZE
        sp = (s + ts - 1) // ts * ts
        x = frags.reshape(k, s, 512)
        if sp != s:
            x = jnp.pad(x, ((0, 0), (0, sp - s), (0, 0)))
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((m, sp, 512), jnp.uint8),
            grid=(sp // ts,),
            in_specs=[pl.BlockSpec((k, ts, 512), lambda i: (0, i, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((m, ts, 512), lambda i: (0, i, 0),
                                   memory_space=pltpu.VMEM),
            interpret=interpret,
            name="gf256_reconstruct",
        )(x)
        return out[:, :s, :].reshape(m, s * gf256.CHUNK_SIZE)

    return run


# Pipelined-launch threshold: inputs past this split into fixed-shape
# chunks that are all launched before any result is fetched, which
# overlaps the two link directions and bounds device memory for huge
# batches.  Each chunk pays a per-call floor, so serving-size flushes
# stay one launch.  The value is not yet measured on a local chip.
_PARITY_CHUNK_BYTES = 64 << 20


def parity(data: np.ndarray, k: int, n: int,
           interpret: bool = False) -> np.ndarray:
    """Systematic parity rows ((n-k), S*512) for stripe-major bytes.

    Large inputs are split into fixed-shape chunks that are ALL
    launched before any result is fetched, which pipelines the link's
    two directions (see ``_PARITY_CHUNK_BYTES``)."""
    data = np.ascontiguousarray(data, dtype=np.uint8).ravel()
    stripe = k * gf256.CHUNK_SIZE
    s = data.size // stripe
    cs = max(1, _PARITY_CHUNK_BYTES // stripe)
    fn = _fused_parity_fn(k, n, interpret)
    if s <= cs:
        return device_call(fn, data)
    launches = []
    for off in range(0, s, cs):
        w = min(cs, s - off)
        chunk = data[off * stripe:(off + w) * stripe]
        if w < cs:  # pad the tail so every launch shares one jit shape
            chunk = np.concatenate(
                [chunk, np.zeros((cs - w) * stripe, dtype=np.uint8)])
        with tracing.phase(None, "codec.h2d"):
            chunk = jnp.asarray(chunk)
        with tracing.phase(None, "codec.launch"):
            launches.append((fn(chunk), w))
    launched()
    with tracing.phase(None, "codec.d2h"):
        return np.concatenate(
            [np.asarray(d)[:, : w * gf256.CHUNK_SIZE]
             for d, w in launches], axis=1)


def reconstruct(frags: np.ndarray, rows, wanted, k: int,
                interpret: bool = False) -> np.ndarray:
    """Missing systematic data rows from k survivors (fragment-major)."""
    fn = _fused_reconstruct_fn(k, tuple(int(x) for x in rows),
                               tuple(int(x) for x in wanted), interpret)
    return device_call(fn, frags)


# ---------------------------------------------------------------------------
# Stripe-major wrappers (same API as gf256_xla): transpose sandwich.
# ---------------------------------------------------------------------------


def _pad_w(s: int) -> int:
    """Stripes padded so plane width S*64 is a multiple of every kernel's
    tile (_TILE3_W = 32 KiB covers _TILE_W = 8 KiB too)."""
    per = _TILE3_W // gf256.WORD_SIZE  # stripes per tile
    return (s + per - 1) // per * per


@functools.lru_cache(maxsize=64)
def _encode_fn(k: int, n: int, formulation: str, interpret: bool):
    abits_np = gf256.expand_bitmatrix(gf256.encode_matrix(k, n))

    @jax.jit
    def run(data):
        s = data.shape[0] // (k * gf256.CHUNK_SIZE)
        sp = _pad_w(s)
        x = data.reshape(s, k * 8, gf256.WORD_SIZE)
        x = jnp.pad(x, ((0, sp - s), (0, 0), (0, 0)))
        xt = x.transpose(1, 0, 2).reshape(k * 8, sp * gf256.WORD_SIZE)
        yt = apply_bitmatrix(abits_np, xt, formulation, interpret)
        y = yt.reshape(n * 8, sp, gf256.WORD_SIZE)[:, :s, :]
        # (n*8, S, 64) -> fragment-major (n, S*512)
        return (
            y.reshape(n, 8, s, gf256.WORD_SIZE)
            .transpose(0, 2, 1, 3)
            .reshape(n, s * gf256.CHUNK_SIZE)
        )

    return run


@functools.lru_cache(maxsize=256)
def _decode_fn(k: int, formulation: str, interpret: bool,
               rows: tuple[int, ...] | None):
    """Transpose-sandwich decode; static (xor/xor3) forms are cached per
    surviving mask ``rows`` — matching the per-mask program LRU keying —
    instead of per bit-matrix tuple (mxu passes rows=None: its bbits is
    a traced operand, one compile serves every mask)."""
    def run(frags, bbits_np):
        s = frags.shape[1] // gf256.CHUNK_SIZE
        sp = _pad_w(s)
        x = jnp.pad(
            frags.reshape(k, s, 8, gf256.WORD_SIZE).transpose(0, 2, 1, 3),
            ((0, 0), (0, 0), (0, sp - s), (0, 0)),
        ).reshape(k * 8, sp * gf256.WORD_SIZE)
        yt = apply_bitmatrix(bbits_np, x, formulation, interpret)
        y = yt.reshape(k * 8, sp, gf256.WORD_SIZE)[:, :s, :]
        # plane rows (k*8) are chunk-major within the stripe: chunk j of the
        # stripe is rows 8j..8j+7 -> output stripe-major bytes
        return (
            y.reshape(k, 8, s, gf256.WORD_SIZE)
            .transpose(2, 0, 1, 3)
            .reshape(s * k * gf256.CHUNK_SIZE)
        )

    if formulation in ("xor", "xor3"):
        bb = gf256.decode_bits_cached(k, rows)
        return jax.jit(lambda frags: run(frags, bb))
    return jax.jit(run)


def encode(data, k: int, n: int, formulation: str = "fused",
           interpret: bool = False) -> np.ndarray:
    data = np.ascontiguousarray(data, dtype=np.uint8).ravel()
    if data.size % (k * gf256.CHUNK_SIZE):
        raise ValueError("data length must be a multiple of k*512")
    if formulation == "fused":
        return device_call(_fused_encode_fn(k, n, interpret), data)
    return device_call(_encode_fn(k, n, formulation, interpret), data)


def decode(frags, rows, k: int, formulation: str = "fused",
           interpret: bool = False) -> np.ndarray:
    frags = np.ascontiguousarray(frags, dtype=np.uint8)
    rows = tuple(int(x) for x in rows)
    if formulation == "fused":
        return device_call(_fused_decode_fn(k, rows, interpret), frags)
    if formulation in ("xor", "xor3"):
        return device_call(_decode_fn(k, formulation, interpret, rows),
                           frags)
    bbits_np = gf256.decode_bits_cached(k, rows)
    return device_call(_decode_fn(k, "mxu", interpret, None), frags,
                       bbits_np.astype(np.int8))
