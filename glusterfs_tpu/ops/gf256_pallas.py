"""Pallas TPU kernels for the GF(256) erasure codec.

One formulation: the CSE'd straight-line XOR program of a bit-matrix
(``gf256.XorProgram``; reference analog: the JIT'd XOR-chain kernels of
xlators/cluster/ec/src/ec-code.c, selected by disperse.cpu-extensions)
unrolled into the trace of one ``pallas_call`` that reads and writes the
wire layouts directly and does the bit-plane relayout in VMEM, so the 8x
bit-expanded intermediates of the XLA path never touch HBM.  Four
kernels, named for the profiler: ``gf256_encode`` and ``gf256_decode``
(the whole code, either direction), ``gf256_parity`` and
``gf256_reconstruct`` (the systematic code's serving kernels: only the
rows the host cannot reshape for itself).  Coefficients are baked into
the trace (per-matrix specialization, like the reference's per-matrix
JIT with its LRU cache, ec-method.c:200-245).
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

# ---------------------------------------------------------------------------
# Wire-layout kernels.  Each reads and writes the wire layouts directly
# and does the plane relayout in VMEM via 64-byte lane slices (a
# transposing wrapper around a plane-major kernel pays 3-4 extra HBM
# passes: XLA materializes the u8 fragment-major <-> plane-major
# transposes at ~1/6 of copy speed):
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
# What the local chip has measured for k=16 is the served launch (PR 30,
# PERF.md section 5 N): a 1 MiB write of a 16+4 volume is 128 stripes,
# one tile and one grid step of gf256_parity, 2.4 us of device time a
# launch (1.25 MiB moved: two thirds of the HBM peak for the kernel
# alone) with 5.0 us of layout copies beside it, parity_roofline 21.7%
# for the launch whole.


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


def encode(data, k: int, n: int, interpret: bool = False) -> np.ndarray:
    """Stripe-major bytes (a multiple of k*512) -> (n, S*512) fragments."""
    data = np.ascontiguousarray(data, dtype=np.uint8).ravel()
    if data.size % (k * gf256.CHUNK_SIZE):
        raise ValueError("data length must be a multiple of k*512")
    return device_call(_fused_encode_fn(k, n, interpret), data)


def decode(frags, rows, k: int, interpret: bool = False) -> np.ndarray:
    """k survivors (k, S*512) with indices ``rows`` -> the bytes."""
    frags = np.ascontiguousarray(frags, dtype=np.uint8)
    rows = tuple(int(x) for x in rows)
    return device_call(_fused_decode_fn(k, rows, interpret), frags)
