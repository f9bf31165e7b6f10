"""JAX/XLA backends for the GF(256) erasure codec.

Two formulations of the same math (see ops/gf256.py for layout semantics;
reference: xlators/cluster/ec/src/ec-method.c:393-433):

* ``matmul``: unpack chunk bytes to GF(2) bits and contract with the
  (R*8, C*8) binary bit-matrix on the MXU (int8 dot, mod 2), then repack.
  One matmul per stripe batch — the TPU-native replacement for the
  reference's JIT-emitted XOR chains (ec-code.c).
* ``xor``: keep bytes packed and XOR-accumulate plane words on the VPU,
  unrolling the CSE'd straight-line XOR program (gf256.build_xor_program)
  into the trace — shared subexpressions are computed once per batch
  instead of once per output plane (the analog of the reference's AVX XOR
  chains, but ~2-3x fewer XORs and traded for XLA fusion instead of
  hand JIT).

``matmul`` takes the coefficient bit-matrix as a traced argument, so decode
does not retrace per surviving-fragment mask; ``xor`` bakes the program
into the trace (one compile per mask, like the reference's per-matrix
JIT).  Decode programs come from the shared per-mask compiled-program LRU
(gf256.DECODE_PROGRAMS), the jitted fns from a cache keyed the same way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import gf256
from ._device import device_call

_BIT_SHIFTS = tuple(1 << t for t in range(8))


def _unpack_bits(x: jnp.ndarray) -> jnp.ndarray:
    """(..., W) uint8 -> (..., W*8) uint8 bits, little-endian within bytes."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (x[..., None] >> shifts) & jnp.uint8(1)
    return bits.reshape(*x.shape[:-1], x.shape[-1] * 8)


def _pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(..., W*8) uint8 bits -> (..., W) uint8 bytes."""
    w8 = bits.shape[-1]
    b = bits.reshape(*bits.shape[:-1], w8 // 8, 8)
    weights = jnp.array(_BIT_SHIFTS, dtype=jnp.uint8)
    return (b * weights).sum(axis=-1, dtype=jnp.uint8)


def _apply_matmul(abits: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """y[s,i,:] = (sum_j abits[i,j] * bits(x)[s,j,:]) mod 2, repacked.

    x: (S, C, 64) uint8 plane words; abits: (R, C) int8 in {0,1}.
    Returns (S, R, 64) uint8.
    """
    bits = _unpack_bits(x).astype(jnp.int8)  # (S, C, 512)
    y = jax.lax.dot_general(
        abits.astype(jnp.int8),
        bits,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (R, S, 512)
    y = jnp.transpose(y, (1, 0, 2))
    return _pack_bits((y & 1).astype(jnp.uint8))


def _apply_program(prog: gf256.XorProgram, x: jnp.ndarray) -> jnp.ndarray:
    """Same contraction, packed bytes on the VPU, via the CSE'd
    straight-line program: each op is one (S, 64) XOR shared by every
    output row that references it."""
    t = [x[:, j, :] for j in range(prog.n_inputs)]
    for _dst, a, b in prog.ops:
        t.append(t[a] ^ t[b])
    zero = jnp.zeros(x.shape[::2], dtype=jnp.uint8)  # (S, 64)
    outs = []
    for o in prog.outs:
        if not o:
            outs.append(zero)
            continue
        acc = t[o[0]]
        for v in o[1:]:
            acc = acc ^ t[v]
        outs.append(acc)
    return jnp.stack(outs, axis=1)  # (S, R, 64)


@functools.lru_cache(maxsize=64)
def _encode_fn(k: int, n: int, formulation: str, systematic: bool = False):
    if formulation == "xor":
        prog = gf256.encode_program(k, n, systematic)
        abits_np = None
    else:
        abits_np = gf256.expand_bitmatrix(gf256.generator_matrix(
            k, n, systematic))

    def run(data: jnp.ndarray) -> jnp.ndarray:
        s = data.shape[0] // (k * gf256.CHUNK_SIZE)
        x = data.reshape(s, k * 8, gf256.WORD_SIZE)
        if formulation == "xor":
            y = _apply_program(prog, x)
        else:
            y = _apply_matmul(jnp.asarray(abits_np), x)
        # (S, n*8, 64) -> fragment-major (n, S*512)
        return (
            y.reshape(s, n, gf256.CHUNK_SIZE)
            .transpose(1, 0, 2)
            .reshape(n, s * gf256.CHUNK_SIZE)
        )

    return jax.jit(run)


@functools.lru_cache(maxsize=256)
def _decode_fn(k: int, formulation: str, rows: tuple[int, ...] | None,
               systematic: bool = False):
    """One jitted decoder per surviving mask for the static ``xor``
    form (keyed exactly like gf256.DECODE_PROGRAMS, whose compiled
    program it unrolls); ``matmul`` passes rows=None — its bit-matrix
    is a traced operand, one compile serves every mask."""
    prog = gf256.decode_program(k, rows, systematic) \
        if formulation == "xor" else None

    def run(frags: jnp.ndarray, bbits: jnp.ndarray | None) -> jnp.ndarray:
        s = frags.shape[1] // gf256.CHUNK_SIZE
        x = (
            frags.reshape(k, s, 8, gf256.WORD_SIZE)
            .transpose(1, 0, 2, 3)
            .reshape(s, k * 8, gf256.WORD_SIZE)
        )
        if formulation == "xor":
            y = _apply_program(prog, x)
        else:
            y = _apply_matmul(bbits, x)
        return y.reshape(s * k * gf256.CHUNK_SIZE)

    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def _parity_fn(k: int, n: int, formulation: str):
    """jitted: stripe-major bytes -> parity fragments ONLY
    ((n-k), S*512) of the systematic code — the delta-encode of the
    parity-delta write plane (only the generator's parity submatrix is
    applied; the data rows of a delta are shipped verbatim)."""
    if formulation == "xor":
        prog = gf256.parity_program(k, n)
        pbits_np = None
    else:
        pbits_np = gf256.parity_bits_cached(k, n)
    m = n - k

    def run(data: jnp.ndarray) -> jnp.ndarray:
        s = data.shape[0] // (k * gf256.CHUNK_SIZE)
        x = data.reshape(s, k * 8, gf256.WORD_SIZE)
        if formulation == "xor":
            y = _apply_program(prog, x)
        else:
            y = _apply_matmul(jnp.asarray(pbits_np), x)
        return (
            y.reshape(s, m, gf256.CHUNK_SIZE)
            .transpose(1, 0, 2)
            .reshape(m, s * gf256.CHUNK_SIZE)
        )

    return jax.jit(run)


def parity(data: np.ndarray, k: int, n: int,
           formulation: str = "matmul") -> np.ndarray:
    """Systematic parity rows ((n-k), S*512) for stripe-major bytes."""
    data = np.ascontiguousarray(data, dtype=np.uint8).ravel()
    if data.size % (k * gf256.CHUNK_SIZE):
        raise ValueError("data length must be a multiple of k*512")
    return device_call(_parity_fn(k, n, formulation), data)


def encode(data: np.ndarray, k: int, n: int, formulation: str = "matmul",
           systematic: bool = False) -> np.ndarray:
    """Encode bytes (len multiple of k*512) -> (n, S*512) fragments."""
    data = np.ascontiguousarray(data, dtype=np.uint8).ravel()
    if data.size % (k * gf256.CHUNK_SIZE):
        raise ValueError("data length must be a multiple of k*512")
    return device_call(_encode_fn(k, n, formulation, systematic), data)


def decode(
    frags: np.ndarray, rows, k: int, formulation: str = "matmul",
    systematic: bool = False
) -> np.ndarray:
    """Decode k fragments (k, S*512) with indices `rows` -> original bytes."""
    frags = np.ascontiguousarray(frags, dtype=np.uint8)
    rows = tuple(int(x) for x in rows)
    if formulation == "xor":
        fn = _decode_fn(k, "xor", rows, systematic)
        return device_call(lambda x: fn(x, None), frags)
    bbits_np = gf256.decode_bits_cached(k, rows, systematic)
    return device_call(_decode_fn(k, "matmul", None), frags, bbits_np)
