"""The plain reference of a disperse volume: what the bricks must hold
and what the door must return, from first principles.

It imports nothing of the program.  The field, the generator and the
chunk layout are upstream's (``ec-method.c``, ``ec-galois.c``,
``doc/developer-guide/ec-implementation.md``), written out the slow and
obvious way: chunks are unpacked into their 512 GF(2^8) elements, the
generator is applied element by element through a multiplication table,
and the result is packed again.  The program's kernels never unpack
(they run XOR programs on bit planes), so the two share no arithmetic.
"""

from __future__ import annotations

import functools

import numpy as np

CHUNK = 512   # EC_METHOD_CHUNK_SIZE: 8 bit planes of 64 bytes
POLY = 0x11D  # GF(2^8) primitive polynomial, generator 2


@functools.cache
def mul_table() -> np.ndarray:
    """(256, 256) uint8: products in GF(2^8) mod 0x11D, by shift-and-add."""
    t = np.zeros((256, 256), dtype=np.uint8)
    for a in range(256):
        for b in range(256):
            x, y, acc = a, b, 0
            while y:
                if y & 1:
                    acc ^= x
                x <<= 1
                if x & 0x100:
                    x ^= POLY
                y >>= 1
            t[a, b] = acc
    return t


def _inv(a: int) -> int:
    row = mul_table()[a]
    return int(np.nonzero(row == 1)[0][0])


def _matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    t = mul_table()
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for i in range(a.shape[0]):
        for j in range(b.shape[1]):
            acc = 0
            for x in range(a.shape[1]):
                acc ^= int(t[a[i, x], b[x, j]])
            out[i, j] = acc
    return out


def _invert(m: np.ndarray) -> np.ndarray:
    """Gauss-Jordan over GF(2^8)."""
    t = mul_table()
    k = m.shape[0]
    a = np.concatenate([m.astype(np.uint8), np.eye(k, dtype=np.uint8)], axis=1)
    for col in range(k):
        piv = next(r for r in range(col, k) if a[r, col])
        a[[col, piv]] = a[[piv, col]]
        a[col] = t[_inv(int(a[col, col]))][a[col]]
        for r in range(k):
            if r != col and a[r, col]:
                a[r] ^= t[int(a[r, col])][a[col]]
    return a[:, k:]


@functools.cache
def generator(k: int, n: int, systematic: bool = True) -> np.ndarray:
    """(n, k) generator.  Upstream's is the reverse Vandermonde, row i =
    [v^(k-1) .. v 1] for v = i + 1 (``ec-method.c:22-35``); the
    systematic layout (the volume default since op-version 12) is that
    matrix times the inverse of its first k rows, so fragments 0..k-1
    are the stripe's own chunks and k.. are parity."""
    t = mul_table()
    v = np.zeros((n, k), dtype=np.uint8)
    for i in range(n):
        acc = 1
        for j in range(k - 1, -1, -1):
            v[i, j] = acc
            acc = int(t[acc, i + 1])
    return _matmul(v, _invert(v[:k])) if systematic else v


def to_elements(chunks: np.ndarray) -> np.ndarray:
    """(..., 512) chunk bytes -> (..., 512) field elements.  Plane p of a
    chunk (bytes 64p .. 64p+63) holds bit p of every element; element
    e's bit sits in plane byte e >> 3 at bit e & 7."""
    planes = chunks.reshape(chunks.shape[:-1] + (8, 64))
    bits = np.unpackbits(planes, axis=-1, bitorder="little")  # (..., 8, 512)
    out = np.zeros(chunks.shape, dtype=np.uint8)
    for p in range(8):
        out |= bits[..., p, :] << p
    return out


def from_elements(elems: np.ndarray) -> np.ndarray:
    planes = np.stack([(elems >> p) & 1 for p in range(8)], axis=-2)
    return np.packbits(planes, axis=-1, bitorder="little").reshape(
        elems.shape)


def encode(data: bytes | np.ndarray, k: int, n: int,
           systematic: bool = True) -> np.ndarray:
    """Whole stripes of user bytes -> (n, S * 512) fragments, fragment i
    being its chunk of every stripe in order (``ec_method_encode``)."""
    data = np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray, memoryview)) else data
    if data.size % (k * CHUNK):
        raise ValueError("not a whole number of stripes")
    gen, t = generator(k, n, systematic), mul_table()
    elems = to_elements(data.reshape(-1, k, CHUNK))  # (S, k, 512)
    out = np.zeros((n,) + elems.shape[::2], dtype=np.uint8)  # (n, S, 512)
    for i in range(n):
        for j in range(k):
            if gen[i, j]:
                out[i] ^= t[int(gen[i, j])][elems[:, j, :]]
    return from_elements(out).reshape(n, -1)
