"""The plain reference of a degraded read: the user bytes of whole
stripes from any k of their n fragments, from first principles.

The other half of ``reference.py``, and as slow and obvious: the rows of
its generator that belong to the surviving fragments are inverted over
GF(2^8) by its Gauss-Jordan, the inverse is applied element by element
through its multiplication table, and the chunks are packed again.  It
imports nothing of the program, whose decode runs XOR programs on bit
planes and, on a systematic volume, rebuilds only the missing rows.
"""

from __future__ import annotations

import numpy as np

from .reference import (CHUNK, _invert, from_elements, generator, mul_table,
                        to_elements)


def decode(frags: np.ndarray, rows, k: int, n: int,
           systematic: bool = True) -> np.ndarray:
    """``frags`` (k, S * 512), fragment ``rows[i]`` in row i -> the
    S * k * 512 stripe-major bytes that ``reference.encode`` was given."""
    rows = [int(r) for r in rows]
    if len(set(rows)) != k or not all(0 <= r < n for r in rows):
        raise ValueError(f"need {k} distinct fragments of {n}, got {rows}")
    frags = np.asarray(frags, dtype=np.uint8)
    if frags.shape[0] != k or frags.shape[1] % CHUNK:
        raise ValueError("not k fragments of whole chunks")
    inv, t = _invert(generator(k, n, systematic)[rows]), mul_table()
    elems = to_elements(frags.reshape(k, -1, CHUNK))  # (k, S, 512)
    out = np.zeros_like(elems)
    for j in range(k):
        for i in range(k):
            if inv[j, i]:
                out[j] ^= t[int(inv[j, i])][elems[i]]
    return from_elements(out).transpose(1, 0, 2).reshape(-1)
