"""The benchmark's plain reference decode (``benchmarks/harness/
reference_decode.py``: the surviving rows of the reference's generator
inverted by Gauss-Jordan, applied through the multiplication table)
inverts the plain reference encode for every geometry the benchmark
serves, and the program's backends are held to it, byte for byte, at
the masks of the 8+4 deployment with a server down: four data rows
missing, three data rows and a parity row missing, parity alone
missing."""

import numpy as np
import pytest

from benchmarks.harness import reference, reference_decode
from glusterfs_tpu import native
from glusterfs_tpu.ops import codec

#: bricks 4-7 of ec-8p4-tpu stopped: what both of its cells decode from
SERVER_DOWN = (0, 1, 2, 3, 8, 9, 10, 11)
MASKS = {
    (4, 2): [(0, 2, 3, 4), (2, 3, 4, 5), (0, 1, 2, 3)],
    (8, 4): [SERVER_DOWN, (4, 5, 6, 7, 8, 9, 10, 11),
             (0, 2, 3, 5, 7, 8, 10, 11), (1, 3, 4, 6, 7, 9, 10, 11)],
    (16, 4): [tuple(range(4, 20)), tuple(range(16)),
              (0, 1, 2, 3, 4, 5, 7, 8, 9, 10, 12, 13, 14, 15, 17, 19)],
}


def _data(k: int, stripes: int, seed: int) -> np.ndarray:
    return np.random.default_rng([seed, k]).integers(
        0, 256, stripes * k * reference.CHUNK, dtype=np.uint8)


@pytest.mark.parametrize("k,r,rows", [
    (k, r, rows) for (k, r), masks in MASKS.items() for rows in masks])
def test_reference_decode_inverts_reference_encode(k, r, rows):
    data = _data(k, 3, 32)
    frags = reference.encode(data, k, k + r)
    assert np.array_equal(
        reference_decode.decode(frags[list(rows)], rows, k, k + r), data)
    # the order in which the fragments are handed over is the caller's
    back = rows[::-1]
    assert np.array_equal(
        reference_decode.decode(frags[list(back)], back, k, k + r), data)


def test_reference_decode_of_the_upstream_format():
    data = _data(4, 2, 33)
    frags = reference.encode(data, 4, 6, systematic=False)
    rows = (1, 2, 4, 5)
    assert np.array_equal(reference_decode.decode(
        frags[list(rows)], rows, 4, 6, systematic=False), data)


def test_reference_decode_refuses_what_is_not_k_fragments():
    frags = np.zeros((4, 512), dtype=np.uint8)
    with pytest.raises(ValueError):
        reference_decode.decode(frags, (0, 1, 2, 2), 4, 6)
    with pytest.raises(ValueError):
        reference_decode.decode(frags, (0, 1, 2, 6), 4, 6)
    with pytest.raises(ValueError):
        reference_decode.decode(frags[:, :100], (0, 1, 2, 3), 4, 6)


def _codec(backend: str) -> codec.Codec:
    """``Codec(8, 4, systematic=True)`` on ``backend``; the chip's
    kernels run interpreted on a host without one."""
    from tests.harness import have_tpu

    if backend == "native" and not native.available():
        pytest.skip("no native toolchain")
    if backend == "pallas-xor" and not have_tpu():
        c = codec.Codec(8, 4, "ref", systematic=True)
        c._impl = codec._Pallas(8, 4, interpret=True)
        return c
    return codec.Codec(8, 4, backend, systematic=True)


@pytest.mark.parametrize("backend", ["ref", "native", "xla", "pallas-xor"])
@pytest.mark.parametrize("rows", [
    SERVER_DOWN,                          # data rows 4-7 missing
    (0, 2, 3, 5, 7, 8, 10, 11),           # data 1, 4, 6 and parity 9
    (0, 1, 2, 3, 4, 5, 6, 7),             # parity alone: nothing to rebuild
], ids=["four-data-rows", "three-data-one-parity", "parity-only"])
def test_the_program_agrees_with_the_reference_decode(backend, rows):
    k, n = 8, 12
    c = _codec(backend)
    data = _data(k, 5, 34)
    frags = reference.encode(data, k, n)
    want = reference_decode.decode(frags[list(rows)], rows, k, n)
    assert np.array_equal(want, data)
    assert np.array_equal(c.encode(data), frags)
    got = c.decode(frags[list(rows)], rows)
    assert got.dtype == np.uint8 and np.array_equal(got, want)
    missing = [j for j in range(k) if j not in rows]
    if missing:
        rebuilt = c._impl.reconstruct(
            np.ascontiguousarray(frags[list(rows)]), list(rows), missing)
        rows_of = want.reshape(-1, k, reference.CHUNK).transpose(1, 0, 2)
        assert np.array_equal(
            rebuilt, rows_of[missing].reshape(len(missing), -1))
