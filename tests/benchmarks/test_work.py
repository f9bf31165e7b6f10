"""The bytes a coding operation has to move, the peaks, and the plain
reference encoding."""

import os

import numpy as np
import pytest

from benchmarks.harness import reference, work
from benchmarks.harness.manifest import ROOT


def test_parity_and_reconstruct_bytes_are_shapes_only():
    mib = 1 << 20
    # 4+2: 512 stripes per MiB; 4 chunks in, 2 out, each 512 bytes
    assert work.stripes(mib, 4) == 512
    assert work.parity_bytes(mib, 4, 2) == 512 * (4 + 2) * 512
    assert work.parity_bytes(mib, 16, 4) == mib + mib // 4
    assert work.reconstruct_bytes(mib, 4, 1) == 512 * (4 + 1) * 512
    assert work.reconstruct_bytes(mib, 4, 2) == 512 * (4 + 2) * 512
    assert work.parity_bytes(0, 4, 2) == 0


def test_peaks_table_and_unknown_kind():
    assert work.peak_bytes_per_s("TPU v5 lite") == 819e9
    assert all(p["source"] for p in work.PEAKS.values())
    with pytest.raises(KeyError, match="no peak recorded"):
        work.peak_bytes_per_s("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.roofline_share(1, "cpu", 1.0)


def test_roofline_share():
    # 819e9 bytes in one second of device time is the roofline itself
    assert work.roofline_share(int(819e9), "TPU v5 lite", 1.0) == \
        pytest.approx(100.0)
    assert work.roofline_share(int(819e9), "TPU v5 lite", 8.0) == \
        pytest.approx(12.5)
    # nothing measured is nothing returned, never 0
    assert work.roofline_share(0, "TPU v5 lite", 1.0) is None
    assert work.roofline_share(10, "TPU v5 lite", 0.0) is None


@pytest.mark.parametrize("k, r", [(4, 2), (8, 3), (16, 4)])
@pytest.mark.parametrize("systematic", [True, False])
def test_reference_agrees_with_the_programs_oracle(k, r, systematic):
    """Two independent writings of upstream's code: this one unpacks
    chunks into field elements and multiplies by table, the program's
    applies bit matrices to the planes."""
    from glusterfs_tpu.ops import gf256

    data = np.random.default_rng(k * 10 + r).integers(
        0, 256, 9 * k * 512, dtype=np.uint8)
    mine = reference.encode(data, k, k + r, systematic)
    theirs = gf256.ref_encode(data, k, k + r, systematic=systematic)
    assert np.array_equal(mine, theirs)
    if systematic:  # data fragments are the stripe's own chunks
        assert np.array_equal(
            mine[:k], data.reshape(-1, k, 512).transpose(1, 0, 2).reshape(
                k, -1))


def test_reference_agrees_with_upstreams_golden_vectors():
    path = os.path.join(ROOT, "tests", "golden", "ec_golden.npz")
    if not os.path.exists(path):
        pytest.skip("the reference C kernel's vectors are not here")
    g = np.load(path)
    for k, r in ((4, 2), (8, 3)):
        frags = np.stack([g[f"frag_{k}_{r}_{i}"] for i in range(k + r)])
        assert np.array_equal(
            reference.encode(g[f"in_{k}_{r}"], k, k + r, False), frags)


def test_field_basics():
    t = reference.mul_table()
    assert t[2, 0x80] == 0x1D  # x * x^7 = x^8 = 0x11D - 0x100
    assert all(t[a, reference._inv(a)] == 1 for a in range(1, 256))
    gen = reference.generator(4, 6)
    assert np.array_equal(gen[:4], np.eye(4, dtype=np.uint8))
    elems = np.arange(512, dtype=np.uint8).reshape(1, 512)
    assert np.array_equal(
        reference.to_elements(reference.from_elements(elems)), elems)
