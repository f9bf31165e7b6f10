"""Cross-backend parity for the unified codec — the TPU build's
``ec-cpu-extensions.t``: every backend must produce byte-identical fragments
and round-trip bytes (reference tests/basic/ec/ec-cpu-extensions.t:19-60
does this end-to-end via sha1; we compare directly)."""

import itertools

import numpy as np
import pytest

from glusterfs_tpu.ops import codec, gf256

CONFIGS = [(2, 1), (4, 2), (8, 3), (8, 4), (16, 4)]

# pallas backends run via interpret mode on CPU elsewhere; here use the
# jax-lowered ones that work on any platform.  native requires a toolchain.
from glusterfs_tpu import native as _native

PARITY_BACKENDS = ["ref", "xla", "xla-xor"] + (
    ["native"] if _native.available() else [])


def _data(k, stripes=3, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, k * gf256.CHUNK_SIZE * stripes, dtype=np.uint8)


@pytest.mark.parametrize("k,r", CONFIGS)
def test_backend_parity(k, r):
    data = _data(k, seed=k * 31 + r)
    ref = codec.Codec(k, r, "ref")
    expect = ref.encode(data)
    for b in PARITY_BACKENDS[1:]:
        c = codec.Codec(k, r, b)
        assert np.array_equal(c.encode(data), expect), f"encode mismatch: {b}"


@pytest.mark.parametrize("k,r", [(4, 2), (8, 4)])
@pytest.mark.parametrize("backend", PARITY_BACKENDS)
def test_roundtrip_all_masks(k, r, backend):
    """Every choose(n, k) surviving-fragment mask reconstructs exactly
    (the decode-matrix-per-mask behavior of ec-method.c:200-245)."""
    data = _data(k, stripes=2, seed=7)
    c = codec.Codec(k, r, backend)
    frags = c.encode(data)
    masks = list(itertools.combinations(range(k + r), k))
    # exhaustive for 4+2 (15 masks); sampled for 8+4 (495)
    if len(masks) > 24:
        masks = masks[::21]
    for rows in masks:
        got = c.decode(frags[list(rows)], rows)
        assert np.array_equal(got, data), f"mask {rows} failed on {backend}"


def test_padded_roundtrip():
    rng = np.random.default_rng(3)
    c = codec.Codec(4, 2, "ref")
    for nbytes in (1, 511, 512, 2048, 2049, 10000):
        data = rng.integers(0, 256, nbytes, dtype=np.uint8)
        frags, orig = c.encode_padded(data)
        assert orig == nbytes
        assert frags.shape == (6, c.pad_length(nbytes) // 4)
        rows = [1, 3, 4, 5]
        got = c.decode_padded(frags[rows], rows, orig)
        assert np.array_equal(got, data)


def test_detect_and_validation():
    assert codec.detect("ref") == "ref"
    with pytest.raises(ValueError):
        codec.detect("avx512")
    b = codec.detect("auto")
    assert b in codec.BACKENDS
    c = codec.Codec(4, 2, "ref")
    with pytest.raises(ValueError):
        c.decode(np.zeros((4, 512), np.uint8), [0, 1, 2, 2])  # dup rows
    with pytest.raises(ValueError):
        c.decode(np.zeros((4, 512), np.uint8), [0, 1, 2, 9])  # out of range
    with pytest.raises(ValueError):
        codec.Codec(17, 2)


def test_backend_init_failure_is_loud(monkeypatch):
    """A backend that cannot initialize (the chip is held by another
    process) is never read as "no chip": ``auto`` takes the CPU ladder
    after ONE error line with the probe state ``error``; any backend
    that runs through jax refuses to resolve."""
    import jax

    from glusterfs_tpu.core import gflog

    def held():
        raise RuntimeError("Unable to initialize backend 'tpu': ABORTED")

    monkeypatch.setattr(jax, "devices", held)
    monkeypatch.setattr(codec, "_probe",
                        {"state": "unprobed", "devices": (), "error": ""})
    before = len([m for m in gflog.recent_messages(1000)
                  if "MSGID: 110040" in m])
    assert codec.detect("auto") in ("native", "xla")
    assert codec.detect("auto") in ("native", "xla")  # asked once
    st = codec.probe_state()
    assert st["state"] == "error" and "ABORTED" in st["error"]
    assert ({"state": "error"}, 1) in codec._probe_samples()
    for backend in ("pallas-xor", "xla", "mesh"):
        with pytest.raises(RuntimeError, match="ABORTED"):
            codec.detect(backend)
    assert codec.detect("ref") == "ref"
    after = [m for m in gflog.recent_messages(1000) if "MSGID: 110040" in m]
    assert len(after) == before + 1 and after[-1].startswith("ERROR")


def test_native_apply_bitmatrix_parity():
    from glusterfs_tpu import native

    if not native.available():
        pytest.skip("no native toolchain")
    rng = np.random.default_rng(11)
    abits = gf256.expand_bitmatrix(gf256.encode_matrix(4, 6))
    x = rng.integers(0, 256, (32, 256), dtype=np.uint8)
    got = native.apply_bitmatrix(abits, x)
    expect = np.zeros((48, 256), np.uint8)
    for i in range(48):
        for j in np.nonzero(abits[i])[0]:
            expect[i] ^= x[j]
    assert np.array_equal(got, expect)


def _backend_object(name, k, r):
    """The object ``name`` resolves to, as a Codec holds it (the one
    table ``codec._IMPLS``); the chip's kernels run interpreted on a
    host without one."""
    from tests.harness import have_tpu

    if name == "native" and not _native.available():
        pytest.skip("no native toolchain")
    if name == "pallas-xor" and not have_tpu():
        return codec._Pallas(k, r, interpret=True)
    return codec.Codec(k, r, name)._impl


@pytest.mark.parametrize("name", codec.BACKENDS)
def test_backend_object_has_the_four_operations(name):
    """Every name left in BACKENDS is one object with ``encode``,
    ``decode``, ``parity`` and ``reconstruct``, each byte-exact against
    the oracle on both codes."""
    k, r = 4, 2
    n = k + r
    impl = _backend_object(name, k, r)
    data = _data(k, stripes=5, seed=11)
    rows = [1, 3, 4, 5]  # data rows 0 and 2 lost
    for systematic in (False, True):
        frags = gf256.ref_encode(data, k, n, systematic=systematic)
        assert np.array_equal(impl.encode(data, systematic), frags)
        assert np.array_equal(
            impl.decode(frags[rows], rows, systematic),
            gf256.ref_decode(frags[rows], rows, k, systematic=systematic))
    assert np.array_equal(impl.parity(data), gf256.ref_parity(data, k, n))
    # ``frags`` is the systematic code now: its first k rows are the data
    for missing in ((1,), (0, 2)):
        rows = [j for j in range(n) if j not in missing][:k]
        assert np.array_equal(
            impl.reconstruct(frags[rows], rows, missing),
            frags[list(missing)]), missing


def test_removed_backend_name_is_refused_by_the_codec():
    with pytest.raises(ValueError) as e:
        codec.Codec(4, 2, "pallas-mxu")
    assert all(b in str(e.value) for b in codec.BACKENDS)


def test_removed_backend_name_is_refused_by_the_volume_option():
    from glusterfs_tpu.cluster.ec import DisperseLayer
    from glusterfs_tpu.core.options import OptionError, validate_options

    with pytest.raises(OptionError) as e:
        validate_options(DisperseLayer.OPTIONS,
                         {"cpu-extensions": "pallas-mxu"})
    assert all(b in str(e.value) for b in ("auto",) + codec.BACKENDS)
