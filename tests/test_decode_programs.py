"""Per-mask compiled decode programs: the CSE'd transposed XOR programs
(gf256.build_xor_program over the inverted Vandermonde bit-matrices) and
the shared compiled-program LRU (gf256.DECODE_PROGRAMS /
RECONSTRUCT_PROGRAMS) every backend decodes through — the compiled-one-
level-further analog of the reference's inverted-matrix LRU
(ec-method.c:200-245).

Byte-parity is asserted against the ``ref`` oracle for every geometry on
the bench sweep and a sampled set of surviving-fragment masks, across
the program-consuming backends (NumPy program walk, native
gf_decode_prog, XLA xor unroll, Pallas fused interpret), plus the
systematic ``reconstruct`` partial decode with 1 and 2 missing data
rows (3 and 4 at the wide geometries: what a server down costs an 8+4
volume), and LRU eviction/recompile behavior.
"""

import itertools

import numpy as np
import pytest

from glusterfs_tpu import native
from glusterfs_tpu.ops import gf256

# the BASELINE redundancy sweep (chip_smoke.py runs the same four)
GEOMETRIES = [(4, 2), (8, 3), (8, 4), (16, 4)]


def _masks(k: int, n: int, limit: int = 4) -> list[tuple[int, ...]]:
    """Deterministic mask sample: worst-case data loss (first fragments
    gone), healthy-data mask, an interleaved mask, plus pseudorandom
    picks — stable across runs so failures reproduce."""
    picks = {tuple(range(n - k, n)), tuple(range(k)),
             tuple(sorted({(2 * i) % n for i in range(n)}))}
    picks = {m for m in picks if len(m) == k}
    rng = np.random.default_rng(k * 131 + n)
    while len(picks) < limit:
        picks.add(tuple(sorted(
            rng.choice(n, size=k, replace=False).tolist())))
    return sorted(picks)[:limit]


def _data(k: int, stripes: int = 2, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, k * gf256.CHUNK_SIZE * stripes,
                        dtype=np.uint8)


# ---------------------------------------------------------------------------
# program construction invariants + NumPy program-walk oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k,r", GEOMETRIES)
def test_decode_program_matches_bitmatrix(k, r):
    """The CSE'd program computes exactly y = bbits @ x (mod 2), with
    dense destination ids and strictly fewer word-XORs than the naive
    per-row chains it replaces."""
    n = k + r
    x = _data(k, seed=k + n).reshape(-1, k * 8, gf256.WORD_SIZE)
    for rows in _masks(k, n):
        prog = gf256.decode_program(k, rows)
        bbits = gf256.decode_bits_cached(k, rows)
        assert prog.n_inputs == k * 8 and len(prog.outs) == k * 8
        for i, (dst, a, b) in enumerate(prog.ops):
            assert dst == prog.n_inputs + i  # dense dst invariant
            assert a < dst and b < dst  # straight-line: no forward refs
        naive = int(bbits.sum()) - bbits.shape[0]
        assert prog.xor_count < naive, \
            f"CSE gained nothing at {k}+{r} mask {rows}"
        got = gf256.run_xor_program(prog, x)
        expect = gf256._xor_matmul_planes(bbits, x)
        assert np.array_equal(got, expect), f"mask {rows}"


def _run_scheduled(code: np.ndarray, n_slots: int, n_rows: int,
                   x: np.ndarray) -> np.ndarray:
    """NumPy interpreter for schedule_program's instruction stream (the
    oracle for the native walker): x (S, C, 64) -> (S, rows, 64)."""
    s = x.shape[0]
    t = np.zeros((n_slots, s, gf256.WORD_SIZE), np.uint8)
    out = np.zeros((s, n_rows, gf256.WORD_SIZE), np.uint8)
    stream = code.tolist()
    i = 0
    while i < len(stream):
        op = stream[i]
        if op == 0:
            _, d, a, b = stream[i:i + 4]
            t[d] = t[a] ^ t[b]
            i += 4
        elif op == 1:
            row, nv = stream[i + 1], stream[i + 2]
            for v in stream[i + 3:i + 3 + nv]:
                out[:, row] ^= t[v]
            i += 3 + nv
        elif op == 2:
            sl, f, p = stream[i + 1:i + 4]
            t[sl] = x[:, f * 8 + p, :]
            i += 4
        elif op == 3:
            src, nv = stream[i + 1], stream[i + 2]
            for sl in stream[i + 3:i + 3 + nv]:
                t[sl] ^= t[src]
            i += 3 + nv
        else:
            assert op == 4, f"bad opcode {op}"
            src, nv = stream[i + 1], stream[i + 2]
            for sl in stream[i + 3:i + 3 + nv]:
                t[sl] = t[src]
            i += 3 + nv
    return out


@pytest.mark.parametrize("k,r", GEOMETRIES)
def test_schedule_program_matches_program(k, r):
    """The register-allocated (transposed, slot-reusing) schedule the
    native kernel walks computes the same function as the program, with
    a slab strictly smaller than one-slot-per-var."""
    n = k + r
    x = _data(k, seed=23 * k + r).reshape(-1, k * 8, gf256.WORD_SIZE)
    for rows in _masks(k, n, limit=2):
        prog = gf256.decode_program(k, rows)
        code, n_slots = gf256.schedule_program(prog)
        assert n_slots < prog.n_inputs + len(prog.ops), "no slot reuse"
        got = _run_scheduled(code, n_slots, len(prog.outs), x)
        assert np.array_equal(got, gf256.run_xor_program(prog, x)), \
            f"mask {rows}"


# ---------------------------------------------------------------------------
# backend parity vs the ref oracle
# ---------------------------------------------------------------------------


@pytest.mark.skipif(not native.available(), reason="no native toolchain")
@pytest.mark.parametrize("k,r", GEOMETRIES)
def test_native_program_decode_parity(k, r):
    n = k + r
    data = _data(k, seed=3 * k + r)
    frags = gf256.ref_encode(data, k, n)
    for rows in _masks(k, n):
        surv = np.ascontiguousarray(frags[list(rows)])
        prog = gf256.decode_program(k, rows)
        got = native.decode_program(surv, k, prog)
        assert np.array_equal(got, data), f"mask {rows}"
        assert np.array_equal(got, gf256.ref_decode(surv, list(rows), k))


@pytest.mark.skipif(not native.available(), reason="no native toolchain")
def test_native_program_rejects_mismatched_program():
    frags = np.zeros((4, gf256.CHUNK_SIZE), dtype=np.uint8)
    prog8 = gf256.decode_program(8, tuple(range(8)))
    with pytest.raises(ValueError):
        native.decode_program(frags, 4, prog8)


@pytest.mark.parametrize("k,r", GEOMETRIES)
def test_xla_xor_program_decode_parity(k, r):
    """The xla 'xor' formulation unrolls the per-mask compiled program
    into its trace; two masks per geometry keep CPU compile time sane."""
    from glusterfs_tpu.ops import gf256_xla

    n = k + r
    data = _data(k, seed=5 * k + r)
    frags = gf256.ref_encode(data, k, n)
    for rows in _masks(k, n, limit=2):
        got = gf256_xla.decode(frags[list(rows)], rows, k,
                               formulation="xor")
        assert np.array_equal(got, data), f"mask {rows}"


@pytest.mark.parametrize("k,r", [(4, 2), (8, 3)])
def test_pallas_fused_program_decode_parity(k, r):
    """Pallas fused decode (interpret mode; silicon covered by chip_smoke.py) on
    sampled masks beyond the first-r-lost one the existing suite uses."""
    from glusterfs_tpu.ops import gf256_pallas

    n = k + r
    data = _data(k, seed=7 * k + r)
    frags = gf256.ref_encode(data, k, n)
    for rows in _masks(k, n, limit=2):
        got = gf256_pallas.decode(frags[list(rows)], rows, k,
                                  interpret=True)
        assert np.array_equal(got, data), f"mask {rows}"


# ---------------------------------------------------------------------------
# systematic reconstruct: programs for ONLY the missing data rows
# ---------------------------------------------------------------------------


def _sys_case(k, r, n_missing, seed):
    """(data, frags, rows, missing): survivors after losing the first
    ``n_missing`` data fragments of a systematic encode."""
    n = k + r
    data = _data(k, seed=seed)
    frags = gf256.ref_encode(data, k, n, systematic=True)
    missing = tuple(range(n_missing))
    rows = tuple(x for x in range(n) if x not in missing)[:k]
    return data, frags, rows, missing


@pytest.mark.parametrize("k,r", GEOMETRIES)
@pytest.mark.parametrize("n_missing", [1, 2])
def test_reconstruct_program_emits_only_missing_rows(k, r, n_missing):
    data, frags, rows, missing = _sys_case(k, r, n_missing, 11 * k + r)
    prog = gf256.reconstruct_program(k, rows, missing)
    # a partial decode: program outputs cover ONLY the wanted rows
    assert len(prog.outs) == len(missing) * 8
    x = gf256.frags_to_planes(frags[list(rows)], k)
    got = gf256.run_xor_program(prog, x)
    expect = gf256._xor_matmul_planes(
        gf256.reconstruct_bits_cached(k, rows, missing), x)
    assert np.array_equal(got, expect)
    # and the reconstructed planes are the original data rows' chunks
    s = x.shape[0]
    full = data.reshape(s, k, gf256.CHUNK_SIZE)
    for i, j in enumerate(missing):
        rec = got[:, i * 8:(i + 1) * 8, :].reshape(s, gf256.CHUNK_SIZE)
        assert np.array_equal(rec, full[:, j, :]), f"row {j}"


@pytest.mark.parametrize("k,r", [(4, 2), (8, 4)])
@pytest.mark.parametrize("n_missing", [1, 2])
def test_pallas_reconstruct_partial_decode(k, r, n_missing):
    from glusterfs_tpu.ops import gf256_pallas

    data, frags, rows, missing = _sys_case(k, r, n_missing, 13 * k + r)
    rec = gf256_pallas.reconstruct(frags[list(rows)], rows, missing, k,
                                   interpret=True)
    assert rec.shape[0] == len(missing)
    s = data.size // (k * gf256.CHUNK_SIZE)
    full = data.reshape(s, k, gf256.CHUNK_SIZE)
    for i, j in enumerate(missing):
        assert np.array_equal(
            rec[i], np.ascontiguousarray(full[:, j, :]).reshape(-1)), \
            f"row {j}"


@pytest.mark.parametrize("k,r", [(8, 4), (16, 4)])
@pytest.mark.parametrize("n_missing", [3, 4])
@pytest.mark.parametrize("via", ["program", "pallas"])
def test_reconstruct_of_three_and_four_rows(k, r, n_missing, via):
    """The wide rebuilds: redundancy-many (and one fewer) data rows
    lost from the middle of the stripe, parity rows standing in.  At
    (8, 4) with four missing the survivors are (0, 1, 2, 3, 8, 9, 10,
    11): the one mask of ``ec-8p4-tpu`` with a server down, whose
    program the benchmark's cells launch."""
    from glusterfs_tpu.ops import gf256_pallas

    n = k + r
    data = _data(k, stripes=3, seed=19 * k + r + n_missing)
    frags = gf256.ref_encode(data, k, n, systematic=True)
    missing = tuple(range(k // 2, k // 2 + n_missing))
    rows = tuple(x for x in range(n) if x not in missing)[:k]
    assert sum(1 for x in rows if x >= k) == n_missing
    s = data.size // (k * gf256.CHUNK_SIZE)
    want = np.ascontiguousarray(
        data.reshape(s, k, gf256.CHUNK_SIZE).transpose(1, 0, 2)
    ).reshape(k, -1)[list(missing)]
    assert np.array_equal(want, frags[list(missing)])
    if via == "pallas":
        rec = gf256_pallas.reconstruct(frags[list(rows)], rows, missing, k,
                                       interpret=True)
        assert np.array_equal(rec, want)
        return
    prog = gf256.reconstruct_program(k, rows, missing)
    assert prog.n_inputs == k * 8 and len(prog.outs) == n_missing * 8
    got = gf256.run_xor_program(
        prog, gf256.frags_to_planes(frags[list(rows)], k))
    for i in range(n_missing):
        assert np.array_equal(
            got[:, i * 8:(i + 1) * 8, :].reshape(-1), want[i]), missing[i]


@pytest.mark.skipif(not native.available(), reason="no native toolchain")
@pytest.mark.parametrize("k,r", [(4, 2), (16, 4)])
@pytest.mark.parametrize("n_missing", [1, 2])
def test_codec_systematic_degraded_read(k, r, n_missing):
    """Codec-level systematic decode with missing data rows, through the
    per-mask program LRU, for every CPU-ladder backend."""
    from glusterfs_tpu.ops import codec

    data, frags, rows, missing = _sys_case(k, r, n_missing, 17 * k + r)
    for backend in ("ref", "native", "xla", "xla-xor"):
        c = codec.Codec(k, r, backend, systematic=True)
        got = c.decode(frags[list(rows)], rows)
        assert np.array_equal(got, data), backend


# ---------------------------------------------------------------------------
# the per-mask compiled-program LRU
# ---------------------------------------------------------------------------


def test_decode_program_lru_hit_and_identity():
    k, r = 4, 2
    rows = (1, 3, 4, 5)
    before = gf256.DECODE_PROGRAMS.cache_info()
    p1 = gf256.decode_program(k, rows)
    p2 = gf256.decode_program(k, [1, 3, 4, 5])  # list vs tuple: same key
    assert p1 is p2, "second request must hit the cache"
    after = gf256.DECODE_PROGRAMS.cache_info()
    assert after["hits"] >= before["hits"] + 1


def test_decode_program_lru_eviction_recompiles():
    """Shrink the LRU, push a mask out, re-request it: the recompiled
    program is identical to the evicted one and still byte-exact."""
    k, r = 4, 2
    n = k + r
    lru = gf256.DECODE_PROGRAMS
    saved_max = lru.maxsize
    lru.cache_clear()
    lru.maxsize = 3
    try:
        victim = (2, 3, 4, 5)
        first = gf256.decode_program(k, victim)
        # three younger masks evict the victim (maxsize=3)
        for rows in ((0, 1, 2, 3), (0, 2, 4, 5), (1, 2, 3, 4)):
            gf256.decode_program(k, rows)
        assert (k, victim, False) not in lru, "victim should be evicted"
        assert lru.cache_info()["evictions"] >= 1
        misses = lru.cache_info()["misses"]
        again = gf256.decode_program(k, victim)
        assert lru.cache_info()["misses"] == misses + 1, "must recompile"
        assert again == first, "recompile must be deterministic"
        # and the recompiled program still decodes byte-exactly
        data = _data(k, seed=99)
        frags = gf256.ref_encode(data, k, n)
        x = gf256.frags_to_planes(frags[list(victim)], k)
        got = gf256.run_xor_program(again, x)
        assert np.array_equal(
            got.reshape(-1)[:data.size],
            gf256.ref_decode(frags[list(victim)], list(victim), k)
            .reshape(x.shape[0], k * 8, gf256.WORD_SIZE).reshape(-1))
    finally:
        lru.maxsize = saved_max
        lru.cache_clear()


def test_program_lru_thread_safety():
    """Concurrent first requests for the same and distinct masks race
    the build-outside-the-lock path; every result must be correct."""
    import threading

    lru = gf256.ProgramLRU(gf256._build_decode_program, maxsize=8)
    masks = [(0, 1, 2, 3), (1, 2, 3, 4), (2, 3, 4, 5), (0, 2, 3, 5)]
    results: dict = {}
    errors: list = []

    def worker(i):
        try:
            rows = masks[i % len(masks)]
            results[(i, rows)] = lru(4, rows, False)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    for (_i, rows), prog in results.items():
        assert prog == gf256.build_xor_program(
            gf256.decode_bits_cached(4, rows)), rows
