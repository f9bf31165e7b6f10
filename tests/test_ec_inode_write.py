"""EC allocation-class fops: fallocate / discard / zerofill / seek —
the tests/basic/ec/ec-fallocate.t + seek coverage analog.  Reference:
ec-inode-write.c (ec_fallocate/ec_discard/ec_zerofill), ec-inode-read.c
(ec_seek).  Zero stripes encode to zero fragments (linear code), so
holes line up across user space and fragments."""

import asyncio
import os

import numpy as np
import pytest

from glusterfs_tpu.api.glfs import SyncClient
from glusterfs_tpu.core.fops import FopError
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.core.layer import Loc
from glusterfs_tpu.utils.volspec import ec_volfile
from tests.test_systematic import _hold_answer, _spy

K, R = 4, 2
N = K + R
STRIPE = K * 512


def _rand(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8)


@pytest.fixture(params=["off", "on"], ids=["reference", "systematic"])
def vol(tmp_path, request):
    """Both layouts: on the systematic one every wave of the window
    write path goes out in two parts around the codec (ISSUE 25)."""
    g = Graph.construct(ec_volfile(
        tmp_path, N, R, options={"systematic": request.param}))
    c = SyncClient(g)
    c.mount()
    yield c, g.top, tmp_path
    c.close()


def test_zerofill_interior(vol):
    c, ec, _ = vol
    data = _rand(4 * STRIPE, seed=1).tobytes()
    c.write_file("/z", data)
    f = c.open("/z")
    off, ln = STRIPE // 2, 2 * STRIPE  # partial head + tail stripes
    c._run(ec.zerofill(f.fd, off, ln))
    f.close()
    got = c.read_file("/z")
    assert got[:off] == data[:off]
    assert got[off:off + ln] == b"\0" * ln
    assert got[off + ln:] == data[off + ln:]
    assert c.stat("/z").size == 4 * STRIPE


def test_zerofill_extends(vol):
    c, ec, _ = vol
    c.write_file("/ze", b"abc")
    f = c.open("/ze")
    c._run(ec.zerofill(f.fd, 3, 2 * STRIPE))
    f.close()
    assert c.stat("/ze").size == 3 + 2 * STRIPE
    assert c.read_file("/ze") == b"abc" + b"\0" * (2 * STRIPE)


def test_discard_keeps_size(vol):
    c, ec, _ = vol
    data = _rand(2 * STRIPE, seed=2).tobytes()
    c.write_file("/d", data)
    f = c.open("/d")
    # range crosses EOF: zeroing is clamped, size must not grow
    c._run(ec.discard(f.fd, STRIPE, 5 * STRIPE))
    f.close()
    assert c.stat("/d").size == 2 * STRIPE
    got = c.read_file("/d")
    assert got[:STRIPE] == data[:STRIPE]
    assert got[STRIPE:] == b"\0" * STRIPE


def test_fallocate_extends_and_keep_size(vol):
    c, ec, _ = vol
    data = _rand(STRIPE, seed=3).tobytes()
    c.write_file("/fa", data)
    f = c.open("/fa")
    ia = c._run(ec.fallocate(f.fd, 0, 0, 3 * STRIPE))
    assert ia.size == 3 * STRIPE
    # KEEP_SIZE: allocation only, size unchanged
    ia = c._run(ec.fallocate(f.fd, 1, 0, 10 * STRIPE))
    assert ia.size == 3 * STRIPE
    f.close()
    got = c.read_file("/fa")
    assert got[:STRIPE] == data
    assert got[STRIPE:] == b"\0" * (2 * STRIPE)
    info = c._run(ec.heal_info(Loc("/fa")))
    assert info["bad"] == [] and not info["dirty"]


def test_seek_data_and_hole(vol):
    """Sparse layout engineered to the FS hole granularity (4096B per
    fragment = 8 stripes of user data): data [0..8s), hole [8s..64s),
    data [64s..72s)."""
    c, ec, _ = vol
    s = STRIPE
    head = _rand(8 * s, seed=4).tobytes()
    tail = _rand(8 * s, seed=5).tobytes()
    f = c.create("/sp")
    f.write(head, 0)
    f.write(tail, 64 * s)
    f.close()
    f = c.open("/sp")
    fd = f.fd
    assert c._run(ec.seek(fd, 0, "data")) == 0
    hole = c._run(ec.seek(fd, 0, "hole"))
    assert 8 * s <= hole <= 64 * s  # first hole (granularity-dependent)
    if hole < 64 * s:
        assert c._run(ec.seek(fd, hole, "data")) == 64 * s
    assert c._run(ec.seek(fd, 64 * s, "hole")) == 72 * s  # EOF hole
    with pytest.raises(FopError):
        c._run(ec.seek(fd, 72 * s, "data"))  # ENXIO past EOF
    f.close()


def test_discard_interior_frees_blocks(vol):
    """The stripe-aligned interior punches real fragment holes
    (FALLOC_FL_PUNCH_HOLE) instead of writing zeros: allocated blocks
    DROP."""
    c, ec, base = vol
    s = STRIPE
    data = _rand(32 * s, seed=6).tobytes()
    c.write_file("/ph", data)
    frag = base / "brick0" / "ph"
    blocks_before = frag.stat().st_blocks
    f = c.open("/ph")
    c._run(ec.discard(f.fd, 8 * s, 16 * s))  # aligned interior
    f.close()
    assert frag.stat().st_blocks < blocks_before, "no blocks freed"
    got = c.read_file("/ph")
    assert got[: 8 * s] == data[: 8 * s]
    assert got[8 * s: 24 * s] == b"\0" * (16 * s)
    assert got[24 * s:] == data[24 * s:]


def test_afr_fallocate_keep_size(tmp_path):
    """FALLOC_FL_KEEP_SIZE must not grow the replicas (libc fallocate
    honors the flag; posix_fallocate would not)."""
    from glusterfs_tpu.utils.volspec import brick_volumes

    chunks, tops = brick_volumes(tmp_path, 3)
    chunks.append("volume afr\n    type cluster/replicate\n"
                  f"    subvolumes {' '.join(tops)}\nend-volume\n")
    g = Graph.construct("\n".join(chunks))
    c = SyncClient(g)
    c.mount()
    try:
        afr = g.top
        c.write_file("/ks", b"B" * 4096)
        f = c.open("/ks")
        c._run(afr.fallocate(f.fd, 1, 0, 65536))
        f.close()
        assert c.stat("/ks").size == 4096
        for i in range(3):
            assert (tmp_path / f"brick{i}" / "ks").stat().st_size == 4096, i
    finally:
        c.close()


def test_afr_allocation_fops_replicate(tmp_path):
    """fallocate/discard/zerofill must hit EVERY replica with counters —
    the default first-child passthrough would silently diverge them."""
    from glusterfs_tpu.utils.volspec import brick_volumes

    chunks, tops = brick_volumes(tmp_path, 3)
    chunks.append("volume afr\n    type cluster/replicate\n"
                  f"    subvolumes {' '.join(tops)}\nend-volume\n")
    g = Graph.construct("\n".join(chunks))
    c = SyncClient(g)
    c.mount()
    try:
        afr = g.top
        c.write_file("/r", b"A" * 4096)
        f = c.open("/r")
        c._run(afr.zerofill(f.fd, 1024, 2048))
        f.close()
        want = b"A" * 1024 + b"\0" * 2048 + b"A" * 1024
        for i in range(3):
            assert (tmp_path / f"brick{i}" / "r").read_bytes() == want, i
        info = c._run(afr.heal_info(Loc("/r")))
        assert info["bad"] == [] and not info["dirty"]
    finally:
        c.close()


def test_unaligned_write_splits_after_its_rmw_read(tmp_path):
    """An EOF-crossing unaligned write on a systematic volume reads the
    stripes it overlaps first (full RMW: the parity-delta path does not
    take it), and then sends the whole buffer's wave in two parts."""
    g = Graph.construct(ec_volfile(tmp_path, N, R,
                                   options={"systematic": "on"}))
    c = SyncClient(g)
    c.mount()
    ec = g.top
    try:
        data = _rand(2 * STRIPE + 1024, seed=9).tobytes()
        c.write_file("/u", data)
        before = dict(ec.write_path)
        log = []
        _spy(ec, log, ops=("readv", "writev"))
        gate = asyncio.Event()
        gate.set()
        _hold_answer(ec, gate, delay=0.02)  # an answer that takes time
        patch = _rand(3000, seed=10).tobytes()
        f = c.open("/u")
        f.write(patch, 2 * STRIPE + 512)
        f.close()
        assert ec.write_path["rmw"] == before["rmw"] + 1
        assert ec.write_path["split"] == before["split"] + 1
        assert ec.write_path["delta"] == before["delta"]
        ops = [op for op, _i in log]
        assert "readv" in ops and ops.count("writev") == N
        assert ops.index("writev") > max(
            j for j, op in enumerate(ops) if op == "readv"), log
        assert [i for op, i in log if op == "writev"][:K] == list(range(K))
        exp = data[: 2 * STRIPE + 512] + patch
        assert c.read_file("/u") == exp
        assert c.stat("/u").size == len(exp)
    finally:
        c.close()
