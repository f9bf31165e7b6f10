"""disperse.ec-read-mask (ec.c:717-775 ec_assign_read_mask, applied
strictly at read dispatch like ec-inode-read.c:1375) and
disperse.parallel-writes (ec.c:284,868 + ec_is_range_conflict,
ec-common.c:185: non-conflicting writes dispatch concurrently inside
one eager window)."""

import asyncio

import numpy as np
import pytest

from glusterfs_tpu.api.glfs import SyncClient
from glusterfs_tpu.core.fops import FopError
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.utils.volspec import ec_volfile

K, R = 4, 2
N = K + R
STRIPE = K * 512


def _rand(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _settle(c, path):
    f = c.open(path)
    f.fsync()
    f.close()


def _mount(tmp_path, options=None):
    g = Graph.construct(ec_volfile(tmp_path, N, R, options=options or {}))
    c = SyncClient(g)
    c.mount()
    return c, g.top


def _readv_counts(ec):
    return [ec.children[i].stats["readv"].count
            if "readv" in ec.children[i].stats else 0 for i in range(N)]


# -- read-mask ---------------------------------------------------------


def test_read_mask_keeps_masked_bricks_out(tmp_path):
    c, ec = _mount(tmp_path, {"ec-read-mask": "0,1,2,3"})
    try:
        data = _rand(4 * STRIPE)
        c.write_file("/f", data)
        before = _readv_counts(ec)
        assert c.read_file("/f") == data
        after = _readv_counts(ec)
        assert after[4] == before[4] and after[5] == before[5], \
            "masked-out bricks served reads"
        assert sum(after) > sum(before)
    finally:
        c.close()


def test_read_mask_honored_in_degraded_read(tmp_path):
    """One masked-in brick down: reads come from the remaining masked
    ids, never from the masked-out brick even though it is up+clean."""
    c, ec = _mount(tmp_path, {"ec-read-mask": "0,1,2,3,4"})
    try:
        data = _rand(4 * STRIPE, seed=1)
        c.write_file("/g", data)
        _settle(c, "/g")  # close the write window (its cached
        # candidate set predates the degrade below)
        ec.up[1] = False  # degrade inside the mask
        before = _readv_counts(ec)
        assert c.read_file("/g") == data
        after = _readv_counts(ec)
        assert after[5] == before[5], "masked-out brick used for reads"
        assert after[1] == before[1]
    finally:
        c.close()


def test_read_mask_is_strict_like_reference(tmp_path):
    """fop->mask &= read_mask: if the masked set cannot supply K
    fragments the read fails rather than widening past the mask."""
    c, ec = _mount(tmp_path, {"ec-read-mask": "0,1,2,3"})
    try:
        data = _rand(2 * STRIPE, seed=2)
        c.write_file("/h", data)
        _settle(c, "/h")
        ec.up[3] = False  # only 3 masked candidates remain, K=4
        with pytest.raises(FopError):
            c.read_file("/h")
    finally:
        c.close()


def test_read_mask_never_fails_writes(tmp_path):
    """The mask is a read-tuning knob: a write's internal RMW reads
    ignore it (the reference applies it only at inode-read dispatch,
    ec-inode-read.c:1375) — a degraded masked set must not turn into
    write unavailability while >= K bricks are healthy."""
    c, ec = _mount(tmp_path, {"ec-read-mask": "0,1,2,3"})
    try:
        data = _rand(2 * STRIPE, seed=9)
        c.write_file("/w", data)
        ec.up[1] = False  # masked candidates drop below K
        f = c.open("/w")
        f.write(b"Z" * 100, 17)  # unaligned: needs an RMW read
        f.close()
    finally:
        c.close()
    exp = bytearray(data)
    exp[17:117] = b"Z" * 100
    c2, _ = _mount(tmp_path)  # unmasked view of the surviving bricks
    try:
        assert c2.read_file("/w") == bytes(exp)
    finally:
        c2.close()


def test_invalid_masks_log_and_clear(tmp_path):
    c, ec = _mount(tmp_path)
    try:
        for bad in ("0,1", "0,1,2,99", "0,1,x,3"):
            ec.reconfigure({"ec-read-mask": bad})
            assert ec._read_mask is None, bad
        ec.reconfigure({"ec-read-mask": "1,2,3,4"})
        assert ec._read_mask == frozenset({1, 2, 3, 4})
        ec.reconfigure({"ec-read-mask": ""})
        assert ec._read_mask is None
    finally:
        c.close()


# -- parallel-writes ---------------------------------------------------


def _spy_dispatch(ec, widen=0.05):
    """Count concurrently in-flight writev waves through _dispatch."""
    state = {"active": 0, "max": 0}
    orig = ec._dispatch

    async def spy(idxs, op, argfn, **meta):
        if op == "writev":
            state["active"] += 1
            state["max"] = max(state["max"], state["active"])
            await asyncio.sleep(widen)
        try:
            return await orig(idxs, op, argfn, **meta)
        finally:
            if op == "writev":
                state["active"] -= 1

    ec._dispatch = spy
    return state


def test_disjoint_writes_dispatch_concurrently(tmp_path):
    c, ec = _mount(tmp_path, {"eager-lock-timeout": 30})
    try:
        a = _rand(4 * STRIPE, seed=3)
        b = _rand(4 * STRIPE, seed=4)

        async def drive():
            f = await c._client.create("/p")
            await f.write(b"\0" * STRIPE, 0)  # window's solo first write
            state = _spy_dispatch(ec)
            await asyncio.gather(f.write(a, 0),
                                 f.write(b, 4 * STRIPE))
            await f.close()
            return state

        state = c._run(drive())
        assert state["max"] >= 2, "disjoint writes serialized"
        assert c.read_file("/p") == a + b
    finally:
        c.close()


def test_overlapping_writes_serialize(tmp_path):
    c, ec = _mount(tmp_path, {"eager-lock-timeout": 30})
    try:
        a = _rand(2 * STRIPE, seed=5)
        b = _rand(2 * STRIPE, seed=6)

        async def drive():
            f = await c._client.create("/q")
            await f.write(b"\0" * STRIPE, 0)
            state = _spy_dispatch(ec)
            # same aligned stripe range: must not interleave
            await asyncio.gather(f.write(a, 0), f.write(b, 0))
            await f.close()
            return state

        state = c._run(drive())
        assert state["max"] == 1, "overlapping writes ran concurrently"
        assert c.read_file("/q") in (a, b)
    finally:
        c.close()


def test_parallel_writes_off_serializes_everything(tmp_path):
    c, ec = _mount(tmp_path, {"parallel-writes": "off",
                              "eager-lock-timeout": 30})
    try:
        a = _rand(2 * STRIPE, seed=7)

        async def drive():
            f = await c._client.create("/r")
            await f.write(b"\0" * STRIPE, 0)
            state = _spy_dispatch(ec)
            await asyncio.gather(f.write(a, 0), f.write(a, 4 * STRIPE))
            await f.close()
            return state

        state = c._run(drive())
        assert state["max"] == 1
    finally:
        c.close()


def test_many_parallel_writers_integrity_and_size(tmp_path):
    """16 concurrent disjoint chunk writers through one fd: bytes land
    exactly, final size is the max end (the size-clobber case), and the
    settled file survives a fresh mount (post-op committed sanely)."""
    chunk = 2 * STRIPE
    parts = [_rand(chunk, seed=10 + i) for i in range(16)]
    c, ec = _mount(tmp_path, {"eager-lock-timeout": 0.05})
    try:
        async def drive():
            f = await c._client.create("/big")
            await f.write(parts[0], 0)  # solo first write lands pre-op
            await asyncio.gather(*(
                f.write(parts[i], i * chunk) for i in range(1, 16)))
            await f.close()

        c._run(drive())
        assert c.stat("/big").size == 16 * chunk
        assert c.read_file("/big") == b"".join(parts)
    finally:
        c.close()
    c2, _ = _mount(tmp_path)
    try:
        assert c2.read_file("/big") == b"".join(parts)
    finally:
        c2.close()
