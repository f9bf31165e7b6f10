"""Many small writers through distribute-over-disperse 2x(4+2), in
process on a jax backend (ISSUE 26): the flushes of the two batching
codecs say how full they were (``stripes`` / ``bucket_stripes`` on the
``codec.flush`` span, ``stripes`` / ``padded_stripes`` in the dump),
``cluster/dht`` says where it sent each data fop (``subvol`` on its
span, ``routed`` in its dump), and what landed on the bricks is the
reference encoding, readable with any two bricks of a group gone."""

import asyncio
import itertools
import os

import numpy as np
import pytest

from glusterfs_tpu.api.glfs import Client
from glusterfs_tpu.core import tracing
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.ops import gf256
from glusterfs_tpu.utils.volspec import ec_volfile

K, R, N = 4, 2, 6
STRIPE = K * 512
BLOCK = 4096
FILE = 64 * 1024
WRITES = 6  # per writer, one in flight


class _Recorder:
    """``tracing.ANNOTATE``'s contract in a test: made once per span
    with its metadata, ``set_metadata`` adds to it while it is open."""

    log: list = []

    def __init__(self, name, **meta):
        self.name, self.meta = name, meta

    @staticmethod
    def is_enabled():
        return True

    def set_metadata(self, **meta):
        self.meta.update(meta)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        _Recorder.log.append((self.name, self.meta))


@pytest.fixture
def recorder(monkeypatch):
    _Recorder.log = []
    monkeypatch.setattr(tracing, "ENABLED", True)
    yield _Recorder
    tracing.ANNOTATE = None  # the next codec on a jax backend sets jax's


@pytest.mark.parametrize("writers", [4, 16])
def test_small_writers_fill_flushes_on_both_codecs(tmp_path, recorder,
                                                   writers):
    g = Graph.construct(ec_volfile(tmp_path, N, R, groups=2, options={
        "cpu-extensions": "xla", "stripe-cache": "on",
        "stripe-cache-min-batch": 0, "systematic": "on"}))
    dht = g.top
    ecs = list(dht.children)
    # names that the hash sends to the two groups in turn
    fresh = (f"w{i}" for i in itertools.count())
    names = [next(n for n in fresh if dht.hashed_idx(n) == j % 2)
             for j in range(writers)]
    rng = np.random.default_rng(writers)
    model = [rng.integers(0, 256, FILE, dtype=np.uint8)
             for _ in range(writers)]
    offsets = [rng.permutation(FILE // BLOCK)[:WRITES] * BLOCK
               for _ in range(writers)]
    wrote = {"bytes": 0, "fops": 0}

    async def writer(c, j):
        f = await c.create("/" + names[j], os.O_RDWR | os.O_EXCL)
        assert await f.write(model[j].tobytes(), 0) == FILE
        for off in offsets[j]:
            data = rng.integers(0, 256, BLOCK, dtype=np.uint8)
            model[j][off:off + BLOCK] = data
            assert await f.write(data.tobytes(), int(off)) == BLOCK
        wrote["bytes"] += FILE + WRITES * BLOCK
        wrote["fops"] += 1 + WRITES
        await f.fsync()
        return f

    async def reader(f, j):
        for off in offsets[j]:
            got = await f.read(BLOCK, int(off))
            assert got == model[j][off:off + BLOCK].tobytes(), (j, off)

    async def run():
        c = Client(g)
        await c.mount()
        tracing.ANNOTATE = recorder
        try:
            files = await asyncio.gather(
                *(writer(c, j) for j in range(writers)))
            assert sum(dht.routed) == wrote["fops"]
            # any two bricks of a group stopped (every third pair, in
            # both groups at once): the same offsets read back what
            # was written
            for a, b in list(itertools.combinations(range(N), 2))[::3]:
                for ec in ecs:
                    ec.up[a] = ec.up[b] = False
                await asyncio.gather(*(reader(f, j)
                                       for j, f in enumerate(files)))
                for ec in ecs:
                    ec.up[a] = ec.up[b] = True
            for f in files:
                await f.close()
        finally:
            await c.unmount()

    asyncio.run(run())

    # what the flush spans say of themselves
    flushes = [m for name, m in recorder.log
               if name == "gftpu:codec.flush" and m["op"] == "encode"]
    assert flushes and all(
        0 < m["stripes"] <= m["bucket_stripes"] and
        m["stripes"] * STRIPE == m["bytes"] and
        m["bucket_stripes"] & (m["bucket_stripes"] - 1) == 0
        for m in flushes), flushes[:3]
    assert sum(m["stripes"] for m in flushes) * STRIPE == wrote["bytes"]
    assert sum(m["fops"] for m in flushes) == wrote["fops"]
    assert any(m["fops"] > 1 for m in flushes), "no flush held two fops"
    # and the sums an operator's statedump shows
    stats = [ec.codec.dump_stats() for ec in ecs]
    assert all(st["stripes"] > 0 for st in stats)
    decoded = [m for name, m in recorder.log
               if name == "gftpu:codec.flush" and m["op"] == "decode"]
    assert decoded and all(m["stripes"] <= m["bucket_stripes"]
                           for m in decoded)
    assert sum(st["stripes"] for st in stats) == \
        sum(m["stripes"] for m in flushes + decoded)
    assert sum(st["padded_stripes"] for st in stats) == \
        sum(m["bucket_stripes"] for m in flushes + decoded)
    assert all(st["padded_stripes"] >= st["stripes"] for st in stats)
    # a decode flush says what its one mask hands in and rebuilds: four
    # survivors, and the data rows that are not among them; every fop
    # of it is one staged read, which the layer's dump sums, and whose
    # wave called as many parity bricks as rows were missing
    assert all(m["rows_in"] == K and 1 <= m["rows_out"] <= R
               for m in decoded), decoded[:3]
    rebuilt = [ec.dump_private()["read_fanout"] for ec in ecs]
    assert sum(d["rows_rebuilt"] for d in rebuilt) == \
        sum(m["rows_out"] * m["fops"] for m in decoded)
    waves = [m for name, m in recorder.log
             if name == "gftpu:ec.fanout" and m["op"] == "readv"]
    assert waves and all(m["width"] == K for m in waves)
    assert sum(m["parity"] for m in waves) == \
        sum(d["rows_rebuilt"] for d in rebuilt)

    # cluster/dht: the dump's count per subvolume is the spans' count
    routed = dht.dump_private()["routed"]
    assert set(routed) == {ec.name for ec in ecs}
    named = [m["subvol"] for name, m in recorder.log
             if name in ("gftpu:cluster/distribute.writev",
                         "gftpu:cluster/distribute.readv")]
    assert {s: named.count(s) for s in routed} == routed
    assert sum(1 for name, m in recorder.log
               if name == "gftpu:cluster/distribute.writev") == wrote["fops"]
    assert all("subvol" not in m for name, m in recorder.log
               if name == "gftpu:cluster/distribute.fsync")

    # the bricks of its group hold the reference encoding of each file
    for j in range(writers):
        want = gf256.ref_encode(model[j], K, N, systematic=True)
        for i in range(N):
            with open(tmp_path / f"brick{j % 2 * N + i}" / names[j],
                      "rb") as f:
                assert f.read() == want[i].tobytes(), (names[j], i)
