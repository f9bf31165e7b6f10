"""Parity-delta sub-stripe writes (ISSUE 10) — the EC write plane's
linear-code delta update: a sub-stripe write on a healthy systematic
volume ships only the overwritten data-fragment bytes plus m parity
deltas applied by the brick-side ``xorv`` fop, skipping the reference's
full read-modify-write (ec-inode-write.c:2141 analog).  Pins:

* the acceptance fop-count pin — touched-data writev + R parity xorv,
  ZERO readv on untouched data bricks, and the
  ``gftpu_ec_delta_writes_total`` family increments;
* the property test — random unaligned write sequences (interleaved
  parallel batches included) through delta-on vs delta-off stacks give
  byte-identical files AND byte-identical fragments + trusted.ec.*
  counters on every brick;
* the fallback matrix — degraded, non-systematic, EOF-crossing and
  zerofill-edge writes keep the RMW path; a live-downgraded brick
  (EOPNOTSUPP xorv) parks the layer on RMW with no divergence;
* the xorv hazard pins — posix read-xor-write semantics (double-apply
  self-cancels), journal batching, write-class / never-retried, and
  the SETVOLUME capability gate;
* the write-behind satellite — pressure drains cut at stripe
  boundaries so streamed writes hit the aligned path.
"""

import asyncio
import errno
import gc
import json
import os

import numpy as np
import pytest

from glusterfs_tpu.api.glfs import Client, SyncClient
from glusterfs_tpu.core.fops import Fop, FopError, WRITE_FOPS
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.core.layer import Loc
from glusterfs_tpu.core.metrics import REGISTRY
from glusterfs_tpu.ops import gf256
from glusterfs_tpu.utils.volspec import ec_volfile

K, R = 4, 2
N = K + R
STRIPE = K * 512


def _rand(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8)


def _mount(tmp_path, delta="on", systematic="on", options=None):
    g = Graph.construct(ec_volfile(
        str(tmp_path), N, R,
        options={"systematic": systematic, "delta-writes": delta,
                 **(options or {})}))
    c = SyncClient(g)
    c.mount()
    return c, g.top


def _counts(ec, op):
    return [ch.stats[op].count if op in ch.stats else 0
            for ch in ec.children]


# -- the acceptance pin ------------------------------------------------


def test_sub_stripe_write_fop_counts_and_family(tmp_path):
    """A healthy systematic 4+2 sub-stripe write provably skips the
    k-fragment decode: touched data bricks see one readv + one writev,
    parity bricks see one xorv each, untouched data bricks see NOTHING
    — and the registry family increments."""
    c, ec = _mount(tmp_path)
    try:
        data = _rand(4 * STRIPE, seed=1).tobytes()
        c.write_file("/f", data)

        def fam():
            # every test's disperse layer is named "disp" and the
            # registry holds layers weakly: free an earlier test's
            # before reading, or it can stand in for the live one
            gc.collect()
            snap = REGISTRY.snapshot()
            return {s[0]["layer"]: s[1]
                    for s in snap["gftpu_ec_delta_writes_total"]["samples"]}

        before = {op: _counts(ec, op) for op in ("readv", "writev",
                                                 "xorv")}
        fam_before = fam().get(ec.name, 0)
        f = c.open("/f")
        # 700 bytes at 1000: chunks 1-3 of stripe 0 — data brick 0 and
        # no other stripe are touched
        f.write(b"Q" * 700, 1000)
        f.close()
        d = {op: [a - b for a, b in zip(_counts(ec, op), before[op])]
             for op in ("readv", "writev", "xorv")}
        assert d["readv"] == [0, 1, 1, 1, 0, 0], d
        assert d["writev"] == [0, 1, 1, 1, 0, 0], d
        assert d["xorv"] == [0, 0, 0, 0, 1, 1], d
        assert ec.write_path["delta"] == 1
        assert ec.write_path["rmw"] == 0
        assert fam().get(ec.name, 0) == fam_before + 1
        assert ec.delta_saved["read"] > 0
        assert ec.delta_saved["write"] > 0
        exp = bytearray(data)
        exp[1000:1700] = b"Q" * 700
        assert c.read_file("/f") == bytes(exp)
    finally:
        c.close()


def test_delta_fragments_match_oracle(tmp_path):
    """The delta wave lands EXACTLY the systematic codeword on every
    brick (the linearity claim, byte-for-byte)."""
    c, ec = _mount(tmp_path)
    try:
        data = _rand(2 * STRIPE, seed=2)
        c.write_file("/f", data.tobytes())
        f = c.open("/f")
        f.write(b"Z" * 1234, 333)
        f.close()
        assert ec.write_path["delta"] == 1
    finally:
        c.close()
    exp = data.copy()
    exp[333:333 + 1234] = np.frombuffer(b"Z" * 1234, dtype=np.uint8)
    oracle = gf256.ref_encode(exp, K, N, systematic=True)
    for i in range(N):
        frag = open(os.path.join(str(tmp_path), f"brick{i}", "f"),
                    "rb").read()
        assert frag == oracle[i].tobytes(), f"brick {i}"


@pytest.mark.parametrize("seed", [11, 12])
@pytest.mark.parametrize("k, r", [(4, 2), (8, 4), (16, 4)])
def test_delta_sequences_match_the_plain_reference(tmp_path, k, r, seed):
    """A seeded sequence of overwrites inside the file, none of them
    stripe-aligned at both ends (inside one chunk, across chunks, half
    a stripe, across a stripe boundary, across several), through a
    served disperse volume of each supported width: every one takes
    the delta wave and none falls back, and afterwards every brick's
    fragment file is the plain reference's encoding of the model
    (``benchmarks/harness/reference.py``, which shares no arithmetic
    with the program; ``gf256.ref_encode`` agrees with it) and the door
    reads the model back."""
    from benchmarks.harness import reference

    n, stripe = k + r, k * 512
    g = Graph.construct(ec_volfile(
        str(tmp_path), n, r, options={"systematic": "on",
                                      "delta-writes": "on"}))
    c = SyncClient(g)
    c.mount()
    ec = g.top
    rng = np.random.default_rng([seed, k, r])
    size = 6 * stripe
    model = rng.integers(0, 256, size, dtype=np.uint8)
    shapes = [(1, 511),                      # inside one chunk
              (513, 3 * 512),                # across chunks
              (stripe // 2, stripe // 2),    # a database's page at 16+4
              (stripe - 700, stripe - 1),    # across a stripe boundary
              (2 * stripe + 1, 3 * stripe)]  # across several
    try:
        c.write_file("/f", model.tobytes())
        f = c.open("/f")
        count = 0
        for _round in range(4):
            for lo, hi in shapes:
                ln = int(rng.integers(lo, hi + 1))
                off = int(rng.integers(0, size - ln + 1))
                if off % stripe == 0 and (off + ln) % stripe == 0:
                    off += 1 if off + ln < size else -1
                data = rng.integers(0, 256, ln, dtype=np.uint8)
                f.write(data.tobytes(), off)
                model[off:off + ln] = data
                count += 1
        f.close()
        assert ec.write_path["delta"] == count, ec.write_path
        assert ec.write_path["rmw"] == 0
        assert ec.write_path["delta_fallback"] == 0
        assert c.read_file("/f") == model.tobytes()
    finally:
        c.close()
    want = reference.encode(model, k, n)
    assert np.array_equal(want, gf256.ref_encode(model, k, n,
                                                 systematic=True))
    for i in range(n):
        frag = open(os.path.join(str(tmp_path), f"brick{i}", "f"),
                    "rb").read()
        assert frag == want[i].tobytes(), f"brick {i} of {k}+{r}"


def test_delta_wave_spans_and_fallback_count(tmp_path):
    """The wave is one ``ec.delta_write`` span under the write; its
    children are ``ec.delta_read`` (over the old bytes' fan-out),
    ``ec.codec_wait``, the mixed wave's ``ec.fanout`` and, where the
    write opens its window, the pre-op's ``ec.xattrop``; both phases are in ``dump_private()["phases"]``.  A
    wave that bails is counted in ``write_path["delta_fallback"]`` and
    as ``cause="delta_fallback"`` of the registry's RMW family."""
    from glusterfs_tpu.core import tracing

    c, ec = _mount(tmp_path)
    try:
        c.write_file("/s", _rand(2 * STRIPE, seed=9).tobytes())
        f = c.open("/s")
        tracing.SPANS.clear()
        f.write(b"S" * 700, 1000)
        spans = [s for s in tracing.SPANS if s[2] == ec.name]
        wave = [s for s in spans if s[3] == "ec.delta_write"]
        assert len(wave) == 1, [s[3] for s in spans]
        root = next(s for s in spans if s[3] == "writev")
        assert wave[0][8] == root[7]  # hangs under the write
        kids = {s[3]: s for s in spans if s[8] == wave[0][7]}
        # (the pre-op's ec.xattrop is a fourth where the write opens
        # its window; here the layout's window is still held)
        assert set(kids) - {"ec.xattrop"} == {
            "ec.delta_read", "ec.codec_wait", "ec.fanout"}
        reads = [s for s in spans if s[8] == kids["ec.delta_read"][7]]
        assert [s[3] for s in reads] == ["ec.fanout"]
        assert not [s for s in spans if s[3] == "delta-write"]
        phases = ec.dump_private()["phases"]
        assert phases["ec.delta_write"]["count"] == 1
        assert phases["ec.delta_read"]["count"] == 1
        assert ec.dump_private()["write_path"]["delta_fallback"] == 0

        async def refuse(*a, **kw):
            raise FopError(errno.EOPNOTSUPP, "no xorv here")

        ec.children[5].xorv = refuse
        f.write(b"T" * 700, 1000)
        f.close()
        path = ec.dump_private()["write_path"]
        assert (path["delta"], path["rmw"], path["delta_fallback"]) == \
            (1, 1, 1)
        gc.collect()
        fam = REGISTRY.snapshot()["gftpu_ec_rmw_writes_total"]["samples"]
        mine = {s[0]["cause"]: s[1] for s in fam
                if s[0]["layer"] == ec.name}
        assert mine == {"ineligible": 0, "delta_fallback": 1}
    finally:
        c.close()


def test_group_db_workload_graph_answers_after_the_wave(tmp_path):
    """A managed volume with the twelve keys of ``group db-workload``
    set: the client graph volgen builds has no write-behind, io-cache,
    read-ahead, quick-read, md-cache or readdir-ahead, keeps
    open-behind and gains client-side io-threads; a sub-stripe write
    through it takes the delta wave, and its answer at the door comes
    after ``cluster/ec``'s (nothing above acknowledges it early)."""
    from glusterfs_tpu.core.layer import walk
    from glusterfs_tpu.mgmt.glusterd import (Glusterd, MgmtClient,
                                             mount_volume)

    # the twelve keys as the deployment's configuration file has them
    # (beside its two pins, which are cluster.* and disperse.*)
    with open(os.path.join(os.path.dirname(__file__), os.pardir,
                           "benchmarks", "configs",
                           "ec-16p4-db-tpu.json")) as f:
        group = {key: value for key, value in json.load(f)["options"].items()
                 if key.startswith(("performance.", "server.", "client."))}
    assert len(group) == 12
    data = _rand(4 * STRIPE, seed=41).tobytes()

    async def run():
        d = Glusterd(str(tmp_path / "gd"))
        await d.start()
        try:
            async with MgmtClient(d.host, d.port) as c:
                await c.call("volume-create", name="db",
                             vtype="disperse", redundancy=2,
                             bricks=[{"path": str(tmp_path / f"b{i}")}
                                     for i in range(6)])
                for key, value in group.items():
                    res = await c.call("volume-set", name="db", key=key,
                                       value=value)
                    assert res["ok"], (key, res)
                await c.call("volume-start", name="db")
                info = await c.call("volume-info", name="db")
                assert group.items() <= \
                    info["db"]["options"].items()
            cl = await mount_volume(d.host, d.port, "db")
            try:
                layers = list(walk(cl.graph.top))
                types = [layer.type_name for layer in layers]
                assert not {"performance/write-behind",
                            "performance/io-cache",
                            "performance/read-ahead",
                            "performance/quick-read",
                            "performance/md-cache",
                            "performance/readdir-ahead"} & set(types)
                assert {"performance/open-behind",
                        "performance/io-threads"} <= set(types)
                # io-threads stands above cluster/ec, open-behind
                # between them
                assert types.index("performance/io-threads") < \
                    types.index("performance/open-behind") < \
                    types.index("cluster/disperse")
                ec = next(l for l in layers
                          if l.type_name == "cluster/disperse")
                iot = next(l for l in layers
                           if l.type_name == "performance/io-threads")
                client = next(l for l in layers
                              if l.type_name == "protocol/client")
                assert client.opts["event-threads"] == 4
                await cl.write_file("/x", data)
                order = []
                real = ec.writev

                async def writev(*args, **kwargs):
                    try:
                        return await real(*args, **kwargs)
                    finally:
                        order.append("cluster/ec answered")

                ec.writev = writev
                f = await cl.open("/x")
                before = iot.executed[1]
                await f.write(b"Q" * 700, 1000)
                order.append("the door answered")
                assert order == ["cluster/ec answered",
                                 "the door answered"]
                assert iot.executed[1] == before + 1  # through its gate
                assert ec.write_path["delta"] == 1, ec.write_path
                assert ec.write_path["rmw"] == 0
                await f.close()
                exp = bytearray(data)
                exp[1000:1700] = b"Q" * 700
                assert bytes(await cl.read_file("/x")) == bytes(exp)
            finally:
                await cl.unmount()
        finally:
            await d.stop()

    asyncio.run(run())


# -- the property test -------------------------------------------------


def _gen_ops(seed, size, n_ops=24):
    """Deterministic mixed write sequence: unaligned interior writes,
    aligned writes, EOF-extending writes, and parallel batches over
    DISJOINT stripe ranges (order-independent, so both stacks converge
    to the same bytes)."""
    rng = np.random.default_rng(seed)
    ops = []
    for i in range(n_ops):
        kind = rng.integers(0, 10)
        if kind < 6:  # unaligned interior
            off = int(rng.integers(1, size - 9000))
            ln = int(rng.integers(1, 8000))
            ops.append(("w", off, ln))
        elif kind < 7:  # stripe-aligned
            off = int(rng.integers(0, (size - 2 * STRIPE) // STRIPE)) * STRIPE
            ops.append(("w", int(off), STRIPE))
        elif kind < 8:  # EOF-crossing extend
            ops.append(("w", size - int(rng.integers(1, 500)),
                        int(rng.integers(1, 3000))))
        else:  # parallel batch over disjoint aligned spans
            batch = []
            for b in range(3):
                span = 4 * STRIPE
                off = b * (size // 3) + int(rng.integers(1, STRIPE))
                ln = int(rng.integers(1, 2000))
                batch.append((off, ln))
            ops.append(("p", batch))
    return ops


async def _apply_ops(base, delta_on, ops, size, seed=99):
    rng = np.random.default_rng(seed)
    init = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
    c = Client(Graph.construct(ec_volfile(
        base, N, R, options={"systematic": "on",
                             "delta-writes": "on" if delta_on
                             else "off"})))
    await c.mount()
    try:
        ec = c.graph.top
        await c.write_file("/f", init)
        f = await c.open("/f")
        payload = rng.integers(0, 256, 16384, dtype=np.uint8).tobytes()
        for op in ops:
            if op[0] == "w":
                _t, off, ln = op
                await f.write(payload[:ln], off)
            else:
                await asyncio.gather(*(f.write(payload[:ln], off)
                                       for off, ln in op[1]))
        await f.close()
        data = bytes(await c.read_file("/f"))
        xattrs = {}
        for i, ch in enumerate(ec.children):
            x = await ch.getxattr(Loc("/f"), None)
            xattrs[i] = {k: v for k, v in x.items()
                         if k.startswith("trusted.ec.")}
        return data, xattrs, dict(ec.write_path)
    finally:
        await c.unmount()


def test_property_delta_vs_rmw_stacks(tmp_path):
    """Random write sequences through delta-on vs delta-off stacks:
    byte-identical files, byte-identical FRAGMENTS, and identical
    trusted.ec.{version,size,dirty} on every brick."""
    for seed in (5, 6):
        size = 8 * STRIPE
        ops = _gen_ops(seed, size)
        base_on = str(tmp_path / f"on{seed}")
        base_off = str(tmp_path / f"off{seed}")
        data_on, xa_on, wp_on = asyncio.run(
            _apply_ops(base_on, True, ops, size))
        data_off, xa_off, wp_off = asyncio.run(
            _apply_ops(base_off, False, ops, size))
        assert data_on == data_off, f"seed {seed}: file bytes diverged"
        assert xa_on == xa_off, f"seed {seed}: xattr counters diverged"
        assert wp_on["delta"] > 0, "delta stack never took the path"
        assert wp_off["delta"] == 0, "delta-off stack took the path"
        # fragments byte-identical on disk
        for i in range(N):
            a = open(os.path.join(base_on, f"brick{i}", "f"),
                     "rb").read()
            b = open(os.path.join(base_off, f"brick{i}", "f"),
                     "rb").read()
            assert a == b, f"seed {seed}: brick {i} fragment diverged"


# -- fallback matrix ---------------------------------------------------


def test_degraded_falls_back_to_rmw(tmp_path):
    c, ec = _mount(tmp_path)
    try:
        data = _rand(4 * STRIPE, seed=3).tobytes()
        c.write_file("/g", data)
        ec.set_child_up(0, False)
        f = c.open("/g")
        f.write(b"D" * 700, 1000)
        f.close()
        assert ec.write_path["delta"] == 0
        assert ec.write_path["rmw"] == 1
        exp = bytearray(data)
        exp[1000:1700] = b"D" * 700
        assert c.read_file("/g") == bytes(exp)
        ec.set_child_up(0, True)
    finally:
        c.close()


def test_non_systematic_never_delta(tmp_path):
    c, ec = _mount(tmp_path, systematic="off")
    try:
        c.write_file("/h", _rand(2 * STRIPE, seed=4).tobytes())
        f = c.open("/h")
        f.write(b"x" * 100, 50)
        f.close()
        assert ec.write_path["delta"] == 0
        assert ec.write_path["rmw"] == 1
        assert _counts(ec, "xorv") == [0] * N
    finally:
        c.close()


def test_eof_crossing_falls_back(tmp_path):
    c, ec = _mount(tmp_path)
    try:
        data = _rand(STRIPE, seed=5).tobytes()
        c.write_file("/e", data)
        f = c.open("/e")
        f.write(b"y" * 1000, STRIPE - 100)  # extends past true size
        f.close()
        assert ec.write_path["delta"] == 0
        assert c.stat("/e").size == STRIPE + 900
        assert c.read_file("/e") == data[:STRIPE - 100] + b"y" * 1000
    finally:
        c.close()


def test_delta_writes_off_by_key(tmp_path):
    c, ec = _mount(tmp_path, delta="off")
    try:
        c.write_file("/k", _rand(2 * STRIPE, seed=6).tobytes())
        f = c.open("/k")
        f.write(b"k" * 600, 700)
        f.close()
        assert ec.write_path["delta"] == 0
        assert ec.write_path["rmw"] == 1
    finally:
        c.close()


def test_zerofill_edges_keep_rmw(tmp_path):
    """Allocation-class edges stay on the proven RMW shape (the
    fallback matrix's zerofill row)."""
    c, ec = _mount(tmp_path)
    try:
        data = _rand(4 * STRIPE, seed=7).tobytes()
        c.write_file("/z", data)
        f = c.open("/z")
        c._run(ec.zerofill(f.fd, STRIPE // 2, STRIPE))
        f.close()
        assert ec.write_path["delta"] == 0
        exp = bytearray(data)
        exp[STRIPE // 2: STRIPE // 2 + STRIPE] = b"\0" * STRIPE
        assert c.read_file("/z") == bytes(exp)
    finally:
        c.close()


def test_live_downgrade_eopnotsupp_parks_layer(tmp_path):
    """A parity brick answering EOPNOTSUPP to xorv (live-downgraded
    peer) converts the write to full RMW in the SAME window with no
    divergence, and parks the layer on RMW for later writes."""
    c, ec = _mount(tmp_path)
    try:
        data = _rand(2 * STRIPE, seed=8).tobytes()
        c.write_file("/d", data)

        async def refuse(*a, **kw):
            raise FopError(errno.EOPNOTSUPP, "no xorv here")

        ec.children[4].xorv = refuse  # instance shadow on one parity
        f = c.open("/d")
        f.write(b"W" * 500, 600)
        f.close()
        assert ec._xorv_ok is False
        assert ec.write_path["delta"] == 0
        assert ec.write_path["rmw"] == 1
        # nothing diverged: the redo rewrote every fragment
        info = c._run(ec.heal_info(Loc("/d")))
        assert info["bad"] == [] and not info["dirty"]
        exp = bytearray(data)
        exp[600:1100] = b"W" * 500
        assert c.read_file("/d") == bytes(exp)
        # later writes skip the delta attempt entirely
        f = c.open("/d")
        f.write(b"V" * 500, 600)
        f.close()
        assert ec.write_path["rmw"] == 2
        exp[600:1100] = b"V" * 500
        # the operator toggling the key re-arms the probe
        ec.reconfigure({"delta-writes": "on", "systematic": "on",
                        "redundancy": R})
        assert ec._xorv_ok is True
    finally:
        c.close()
    oracle = gf256.ref_encode(np.frombuffer(bytes(exp), dtype=np.uint8),
                              K, N, systematic=True)
    for i in range(N):
        frag = open(os.path.join(str(tmp_path), f"brick{i}", "d"),
                    "rb").read()
        assert frag == oracle[i].tobytes(), f"brick {i}"


# -- xorv fop pins ------------------------------------------------------


def test_posix_xorv_semantics(tmp_path):
    """Read-xor-write at an offset: applies a delta in place, a
    DOUBLE-apply self-cancels (the no-blind-retry hazard made
    visible), and past-EOF bytes XOR against zeros."""
    vol = (f"volume posix\n    type storage/posix\n"
           f"    option directory {tmp_path}/b\nend-volume\n")
    c = SyncClient(Graph.construct(vol))
    c.mount()
    try:
        posix = c.graph.top
        c.write_file("/f", bytes(range(64)))
        f = c.open("/f")
        delta = bytes(0x55 for _ in range(16))
        c._run(posix.xorv(f.fd, delta, 8))
        got = c.read_file("/f")
        exp = bytearray(range(64))
        for i in range(16):
            exp[8 + i] ^= 0x55
        assert got == bytes(exp)
        # double-apply self-cancels — exactly why xorv must never be
        # blindly retried
        c._run(posix.xorv(f.fd, delta, 8))
        assert c.read_file("/f") == bytes(range(64))
        # past EOF: 0 ⊕ d = d (a delta on a sparse tail degenerates
        # to a plain write)
        c._run(posix.xorv(f.fd, b"\xaa\xbb", 100))
        got = c.read_file("/f")
        assert got[100:102] == b"\xaa\xbb"
        assert got[64:100] == b"\0" * 36
        f.close()
    finally:
        c.close()


def test_posix_xorv_journal_batched(tmp_path):
    """The pre-xattrop marker's sidecar append coalesces with the xorv
    into ONE journal write (the compound journal_batch machinery)."""
    vol = (f"volume posix\n    type storage/posix\n"
           f"    option directory {tmp_path}/b\nend-volume\n")
    c = SyncClient(Graph.construct(vol))
    c.mount()
    try:
        posix = c.graph.top
        c.write_file("/f", b"\0" * 1024)
        f = c.open("/f")
        writes = []
        orig = os.write

        def counting_write(fd, buf):
            writes.append(len(buf))
            return orig(fd, buf)

        import glusterfs_tpu.storage.posix as posix_mod

        posix_mod.os.write = counting_write
        try:
            c._run(posix.xorv(
                f.fd, b"\x11" * 64, 0,
                {"pre-xattrop": {"trusted.ec.dirty":
                                 b"\0\0\0\0\0\0\0\x01" + b"\0" * 8}}))
        finally:
            posix_mod.os.write = orig
        # one coalesced journal append for the whole op (the data path
        # uses pwrite, not write)
        assert len(writes) == 1, writes
        f.close()
    finally:
        c.close()


def test_xorv_class_pins():
    """xorv is write-class (EC/AFR accounting, read-only rejection,
    barrier gating) and NEVER in the idempotent-retry allowlist."""
    from glusterfs_tpu.protocol.client import ClientLayer

    assert Fop.XORV in WRITE_FOPS
    assert "xorv" not in ClientLayer._IDEMPOTENT_FOPS
    assert "xorv" not in ClientLayer._LOCK_FOPS


def test_client_capability_gate(tmp_path):
    """A connected client whose peer did not advertise xorv fails the
    fop EOPNOTSUPP locally — zero round trips against a pre-12 brick."""
    from glusterfs_tpu.core.layer import FdObj
    from glusterfs_tpu.protocol.client import ClientLayer

    cl = ClientLayer("c0", {"remote-host": "127.0.0.1",
                            "remote-port": 1,
                            "remote-subvolume": "x"})
    cl.connected = True  # pretend: handshake done, no xorv advertised
    rt_before = cl.rpc_roundtrips
    with pytest.raises(FopError) as ei:
        asyncio.run(cl.xorv(FdObj(b"\0" * 16, anonymous=True),
                            b"\x01", 0))
    assert ei.value.err == errno.EOPNOTSUPP
    assert cl.rpc_roundtrips == rt_before  # nothing hit the wire


def test_read_only_rejects_xorv(tmp_path):
    """WRITE_FOPS membership is live: features/read-only refuses it."""
    vol = (f"volume posix\n    type storage/posix\n"
           f"    option directory {tmp_path}/b\nend-volume\n"
           f"volume ro\n    type features/read-only\n"
           f"    subvolumes posix\nend-volume\n")
    c = SyncClient(Graph.construct(vol))
    c.mount()
    try:
        from glusterfs_tpu.core.layer import FdObj

        with pytest.raises(FopError) as ei:
            c._run(c.graph.top.xorv(
                FdObj(b"\0" * 16, anonymous=True), b"\x01", 0))
        assert ei.value.err == errno.EROFS
    finally:
        c.close()


# -- write-behind satellite --------------------------------------------


def test_wb_stripe_aligned_cut_points(tmp_path):
    """Streamed sub-stripe chunks below a stripe-size window: every
    PRESSURE drain the child sees ENDS on a stripe boundary (and,
    for this aligned-start stream, starts on one too — an
    unaligned-start stream keeps its one intrinsic head partial);
    the final close drains the sub-stripe tail."""
    vol = (f"volume posix\n    type storage/posix\n"
           f"    option directory {tmp_path}/b\nend-volume\n"
           f"volume wb\n    type performance/write-behind\n"
           f"    option window-size 4096\n"
           f"    option stripe-size {STRIPE}\n"
           f"    subvolumes posix\nend-volume\n")
    c = SyncClient(Graph.construct(vol))
    c.mount()
    try:
        posix = c.graph.by_name["posix"]
        writes = []
        orig = posix.writev

        async def recording(fd, data, offset, xdata=None):
            writes.append((int(offset), len(data)))
            return await orig(fd, data, offset, xdata)

        posix.writev = recording
        f = c.create("/f")
        # stream 3000-byte chunks (the gateway chunked-PUT shape):
        # window 4096 forces pressure drains mid-stream
        for i in range(4):
            f.write(b"c" * 3000, i * 3000)
        pressure = list(writes)
        f.close()  # release drains the tail fully
        assert pressure, "window never hit pressure"
        for off, ln in pressure:
            assert off % STRIPE == 0 and ln % STRIPE == 0, \
                (pressure, "unaligned pressure drain")
        assert c.read_file("/f") == b"c" * 12000
    finally:
        c.close()


def test_wb_stripe_cut_points_unit(tmp_path):
    """Unit-level pin on the cut machinery: a partial drain emits only
    whole stripes and retains the tail; an all-sub-stripe window still
    flushes fully (bounded window invariant)."""
    from glusterfs_tpu.performance.write_behind import WriteBehindLayer

    class Rec:
        def __init__(self):
            self.writes = []
            self.type_name = "rec"
            self.name = "rec"
            self.children = []
            self.parents = []

        async def writev(self, fd, data, offset, xdata=None):
            self.writes.append((offset, len(data)))
            return None

    rec = Rec()
    wb = WriteBehindLayer("wb", {"stripe-size": STRIPE},
                          children=[rec])

    from glusterfs_tpu.core.layer import FdObj

    async def run():
        fd = FdObj(b"\0" * 16)
        ctx = wb._ctx(fd)
        wb._absorb(ctx, b"a" * (2 * STRIPE + 300), 0)
        await wb._send(fd, ctx, wb._cut(ctx, partial=True))
        assert rec.writes == [(0, 2 * STRIPE)], rec.writes
        assert ctx.chunks == [(2 * STRIPE, bytearray(b"a" * 300))]
        assert ctx.bytes == 300
        # extend the retained tail and force a FULL drain
        wb._absorb(ctx, b"b" * 100, 2 * STRIPE + 300)
        await wb._drain(fd, ctx)
        assert rec.writes[-1] == (2 * STRIPE, 400)
        assert ctx.chunks == []
        # all-sub-stripe window: partial drain must still flush
        wb._absorb(ctx, b"c" * 100, 0)
        await wb._send(fd, ctx, wb._cut(ctx, partial=True))
        assert rec.writes[-1] == (0, 100)
        assert ctx.chunks == []
        assert wb.window_bytes == 0

    asyncio.run(run())


def test_volgen_wires_wb_stripe_size():
    """A disperse client graph carries the EC stripe into
    write-behind's cut points (and the delta-writes key maps)."""
    from glusterfs_tpu.mgmt import volgen

    volinfo = {
        "name": "dv", "type": "disperse", "redundancy": 2,
        "bricks": [{"name": f"dv-brick-{i}", "host": "h", "index": i,
                    "path": f"/b{i}"} for i in range(6)],
        "options": {},
    }
    vf = volgen.build_client_volfile(volinfo)
    assert "option stripe-size 2048" in vf
    assert volgen.OPTION_MAP["cluster.delta-writes"] == \
        ("cluster/disperse", "delta-writes")
    assert volgen.OPTION_MIN_OPVERSION["cluster.delta-writes"] == 12


# -- mgmt satellite -----------------------------------------------------


def test_changelog_graph_disables_delta():
    """A changelog-armed (geo-rep) disperse graph keeps RMW: gsyncd's
    one-Active-worker-per-group election assumes every brick journals
    the same logical ops, which a delta wave's untouched data bricks
    would break.  An explicit operator key still wins."""
    from glusterfs_tpu.mgmt import volgen

    volinfo = {
        "name": "gv", "type": "disperse", "redundancy": 2,
        "bricks": [{"name": f"gv-brick-{i}", "host": "h", "index": i,
                    "path": f"/b{i}"} for i in range(6)],
        "options": {"changelog.changelog": "on"},
    }
    vf = volgen.build_client_volfile(volinfo)
    assert "option delta-writes off" in vf
    volinfo["options"]["cluster.delta-writes"] = "on"
    vf = volgen.build_client_volfile(volinfo)
    assert "option delta-writes on" in vf
    # xorv journals as a data op wherever it does land
    from glusterfs_tpu.features.changelog import D_FOPS

    assert Fop.XORV in D_FOPS


def test_mesh_codec_on_systematic_volume_gated_by_opversion(tmp_path):
    """The mesh-codec-vs-systematic exclusion is LIFTED at cluster
    op-version >= 14 (the mesh tier's parity-rows-only systematic
    encode, ISSUE 12): volume set accepts the key on a systematic
    volume now — and still refuses while any member would be pre-14
    (pinned by forcing the stored op-version down)."""
    from glusterfs_tpu.mgmt.glusterd import Glusterd, MgmtClient

    async def run():
        d = Glusterd(str(tmp_path / "gd"))
        await d.start()
        try:
            async with MgmtClient(d.host, d.port) as c:
                await c.call("volume-create", name="sv",
                             vtype="disperse", redundancy=2,
                             bricks=[{"path": str(tmp_path / f"b{i}")}
                                     for i in range(6)])
                res = await c.call("volume-set", name="sv",
                                   key="cluster.mesh-codec",
                                   value="on")
                assert res["ok"]
            # a pre-14 member keeps the old refusal (its BatchingCodec
            # has no systematic mesh tier): MgmtError rides the wire
            # as FopError(EINVAL)
            d.op_version = 13
            async with MgmtClient(d.host, d.port) as c:
                with pytest.raises(OSError, match="op-version >= 14"):
                    await c.call("volume-set", name="sv",
                                 key="cluster.mesh-codec", value="on")
        finally:
            await d.stop()

    asyncio.run(run())


def test_opversion_floor_for_delta_writes():
    # the delta plane shipped at 12; later rounds may raise the build's
    # op-version but must never lower it below the xorv capability
    import glusterfs_tpu

    assert glusterfs_tpu.OP_VERSION >= 12


def test_delta_over_wire_managed(tmp_path):
    """End to end over real TCP: a managed volume (systematic by
    default now) serves an unaligned write through the delta path —
    xorv crosses the wire under the SETVOLUME capability — and the
    file reads back exact."""
    from glusterfs_tpu.core.layer import walk
    from glusterfs_tpu.mgmt.glusterd import (Glusterd, MgmtClient,
                                             mount_volume)

    data = _rand(4 * STRIPE, seed=31).tobytes()

    async def run():
        d = Glusterd(str(tmp_path / "gd"))
        await d.start()
        try:
            async with MgmtClient(d.host, d.port) as c:
                await c.call("volume-create", name="dw",
                             vtype="disperse", redundancy=2,
                             bricks=[{"path": str(tmp_path / f"b{i}")}
                                     for i in range(6)])
                await c.call("volume-start", name="dw")
            cl = await mount_volume(d.host, d.port, "dw")
            try:
                ec = next(l for l in walk(cl.graph.top)
                          if l.type_name == "cluster/disperse")
                assert ec.opts["systematic"] is True  # the new default
                await cl.write_file("/x", data)
                f = await cl.open("/x")
                await f.write(b"Q" * 700, 1000)
                await f.close()
                assert ec.write_path["delta"] == 1, ec.write_path
                exp = bytearray(data)
                exp[1000:1700] = b"Q" * 700
                assert bytes(await cl.read_file("/x")) == bytes(exp)
            finally:
                await cl.unmount()
        finally:
            await d.stop()

    asyncio.run(run())


def test_volume_create_systematic_default(tmp_path):
    """New disperse volumes default to the systematic layout at
    cluster op-version >= 12; the explicit opt-out key holds; replicate
    volumes are untouched."""
    from glusterfs_tpu.mgmt.glusterd import Glusterd, MgmtClient

    async def run():
        d = Glusterd(str(tmp_path / "gd"))
        await d.start()
        try:
            async with MgmtClient(d.host, d.port) as c:
                await c.call("volume-create", name="dflt",
                             vtype="disperse", redundancy=2,
                             bricks=[{"path": str(tmp_path / f"a{i}")}
                                     for i in range(6)])
                await c.call("volume-create", name="optout",
                             vtype="disperse", redundancy=2,
                             systematic=0,
                             bricks=[{"path": str(tmp_path / f"b{i}")}
                                     for i in range(6)])
                info = await c.call("volume-info", name="dflt")
                assert info["dflt"].get("systematic") == 1
                info = await c.call("volume-info", name="optout")
                assert not info["optout"].get("systematic")
        finally:
            await d.stop()

    asyncio.run(run())
