"""disperse.systematic — the systematic generator (data fragments are
raw stripe chunks; gf256.systematic_matrix).  The reference's code is
non-systematic (ec-method.c:393-433: every fragment is a codeword, every
read decodes); the systematic form is this framework's tpu-first layout
for device-behind-a-link serving: healthy reads skip decode, encode
ships only parity off-device, degraded reads reconstruct only the
missing rows."""

import random

import numpy as np
import pytest

from glusterfs_tpu.api.glfs import SyncClient
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.ops import gf256, gf256_pallas
from glusterfs_tpu.ops.codec import Codec
from glusterfs_tpu.utils.volspec import ec_volfile
from tests.harness import have_tpu

K, R = 4, 2
N = K + R
STRIPE = K * 512


def _rand(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8)


# -- matrix ------------------------------------------------------------


@pytest.mark.parametrize("k,n", [(1, 2), (2, 3), (4, 6), (8, 12),
                                 (16, 20)])
def test_systematic_matrix_properties(k, n):
    m = np.asarray(gf256.systematic_matrix(k, n))
    assert np.array_equal(m[:k], np.eye(k, dtype=np.uint8))
    rnd = random.Random(k * n)
    for _ in range(8):
        rows = sorted(rnd.sample(range(n), k))
        gf256.decode_matrix(k, rows, systematic=True)  # raises if singular


def test_ref_systematic_round_trip_any_rows():
    data = _rand(5 * STRIPE)
    fr = gf256.ref_encode(data, K, N, systematic=True)
    s = data.size // STRIPE
    chunks = data.reshape(s, K, 512).transpose(1, 0, 2).reshape(K, -1)
    assert np.array_equal(fr[:K], chunks)  # data rows ARE the chunks
    rnd = random.Random(7)
    for _ in range(6):
        rows = sorted(rnd.sample(range(N), K))
        out = gf256.ref_decode(fr[rows], rows, K, systematic=True)
        assert np.array_equal(out, data), rows


def test_formats_are_incompatible():
    """Guard against silently mixing the two fragment formats."""
    data = _rand(2 * STRIPE, seed=1)
    sys_fr = gf256.ref_encode(data, K, N, systematic=True)
    ref_fr = gf256.ref_encode(data, K, N)
    assert not np.array_equal(sys_fr, ref_fr)


# -- codec backends ----------------------------------------------------


def _backends():
    out = ["ref"]
    try:
        from glusterfs_tpu import native

        if native.available():
            out.append("native")
    except Exception:
        pass
    out += ["xla", "xla-xor"]
    return out


@pytest.mark.parametrize("backend", _backends())
def test_codec_backends_byte_exact(backend):
    data = _rand(6 * STRIPE, seed=2)
    oracle = gf256.ref_encode(data, K, N, systematic=True)
    c = Codec(K, R, backend, systematic=True)
    fr = c.encode(data)
    assert np.array_equal(fr, oracle), backend
    rnd = random.Random(3)
    for _ in range(4):
        rows = sorted(rnd.sample(range(N), K))
        out = c.decode(fr[rows], rows)
        assert np.array_equal(out, data), (backend, rows)


def test_identity_decode_is_host_only():
    """All-data-rows decode must be pure assembly: byte-exact and never
    touching any math backend (we use ref and compare to raw chunks)."""
    data = _rand(3 * STRIPE, seed=4)
    c = Codec(K, R, "ref", systematic=True)
    fr = c.encode(data)
    out = c.decode(fr[: K], list(range(K)))
    assert np.array_equal(out, data)
    # shuffled survivor order too
    order = [2, 0, 3, 1]
    out = c.decode(fr[order], order)
    assert np.array_equal(out, data)


# -- pallas kernels (interpret; silicon variant below) -----------------


@pytest.mark.parametrize("k,r", [(4, 2), (8, 4), (16, 4)])
def test_pallas_parity_and_reconstruct_interpret(k, r):
    n = k + r
    data = _rand(3 * k * 512, seed=5 + k)
    full = gf256.ref_encode(data, k, n, systematic=True)
    par = gf256_pallas.parity(data, k, n, interpret=True)
    assert np.array_equal(par, full[k:])
    rnd = random.Random(6)
    for _ in range(3):
        rows = tuple(sorted(rnd.sample(range(n), k)))
        missing = tuple(j for j in range(k) if j not in rows)
        if not missing:
            continue
        rec = gf256_pallas.reconstruct(full[list(rows)], rows, missing,
                                       k, interpret=True)
        assert np.array_equal(rec, full[list(missing)]), rows


@pytest.mark.skipif(not have_tpu(), reason="needs a real TPU")
@pytest.mark.parametrize("k,r", [(4, 2), (16, 4)])
def test_pallas_systematic_on_silicon(k, r):
    n = k + r
    data = _rand(300 * k * 512, seed=9)
    full = gf256.ref_encode(data, k, n, systematic=True)
    assert np.array_equal(gf256_pallas.parity(data, k, n), full[k:])
    rows = tuple(range(1, k + 1))
    missing = (0,)
    rec = gf256_pallas.reconstruct(full[list(rows)], rows, missing, k)
    assert np.array_equal(rec, full[:1])


_HELD_CHILD = r"""
import json, sys, tempfile
sys.path.insert(0, {root!r})
from glusterfs_tpu.api.glfs import SyncClient
from glusterfs_tpu.core import gflog
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.ops import codec
from glusterfs_tpu.utils.volspec import ec_volfile

out = {{}}
g = Graph.construct(ec_volfile(tempfile.mkdtemp(), 6, 2,
                               options={{"systematic": "on"}}))
c = SyncClient(g)
c.mount()
c.write_file("/f", b"x" * 300000)
out["read_back"] = c.read_file("/f") == b"x" * 300000
out["auto_backend"] = g.top.codec.backend
c.unmount()
out["probe"] = codec.probe_state()
out["errors"] = [m for m in gflog.recent_messages() if m.startswith("ERROR")]
try:
    Graph.construct(ec_volfile(tempfile.mkdtemp(), 6, 2, options={{
        "systematic": "on", "cpu-extensions": "pallas-xor"}}))
    out["explicit"] = "mounted"
except RuntimeError as e:
    out["explicit"] = str(e)
print(json.dumps(out))
"""


@pytest.mark.skipif(not have_tpu(), reason="needs a real TPU")
def test_chip_held_elsewhere_is_loud_on_silicon():
    """This process holds the chip (``have_tpu()`` opened it).  A second
    process that asks for it is told no by libtpu: under ``auto`` its
    volume serves from ``native`` after ONE error line, with the probe
    state ``error``; asked for ``pallas-xor`` it does not mount."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run([sys.executable, "-c", _HELD_CHILD.format(root=root)],
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["read_back"] and out["auto_backend"] == "native", out
    assert out["probe"]["state"] == "error", out
    assert "Unable to initialize backend" in out["probe"]["error"], out
    assert len(out["errors"]) == 1 and "110040" in out["errors"][0], out
    assert "Unable to initialize backend" in out["explicit"], out


# -- volume-level ------------------------------------------------------


def _mount(tmp_path, options=None):
    g = Graph.construct(ec_volfile(
        tmp_path, N, R,
        options={"systematic": "on", **(options or {})}))
    c = SyncClient(g)
    c.mount()
    return c, g.top


def test_systematic_volume_round_trip_and_read_rows(tmp_path):
    """Healthy reads on a systematic volume come from the K data bricks
    only (no decode) and the bytes are exact."""
    c, ec = _mount(tmp_path)
    try:
        data = _rand(4 * STRIPE, seed=11).tobytes()
        c.write_file("/f", data)

        def counts():
            return [ec.children[i].stats["readv"].count
                    if "readv" in ec.children[i].stats else 0
                    for i in range(N)]

        before = counts()
        assert c.read_file("/f") == data
        after = counts()
        assert after[4] == before[4] and after[5] == before[5], \
            "parity bricks served a healthy systematic read"
    finally:
        c.close()


def test_systematic_degraded_read_and_unaligned_write(tmp_path):
    c, ec = _mount(tmp_path)
    try:
        data = _rand(4 * STRIPE, seed=12).tobytes()
        c.write_file("/g", data)
        ec.up[0] = False  # lose a data brick: reads must reconstruct
        assert c.read_file("/g") == data
        f = c.open("/g")
        f.write(b"Q" * 777, 100)  # unaligned RMW while degraded
        f.close()
        exp = bytearray(data)
        exp[100:877] = b"Q" * 777
        assert c.read_file("/g") == bytes(exp)
    finally:
        c.close()


def test_systematic_fragments_on_bricks_match_oracle(tmp_path):
    c, ec = _mount(tmp_path)
    try:
        data = _rand(2 * STRIPE, seed=13)
        c.write_file("/h", data.tobytes())
    finally:
        c.close()
    import os

    oracle = gf256.ref_encode(data, K, N, systematic=True)
    for i in range(N):
        frag = open(os.path.join(str(tmp_path), f"brick{i}", "h"),
                    "rb").read()
        assert frag == oracle[i].tobytes(), f"brick {i}"


def test_systematic_is_immutable_live(tmp_path):
    c, ec = _mount(tmp_path)
    try:
        ec.reconfigure({"systematic": "off"})
        assert ec.opts["systematic"] is True
        assert ec.codec.systematic is True
    finally:
        c.close()


def test_systematic_managed_volume_over_wire(tmp_path):
    """volume-create ... systematic through glusterd: the flag rides
    volinfo into the client volfile, fragments on the real bricks are
    the systematic oracle's bytes, and wire reads are exact."""
    import asyncio
    import glob
    import os

    from glusterfs_tpu.mgmt.glusterd import (Glusterd, MgmtClient,
                                             mount_volume)

    data = _rand(2 * STRIPE, seed=21)

    async def run():
        d = Glusterd(str(tmp_path / "gd"))
        await d.start()
        try:
            async with MgmtClient(d.host, d.port) as c:
                await c.call("volume-create", name="sv", vtype="disperse",
                             bricks=[{"path": str(tmp_path / f"b{i}")}
                                     for i in range(N)],
                             redundancy=R, systematic=1)
                await c.call("volume-start", name="sv")
            cl = await mount_volume(d.host, d.port, "sv")
            try:
                await cl.write_file("/x", data.tobytes())
                assert await cl.read_file("/x") == data.tobytes()
            finally:
                await cl.unmount()
        finally:
            await d.stop()

    asyncio.run(run())
    oracle = gf256.ref_encode(data, K, N, systematic=True)
    for i in range(N):
        frag = open(str(tmp_path / f"b{i}" / "x"), "rb").read()
        assert frag == oracle[i].tobytes(), f"brick {i}"


def test_systematic_heal_rebuilds_reference_bytes(tmp_path):
    """Kill a brick, overwrite, revive, heal: the healed fragment must
    be the systematic oracle's bytes for the new content."""
    import os

    c, ec = _mount(tmp_path)
    try:
        data1 = _rand(2 * STRIPE, seed=14)
        c.write_file("/z", data1.tobytes())
        ec.set_child_up(2, False)
        data2 = _rand(2 * STRIPE, seed=15)
        c.write_file("/z", data2.tobytes())
        ec.set_child_up(2, True)
        c._run(ec.heal_file("/z"))
        assert c.read_file("/z") == data2.tobytes()
    finally:
        c.close()
    oracle = gf256.ref_encode(data2, K, N, systematic=True)
    frag = open(os.path.join(str(tmp_path), "brick2", "z"), "rb").read()
    assert frag == oracle[2].tobytes()


# -- the write wave in two parts (ISSUE 25) ----------------------------


def _spy(ec, log, ops=("writev",)):
    """``(op, child)`` of every child call of ``ops`` as it arrives."""
    for i, ch in enumerate(ec.children):
        for op in ops:
            def make(real, _i=i, _op=op):
                async def call(*a, **kw):
                    log.append((_op, _i))
                    return await real(*a, **kw)
                return call

            setattr(ch, op, make(getattr(ch, op)))


def _hold_answer(ec, gate, delay=0.0):
    """The codec computes as it does (its flush is submitted, its
    launch told), and its answer then waits ``delay`` seconds and for
    ``gate``: the parity of a write, held."""
    import asyncio

    real = ec.codec.encode_async

    async def held(buf, origin="serve", launched=None):
        out = await real(buf, origin=origin, launched=launched)
        await asyncio.sleep(delay)
        await gate.wait()
        return out

    ec.codec.encode_async = held


def _brick_files_are_the_oracle(tmp_path, name, data):
    import os

    oracle = gf256.ref_encode(np.frombuffer(data, dtype=np.uint8), K, N,
                              systematic=True)
    for i in range(N):
        frag = open(os.path.join(str(tmp_path), f"brick{i}", name),
                    "rb").read()
        assert frag == oracle[i].tobytes(), f"brick {i}"


def test_data_fragments_go_out_while_parity_is_held(tmp_path):
    """With the codec's answer held, the k data children have each
    received their writev and the parity children none; released, all
    six have it and the brick files are the reference encoding.  The
    flush was handed to the batcher's pool before the first data call
    was made."""
    import asyncio

    c, ec = _mount(tmp_path)
    data = _rand(4 * STRIPE, seed=41).tobytes()
    log: list = []
    try:
        async def drive():
            gate = asyncio.Event()
            f = await c._client.create("/a")
            _spy(ec, log)
            submit = ec.codec._submit

            def submitted(*a):
                log.append(("flush", -1))
                return submit(*a)

            ec.codec._submit = submitted
            _hold_answer(ec, gate)
            w = asyncio.ensure_future(ec.writev(f.fd, data, 0))
            for _ in range(400):
                if len(log) > K:
                    break
                await asyncio.sleep(0.005)
            await asyncio.sleep(0.02)
            assert log == [("flush", -1)] + [("writev", i)
                                             for i in range(K)], log
            assert not w.done()
            gate.set()
            await w
            assert sorted(log[K + 1:]) == [("writev", i)
                                           for i in range(K, N)]
            await f.close()

        c._run(drive())
        assert ec.dump_private()["write_path"] == {
            "delta": 0, "rmw": 0, "split": 1, "delta_fallback": 0}
        assert c.read_file("/a") == data
    finally:
        c.close()
    _brick_files_are_the_oracle(tmp_path, "a", data)


@pytest.mark.parametrize("options", [
    {"systematic": "off"},
    {"systematic": "on", "stripe-cache": "off"},
], ids=["non-systematic", "stripe-cache-off"])
def test_one_wave_where_nothing_can_overlap(tmp_path, options):
    """Every fragment a codeword, or a codec that holds the loop: the
    six calls follow the encode as one wave, and nothing counts as
    split."""
    g = Graph.construct(ec_volfile(tmp_path, N, R, options=options))
    c = SyncClient(g)
    c.mount()
    ec = g.top
    data = _rand(4 * STRIPE, seed=42).tobytes()
    log: list = []
    try:
        async def drive():
            f = await c._client.create("/one")
            _spy(ec, log)
            encode = ec._codec_encode

            async def encoded(buf, origin=None):
                out = await encode(buf, origin)
                log.append(("encoded", -1))
                return out

            ec._codec_encode = encoded
            await ec.writev(f.fd, data, 0)
            await f.close()

        c._run(drive())
        assert log == [("encoded", -1)] + [("writev", i)
                                           for i in range(N)], log
        assert ec.dump_private()["write_path"]["split"] == 0
        assert c.read_file("/one") == data
    finally:
        c.close()


def test_parallel_writes_on_disjoint_ranges_both_split(tmp_path):
    """Two parallel-writes waves in flight at once, each in two parts:
    the parity of both is held until all eight data calls are in, and
    the bytes on the bricks are exact."""
    import asyncio

    c, ec = _mount(tmp_path)
    a, b, head = (_rand(2 * STRIPE, seed=s).tobytes() for s in (43, 44, 45))
    log: list = []
    try:
        async def drive():
            gate = asyncio.Event()
            _hold_answer(ec, gate, delay=0.01)  # an answer takes time
            f = await c._client.create("/pw")
            gate.set()
            await ec.writev(f.fd, head, 0)  # the pre-op has landed
            gate.clear()
            _spy(ec, log)
            ws = [asyncio.ensure_future(ec.writev(f.fd, a, 2 * STRIPE)),
                  asyncio.ensure_future(ec.writev(f.fd, b, 6 * STRIPE))]
            for _ in range(400):
                if len(log) >= 2 * K:
                    break
                await asyncio.sleep(0.005)
            await asyncio.sleep(0.02)
            st = ec._eager[f.fd.gfid]
            assert sorted(log) == sorted([("writev", i) for i in range(K)]
                                         * 2), log
            assert st.inflight == 2 and len(st.ranges) == 2
            gate.set()
            await asyncio.gather(*ws)
            assert st.good == set(range(N)) and st.delta == 3
            await f.close()

        c._run(drive())
        assert ec.dump_private()["write_path"]["split"] == 3
        want = head + a + b"\0" * (2 * STRIPE) + b
        assert c.read_file("/pw") == want
    finally:
        c.close()
    _brick_files_are_the_oracle(tmp_path, "pw", want)
