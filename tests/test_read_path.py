"""Zero-copy read pipeline (ISSUE 3): scatter-gather wire replies,
read-ahead chain fusion + adaptive windows, EC fan-out fast path,
open-behind anon-fd hygiene, client strict-locks, and the volgen keys
that arm it all."""

import asyncio
import errno
import os

import pytest

from glusterfs_tpu.api.glfs import Client
from glusterfs_tpu.core.fops import FopError
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.core.layer import FdObj, Layer, Loc, register, walk
from glusterfs_tpu.daemon import serve_brick
from glusterfs_tpu.rpc import wire

from .harness import BRICK_VOLFILE

CLIENT_VOLFILE = """
volume c0
    type protocol/client
    option remote-host 127.0.0.1
    option remote-port {port}
    option remote-subvolume {sub}
{opts}end-volume
"""


async def _wait_connected(layer, timeout=10.0):
    for _ in range(int(timeout / 0.05)):
        if layer.connected:
            return True
        await asyncio.sleep(0.05)
    return layer.connected


# -- wire layer --------------------------------------------------------


def test_sgbuf_semantics():
    sg = wire.SGBuf([b"abc", memoryview(b"defg"), b""])
    assert len(sg) == 7
    assert bytes(sg) == b"abcdefg"
    assert sg.tobytes() == b"abcdefg"
    assert sg == b"abcdefg"
    assert sg == wire.SGBuf([b"abcd", b"efg"])
    assert not sg == b"abcdefX"
    assert wire.as_single_buffer(sg) == b"abcdefg"
    one = wire.SGBuf([b"solo"])
    assert wire.as_single_buffer(one) == b"solo"
    # single-segment as_single_buffer stays a view, not a copy
    assert isinstance(wire.as_single_buffer(one), memoryview)


def test_sg_vector_rides_one_frame_as_blobs():
    """An sg dict's segments ride the frame as separate trailing blob
    buffers (one gathered writelines), and decode back to views into
    the received frame — no join on either side."""
    segs = [b"A" * 8000, b"B" * 5000]
    payload = {wire.SG_KEY: [wire.Blob(s) for s in segs]}
    before = dict(wire.blob_stats)
    frames = wire.pack_frames(7, wire.MT_REPLY, payload)
    assert len(frames) == 3  # prefix + one buffer per segment
    assert wire.blob_stats["tx_blobs"] == before["tx_blobs"] + 2
    xid, mtype, out = wire.unpack(b"".join(frames)[4:])
    assert xid == 7
    got = out[wire.SG_KEY]
    assert [bytes(g) for g in got] == segs
    assert all(isinstance(g, memoryview) for g in got)


# -- wire end-to-end: server sg replies --------------------------------


@register("test/sg-source")
class SgSourceLayer(Layer):
    """Serves readv as a 2-segment SGBuf (the brick-side stand-in for
    any multi-buffer reply source)."""

    async def readv(self, fd, size, offset, xdata=None):
        data = await self.children[0].readv(fd, size, offset, xdata)
        data = bytes(data)
        mid = len(data) // 2
        return wire.SGBuf([data[:mid], data[mid:]])


SG_BRICK = BRICK_VOLFILE + """
volume sgsrc
    type test/sg-source
    subvolumes locks
end-volume
"""


def _sg_client(port, sub="sgsrc", sg="on"):
    g = Graph.construct(CLIENT_VOLFILE.format(
        port=port, sub=sub,
        opts=f"    option sg-replies {sg}\n"))
    return g


def test_wire_sg_readv_reply(tmp_path):
    """A brick-side multi-buffer readv reply crosses the wire as a blob
    vector and lands client-side as an SGBuf of frame views; a client
    that didn't advertise sg gets plain joined bytes."""
    async def run():
        server = await serve_brick(SG_BRICK.format(dir=tmp_path / "b"))
        payload = bytes(range(256)) * 64
        g = _sg_client(server.port)
        c = Client(g)
        await c.mount()
        cl = g.top
        assert await _wait_connected(cl)
        await c.write_file("/f", payload)
        f = await c.open("/f", os.O_RDONLY)
        data = await c.graph.top.readv(f.fd, 1 << 20, 0)
        assert isinstance(data, wire.SGBuf)
        assert len(data.segments) == 2
        assert data == payload
        assert await c.read_file("/f") == payload  # API edge: bytes
        await f.close()
        await c.unmount()

        # sg off: same bytes, single joined buffer (old-peer behavior)
        g2 = _sg_client(server.port, sg="off")
        c2 = Client(g2)
        await c2.mount()
        assert await _wait_connected(g2.top)
        f2 = await c2.open("/f", os.O_RDONLY)
        data2 = await c2.graph.top.readv(f2.fd, 1 << 20, 0)
        assert not isinstance(data2, wire.SGBuf)
        assert bytes(data2) == payload
        await f2.close()
        await c2.unmount()
        await server.stop()

    asyncio.run(run())


# -- client-side pipeline: io-cache / read-ahead sg serving ------------


def _vol(tmp_path, *layers) -> str:
    out = [f"volume posix\n    type storage/posix\n"
           f"    option directory {tmp_path}/b\nend-volume\n"]
    prev = "posix"
    for i, (ltype, opts) in enumerate(layers):
        name = f"l{i}"
        body = "".join(f"    option {k} {v}\n" for k, v in opts.items())
        out.append(f"volume {name}\n    type {ltype}\n{body}"
                   f"    subvolumes {prev}\nend-volume\n")
        prev = name
    return "\n".join(out)


def test_io_cache_serves_sg_page_views(tmp_path):
    """A multi-page cache hit is served as an SGBuf of page views —
    byte-identical to the page bytes, no join inside the layer."""
    async def run():
        g = Graph.construct(_vol(
            tmp_path, ("performance/io-cache", {"page-size": "4KB"})))
        c = Client(g)
        await c.mount()
        payload = bytes(range(256)) * 100  # 25600B: 7 pages
        await c.write_file("/f", payload)
        await c.read_file("/f")  # fill
        f = await c.open("/f", os.O_RDONLY)
        data = await g.top.readv(f.fd, len(payload), 0)
        assert isinstance(data, wire.SGBuf)
        assert len(data.segments) >= 2
        assert data == payload
        # an unaligned window straddling pages is sliced correctly
        part = await g.top.readv(f.fd, 9000, 1000)
        assert bytes(part) == payload[1000:10000]
        await f.close()
        await c.unmount()

    asyncio.run(run())


def test_read_ahead_adaptive_window(tmp_path):
    """The look-ahead window starts at one page, doubles per sustained
    sequential prefetch up to page-count, and a seek resets it."""
    async def run():
        g = Graph.construct(_vol(
            tmp_path, ("performance/read-ahead",
                       {"page-size": "4KB", "page-count": "8"})))
        c = Client(g)
        await c.mount()
        ra = g.top
        payload = bytes(range(256)) * 1024  # 256 KiB
        await c.write_file("/f", payload)
        f = await c.open("/f", os.O_RDONLY)
        ctx = None
        for i in range(6):
            got = await ra.readv(f.fd, 4096, i * 4096)
            assert bytes(got) == payload[i * 4096:(i + 1) * 4096]
            ctx = f.fd.ctx_get(ra)
        assert ctx.window > 1  # doubled under sequential load
        grown = ctx.window
        await ra.readv(f.fd, 4096, 200000)  # far seek
        assert f.fd.ctx_get(ra).window == 1 < grown  # ramp restarted
        await f.close()
        await c.unmount()

    asyncio.run(run())


def test_read_ahead_chain_fuses_demand_and_window(tmp_path):
    """With compound-fops on, the demand readv and its look-ahead
    window ride ONE wire frame: a sequential stream costs fewer round
    trips than the unfused task path, with identical bytes."""
    async def run():
        server = await serve_brick(
            BRICK_VOLFILE.format(dir=tmp_path / "b"))
        payload = bytes(range(256)) * 512  # 128 KiB

        async def stream(ra_opts):
            g = Graph.construct(
                CLIENT_VOLFILE.format(
                    port=server.port, sub="locks",
                    opts="    option compound-fops on\n")
                + f"""
volume ra
    type performance/read-ahead
    option page-size 4KB
    option page-count 4
{ra_opts}    subvolumes c0
end-volume
""")
            c = Client(g)
            await c.mount()
            cl = next(l for l in walk(g.top)
                      if l.type_name == "protocol/client")
            assert await _wait_connected(cl)
            if not os.path.exists(tmp_path / "b" / "f"):
                await c.write_file("/f", payload)
            f = await c.open("/f", os.O_RDONLY)
            base = cl.rpc_roundtrips
            out = b""
            for i in range(16):
                got = await g.top.readv(f.fd, 4096, i * 4096)
                out += bytes(got)
            # the look-ahead the last read decided on counts on both
            # sides, whether it had left before that read was served
            # (the read parked) or not
            await asyncio.sleep(0)
            while f.fd.ctx_get(g.top).fetches:
                await asyncio.sleep(0.01)
            rts = cl.rpc_roundtrips - base
            await f.close()
            await c.unmount()
            return out, rts

        fused_out, fused_rts = await stream(
            "    option compound-fops on\n")
        plain_out, plain_rts = await stream("")
        assert fused_out == plain_out == payload[:16 * 4096]
        assert fused_rts < plain_rts, (fused_rts, plain_rts)
        await server.stop()

    asyncio.run(run())


def test_read_ahead_chain_survives_release_race(tmp_path):
    """release() cancels the in-flight demand+window chain task; a
    reader parked on it must still get its bytes (direct fallback),
    not a spurious CancelledError."""

    @register("test/slow-compound")
    class SlowCompound(Layer):
        async def compound(self, links, xdata=None):
            await asyncio.sleep(0.2)
            from glusterfs_tpu.rpc import compound as cfop

            return await cfop.decompose(self.children[0], links, xdata)

    async def run():
        g = Graph.construct(_vol(
            tmp_path,
            ("test/slow-compound", {}),
            ("performance/read-ahead",
             {"page-size": "4KB", "compound-fops": "on"})))
        c = Client(g)
        await c.mount()
        ra = g.top
        payload = bytes(range(256)) * 64
        await c.write_file("/f", payload)
        f = await c.open("/f", os.O_RDONLY)
        reader = asyncio.create_task(ra.readv(f.fd, 4096, 0))
        await asyncio.sleep(0.05)  # chain is parked in slow-compound
        await ra.release(f.fd)     # cancels the chain task
        got = await reader
        assert bytes(got) == payload[:4096]
        await c.unmount()

    asyncio.run(run())


# -- open-behind / read-ahead interaction ------------------------------


def test_open_behind_retires_anon_standin_on_materialize(tmp_path):
    """The anonymous stand-in fd (and its downstream read-ahead window,
    including any in-flight prefetch) is released when the deferred
    open materializes — prefetches issued pre-open never race the real
    fd's view of the file."""
    async def run():
        g = Graph.construct(_vol(
            tmp_path,
            ("performance/read-ahead", {"page-size": "4KB"}),
            ("performance/open-behind", {})))
        c = Client(g)
        await c.mount()
        ob = g.top
        ra = g.by_name["l0"]
        payload = bytes(range(256)) * 64
        await c.write_file("/f", payload)
        f = await c.open("/f", os.O_RDONLY)
        await g.top.readv(f.fd, 4096, 0)  # anon-routed, arms read-ahead
        ctx = f.fd.ctx_get(ob)
        anon = ctx.anon_fd
        assert anon is not None and anon.ctx_get(ra) is not None
        await g.top.fsync(f.fd, 0)  # forces the real open
        assert ctx.real_fd is not None
        assert ctx.anon_fd is None  # stand-in retired...
        assert anon.ctx_get(ra) is None  # ...and its ra window released
        got = await g.top.readv(f.fd, 4096, 0)  # now rides the real fd
        assert bytes(got) == payload[:4096]
        await f.close()
        await c.unmount()

    asyncio.run(run())


def test_open_behind_releases_anon_standin_on_close(tmp_path):
    """A lazy open/read/close pass must not leak the stand-in's
    downstream state (read-ahead pages + running prefetch task)."""
    async def run():
        g = Graph.construct(_vol(
            tmp_path,
            ("performance/read-ahead", {"page-size": "4KB"}),
            ("performance/open-behind", {})))
        c = Client(g)
        await c.mount()
        ob = g.top
        ra = g.by_name["l0"]
        await c.write_file("/f", bytes(range(256)) * 64)
        f = await c.open("/f", os.O_RDONLY)
        await g.top.readv(f.fd, 4096, 0)
        anon = f.fd.ctx_get(ob).anon_fd
        assert anon is not None and anon.ctx_get(ra) is not None
        await f.close()
        assert anon.ctx_get(ra) is None  # released, task cancelled
        await c.unmount()

    asyncio.run(run())


# -- client strict-locks -----------------------------------------------


def test_strict_locks_refuses_anon_bypass(tmp_path):
    """client.strict-locks (reference client.c:2438): an fd that holds
    posix locks and lost its server-side handle fails I/O with EBADFD
    instead of silently riding an anonymous fd past the lock."""
    async def run():
        server = await serve_brick(
            BRICK_VOLFILE.format(dir=tmp_path / "b"))
        g = Graph.construct(CLIENT_VOLFILE.format(
            port=server.port, sub="locks",
            opts="    option strict-locks on\n"))
        c = Client(g)
        await c.mount()
        cl = g.top
        assert await _wait_connected(cl)
        await c.write_file("/lk", b"locked")
        f = await c.open("/lk", os.O_RDWR)
        await cl.lk(f.fd, "setlk",
                    {"type": "wr", "start": 0, "len": 0},
                    xdata={"lk-owner": b"me"})
        assert cl._fd_holds_locks(f.fd)
        # simulate a reconnect whose re-open failed: the handle is gone
        f.fd.ctx_del(cl)
        with pytest.raises(FopError) as ei:
            await cl.readv(f.fd, 6, 0)
        assert ei.value.err == errno.EBADFD
        # unlock drops the record; the anon route is then allowed again
        await cl.lk(f.fd, "setlk",
                    {"type": "unlck", "start": 0, "len": 0},
                    xdata={"lk-owner": b"me"})
        assert not cl._fd_holds_locks(f.fd)
        assert bytes(await cl.readv(f.fd, 6, 0)) == b"locked"
        await c.unmount()
        await server.stop()

    asyncio.run(run())


def test_strict_locks_off_allows_anon(tmp_path):
    async def run():
        server = await serve_brick(
            BRICK_VOLFILE.format(dir=tmp_path / "b"))
        g = Graph.construct(CLIENT_VOLFILE.format(
            port=server.port, sub="locks", opts=""))
        c = Client(g)
        await c.mount()
        cl = g.top
        assert await _wait_connected(cl)
        await c.write_file("/lk", b"locked")
        f = await c.open("/lk", os.O_RDWR)
        await cl.lk(f.fd, "setlk",
                    {"type": "wr", "start": 0, "len": 0},
                    xdata={"lk-owner": b"me"})
        f.fd.ctx_del(cl)
        assert bytes(await cl.readv(f.fd, 6, 0)) == b"locked"
        await c.unmount()
        await server.stop()

    asyncio.run(run())


# -- EC fan-out --------------------------------------------------------


def _ec_client(tmp_path, n, r, options=None):
    from glusterfs_tpu.utils.volspec import ec_volfile

    g = Graph.construct(ec_volfile(str(tmp_path), n, r,
                                   options=options))
    return Client(g)


def test_ec_systematic_fanout_fast_path(tmp_path):
    """Healthy systematic reads take the zero-staging reassembly lane
    (fragment buffers straight into the output); the answer is
    byte-identical to the staged decode."""
    from glusterfs_tpu.cluster.ec import DisperseLayer

    async def run():
        c = _ec_client(tmp_path, 6, 2,
                       {"systematic": "on", "cpu-extensions": "ref"})
        await c.mount()
        ec = next(l for l in walk(c.graph.top)
                  if isinstance(l, DisperseLayer))
        payload = bytes(range(256)) * 300
        await c.write_file("/s", payload + b"odd")
        assert ec.read_fanout["fast"] == 0
        got = await c.read_file("/s")
        assert got == payload + b"odd"
        assert ec.read_fanout["fast"] > 0
        assert ec.read_fanout["staged"] == 0
        # staged reference: force the decode path on the same fragments
        f = await c.open("/s", os.O_RDONLY)
        fast = ec.read_fanout["fast"]
        orig = ec.codec.reassemble
        ec.codec.reassemble = lambda *a, **kw: None
        try:
            staged = await f.read(1 << 20, 0)
        finally:
            ec.codec.reassemble = orig
        await f.close()
        assert staged == payload + b"odd"
        assert ec.read_fanout["staged"] > 0
        assert ec.read_fanout["fast"] == fast
        await c.unmount()

    asyncio.run(run())


def test_ec_systematic_degraded_read_mask_identical(tmp_path):
    """With data bricks down (read-mask path) the staged reconstruct
    serves the same bytes the fast path served healthy."""
    from glusterfs_tpu.cluster.ec import DisperseLayer

    async def run():
        c = _ec_client(tmp_path, 6, 2,
                       {"systematic": "on", "cpu-extensions": "ref"})
        await c.mount()
        ec = next(l for l in walk(c.graph.top)
                  if isinstance(l, DisperseLayer))
        payload = bytes(range(251)) * 300  # prime-ish pattern
        await c.write_file("/d", payload)
        healthy = await c.read_file("/d")
        assert ec.read_fanout["fast"] > 0
        # operator read-mask excludes two DATA fragments: reads must
        # reconstruct from the remaining data + parity (staged path)
        ec._read_mask = {1, 2, 4, 5}
        degraded = await c.read_file("/d")
        assert degraded == healthy == payload
        assert ec.read_fanout["staged"] > 0  # reconstruction ran
        # data rows 0 and 3 rebuilt in every staged read
        assert ec.dump_private()["read_fanout"]["rows_rebuilt"] == \
            2 * ec.read_fanout["staged"]
        ec._read_mask = None
        await c.unmount()

    asyncio.run(run())


def test_ec_nonsystematic_stays_staged(tmp_path):
    async def run():
        from glusterfs_tpu.cluster.ec import DisperseLayer

        c = _ec_client(tmp_path, 4, 2, {"cpu-extensions": "ref"})
        await c.mount()
        ec = next(l for l in walk(c.graph.top)
                  if isinstance(l, DisperseLayer))
        payload = b"nonsys" * 1000
        await c.write_file("/n", payload)
        assert await c.read_file("/n") == payload
        assert ec.read_fanout["fast"] == 0
        assert ec.read_fanout["staged"] > 0
        # no row of the upstream format is the stripe's own bytes
        assert ec.dump_private()["read_fanout"]["rows_rebuilt"] == \
            ec.k * ec.read_fanout["staged"]
        await c.unmount()

    asyncio.run(run())


def test_shard_over_ec_read_roundtrip(tmp_path):
    """features/shard pads child readv results; EC now returns views —
    shard must own the buffer before .ljust (review regression)."""
    from glusterfs_tpu.utils.volspec import ec_volfile

    async def run():
        g = Graph.construct(ec_volfile(
            str(tmp_path), 6, 2, options={"cpu-extensions": "ref"}) + """
volume sh
    type features/shard
    option block-size 64KB
    subvolumes disp
end-volume
""")
        c = Client(g)
        await c.mount()
        payload = bytes(range(256)) * 700  # ~175KB: 3 shards
        await c.write_file("/s", payload)
        assert await c.read_file("/s") == payload
        await c.unmount()

    asyncio.run(run())


def test_codec_reassemble_matches_decode():
    """Oracle: reassemble == staged systematic decode on random
    fragments, including short (sparse-tail) buffers."""
    import numpy as np

    from glusterfs_tpu.ops.codec import Codec

    rng = np.random.default_rng(3)
    codec = Codec(4, 2, "ref", systematic=True)
    data = rng.integers(0, 256, 4 * 512 * 5, dtype=np.uint8)
    frags = codec.encode(data)
    bufs = [frags[i].tobytes() for i in range(4)]
    out = codec.reassemble(bufs, [0, 1, 2, 3], frags.shape[1])
    assert out is not None
    np.testing.assert_array_equal(out, data)
    # short buffer zero-fills exactly like the staging array did
    short = [bufs[0], bufs[1][: 512 * 3], bufs[2], bufs[3][:100]]
    staged = np.zeros((4, frags.shape[1]), dtype=np.uint8)
    for j, b in enumerate(short):
        staged[j, : len(b)] = np.frombuffer(b, dtype=np.uint8)
    want = codec.decode(staged, [0, 1, 2, 3])
    got = codec.reassemble(short, [0, 1, 2, 3], frags.shape[1])
    np.testing.assert_array_equal(got, want)
    # non-qualifying row sets refuse (parity row present)
    assert codec.reassemble(bufs, [0, 1, 2, 4], frags.shape[1]) is None
    assert Codec(4, 2, "ref").reassemble(
        bufs, [0, 1, 2, 3], frags.shape[1]) is None


# -- ranged read_file (object-gateway satellite, ISSUE 6) --------------


def test_ranged_read_file_one_roundtrip(tmp_path):
    """A ranged ``read_file(path, offset=, size=)`` window inside the
    file is ONE fused chain — lookup+open+readv(window)+release in a
    single wire round trip — and the payload comes back RAW (a frame
    view / SGBuf, not joined bytes): the gateway's ranged GET hands the
    segments straight to the socket."""
    async def run():
        server = await serve_brick(
            BRICK_VOLFILE.format(dir=tmp_path / "b"))
        payload = bytes(range(256)) * 256  # 64 KiB
        g = Graph.construct(CLIENT_VOLFILE.format(
            port=server.port, sub="locks",
            opts="    option compound-fops on\n"))
        c = Client(g)
        await c.mount()
        cl = g.top
        assert await _wait_connected(cl)
        await c.write_file("/f", payload)
        base = cl.rpc_roundtrips
        data = await c.read_file("/f", offset=1000, size=5000)
        assert cl.rpc_roundtrips - base == 1, \
            "in-window ranged read_file must be one chain frame"
        assert not isinstance(data, bytes), \
            "ranged window must stay raw (join is the caller's call)"
        assert bytes(data) == payload[1000:6000]
        # EOF truncation, still one round trip
        base = cl.rpc_roundtrips
        data = await c.read_file("/f", offset=len(payload) - 100,
                                 size=4096)
        assert cl.rpc_roundtrips - base == 1
        assert bytes(data) == payload[-100:]
        # degenerate windows
        assert await c.read_file("/f", offset=0, size=0) == b""
        # open-ended tail (no size): windowed loop to EOF, still raw
        tail = await c.read_file("/f", offset=len(payload) - 300)
        assert bytes(tail) == payload[-300:]
        # whole-file default keeps returning owned bytes
        whole = await c.read_file("/f")
        assert isinstance(whole, bytes) and whole == payload
        # without compound the ranged contract holds (open+readv path)
        g2 = Graph.construct(CLIENT_VOLFILE.format(
            port=server.port, sub="locks", opts=""))
        c2 = Client(g2)
        await c2.mount()
        assert await _wait_connected(g2.top)
        d2 = await c2.read_file("/f", offset=4096, size=4096)
        assert bytes(d2) == payload[4096:8192]
        await c2.unmount()
        await c.unmount()
        await server.stop()

    asyncio.run(run())


# -- volgen keys -------------------------------------------------------


def test_volgen_read_pipeline_keys():
    """network.zero-copy-reads lands on both transport ends,
    cluster.use-compound-fops arms read-ahead, client.strict-locks and
    performance.read-ahead-adaptive map, and disperse volumes get
    stripe-aligned page sizes on the page-granular read layers."""
    from glusterfs_tpu.mgmt import volgen

    volinfo = {
        "name": "zv", "type": "disperse", "redundancy": 2,
        "group-size": 8,
        "bricks": [{"name": f"zv-brick-{i}", "host": "127.0.0.1",
                    "path": f"/tmp/zvb{i}", "index": i, "port": 0}
                   for i in range(8)],
        "options": {"cluster.use-compound-fops": "on",
                    "network.zero-copy-reads": "on",
                    "client.strict-locks": "on",
                    "performance.read-ahead-adaptive": "off"},
    }
    cvol = volgen.build_client_volfile(volinfo)
    bvol = volgen.build_brick_volfile(volinfo, volinfo["bricks"][0])
    client_stanza = cvol.split("volume zv-client-0")[1] \
                        .split("end-volume")[0]
    ra_stanza = cvol.split("volume zv-read-ahead")[1] \
                    .split("end-volume")[0]
    ioc_stanza = cvol.split("volume zv-io-cache")[1] \
                     .split("end-volume")[0]
    srv_stanza = bvol.split("volume zv-brick-0-server")[1] \
                     .split("end-volume")[0]
    assert "sg-replies on" in client_stanza
    assert "sg-replies on" in srv_stanza
    assert "strict-locks on" in client_stanza
    assert "compound-fops on" in ra_stanza
    assert "adaptive-window off" in ra_stanza
    # k=6 -> stripe 3072; largest multiple <= 128KB is 129024
    assert "page-size 129024" in ra_stanza
    assert "page-size 129024" in ioc_stanza
    for key in ("network.zero-copy-reads", "client.strict-locks",
                "performance.read-ahead-adaptive"):
        assert volgen.OPTION_MIN_OPVERSION[key] == 6
    # a power-of-two geometry keeps the 128KB default exactly
    volinfo4 = dict(volinfo, options={}, redundancy=2)
    volinfo4["group-size"] = 6
    cvol4 = volgen.build_client_volfile(volinfo4)
    ra4 = cvol4.split(f"volume zv-read-ahead")[1].split("end-volume")[0]
    assert "page-size 131072" in ra4
