"""End-to-end observability (ISSUE 4): log-bucket latency histograms +
percentile math, wire-propagated trace spans (client -> server ->
posix), compound-chain span nesting, slow-fop span-tree logging,
live-downgrade peers ignoring the trace wire field, and the unified
metrics registry (families, monotonicity, .meta/metrics, the daemon
endpoint, the per-brick metrics_dump RPC)."""

import asyncio
import os
import threading

import pytest

from glusterfs_tpu.api.glfs import Client
from glusterfs_tpu.core import tracing
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.core.metrics import (HIST_BUCKETS, LogHistogram,
                                        REGISTRY)
from glusterfs_tpu.daemon import serve_brick
from glusterfs_tpu.core import gflog

from .harness import BRICK_VOLFILE

CLIENT_VOLFILE = """
volume c0
    type protocol/client
    option remote-host 127.0.0.1
    option remote-port {port}
    option remote-subvolume locks
end-volume
"""

# brick graph with a protocol/server top so capability options
# (trace-fops) are enforceable, plus io-stats for the RPC extras
SERVER_TOP_VOLFILE = """
volume posix
    type storage/posix
    option directory {dir}
end-volume
volume locks
    type features/locks
    subvolumes posix
end-volume
volume stats
    type debug/io-stats
    subvolumes locks
end-volume
volume srv
    type protocol/server
    option trace-fops {trace}
    subvolumes stats
end-volume
"""

# the blob-lane monotonicity test speaks the inline wire on purpose:
# with the same-host shm lane armed (default on, op-ver 17) payload
# blobs ride the arenas and gftpu_wire_blob_stats legitimately stays
# flat — the lane's own counters are pinned in test_shm_transport.py
INLINE_CLIENT_VOLFILE = CLIENT_VOLFILE.replace(
    "end-volume", "    option shm-transport off\nend-volume")

SRV_CLIENT_VOLFILE = """
volume c0
    type protocol/client
    option remote-host 127.0.0.1
    option remote-port {port}
    option remote-subvolume stats
end-volume
"""


async def _connect(port, volfile=CLIENT_VOLFILE):
    g = Graph.construct(volfile.format(port=port))
    c = Client(g)
    await c.mount()
    for _ in range(200):
        if g.top.connected:
            break
        await asyncio.sleep(0.05)
    assert g.top.connected
    return c, g


# -- histogram math --------------------------------------------------------

def test_histogram_percentiles_known_samples():
    """Percentile math against a known sample set: bucket i holds
    [2^(i-1), 2^i) µs and percentile() reports the bucket's UPPER
    bound in seconds."""
    h = LogHistogram()
    # 90 samples of ~3µs (bucket 2: (2,4]µs upper bound 4µs) and 10 of
    # ~1000µs (bucket 10: (512,1024]µs upper bound 1024µs)
    for _ in range(90):
        h.record(3e-6)
    for _ in range(10):
        h.record(1000e-6)
    assert h.total == 100
    assert h.percentile(50) == pytest.approx(4e-6)
    assert h.percentile(90) == pytest.approx(4e-6)
    assert h.percentile(99) == pytest.approx(1024e-6)
    # empty histogram: percentiles are 0, not a crash
    assert LogHistogram().percentile(50) == 0.0


def test_histogram_bucket_edges_and_merge():
    h = LogHistogram()
    h.record(0.0)            # sub-µs -> bucket 0
    h.record(1e-6)           # 1µs -> bit_length(1)=1 -> bucket 1
    h.record(1e6)            # absurdly slow -> clamped to last bucket
    assert h.buckets[0] == 1 and h.buckets[1] == 1
    assert h.buckets[HIST_BUCKETS - 1] == 1
    other = LogHistogram()
    other.record(3e-6)
    h.merge(other)
    assert h.total == 4 and h.buckets[2] == 1


def test_fop_stats_percentiles_surface(tmp_path):
    """p50/p90/p99 show up in layer stats -> statedump -> io-stats
    profile (the volume-profile feed)."""
    vf = f"""
volume posix
    type storage/posix
    option directory {tmp_path}/b
end-volume
volume stats
    type debug/io-stats
    subvolumes posix
end-volume
"""
    async def run():
        g = Graph.construct(vf)
        c = Client(g)
        await c.mount()
        await c.write_file("/f", b"x" * 1000)
        st = g.by_name["stats"]
        prof = st.profile()
        assert "latency_p50" in prof["fops"]["writev"]
        assert prof["fops"]["writev"]["latency_p99"] >= \
            prof["fops"]["writev"]["latency_p50"] > 0
        dump = g.by_name["posix"].statedump()
        assert "latency_p50" in dump["stats"]["writev"]
        await c.unmount()

    asyncio.run(run())


def test_latency_measurement_gates_histograms(tmp_path):
    """io-stats latency-measurement off: count/avg/max keep counting,
    the histograms stop (and the option re-arms live)."""
    from glusterfs_tpu.core import layer as layer_mod

    vf = f"""
volume posix
    type storage/posix
    option directory {tmp_path}/b
end-volume
volume stats
    type debug/io-stats
    option latency-measurement off
    subvolumes posix
end-volume
"""
    async def run():
        g = Graph.construct(vf)
        c = Client(g)
        await c.mount()
        try:
            assert layer_mod.HISTOGRAMS_ENABLED is False
            await c.write_file("/f", b"x")
            st = g.by_name["posix"].stats["writev"]
            assert st.count > 0 and st.hist.total == 0
            assert "latency_p50" not in st.to_dict()
            g.by_name["stats"].reconfigure({"latency-measurement": "on"})
            assert layer_mod.HISTOGRAMS_ENABLED is True
            await c.write_file("/g", b"x")
            assert g.by_name["posix"].stats["writev"].hist.total > 0
        finally:
            layer_mod.HISTOGRAMS_ENABLED = True
            await c.unmount()

    asyncio.run(run())


def test_dark_process_survives_iostats_init(tmp_path):
    """GFTPU_NO_OBSERVABILITY darkening must WIN over io-stats init:
    latency-measurement defaults 'on', and mounting a graph with an
    io-stats layer must not re-arm histograms on a darkened process
    (the bench metrics-off pass mounts volumes mid-pass)."""
    from glusterfs_tpu.core import layer as layer_mod

    vf = f"""
volume posix
    type storage/posix
    option directory {tmp_path}/b
end-volume
volume stats
    type debug/io-stats
    subvolumes posix
end-volume
"""
    async def run():
        tracing.DARK = True
        layer_mod.HISTOGRAMS_ENABLED = False
        g = Graph.construct(vf)
        c = Client(g)
        await c.mount()
        try:
            assert layer_mod.HISTOGRAMS_ENABLED is False
            await c.write_file("/f", b"x")
            assert g.by_name["posix"].stats["writev"].hist.total == 0
        finally:
            tracing.DARK = False
            layer_mod.HISTOGRAMS_ENABLED = True
            await c.unmount()

    asyncio.run(run())


# -- trace propagation -----------------------------------------------------

def test_trace_propagation_client_server_posix(tmp_path):
    """One wire readv = ONE trace id spanning the client graph, the
    brick dispatch and storage/posix (>= 3 spans), visible in
    statedump."""
    async def run():
        server = await serve_brick(
            BRICK_VOLFILE.format(dir=tmp_path / "b"))
        c, g = await _connect(server.port)
        try:
            await c.write_file("/x", b"payload" * 1024)
            tracing.SPANS.clear()
            assert await c.read_file("/x") == b"payload" * 1024
            spans = list(tracing.SPANS)
            readv = [s for s in spans if s[3] == "readv"]
            tids = {s[0] for s in readv}
            assert len(tids) == 1, readv
            layers = {s[2] for s in readv}
            # client graph (c0), brick graph (locks), storage (posix)
            assert {"c0", "locks", "posix"} <= layers
            assert len(readv) >= 3
            # the root is the client layer; brick spans nest deeper
            by_layer = {s[2]: s[1] for s in readv}
            assert by_layer["c0"] == 0
            assert by_layer["posix"] > by_layer["locks"] > 0
            # statedump surfaces the ring
            dumped = g.statedump()["trace_spans"]
            assert any(d["op"] == "readv" and d["layer"] == "posix"
                       for d in dumped)
        finally:
            await c.unmount()
            await server.stop()

    asyncio.run(run())


def test_compound_chain_single_trace(tmp_path):
    """One compound chain = one trace: the chain's outermost compound
    call is the root span and every link is a child span under the
    same id."""
    vf = f"""
volume posix
    type storage/posix
    option directory {tmp_path}/b
end-volume
volume stats
    type debug/io-stats
    subvolumes posix
end-volume
"""
    async def run():
        from glusterfs_tpu.core.layer import Loc
        from glusterfs_tpu.rpc import compound as cfop

        g = Graph.construct(vf)
        c = Client(g)
        await c.mount()
        try:
            tracing.SPANS.clear()
            replies = await g.top.compound([
                ("create", (Loc("/f"), os.O_RDWR, 0o644), {}),
                ("writev", (cfop.FdRef(0), b"abc", 0), {}),
                ("flush", (cfop.FdRef(0),), {}),
                ("release", (cfop.FdRef(0),), {})])
            assert cfop.first_error(replies) is None
            spans = list(tracing.SPANS)
            roots = [s for s in spans if s[1] == 0]
            assert len(roots) == 1 and roots[0][3] == "compound"
            tid = roots[0][0]
            assert all(s[0] == tid for s in spans), spans
            link_ops = {s[3] for s in spans if s[1] > 0}
            assert {"create", "writev", "flush"} <= link_ops
        finally:
            await c.unmount()

    asyncio.run(run())


def test_slow_fop_threshold_logs_tree(tmp_path):
    """A root fop slower than diagnostics.slow-fop-threshold logs its
    full span tree (and bumps the slow-fop counter)."""
    vf = f"""
volume posix
    type storage/posix
    option directory {tmp_path}/b
end-volume
volume slow
    type debug/delay-gen
    option delay-duration 20000
    option delay-percentage 100
    option enable writev
    subvolumes posix
end-volume
volume stats
    type debug/io-stats
    option slow-fop-threshold 0.005
    subvolumes slow
end-volume
"""
    async def run():
        g = Graph.construct(vf)
        c = Client(g)
        await c.mount()
        try:
            before = sum(tracing.SLOW_FOP_COUNTS.values())
            await c.write_file("/f", b"x")
            assert sum(tracing.SLOW_FOP_COUNTS.values()) > before
            # the counter is labeled {layer,op}: the slow write must
            # be attributed to a concrete layer+op pair
            assert any(op == "writev"
                       for (_, op) in tracing.SLOW_FOP_COUNTS)
            logs = "\n".join(gflog.recent_messages(50))
            assert "slow fop" in logs
            # the logged tree names the layer below (where time went)
            assert "slow.writev" in logs or "posix.writev" in logs
        finally:
            tracing.SLOW_FOP_THRESHOLD = 0.0
            await c.unmount()

    asyncio.run(run())


def test_live_downgrade_peer_ignores_trace_field(tmp_path):
    """A brick with diagnostics.trace-propagation off never advertises
    trace at SETVOLUME: the client sends bare 3-element frames, I/O
    keeps working, and brick-side spans mint their OWN ids instead of
    joining the client's."""
    async def run():
        server = await serve_brick(SERVER_TOP_VOLFILE.format(
            dir=tmp_path / "b", trace="off"))
        c, g = await _connect(server.port, SRV_CLIENT_VOLFILE)
        try:
            assert g.top._peer_trace is False
            await c.write_file("/x", b"data" * 2048)
            tracing.SPANS.clear()
            assert await c.read_file("/x") == b"data" * 2048
            readv = [s for s in list(tracing.SPANS) if s[3] == "readv"]
            client_tids = {s[0] for s in readv if s[2] == "c0"}
            brick_tids = {s[0] for s in readv if s[2] == "posix"}
            assert client_tids and brick_tids
            assert not (client_tids & brick_tids)
        finally:
            await c.unmount()
            await server.stop()

    asyncio.run(run())


def test_trace_enabled_peer_joins(tmp_path):
    """Counter-case to the downgrade test: with the server option on
    (the default) the brick's posix spans carry the client's id."""
    async def run():
        server = await serve_brick(SERVER_TOP_VOLFILE.format(
            dir=tmp_path / "b", trace="on"))
        c, g = await _connect(server.port, SRV_CLIENT_VOLFILE)
        try:
            assert g.top._peer_trace is True
            await c.write_file("/x", b"data" * 2048)
            tracing.SPANS.clear()
            await c.read_file("/x")
            readv = [s for s in list(tracing.SPANS) if s[3] == "readv"]
            client_tids = {s[0] for s in readv if s[2] == "c0"}
            brick_tids = {s[0] for s in readv if s[2] == "posix"}
            assert client_tids & brick_tids
        finally:
            await c.unmount()
            await server.stop()

    asyncio.run(run())


def test_trace_fops_toggles_live(tmp_path):
    """The client's trace-fops option is read per-call: a live
    volume-set of diagnostics.trace-propagation off stops the wire
    field without a reconnect (the compound-fops pattern)."""
    async def run():
        server = await serve_brick(SERVER_TOP_VOLFILE.format(
            dir=tmp_path / "b", trace="on"))
        c, g = await _connect(server.port, SRV_CLIENT_VOLFILE)
        try:
            await c.write_file("/x", b"live" * 2048)
            g.top.reconfigure({"trace-fops": "off"})
            tracing.SPANS.clear()
            await c.read_file("/x")
            readv = [s for s in list(tracing.SPANS) if s[3] == "readv"]
            client_tids = {s[0] for s in readv if s[2] == "c0"}
            brick_tids = {s[0] for s in readv if s[2] == "posix"}
            assert client_tids and brick_tids
            assert not (client_tids & brick_tids)  # field stopped
            g.top.reconfigure({"trace-fops": "on"})
            tracing.SPANS.clear()
            await c.read_file("/x")
            readv = [s for s in list(tracing.SPANS) if s[3] == "readv"]
            assert {s[0] for s in readv if s[2] == "c0"} & \
                {s[0] for s in readv if s[2] == "posix"}
        finally:
            await c.unmount()
            await server.stop()

    asyncio.run(run())


def test_span_ring_bounded():
    tracing.set_ring_size(64)
    try:
        for i in range(500):
            tracing.SPANS.append(("t", 0, "l", "op", 0.0, 0.0, False))
        assert len(tracing.SPANS) == 64
    finally:
        tracing.set_ring_size(4096)


# -- unified metrics registry ----------------------------------------------

def test_registry_families_present_and_monotonic(tmp_path):
    """The acceptance families: decode-program cache events and
    wire.blob_stats, present in the render and monotonic across wire
    traffic."""
    from glusterfs_tpu.ops import gf256

    # touch the decode-program cache so the family has real counts
    gf256.decode_program(4, (0, 1, 2, 4))
    gf256.decode_program(4, (0, 1, 2, 4))

    def family_value(snap, name, **labels):
        total = 0
        for lbl, v in snap[name]["samples"]:
            if all(lbl.get(k) == val for k, val in labels.items()):
                total += v
        return total

    async def run():
        server = await serve_brick(
            BRICK_VOLFILE.format(dir=tmp_path / "b"))
        c, _g = await _connect(server.port, INLINE_CLIENT_VOLFILE)
        try:
            snap0 = REGISTRY.snapshot()
            assert "gftpu_wire_blob_stats" in snap0
            assert "gftpu_decode_program_cache_events_total" in snap0
            assert family_value(
                snap0, "gftpu_decode_program_cache_events_total",
                cache="decode", event="hits") >= 1
            await c.write_file("/m", b"z" * 65536)
            await c.read_file("/m")
            snap1 = REGISTRY.snapshot()
            b0 = family_value(snap0, "gftpu_wire_blob_stats",
                              counter="tx_bytes")
            b1 = family_value(snap1, "gftpu_wire_blob_stats",
                              counter="tx_bytes")
            assert b1 > b0
            text = REGISTRY.render()
            assert "# TYPE gftpu_wire_blob_stats counter" in text
            assert 'counter="tx_bytes"' in text
        finally:
            await c.unmount()
            await server.stop()

    asyncio.run(run())


def test_registry_collector_isolation():
    """A raising collector loses only its own family."""
    REGISTRY.register("gftpu_test_bad", "gauge", "boom",
                      lambda: (_ for _ in ()).throw(RuntimeError()))
    try:
        snap = REGISTRY.snapshot()
        assert "gftpu_test_bad" not in snap
        assert "gftpu_wire_blob_stats" in snap
    finally:
        REGISTRY.unregister("gftpu_test_bad")


def test_metrics_dump_rpc_and_daemon_endpoint(tmp_path):
    """metrics_dump resolves by graph walk over the wire (the `gftpu
    volume metrics` backend), and the daemon's opt-in HTTP endpoint
    serves the same text dump."""
    async def run():
        from glusterfs_tpu.daemon import serve_metrics

        server = await serve_brick(SERVER_TOP_VOLFILE.format(
            dir=tmp_path / "b", trace="on"))
        c, g = await _connect(server.port, SRV_CLIENT_VOLFILE)
        msrv = await serve_metrics("127.0.0.1", 0)
        try:
            snap = await g.top.remote("metrics_dump")
            assert "gftpu_wire_blob_stats" in snap
            assert snap["gftpu_wire_blob_stats"]["type"] == "counter"
            mport = msrv.sockets[0].getsockname()[1]
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", mport)
            writer.write(b"GET /metrics HTTP/1.0\r\n\r\n")
            await writer.drain()
            body = await reader.read()
            writer.close()
            assert b"200 OK" in body
            assert b"gftpu_wire_blob_stats" in body
        finally:
            msrv.close()
            await c.unmount()
            await server.stop()

    asyncio.run(run())


# -- satellite regressions -------------------------------------------------

def test_iostats_compound_readv_replay(tmp_path):
    """Fused read chains must not vanish from `volume profile`: an ok
    readv link's reply bytes land in read_bytes + the per-path reads
    counters (writev was handled, readv was not)."""
    vf = f"""
volume posix
    type storage/posix
    option directory {tmp_path}/b
end-volume
volume stats
    type debug/io-stats
    subvolumes posix
end-volume
"""
    async def run():
        from glusterfs_tpu.core.layer import Loc
        from glusterfs_tpu.rpc import compound as cfop

        g = Graph.construct(vf)
        c = Client(g)
        await c.mount()
        try:
            await c.write_file("/f", b"0123456789")
            st = g.by_name["stats"]
            st.read_bytes = 0
            replies = await g.top.compound([
                ("lookup", (Loc("/f"),), {}),
                ("open", (Loc("/f"), os.O_RDONLY), {}),
                ("readv", (cfop.FdRef(1), 1 << 20, 0), {}),
                ("release", (cfop.FdRef(1),), {})])
            assert cfop.first_error(replies) is None
            assert st.read_bytes == 10
            rows = st.top("read")
            assert rows and rows[0]["path"] == "/f"
            assert rows[0]["read_bytes"] == 10
        finally:
            await c.unmount()

    asyncio.run(run())


def test_trace_layer_exclude_ops_reconfigure(tmp_path):
    """Live `volume set ... exclude-ops` takes effect: the excluded set
    is re-derived in reconfigure (it was frozen at init)."""
    vf = f"""
volume posix
    type storage/posix
    option directory {tmp_path}/b
end-volume
volume tr
    type debug/trace
    subvolumes posix
end-volume
"""
    async def run():
        g = Graph.construct(vf)
        c = Client(g)
        await c.mount()
        try:
            tr = g.by_name["tr"]
            await c.write_file("/a", b"x")
            assert any("writev" in line for line in tr.history)
            tr.reconfigure({"exclude-ops": "writev,flush"})
            assert tr._excluded == {"writev", "flush"}
            tr.history.clear()
            await c.write_file("/b", b"x")
            assert not any("writev(" in line for line in tr.history)
        finally:
            await c.unmount()

    asyncio.run(run())


def test_iostats_dump_interval_restarts_live(tmp_path):
    """A live diagnostics.stats-dump-interval change cancels the old
    dump task and arms one on the new interval."""
    vf = f"""
volume posix
    type storage/posix
    option directory {tmp_path}/b
end-volume
volume stats
    type debug/io-stats
    subvolumes posix
end-volume
"""
    async def run():
        g = Graph.construct(vf)
        c = Client(g)
        await c.mount()
        try:
            st = g.by_name["stats"]
            assert st._dump_task is None
            st.reconfigure({"ios-dump-interval": "0.05"})
            task = st._dump_task
            assert task is not None
            for _ in range(40):  # EXPECT_WITHIN: loaded-host tolerant
                if any("stats: profile" in line
                       for line in gflog.recent_messages(50)):
                    break
                await asyncio.sleep(0.1)
            logs = "\n".join(gflog.recent_messages(50))
            assert "stats: profile" in logs
            st.reconfigure({"ios-dump-interval": "0"})
            assert st._dump_task is None
            await asyncio.sleep(0)
            assert task.cancelled() or task.done()
        finally:
            await c.unmount()

    asyncio.run(run())


# -- phases below the fop boundary (ISSUE 24) ------------------------------

class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation`` as
    ``tracing.ANNOTATE``: keeps every annotation's name, metadata and
    the thread that ended it."""

    log: list = []

    def __init__(self, name, **meta):
        self.name, self.meta = name, meta

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        return self

    def set_metadata(self, **meta):
        self.meta.update(meta)

    def __exit__(self, *exc):
        import threading

        _Recorder.log.append((self.name, self.meta,
                              threading.current_thread().name))


@pytest.fixture
def recorder(monkeypatch):
    _Recorder.log = []
    monkeypatch.setattr(tracing, "ENABLED", True)
    yield _Recorder
    # after the graph is down: a codec built meanwhile has put jax's
    # annotation there, which is what the next test expects to find
    tracing.ANNOTATE = None


def _names(log, skip=()):
    """The spans' names, counted: the mounted loop's samples
    (``gftpu:loop.sample``, ISSUE 34) are no span and carry no
    ``span``."""
    from collections import Counter

    return Counter(n for n, m, _t in log if n not in skip and "span" in m)


# per fop inside a held eager window with the gfid lock free (one fop
# that fills its bucket has nothing to gather or scatter; a window's
# first fop adds ``ec.lock``, the fop under which it closes
# ``ec.unlock`` over ``ec.xattrop``)
WRITE_PHASES = {"ec.codec_wait", "ec.fanout", "codec.queue", "codec.flush",
                "codec.h2d", "codec.launch", "codec.d2h", "codec.resume"}
READ_PHASES = WRITE_PHASES | {"ec.reassemble"}


def test_ring_tuple_and_parent_ids_under_gather(tmp_path):
    """Six wire children under one ``asyncio.gather`` are SIBLINGS: the
    six ``protocol/client`` writev spans share one ``ec.fanout`` parent
    (a depth alone cannot tell them from nested calls), and the ring
    tuple keeps its first seven fields where they were."""
    import time

    async def run():
        servers = [await serve_brick(BRICK_VOLFILE.format(
            dir=tmp_path / f"b{i}")) for i in range(6)]
        vf = "".join(CLIENT_VOLFILE.replace("c0", f"c{i}").format(
            port=s.port) for i, s in enumerate(servers))
        vf += ("volume disp\n    type cluster/disperse\n"
               "    option redundancy 2\n    option cpu-extensions ref\n"
               f"    subvolumes {' '.join(f'c{i}' for i in range(6))}\n"
               "end-volume\n")
        g = Graph.construct(vf)
        c = Client(g)
        await c.mount()
        try:
            for _ in range(200):
                if all(ch.connected for ch in g.top.children):
                    break
                await asyncio.sleep(0.05)
            f = await c.create("/x", os.O_RDWR)
            await f.write(b"a" * 8192, 0)
            tracing.SPANS.clear()
            t_before = time.time()
            await f.write(b"b" * 8192, 8192)
            spans = list(tracing.SPANS)
            await f.close()
        finally:
            await c.unmount()
            for s in servers:
                await s.stop()
        root = next(s for s in spans if s[2] == "disp" and s[3] == "writev")
        tid, depth, layer, op, start, dur, err = root[:7]
        assert (len(tid), depth, err) == (16, 0, False)
        assert t_before - 1 < start < time.time() and 0 < dur < 60
        sid, pid, start_ns = root[7:]
        assert len(root) == 10 and sid > 0 and pid == 0
        assert abs(start_ns - time.perf_counter_ns()) < 60e9
        fan = [s for s in spans if s[3] == "ec.fanout" and s[0] == tid]
        assert len(fan) == 1 and fan[0][8] == sid, fan
        wire = [s for s in spans if s[3] == "writev" and s[0] == tid
                and s[2] in {f"c{i}" for i in range(6)}]
        assert len(wire) == 6 and {s[8] for s in wire} == {fan[0][7]}
        assert len({s[7] for s in wire}) == 6
        assert {s[1] for s in wire} == {fan[0][1] + 1}
        # the brick side joined the trace over the wire, and the dump
        # shows ids beside the wall-clock start it always had
        assert any(s[2] == "posix" and s[0] == tid for s in spans)
        dumped = [d for d in tracing.recent_spans(4096)
                  if d["trace"] == tid and d["op"] == "ec.fanout"]
        assert dumped[0]["span"] == fan[0][7] and \
            abs(dumped[0]["start"] - fan[0][4]) < 1e-5

    asyncio.run(run())


def test_flush_hangs_under_first_fop_and_lists_others(recorder):
    """The flush runs in a pool thread, outside every ContextVar: it
    hangs under the span the FIRST fop of its batch waits in (from the
    open ``codec.queue`` phase in the queue tuple) and names the other
    waiters; each fop has its own queue and resume span."""
    import numpy as np

    from glusterfs_tpu.ops.batch import BatchingCodec

    codec = BatchingCodec(4, 2, "xla", window=0.01, min_batch=0,
                          systematic=True, name="batcher")
    tracing.ANNOTATE = recorder
    waits = []

    async def fop(i):
        with tracing.phase("batcher", "ec.codec_wait") as w:
            waits.append(w._span[7])
            return await codec.encode_async(
                np.full(4 * 2048, i, dtype=np.uint8))

    async def run():
        tracing.SPANS.clear()
        return await asyncio.gather(fop(1), fop(2))

    a, b = asyncio.run(run())
    codec.close()
    assert a.shape == b.shape == (6, 2048) and a[0, 0] == 1 and b[0, 0] == 2
    by_name = {}
    for name, meta, thread in recorder.log:
        by_name.setdefault(name, []).append((meta, thread))
    (flush, thread), = by_name["gftpu:codec.flush"]
    assert thread.startswith("ec-codec-") and flush["parent"] == waits[0]
    assert flush["others"] == str(waits[1])
    assert (flush["fops"], flush["route"], flush["op"], flush["bytes"]) \
        == (2, "device", "encode", 2 * 4 * 2048)
    for name in ("gftpu:codec.queue", "gftpu:codec.resume"):
        assert sorted(m["parent"] for m, _t in by_name[name]) == \
            sorted(waits), name
    # queue ends where the flush starts (pool), resume on the loop
    assert all(t.startswith("ec-codec-")
               for _m, t in by_name["gftpu:codec.queue"])
    assert all(t == "MainThread"
               for _m, t in by_name["gftpu:codec.resume"])
    for name in ("gather", "h2d", "launch", "d2h", "scatter"):
        (meta, _t), = by_name[f"gftpu:codec.{name}"]
        assert meta["parent"] == flush["span"], name
    # the ring shows the same tree, under the first fop's trace
    ring = {s[3]: s for s in tracing.SPANS if s[3].startswith("codec.")}
    assert ring["codec.flush"][8] == waits[0]
    assert ring["codec.h2d"][8] == ring["codec.flush"][7]
    tree = tracing.render_tree(ring["codec.flush"][0])
    assert tree.index("ec.codec_wait") < tree.index("codec.queue") < \
        tree.index("codec.flush") < tree.index("codec.d2h") < \
        tree.index("codec.resume")
    sums = codec.dump_stats()["phases"]
    assert sums["codec.queue"]["count"] == 2 == sums["codec.resume"]["count"]
    assert sums["codec.flush"]["count"] == 1
    assert sums["codec.flush"]["seconds"] >= sums["codec.d2h"]["seconds"] > 0


def _ec_4p2(tmp_path):
    from glusterfs_tpu.utils.volspec import ec_volfile

    g = Graph.construct(ec_volfile(tmp_path, 6, 2, options={
        "cpu-extensions": "xla", "stripe-cache": "on",
        "stripe-cache-min-batch": 0, "systematic": "on"}))
    return Client(g), g.top


@pytest.mark.parametrize("kind", ["write", "degraded-read"])
def test_one_mib_through_4p2_yields_every_phase_once(tmp_path, recorder,
                                                     kind):
    """A 1 MiB write through an in-process 4+2 graph on a jax backend
    yields every write-side phase exactly once under its fop span, and
    a degraded read every read-side phase; taking the window shows as
    ``ec.lock`` on its first fop and closing it as ``ec.unlock`` on its
    last, and a fop in between has neither; the sums show in the dumps
    an operator already reads."""
    c, ec = _ec_4p2(tmp_path)
    data = os.urandom(1 << 20)
    skip = {f"gftpu:storage/posix.{fop}"
            for fop in ("writev", "readv", "xattrop")}

    def held(fop, want):
        return {fop: 1, **{f"gftpu:{p}": 1 for p in want}}

    async def run():
        await c.mount()
        tracing.ANNOTATE = recorder
        f = await c.create("/f", os.O_RDWR)
        try:
            recorder.log.clear()
            await f.write(data, 0)  # opens the window: lock, metadata
            assert _names(recorder.log)["gftpu:ec.lock"] == 1
            if kind == "write":
                fop = "gftpu:cluster/disperse.writev"
                recorder.log.clear()
                await f.write(data, 1 << 20)
                got = _names(recorder.log, skip)
                # a systematic write behind the batcher: two fan-outs,
                # the data part beside the codec wait (ISSUE 25)
                assert got == held(fop, WRITE_PHASES) | {
                    "gftpu:ec.fanout": 2}, got
                parts = [(m["part"], m["parent"]) for n, m, _t
                         in recorder.log if n == "gftpu:ec.fanout"]
                wait, = [m for n, m, _t in recorder.log
                         if n == "gftpu:ec.codec_wait"]
                assert [p for p, _ in parts] == ["data", "parity"]
                assert {par for _, par in parts} == {wait["parent"]}
                # the fop under which the window closes (max-hold
                # reached): the post-op rides its ec.unlock
                ec.opts["eager-lock-max-hold"], hold = \
                    0, ec.opts["eager-lock-max-hold"]
                recorder.log.clear()
                await f.write(data, 2 << 20)
                ec.opts["eager-lock-max-hold"] = hold
                last = _names(recorder.log, skip)
                assert last == held(fop, WRITE_PHASES | {
                    "ec.unlock", "ec.xattrop"}) | {"gftpu:ec.fanout": 3}, \
                    last
                unlock, = [m for n, m, _t in recorder.log
                           if n == "gftpu:ec.unlock"]
                post, = [m for n, m, _t in recorder.log
                         if n == "gftpu:ec.xattrop"]
                assert post["parent"] == unlock["span"]
                recorder.log.clear()
                await f.write(b"tail", (3 << 20) - 2)  # crosses EOF: RMW
                rmw = _names(recorder.log)
                assert rmw["gftpu:ec.rmw_read"] == 1 == \
                    rmw["gftpu:ec.reassemble"] == rmw["gftpu:ec.lock"]
                recorder.log.clear()
                await f.fsync()  # the window closes outside a write
                assert _names(recorder.log)["gftpu:ec.xattrop"] == 1
            else:
                await f.fsync()
                ec.set_child_up(1, False)
                recorder.log.clear()
                await f.read(4096, 0)  # opens the window again
                assert _names(recorder.log)["gftpu:ec.lock"] == 1
                recorder.log.clear()
                assert bytes(await f.read(1 << 20, 0)) == data
                got = _names(recorder.log, skip)
                assert got == held("gftpu:cluster/disperse.readv",
                                   READ_PHASES), got
        finally:
            await f.close()
            await c.unmount()

    asyncio.run(run())
    want = WRITE_PHASES if kind == "write" else READ_PHASES
    ec_sums = ec.dump_private()["phases"]
    codec_sums = ec.dump_private()["stripe_cache"]["phases"]
    assert set(ec_sums) >= {"ec.lock"} | {
        p for p in want if p.startswith("ec.")}
    # (the RMW's edge stripe, or the 4 KiB read, was padded to its
    # bucket on the way)
    assert set(codec_sums) == {"codec.gather"} | {
        p for p in WRITE_PHASES if p.startswith("codec.")}, codec_sums
    assert all(v["count"] >= 1 and v["seconds"] > 0 and v["max_ms"] > 0
               for v in {**ec_sums, **codec_sums}.values())
    # every span names its trace, itself and its parent (the mounted
    # loop's samples are no span)
    assert all({"trace", "span", "parent"} <= set(m)
               for n, m, _t in recorder.log if n != tracing.SAMPLE)


@pytest.mark.parametrize("compression", ["off", "on"])
def test_wire_send_is_a_phase_of_every_call(tmp_path, recorder, monkeypatch,
                                            compression):
    """``protocol/client._call`` opens one ``wire.send`` per call, under
    the ``protocol/client.<fop>`` span that made it, with the fop and
    the bytes it put on the socket (on both framings); the layer's
    ``dump_private()`` sums them, also with span work off."""
    vf = CLIENT_VOLFILE.replace(
        "end-volume", f"    option compression {compression}\n"
                      "    option shm-transport off\nend-volume")

    def count(dump):
        return dump["phases"]["wire.send"]["count"]

    async def run():
        server = await serve_brick(BRICK_VOLFILE.format(dir=tmp_path / "b"))
        c, g = await _connect(server.port, vf)
        top = g.top
        try:
            f = await c.create("/x", os.O_RDWR)
            before = top.dump_private()
            tracing.ANNOTATE = recorder
            recorder.log.clear()
            await f.write(os.urandom(65536), 0)
            assert bytes(await f.read(4096, 0))
            tracing.ANNOTATE = None
            after = top.dump_private()
            sends = [m for n, m, _t in recorder.log
                     if n == "gftpu:wire.send"]
            calls = after["rpc_roundtrips"] - before["rpc_roundtrips"]
            assert calls >= 2 and len(sends) == calls
            assert {"writev", "readv"} <= {m["fop"] for m in sends}
            assert sum(m["bytes"] for m in sends) == \
                after["bytes_tx"] - before["bytes_tx"]
            wrote, = [m for m in sends if m["fop"] == "writev"]
            assert wrote["bytes"] > 65536 or compression == "on"
            fops = {m["span"]: n for n, m, _t in recorder.log
                    if n.startswith("gftpu:protocol/client.")}
            assert all(fops[m["parent"]] ==
                       "gftpu:protocol/client." + m["fop"] for m in sends)
            assert count(after) - count(before) == calls
            assert after["phases"]["wire.send"]["seconds"] > 0
            # dark: the sums count on, the ring gets nothing
            monkeypatch.setattr(tracing, "ENABLED", False)
            tracing.SPANS.clear()
            await f.write(b"dark", 0)
            assert count(top.dump_private()) == count(after) + 1
            assert not tracing.SPANS
            await f.close()
        finally:
            await c.unmount()
            await server.stop()

    asyncio.run(run())


def test_core_tracing_imports_no_jax():
    """core/ stays importable in a process that must never load jax
    (bricks, glusterd): the profiler sink is injected, not imported."""
    import subprocess
    import sys

    code = ("import sys; import glusterfs_tpu.core.tracing as t; "
            "import glusterfs_tpu.core.layer; "
            "assert t.ANNOTATE is None; "
            "sys.exit('jax' in sys.modules)")
    assert subprocess.run([sys.executable, "-c", code],
                          timeout=120).returncode == 0


def test_sums_live_on_the_instance_and_count_in_the_dark(monkeypatch):
    """Sink one is a dict on the codec (or layer) that ran the phase:
    two codecs of one name in one process (a client mount and the shd
    graph; the codec rebuilt by a reconfigure) share no row, a device
    entry's ownerless phases land with the codec whose pool thread ran
    them, and with span work off the sums still count."""
    import numpy as np

    from glusterfs_tpu.ops.batch import BatchingCodec

    monkeypatch.setattr(tracing, "ENABLED", False)
    a, b = (BatchingCodec(4, 2, "xla", window=0, min_batch=0,
                          systematic=True, name="vol") for _ in "ab")
    tracing.SPANS.clear()

    async def run():
        for _ in range(3):
            await a.encode_async(np.zeros(16 * 2048, dtype=np.uint8))

    asyncio.run(run())
    try:
        sums = a.dump_stats()["phases"]
        assert {n: v["count"] for n, v in sums.items()} == dict.fromkeys(
            ("codec.queue", "codec.flush", "codec.h2d", "codec.launch",
             "codec.d2h", "codec.resume"), 3), sums
        assert a.dump_stats()["flushes"] == 3
        assert b.dump_stats()["phases"] == {} == b.phases
        assert not tracing.SPANS  # dark: sums and nothing else
    finally:
        a.close()
        b.close()


def test_a_phase_nests_under_the_phase_it_opens_in():
    """Every phase becomes its context's current span: a fop span or a
    phase opened inside one hangs under it, siblings share it, and a
    phase begun for another thread (``start(push=False)``) is nobody's
    parent by context."""
    tracing.SPANS.clear()
    sums: dict = {}
    with tracing.phase("l", "x.outer", sums) as outer:
        handed = tracing.phase("l", "x.handed", sums).start(push=False)
        with tracing.phase("l", "x.inner", sums) as inner:
            with tracing.phase(None, "x.ownerless") as deepest:
                pass
        with tracing.phase("l", "x.sibling", sums) as sibling:
            pass
        handed.stop()
    sid = {p.name: p._span[7] for p in (outer, handed, inner, deepest,
                                        sibling)}
    parent = {s[3]: s[8] for s in tracing.SPANS}
    assert parent == {"x.outer": 0, "x.handed": sid["x.outer"],
                      "x.inner": sid["x.outer"],
                      "x.ownerless": sid["x.inner"],
                      "x.sibling": sid["x.outer"]}
    # the ownerless phase took the enclosing span's layer, and no sums
    assert {s[3]: s[2] for s in tracing.SPANS}["x.ownerless"] == "l"
    assert set(tracing.phase_sums(sums)) == {
        "x.outer", "x.handed", "x.inner", "x.sibling"}


# -- the loop's clock and the pool thread's (ISSUE 34) ---------------------

MS = 1_000_000


class _Clock:
    """Stands in for ``time`` inside ``core/tracing.py``: both clocks
    stand still unless the test moves them."""

    def __init__(self):
        self.now = self.cpu = self.thread_reads = 0

    def perf_counter_ns(self):
        return self.now

    def thread_time_ns(self):
        self.thread_reads += 1
        return self.cpu

    # the same clock, as another thread asks for it (``dump``)
    def pthread_getcpuclockid(self, ident):
        return -1

    def clock_gettime_ns(self, clock):
        return self.thread_time_ns()


class _Handle:
    def __init__(self, delay, cb):
        self.delay, self.cb, self.live = delay, cb, True

    def cancel(self):
        self.live = False


class _Selector:
    """``select`` takes ``idle`` ns of the clock and returns two
    events."""

    def __init__(self, clock):
        self.clock, self.idle = clock, 0

    def select(self, timeout=None):
        self.clock.now += self.idle
        return ["r", "w"]


class _Loop:
    def __init__(self, clock):
        self._selector = _Selector(clock)
        self.timers = []

    def call_later(self, delay, cb):
        self.timers.append(_Handle(delay, cb))
        return self.timers[-1]

    def passes(self, clock, *busy_ms, idle_ms=0):
        """Callbacks of ``busy_ms`` each, a ``select`` after each."""
        self._selector.idle = idle_ms * MS
        for ms in busy_ms:
            clock.now += ms * MS
            self._selector.select(0)


@pytest.fixture
def metered(monkeypatch):
    """(clock, loop, meter): a meter on a loop whose time the test
    makes."""
    clock = _Clock()
    monkeypatch.setattr(tracing, "time", clock)
    loop = _Loop(clock)
    meter = tracing.LoopMeter.install(loop)
    yield clock, loop, meter
    tracing.ANNOTATE = None


def test_the_weighted_pass_is_the_one_an_answer_lands_in(metered):
    """One pass of 20 ms and twenty of 1 ms: the mean pass is 1.9 ms,
    but an answer arriving at a random moment lands in the long one
    half the time: ``busy_sq / busy_ns`` is (400 + 20) / 40 = 10.5."""
    clock, loop, meter = metered
    loop.passes(clock, 20, *[1] * 20, idle_ms=3)
    assert meter.passes == 21
    assert meter.busy_ns == 40 * MS and meter.select_ns == 63 * MS
    assert meter.busy_sq / meter.busy_ns == 10.5 * MS
    assert meter.dump()["weighted_pass_ms"] == 10.5
    # no thread clock in a pass: one read when the meter was made, one
    # for the dump
    assert clock.thread_reads == 2
    # every select so far was a poll (callbacks ready, no time to
    # wait); one that may block is none
    assert meter.polls == 21 and meter.poll_ns == meter.select_ns
    loop._selector.select(1.5)
    assert (meter.passes, meter.polls) == (22, 21)
    assert meter.select_ns - meter.poll_ns == 3 * MS


def test_the_slowest_pass_is_kept_across_the_periods(metered):
    """A sample carries its own period's slowest pass; the dump shows
    the slowest since the meter was installed, sampled or not."""
    clock, loop, meter = metered
    _Recorder.log = []
    loop.passes(clock, 2, 20, 4, idle_ms=1)
    assert meter.dump()["slowest_pass_ms"] == 20.0  # no profiler yet
    tracing.ANNOTATE = _Recorder
    loop.timers[-1].cb()  # the first sample opens
    loop.passes(clock, 7, 3)
    loop.timers[-1].cb()
    loop.passes(clock, 5)
    loop.timers[-1].cb()
    assert [m["slowest_pass_ns"] for _n, m, _t in _Recorder.log] == \
        [7 * MS, 5 * MS]
    assert meter.slowest_pass_ns == 20 * MS
    assert meter.dump()["slowest_pass_ms"] == 20.0


def test_install_twice_is_one_wrapper_and_the_last_user_restores(metered):
    clock, loop, meter = metered
    sel = loop._selector
    assert tracing.LoopMeter.install(loop) is meter and meter.users == 2
    assert sel.select == meter._pass and sel.select.__self__ is meter
    meter.remove()
    assert sel.__dict__["select"] == meter._pass and loop.timers[-1].live
    meter.remove()
    assert "select" not in sel.__dict__
    assert sel.select.__func__ is _Selector.select
    assert not any(h.live for h in loop.timers)
    # a loop that polls through no selector stays unmetered
    assert tracing.LoopMeter.install(object()) is None


def test_a_sample_a_period_with_the_periods_deltas_and_no_span(metered):
    """While a profiler runs every tick closes one ``gftpu:loop.sample``
    with its keys, each the period's own, and opens the next; it carries
    no ``span`` (``benchmarks/harness/spans.py`` would hang an
    always-open span over every idle gap)."""
    clock, loop, meter = metered
    _Recorder.log = []
    tracing.ANNOTATE = _Recorder
    loop.timers[-1].cb()  # opens the first sample
    assert _Recorder.log == [] and loop.timers[-1].delay == \
        tracing.SAMPLE_PERIOD == 0.1
    for k, (busy, cpu) in enumerate([((5, 1, 1), 6), ((30,), 2)]):
        loop.passes(clock, *busy, idle_ms=10)
        clock.now += 2 * MS  # the tick's own pass, under way
        clock.cpu += cpu * MS
        loop.timers[-1].cb()
        name, meta, _thread = _Recorder.log[k]
        assert name == "gftpu:loop.sample" and "span" not in meta
        assert set(meta) == set(tracing.SAMPLE_KEYS) | {"slowest_pass_ns"}
    first, second = (m for _n, m, _t in _Recorder.log)
    assert first == {
        "passes": 3, "busy_ns": 9 * MS, "select_ns": 30 * MS,
        "busy_sq": 27 * MS * MS, "cpu_ns": 6 * MS,
        "slowest_pass_ns": 5 * MS, "polls": 3, "poll_ns": 30 * MS}
    # the pass the first tick ran in ends in the second period (2 of
    # its ms before the tick were the first's); busy and select add up
    # to the time between the two ticks
    assert second == {
        "passes": 1, "busy_ns": 32 * MS, "select_ns": 10 * MS,
        "busy_sq": 32 * 32 * MS * MS, "cpu_ns": 2 * MS,
        "slowest_pass_ns": 32 * MS, "polls": 1, "poll_ns": 10 * MS}


def test_no_sample_while_no_profiler_runs(metered, monkeypatch):
    clock, loop, meter = metered
    _Recorder.log = []
    tracing.ANNOTATE = _Recorder
    monkeypatch.setattr(_Recorder, "is_enabled", staticmethod(lambda: False))
    reads = clock.thread_reads
    for _ in range(3):
        loop.passes(clock, 4, idle_ms=1)
        loop.timers[-1].cb()
    assert _Recorder.log == [] and meter._ann is None
    # such a tick asks is_enabled() and reads no clock
    assert clock.thread_reads == reads and meter._last is None
    monkeypatch.setattr(_Recorder, "is_enabled", staticmethod(lambda: True))
    loop.timers[-1].cb()
    loop.passes(clock, 4, idle_ms=1)
    # the session ends: the open sample is closed, none is opened
    monkeypatch.setattr(_Recorder, "is_enabled", staticmethod(lambda: False))
    loop.timers[-1].cb()
    assert [m["passes"] for _n, m, _t in _Recorder.log] == [1]
    assert meter._ann is None
    loop.timers[-1].cb()
    assert len(_Recorder.log) == 1
    # and with no annotation class at all (a process without jax)
    tracing.ANNOTATE = None
    loop.timers[-1].cb()
    assert len(_Recorder.log) == 1


def test_removing_the_meter_closes_the_open_sample(metered):
    clock, loop, meter = metered
    _Recorder.log = []
    tracing.ANNOTATE = _Recorder
    loop.timers[-1].cb()
    loop.passes(clock, 6, idle_ms=1)
    meter.remove()
    (name, meta, _t), = _Recorder.log
    assert meta["busy_ns"] == 6 * MS and meter._ann is None


@pytest.mark.parametrize("what", ["spin", "sleep", "idle"])
def test_on_a_cpu_off_it_and_in_select_on_a_real_loop(what):
    """On a private loop: a callback that spins 20 ms of CPU reads as
    ``busy_ns`` and ``cpu_ns``, one that sleeps 20 ms as ``busy_ns``
    without ``cpu_ns``, an idle 50 ms as ``select_ns``; busy and
    select add up to the time between the two readings."""
    import time

    def spin():
        end = time.thread_time_ns() + 20 * MS
        while time.thread_time_ns() < end:
            pass

    async def run():
        meter = tracing.LoopMeter.install(asyncio.get_running_loop())
        await asyncio.sleep(0)
        t0, a = time.perf_counter_ns(), meter._reading()
        if what == "idle":
            await asyncio.sleep(0.05)
        else:
            spin() if what == "spin" else time.sleep(0.02)
            await asyncio.sleep(0)  # the pass ends
        b, t1 = meter._reading(), time.perf_counter_ns()
        meter.remove()
        return dict(zip(tracing.SAMPLE_KEYS,
                        (y - x for x, y in zip(a, b)))), t1 - t0, meter

    d, elapsed, meter = asyncio.run(run())
    assert abs(d["busy_ns"] + d["select_ns"] - elapsed) < 2 * MS
    if what == "idle":
        assert d["select_ns"] >= 0.6 * elapsed >= 30 * MS
        assert d["cpu_ns"] < 10 * MS
    else:
        assert d["busy_ns"] >= 20 * MS > d["select_ns"]
        assert meter.dump()["slowest_pass_ms"] >= 20
        assert d["busy_sq"] >= (20 * MS) ** 2
        if what == "spin":
            assert 20 * MS <= d["cpu_ns"] <= d["busy_ns"] + MS
        else:
            assert d["cpu_ns"] < 10 * MS


def _posix_client(tmp_path):
    return Client(Graph.construct(BRICK_VOLFILE.format(dir=tmp_path)))


def test_mount_meters_the_loop_and_unmount_leaves_nothing(tmp_path):
    """``Client.mount`` installs the meter on the running loop, two
    mounts on one loop share it, the statedump has the ``loop`` section,
    and after the last ``unmount`` ``select`` is the selector's own and
    no sample timer is pending."""
    async def run():
        loop = asyncio.get_running_loop()
        sel = loop._selector
        a, b = _posix_client(tmp_path / "a"), _posix_client(tmp_path / "b")
        assert a.statedump()["loop"] == {"metered": False}
        await a.mount()
        await a.mount()  # mounted again: still one user
        await b.mount()
        meter = a.loop_meter
        assert meter is b.loop_meter and meter.users == 2
        assert sel.select == meter._pass
        await asyncio.sleep(0.25)  # two ticks, no profiler
        dump = a.statedump()["loop"]
        assert set(dump) == {"metered", "passes", "busy_s", "select_s",
                             "polls", "poll_s", "cpu_s",
                             "weighted_pass_ms", "slowest_pass_ms"}
        assert dump["metered"] is True and dump["passes"] >= 2
        assert dump["select_s"] > 0.2 > dump["busy_s"] >= dump["cpu_s"] * 0.5
        await a.unmount()
        assert a.loop_meter is None and sel.select == meter._pass
        assert a.statedump()["loop"] == {"metered": False}
        await b.unmount()
        assert "select" not in sel.__dict__
        assert sel.select.__func__ is type(sel).select
        assert meter._timer.cancelled()
        assert not [h for h in loop._scheduled if not h.cancelled()
                    and getattr(h._callback, "__self__", None) is meter]

    asyncio.run(run())


def test_another_threads_dump_reads_the_loops_threads_clock(tmp_path):
    """``SyncClient.statedump()`` runs on the caller's thread: the
    section's ``cpu_s`` is the loop's thread's CPU time all the same,
    not the caller's."""
    import time

    from glusterfs_tpu.api.glfs import SyncClient

    def spin():
        end = time.thread_time_ns() + 30 * MS
        while time.thread_time_ns() < end:
            pass

    c = SyncClient(Graph.construct(BRICK_VOLFILE.format(dir=tmp_path)))
    try:
        c.mount()
        meter = c.loop_meter
        assert meter.thread == c._thread.ident != threading.get_ident()
        before = c.statedump()["loop"]
        c._loop.call_soon_threadsafe(spin)
        # on a busy machine 30 ms of the thread's CPU can take most of
        # a quarter second: wait for the idle time to show, not a fixed
        # sleep
        deadline = time.monotonic() + 10
        while True:
            time.sleep(0.25)
            dump = c.statedump()["loop"]
            if dump["select_s"] - before["select_s"] > 0.15 \
                    or time.monotonic() > deadline:
                break
        assert dump["metered"] and dump["passes"] >= before["passes"] + 2
        assert dump["cpu_s"] - before["cpu_s"] >= 0.03
        assert dump["busy_s"] - before["busy_s"] >= 0.03
        assert dump["slowest_pass_ms"] >= 30
        assert dump["select_s"] - before["select_s"] > 0.15
    finally:
        c.close()
    assert c.loop_meter is None
    assert "select" not in c._loop._selector.__dict__


def test_a_cpu_phase_reads_the_thread_clock_only_under_an_annotation(
        monkeypatch):
    """``phase(cpu=True)`` puts ``cpu_ns`` on its span's annotation;
    with no annotation (no profiler) it reads no clock, and a phase
    without the flag never does."""
    clock = _Clock()
    monkeypatch.setattr(tracing, "time", clock)
    monkeypatch.setattr(tracing, "ENABLED", True)
    _Recorder.log = []
    sums: dict = {}
    try:
        with tracing.phase("l", "x.dark", sums, cpu=True):  # no ANNOTATE
            clock.cpu += 5
        assert clock.thread_reads == 0
        tracing.ANNOTATE = _Recorder
        with tracing.phase("l", "x.pool", sums, cpu=True, op="encode"):
            clock.cpu += 7
            clock.now += 10
        assert clock.thread_reads == 2
        with tracing.phase("l", "x.loop", sums):
            clock.cpu += 9
        assert clock.thread_reads == 2
        monkeypatch.setattr(_Recorder, "is_enabled",
                            staticmethod(lambda: False))
        with tracing.phase("l", "x.off", sums, cpu=True):
            pass
        assert clock.thread_reads == 2
        monkeypatch.setattr(tracing, "ENABLED", False)
        with tracing.phase("l", "x.disabled", sums, cpu=True):
            pass
        assert clock.thread_reads == 2
    finally:
        tracing.ANNOTATE = None
    meta = {n: m for n, m, _t in _Recorder.log}
    assert set(meta) == {"gftpu:x.pool", "gftpu:x.loop"}
    assert meta["gftpu:x.pool"]["cpu_ns"] == 7
    assert meta["gftpu:x.pool"]["op"] == "encode"
    assert "cpu" not in meta["gftpu:x.pool"]
    assert "cpu_ns" not in meta["gftpu:x.loop"]
    assert tracing.phase_sums(sums)["x.pool"]["count"] == 1


def test_the_pool_threads_phases_carry_cpu_ns_and_the_loops_none(recorder):
    """The six phases that begin and end on one pool thread carry their
    thread's CPU time; ``codec.queue`` and ``codec.resume`` cross
    threads, ``ec.codec_wait`` crosses ``await``: no thread clock says
    anything about them."""
    import numpy as np

    from glusterfs_tpu.ops.batch import BatchingCodec

    codec = BatchingCodec(4, 2, "xla", window=0.01, min_batch=0,
                          systematic=True, name="batcher")
    tracing.ANNOTATE = recorder

    async def fop(i):
        with tracing.phase("batcher", "ec.codec_wait"):
            return await codec.encode_async(
                np.full(4 * 2048, i, dtype=np.uint8))

    async def run():
        return await asyncio.gather(fop(1), fop(2))

    asyncio.run(run())
    codec.close()
    by_name = {}
    for name, meta, thread in recorder.log:
        by_name.setdefault(name[len("gftpu:"):], []).append((meta, thread))
    pool = {"codec.flush", "codec.gather", "codec.h2d", "codec.launch",
            "codec.d2h", "codec.scatter"}
    assert pool | {"codec.queue", "codec.resume", "ec.codec_wait"} \
        == set(by_name)
    for name, found in by_name.items():
        for meta, thread in found:
            assert ("cpu_ns" in meta) == (name in pool), name
            if name in pool:
                assert thread.startswith("ec-codec-")
                assert isinstance(meta["cpu_ns"], int) and meta["cpu_ns"] >= 0
    (flush, _t), = by_name["codec.flush"]
    inner = sum(m["cpu_ns"] for n in pool - {"codec.flush"}
                for m, _t in by_name[n])
    assert flush["cpu_ns"] >= inner > 0
