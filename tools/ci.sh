#!/usr/bin/env bash
# ci.sh — the one-command pre-merge gate (ISSUE 3 satellite; the
# regression signal ROADMAP's tier-1 bar depends on):
#
#   0. graft-lint               tools/graft_lint cross-file invariant
#                               suite (fop/option/async/errno/metrics
#                               planes, ISSUE 13) — runs FIRST because
#                               it is the cheapest signal (<30s);
#                               --json archived to /tmp/gftpu-ci
#   1. tools/flake_gate.sh      tier-1 twice, diffing the failure sets
#                               (stable failures -> exit 1, flakes -> 2)
#   3. metrics smoke            start a 1-brick volume, drive two fops,
#                               scrape the unified registry and assert
#                               the required families are present and
#                               monotonic (ISSUE 4: a silently-empty
#                               metrics dump must not merge)
#   4. gateway smoke            serve a managed 1-brick volume through
#                               the HTTP object gateway: PUT/GET/
#                               ranged-GET/DELETE/list over real HTTP,
#                               gateway registry families asserted, and
#                               the glusterd-spawned daemon lifecycle
#                               (`volume gateway start|status|stop`)
#                               exercised end to end (ISSUE 6)
#   5. concurrency smoke        1-brick volume served with
#                               server.event-threads=4: interleaved
#                               pipelined writes from one connection
#                               dispatch in order and read back
#                               byte-identical, a second connection
#                               proceeds in parallel, the
#                               gftpu_event_threads* families are
#                               present and moving, and the managed
#                               op-version-9 volume-set path applies
#                               the key to a live brick (ISSUE 7)
#   6. mesh smoke               the mesh-codec data plane under 8
#                               forced host devices: the parity +
#                               routing tests of test_mesh_plane.py,
#                               then a batched encode through a
#                               mesh-armed BatchingCodec asserting the
#                               gftpu_mesh_launches_total family
#                               appears with origin=serve (ISSUE 8)
#   7. chaos smoke              ONE bounded failure-containment
#                               scenario (tools/chaos.py
#                               degraded_read): brick SIGKILL
#                               mid-write -> degraded reads
#                               byte-identical -> restart -> heal
#                               converges -> the healed brick serves,
#                               with the zero-leak audit (ISSUE 9)
#   8. delta-write smoke        managed systematic-by-default volume
#                               serves an unaligned write via the
#                               parity-delta path (ISSUE 10)
#   9. rebalance smoke          add-brick + managed rebalance daemon
#                               converges, task row + families,
#                               bytes exact (ISSUE 11)
#  10. process-plane smoke      workers=2 managed gateway pool:
#                               byte-exact PUT/GET through the
#                               shared-nothing workers, worker
#                               SIGKILL respawns and keeps serving
#                               (ISSUE 12)
#  11. lease smoke              hot GETs off the lease-held gateway
#                               object cache at zero wire fops,
#                               recall-exact coherence, cache/lease
#                               families, v15 keys (ISSUE 16)
#  12. qos smoke                per-client admission shed at a tight
#                               fops cap on both wire paths,
#                               gftpu_qos_* family monotonicity, live
#                               v16 volume-set flip, shaping column in
#                               volume-status-deep (ISSUE 17)
#  13. shm smoke                same-host bulk lane arms against a
#                               managed brick, shm families move both
#                               directions, live volume-set off
#                               downgrades inline (ISSUE 18)
#  14. incident smoke           managed volume with
#                               diagnostics.incident-dir armed: brick
#                               SIGKILL auto-captures a local bundle,
#                               `volume incident list` shows it,
#                               `show` round-trips the JSON (ISSUE 19)
#  15. alert smoke              managed volume with a v19 error-ratio
#                               SLO rule: an error-gen readv storm
#                               raises the alert in `volume alerts`,
#                               ALERT_RAISED rides real UDP eventsd and
#                               auto-captures an incident bundle whose
#                               history section shows the error ramp;
#                               healthy traffic clears it and the
#                               CLEARED edge lands in alert history
#                               (ISSUE 20)
#
# Usage:  tools/ci.sh [extra pytest args for the tier-1 runs...]
# Exit: first failing stage's code; 0 = mergeable.

set -u
cd "$(dirname "$0")/.."

echo "== ci: stage 0 — graft-lint (cross-file invariants) =="
mkdir -p /tmp/gftpu-ci
timeout -k 5 60 env JAX_PLATFORMS=cpu \
    python tools/graft_lint/run.py --json \
    > /tmp/gftpu-ci/graft_lint.json
lint_rc=$?
if [ $lint_rc -ne 0 ]; then
    echo "ci: graft-lint findings (archived at"
    echo "    /tmp/gftpu-ci/graft_lint.json) — not mergeable"
    python - <<'PYEOF'
import json
try:
    d = json.load(open("/tmp/gftpu-ci/graft_lint.json"))
except Exception as e:  # internal error/timeout: archive is not JSON
    print(f"  (no findings archive — linter internal error or "
          f"timeout: {e})")
else:
    for f in d.get("findings", []):
        print(f"  {f['path']}:{f['line']}: {f['code']} {f['message']}")
PYEOF
    exit $lint_rc
fi
python - <<'PYEOF'
import json
d = json.load(open("/tmp/gftpu-ci/graft_lint.json"))
per = d.get("checker_seconds", {})
slow = sorted(per.items(), key=lambda kv: -kv[1])[:3]
pretty = ", ".join(f"{k} {v:.1f}s" for k, v in slow)
print(f"ci: lint clean ({d['seconds']}s of a 30s budget; "
      f"slowest: {pretty}; archived with per-checker timings)")
PYEOF

echo "== ci: flake gate (tier-1 x2) =="
tools/flake_gate.sh "$@"
gate_rc=$?
if [ $gate_rc -eq 1 ]; then
    echo "ci: STABLE tier-1 failures — not mergeable"
    exit 1
fi

echo "== ci: metrics smoke (1-brick volume, scrape + monotonicity,"
echo "       status clients + eventsapi) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'EOF'
import asyncio, os, tempfile

from glusterfs_tpu.api.glfs import Client
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.core.metrics import REGISTRY
from glusterfs_tpu.daemon import serve_brick

BRICK = """
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
"""
CLIENT = """
volume c0
    type protocol/client
    option remote-host 127.0.0.1
    option remote-port {port}
    option remote-subvolume stats
end-volume
"""
REQUIRED = ("gftpu_wire_blob_stats",
            "gftpu_decode_program_cache_events_total",
            "gftpu_codec_device_probe",
            "gftpu_slow_fops_total")

def tx_bytes(snap):
    return sum(v for l, v in snap["gftpu_wire_blob_stats"]["samples"]
               if l.get("counter") == "tx_bytes")

async def main():
    base = tempfile.mkdtemp(prefix="metrics-smoke")
    server = await serve_brick(BRICK.format(dir=os.path.join(base, "b")))
    g = Graph.construct(CLIENT.format(port=server.port))
    c = Client(g)
    await c.mount()
    for _ in range(200):
        if g.top.connected:
            break
        await asyncio.sleep(0.05)
    assert g.top.connected, "client never connected"
    snap0 = REGISTRY.snapshot()
    for fam in REQUIRED:
        assert fam in snap0, f"missing metrics family {fam}"
    await c.write_file("/smoke", b"m" * 65536)      # fop 1
    assert await c.read_file("/smoke") == b"m" * 65536  # fop 2
    snap1 = REGISTRY.snapshot()
    assert tx_bytes(snap1) > tx_bytes(snap0), \
        "wire blob counters not monotonic across fops"
    rpc = await g.top.remote("metrics_dump")
    assert "gftpu_wire_blob_stats" in rpc, "metrics_dump RPC empty"
    text = REGISTRY.render()
    assert "# TYPE gftpu_wire_blob_stats counter" in text
    # per-client accounting rode the same fops (ISSUE 5): the brick
    # names this client and its byte counters moved
    st = await g.top._call("__status__", ("clients",), {})
    rows = [r for r in st["clients"] if not r["mgmt"]]
    assert rows and rows[0]["bytes_rx"] >= 65536, \
        "client accounting row missing or empty"
    await c.unmount()
    await server.stop()

    # -- managed path: glusterd volume + eventsd (ISSUE 5) --------------
    from glusterfs_tpu.mgmt.eventsd import EventsDaemon
    from glusterfs_tpu.mgmt.glusterd import (Glusterd, MgmtClient,
                                             mount_volume)

    ed = EventsDaemon()
    udp, ctl = await ed.start()
    os.environ["GFTPU_EVENTSD"] = f"127.0.0.1:{udp}"
    os.environ["GFTPU_EVENTSD_CTL"] = f"127.0.0.1:{ctl}"
    d = Glusterd(os.path.join(base, "gd"))
    await d.start()
    try:
        async with MgmtClient(d.host, d.port) as mc:
            await mc.call("volume-create", name="smoke",
                          vtype="distribute",
                          bricks=[{"path": os.path.join(base, "vb0")}])
            await mc.call("volume-start", name="smoke")
        m = await mount_volume(d.host, d.port, "smoke")
        try:
            await m.write_file("/s", b"s" * 65536)
            st = await d.op_volume_status_deep("smoke", "clients")
            assert "partial" not in st, st
            rows = [r for r in
                    st["bricks"]["smoke-brick-0"]["clients"]
                    if not r["mgmt"]]
            assert rows and rows[0]["bytes_rx"] >= 65536, \
                f"volume status clients: no accounted client row: {st}"
            ev = await d.op_eventsapi("status")
            assert ev["nodes"], "eventsapi status empty"
            ok = False
            for _ in range(50):
                recent = (await d.op_eventsapi_local("recent"))["events"]
                if any(e.get("event") == "CLIENT_CONNECT"
                       for e in recent):
                    ok = True
                    break
                await asyncio.sleep(0.1)
            assert ok, "no CLIENT_CONNECT in eventsd history"
        finally:
            await m.unmount()
    finally:
        await d.stop()
        await ed.stop()
    print("metrics smoke: families present, counters monotonic, "
          "client accounting + CLIENT_CONNECT event observed")

asyncio.run(main())
EOF
smoke_rc=$?
if [ $smoke_rc -ne 0 ]; then
    echo "ci: metrics smoke failed — not mergeable"
    exit $smoke_rc
fi

echo "== ci: gateway smoke (managed volume, real HTTP, registry"
echo "       families, spawned-daemon lifecycle) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'EOF'
import asyncio, json, os, tempfile

from glusterfs_tpu.api.glfs import Client, wait_connected
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.core.metrics import REGISTRY
from glusterfs_tpu.gateway import ClientPool, ObjectGateway
from glusterfs_tpu.gateway.minihttp import fetch as http
from glusterfs_tpu.mgmt.glusterd import Glusterd, MgmtClient

async def main():
    base = tempfile.mkdtemp(prefix="gw-smoke")
    d = Glusterd(os.path.join(base, "gd"))
    await d.start()
    try:
        async with MgmtClient(d.host, d.port) as c:
            await c.call("volume-create", name="gwv",
                         vtype="distribute",
                         bricks=[{"path": os.path.join(base, "b0")}])
            await c.call("volume-start", name="gwv")
            spec = await c.call("getspec", name="gwv")

        # in-process gateway over the managed volfile: the dialect +
        # the registry families live in THIS process for asserting
        async def factory():
            g = Graph.construct(spec["volfile"])
            cl = Client(g)
            await cl.mount()
            await wait_connected(g)
            return cl

        gw = ObjectGateway(ClientPool(factory, 2), volume="gwv")
        await gw.start()
        H, P = gw.host, gw.port
        payload = bytes(range(256)) * 256  # 64 KiB
        st, _, _ = await http(H, P, "PUT", "/bkt")
        assert st == 200, st
        st, hd, _ = await http(H, P, "PUT", "/bkt/dir/obj",
                               body=payload)
        assert st == 200 and hd.get("etag"), (st, hd)
        st, _, data = await http(H, P, "GET", "/bkt/dir/obj")
        assert st == 200 and data == payload
        st, hd, data = await http(H, P, "GET", "/bkt/dir/obj",
                                  headers={"range": "bytes=100-4099"})
        assert st == 206 and data == payload[100:4100], st
        assert hd["content-range"] == f"bytes 100-4099/{len(payload)}"
        st, _, data = await http(H, P, "GET", "/bkt?list&delimiter=/")
        out = json.loads(data)
        assert st == 200 and out["common_prefixes"] == ["dir/"], out
        st, _, _ = await http(H, P, "DELETE", "/bkt/dir/obj")
        assert st == 204, st
        st, _, _ = await http(H, P, "GET", "/bkt/dir/obj")
        assert st == 404, st
        snap = REGISTRY.snapshot()
        for fam in ("gftpu_gateway_requests_total",
                    "gftpu_gateway_request_seconds",
                    "gftpu_gateway_inflight",
                    "gftpu_gateway_bytes_total",
                    "gftpu_gateway_body_writes_total",
                    "gftpu_gateway_throttled_total",
                    "gftpu_gateway_events_total"):
            assert fam in snap, f"missing gateway family {fam}"
        reqs = {(s[0]["method"], s[0]["status"]): s[1] for s in
                snap["gftpu_gateway_requests_total"]["samples"]}
        assert reqs[("GET", "200")] >= 1 and reqs[("PUT", "200")] >= 2
        await gw.stop()

        # spawned-daemon lifecycle: volume gateway start -> HTTP ->
        # status -> stop (the CLI path, sans argparse)
        st = await d.op_volume_gateway("gwv", "start")
        port = 0
        for _ in range(600):
            st = await d.op_volume_gateway("gwv", "status")
            if st["gateway"]["online"] and st["gateway"]["port"]:
                port = st["gateway"]["port"]
                break
            await asyncio.sleep(0.1)
        assert port, f"spawned gateway never came up: {st}"
        s = 0
        for _ in range(100):
            try:
                s, _, _ = await http("127.0.0.1", port, "PUT", "/lb")
                if s == 200:
                    break
            except (ConnectionError, OSError):
                pass
            await asyncio.sleep(0.1)
        assert s == 200, f"spawned gateway unreachable (last: {s})"
        s, _, _ = await http("127.0.0.1", port, "PUT", "/lb/k",
                             body=b"spawned")
        assert s == 200
        s, _, data = await http("127.0.0.1", port, "GET", "/lb/k")
        assert s == 200 and data == b"spawned"
        await d.op_volume_gateway("gwv", "stop")
        for _ in range(100):
            st = await d.op_volume_gateway("gwv", "status")
            if not st["gateway"]["online"]:
                break
            await asyncio.sleep(0.1)
        assert not st["gateway"]["online"], st
    finally:
        await d.stop()
    print("gateway smoke: dialect + ranged GET + listing over real "
          "HTTP, families present, spawned lifecycle green")

asyncio.run(main())
EOF
gw_rc=$?
if [ $gw_rc -ne 0 ]; then
    echo "ci: gateway smoke failed — not mergeable"
    exit $gw_rc
fi

echo "== ci: concurrency smoke (event-threads=4, interleaved clients,"
echo "       ordering + gftpu_event_threads families) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'EOF'
import asyncio, os, tempfile

from glusterfs_tpu.api.glfs import Client
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.core.layer import Loc, walk
from glusterfs_tpu.core.metrics import REGISTRY
from glusterfs_tpu.daemon import serve_brick
from glusterfs_tpu.storage.posix import PosixLayer

BRICK = """
volume posix
    type storage/posix
    option directory {dir}
end-volume
volume locks
    type features/locks
    subvolumes posix
end-volume
volume srv
    type protocol/server
    option event-threads 4
    subvolumes locks
end-volume
"""
CLIENT = """
volume c0
    type protocol/client
    option remote-host 127.0.0.1
    option remote-port {port}
    option remote-subvolume srv
    option event-threads 2
end-volume
"""

async def connect(port):
    g = Graph.construct(CLIENT.format(port=port))
    c = Client(g)
    await c.mount()
    for _ in range(200):
        if g.top.connected:
            break
        await asyncio.sleep(0.05)
    assert g.top.connected, "client never connected"
    return c, g.top

async def main():
    base = tempfile.mkdtemp(prefix="evt-smoke")
    server = await serve_brick(BRICK.format(dir=os.path.join(base, "b")))
    assert server.event_pool().size == 4, server.event_pool().size
    c1, cl1 = await connect(server.port)
    c2, cl2 = await connect(server.port)

    # ordering: 16 pipelined 8KiB writes from ONE connection must
    # enter the brick graph in send order through the 4-thread plane
    arrivals = []
    real = PosixLayer.writev
    async def recording(self, fd, data, offset, *a, **kw):
        arrivals.append(offset)
        return await real(self, fd, data, offset, *a, **kw)
    chunk = 8192
    fd, _ = await cl1.create(Loc("/ord"), os.O_CREAT | os.O_RDWR, 0o644)
    PosixLayer.writev = recording
    try:
        await asyncio.gather(*(
            asyncio.ensure_future(
                cl1.writev(fd, bytes([i]) * chunk, i * chunk))
            for i in range(16)))
    finally:
        PosixLayer.writev = real
    assert arrivals == [i * chunk for i in range(16)], \
        f"dispatch reordered: {arrivals}"
    # interleaved second connection, byte identity on both
    await asyncio.gather(
        c1.write_file("/a", b"a" * 65536),
        c2.write_file("/b", b"b" * 65536))
    assert await c2.read_file("/a") == b"a" * 65536
    assert await c1.read_file("/b") == b"b" * 65536
    assert await c1.read_file("/ord") == b"".join(
        bytes([i]) * chunk for i in range(16))

    snap = REGISTRY.snapshot()
    for fam in ("gftpu_event_threads", "gftpu_event_threads_busy",
                "gftpu_event_frames_total"):
        assert fam in snap, f"missing family {fam}"
    pools = {s[0]["pool"]: s[1]
             for s in snap["gftpu_event_threads"]["samples"]}
    assert pools.get("srv") == 4, pools
    turned = sum(s[1] for s in
                 snap["gftpu_event_frames_total"]["samples"]
                 if s[0]["pool"] == "srv")
    assert turned > 0, "no frames turned on the brick pool"
    await c1.unmount()
    await c2.unmount()
    await server.stop()

    # managed path: the op-version-9 key reaches a live brick
    # subprocess through `volume set` (glusterd gating + volgen map +
    # live reconfigure)
    from glusterfs_tpu.mgmt.glusterd import (Glusterd, MgmtClient,
                                             mount_volume)

    d = Glusterd(os.path.join(base, "gd"))
    await d.start()
    try:
        async with MgmtClient(d.host, d.port) as mc:
            await mc.call("volume-create", name="evt",
                          vtype="distribute",
                          bricks=[{"path": os.path.join(base, "vb0")}])
            await mc.call("volume-start", name="evt")
            await mc.call("volume-set", name="evt",
                          key="server.event-threads", value="4")
            await mc.call("volume-set", name="evt",
                          key="client.event-threads", value="2")
        m = await mount_volume(d.host, d.port, "evt")
        try:
            await m.write_file("/s", b"s" * 65536)
            assert await m.read_file("/s") == b"s" * 65536
            g = m.graph
            cl = next(l for l in walk(g.top)
                      if l.type_name == "protocol/client")
            rpc = await cl._call("metrics_dump", (), {})
            pools = {s[0]["pool"]: s[1] for s in
                     rpc["gftpu_event_threads"]["samples"]}
            assert any(v == 4 for v in pools.values()), \
                f"brick pool not resized by volume set: {pools}"
        finally:
            await m.unmount()
    finally:
        await d.stop()
    print("concurrency smoke: ordering held through 4 frame turners, "
          "interleaved clients byte-identical, families present, "
          "managed volume-set applied event-threads=4 live")

asyncio.run(main())
EOF
evt_rc=$?
if [ $evt_rc -ne 0 ]; then
    echo "ci: concurrency smoke failed — not mergeable"
    exit $evt_rc
fi

echo "== ci: mesh smoke (parity + routing on 8 forced host devices,"
echo "       gftpu_mesh_launches_total after a batched encode) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python -m pytest tests/test_mesh_plane.py -q -m 'not slow' \
    -p no:cacheprovider -p no:xdist -p no:randomly
mesh_rc=$?
if [ $mesh_rc -ne 0 ]; then
    echo "ci: mesh parity/routing tests failed — not mergeable"
    exit $mesh_rc
fi
timeout -k 10 180 env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" python - <<'EOF'
import asyncio, numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from glusterfs_tpu.core.metrics import REGISTRY
from glusterfs_tpu.ops import gf256
from glusterfs_tpu.ops.batch import BatchingCodec

async def main():
    codec = BatchingCodec(4, 2, "ref", mesh=True, min_batch=0,
                          window=0.005)
    assert await codec.ensure_mesh(), codec._mesh_state
    datas = [np.random.default_rng(i).integers(0, 256, 4 * 512 * 4,
                                               dtype=np.uint8)
             for i in range(6)]
    outs = await asyncio.gather(*(codec.encode_async(d) for d in datas))
    for d, o in zip(datas, outs):
        assert np.array_equal(o, gf256.ref_encode(d, 4, 6)), "parity"
    snap = REGISTRY.snapshot()
    fam = snap.get("gftpu_mesh_launches_total")
    assert fam, "gftpu_mesh_launches_total family missing"
    serve = [s for s in fam["samples"]
             if s[0].get("op") == "encode"
             and s[0].get("origin") == "serve"]
    assert serve and serve[0][1] >= 1, fam["samples"]
    assert codec.max_batch == 6, codec.max_batch
    devs = {s[0]["axis"]: s[1]
            for s in snap["gftpu_mesh_devices"]["samples"]}
    assert devs.get("total") == 8, devs
    codec.close()
    print("mesh smoke: 6 concurrent encodes coalesced onto the "
          "(dp, frag) mesh, launches family present, parity held")

asyncio.run(main())
EOF
mesh_rc=$?
if [ $mesh_rc -ne 0 ]; then
    echo "ci: mesh smoke failed — not mergeable"
    exit $mesh_rc
fi

echo "== ci: chaos smoke (brick kill -> degraded read parity ->"
echo "       restart -> heal converges; zero-leak audit) =="
timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python tools/chaos.py --scenario degraded_read --json
chaos_rc=$?
if [ $chaos_rc -ne 0 ]; then
    echo "ci: chaos smoke failed — not mergeable"
    exit $chaos_rc
fi

echo "== ci: delta-write smoke (managed systematic volume, unaligned"
echo "       write -> gftpu_ec_delta_writes_total monotonicity) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'EOF'
import asyncio, os, shutil, tempfile

async def main():
    from glusterfs_tpu.core.layer import walk
    from glusterfs_tpu.core.metrics import REGISTRY
    from glusterfs_tpu.mgmt.glusterd import (Glusterd, MgmtClient,
                                             mount_volume)

    base = tempfile.mkdtemp(prefix="ci-delta")
    d = Glusterd(os.path.join(base, "gd"))
    await d.start()
    try:
        async with MgmtClient(d.host, d.port) as c:
            await c.call("volume-create", name="dv", vtype="disperse",
                         redundancy=2,
                         bricks=[{"path": os.path.join(base, f"b{i}")}
                                 for i in range(6)])
            info = await c.call("volume-info", name="dv")
            assert info["dv"].get("systematic") == 1, \
                "disperse create did not default systematic at op12"
            await c.call("volume-start", name="dv")
        cl = await mount_volume(d.host, d.port, "dv")
        try:
            ec = next(l for l in walk(cl.graph.top)
                      if l.type_name == "cluster/disperse")
            data = bytes(range(256)) * 32  # 8 KiB = 4 stripes at 4+2
            await cl.write_file("/f", data)

            def fam(name):
                snap = REGISTRY.snapshot()
                return sum(s[1] for s in snap[name]["samples"]
                           if s[0].get("layer") == ec.name)

            d0 = fam("gftpu_ec_delta_writes_total")
            f = await cl.open("/f")
            await f.write(b"Q" * 700, 1000)  # sub-stripe, inside size
            await f.close()
            d1 = fam("gftpu_ec_delta_writes_total")
            assert d1 == d0 + 1, (d0, d1)
            saved = fam("gftpu_ec_delta_bytes_saved_total")
            assert saved > 0, "delta path saved nothing?"
            exp = bytearray(data); exp[1000:1700] = b"Q" * 700
            got = await cl.read_file("/f")
            assert bytes(got) == bytes(exp), "delta smoke parity"
        finally:
            await cl.unmount()
    finally:
        await d.stop()
        shutil.rmtree(base, ignore_errors=True)
    print("delta smoke: managed systematic-by-default volume served an "
          "unaligned write via the parity-delta path (family +1, "
          "bytes-saved > 0, bytes exact)")

asyncio.run(main())
EOF
delta_rc=$?
if [ $delta_rc -ne 0 ]; then
    echo "ci: delta-write smoke failed — not mergeable"
    exit $delta_rc
fi

echo "== ci: rebalance smoke (managed volume, add-brick, daemon"
echo "       start -> status converges, families present) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python - <<'EOF'
import asyncio, json, os, shutil, tempfile, time

async def main():
    from glusterfs_tpu.mgmt.glusterd import (Glusterd, MgmtClient,
                                             mount_volume)

    base = tempfile.mkdtemp(prefix="ci-rebal")
    d = Glusterd(os.path.join(base, "gd"))
    await d.start()
    try:
        async with MgmtClient(d.host, d.port) as c:
            await c.call("volume-create", name="rv", vtype="distribute",
                         redundancy=0,
                         bricks=[{"path": os.path.join(base, f"b{i}")}
                                 for i in range(2)])
            await c.call("volume-start", name="rv")
        cl = await mount_volume(d.host, d.port, "rv")
        data = {}
        try:
            for dd in range(3):
                await cl.mkdir(f"/d{dd}")
                for i in range(5):
                    p = f"/d{dd}/f{i}"
                    data[p] = f"{p}-payload".encode() * 150
                    await cl.write_file(p, data[p])
            async with MgmtClient(d.host, d.port) as c:
                await c.call("volume-add-brick", name="rv",
                             bricks=[{"path": os.path.join(base, "b2")}])
                out = await c.call("volume-rebalance", name="rv",
                                   action="start")
                assert out["status"] == "started", out
                deadline = time.monotonic() + 240
                while True:
                    st = await c.call("volume-rebalance", name="rv",
                                      action="status")
                    rb = st["rebalance"]
                    if rb.get("status") in ("completed", "failed"):
                        break
                    assert time.monotonic() < deadline, rb
                    await asyncio.sleep(0.3)
                assert rb["status"] == "completed", rb
                ctr = rb["counters"]
                assert ctr["moved"] >= 1 and ctr["failed"] == 0, ctr
                assert ctr["scanned"] == ctr["moved"] + ctr["skipped"], ctr
                vs = await c.call("volume-status", name="rv")
                kinds = [t["type"] for t in vs.get("tasks", [])]
                assert "rebalance" in kinds, vs.get("tasks")
            with open(os.path.join(d.workdir,
                                   "rebalanced-rv.json")) as f:
                fams = json.load(f)["families"]
            for fam in ("gftpu_rebalance_files_total",
                        "gftpu_rebalance_bytes_total",
                        "gftpu_rebalance_failures_total",
                        "gftpu_rebalance_phase"):
                assert fam in fams, fam
            for p, body in data.items():
                assert bytes(await cl.read_file(p)) == body, p
        finally:
            await cl.unmount()
    finally:
        await d.stop()
        shutil.rmtree(base, ignore_errors=True)
    print("rebalance smoke: add-brick + managed daemon converged "
          "(moved>=1, task row rendered, all four gftpu_rebalance_* "
          "families in the daemon's snapshot, bytes exact)")

asyncio.run(main())
EOF
rebal_rc=$?
if [ $rebal_rc -ne 0 ]; then
    echo "ci: rebalance smoke failed — not mergeable"
    exit $rebal_rc
fi

echo "== ci: process-plane smoke (workers=2 managed gateway,"
echo "       byte-exact PUT/GET, worker respawn) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python - <<'EOF'
import asyncio, json, os, shutil, signal, tempfile, time

async def main():
    from glusterfs_tpu.mgmt.glusterd import Glusterd, MgmtClient
    from glusterfs_tpu.gateway.minihttp import fetch as http

    base = tempfile.mkdtemp(prefix="ci-procplane")
    d = Glusterd(os.path.join(base, "gd"))
    await d.start()
    try:
        async with MgmtClient(d.host, d.port) as c:
            await c.call("volume-create", name="pv", vtype="distribute",
                         bricks=[{"path": os.path.join(base, "b0")}])
            await c.call("volume-start", name="pv")
            await c.call("volume-set", name="pv",
                         key="gateway.workers", value="2")
            await c.call("volume-gateway", name="pv", action="start")
            port = 0
            for _ in range(600):
                st = await c.call("volume-gateway", name="pv",
                                  action="status")
                if st["gateway"]["online"] and st["gateway"]["port"]:
                    port = st["gateway"]["port"]
                    break
                await asyncio.sleep(0.1)
            assert port, f"worker-pool gateway never up: {st}"
            statusfile = os.path.join(d.workdir, "gateway-pv.workers")
            with open(statusfile) as f:
                wst = json.load(f)
            assert len(wst["workers"]) == 2, wst
            body = b"process-plane" * 300
            s = 0
            for _ in range(100):
                try:
                    s, _, _ = await http("127.0.0.1", port, "PUT", "/b")
                    if s == 200:
                        break
                except (ConnectionError, OSError):
                    pass
                await asyncio.sleep(0.1)
            assert s == 200, "pool unreachable"
            s, _, _ = await http("127.0.0.1", port, "PUT", "/b/k",
                                 body=body)
            assert s == 200, s
            s, _, data = await http("127.0.0.1", port, "GET", "/b/k")
            assert s == 200 and data == body, (s, len(data))
            # respawn: SIGKILL a worker, the pool recovers and serves
            os.kill(wst["workers"][0]["pid"], signal.SIGKILL)
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                with open(statusfile) as f:
                    wst2 = json.load(f)
                if wst2["respawns"] >= 1 and \
                        all(w["alive"] for w in wst2["workers"]):
                    break
                await asyncio.sleep(0.3)
            assert wst2["respawns"] >= 1, wst2
            ok = 0
            for _ in range(8):
                try:
                    s, _, data = await http("127.0.0.1", port, "GET",
                                            "/b/k")
                    if s == 200 and data == body:
                        ok += 1
                except (ConnectionError, OSError):
                    pass
                await asyncio.sleep(0.1)
            assert ok >= 6, f"pool dropped after worker kill ({ok}/8)"
            await c.call("volume-gateway", name="pv", action="stop")
    finally:
        await d.stop()
        shutil.rmtree(base, ignore_errors=True)
    print("process-plane smoke: managed workers=2 pool served "
          "byte-exact PUT/GET (mode=%s), worker SIGKILL respawned "
          "and kept serving" % wst["mode"])

asyncio.run(main())
EOF
procplane_rc=$?
if [ $procplane_rc -ne 0 ]; then
    echo "ci: process-plane smoke failed — not mergeable"
    exit $procplane_rc
fi

echo "== ci: lease smoke (hot GETs off the lease-held object cache at"
echo "       zero wire fops, recall coherence, gftpu_cache_*/gftpu_leases"
echo "       families, v15 volume-set keys) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'EOF'
import asyncio, os, shutil, tempfile

from glusterfs_tpu.api.glfs import Client, wait_connected
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.core.layer import walk
from glusterfs_tpu.core.metrics import REGISTRY
from glusterfs_tpu.daemon import serve_brick
from glusterfs_tpu.gateway import ClientPool, ObjectGateway
from glusterfs_tpu.gateway.minihttp import fetch as http
from glusterfs_tpu.protocol.client import ClientLayer

BRICK = """
volume posix
    type storage/posix
    option directory {dir}
end-volume
volume locks
    type features/locks
    subvolumes posix
end-volume
volume leases
    type features/leases
    subvolumes locks
end-volume
volume upcall
    type features/upcall
    subvolumes leases
end-volume
"""
CLIENT = """
volume c0
    type protocol/client
    option remote-host 127.0.0.1
    option remote-port {port}
    option remote-subvolume upcall
end-volume
"""

def sample(snap, fam, **labels):
    return sum(v for l, v in snap.get(fam, {}).get("samples", [])
               if all(l.get(k) == lv for k, lv in labels.items()))

def wire(graphs):
    return sum(l.rpc_roundtrips for g in graphs for l in walk(g.top)
               if isinstance(l, ClientLayer))

async def main():
    base = tempfile.mkdtemp(prefix="lease-smoke")
    server = await serve_brick(BRICK.format(dir=os.path.join(base, "b")))
    vf = CLIENT.format(port=server.port)

    async def factory():
        c = Client(Graph.construct(vf))
        await c.mount()
        await wait_connected(c.graph)
        return c

    gw = ObjectGateway(ClientPool(factory, 2),
                       volume="leasev", object_cache_size=4 << 20)
    await gw.start()
    H, P = gw.host, gw.port
    fuse = await factory()
    payload = bytes(range(256)) * 128  # 32 KiB
    try:
        assert (await http(H, P, "PUT", "/b"))[0] == 200
        st, hd, _ = await http(H, P, "PUT", "/b/hot", body=payload)
        assert st == 200, st
        etag = hd["etag"]
        st, _, data = await http(H, P, "GET", "/b/hot")  # fills cache
        assert st == 200 and data == payload
        snap0 = REGISTRY.snapshot()
        n0 = wire(c.graph for c in gw.pool.clients)
        for _ in range(20):
            st, _, data = await http(H, P, "GET", "/b/hot")
            assert st == 200 and data == payload
        for _ in range(5):
            st, _, _ = await http(H, P, "GET", "/b/hot",
                                  headers={"if-none-match": etag})
            assert st == 304, st
        assert wire(c.graph for c in gw.pool.clients) == n0, \
            "hot-GET loop touched the wire"
        snap1 = REGISTRY.snapshot()
        h0 = sample(snap0, "gftpu_cache_hits_total", cache="gateway")
        h1 = sample(snap1, "gftpu_cache_hits_total", cache="gateway")
        assert h1 >= h0 + 25, f"gateway cache hits not monotonic " \
            f"({h0} -> {h1})"
        assert sample(snap1, "gftpu_cache_bytes_total",
                      cache="gateway") > 0
        assert sample(snap1, "gftpu_leases", state="held") >= 1, \
            "brick lease gauge empty while the cache serves"
        # recall coherence: an out-of-band overwrite drops the entry
        # before the ack; the next GET serves the new bytes
        v2 = b"recalled" * 4096
        await fuse.write_file("/b/hot", v2)
        for _ in range(100):
            if gw._ocache.dump()["objects"] == 0:
                break
            await asyncio.sleep(0.05)
        st, _, data = await http(H, P, "GET", "/b/hot")
        assert st == 200 and data == v2, "stale bytes after recall"
        snap2 = REGISTRY.snapshot()
        assert sample(snap2, "gftpu_lease_recalls_total",
                      reason="conflict") >= 1
    finally:
        await fuse.unmount()
        await gw.stop()
        await server.stop()

    # -- managed path: the op-version 15 volume-set keys ----------------
    from glusterfs_tpu.mgmt.glusterd import (Glusterd, MgmtClient,
                                             mount_volume)
    d = Glusterd(os.path.join(base, "gd"))
    await d.start()
    try:
        async with MgmtClient(d.host, d.port) as mc:
            await mc.call("volume-create", name="lv",
                          vtype="distribute",
                          bricks=[{"path": os.path.join(base, "vb0")}])
            await mc.call("volume-start", name="lv")
            await mc.call("volume-set", name="lv",
                          key="features.leases", value="on")
            for key, val in (("features.lease-timeout", "600"),
                             ("gateway.object-cache-size", "4MB")):
                r = await mc.call("volume-set", name="lv",
                                  key=key, value=val)
                assert r.get("ok", True), (key, r)
        m = await mount_volume(d.host, d.port, "lv")
        try:
            await m.write_file("/leased", b"managed" * 1024)
            assert await m.lease_acquire("/leased") is True, \
                "managed brick refused a lease grant"
            assert bytes(await m.read_file("/leased")) == \
                b"managed" * 1024
        finally:
            await m.unmount()
    finally:
        await d.stop()
        shutil.rmtree(base, ignore_errors=True)
    print("lease smoke: 25 hot GETs at zero wire fops, recall-exact "
          "coherence, cache/lease families monotonic, v15 keys accepted")

asyncio.run(main())
EOF
lease_rc=$?
if [ $lease_rc -ne 0 ]; then
    echo "ci: lease smoke failed — not mergeable"
    exit $lease_rc
fi

echo "== ci: qos smoke (per-client admission shed at a tight fops cap,"
echo "       gftpu_qos_* family monotonicity, live v16 volume-set flip,"
echo "       shaping column in volume-status-deep) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'EOF'
import asyncio, os, shutil, tempfile

from glusterfs_tpu.core.fops import FopError
from glusterfs_tpu.core.graph import Graph
from glusterfs_tpu.core.metrics import REGISTRY
from glusterfs_tpu.daemon import serve_brick

BRICK = """
volume posix
    type storage/posix
    option directory {dir}
end-volume
volume srv
    type protocol/server
    option qos on
    option qos-fops-per-sec 30
    option qos-burst 1
    subvolumes posix
end-volume
"""
CLIENT = """
volume c0
    type protocol/client
    option remote-host 127.0.0.1
    option remote-port {port}
    option remote-subvolume srv
end-volume
"""

def sample(snap, fam, **labels):
    return sum(v for l, v in snap.get(fam, {}).get("samples", [])
               if all(l.get(k) == lv for k, lv in labels.items()))

async def main():
    from glusterfs_tpu.core.layer import Loc
    base = tempfile.mkdtemp(prefix="qos-smoke")
    # -- in-process brick: the registry families are reachable --------
    server = await serve_brick(BRICK.format(dir=os.path.join(base, "b")))
    try:
        g = Graph.construct(CLIENT.format(port=server.port))
        await g.activate()
        for _ in range(200):
            if g.top.connected:
                break
            await asyncio.sleep(0.01)
        snap0 = REGISTRY.snapshot()
        for _ in range(60):  # ~30 past the burst at 30 fops/s
            await g.top.lookup(Loc("/"))
        assert g.top.qos_backoff_total > 0, \
            "client absorbed no sheds at a 30 fops/s cap"
        eng = server._qos["srv"]
        assert eng.stats["shed"] > 0, "brick engine counted no sheds"
        snap1 = REGISTRY.snapshot()
        t0 = sample(snap0, "gftpu_qos_throttled_fops_total")
        t1 = sample(snap1, "gftpu_qos_throttled_fops_total")
        assert t1 > t0, f"qos throttle family not monotonic ({t0}->{t1})"
        assert "gftpu_qos_tokens" in snap1, "token gauge family missing"
        rows = server._status_of(server.top, "clients")["clients"]
        assert any(r.get("qos", {}).get("shed_fops", 0) > 0
                   for r in rows), "no shaping column in client status"
        await g.fini()
    finally:
        await server.stop()

    # -- managed path: v16 volume-set keys + a LIVE flip ---------------
    from glusterfs_tpu.mgmt.glusterd import (Glusterd, MgmtClient,
                                             mount_volume)
    d = Glusterd(os.path.join(base, "gd"))
    await d.start()
    try:
        async with MgmtClient(d.host, d.port) as mc:
            await mc.call("volume-create", name="qv",
                          vtype="distribute",
                          bricks=[{"path": os.path.join(base, "vb0")}])
            await mc.call("volume-start", name="qv")
        m = await mount_volume(d.host, d.port, "qv")
        try:
            await m.write_file("/warm", b"q" * 4096)  # pre-flip baseline
            async with MgmtClient(d.host, d.port) as mc:
                for key, val in (("server.qos-fops-per-sec", "20"),
                                 ("server.qos-burst", "1"),
                                 ("server.qos", "on")):
                    r = await mc.call("volume-set", name="qv",
                                      key=key, value=val)
                    assert r.get("ok", True), (key, r)
            await asyncio.sleep(1.5)  # volfile watcher propagation
            for i in range(40):  # writes: reads are cache-served
                try:
                    await m.write_file(f"/f{i}", b"q" * 512)
                except FopError:  # graph-reload blip, one retry
                    await m.write_file(f"/f{i}", b"q" * 512)
            async with MgmtClient(d.host, d.port) as mc:
                deep = await mc.call("volume-status-deep", name="qv",
                                     what="clients")
            shed = sum(r.get("qos", {}).get("shed_fops", 0)
                       for b in deep["bricks"].values()
                       for r in b.get("clients", []))
            assert shed > 0, "live flip shed nothing at 20 fops/s"
        finally:
            await m.unmount()
    finally:
        await d.stop()
        shutil.rmtree(base, ignore_errors=True)
    print("qos smoke: admission sheds on both paths, qos families "
          "monotonic, v16 keys flip the plane live, shaping column "
          "populated")

asyncio.run(main())
EOF
qos_rc=$?
if [ $qos_rc -ne 0 ]; then
    echo "ci: qos smoke failed — not mergeable"
    exit $qos_rc
fi

echo "== ci: shm smoke (managed volume, bulk lane armed, families"
echo "       monotonic, live volume-set off downgrades inline) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'EOF'
import asyncio, os, shutil, tempfile

async def main():
    from glusterfs_tpu.core.layer import walk
    from glusterfs_tpu.core.metrics import REGISTRY
    from glusterfs_tpu.mgmt.glusterd import (Glusterd, MgmtClient,
                                             mount_volume)
    from glusterfs_tpu.rpc import shm

    if not shm.supported():
        print("shm smoke: platform has no memfd/SCM_RIGHTS — skipped")
        return

    def fam(name):
        return sum(s[1] for s in REGISTRY.snapshot()[name]["samples"])

    base = tempfile.mkdtemp(prefix="ci-shm")
    d = Glusterd(os.path.join(base, "gd"))
    await d.start()
    try:
        async with MgmtClient(d.host, d.port) as c:
            await c.call("volume-create", name="sv", vtype="distribute",
                         bricks=[{"path": os.path.join(base, "b0")}])
            await c.call("volume-start", name="sv")
        cl = await mount_volume(d.host, d.port, "sv")
        try:
            def lanes():
                return [l for l in walk(cl.graph.top)
                        if l.type_name == "protocol/client"]

            for _ in range(200):  # subprocess brick: give arming time
                if lanes() and all(l._peer_shm for l in lanes()):
                    break
                await asyncio.sleep(0.05)
            assert lanes() and all(l._peer_shm for l in lanes()), \
                "bulk lane never armed against the managed brick"
            data = os.urandom(1 << 20)
            tx0, rx0 = fam("gftpu_shm_tx_bytes_total"), \
                fam("gftpu_shm_rx_bytes_total")
            await cl.write_file("/f", data)  # dd stand-in: 1 MiB
            got = bytes(await cl.read_file("/f"))
            assert got == data, "armed-lane bytes diverged"
            tx1, rx1 = fam("gftpu_shm_tx_bytes_total"), \
                fam("gftpu_shm_rx_bytes_total")
            assert tx1 - tx0 >= len(data), (tx0, tx1)
            assert rx1 - rx0 >= len(data), (rx0, rx1)
            # the per-connection state is on the status surface
            assert any(l.dump_private()["shm"]["armed"]
                       for l in lanes())

            # live downgrade: volume set off must drop BOTH directions
            # to inline with no reconnect and no byte damage
            async with MgmtClient(d.host, d.port) as c:
                await c.call("volume-set", name="sv",
                             key="network.shm-transport", value="off")
            for _ in range(200):
                ls = lanes()
                if ls and all(not l.opts["shm-transport"] for l in ls):
                    break
                await asyncio.sleep(0.05)
            assert all(not l.opts["shm-transport"] for l in lanes()), \
                "volume-set never reached the mounted client"
            tx2 = fam("gftpu_shm_tx_bytes_total")
            data2 = os.urandom(1 << 20)
            await cl.write_file("/g", data2)
            assert bytes(await cl.read_file("/g")) == data2, \
                "inline downgrade bytes diverged"
            assert fam("gftpu_shm_tx_bytes_total") == tx2, \
                "a frame rode the lane after volume-set off"
        finally:
            await cl.unmount()
    finally:
        await d.stop()
        shutil.rmtree(base, ignore_errors=True)
    print("shm smoke: managed volume armed the bulk lane (families "
          "+1 MiB both directions), live volume-set off downgraded "
          "to inline, bytes exact throughout")

asyncio.run(main())
EOF
shm_rc=$?
if [ $shm_rc -ne 0 ]; then
    echo "ci: shm smoke failed — not mergeable"
    exit $shm_rc
fi

echo "== ci: incident smoke (managed volume, brick SIGKILL"
echo "       auto-captures, list shows it, show round-trips) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu python - <<'EOF'
import asyncio, os, shutil, tempfile

async def main():
    from glusterfs_tpu.mgmt.glusterd import (Glusterd, MgmtClient,
                                             mount_volume)

    base = tempfile.mkdtemp(prefix="ci-inc")
    inc = os.path.join(base, "incidents")
    d = Glusterd(os.path.join(base, "gd"))
    await d.start()
    try:
        async with MgmtClient(d.host, d.port) as c:
            await c.call("volume-create", name="iv",
                         vtype="distribute",
                         bricks=[{"path": os.path.join(base, "b0")}])
            await c.call("volume-set", name="iv",
                         key="diagnostics.incident-dir", value=inc)
            await c.call("volume-set", name="iv",
                         key="diagnostics.incident-min-interval",
                         value="0")
            await c.call("volume-start", name="iv")
        cl = await mount_volume(d.host, d.port, "iv")
        try:
            await cl.write_file("/f", b"i" * 65536)
            assert bytes(await cl.read_file("/f")) == b"i" * 65536

            # brick SIGKILL is a failure-class event: the client's
            # BRICK_DISCONNECTED must auto-capture a local bundle into
            # the armed dir with no operator in the loop
            d.bricks["iv-brick-0"].kill()
            rows = []
            for _ in range(200):
                async with MgmtClient(d.host, d.port) as c:
                    rows = (await c.call("volume-incident-list",
                                         name="iv"))["bundles"]
                if rows:
                    break
                await asyncio.sleep(0.1)
            assert rows, "brick SIGKILL auto-captured no bundle"
            assert any("BRICK_DISCONNECTED" in r["name"]
                       for r in rows), rows

            # show must round-trip the bundle JSON (newest by default
            # AND by explicit name)
            async with MgmtClient(d.host, d.port) as c:
                shown = await c.call("volume-incident-show",
                                     name="iv")
                named = await c.call("volume-incident-show",
                                     name="iv",
                                     bundle=rows[-1]["name"])
            for b in (shown, named):
                assert b.get("reason"), b.keys()
                assert "spans" in b and "metrics" in b, b.keys()
        finally:
            await cl.unmount()
    finally:
        await d.stop()
        shutil.rmtree(base, ignore_errors=True)
    print("incident smoke: brick kill auto-captured a "
          "BRICK_DISCONNECTED bundle, list surfaced it, show "
          "round-tripped the JSON")

asyncio.run(main())
EOF
inc_rc=$?
if [ $inc_rc -ne 0 ]; then
    echo "ci: incident smoke failed — not mergeable"
    exit $inc_rc
fi

echo "== ci: alert smoke (v19 slo-rules, error-gen storm raises, UDP"
echo "       event + auto-captured bundle, clears on healthy traffic) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python - <<'EOF'
import asyncio, json, os, shutil, tempfile

async def main():
    from glusterfs_tpu.core import events as gf_events
    from glusterfs_tpu.core.fops import FopError
    from glusterfs_tpu.mgmt.eventsd import EventsDaemon
    from glusterfs_tpu.mgmt.glusterd import (Glusterd, MgmtClient,
                                             mount_volume)

    base = tempfile.mkdtemp(prefix="ci-alert")
    inc = os.path.join(base, "incidents")
    rules = json.dumps([{
        "name": "readv-errors", "kind": "error-ratio",
        "errors": "gftpu_fop_errors_total",
        "total": "gftpu_fops_total",
        "labels": {"op": "readv"},
        "target": 0.05, "window": 4,
    }], separators=(",", ":"))
    ev = EventsDaemon()
    udp, _ctl = await ev.start()
    os.environ["GFTPU_EVENTSD"] = f"127.0.0.1:{udp}"
    gf_events.configure(f"127.0.0.1:{udp}")
    d = Glusterd(os.path.join(base, "gd"))
    try:
        await d.start()
        async with MgmtClient(d.host, d.port) as c:
            await c.call("volume-create", name="av",
                         vtype="distribute",
                         bricks=[{"path": os.path.join(base, "b0")}])
            await c.call("volume-start", name="av")
            for k, v in (("diagnostics.history-interval", "0.25"),
                         ("diagnostics.slo-rules", rules),
                         ("diagnostics.incident-dir", inc),
                         ("diagnostics.incident-min-interval", "0")):
                await c.call("volume-set", name="av", key=k, value=v)
        m = await mount_volume(d.host, d.port, "av")
        try:
            await m.write_file("/f", b"x" * 8192)
            assert bytes(await m.read_file("/f")) == b"x" * 8192
            # ARM THE STORM: every readv on the brick fails
            async with MgmtClient(d.host, d.port) as c:
                await c.call("volume-set", name="av",
                             key="debug.error-gen", value="on")
                await c.call("volume-set", name="av",
                             key="debug.error-fops", value="readv")
                await c.call("volume-set", name="av",
                             key="debug.error-failure", value="100")
            deadline = asyncio.get_event_loop().time() + 60
            active = []
            while asyncio.get_event_loop().time() < deadline:
                try:
                    await m.read_file("/f")
                except FopError:
                    pass
                out = await d.op_volume_alerts("av")
                active = [a for a in out["active"]
                          if a["rule"] == "readv-errors"]
                if active:
                    break
                await asyncio.sleep(0.3)
            assert active, "storm never raised the alert"
            assert active[0]["observed"] > 0.05, active[0]
            raised = [e for e in ev.recent
                      if e.get("event") == "ALERT_RAISED"]
            assert raised, "ALERT_RAISED never reached eventsd"
            caps = []
            deadline = asyncio.get_event_loop().time() + 20
            while asyncio.get_event_loop().time() < deadline:
                caps = [f for f in (os.listdir(inc)
                                    if os.path.isdir(inc) else [])
                        if "ALERT_RAISED" in f]
                if caps:
                    break
                await asyncio.sleep(0.3)
            assert caps, "alert auto-captured no incident bundle"
            with open(os.path.join(inc, caps[0])) as f:
                bundle = json.load(f)
            ramp = [pts for k, pts in bundle["history"]["series"].items()
                    if k.startswith("gftpu_fop_errors_total")]
            assert ramp and any(p[-1][1] > p[0][1] for p in ramp), \
                "bundle history shows no error ramp"
            # clear by shifting traffic to writes (only readv storms);
            # no volume-set, so the raising process keeps its history
            deadline = asyncio.get_event_loop().time() + 60
            while asyncio.get_event_loop().time() < deadline:
                await m.write_file("/f", b"y" * 4096)
                out = await d.op_volume_alerts("av")
                if not out["active"]:
                    break
                await asyncio.sleep(0.3)
            assert out["active"] == [], "alert never cleared"
            hist = await d.op_volume_alerts("av", "history")
            edges = [t["edge"] for t in hist["history"]
                     if t["rule"] == "readv-errors"]
            assert "RAISED" in edges and "CLEARED" in edges, edges
        finally:
            await m.unmount()
    finally:
        await d.stop()
        os.environ.pop("GFTPU_EVENTSD", None)
        gf_events.configure(None)
        await ev.stop()
        shutil.rmtree(base, ignore_errors=True)
    print("alert smoke: error-gen storm raised the error-ratio alert "
          "(UDP event + auto-captured bundle with the error ramp), "
          "healthy traffic cleared it, both edges in alert history")

asyncio.run(main())
EOF
alert_rc=$?
if [ $alert_rc -ne 0 ]; then
    echo "ci: alert smoke failed — not mergeable"
    exit $alert_rc
fi

if [ $gate_rc -eq 2 ]; then
    echo "ci: green, but flaky tests were seen (flake gate exit 2)"
    exit 2
fi
echo "ci: mergeable (two identical green tier-1 runs"
echo "    + metrics smoke + gateway smoke + concurrency smoke"
echo "    + mesh smoke + chaos smoke + delta-write smoke"
echo "    + rebalance smoke + process-plane smoke + lease smoke"
echo "    + qos smoke + shm smoke + incident smoke + alert smoke)"
exit 0
