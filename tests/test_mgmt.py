"""Management plane e2e: glusterd volume lifecycle (create/start/mount/
set/stop/delete), volgen output, CLI command surface, peers + txn —
the tests/basic/glusterd + volume.rc analog."""

import asyncio
import io
import sys

import pytest

from glusterfs_tpu.mgmt import volgen
from glusterfs_tpu.mgmt.cli import main as cli_main
from glusterfs_tpu.mgmt.glusterd import (Glusterd, MgmtClient, MgmtError,
                                         mount_volume)


# -- volgen ----------------------------------------------------------------

def _volinfo(tmp_path, vtype="disperse", n=6, **kw):
    return {
        "name": "tv", "type": vtype, "redundancy": 2,
        "bricks": [{"index": i, "host": "127.0.0.1", "port": 4000 + i,
                    "path": str(tmp_path / f"b{i}"),
                    "name": f"tv-brick-{i}", "node": "x"}
                   for i in range(n)],
        "options": kw.get("options", {}),
        **{k: v for k, v in kw.items() if k != "options"},
    }


def test_volgen_brick_volfile(tmp_path):
    from glusterfs_tpu.core.graph import Graph

    vi = _volinfo(tmp_path)
    text = volgen.build_brick_volfile(vi, vi["bricks"][0])
    g = Graph.construct(text)
    assert g.top.type_name == "protocol/server"
    types = [l.type_name for l in g.by_name.values()]
    assert "storage/posix" in types and "features/locks" in types
    assert "debug/io-stats" in types


def test_volgen_client_volfile(tmp_path):
    from glusterfs_tpu.core.graph import Graph

    vi = _volinfo(tmp_path, options={"performance.io-cache": "on"})
    text = volgen.build_client_volfile(vi)
    g = Graph.construct(text)
    types = [l.type_name for l in g.by_name.values()]
    assert types.count("protocol/client") == 6
    assert "cluster/disperse" in types
    assert "performance/write-behind" in types  # default on
    assert "performance/io-cache" in types  # enabled by option
    assert "debug/io-stats" in types
    assert g.top.type_name == "meta"


def test_volgen_distributed_disperse(tmp_path):
    from glusterfs_tpu.core.graph import Graph

    vi = _volinfo(tmp_path, n=12)
    vi["group-size"] = 6
    text = volgen.build_client_volfile(vi)
    g = Graph.construct(text)
    types = [l.type_name for l in g.by_name.values()]
    assert types.count("cluster/disperse") == 2
    assert "cluster/distribute" in types


# -- glusterd lifecycle ----------------------------------------------------

@pytest.mark.slow
def test_glusterd_volume_lifecycle(tmp_path):
    async def run():
        d = Glusterd(str(tmp_path / "gd"))
        await d.start()
        try:
            async with MgmtClient(d.host, d.port) as c:
                bricks = [{"path": str(tmp_path / f"b{i}")}
                          for i in range(6)]
                await c.call("volume-create", name="vol1", vtype="disperse",
                             bricks=bricks, redundancy=2)
                info = await c.call("volume-info", name="vol1")
                assert info["vol1"]["status"] == "created"
                await c.call("volume-start", name="vol1")
                status = await c.call("volume-status", name="vol1")
                assert all(b["online"] for b in status["bricks"])
                # duplicate create fails
                with pytest.raises(Exception):
                    await c.call("volume-create", name="vol1",
                                 vtype="disperse", bricks=bricks,
                                 redundancy=2)
                # volume set flows into the client volfile
                await c.call("volume-set", name="vol1",
                             key="disperse.read-policy", value="first-k")
                spec = await c.call("getspec", name="vol1")
                assert "option read-policy first-k" in spec["volfile"]

            # mount and do I/O through the full managed stack
            client = await mount_volume(d.host, d.port, "vol1")
            ec = None
            for layer in client.graph.by_name.values():
                if layer.type_name == "cluster/disperse":
                    ec = layer
            for _ in range(150):
                if all(ch.connected for ch in ec.children):
                    break
                await asyncio.sleep(0.1)
            assert all(ch.connected for ch in ec.children)
            f = await client.create("/hello")
            await f.write(b"managed!", 0)
            await f.close()
            assert await client.read_file("/hello") == b"managed!"
            await client.unmount()

            # `volume top`: brick-side per-path counters over the RPC
            async with MgmtClient(d.host, d.port) as c:
                top = await c.call("volume-top", name="vol1",
                                   metric="write")
                rows = [r for rows_ in top["bricks"].values()
                        for r in rows_]
                assert any(r["path"] == "/hello" and r["writes"] >= 1
                           for r in rows), top
                # `volume profile`: BRICK-side cumulative fop stats
                prof = await c.call("volume-profile", name="vol1")
                assert len(prof["bricks"]) == 6
                assert all(p["fops"]["writev"]["count"] >= 1
                           for p in prof["bricks"].values()), prof

            async with MgmtClient(d.host, d.port) as c:
                await c.call("volume-stop", name="vol1")
                with pytest.raises(Exception):
                    await c.call("getspec", name="vol1")  # not started
                await c.call("volume-delete", name="vol1")
                info = await c.call("volume-info")
                assert info == {}
        finally:
            await d.stop()

    asyncio.run(run())


@pytest.mark.slow
def test_glusterd_peers_and_txn(tmp_path):
    async def run():
        d1 = Glusterd(str(tmp_path / "n1"))
        d2 = Glusterd(str(tmp_path / "n2"))
        await d1.start()
        await d2.start()
        try:
            async with MgmtClient(d1.host, d1.port) as c:
                await c.call("peer-probe", host=d2.host, port=d2.port)
                st = await c.call("peer-status")
                assert len(st["peers"]) == 1
                # cluster txn replicates volinfo to the peer
                await c.call("volume-create", name="pv", vtype="replicate",
                             bricks=[{"path": str(tmp_path / "pb0")},
                                     {"path": str(tmp_path / "pb1")}],
                             redundancy=0)
            assert "pv" in d2.state["volumes"]
            # txn lock blocks concurrent ops
            d2._txn_holder = "someone-else"
            async with MgmtClient(d1.host, d1.port) as c:
                with pytest.raises(Exception):
                    await c.call("volume-create", name="pv2",
                                 vtype="replicate",
                                 bricks=[{"path": str(tmp_path / "x0")},
                                         {"path": str(tmp_path / "x1")}],
                                 redundancy=0)
            d2._txn_holder = None
        finally:
            await d1.stop()
            await d2.stop()

    asyncio.run(run())


@pytest.mark.slow
def test_peer_volinfo_reconciliation(tmp_path):
    """A peer that was down during a config txn catches up on restart:
    peer-hello carries per-volume generation counters and the newer
    volinfo is imported (glusterd friend-sm volinfo import analog);
    a missed volume-delete travels as a tombstone instead of being
    resurrected by the returning peer."""
    async def run():
        d1 = Glusterd(str(tmp_path / "r1"))
        d2 = Glusterd(str(tmp_path / "r2"))
        await d1.start()
        await d2.start()
        try:
            async with MgmtClient(d1.host, d1.port) as c:
                await c.call("peer-probe", host=d2.host, port=d2.port)
                await c.call("volume-create", name="rv", vtype="replicate",
                             bricks=[{"path": str(tmp_path / "rb0")},
                                     {"path": str(tmp_path / "rb1")}],
                             redundancy=0)
            assert "rv" in d2.state["volumes"]
            gen0 = d1.state["volumes"]["rv"]["version"]
            # peer goes down; a volume-set commits without it
            await d2.stop()
            async with MgmtClient(d1.host, d1.port) as c:
                await c.call("volume-set", name="rv",
                             key="performance.io-cache", value="on")
            assert d1.state["volumes"]["rv"]["version"] > gen0
            assert d2.state["volumes"]["rv"].get("options", {}).get(
                "performance.io-cache") != "on"
            # peer restarts: the start-time re-handshake imports the
            # missed generation
            d2b = Glusterd(str(tmp_path / "r2"))
            await d2b.start()
            try:
                for _ in range(100):
                    if d2b.state["volumes"].get("rv", {}).get(
                            "options", {}).get(
                            "performance.io-cache") == "on":
                        break
                    await asyncio.sleep(0.05)
                vol = d2b.state["volumes"]["rv"]
                assert vol["options"]["performance.io-cache"] == "on"
                assert vol["version"] == \
                    d1.state["volumes"]["rv"]["version"]
            finally:
                await d2b.stop()
            # missed DELETE: tombstone wins over the stale volinfo
            async with MgmtClient(d1.host, d1.port) as c:
                await c.call("volume-delete", name="rv")
            d2c = Glusterd(str(tmp_path / "r2"))
            await d2c.start()
            try:
                for _ in range(100):
                    if "rv" not in d2c.state["volumes"]:
                        break
                    await asyncio.sleep(0.05)
                assert "rv" not in d2c.state["volumes"]
                assert "rv" in d2c.state.get("tombstones", {})
            finally:
                await d2c.stop()
        finally:
            await d1.stop()

    asyncio.run(run())


# -- the brick spawner (ISSUE 35) ------------------------------------------
# ``_spawn_daemon`` replaced by a stub that takes BOOT_S on the loop's
# clock: what is held here is the order, the overlap, the tables and the
# store, not an interpreter's boot.

BOOT_S = 0.3


class _FakeProc:
    """What the tables hold of a brick: alive until told otherwise."""

    def __init__(self):
        self.rc = None

    def poll(self):
        return self.rc

    def terminate(self):
        self.rc = -15

    kill = terminate

    def wait(self, timeout=None):
        return self.rc


@pytest.fixture
def stub_daemons(monkeypatch):
    """Every ``_spawn_daemon`` of every Glusterd: (brick, port asked
    for, start, end) in ``calls``; bricks named in ``failing`` raise
    after the seconds given."""
    calls, failing, procs = [], {}, []

    async def spawn(self, volfile, text, portfile, logfile, top,
                    port=None, what="brick", extra_env=None):
        loop = asyncio.get_running_loop()
        call = {"top": top, "port": port, "start": loop.time()}
        calls.append(call)
        if top in failing:
            await asyncio.sleep(failing[top])
            call["end"] = loop.time()
            raise MgmtError(f"{what} failed: boom")
        # the last one forked is the first to answer
        await asyncio.sleep(BOOT_S - 0.01 * len(calls))
        call["end"] = loop.time()
        procs.append(_FakeProc())
        return procs[-1], port or 5000 + len(procs)

    monkeypatch.setattr(Glusterd, "_spawn_daemon", spawn)
    monkeypatch.setattr(Glusterd, "_spawn_shd", lambda self, vol: None)
    return calls, failing, procs


def _sum_of_boots(calls):
    return sum(c["end"] - c["start"] for c in calls)


def _under_way_at_once(calls):
    """Every one forked before the first has answered, and the lot in
    under half the sum of their own times (in turn it is the sum;
    measured against the stubs' own clock, so a stalled machine
    stretches both)."""
    first, last = (min(c["start"] for c in calls),
                   max(c["end"] for c in calls))
    return max(c["start"] for c in calls) < min(c["end"] for c in calls) \
        and last - first < _sum_of_boots(calls) / 2


async def _created(tmp_path, n=6, name="sv", workdir="gd"):
    d = Glusterd(str(tmp_path / workdir))
    await d.start()
    async with MgmtClient(d.host, d.port) as c:
        await c.call("volume-create", name=name, vtype="disperse",
                     bricks=[{"path": str(tmp_path / f"b{i}")}
                             for i in range(n)], redundancy=2)
    return d


def _stored_ports(d, name="sv"):
    import json

    with open(d._store) as f:
        return [b.get("port")
                for b in json.load(f)["volumes"][name]["bricks"]]


def test_volume_start_spawns_local_bricks_side_by_side(tmp_path,
                                                       stub_daemons):
    calls, _, procs = stub_daemons

    async def run():
        d = await _created(tmp_path)
        saves = []
        save, start = d._save, d._start_bricks

        async def counted(*a, **kw):
            saves.clear()
            await start(*a, **kw)
            saves.append("returned")

        d._save = lambda: (saves.append("save"), save())
        d._start_bricks = counted
        try:
            loop = asyncio.get_running_loop()
            t = loop.time()
            async with MgmtClient(d.host, d.port) as c:
                await c.call("volume-start", name="sv")
            took = loop.time() - t
            assert len(calls) == 6 and _under_way_at_once(calls)
            # the whole RPC (lock, stage, hooks, commit) in less than
            # the six boots in turn would take
            assert took < _sum_of_boots(calls)
            # one store for the six, after the last has settled
            assert saves[:2] == ["save", "returned"]
            names = [f"sv-brick-{i}" for i in range(6)]
            vol = d.state["volumes"]["sv"]
            assert [b["name"] for b in vol["bricks"]] == names
            assert list(d.bricks) == names and list(d.ports) == names
            ports = [d.ports[n] for n in names]
            assert len(set(ports)) == 6
            assert [b["port"] for b in vol["bricks"]] == ports
            assert _stored_ports(d) == ports
            # ... whatever order they answered in
            assert [d.bricks[n] for n in names] == procs[::-1]
        finally:
            await d.stop()

    asyncio.run(run())


@pytest.mark.parametrize("failing", [
    {3: BOOT_S / 2},
    # a later brick that fails sooner is not the one reported
    {3: 1.5 * BOOT_S, 5: BOOT_S / 3}])
def test_volume_start_reports_first_failed_brick_and_keeps_the_rest(
        tmp_path, stub_daemons, failing):
    calls, fails, procs = stub_daemons
    fails.update({f"sv-brick-{i}-server": s for i, s in failing.items()})

    async def run():
        d = await _created(tmp_path)
        try:
            async with MgmtClient(d.host, d.port) as c:
                with pytest.raises(Exception) as e:
                    await c.call("volume-start", name="sv")
            assert "brick sv-brick-3 failed" in str(e.value)
            # every brick was tried, beside the one that failed
            assert len(calls) == 6 and _under_way_at_once(calls)
            up = [f"sv-brick-{i}" for i in range(6) if i not in failing]
            assert list(d.bricks) == up and list(d.ports) == up
            # no process the tables do not hold
            assert sorted(map(id, d.bricks.values())) == \
                sorted(map(id, procs))
            assert _stored_ports(d) == [
                None if i in failing else d.ports[f"sv-brick-{i}"]
                for i in range(6)]
        finally:
            await d.stop()

    asyncio.run(run())


def test_restart_resume_respawns_side_by_side(tmp_path, stub_daemons):
    calls, _, _ = stub_daemons

    async def run():
        d = await _created(tmp_path)
        async with MgmtClient(d.host, d.port) as c:
            await c.call("volume-start", name="sv")
        await d.stop()
        del calls[:]
        d = Glusterd(str(tmp_path / "gd"))
        await d.start()
        try:
            assert [c["top"] for c in calls] == [
                f"sv-brick-{i}-server" for i in range(6)]
            assert _under_way_at_once(calls)
            # a node that comes back binds fresh ports (and pushes
            # them: _broadcast_local_ports), as before
            assert [c["port"] for c in calls] == [None] * 6
            assert _stored_ports(d) == [
                d.ports[f"sv-brick-{i}"] for i in range(6)]
        finally:
            await d.stop()

    asyncio.run(run())


def test_quorum_respawn_is_side_by_side_on_the_persisted_ports(
        tmp_path, stub_daemons):
    calls, _, _ = stub_daemons

    async def run():
        d = await _created(tmp_path)
        try:
            async with MgmtClient(d.host, d.port) as c:
                await c.call("volume-start", name="sv")
            vol = d.state["volumes"]["sv"]
            ports = [b["port"] for b in vol["bricks"]]
            # fenced by a lost quorum, then the peers are gone
            # (detach): the next tick lifts the fence
            for b in vol["bricks"]:
                await d._stop_brick(vol, b)
            d._quorum_blocked.add("sv")
            del calls[:]
            await d._check_server_quorum()
            assert not d._quorum_blocked and len(d.bricks) == 6
            assert _under_way_at_once(calls)
            assert [c["port"] for c in calls] == ports
            assert [d.ports[b["name"]] for b in vol["bricks"]] == ports
        finally:
            await d.stop()

    asyncio.run(run())


def test_mux_volume_attaches_in_brick_order(tmp_path, stub_daemons,
                                            monkeypatch):
    calls, _, _ = stub_daemons
    attaches = []

    async def brick_call(vol, port, name, args, **kw):
        loop = asyncio.get_running_loop()
        attaches.append({"top": args[1], "start": loop.time()})
        await asyncio.sleep(0.02)
        attaches[-1]["end"] = loop.time()
        return {"ok": True}

    monkeypatch.setattr(Glusterd, "_brick_call", staticmethod(brick_call))

    async def run():
        d = await _created(tmp_path)
        try:
            async with MgmtClient(d.host, d.port) as c:
                await c.call("volume-set", name="sv",
                             key="cluster.brick-multiplex", value="on")
                await c.call("volume-start", name="sv")
            assert [c["top"] for c in calls] == ["mux-anchor-server"]
            assert [a["top"] for a in attaches] == [
                f"sv-brick-{i}-server" for i in range(6)]
            assert all(a["end"] <= b["start"]
                       for a, b in zip(attaches, attaches[1:]))
            port = d._mux["port"]
            assert _stored_ports(d) == [port] * 6
            d._mux["bricks"].clear()  # no daemon to detach from
        finally:
            await d.stop()

    asyncio.run(run())


def test_cancelled_volume_start_leaves_no_untracked_brick(tmp_path,
                                                          monkeypatch):
    """Real children that never write a port file: a ``volume-start``
    cancelled while it waits for them takes every one of them along."""
    import subprocess

    from glusterfs_tpu.mgmt import glusterd as gd_mod

    children = []

    def popen(argv, **kw):
        assert argv[1:3] == ["-m", "glusterfs_tpu.daemon"]
        children.append(subprocess.Popen(["sleep", "60"], **kw))
        return children[-1]

    async def run():
        d = await _created(tmp_path)
        monkeypatch.setattr(
            gd_mod, "subprocess", type("sub", (), {
                "Popen": staticmethod(popen), "DEVNULL": subprocess.DEVNULL,
                "TimeoutExpired": subprocess.TimeoutExpired}))
        try:
            task = asyncio.create_task(d.op_volume_start("sv"))
            for _ in range(2000):
                if len(children) == 6:
                    break
                await asyncio.sleep(0.01)
            assert len(children) == 6
            assert all(c.poll() is None for c in children)
            task.cancel()
            with pytest.raises(asyncio.CancelledError):
                await asyncio.wait_for(task, 30)
            # terminated AND waited for: none is left a zombie
            assert [c.returncode for c in children] == [-15] * 6
            assert not d.bricks and not d.ports
            assert d._txn_holder is None
        finally:
            for c in children:
                if c.poll() is None:
                    c.kill()
                    c.wait()
            await d.stop()

    asyncio.run(run())


# -- CLI -------------------------------------------------------------------

@pytest.mark.slow
def test_cli_surface(tmp_path, capsys):
    async def start():
        d = Glusterd(str(tmp_path / "gd"))
        await d.start()
        return d

    loop = asyncio.new_event_loop()
    d = loop.run_until_complete(start())
    import threading

    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()
    try:
        server = f"--server=127.0.0.1:{d.port}"
        bricks = [f"localhost:{tmp_path}/cb{i}" for i in range(6)]
        assert cli_main([server, "volume", "create", "cvol",
                         "disperse", "2", *bricks]) == 0
        assert cli_main([server, "volume", "start", "cvol"]) == 0
        assert cli_main([server, "--json", "volume", "info", "cvol"]) == 0
        out = capsys.readouterr().out
        assert '"cvol"' in out and '"started"' in out
        assert cli_main([server, "volume", "set", "cvol",
                         "disperse.read-policy", "first-k"]) == 0
        assert cli_main([server, "volume", "status", "cvol"]) == 0
        out = capsys.readouterr().out
        assert "online" in out
        assert cli_main([server, "peer", "status"]) == 0
        assert cli_main([server, "volume", "stop", "cvol"]) == 0
        assert cli_main([server, "volume", "delete", "cvol"]) == 0
        # error path: unknown volume
        assert cli_main([server, "volume", "start", "nope"]) == 1
    finally:
        fut = asyncio.run_coroutine_threadsafe(d.stop(), loop)
        fut.result(timeout=10)
        loop.call_soon_threadsafe(loop.stop)
        t.join(timeout=5)

    asyncio_fix = None  # keep pytest happy
