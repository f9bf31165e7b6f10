#!/usr/bin/env python3
"""chaos.py — the failure-containment proof harness (ISSUE 9).

Scenario runner over a MANAGED disperse 4+2 volume (glusterd + six
real brick subprocesses, I/O through the full wire stack): each
scenario breaks the cluster a specific way and asserts the degraded
contract the EC/protocol planes promise:

* ``degraded_read``   — SIGKILL a brick mid-write: every write still
                        lands (5/6 >= quorum), every read with the
                        brick down is byte-identical, the restarted
                        brick heals to convergence (heal-count -> 0),
                        and a read forced THROUGH the healed brick
                        (disperse.ec-read-mask) is byte-identical.
* ``quorum_write``    — SIGKILL R+1 bricks: writes fail CLEANLY
                        (FopError, bounded time, no hang), and after
                        restart + heal no torn state is visible — the
                        pre-kill file is byte-identical and the failed
                        write's target either errors or reads back
                        exactly what was attempted.
* ``blackhole``       — SIGSTOP a brick (transport alive, nothing
                        answers): reads complete degraded within a
                        bound (ping-timeout + failfast drop, never a
                        call-timeout serial crawl), byte-identical.
* ``error_storm``     — debug.error-gen in deterministic
                        failure-count mode on a brick's readv: reads
                        stay byte-identical while the injected
                        failures burn down, and the budget is exact.
* ``delay_storm``     — debug.delay-gen on every brick's readv:
                        reads stay correct and bounded.
* ``gateway``         — the HTTP front door over the same volume —
                        served by a workers=2 shared-nothing pool —
                        keeps answering (correct bytes or clean
                        error, never a hang) while a brick is down,
                        and a worker SIGKILL mid-load never drops
                        the volume (supervisor respawn, ISSUE 12).
* ``lease_storm``     — leased readers vs a hot writer (ISSUE 16):
                        every overwrite recalls every holder within a
                        bound, every holder returns voluntarily (a
                        revocation would poison the next grant), every
                        post-recall read is byte-exact, and a holder
                        that dies WITHOUT releasing is reaped at
                        disconnect instead of stalling the writer for
                        the recall grace.
* ``qos_storm``       — a greedy flooder vs a polite reader on the
                        same volume (ISSUE 17): with server.qos off
                        the flood runs unshaped (baseline); a LIVE
                        volume-set flip arms per-client token buckets
                        and the greedy client's throughput drops
                        measurably while the polite client's p99 stays
                        bounded and error-free, THROTTLE_START lands
                        in eventsd history, and the shaping shows in
                        volume status clients.
* ``rebalance_grow``  — grow the loaded 4+2 volume by a second
                        distribute leg WHILE serving: managed daemon
                        migration under live reads/writes, SIGKILL +
                        respawn resumes from its checkpoint, bounded
                        read latency, every pre-existing and
                        in-flight object byte-identical after
                        convergence (ISSUE 11 acceptance).
* ``fuse``            — (--with-fuse only; kernel-dependent) the
                        mount stays responsive through a brick kill.

Every scenario is wall-clock bounded (a hang IS a failure), and the
run reports leaked threads/tasks against a warmed baseline — the
containment plane must not pay for failure handling with leaks.

``--baseline FILE`` loads a previous ``--json`` report and judges this
run's timing rows against it at the documented 2-core swing band
(ISSUE 20) — machine-readable ``regressions: [...]`` rows land in the
report.

Usage:
    python tools/chaos.py [--scenario NAME ...] [--json] [--with-fuse]
                          [--baseline FILE]
Exit 0 iff every selected scenario passed and nothing leaked.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import signal
import sys
import tempfile
import threading
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

from glusterfs_tpu.core.fops import FopError  # noqa: E402
from glusterfs_tpu.core.layer import walk  # noqa: E402
from glusterfs_tpu.mgmt.glusterd import (Glusterd, MgmtClient,  # noqa: E402
                                         mount_volume)

K, R = 4, 2
N = K + R
MIB = 1 << 20

#: per-scenario wall-clock bound (a wedged scenario FAILS, it never
#: hangs the harness); sized for rebalance_grow, which spawns six
#: extra bricks plus two rebalance daemons on a loaded host
SCENARIO_DEADLINE_S = 420.0

SCENARIOS: dict = {}


def scenario(name: str):
    def deco(fn):
        SCENARIOS[name] = fn
        return fn
    return deco


def payload_for(i: int, mib: int = 1) -> bytes:
    return np.random.default_rng(1000 + i).integers(
        0, 256, mib * MIB, dtype=np.uint8).tobytes()


class Stack:
    """One managed disperse 4+2 stack: glusterd + 6 brick subprocesses
    + helpers to break and mend them."""

    def __init__(self, base: str, name: str = "chaos"):
        self.base = base
        self.name = name
        self.d: Glusterd | None = None

    async def __aenter__(self):
        self.d = Glusterd(os.path.join(self.base, "gd"))
        await self.d.start()
        async with MgmtClient(self.d.host, self.d.port) as c:
            await c.call("volume-create", name=self.name,
                         vtype="disperse", redundancy=R,
                         bricks=[{"path": os.path.join(self.base,
                                                       f"b{i}")}
                                 for i in range(N)])
            await c.call("volume-start", name=self.name)
        return self

    async def __aexit__(self, *exc):
        await self.d.stop()

    async def set(self, key: str, value: str) -> None:
        async with MgmtClient(self.d.host, self.d.port) as c:
            await c.call("volume-set", name=self.name, key=key,
                         value=value)

    async def mount(self):
        cl = await mount_volume(self.d.host, self.d.port, self.name)
        # calibrate the codec router off the clock (its first device
        # probe pays jax imports that would eat a scenario's bound)
        for layer in walk(cl.graph.top):
            cal = getattr(getattr(layer, "codec", None),
                          "ensure_calibrated", None)
            if cal is not None:
                await cal()
        return cl

    def brick_name(self, i: int) -> str:
        return f"{self.name}-brick-{i}"

    def kill_brick(self, i: int, sig=signal.SIGKILL) -> int:
        """SIGKILL brick i; returns the port it was serving (for the
        same-port respawn clients expect)."""
        bname = self.brick_name(i)
        proc = self.d.bricks.pop(bname)
        port = self.d.ports.pop(bname)
        os.kill(proc.pid, sig)
        proc.wait()
        return port

    def pause_brick(self, i: int) -> None:
        os.kill(self.d.bricks[self.brick_name(i)].pid, signal.SIGSTOP)

    def resume_brick(self, i: int) -> None:
        os.kill(self.d.bricks[self.brick_name(i)].pid, signal.SIGCONT)

    async def restart_brick(self, i: int, port: int) -> None:
        vol = self.d._vol(self.name)
        b = next(x for x in vol["bricks"]
                 if x["name"] == self.brick_name(i))
        await self.d._spawn_brick(vol, b, port=port)

    async def heal_until_converged(self, timeout: float = 120.0) -> dict:
        """heal full, then poll heal-count to 0 (convergence proof)."""
        res = await self.d.op_volume_heal(self.name, "full")
        deadline = time.monotonic() + timeout
        while True:
            hc = await self.d.op_volume_heal_count(self.name)
            if hc.get("total", -1) == 0 and "partial" not in hc:
                return {"healed": res, "heal_count": hc["total"]}
            if time.monotonic() > deadline:
                raise TimeoutError(f"heal never converged: {hc}")
            await asyncio.sleep(1.0)


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------


@scenario("degraded_read")
async def degraded_read(base: str, opts) -> dict:
    """Brick SIGKILL mid-write -> degraded byte-identical reads ->
    restart -> heal converges -> the healed brick serves reads.
    The incident plane rides along: the kill must auto-capture a
    bundle (BRICK_DISCONNECTED is failure-class), the cluster capture
    must show one trace id spanning >=2 distinct processes, and the
    incident dir must respect its size bound."""
    out: dict = {}
    n_files = 6
    victim = 2
    async with Stack(base) as st:
        inc_dir = os.path.join(base, "incidents")
        await st.set("diagnostics.incident-dir", inc_dir)
        await st.set("diagnostics.incident-max-bytes", "8MB")
        await st.set("diagnostics.incident-min-interval", "0")
        cl = await st.mount()
        try:
            pay = [payload_for(i) for i in range(n_files)]
            # writes in flight when the brick dies: the kill lands
            # mid-stream, not between fops
            writes = [asyncio.ensure_future(
                cl.write_file(f"/f{i}", pay[i])) for i in range(n_files)]
            await asyncio.sleep(0.3)
            port = st.kill_brick(victim)
            out["killed_mid_write"] = sum(1 for w in writes
                                          if not w.done())
            await asyncio.gather(*writes)
            # degraded reads: one brick down, byte-identical
            datas = await asyncio.gather(*(cl.read_file(f"/f{i}")
                                           for i in range(n_files)))
            assert all(bytes(d) == p for d, p in zip(datas, pay)), \
                "degraded read parity broken"
            out["degraded_reads_ok"] = n_files
            # the kill auto-captured a local bundle: the mounted
            # client saw BRICK_DISCONNECTED (failure-class) and wrote
            # its flight ring into the incident dir
            caps: list = []
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                caps = [f for f in (os.listdir(inc_dir)
                                    if os.path.isdir(inc_dir) else [])
                        if "BRICK_DISCONNECTED" in f]
                if caps:
                    break
                await asyncio.sleep(0.2)
            assert caps, "brick SIGKILL never auto-captured a bundle"
            out["auto_captured"] = len(caps)
            # cluster capture: the merged bundle holds >=1 trace id
            # whose spans come from >=2 distinct PROCESSES (the
            # degraded reads fanned one client trace across bricks)
            cluster = await st.d.op_volume_incident_capture(st.name)
            with open(cluster["bundle"]) as f:
                merged = json.load(f)
            pids_by_tid: dict = {}
            for proc_bundle in merged["processes"].values():
                if not isinstance(proc_bundle, dict) \
                        or "spans" not in proc_bundle:
                    continue
                for sp in proc_bundle["spans"]:
                    pids_by_tid.setdefault(sp["trace"], set()).add(
                        proc_bundle["pid"])
            shared = [t for t, pids in pids_by_tid.items()
                      if len(pids) >= 2]
            assert shared, "no trace id spans two processes"
            out["cross_process_traces"] = len(shared)
            # leak audit, incident-dir edition: captures + the
            # cluster bundle stay inside the configured size bound
            total = sum(os.path.getsize(os.path.join(inc_dir, f))
                        for f in os.listdir(inc_dir))
            assert total <= 8 * MIB, \
                f"incident dir exceeded its size bound ({total}B)"
            out["incident_dir_bytes"] = total
            # restart + heal to convergence
            await st.restart_brick(victim, port)
            conv = await st.heal_until_converged()
            out["heal_count_after"] = conv["heal_count"]
        finally:
            await cl.unmount()
        # the healed brick must actually SERVE: force it into the
        # read set (ec-read-mask is strict) with exactly K ids
        mask = ",".join(str(i) for i in
                        [victim] + [i for i in range(N)
                                    if i != victim][:K - 1])
        await st.set("disperse.ec-read-mask", mask)
        cl2 = await st.mount()
        try:
            datas = await asyncio.gather(*(cl2.read_file(f"/f{i}")
                                           for i in range(n_files)))
            assert all(bytes(d) == p for d, p in zip(datas, pay)), \
                "post-heal read through the healed brick broke parity"
            out["healed_brick_serves"] = True
        finally:
            await cl2.unmount()
    return out


@scenario("quorum_write")
async def quorum_write(base: str, opts) -> dict:
    """R+1 bricks dead -> writes fail cleanly; after restart + heal
    nothing torn is visible."""
    out: dict = {}
    async with Stack(base) as st:
        cl = await st.mount()
        pre = payload_for(100)
        attempted = payload_for(101)
        ports = {}
        try:
            await cl.write_file("/pre", pre)
            # make /pre DURABLE before the blast: fsync forces the
            # eager window's version/size commit onto all six bricks.
            # Without it the deferred post-op would reach only the
            # three survivors — a below-K version split that is
            # legitimately unhealable once the others return (a
            # non-fsynced write's durability is quorum-best-effort,
            # here we are testing the durable file's contract)
            f = await cl.open("/pre", os.O_RDWR)
            await f.fsync()
            await f.close()
            for i in range(R + 1):   # 3 dead of 6: 3 < K=4
                ports[i] = st.kill_brick(i)
            t0 = time.monotonic()
            try:
                await asyncio.wait_for(cl.write_file("/torn", attempted),
                                       60)
                raise AssertionError(
                    "below-quorum write succeeded (3/6 bricks up)")
            except FopError as e:
                out["write_failed_cleanly"] = repr(e)[:120]
            out["fail_latency_s"] = round(time.monotonic() - t0, 2)
        finally:
            await cl.unmount()
        for i, port in ports.items():
            await st.restart_brick(i, port)
        conv = await st.heal_until_converged()
        out["heal_count_after"] = conv["heal_count"]
        cl2 = await st.mount()
        try:
            got = await cl2.read_file("/pre")
            assert bytes(got) == pre, "pre-kill file torn after recovery"
            out["pre_file_intact"] = True
            # the failed write must not be VISIBLY torn: either a clean
            # error, or exactly the attempted bytes (had it reached
            # quorum after all) — never a mangled in-between
            try:
                got = await asyncio.wait_for(cl2.read_file("/torn"), 60)
                assert bytes(got) == attempted, \
                    "failed write left torn bytes visible"
                out["failed_write_state"] = "complete"
            except FopError as e:
                out["failed_write_state"] = f"clean error {e.err}"
        finally:
            await cl2.unmount()
    return out


@scenario("blackhole")
async def blackhole(base: str, opts) -> dict:
    """SIGSTOP a brick: the transport stays up but answers nothing —
    ping-timeout + disconnect failfast turn it into a bounded degrade,
    not a call-timeout crawl."""
    out: dict = {}
    victim = 1
    async with Stack(base) as st:
        cl = await st.mount()
        try:
            pay = payload_for(200)
            await cl.write_file("/bh", pay)
            st.pause_brick(victim)
            try:
                t0 = time.monotonic()
                # several reads: the FIRST eats the ping-timeout
                # detection window, the rest ride the dropped child
                for _ in range(3):
                    got = await asyncio.wait_for(cl.read_file("/bh"), 60)
                    assert bytes(got) == pay, "blackhole read parity"
                dt = time.monotonic() - t0
                out["blackhole_3_reads_s"] = round(dt, 2)
                assert dt < 45, f"blackhole reads not bounded: {dt:.1f}s"
                # a write through the same hole also completes (5/6)
                await asyncio.wait_for(
                    cl.write_file("/bh2", pay[:256 * 1024]), 60)
                out["blackhole_write_ok"] = True
            finally:
                st.resume_brick(victim)
        finally:
            await cl.unmount()
    return out


@scenario("error_storm")
async def error_storm(base: str, opts) -> dict:
    """debug.error-gen deterministic failure-count storm: every
    brick's readv fails exactly N times, then passes.  While the
    budget burns a read either succeeds byte-identical or fails
    CLEANLY within its bound (never a hang, never wrong bytes); once
    it is spent — deterministically, no probability/seed tuning —
    reads recover and STAY byte-identical."""
    out: dict = {}
    async with Stack(base) as st:
        cl = await st.mount()
        try:
            pay = payload_for(300)
            await cl.write_file("/es", pay)
        finally:
            await cl.unmount()
        # arm the storm: exactly 4 readv failures per brick, then pass
        await st.set("debug.error-gen", "on")
        await st.set("debug.error-fops", "readv")
        await st.set("debug.error-number", "EIO")
        await st.set("debug.error-failure-count", "4")
        cl = await st.mount()
        try:
            clean_failures = 0
            recovered_at = None
            streak = 0
            for i in range(24):
                try:
                    got = await asyncio.wait_for(cl.read_file("/es"), 60)
                    assert bytes(got) == pay, \
                        "error-storm served WRONG bytes"
                    streak += 1
                    if recovered_at is None:
                        recovered_at = i
                    if streak >= 5:
                        break
                except FopError:
                    clean_failures += 1
                    streak = 0
                    recovered_at = None
            assert streak >= 5, \
                f"reads never recovered after the deterministic " \
                f"budget ({clean_failures} failures)"
            out["clean_failures_during_storm"] = clean_failures
            out["recovered_at_attempt"] = recovered_at
        finally:
            await cl.unmount()
        await st.set("debug.error-gen", "off")
    return out


@scenario("delay_storm")
async def delay_storm(base: str, opts) -> dict:
    """debug.delay-gen on every brick's readv: correctness and a
    bounded completion under injected latency."""
    out: dict = {}
    async with Stack(base) as st:
        cl = await st.mount()
        try:
            pay = payload_for(400)
            await cl.write_file("/ds", pay)
        finally:
            await cl.unmount()
        await st.set("debug.delay-gen", "on")
        await st.set("debug.delay-fops", "readv")
        await st.set("debug.delay-duration", "200000")  # 200ms
        await st.set("debug.delay-percent", "100")
        cl = await st.mount()
        try:
            t0 = time.monotonic()
            got = await asyncio.wait_for(cl.read_file("/ds"), 90)
            dt = time.monotonic() - t0
            assert bytes(got) == pay, "delay-storm read parity"
            out["delayed_read_s"] = round(dt, 2)
        finally:
            await cl.unmount()
        await st.set("debug.delay-gen", "off")
    return out


@scenario("gateway")
async def gateway(base: str, opts) -> dict:
    """The HTTP front door stays responsive while a brick is down —
    now against a ``workers=2`` shared-nothing pool (ISSUE 12): the
    supervisor subprocess owns the port, two worker processes serve
    it, a brick SIGKILL degrades GETs byte-identically, and a WORKER
    SIGKILL mid-load never drops the volume (the supervisor respawns,
    the sibling keeps serving)."""
    import subprocess

    from glusterfs_tpu.gateway.minihttp import fetch as http

    out: dict = {}
    async with Stack(base) as st:
        async with MgmtClient(st.d.host, st.d.port) as c:
            spec = await c.call("getspec", name=st.name)
        volfile = os.path.join(base, "gw-client.vol")
        with open(volfile, "w") as f:
            f.write(spec["volfile"])
        portfile = os.path.join(base, "gw.port")
        statusfile = os.path.join(base, "gw.status")
        inc_dir = os.path.join(base, "incidents")
        import socket

        with socket.socket() as _s:  # ephemeral metrics port
            _s.bind(("127.0.0.1", 0))
            mport = _s.getsockname()[1]
        env = dict(os.environ)
        sup = subprocess.Popen(
            [sys.executable, "-m", "glusterfs_tpu.gateway",
             "--volfile", volfile, "--workers", "2", "--pool", "2",
             "--portfile", portfile, "--statusfile", statusfile,
             "--max-clients", "128", "--metrics-port", str(mport),
             "--incident-dir", inc_dir],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
        try:
            deadline = time.monotonic() + 120
            while not os.path.exists(portfile):
                assert sup.poll() is None, "gateway supervisor died"
                assert time.monotonic() < deadline, \
                    "worker pool never came up"
                await asyncio.sleep(0.2)
            with open(portfile) as f:
                gw_port = int(f.read())
            with open(statusfile) as f:
                wst = json.load(f)
            out["workers_mode"] = wst["mode"]
            assert len(wst["workers"]) == 2
            body = payload_for(500, 1)[:512 * 1024]
            s, _, _ = await http("127.0.0.1", gw_port, "PUT", "/b")
            assert s == 200, s
            s, _, _ = await http("127.0.0.1", gw_port, "PUT",
                                 "/b/obj", body=body)
            assert s == 200, s
            # let the EC eager window's deferred size commit land
            # before breaking things: without a read lease settling it
            # (features/leases, lease_storm below), cross-pool-client
            # read-after-PUT coherence is bounded by the post-op delay
            # (~eager-lock-timeout), and THIS scenario measures
            # degraded responsiveness, not that window
            deadline = time.monotonic() + 10
            while True:
                s, _, data = await http("127.0.0.1", gw_port, "GET",
                                        "/b/obj")
                if s == 200 and data == body:
                    break
                assert time.monotonic() < deadline, \
                    f"healthy GET never settled ({s}, {len(data)}B)"
                await asyncio.sleep(0.3)
            port = st.kill_brick(3)
            t0 = time.monotonic()
            s, _, data = await asyncio.wait_for(
                http("127.0.0.1", gw_port, "GET", "/b/obj"), 60)
            assert s == 200 and data == body, \
                f"degraded gateway GET broke ({s})"
            out["degraded_get_s"] = round(time.monotonic() - t0, 2)
            s, _, _ = await asyncio.wait_for(
                http("127.0.0.1", gw_port, "PUT", "/b/obj2",
                     body=body[:64 * 1024]), 60)
            assert s in (200, 503), f"degraded PUT hung or broke ({s})"
            out["degraded_put_status"] = s
            await st.restart_brick(3, port)

            # worker kill MID-LOAD: a steady GET stream keeps running
            # while one worker dies — the volume (and the pool's port)
            # must keep answering right bytes; the supervisor respawns
            served = {"ok": 0, "refused": 0}
            stop_load = asyncio.Event()

            async def load():
                while not stop_load.is_set():
                    try:
                        s, _, d = await asyncio.wait_for(
                            http("127.0.0.1", gw_port, "GET",
                                 "/b/obj"), 30)
                        if s == 200 and d == body:
                            served["ok"] += 1
                        else:
                            served["refused"] += 1
                    except (ConnectionError, OSError,
                            asyncio.TimeoutError):
                        served["refused"] += 1
                    await asyncio.sleep(0.05)

            loader = asyncio.ensure_future(load())
            await asyncio.sleep(0.5)
            victim = wst["workers"][0]["pid"]
            os.kill(victim, signal.SIGKILL)
            t0 = time.monotonic()
            respawned = False
            while time.monotonic() - t0 < 30:
                with open(statusfile) as f:
                    wst2 = json.load(f)
                if wst2["respawns"] >= 1 and \
                        all(w["alive"] for w in wst2["workers"]):
                    respawned = True
                    break
                await asyncio.sleep(0.3)
            await asyncio.sleep(1.0)  # load rides the respawned pool
            stop_load.set()
            await loader
            assert respawned, "killed worker never respawned"
            assert served["ok"] >= 5, \
                f"volume dropped under worker kill: {served}"
            out["worker_kill_respawn_s"] = round(
                time.monotonic() - t0, 2)
            out["worker_kill_load"] = dict(served)

            # the respawn is a failure-class event: the supervisor must
            # have auto-captured an incident bundle into --incident-dir
            bundle = None
            t0 = time.monotonic()
            while time.monotonic() - t0 < 15:
                hits = [f for f in sorted(os.listdir(inc_dir))
                        if "GATEWAY_WORKER_RESPAWN" in f] \
                    if os.path.isdir(inc_dir) else []
                if hits:
                    bundle = hits[-1]
                    break
                await asyncio.sleep(0.3)
            assert bundle, "worker respawn did not auto-capture an " \
                "incident bundle"
            out["auto_captured"] = bundle

            # cross-process trace stitch: a GET's trace id minted in a
            # gateway WORKER must also appear in a BRICK daemon's span
            # ring (the wire trace element crossed the client graph)
            s, _, d = await asyncio.wait_for(
                http("127.0.0.1", mport, "GET", "/incident.json"), 15)
            assert s == 200, f"/incident.json -> {s}"
            sup_bundle = json.loads(d)
            worker_tids = {sp.get("trace")
                           for w_ in sup_bundle["workers"]
                           for sp in w_.get("flight", {}).get(
                               "spans", [])} - {None}
            local = await st.d.op_volume_incident_local(st.name)
            brick_tids = set()
            for proc in local["bricks"].values():
                if isinstance(proc, dict):
                    for sp in proc.get("spans") or []:
                        if sp.get("trace"):
                            brick_tids.add(sp["trace"])
            shared = worker_tids & brick_tids
            assert shared, "no trace id spans both a gateway worker " \
                "and a brick process"
            out["cross_process_traces"] = len(shared)
        finally:
            if sup.poll() is None:
                sup.terminate()
                try:
                    await asyncio.to_thread(sup.wait, timeout=10)
                except subprocess.TimeoutExpired:
                    sup.kill()
    return out


@scenario("lease_storm")
async def lease_storm(base: str, opts) -> dict:
    """Leased readers vs a hot writer over the managed volume (ISSUE
    16): recalls fan in bounded and voluntary, post-recall reads are
    byte-exact, re-grants keep working round after round (revocation
    would poison them), and a holder that dies without releasing is
    reaped at disconnect instead of stalling the writer."""
    out: dict = {}
    n_readers, rounds = 6, 3
    hot = 48 * 1024
    async with Stack(base) as st:
        # leases are volgen-gated off by default; flipping them on is a
        # graph-shape change -> bricks respawn with the layer.  The
        # long recall grace makes the reap assertion sharp: a holder
        # that is NOT returned/reaped costs 10s, visibly over bound.
        await st.set("features.leases", "on")
        await st.set("features.lease-recall-timeout", "10")
        await st.set("features.lease-timeout", "600")   # v15 key
        w = await st.mount()
        readers = [await st.mount() for _ in range(n_readers)]
        victim = None
        try:
            body = payload_for(7)[:hot]
            await w.write_file("/hot", body)
            write_s = []
            for rnd in range(rounds):
                for r in readers:
                    assert await r.lease_acquire("/hot"), \
                        "re-grant refused: a voluntary return poisoned"
                    assert bytes(await r.read_file("/hot")) == body
                body = payload_for(100 + rnd)[:hot]
                t0 = time.monotonic()
                await w.write_file("/hot", body)
                write_s.append(round(time.monotonic() - t0, 2))
                assert write_s[-1] < 8, \
                    f"recall fan-in stalled: {write_s}"
                for r in readers:
                    assert bytes(await r.read_file("/hot")) == body
            assert all(r.lease_recalls >= rounds for r in readers), \
                [r.lease_recalls for r in readers]
            out["write_recall_s"] = write_s
            out["recalls_per_reader"] = rounds

            # a holder that never releases: unmount drops the sockets
            # with the lease still granted; the brick's disconnect reap
            # (release_client) must clear it — the next write completes
            # inside the bound instead of burning the 10s grace
            victim = readers.pop()
            assert await victim.lease_acquire("/hot")
            await victim.unmount()
            victim = None
            await asyncio.sleep(1.0)  # let the reap land
            body = payload_for(999)[:hot]
            t0 = time.monotonic()
            await w.write_file("/hot", body)
            reap_s = time.monotonic() - t0
            assert reap_s < 8, \
                f"dead holder stalled the writer {reap_s:.1f}s"
            out["dead_holder_write_s"] = round(reap_s, 2)
            for r in readers:
                assert bytes(await r.read_file("/hot")) == body
        finally:
            if victim is not None:
                await victim.unmount()
            for r in readers:
                await r.unmount()
            await w.unmount()
    return out


@scenario("qos_storm")
async def qos_storm(base: str, opts) -> dict:
    """Greedy flooder vs polite reader (ISSUE 17): the QoS plane,
    armed by a LIVE volume-set, caps the greedy client per identity —
    its throughput drops vs the unshaped baseline, the polite client
    never errors and its p99 stays bounded, THROTTLE_START reaches
    eventsd, and volume status clients shows the shaping."""
    from glusterfs_tpu.core import events as gf_events
    from glusterfs_tpu.mgmt.eventsd import EventsDaemon

    out: dict = {}
    ev = EventsDaemon()
    udp, _ctl = await ev.start()
    # BEFORE Stack: brick subprocesses inherit the env at spawn
    os.environ["GFTPU_EVENTSD"] = f"127.0.0.1:{udp}"
    gf_events.configure(f"127.0.0.1:{udp}")
    try:
        async with Stack(base) as st:
            greedy = await st.mount()
            polite = await st.mount()
            try:
                # WRITE load: client caches would serve a read flood
                # at zero wire fops (the leased-reader exemption by
                # construction) — writes always meet the admission gate
                body = payload_for(17)[:4096]
                retries = {"greedy": 0, "polite": 0}

                async def phase(seconds: float) -> tuple[float, float]:
                    """(greedy write_file/s, polite p99 seconds) under
                    a sequential greedy flood + a paced polite writer.
                    One bounded retry absorbs the live graph-reload
                    window (the rebalance_grow discipline) — QoS sheds
                    themselves are invisible here, client backoff
                    re-sends them."""
                    stop = asyncio.Event()
                    done = {"n": 0}

                    async def put(cl, path, who) -> None:
                        try:
                            await cl.write_file(path, body)
                        except FopError:
                            retries[who] += 1
                            await cl.write_file(path, body)

                    async def flood(i: int):
                        # 4-way concurrency on distinct paths: greedy
                        # means MORE OUTSTANDING WORK, not merely a
                        # tighter loop — and no lock contention noise
                        while not stop.is_set():
                            await put(greedy, f"/g{i}", "greedy")
                            done["n"] += 1

                    ft = [asyncio.create_task(flood(i))
                          for i in range(4)]
                    lat: list[float] = []
                    t0 = time.monotonic()
                    while time.monotonic() - t0 < seconds:
                        s = time.monotonic()
                        await put(polite, "/p", "polite")
                        lat.append(time.monotonic() - s)
                        await asyncio.sleep(0.15)  # ~5/s: in budget
                    stop.set()
                    await asyncio.gather(*ft)
                    lat.sort()
                    return (done["n"] / seconds,
                            lat[int(0.99 * (len(lat) - 1))])

                g_off, p99_off = await phase(4.0)

                # LIVE flip — no remount, no brick respawn: the watcher
                # reconfigures the running server tops and the very
                # next frames meet the buckets
                await st.set("server.qos-fops-per-sec", "60")
                await st.set("server.qos-burst", "1")
                await st.set("server.qos", "on")
                await asyncio.sleep(1.5)  # volfile watcher propagation

                g_on, p99_on = await phase(6.0)
                out["greedy_rps"] = {"off": round(g_off, 1),
                                     "on": round(g_on, 1)}
                out["polite_p99_s"] = {"off": round(p99_off, 3),
                                       "on": round(p99_on, 3)}
                assert g_on < g_off * 0.7, \
                    f"flood not shaped: {g_off:.0f} -> {g_on:.0f}/s"
                assert p99_on < 2.0, \
                    f"polite p99 unbounded under flood: {p99_on:.2f}s"
                assert retries["polite"] <= 2, \
                    f"polite writer kept erroring: {retries}"
                out["reload_retries"] = dict(retries)
                assert greedy.graph and any(
                    l.qos_backoff_total > 0 for l in walk(greedy.graph.top)
                    if hasattr(l, "qos_backoff_total")), \
                    "greedy client never paid a backoff"

                # the shaping is visible in volume status clients
                async with MgmtClient(st.d.host, st.d.port) as c:
                    deep = await c.call("volume-status-deep",
                                        name=st.name, what="clients")
                rows = [r for b in deep["bricks"].values()
                        for r in b.get("clients", [])]
                shed = sum(r.get("qos", {}).get("shed_fops", 0)
                           for r in rows)
                assert shed > 0, "no brick reported qos sheds"
                out["status_shed_fops"] = shed

                # ...and in the event plane: transition-edge THROTTLE
                starts = [e for e in ev.recent
                          if e.get("event") == "THROTTLE_START"]
                assert starts, "no THROTTLE_START reached eventsd"
                assert all(e.get("reason") == "rate" for e in starts)
                out["throttle_starts"] = len(starts)
            finally:
                await greedy.unmount()
                await polite.unmount()
    finally:
        os.environ.pop("GFTPU_EVENTSD", None)
        gf_events.configure(None)
        await ev.stop()
    return out


@scenario("rebalance_grow")
async def rebalance_grow(base: str, opts) -> dict:
    """ISSUE 11 acceptance: grow a LOADED disperse 4+2 volume by an
    added distribute leg while it serves — fix-layout + daemon
    migration under live reads/writes, a SIGKILL + respawn mid-run
    RESUMES from the checkpoint (never restarts the walk), serving
    read latency stays bounded throughout, and every pre-existing and
    in-flight object is byte-identical after convergence."""
    out: dict = {}
    async with Stack(base) as st:
        await st.set("cluster.rebal-throttle", "lazy")
        await st.set("rebalance.checkpoint-interval", "0.1")
        cl = await st.mount()
        try:
            # pre-existing namespace spread over directories, so the
            # checkpoint has directory boundaries to land on
            pre: dict[str, bytes] = {}
            for dd in range(6):
                await cl.mkdir(f"/d{dd}")
                for i in range(6):
                    p = f"/d{dd}/f{i}"
                    pre[p] = payload_for(dd * 16 + i)[:256 * 1024]
                    await cl.write_file(p, pre[p])
            # serving load: reads with latency recorded (bounded!),
            # plus in-flight writes landing under the NEW layout
            lat: list[float] = []
            inflight: dict[str, bytes] = {}
            retries = {"n": 0}
            stop_load = asyncio.Event()

            async def load():
                i = 0
                names = list(pre)
                while not stop_load.is_set():
                    p = names[i % len(names)]
                    t0 = time.monotonic()
                    try:
                        got = await asyncio.wait_for(cl.read_file(p), 60)
                    except FopError:
                        # one bounded retry: the live add-brick graph
                        # swap can catch a read mid-flight
                        retries["n"] += 1
                        got = await asyncio.wait_for(cl.read_file(p), 60)
                    lat.append(time.monotonic() - t0)
                    assert bytes(got) == pre[p], \
                        f"serving read of {p} returned wrong bytes"
                    if i % 3 == 0:
                        np_path = f"/d{i % 6}/new{i}"
                        body = payload_for(7000 + i)[:64 * 1024]
                        try:
                            await asyncio.wait_for(
                                cl.write_file(np_path, body), 60)
                        except FopError:
                            # same graph-swap blip as the read above
                            # (EEXIST from a landed first try falls
                            # back to open+write inside write_file)
                            retries["n"] += 1
                            await asyncio.wait_for(
                                cl.write_file(np_path, body), 60)
                        inflight[np_path] = body
                    i += 1
                    await asyncio.sleep(0.05)

            loader = asyncio.ensure_future(load())
            try:
                async with MgmtClient(st.d.host, st.d.port) as c:
                    # a second 4+2 leg: the volume becomes 2x(4+2)
                    await c.call("volume-add-brick", name=st.name,
                                 bricks=[{"path": os.path.join(
                                     base, f"nb{i}")} for i in range(N)])
                    await c.call("volume-rebalance", name=st.name,
                                 action="start")

                def rb() -> dict:
                    return st.d._vol(st.name).get("rebalance") or {}

                # wait for a mid-migration checkpoint, then SIGKILL
                deadline = time.monotonic() + 150
                while True:
                    r = rb()
                    ck = r.get("checkpoint") or {}
                    if r.get("phase") == "migrate" and \
                            ck.get("last_dir") and \
                            (r.get("counters") or {}).get("moved", 0):
                        break
                    assert r.get("status") == "started", \
                        f"rebalance died or finished too fast: {r}"
                    assert time.monotonic() < deadline, r
                    await asyncio.sleep(0.05)
                pre_ctr = dict(rb()["counters"])
                proc = st.d.rebalanced[st.name]
                os.kill(proc.pid, signal.SIGKILL)
                await asyncio.to_thread(proc.wait)
                out["killed_at_checkpoint"] = \
                    rb()["checkpoint"]["last_dir"]
                async with MgmtClient(st.d.host, st.d.port) as c:
                    resp = await c.call("volume-rebalance",
                                        name=st.name, action="start")
                assert resp["status"] == "resumed", resp
                deadline = time.monotonic() + 240
                while rb().get("status") not in ("completed", "failed"):
                    assert time.monotonic() < deadline, rb()
                    await asyncio.sleep(0.3)
                r = rb()
                assert r["status"] == "completed", r
                assert r.get("resumed_from", {}).get("last_dir"), \
                    f"respawn restarted instead of resuming: {r}"
                fin = r["counters"]
                assert fin["scanned"] > pre_ctr["scanned"], (pre_ctr, fin)
                assert fin["dirs_fixed"] == pre_ctr["dirs_fixed"], \
                    "respawn redid fix-layout"
                out["resumed_from"] = r["resumed_from"]
                out["migrated"] = {"moved": fin["moved"],
                                   "bytes": fin["bytes_moved"],
                                   "failed": fin["failed"]}
                assert fin["failed"] == 0, fin
            finally:
                stop_load.set()
                await loader
            assert lat, "serving load never ran"
            p99 = sorted(lat)[int(0.99 * (len(lat) - 1))]
            out["serving_reads"] = len(lat)
            out["read_retries"] = retries["n"]
            out["read_p99_s"] = round(p99, 2)
            assert p99 < 30, \
                f"serving latency unbounded during rebalance: {p99:.1f}s"
        finally:
            await cl.unmount()
        # fresh mount: every object byte-identical after convergence
        cl2 = await st.mount()
        try:
            for p, body in {**pre, **inflight}.items():
                got = await asyncio.wait_for(cl2.read_file(p), 60)
                assert bytes(got) == body, \
                    f"{p} not byte-identical after growth"
            out["objects_verified"] = len(pre) + len(inflight)
        finally:
            await cl2.unmount()
    return out


@scenario("fuse")
async def fuse(base: str, opts) -> dict:
    """Kernel-mount responsiveness through a brick kill (gated behind
    --with-fuse: /dev/fuse behavior is kernel-dependent in sandboxes)."""
    if not opts.with_fuse:
        return {"skipped": "pass --with-fuse to run (kernel-dependent)"}
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "..", "tests"))
    from harness import spawn_fuse, stop_fuse

    out: dict = {}
    async with Stack(base) as st:
        mnt = os.path.join(base, "mnt")
        os.makedirs(mnt)
        proc = spawn_fuse(f"127.0.0.1:{st.d.port}", st.name,
                          os.path.join(base, "ready"), mnt)
        try:
            pay = payload_for(600)

            def timed(fn, seconds, label):
                box: dict = {}

                def work():
                    try:
                        box["v"] = fn()
                    except BaseException as e:  # noqa: BLE001
                        box["e"] = e

                th = threading.Thread(target=work, daemon=True)
                th.start()
                th.join(seconds)
                if th.is_alive():
                    raise TimeoutError(f"fuse {label} hung")
                if "e" in box:
                    raise box["e"]
                return box.get("v")

            timed(lambda: open(os.path.join(mnt, "f"), "wb").write(pay),
                  120, "write")
            port = st.kill_brick(4)
            got = timed(lambda: open(os.path.join(mnt, "f"),
                                     "rb").read(), 120, "degraded read")
            assert got == pay, "fuse degraded read parity"
            out["fuse_degraded_read_ok"] = True
            await st.restart_brick(4, port)
        finally:
            stop_fuse(proc, mnt)
    return out


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------


async def warmup(base: str) -> None:
    """Spin every process-wide lazy pool (client event pool, codec
    probe, wirec build) BEFORE the leak baseline: those threads are
    by-design persistent, not leaks."""
    async with Stack(os.path.join(base, "warm"), name="warm") as st:
        cl = await st.mount()
        try:
            pay = payload_for(0)
            await cl.write_file("/w", pay)
            assert bytes(await cl.read_file("/w")) == pay
        finally:
            await cl.unmount()


def live_threads() -> set:
    return {t.name for t in threading.enumerate() if t.is_alive()}


async def settle_tasks(grace: float = 3.0) -> list:
    """Let teardown finish, then report still-pending tasks (excluding
    the runner itself)."""
    me = asyncio.current_task()
    deadline = time.monotonic() + grace
    while time.monotonic() < deadline:
        rest = [t for t in asyncio.all_tasks() if t is not me]
        if not rest:
            return []
        await asyncio.sleep(0.2)
    return [repr(t)[:120] for t in asyncio.all_tasks() if t is not me]


async def amain(opts) -> dict:
    names = opts.scenario or [n for n in SCENARIOS if n != "fuse"]
    if opts.with_fuse and "fuse" not in names:
        names.append("fuse")
    for n in names:
        if n not in SCENARIOS:
            raise SystemExit(f"unknown scenario {n!r} "
                             f"(have: {', '.join(SCENARIOS)})")
    root = tempfile.mkdtemp(prefix="gftpu-chaos")
    report: dict = {"ok": True, "scenarios": {},
                    "host_cores": len(os.sched_getaffinity(0))}
    try:
        await warmup(root)
        baseline_threads = live_threads()
        for name in names:
            base = os.path.join(root, name)
            os.makedirs(base, exist_ok=True)
            t0 = time.monotonic()
            try:
                detail = await asyncio.wait_for(
                    SCENARIOS[name](base, opts), SCENARIO_DEADLINE_S)
                detail["ok"] = True
            except BaseException as e:  # noqa: BLE001 - report, don't die
                detail = {"ok": False, "error": repr(e)[:300],
                          "trace": traceback.format_exc()[-1200:]}
                report["ok"] = False
            detail["elapsed_s"] = round(time.monotonic() - t0, 1)
            report["scenarios"][name] = detail
            print(f"[chaos] {name}: "
                  f"{'ok' if detail['ok'] else 'FAIL'} "
                  f"({detail['elapsed_s']}s)", file=sys.stderr)
        # leak audit: nothing the failure paths spun up may survive
        leaked_tasks = await settle_tasks()
        # codec/executor threads shut down asynchronously: poll out
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            leaked = sorted(live_threads() - baseline_threads)
            if not leaked:
                break
            await asyncio.sleep(0.3)
        report["leaked_threads"] = leaked
        report["leaked_tasks"] = leaked_tasks
        if leaked or leaked_tasks:
            report["ok"] = False
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return report


#: the allowed new/old ratio of a timing row (ISSUE 20).  Every row is
#: a full-stack time on a host that timeshares glusterd, six brick
#: processes and the clients, where IDENTICAL code swings widely from
#: run to run: the recorded identical-config wire rows of the 2-core
#: sandbox spanned 9.7-45.1 MiB/s (docs/observability.md).  Inside
#: that band a slower row is scheduling noise, not a regression.
SWING_BAND_WIRE = 45.1 / 9.7


def compare_reports(now: dict, prev: dict) -> list[dict]:
    """Baseline-compare (ISSUE 20): judge this run's timing rows
    against a previous ``--json`` report.  Chaos rows are WALL-CLOCK
    TIMES: a regression is a time that GREW beyond
    :data:`SWING_BAND_WIRE`.  Only scenarios that PASSED in both runs
    are comparable; every flag is machine-readable: {"row", "prev",
    "now", "grow_pct", "band"}."""
    band = SWING_BAND_WIRE
    flags: list[dict] = []

    def check(name: str, new, old) -> None:
        if isinstance(new, (int, float)) and isinstance(old, (int, float)) \
                and old > 0 and new > old * band:
            flags.append({"row": name, "prev": old, "now": new,
                          "grow_pct": round(100 * (new / old - 1), 1),
                          "band": round(band, 2)})

    for name, d in (now.get("scenarios") or {}).items():
        pd = (prev.get("scenarios") or {}).get(name)
        if not isinstance(pd, dict) or not (d.get("ok") and pd.get("ok")):
            continue  # a failed run's timings are not a baseline
        for k, v in d.items():
            if k.endswith("_s"):
                check(f"{name}.{k}", v, pd.get(k))
    return flags


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--scenario", action="append",
                   help="scenario name (repeatable); default = all "
                        "except fuse")
    p.add_argument("--json", action="store_true")
    p.add_argument("--with-fuse", action="store_true",
                   help="include the kernel-mount scenario")
    p.add_argument("--baseline",
                   help="previous --json report to judge this run's "
                        "timing rows against (2-core swing band)")
    opts = p.parse_args()
    report = asyncio.run(amain(opts))
    if opts.baseline:
        try:
            with open(opts.baseline) as f:
                report["regressions"] = compare_reports(report,
                                                        json.load(f))
        except (OSError, ValueError) as e:
            report["regressions"] = [{"row": "baseline-unreadable",
                                      "error": repr(e)[:200]}]
    if opts.json:
        print(json.dumps(report, indent=1, default=repr))
    else:
        for name, d in report["scenarios"].items():
            print(f"{name}: {'ok' if d.get('ok') else 'FAIL'}  {d}")
        print(f"leaked_threads={report['leaked_threads']} "
              f"leaked_tasks={len(report['leaked_tasks'])}")
        for r in report.get("regressions", []):
            print(f"regression: {r}")
        print("chaos:", "GREEN" if report["ok"] else "RED")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
