"""Management daemon — the glusterd analog (scoped to the ~5% that
matters: volinfo store, peers, txn, volgen, brick lifecycle, portmap,
volfile serving; SURVEY.md §7 "keep the management plane small").

Reference: xlators/mgmt/glusterd (108k LoC).  Kept behaviors:

* **Persistent store** (glusterd-store.c:561,1643): volumes + peers
  survive restart (JSON under the workdir).
* **Volume lifecycle**: create/start/stop/delete/set + info/status
  (op-sm commit path); start spawns one brick daemon per local brick
  (glusterd-utils.c runner) and records its port (portmap,
  glusterd-pmap.c:661).
* **Volgen** (glusterd-volgen.c): brick + client volfiles from volinfo.
* **Volfile serving** (__server_getspec, glusterd-handshake.c:867):
  clients fetch their graph over the mgmt RPC and mount it.
* **Peers + distributed txn** (glusterd-op-sm.c states lock -> stage ->
  commit): peer probe forms a cluster; volume ops lock all peers, stage
  (validate), commit (apply + store) — driven by the originating node
  (mgmt-v3 style, glusterd-mgmt.c).
* **Heal/profile/rebalance entry points** (glusterd-op-sm op handlers):
  forwarded to a temporary client graph mounted in-process.

The mgmt wire protocol reuses rpc/wire framing with method dispatch.
"""

from __future__ import annotations

import argparse
import asyncio
import errno
import json
import os
import signal
import subprocess
import sys
import time
import uuid
from typing import Any

from .. import pin_cpu
from ..core import flight, gflog
from ..core.events import gf_event
from .bitd import DEFAULT_SCRUB_THROTTLE
from ..core.fops import FopError
from ..protocol.server import STATUS_KINDS
from ..rpc import wire
from . import volgen

log = gflog.get_logger("mgmt")

# this build's management op-version (xlator.h:758 / GD_OP_VERSION):
# the constant lives at the package root so client processes can
# advertise it without importing the mgmt plane; re-exported here for
# the historical import path
from .. import OP_VERSION  # noqa: F401


def _new_volinfo(state: dict, name: str, vtype: str, bricks: list,
                 redundancy: int) -> dict:
    """Volinfo scaffolding shared by volume-create and snapshot-clone:
    tombstone-seeded config generation, fresh id, and the per-volume
    credential pairs (client pair in every volfile, mgmt pair only in
    brick volfiles — glusterd_auth_set_username trusted-volfile model).
    The two creation paths must mint identical shapes."""
    return {
        "name": name, "type": vtype, "bricks": bricks,
        "redundancy": redundancy, "status": "created",
        "version": int(state.get("tombstones", {}).get(name, 0)) + 1,
        "options": {}, "id": str(uuid.uuid4()),
        "auth": {"username": str(uuid.uuid4()),
                 "password": str(uuid.uuid4()),
                 "mgmt-username": str(uuid.uuid4()),
                 "mgmt-password": str(uuid.uuid4())},
    }


def _copy_store(src: str, dst: str) -> None:
    """Replace a brick store with a copy of another (snapshot restore
    and clone both land here): a file-level copy changes every inode,
    so the gfid identity store and handle farm are rebound onto the
    copied files afterwards."""
    import shutil

    from ..storage.posix import rebuild_identity

    shutil.rmtree(dst, ignore_errors=True)
    os.makedirs(os.path.dirname(dst), exist_ok=True)
    shutil.copytree(src, dst, symlinks=True)
    rebuild_identity(dst)


class MgmtError(Exception):
    pass


class Glusterd:
    """One management daemon instance (one per node)."""

    def __init__(self, workdir: str, host: str = "127.0.0.1",
                 port: int = 0):
        self.workdir = os.path.abspath(workdir)
        self.host = host
        self.port = port
        os.makedirs(self.workdir, exist_ok=True)
        self._store = os.path.join(self.workdir, "store.json")
        self.state = self._load()
        self.uuid = self.state.setdefault("uuid", str(uuid.uuid4()))
        self.op_version = OP_VERSION
        self.bricks: dict[str, subprocess.Popen] = {}  # brickname -> proc
        self.ports: dict[str, int] = {}  # portmap: brickname -> port
        self.shd: dict[str, subprocess.Popen] = {}  # volname -> shd proc
        self.gsync: dict[str, subprocess.Popen] = {}  # volname -> gsyncd
        self.bitd: dict[str, subprocess.Popen] = {}  # volname -> bitd
        self.quotad: dict[str, subprocess.Popen] = {}  # volname -> quotad
        self.gateway: dict[str, subprocess.Popen] = {}  # volname -> gateway
        self.rebalanced: dict[str, subprocess.Popen] = {}  # volname -> rebal
        self._rb_saved: dict[str, float] = {}  # volname -> last ckpt save
        self._server: asyncio.AbstractServer | None = None
        self._txn_lock = asyncio.Lock()
        self._txn_holder: str | None = None
        self._subs: dict[str, set] = {}  # volname -> subscribed writers
        # server-quorum (glusterd-server-quorum.c): volumes whose bricks
        # this node killed because the mgmt cluster lost quorum
        self.quorum_interval = 5.0
        self._quorum_blocked: set[str] = set()
        self._quorum_task: asyncio.Task | None = None
        # brick multiplexing (glusterfsd-mgmt.c ATTACH): one shared
        # daemon per node serving every brick-multiplex'd brick
        self._mux: dict | None = None  # {proc, port, bricks:set}
        self._mux_lock = asyncio.Lock()
        # strong refs to fire-and-forget work (drain, post-replace
        # heal): the loop keeps only weak refs, and a GC'd drain task
        # would strand remove-brick in status "started" forever
        self._bg_tasks: set[asyncio.Task] = set()

    # -- store (glusterd-store.c analog) -----------------------------------

    def _load(self) -> dict:
        try:
            with open(self._store) as f:
                return json.load(f)
        except FileNotFoundError:
            return {"volumes": {}, "peers": {}}

    def _save(self) -> None:
        tmp = self._store + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.state, f, indent=1)
        os.replace(tmp, self._store)

    @staticmethod
    def _bump(vol: dict) -> None:
        """Advance a volume's config generation.  Every cluster-txn commit
        that mutates volinfo bumps in lockstep on the nodes that saw it;
        peer-hello reconciliation then imports the higher generation into
        nodes that missed the txn (the friend-sm volinfo import of
        glusterd-utils.c glusterd_compare_friend_volume, keyed there on
        volinfo->version exactly like this)."""
        vol["version"] = int(vol.get("version", 1)) + 1

    # -- service -----------------------------------------------------------

    async def start(self) -> int:
        self._server = await asyncio.start_server(self._serve, self.host,
                                                  self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        self.state["endpoint"] = f"{self.host}:{self.port}"
        self._save()
        log.info(10, "glusterd %s on %s:%d (workdir %s)", self.uuid[:8],
                 self.host, self.port, self.workdir)
        # restart-resume: bricks/shd/gsyncd/bitd of started volumes
        for vol in self.state["volumes"].values():
            if vol.get("status") == "started":
                await self._start_local_bricks(vol)
                # fire-and-forget: the fan-out waits up to 10s per
                # unreachable peer and must not stall daemon startup
                self._spawn_task(self._broadcast_local_ports(vol))
                self._spawn_shd(vol)
                if vol.get("georep", {}).get("status") == "started":
                    self._spawn_gsync(vol)
                if volgen._bool(vol.get("options", {}).get(
                        "features.bitrot", "off")):
                    self._spawn_bitd(vol)
                if volgen._bool(vol.get("options", {}).get(
                        "features.quota", "off")):
                    self._spawn_quotad(vol)
                if vol.get("gateway", {}).get("status") == "started":
                    self._spawn_gateway(vol)
                if vol.get("rebalance", {}).get("status") == "started" \
                        and vol["rebalance"].get("node") == self.uuid:
                    # restart-resume: the daemon picks its checkpoint
                    # out of the volinfo and CONTINUES the walk
                    self._spawn_rebalanced(vol)
        # activated snapshots resume serving too
        for s in self.state.get("snaps", {}).values():
            vi = s.get("volinfo")
            if vi:
                await self._start_bricks(vi, vi["bricks"])
        self._quorum_task = asyncio.create_task(self._quorum_loop())
        # catch up on config txns committed while this node was down
        # (the restart side of the friend handshake)
        if any(p["uuid"] != self.uuid
               for p in self.state["peers"].values()):
            self._spawn_task(self._refresh_peers())
        return self.port

    async def stop(self) -> None:
        # daemon shutdown kills workers WITHOUT touching the persisted
        # session status: a restarted glusterd resumes started sessions
        if self._quorum_task is not None:
            self._quorum_task.cancel()
            try:
                await self._quorum_task
            except (asyncio.CancelledError, Exception):
                pass
            self._quorum_task = None
        for name in list(self.gsync):
            self._kill_gsync(name)
        for name in list(self.bitd):
            self._kill_bitd(name)
        for name in list(self.quotad):
            self._kill_quotad(name)
        for name in list(self.gateway):
            self._kill_gateway(name)
        for name in list(self.rebalanced):
            self._kill_rebalanced(name)
        for name in list(self.shd):
            self._kill_shd(name)
        for name in list(self.bricks):
            self._kill_brick(name)
        if self._mux is not None:
            await self._reap(self._mux["proc"])
            self._mux = None
        if self._server is not None:
            self._server.close()
            for w in list(getattr(self, "_writers", [])):
                try:
                    w.close()
                except Exception:
                    pass
            await self._server.wait_closed()
            self._server = None

    async def _serve(self, reader, writer) -> None:
        self._writers = getattr(self, "_writers", set())
        self._writers.add(writer)
        try:
            while True:
                try:
                    rec = await wire.read_frame(reader)
                except (asyncio.IncompleteReadError, ConnectionError):
                    break
                xid, mtype, payload = wire.unpack(rec)
                try:
                    method, kwargs = payload
                    if method == "subscribe":
                        # volfile-change notifications for this
                        # connection (the reference's mgmt fetch-spec
                        # callback channel, glusterfsd-mgmt.c)
                        self._subs.setdefault(
                            kwargs["name"], set()).add(writer)
                        writer.write(wire.pack(xid, wire.MT_REPLY,
                                               {"ok": True}))
                        await writer.drain()
                        continue
                    fn = getattr(self, "op_" + method.replace("-", "_"),
                                 None)
                    if fn is None:
                        raise MgmtError(f"unknown op {method!r}")
                    ret = fn(**(kwargs or {}))
                    if asyncio.iscoroutine(ret):
                        ret = await ret
                    resp = (wire.MT_REPLY, ret)
                except (MgmtError, FopError) as e:
                    resp = (wire.MT_ERROR, FopError(
                        getattr(e, "err", errno.EINVAL), str(e)))
                except Exception as e:
                    log.error(11, "mgmt op failed: %r", e)
                    resp = (wire.MT_ERROR, FopError(errno.EIO, repr(e)))
                try:
                    writer.write(wire.pack(xid, *resp))
                    await writer.drain()
                except ConnectionError:
                    break
        finally:
            self._writers.discard(writer)
            for subs in self._subs.values():
                subs.discard(writer)
            try:
                writer.close()
            except Exception:
                pass

    def _notify_subscribers(self, name: str) -> None:
        """Push volfile-modified to every subscribed client connection."""
        frame = wire.pack(0, wire.MT_EVENT,
                          {"event": "volfile-modified", "volume": name})
        for w in list(self._subs.get(name, ())):
            try:
                w.write(frame)
            except Exception:
                self._subs[name].discard(w)

    # -- peers (glusterd-sm.c peer membership) -----------------------------

    async def op_peer_probe(self, host: str, port: int) -> dict:
        async with MgmtClient(host, port) as peer:
            info = await peer.call("peer-hello", me=self._peer_info(),
                                   **self._volume_export())
        self.state["peers"][info["uuid"]] = {
            k: v for k, v in info.items()
            if k not in ("volumes", "tombstones")}
        self._save()
        await self._reconcile_volumes(info.get("volumes"),
                                      info.get("tombstones"),
                                      from_uuid=info["uuid"])
        return {"ok": True, "peer": info}

    async def op_peer_hello(self, me: dict, volumes: dict | None = None,
                            tombstones: dict | None = None) -> dict:
        self.state["peers"][me["uuid"]] = me
        self._save()
        await self._reconcile_volumes(volumes, tombstones,
                                      from_uuid=me["uuid"])
        return {**self._peer_info(), **self._volume_export()}

    def _volume_export(self) -> dict:
        """Everything a peer needs to catch up on missed config txns."""
        return {"volumes": self.state["volumes"],
                "tombstones": self.state.get("tombstones", {})}

    async def _reconcile_volumes(self, volumes: dict | None,
                                 tombstones: dict | None,
                                 from_uuid: str | None = None
                                 ) -> list[str]:
        """Import newer volume generations a handshaking peer carries.

        The reference's friend handshake imports/compares volumes
        (glusterd-sm.c friend-sm + glusterd_compare_friend_volume); this
        is what lets its op-sm safely skip disconnected peers — they
        catch up here, not in the txn.  Deletions travel as tombstones
        (name -> generation at delete) so a peer that missed
        volume-delete drops the volume instead of resurrecting it; a
        re-created volume starts past its tombstone generation, so it
        survives reconciliation against stale tombstones.
        """
        changed: list[str] = []
        dirty = False  # learned tombstones must persist even with no
        vols = self.state["volumes"]  # volume change (else a restart
        tset = self.state.setdefault("tombstones", {})  # forgets them)
        for name, tver in (tombstones or {}).items():
            mine = vols.get(name)
            if mine is not None and tver >= int(mine.get("version", 1)):
                log.info(24, "reconcile: dropping %s (deleted at gen %d "
                         "while this node was away)", name, tver)
                vols.pop(name)
                await self._conform_local_daemons(
                    {**mine, "status": "stopped", "name": name},
                    deleted=True)
                self._notify_subscribers(name)
                changed.append(name)
            if int(tset.get(name, 0)) < int(tver):
                tset[name] = int(tver)
                dirty = True
        for name, vi in (volumes or {}).items():
            if int(tset.get(name, -1)) >= int(vi.get("version", 1)):
                continue  # deleted here at/after that generation
            mine = vols.get(name)
            if mine is None or \
                    int(vi.get("version", 1)) > int(mine.get("version", 1)):
                log.info(24, "reconcile: importing %s gen %d (had %s)",
                         name, int(vi.get("version", 1)),
                         "none" if mine is None
                         else f"gen {int(mine.get('version', 1))}")
                vols[name] = json.loads(json.dumps(vi))  # own copy
                changed.append(name)
        # brick ports are RUNTIME state owned by the hosting node, not
        # config: adopt the sender's ports for bricks IT hosts even when
        # generations tie (two nodes that both restarted hold equal gens
        # yet each has rebound its own bricks — version-keyed import
        # alone would leave both serving the other's dead ports)
        for name, vi in (volumes or {}).items():
            mine = vols.get(name)
            if mine is None or from_uuid is None or name in changed:
                continue
            theirs = {b["name"]: b["port"] for b in vi.get("bricks", ())
                      if b.get("node") == from_uuid and b.get("port")}
            for b in mine["bricks"]:
                p = theirs.get(b["name"])
                if p and b.get("port") != p:
                    b["port"] = p
                    self.ports[b["name"]] = p
                    dirty = True
                    if name not in changed:
                        changed.append(name)
        if changed or dirty:
            self._save()
            for name in changed:
                vol = vols.get(name)
                if vol is not None:
                    await self._conform_local_daemons(vol)
                    self._notify_subscribers(name)
        return changed

    async def _conform_local_daemons(self, vol: dict,
                                     deleted: bool = False) -> None:
        """Make local processes match an imported volinfo: start missing
        bricks/daemons of started volumes, stop leftovers of stopped or
        shrunk ones (the respawn side of glusterd_import_friend_volume).
        ``deleted``: the volume was dropped by a tombstone — every
        worker goes, including the geo-rep one a plain stop keeps."""
        name = vol["name"]
        started = vol.get("status") == "started"
        want = {b["name"] for b in vol["bricks"] if b["node"] == self.uuid}
        prefix = f"{name}-brick-"
        for bname in [b for b in self.bricks if b.startswith(prefix)]:
            if not started or bname not in want:
                b = next((x for x in vol["bricks"] if x["name"] == bname),
                         {"name": bname, "node": self.uuid})
                await self._stop_brick(vol, b)
        if started:
            try:
                await self._start_local_bricks(vol)
            except MgmtError as e:
                log.error(24, "reconcile: brick start for %s failed: %s",
                          name, e)
            # the imported volinfo carries the PEER's (possibly stale)
            # view of this node's brick ports: re-assert the live local
            # ports and push them cluster-wide, else peers keep serving
            # client volfiles pointing at the pre-restart ports.
            # Fire-and-forget: this runs inside the peer-hello RPC
            # handler, and a second unreachable peer would stall the
            # reply past the caller's 5s timeout, losing the catch-up.
            self._spawn_task(self._broadcast_local_ports(vol))
            self._spawn_shd(vol)
            if volgen._bool(vol.get("options", {}).get(
                    "features.bitrot", "off")):
                self._spawn_bitd(vol)
            if volgen._bool(vol.get("options", {}).get(
                    "features.quota", "off")):
                self._spawn_quotad(vol)
            if vol.get("georep", {}).get("status") == "started":
                self._spawn_gsync(vol)
            if vol.get("gateway", {}).get("status") == "started":
                self._spawn_gateway(vol)
            else:
                self._kill_gateway(name)
            if vol.get("rebalance", {}).get("status") == "started" and \
                    vol["rebalance"].get("node") == self.uuid:
                self._spawn_rebalanced(vol)
        else:
            self._kill_shd(name)
            self._kill_bitd(name)
            self._kill_quotad(name)
            self._kill_gateway(name)
            self._kill_rebalanced(name)
            if deleted:
                self._kill_gsync(name)

    def op_peer_status(self) -> dict:
        return {"me": self._peer_info(),
                "peers": list(self.state["peers"].values())}

    def _peer_info(self) -> dict:
        return {"uuid": self.uuid, "host": self.host, "port": self.port,
                "workdir": self.workdir, "op-version": self.op_version}

    def cluster_op_version(self) -> int:
        """The version every member supports: min over self + peers
        (peers probed by older builds advertise nothing -> 1)."""
        vers = [self.op_version]
        for p in self.state["peers"].values():
            if p["uuid"] != self.uuid:
                vers.append(int(p.get("op-version", 1)))
        return min(vers)

    async def _refresh_peers(self) -> None:
        """Re-handshake every reachable peer so stored peer info (esp.
        op-version) reflects its CURRENT build — the stored value is a
        probe-time snapshot, and an upgraded-and-restarted peer must be
        able to lift the cluster op-version without detach+re-probe
        (the reference re-advertises on every RPC handshake)."""
        for p in list(self.state["peers"].values()):
            if p["uuid"] == self.uuid:
                continue
            try:
                info = await asyncio.wait_for(self._node_call(
                    p, "peer-hello", me=self._peer_info(),
                    **self._volume_export()), 5)
                self.state["peers"][info["uuid"]] = {
                    k: v for k, v in info.items()
                    if k not in ("volumes", "tombstones")}
            except Exception:
                continue  # unreachable: keep the snapshot
            await self._reconcile_volumes(info.get("volumes"),
                                          info.get("tombstones"),
                                          from_uuid=info["uuid"])
        self._save()

    def _all_nodes(self) -> list[dict]:
        return [self._peer_info()] + [
            p for p in self.state["peers"].values()
            if p["uuid"] != self.uuid]

    def op_peer_ping(self) -> dict:
        return {"ok": True, "uuid": self.uuid}

    def _spawn_task(self, coro) -> asyncio.Task:
        t = asyncio.create_task(coro)
        self._bg_tasks.add(t)
        t.add_done_callback(self._bg_tasks.discard)
        return t

    # -- server quorum (glusterd-server-quorum.c) --------------------------
    # cluster.server-quorum-type=server volumes have their local bricks
    # killed while fewer than server-quorum-ratio percent of the mgmt
    # cluster's nodes are reachable, and respawned when quorum returns —
    # fencing writes on a partitioned node so the majority side's heal
    # has a single authoritative history.

    def _quorum_volumes(self) -> list[dict]:
        return [v for v in self.state["volumes"].values()
                if v.get("status") == "started"
                and v.get("options", {}).get(
                    "cluster.server-quorum-type") == "server"]

    async def _alive_count(self) -> tuple[int, int]:
        """(reachable nodes incl. me, total nodes incl. me)."""
        peers = [p for p in self.state["peers"].values()
                 if p["uuid"] != self.uuid]

        async def ping(p: dict) -> bool:
            async def one() -> None:
                async with MgmtClient(p["host"], p["port"]) as c:
                    await c.call("peer-ping")

            # bound the CONNECT too: a black-holed peer (packets dropped,
            # no RST) must not stall loss detection for the kernel's
            # minutes-long connect timeout
            try:
                await asyncio.wait_for(one(), 2)
                return True
            except Exception:
                return False

        alive = await asyncio.gather(*(ping(p) for p in peers))
        return 1 + sum(alive), 1 + len(peers)

    def _quorum_met(self, vol: dict, alive: int, total: int) -> bool:
        ratio = float(vol.get("options", {}).get(
            "cluster.server-quorum-ratio", 51))
        return alive * 100 >= ratio * total

    async def _quorum_loop(self) -> None:
        while True:
            await asyncio.sleep(self.quorum_interval)
            try:
                await self._check_server_quorum()
            except asyncio.CancelledError:
                raise
            except Exception as e:
                log.debug(14, "quorum check failed: %r", e)

    async def _check_server_quorum(self) -> None:
        vols = self._quorum_volumes()
        peers = [p for p in self.state["peers"].values()
                 if p["uuid"] != self.uuid]
        # volumes blocked earlier that stopped enforcing (option
        # flipped to none) or lost their peers (detach): unblock them —
        # single-node clusters are quorate, and a non-enforcing volume
        # must never stay fenced
        if self._quorum_blocked:
            enforcing = {v["name"] for v in vols} if peers else set()
            for stale in list(self._quorum_blocked - enforcing):
                vol = self.state["volumes"].get(stale)
                if vol is None or vol.get("status") != "started":
                    self._quorum_blocked.discard(stale)
                    continue
                # un-block only AFTER the respawn succeeds: a failed
                # spawn must leave the name in the set so the next
                # tick retries instead of stranding the bricks
                await self._start_local_bricks(vol, reuse_ports=True)
                self._quorum_blocked.discard(stale)
                log.info(16, "quorum enforcement lifted: restarted "
                         "bricks of %s", stale)
        if not vols or not peers:
            return
        alive, total = await self._alive_count()
        for vol in vols:
            name = vol["name"]
            met = self._quorum_met(vol, alive, total)
            if not met and name not in self._quorum_blocked:
                self._quorum_blocked.add(name)
                for b in vol["bricks"]:
                    if b["node"] == self.uuid:
                        await self._stop_brick(vol, b)
                log.error(15, "server quorum lost (%d/%d): stopped "
                          "bricks of %s", alive, total, name)
                gf_event("SERVER_QUORUM_LOST", volume=name,
                         alive=alive, total=total)
            elif met and name in self._quorum_blocked:
                # reuse the recorded ports: fenced clients are still
                # retrying them
                await self._start_local_bricks(vol, reuse_ports=True)
                # only now: a failed respawn keeps the volume blocked
                # so the next tick retries
                self._quorum_blocked.discard(name)
                log.info(16, "server quorum regained (%d/%d): restarted "
                         "bricks of %s", alive, total, name)
                gf_event("SERVER_QUORUM_REGAINED", volume=name,
                         alive=alive, total=total)

    # -- hooks (glusterd-hooks.c) ------------------------------------------
    # Executable S*-prefixed scripts under
    # <workdir>/hooks/1/<op>/{pre,post}/ run around each volume op's
    # commit on every committing node, with --volname=<name> plus
    # op-specific args; failures are logged, never fatal (the
    # reference's advisory hook semantics).

    async def _run_hooks(self, op: str, phase: str, volname: str,
                         extra: tuple = ()) -> list[str]:
        # scripts block; keep the mgmt event loop (peer pings!) live
        return await asyncio.to_thread(
            self._run_hooks_sync, op, phase, volname, extra)

    def _run_hooks_sync(self, op: str, phase: str, volname: str,
                        extra: tuple = ()) -> list[str]:
        hookdir = os.path.join(self.workdir, "hooks", "1", op, phase)
        try:
            scripts = sorted(s for s in os.listdir(hookdir)
                             if s.startswith("S"))
        except FileNotFoundError:
            return []
        env = dict(os.environ)
        env["GLUSTERD_WORKDIR"] = self.workdir
        ran = []
        for s in scripts:
            path = os.path.join(hookdir, s)
            if not os.access(path, os.X_OK):
                continue
            try:
                res = subprocess.run(
                    [path, f"--volname={volname}", *extra], env=env,
                    timeout=30, check=False, stdout=subprocess.DEVNULL,
                    stderr=subprocess.PIPE)
                if res.returncode != 0:
                    log.error(17, "hook %s/%s/%s exited %d: %s", op,
                              phase, s, res.returncode,
                              (res.stderr or b"")[-500:].decode(
                                  errors="replace"))
                ran.append(s)
            except Exception as e:
                log.error(17, "hook %s/%s/%s failed: %r", op, phase, s, e)
        return ran

    # -- txn engine (lock -> stage -> commit, glusterd-op-sm.h:28-43) ------

    def op_txn_lock(self, holder: str) -> dict:
        # single-threaded event loop: check-and-set is atomic here
        if self._txn_holder is not None and self._txn_holder != holder:
            raise MgmtError(f"cluster busy (locked by {self._txn_holder})")
        self._txn_holder = holder
        return {"ok": True}

    def op_txn_unlock(self, holder: str) -> dict:
        if self._txn_holder == holder:
            self._txn_holder = None
        return {"ok": True}

    async def op_txn_stage(self, op: str, payload: dict) -> dict:
        fn = getattr(self, "stage_" + op.replace("-", "_"), None)
        if fn is not None:
            fn(**payload)
        return {"ok": True}

    async def op_txn_commit(self, op: str, payload: dict) -> dict:
        fn = getattr(self, "commit_" + op.replace("-", "_"))
        ret = fn(**payload)
        if asyncio.iscoroutine(ret):
            ret = await ret
        return {"ok": True, "result": ret}

    async def _cluster_txn(self, op: str, payload: dict) -> list:
        """Run lock/stage/commit across all reachable nodes (originator
        drives).  Peers that cannot be reached at lock time are skipped
        for the whole txn — the reference's op-sm spans only connected
        peers (rpc-state gated), so a dead node never wedges volume ops;
        it re-syncs state on its next handshake."""
        nodes = []
        holder = self.uuid
        locked: list[dict] = []
        try:
            for n in self._all_nodes():
                try:
                    # EOFError: peer died between connect and reply
                    # (IncompleteReadError); 10s bound: accepted-but-hung
                    # peers must not wedge every volume op
                    await asyncio.wait_for(
                        self._node_call(n, "txn-lock", holder=holder), 10)
                except FopError:
                    # the peer ANSWERED (e.g. cluster busy): a real
                    # rejection, not unreachability — abort the txn
                    raise
                except asyncio.TimeoutError:
                    # the peer may have APPLIED the lock after we gave
                    # up: keep it out of stage/commit but send the
                    # best-effort unlock, else its stale holder wedges
                    # every later txn
                    locked.append(n)
                    log.error(18, "peer %s lock timed out: skipped "
                              "from %s txn", n["uuid"][:8], op)
                    continue
                except (ConnectionError, OSError, EOFError):
                    log.error(18, "peer %s unreachable: skipped from "
                              "%s txn", n["uuid"][:8], op)
                    continue
                nodes.append(n)
                locked.append(n)
            # bounded like the lock phase: a peer hanging AFTER it
            # granted its lock must not hold the cluster lock forever.
            # Stage validates (fast); commit may spawn bricks, so its
            # bound is generous.  Timeout aborts the txn (a commit is
            # not safely skippable) — the finally-unlock still runs.
            for n in nodes:
                await asyncio.wait_for(
                    self._node_call(n, "txn-stage", op=op,
                                    payload=payload), 60)
            results = []
            for n in nodes:
                results.append(await asyncio.wait_for(
                    self._node_call(n, "txn-commit", op=op,
                                    payload=payload), 600))
            return results
        finally:
            for n in locked:
                try:
                    await asyncio.wait_for(
                        self._node_call(n, "txn-unlock", holder=holder),
                        10)
                except Exception:
                    pass

    async def _node_call(self, node: dict, method: str, **kwargs):
        if node["uuid"] == self.uuid:
            fn = getattr(self, "op_" + method.replace("-", "_"))
            ret = fn(**kwargs)
            if asyncio.iscoroutine(ret):
                ret = await ret
            return ret
        async with MgmtClient(node["host"], node["port"]) as c:
            return await c.call(method, **kwargs)

    # -- volume ops --------------------------------------------------------

    async def op_volume_create(self, name: str, vtype: str,
                               bricks: list, redundancy: int = 2,
                               group_size: int = 0,
                               arbiter: int = 0,
                               thin_arbiter: int = 0,
                               systematic: int = -1) -> dict:
        """bricks: list of {host, port(optional: mgmt node), path} or
        'host:/path' strings; host must match a node's host:port mgmt id
        or 'localhost'.

        ``systematic``: -1 (unset) defaults NEW disperse volumes to the
        systematic code layout once the whole cluster is at op-version
        12 (ROADMAP item 5's standing note; the parity-delta write
        plane is the write-side justification, zero-decode healthy
        reads were the read side).  Explicit 0 opts out (CLI:
        ``volume create ... non-systematic``)."""
        if name in self.state["volumes"]:
            raise MgmtError(f"volume {name} exists")
        if name.startswith("snap-"):
            raise MgmtError("volume names starting with 'snap-' are "
                            "reserved for activated snapshots")
        parsed = []
        for i, b in enumerate(bricks):
            if isinstance(b, str):
                nodeid, _, path = b.partition(":")
                b = {"node": nodeid, "path": path}
            node = self._resolve_node(b["node"]) if b.get("node") \
                else self._peer_info()
            parsed.append({
                "index": i, "node": node["uuid"],
                "host": b.get("host", node["host"]),
                "path": b["path"],
                "name": f"{name}-brick-{i}",
            })
        volinfo = _new_volinfo(self.state, name, vtype, parsed,
                               redundancy)
        if group_size:
            volinfo["group-size"] = group_size
        if arbiter:
            g = group_size or len(parsed)
            if vtype != "replicate" or arbiter != 1 or g != 3:
                # 2 data copies + 1 witness; anything else either has a
                # single data copy or is shapes gluster also rejects
                raise MgmtError("arbiter needs replica 3 arbiter 1")
            volinfo["arbiter"] = 1
        if thin_arbiter:
            if vtype != "replicate" or len(parsed) != 3 or arbiter:
                raise MgmtError("thin-arbiter needs replica 2 + one "
                                "tie-breaker brick (3 bricks)")
            volinfo["thin-arbiter"] = 1
        if systematic < 0:
            # default-on for new disperse volumes (explicit opt-out
            # only), mixed-version guarded: a pre-12 peer's volgen
            # would hand out non-systematic volfiles for this volume
            systematic = 1 if vtype == "disperse" and \
                self.cluster_op_version() >= 12 else 0
        if systematic:
            if vtype != "disperse":
                raise MgmtError("systematic applies to disperse volumes")
            # mixed-version guard (same gate volume-set keys get): an
            # older peer's volgen has no systematic branch and would
            # hand clients non-systematic volfiles for this volume —
            # writes through them would corrupt the fragment format
            if self.cluster_op_version() < 4:
                raise MgmtError(
                    "systematic volumes need cluster op-version >= 4 "
                    f"(cluster is at {self.cluster_op_version()})")
            # fragment format on the bricks: create-time only (flipping
            # it on existing fragments decodes to garbage)
            volinfo["systematic"] = 1
        if vtype == "disperse":
            n = len(parsed)
            g = group_size or n
            if g - redundancy < 1 or g % 1 or n % g:
                raise MgmtError("bad disperse geometry")
        await self._cluster_txn("volume-create", {"volinfo": volinfo})
        return {"ok": True, "volume": name}

    async def commit_volume_create(self, volinfo: dict) -> dict:
        await self._run_hooks("create", "pre", volinfo["name"])
        self.state["volumes"][volinfo["name"]] = volinfo
        self.state.get("tombstones", {}).pop(volinfo["name"], None)
        self._save()
        gf_event("VOLUME_CREATE", name=volinfo["name"],
                 type=volinfo["type"])
        await self._run_hooks("create", "post", volinfo["name"])
        return {"created": volinfo["name"]}

    def stage_volume_create(self, volinfo: dict) -> None:
        if volinfo["name"] in self.state["volumes"]:
            raise MgmtError(f"volume {volinfo['name']} exists here")

    async def op_volume_start(self, name: str) -> dict:
        self._vol(name)
        results = await self._cluster_txn("volume-start", {"name": name})
        # merge every node's portmap and broadcast it (pmap sync)
        ports: dict[str, int] = {}
        for r in results:
            ports.update(r.get("result", {}).get("ports", {}))
        for node in self._all_nodes():
            try:
                await self._node_call(node, "portmap-update",
                                      name=name, ports=ports)
            except Exception:
                pass
        return {"ok": True, "ports": ports}

    async def commit_volume_start(self, name: str) -> dict:
        vol = self._vol(name)
        await self._run_hooks("start", "pre", name)
        vol["status"] = "started"
        self._bump(vol)
        self._save()
        await self._start_local_bricks(vol)
        self._spawn_shd(vol)
        if volgen._bool(vol.get("options", {}).get("features.bitrot",
                                                   "off")):
            self._spawn_bitd(vol)
        if volgen._bool(vol.get("options", {}).get("features.quota",
                                                   "off")):
            self._spawn_quotad(vol)
        if vol.get("gateway", {}).get("status") == "started":
            self._spawn_gateway(vol)
        if vol.get("rebalance", {}).get("status") == "started" and \
                vol["rebalance"].get("node") == self.uuid:
            self._spawn_rebalanced(vol)
        gf_event("VOLUME_START", name=name)
        await self._run_hooks("start", "post", name)
        return {"started": name,
                "ports": {b["name"]: self.ports[b["name"]]
                          for b in vol["bricks"]
                          if b["name"] in self.ports}}

    def op_portmap_update(self, name: str, ports: dict) -> dict:
        vol = self._vol(name)
        for b in vol["bricks"]:
            if b["name"] in ports:
                b["port"] = ports[b["name"]]
        self.ports.update(ports)
        self._save()
        return {"ok": True}

    async def op_volume_stop(self, name: str) -> dict:
        await self._cluster_txn("volume-stop", {"name": name})
        return {"ok": True}

    async def commit_volume_stop(self, name: str) -> dict:
        vol = self._vol(name)
        await self._run_hooks("stop", "pre", name)
        vol["status"] = "stopped"
        self._quorum_blocked.discard(name)
        self._bump(vol)
        self._save()
        self._kill_bitd(name)
        self._kill_quotad(name)
        self._kill_gateway(name)
        self._kill_rebalanced(name)
        self._kill_shd(name)
        for b in vol["bricks"]:
            if b["node"] == self.uuid:
                await self._stop_brick(vol, b)
        gf_event("VOLUME_STOP", name=name)
        await self._run_hooks("stop", "post", name)
        return {"stopped": name}

    async def op_volume_delete(self, name: str) -> dict:
        vol = self._vol(name)
        if vol["status"] == "started":
            raise MgmtError("stop the volume first")
        await self._cluster_txn("volume-delete", {"name": name})
        return {"ok": True}

    async def commit_volume_delete(self, name: str) -> dict:
        await self._run_hooks("delete", "pre", name)
        vol = self.state["volumes"].pop(name, None)
        if vol is not None:
            self.state.setdefault("tombstones", {})[name] = \
                int(vol.get("version", 1))
        self._save()
        gf_event("VOLUME_DELETE", name=name)
        await self._run_hooks("delete", "post", name)
        return {"deleted": name}

    async def op_volume_set(self, name: str, key: str, value: str) -> dict:
        if key not in volgen.OPTION_MAP:
            raise MgmtError(f"unknown option {key!r}")
        need = volgen.OPTION_MIN_OPVERSION.get(key, 1)
        if need > self.cluster_op_version():
            # stored versions are probe-time snapshots: re-handshake
            # before refusing, so upgraded-and-restarted peers lift the
            # cluster without a detach + re-probe
            await self._refresh_peers()
        have = self.cluster_op_version()
        if need > have:
            # mixed-version skew guard (glusterd op-version gating): a
            # member that doesn't understand the option would silently
            # build wrong volfiles
            raise MgmtError(
                f"option {key!r} requires cluster op-version {need}, "
                f"but a member is at {have} (upgrade all nodes first)")
        if key == "server.ssl" and volgen._bool(value):
            opts = self._vol(name).get("options", {})
            if not opts.get("ssl.cert"):
                raise MgmtError("server.ssl needs ssl.cert set first "
                                "(bricks would fail to start)")
        if key == "config.transport" and value not in ("tcp",):
            # the one transport this build speaks (rdma is a descope;
            # see docs/volume_options.md)
            raise MgmtError(f"unsupported transport {value!r} "
                            "(this build speaks tcp)")
        if key == "cluster.mesh-codec" and volgen._bool(value) and \
                self._vol(name).get("systematic") and \
                self.cluster_op_version() < 14:
            # pre-14 members have no systematic mesh tier (ops/batch
            # only armed it on non-systematic codecs): storing the key
            # would silently do nothing on them — refuse loudly.  At
            # cluster op-version >= 14 the mesh tier runs systematic
            # volumes through the parity-rows-only sharded encode, so
            # the old mutual exclusion is lifted (ROADMAP item 5).
            raise MgmtError(
                "cluster.mesh-codec on a systematic volume needs "
                "cluster op-version >= 14 (a member's mesh tier has "
                f"no systematic mode; cluster is at "
                f"{self.cluster_op_version()})")
        results = await self._cluster_txn(
            "volume-set", {"name": name, "key": key, "value": value})
        return {"ok": True,
                "applied": [r.get("result", {}).get("applied", "stored")
                            for r in results]}

    async def commit_volume_set(self, name: str, key: str, value: str) -> dict:
        vol = self._vol(name)
        await self._run_hooks("set", "pre", name, (f"-o{key}={value}",))
        vol.setdefault("options", {})[key] = value
        self._bump(vol)
        self._save()
        applied = "stored"
        if vol["status"] == "started":
            applied = await self._apply_to_bricks(vol)
            self._notify_subscribers(name)
        await self._run_hooks("set", "post", name, (f"-o{key}={value}",))
        return {name: {key: value}, "applied": applied}

    async def _apply_to_bricks(self, vol: dict) -> str:
        """Push the regenerated brick volfiles to running local bricks:
        same topology -> live __reconfigure__ over the brick RPC; shape
        change (feature toggle) -> respawn on the same port (the
        reference's volfile-compare + graph switch, graph.c:980-1089)."""
        outcome = "reconfigured"
        bdir = os.path.join(self.workdir, "bricks")
        for b in vol["bricks"]:
            if b["node"] != self.uuid or b["name"] not in self.bricks:
                continue
            text = volgen.build_brick_volfile(vol, b)
            ok = False
            port = self.ports.get(b["name"])
            if port:
                ok = await self._brick_reconfigure(
                    vol, port, text, subvol=b["name"] + "-server")
            if not ok:
                await self._stop_brick(vol, b)
                await self._spawn_brick(vol, b, port=b.get("port"))
                outcome = "respawned"
            volfile = os.path.join(bdir, b["name"] + ".vol")
            try:
                with open(volfile, "w") as f:
                    f.write(text)
            except OSError:
                pass
        return outcome

    @staticmethod
    async def _brick_call(vol: dict, port: int, name: str, args: list,
                          subvol: str = ""):
        """One authenticated mgmt call to a local brick: SETVOLUME
        handshake with the volume's generated credentials, then the
        call (bricks refuse unauthenticated RPC).  subvol routes to a
        specific brick graph on a multiplexed daemon."""
        ssl_ctx = None
        opts = vol.get("options", {})
        if volgen._bool(opts.get("server.ssl", "off")):
            from ..rpc import tls

            ssl_ctx = tls.client_context(opts.get("ssl.ca", ""),
                                         opts.get("ssl.cert", ""),
                                         opts.get("ssl.key", ""))
        # short timeout: during an ssl on/off transition the brick may
        # still speak the other protocol — fail fast to the respawn path
        # instead of wedging the cluster txn on a mutual stall
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection("127.0.0.1", port, ssl=ssl_ctx), 5)
        try:
            auth = vol.get("auth") or {}
            creds = {"username": auth.get("mgmt-username",
                                          auth.get("username", "")),
                     "password": auth.get("mgmt-password",
                                          auth.get("password", ""))}
            writer.write(wire.pack(1, wire.MT_CALL, [
                "__handshake__", [b"glusterd", subvol, creds], {}]))
            await writer.drain()
            rec = await asyncio.wait_for(wire.read_frame(reader), 5)
            _, mtype, payload = wire.unpack(rec)
            if mtype != wire.MT_REPLY or not payload.get("ok"):
                raise MgmtError("brick handshake refused")
            writer.write(wire.pack(2, wire.MT_CALL, [name, args, {}]))
            await writer.drain()
            rec = await asyncio.wait_for(wire.read_frame(reader), 5)
            _, mtype, payload = wire.unpack(rec)
            return payload if mtype == wire.MT_REPLY else None
        finally:
            writer.close()

    @classmethod
    async def _brick_reconfigure(cls, vol: dict, port: int,
                                 text: str, subvol: str = "") -> bool:
        try:
            payload = await cls._brick_call(vol, port,
                                            "__reconfigure__", [text],
                                            subvol=subvol)
            return bool(payload and payload.get("ok"))
        except Exception:
            return False

    def op_volume_info(self, name: str | None = None) -> dict:
        if name:
            return {name: self._vol(name)}
        return dict(self.state["volumes"])

    def op_volume_status(self, name: str) -> dict:
        vol = self._vol(name)
        bricks = []
        for b in vol["bricks"]:
            proc = self.bricks.get(b["name"])
            bricks.append({
                "name": b["name"], "path": b["path"], "node": b["node"],
                "port": self.ports.get(b["name"], 0),
                "online": proc is not None and proc.poll() is None,
            })
        shd = self.shd.get(name)
        out = {"volume": name, "status": vol["status"], "bricks": bricks,
               "shd": {"online": shd is not None and shd.poll() is None,
                       "pid": shd.pid if shd is not None else 0}}
        tasks = self._volume_tasks(vol)
        if tasks:
            out["tasks"] = tasks
        alerts = self._volume_alerts_block(vol)
        if alerts is not None:
            out["alerts"] = alerts
        return out

    def _volume_alerts_block(self, vol: dict) -> dict | None:
        """The status "alerts" section: rule-set shape from volume
        config (validation errors surface HERE, where the operator who
        just volume-set a bad rule is looking) plus the most recent
        ``volume alerts`` fan-out's active set.  Status stays a sync
        local op, so the live set is as-of the last fan-out — ``gftpu
        volume alerts`` is the fresh view."""
        rules_text = str(vol.get("options", {}).get(
            "diagnostics.slo-rules", "") or "")
        if not rules_text.strip():
            return None
        from ..core import slo

        rules, errors = slo.parse_rules(rules_text)
        block: dict[str, Any] = {"rules": len(rules)}
        if errors:
            block["rule_errors"] = errors
        cached = getattr(self, "_alerts_cache", {}).get(vol["name"])
        if cached:
            block["active"] = cached["active"]
            block["as_of"] = cached["ts"]
        return block

    @staticmethod
    def _volume_tasks(vol: dict) -> list[dict]:
        """Active background task state for the status "tasks" section
        (the reference appends rebalance/remove-brick task rows to
        every status answer, glusterd-op-sm.c _add_task_to_dict) — the
        data already lives in volinfo, it just wasn't surfaced."""
        tasks = []
        rb = vol.get("remove-brick")
        if rb:
            row = {"type": "remove-brick",
                   "status": rb.get("status", "unknown"),
                   "bricks": rb.get("bricks", [])}
            for k in ("progress", "moved", "scanned", "error"):
                if k in rb:
                    row[k] = rb[k]
            tasks.append(row)
        reb = vol.get("rebalance")
        if reb and reb.get("mode") != "drain":
            # a drain's task row is the remove-brick one above — two
            # rows for one background walk would double-report it
            row = {"type": "rebalance",
                   "status": reb.get("status", "unknown"),
                   "mode": reb.get("mode", "full"),
                   "phase": reb.get("phase", "idle")}
            for k in ("counters", "throttle", "error", "resumed_from"):
                if k in reb:
                    row[k] = reb[k]
            tasks.append(row)
        return tasks

    async def op_volume_heal(self, name: str, action: str = "info",
                             path: str = "") -> dict:
        """``gluster volume heal <v> [info|full|<path>]`` (glfs-heal.c /
        glusterd heal op analog): mounts a temporary client graph and
        drives the index-based heal surface."""
        vol = self._vol(name)
        if vol["status"] != "started":
            raise MgmtError(f"volume {name} not started")
        from . import shd as shd_mod

        client = await mount_volume(self.host, self.port, name)
        try:
            if action == "info":
                return await shd_mod.gather_heal_info(client)
            if action == "full":
                # full namespace sweep (ec_shd_full_sweep): also heals
                # bricks with no index record (replaced/wiped); file
                # heals run shd-max-threads wide so their re-encodes
                # coalesce (one mesh launch on a mesh-codec volume)
                return await shd_mod.full_crawl(
                    client, max_heals=self._shd_max_heals(vol))
            if action == "index":
                return await shd_mod.crawl_once(client)
            if action == "file":
                if not path:
                    raise MgmtError("heal file needs a path")
                layers = shd_mod._heal_layers(client.graph)
                if not layers:
                    raise MgmtError("volume has no heal-capable layer")
                out = {}
                for l in layers:
                    try:
                        out[l.name] = await l.heal_file(path)
                    except FopError as e:
                        out[l.name] = {"error": str(e)}
                return out
            raise MgmtError(f"unknown heal action {action!r}")
        finally:
            await client.unmount()

    # -- deep volume status (GF_CLI_STATUS_{DETAIL,CLIENTS,INODE,FD,
    # CALLPOOL,MEM}, glusterd-op-sm.c) -------------------------------------

    STATUS_KINDS = STATUS_KINDS  # the protocol/server op family

    async def op_volume_status_deep(self, name: str,
                                    what: str = "clients") -> dict:
        """``gftpu volume status <v> detail|clients|fds|inodes|
        callpool|mem`` — per-brick deep state gathered from every
        node's live brick processes and merged, with a ``partial``
        field naming unreachable nodes (never a fake-complete merge)."""
        if what not in self.STATUS_KINDS:
            raise MgmtError(f"unknown status kind {what!r} "
                            f"(one of {', '.join(self.STATUS_KINDS)})")
        vol = self._vol(name)
        if vol["status"] != "started":
            raise MgmtError(f"volume {name} not started")
        bricks, partial = await self._gather_bricks(
            "volume-status-local", nodes=self._vol_nodes(vol),
            name=name, what=what)
        return self._merge_partial(
            {"volume": name, "what": what, "bricks": bricks}, partial)

    async def op_volume_status_local(self, name: str,
                                     what: str = "clients") -> dict:
        """One node's share of deep status: its local bricks' __status__
        RPC (the brick half lives in protocol/server._status_of)."""
        vol = self._vol(name)
        out: dict[str, Any] = {}
        for b in vol["bricks"]:
            if b["node"] != self.uuid:
                continue
            port = self.ports.get(b["name"])
            proc = self.bricks.get(b["name"])
            if not port or proc is None or proc.poll() is not None:
                # a dead LOCAL brick is still reported — as offline,
                # not silently dropped from the merge
                out[b["name"]] = {"offline": True}
                continue
            try:
                payload = await self._brick_call(
                    vol, port, "__status__", [what],
                    subvol=b["name"] + "-server")
            except Exception as e:
                out[b["name"]] = {"offline": True,
                                  "error": repr(e)[:200]}
                continue
            # None = the brick ANSWERED with an error (a pre-__status__
            # build, or an EINVAL kind): it is live and serving, so
            # report the refusal — never mislabel it offline
            out[b["name"]] = payload if payload is not None \
                else {"error": "__status__ refused "
                               "(older brick build?)"}
        return {"bricks": out}

    async def op_volume_heal_count(self, name: str) -> dict:
        """``volume heal <v> statistics heal-count`` — pending-heal
        entry counts straight from each brick's index layer
        (XA_INDEX_COUNT virtual xattr), no temporary client graph
        mounted (the reference answers from shd counters the same
        way, glusterd-volume-ops.c heal statistics)."""
        vol = self._vol(name)
        if vol["status"] != "started":
            raise MgmtError(f"volume {name} not started")
        bricks, partial = await self._gather_bricks(
            "volume-heal-count-local", nodes=self._vol_nodes(vol),
            name=name)
        total = sum(v.get("count", 0) for v in bricks.values()
                    if isinstance(v, dict))
        return self._merge_partial(
            {"volume": name, "bricks": bricks, "total": total}, partial)

    async def op_volume_heal_count_local(self, name: str) -> dict:
        """One node's share of heal-count: each local brick's pending
        index entry count via one authenticated getxattr."""
        from ..core.layer import Loc
        from ..features.index import XA_INDEX_COUNT

        vol = self._vol(name)
        out: dict[str, dict] = {}
        for b in vol["bricks"]:
            if b["node"] != self.uuid:
                continue
            port = self.ports.get(b["name"])
            if not port:
                out[b["name"]] = {"offline": True, "count": 0}
                continue
            try:
                r = await self._brick_call(
                    vol, port, "getxattr", [Loc("/"), XA_INDEX_COUNT],
                    subvol=b["name"] + "-server")
                out[b["name"]] = {
                    "count": int((r or {}).get(XA_INDEX_COUNT, b"0"))}
            except Exception as e:
                out[b["name"]] = {"offline": True, "count": 0,
                                  "error": repr(e)[:200]}
        return {"bricks": out}

    async def op_volume_clear_locks(self, name: str, path: str,
                                    kind: str = "all") -> dict:
        """``gftpu volume clear-locks <v> <path> kind
        {blocked|granted|all}`` — operator-forced lock clearing riding
        the revocation machinery (the reference's clear-locks command,
        glusterd-volume-ops.c GF_CLI_CLEAR_LOCKS): fans out to every
        brick's features/locks and merges the per-brick cleared
        counts."""
        if kind not in ("blocked", "granted", "all"):
            raise MgmtError(f"clear-locks kind {kind!r} not one of "
                            "blocked/granted/all")
        vol = self._vol(name)
        if vol["status"] != "started":
            raise MgmtError(f"volume {name} not started")
        bricks, partial = await self._gather_bricks(
            "volume-clear-locks-local", nodes=self._vol_nodes(vol),
            name=name, path=path, kind=kind)
        total = sum(v.get("total", 0) for v in bricks.values()
                    if isinstance(v, dict))
        return self._merge_partial(
            {"volume": name, "path": path, "kind": kind,
             "bricks": bricks, "total": total}, partial)

    async def op_volume_clear_locks_local(self, name: str, path: str,
                                          kind: str = "all") -> dict:
        """One node's share of clear-locks: each local brick's
        features/locks.clear_locks via the authenticated RPC extra."""
        vol = self._vol(name)
        out: dict[str, dict] = {}
        for b in vol["bricks"]:
            if b["node"] != self.uuid:
                continue
            port = self.ports.get(b["name"])
            if not port:
                out[b["name"]] = {"offline": True, "total": 0}
                continue
            try:
                r = await self._brick_call(
                    vol, port, "clear_locks", [path, kind],
                    subvol=b["name"] + "-server")
                out[b["name"]] = r or {"total": 0}
            except FopError as e:
                if e.err == errno.ENOENT:  # path not on this brick (dht)
                    out[b["name"]] = {"total": 0, "absent": True}
                else:
                    out[b["name"]] = {"total": 0, "error": str(e)}
            except Exception as e:
                out[b["name"]] = {"offline": True, "total": 0,
                                  "error": repr(e)[:200]}
        return {"bricks": out}

    _TOP_METRICS = ("open", "read", "write", "read-bytes",
                    "write-bytes")

    def _vol_nodes(self, vol: dict) -> list[dict]:
        """The nodes actually hosting this volume's bricks (fan-out
        targets: a peer with no brick of the volume can neither answer
        nor meaningfully be 'missing' from the merge)."""
        want = {b["node"] for b in vol["bricks"]}
        return [n for n in self._all_nodes() if n["uuid"] in want]

    async def _gather_bricks(self, local_op: str, nodes=None,
                             **kw) -> tuple[dict, list[str]]:
        """Fan a per-node brick query out CONCURRENTLY (bounded per
        node) and merge the 'bricks' maps — shared by volume status /
        top / profile / metrics / heal-count; a hung peer costs one
        timeout, not a serial wait, and never hides the other nodes'
        answers.

        Returns ``(bricks, partial)``: a dead or hung peer no longer
        vanishes into an empty merge — it is NAMED in ``partial`` so
        every consumer can say which nodes are missing instead of
        pretending full coverage (the silent-{} bug of ISSUE 5)."""
        targets = list(nodes) if nodes is not None else self._all_nodes()

        async def one(node):
            try:
                return await asyncio.wait_for(
                    self._node_call(node, local_op, **kw), 30)
            except Exception as e:
                log.warning(22, "node %s missing from %s fan-out: %r",
                            node["uuid"][:8], local_op, e)
                return None

        parts = await asyncio.gather(*(one(n) for n in targets))
        out: dict[str, dict] = {}
        partial: list[str] = []
        for node, part in zip(targets, parts):
            if part is None:
                partial.append(f"{node['uuid'][:8]}"
                               f"@{node['host']}:{node['port']}")
                continue
            out.update(part.get("bricks", {}))
        return out, partial

    @staticmethod
    def _merge_partial(out: dict, partial: list[str]) -> dict:
        if partial:
            out["partial"] = partial
        return out

    async def op_volume_profile(self, name: str) -> dict:
        """``gluster volume profile <v> info`` — BRICK-side cumulative
        per-fop counters/latency from each brick's io-stats layer (the
        reference aggregates brick responses the same way;
        io-stats.c:129-197)."""
        vol = self._vol(name)
        if vol["status"] != "started":
            raise MgmtError(f"volume {name} not started")
        bricks, partial = await self._gather_bricks(
            "volume-profile-local", nodes=self._vol_nodes(vol),
            name=name)
        return self._merge_partial(
            {"volume": name, "bricks": bricks}, partial)

    async def op_volume_profile_local(self, name: str) -> dict:
        vol = self._vol(name)
        out: dict[str, dict] = {}
        for b in vol["bricks"]:
            if b["node"] != self.uuid:
                continue
            port = self.ports.get(b["name"])
            if not port:
                continue
            dump = await self._brick_statedump(
                vol, port, subvol=b["name"] + "-server")
            layers = (dump or {}).get("layers", {})
            prof = next((l.get("private") for l in layers.values()
                         if l.get("type") == "debug/io-stats"
                         and "fops" in (l.get("private") or {})), None)
            if prof is not None:
                out[b["name"]] = prof
        return {"bricks": out}

    async def op_volume_metrics(self, name: str) -> dict:
        """``gftpu volume metrics <v>`` — each brick process's unified
        metrics-registry scrape (core/metrics.py): decode-program cache
        hit/miss, wire blob lanes, io-threads queue depth, write-behind
        occupancy, codec probe state... resolved per brick by graph
        walk like top_stats."""
        vol = self._vol(name)
        if vol["status"] != "started":
            raise MgmtError(f"volume {name} not started")
        bricks, partial = await self._gather_bricks(
            "volume-metrics-local", nodes=self._vol_nodes(vol),
            name=name)
        return self._merge_partial(
            {"volume": name, "bricks": bricks}, partial)

    async def op_volume_metrics_local(self, name: str) -> dict:
        """One node's share of volume-metrics: its local bricks, plus
        this node's gateway daemon's families when it exposes them
        (``gateway.metrics-port``) — under a worker pool that endpoint
        is the supervisor's AGGREGATED per-worker merge, so `volume
        metrics` sees the whole pool as one front door."""
        vol = self._vol(name)
        out: dict[str, dict] = {}
        for b in vol["bricks"]:
            if b["node"] != self.uuid:
                continue
            port = self.ports.get(b["name"])
            if not port:
                continue
            try:
                snap = await self._brick_call(
                    vol, port, "metrics_dump", [],
                    subvol=b["name"] + "-server")
            except Exception:
                snap = None  # dead brick: report empty, not an error
            out[b["name"]] = snap or {}
        gw_snap = await self._gateway_metrics(vol)
        if gw_snap is not None:
            out[f"gateway:{self.host}"] = gw_snap
        return {"bricks": out}

    async def _gateway_metrics(self, vol: dict) -> dict | None:
        """This node's gateway families over its /metrics.json (both
        the single-process daemon and the worker-pool supervisor serve
        it); None when no gateway/metrics-port is armed."""
        name = vol["name"]
        proc = self.gateway.get(name)
        mport = int(vol.get("options", {}).get("gateway.metrics-port",
                                               0) or 0)
        if proc is None or proc.poll() is not None or not mport:
            return None
        host = str(vol.get("options", {}).get("gateway.listen-host",
                                              "127.0.0.1"))
        try:
            reader, writer = await asyncio.wait_for(
                asyncio.open_connection(host, mport), 3)
            try:
                writer.write(b"GET /metrics.json HTTP/1.0\r\n\r\n")
                await writer.drain()
                raw = await asyncio.wait_for(reader.read(-1), 5)
            finally:
                writer.close()
            body = raw.split(b"\r\n\r\n", 1)[1]
            return json.loads(body.decode())
        except Exception:  # noqa: BLE001 - metrics are best-effort
            return None

    # -- incident plane (flight-recorder capture fan-out) ------------------

    def _incident_dir(self, vol: dict) -> str:
        """Effective incident directory for this volume's cluster
        bundles: ``diagnostics.incident-dir`` when set (the same dir
        every process auto-captures into, so ``incident list`` shows
        both kinds side by side), else a workdir fallback so the
        operator command works on an unconfigured volume."""
        d = str(vol.get("options", {}).get("diagnostics.incident-dir",
                                           "") or "")
        return d or os.path.join(self.workdir, "incidents", vol["name"])

    def _incident_max_bytes(self, vol: dict) -> int:
        from ..core.options import parse_size

        try:
            return parse_size(vol.get("options", {}).get(
                "diagnostics.incident-max-bytes", "64MB"))
        except Exception:
            return 64 * 1024 * 1024

    async def op_volume_incident_capture(self, name: str) -> dict:
        """``gftpu volume incident capture <v>`` — fan a flight-recorder
        snapshot request across every node's bricks, gateway and
        service daemons, and merge the answers into ONE timestamped
        cluster bundle in the effective incident dir.  A dead peer is
        NAMED in ``partial`` (the volume-status contract), never
        silently missing from the merge."""
        vol = self._vol(name)
        if vol["status"] != "started":
            raise MgmtError(f"volume {name} not started")
        procs, partial = await self._gather_bricks(
            "volume-incident-local", nodes=self._vol_nodes(vol),
            name=name)
        bundle = self._merge_partial(
            {"volume": name, "ts": round(time.time(), 6),
             "reason": "capture", "origin": self.uuid,
             "processes": procs}, partial)
        idir = self._incident_dir(vol)
        os.makedirs(idir, exist_ok=True)
        path = os.path.join(
            idir, f"incident-{time.time_ns()}-cluster-{name}.json")
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(bundle, f, default=repr, separators=(",", ":"),
                      sort_keys=True)
        os.replace(tmp, path)
        from ..core import flight

        flight.prune_dir(idir, self._incident_max_bytes(vol))
        return self._merge_partial(
            {"volume": name, "bundle": path,
             "processes": sorted(procs)}, partial)

    async def op_volume_incident_local(self, name: str) -> dict:
        """One node's share of incident capture: each local brick's
        ``__incident__`` RPC, the gateway's ``/incident.json`` (the
        supervisor aggregates its workers there), and the SIGUSR2
        capture door of shd / rebalanced.  Non-brick processes ride the
        shared 'bricks' merge under reserved ``role:host`` keys, the
        volume-metrics idiom."""
        vol = self._vol(name)
        out: dict[str, Any] = {}
        for b in vol["bricks"]:
            if b["node"] != self.uuid:
                continue
            port = self.ports.get(b["name"])
            proc = self.bricks.get(b["name"])
            if not port or proc is None or proc.poll() is not None:
                out[b["name"]] = {"offline": True}
                continue
            try:
                payload = await self._brick_call(
                    vol, port, "__incident__", [],
                    subvol=b["name"] + "-server")
            except Exception as e:
                out[b["name"]] = {"offline": True,
                                  "error": repr(e)[:200]}
                continue
            out[b["name"]] = payload if payload is not None \
                else {"error": "__incident__ refused "
                               "(older brick build?)"}
        gw = await self._gateway_incident(vol)
        if gw is not None:
            out[f"gateway:{self.host}"] = gw
        name_ = vol["name"]
        shd_snap = await self._signal_incident(
            self.shd.get(name_),
            os.path.join(self.workdir, f"shd-{name_}.json.incident"))
        if shd_snap is not None:
            out[f"shd:{self.host}"] = shd_snap
        reb_snap = await self._signal_incident(
            self.rebalanced.get(name_),
            os.path.join(self.workdir,
                         f"rebalanced-{name_}.json.incident"))
        if reb_snap is not None:
            out[f"rebalance:{self.host}"] = reb_snap
        return {"bricks": out}

    async def _gateway_incident(self, vol: dict) -> dict | None:
        """This node's gateway flight bundle over /incident.json (the
        worker-pool supervisor answers with supervisor + per-worker
        snapshots merged); None when no gateway runs here."""
        name = vol["name"]
        proc = self.gateway.get(name)
        if proc is None or proc.poll() is not None:
            return {"offline": True} if proc is not None else None
        mport = int(vol.get("options", {}).get("gateway.metrics-port",
                                               0) or 0)
        if not mport:
            return {"error": "gateway.metrics-port not set "
                             "(no incident door)"}
        host = str(vol.get("options", {}).get("gateway.listen-host",
                                              "127.0.0.1"))
        try:
            reader, writer = await asyncio.wait_for(
                asyncio.open_connection(host, mport), 3)
            try:
                writer.write(b"GET /incident.json HTTP/1.0\r\n\r\n")
                await writer.drain()
                raw = await asyncio.wait_for(reader.read(-1), 5)
            finally:
                writer.close()
            body = raw.split(b"\r\n\r\n", 1)[1]
            return json.loads(body.decode())
        except Exception as e:  # noqa: BLE001 - one process of many
            return {"offline": True, "error": repr(e)[:200]}

    @staticmethod
    async def _signal_incident(proc, path: str) -> dict | None:
        """SIGUSR2 capture door for service daemons with no inbound
        RPC surface (shd, rebalanced): signal, poll for the bundle
        file, parse it.  None = no such daemon on this node."""
        if proc is None:
            return None
        if proc.poll() is not None:
            return {"offline": True}
        try:
            os.unlink(path)
        except OSError:
            pass
        try:
            proc.send_signal(signal.SIGUSR2)
        except OSError as e:
            return {"offline": True, "error": repr(e)[:200]}
        for _ in range(40):
            await asyncio.sleep(0.05)
            try:
                with open(path) as f:
                    return json.load(f)
            except (OSError, ValueError):
                continue  # not written yet / mid-rename
        return {"error": "signal capture timed out"}

    def op_volume_incident_list(self, name: str) -> dict:
        """``gftpu volume incident list <v>`` — the bundles (auto-
        captured AND operator-captured) in the effective incident
        dir."""
        vol = self._vol(name)
        idir = self._incident_dir(vol)
        bundles = []
        try:
            names = os.listdir(idir)
        except OSError:
            names = []
        for fn in sorted(names):
            if not (fn.startswith("incident-")
                    and fn.endswith(".json")):
                continue
            try:
                st = os.stat(os.path.join(idir, fn))
            except OSError:
                continue
            bundles.append({"name": fn, "bytes": st.st_size,
                            "mtime": round(st.st_mtime, 3)})
        return {"volume": name, "dir": idir, "bundles": bundles}

    def op_volume_incident_show(self, name: str,
                                bundle: str = "") -> dict:
        """``gftpu volume incident show <v> [bundle]`` — round-trip one
        bundle's JSON (default: the newest)."""
        vol = self._vol(name)
        idir = self._incident_dir(vol)
        if not bundle:
            rows = self.op_volume_incident_list(name)["bundles"]
            if not rows:
                raise MgmtError(
                    f"no incident bundles for {name} in {idir}")
            bundle = max(rows, key=lambda r: r["mtime"])["name"]
        base = os.path.basename(bundle)  # stay inside the incident dir
        path = os.path.join(idir, base)
        try:
            with open(path) as f:
                return json.load(f)
        except OSError as e:
            raise MgmtError(f"cannot read bundle {base}: "
                            f"{e}") from e
        except ValueError as e:
            raise MgmtError(f"bundle {base} is not valid JSON: "
                            f"{e}") from e

    # -- alerts plane (SLO engine fan-out, ISSUE 20) -----------------------

    _ALERT_ACTIONS = ("list", "history", "rules")

    async def op_volume_alerts(self, name: str,
                               action: str = "list") -> dict:
        """``gftpu volume alerts <v> [list|history|rules]`` — the
        cluster view of the SLO plane: every process evaluates rules
        against its OWN history ring (core/slo.py); this op gathers
        and merges their engine state per node, tagging each row with
        the process it came from.  ``rules`` answers from volume
        config alone (validation errors included) — no fan-out."""
        if action not in self._ALERT_ACTIONS:
            raise MgmtError(f"unknown alerts action {action!r} "
                            f"(one of {', '.join(self._ALERT_ACTIONS)})")
        vol = self._vol(name)
        rules_text = str(vol.get("options", {}).get(
            "diagnostics.slo-rules", "") or "")
        if action == "rules":
            from ..core import slo

            rules, errors = slo.parse_rules(rules_text)
            return {"volume": name, "rules": rules,
                    "rule_errors": errors}
        if vol["status"] != "started":
            raise MgmtError(f"volume {name} not started")
        procs, partial = await self._gather_bricks(
            "volume-alerts-local", nodes=self._vol_nodes(vol),
            name=name)
        active: list[dict] = []
        transitions: list[dict] = []
        rule_errors: list[str] = []
        for proc_name, st in sorted(procs.items()):
            if not isinstance(st, dict):
                continue
            for a in st.get("active", []):
                active.append({"process": proc_name, **a})
            for t in st.get("history", []):
                transitions.append({"process": proc_name, **t})
            for e in st.get("rule_errors", []):
                if e not in rule_errors:
                    rule_errors.append(e)
        active.sort(key=lambda a: a.get("since", 0.0))
        transitions.sort(key=lambda t: t.get("ts", 0.0))
        out = {"volume": name, "active": active,
               "processes": sorted(procs)}
        if rule_errors:
            out["rule_errors"] = rule_errors
        if action == "history":
            out["history"] = transitions
        # volume status surfaces this summary without re-fanning-out
        self._alerts_cache = getattr(self, "_alerts_cache", {})
        self._alerts_cache[name] = {"ts": round(time.time(), 3),
                                    "active": active}
        return self._merge_partial(out, partial)

    async def op_volume_alerts_local(self, name: str) -> dict:
        """One node's share of volume-alerts: each local brick's
        ``__alerts__`` door, the gateway's ``/alerts.json`` (the
        supervisor unions its workers there), and shd's tick-mirrored
        ``<statefile>.alerts`` file — the incident-local trio, minus
        daemons that mount no io-stats graph."""
        vol = self._vol(name)
        out: dict[str, Any] = {}
        for b in vol["bricks"]:
            if b["node"] != self.uuid:
                continue
            port = self.ports.get(b["name"])
            proc = self.bricks.get(b["name"])
            if not port or proc is None or proc.poll() is not None:
                out[b["name"]] = {"offline": True}
                continue
            try:
                payload = await self._brick_call(
                    vol, port, "__alerts__", [],
                    subvol=b["name"] + "-server")
            except Exception as e:
                out[b["name"]] = {"offline": True,
                                  "error": repr(e)[:200]}
                continue
            out[b["name"]] = payload if payload is not None \
                else {"error": "__alerts__ refused "
                               "(older brick build?)"}
        gw = await self._gateway_json(vol, "/alerts.json")
        if gw is not None:
            out[f"gateway:{self.host}"] = gw
        shd_st = self._read_alerts_file(
            self.shd.get(vol["name"]),
            os.path.join(self.workdir,
                         f"shd-{vol['name']}.json.alerts"))
        if shd_st is not None:
            out[f"shd:{self.host}"] = shd_st
        return {"bricks": out}

    async def _gateway_json(self, vol: dict, path: str) -> dict | None:
        """GET one JSON document off this node's gateway metrics
        endpoint (single-process daemon and worker-pool supervisor
        both serve it); None when no gateway runs here."""
        proc = self.gateway.get(vol["name"])
        if proc is None or proc.poll() is not None:
            return {"offline": True} if proc is not None else None
        mport = int(vol.get("options", {}).get("gateway.metrics-port",
                                               0) or 0)
        if not mport:
            return None
        host = str(vol.get("options", {}).get("gateway.listen-host",
                                              "127.0.0.1"))
        try:
            reader, writer = await asyncio.wait_for(
                asyncio.open_connection(host, mport), 3)
            try:
                writer.write(f"GET {path} HTTP/1.0\r\n\r\n".encode())
                await writer.drain()
                raw = await asyncio.wait_for(reader.read(-1), 5)
            finally:
                writer.close()
            return json.loads(raw.split(b"\r\n\r\n", 1)[1].decode())
        except Exception as e:  # noqa: BLE001 - one process of many
            return {"offline": True, "error": repr(e)[:200]}

    @staticmethod
    def _read_alerts_file(proc, path: str) -> dict | None:
        """shd's alerts door: the daemon mirrors its engine status
        beside the statefile on every sampler tick (mgmt/shd.py), so
        reading it is passive — no signal round-trip.  None = no such
        daemon on this node or no rules configured (the mirror is only
        written once rules exist)."""
        if proc is None:
            return None
        if proc.poll() is not None:
            return {"offline": True}
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    async def op_volume_top(self, name: str, metric: str = "open",
                            count: int = 10) -> dict:
        """``gluster volume top <v> open|read|write|read-bytes|
        write-bytes`` — per-brick ranked per-path counters from each
        brick's io-stats layer (io-stats.c ios_stat_list backend),
        aggregated across every node's bricks."""
        if metric not in self._TOP_METRICS:
            # validate HERE: a typo'd metric must not come back as
            # empty rows indistinguishable from "no activity"
            raise MgmtError(f"unknown top metric {metric!r} "
                            f"(one of {', '.join(self._TOP_METRICS)})")
        vol = self._vol(name)
        if vol["status"] != "started":
            raise MgmtError(f"volume {name} not started")
        bricks, partial = await self._gather_bricks(
            "volume-top-local", nodes=self._vol_nodes(vol), name=name,
            metric=metric, count=int(count))
        return self._merge_partial(
            {"volume": name, "metric": metric, "bricks": bricks},
            partial)

    async def op_volume_top_local(self, name: str, metric: str = "open",
                                  count: int = 10) -> dict:
        """One node's share of volume-top: its local bricks."""
        vol = self._vol(name)
        out: dict[str, list] = {}
        for b in vol["bricks"]:
            if b["node"] != self.uuid:
                continue
            port = self.ports.get(b["name"])
            if not port:
                continue
            try:
                rows = await self._brick_call(
                    vol, port, "top_stats", [metric, int(count)],
                    subvol=b["name"] + "-server")
            except Exception:
                rows = None  # dead brick: report empty, not an error
            out[b["name"]] = rows or []
        return {"bricks": out}

    async def op_volume_brick(self, name: str, brick: str,
                              action: str) -> dict:
        """Stop / start one local brick daemon (the tests' kill_brick +
        ``volume start force`` analog); restart reuses the recorded port
        so connected clients can reconnect."""
        vol = self._vol(name)
        b = next((x for x in vol["bricks"] if x["name"] == brick), None)
        if b is None:
            raise MgmtError(f"no brick {brick!r} in {name}")
        if action == "stop":
            await self._stop_brick(vol, b)
            return {"stopped": brick}
        if action == "start":
            proc = self.bricks.get(brick)
            if proc is not None and proc.poll() is None:
                return {"already-running": brick}
            await self._spawn_brick(vol, b, port=b.get("port"))
            return {"started": brick, "port": self.ports.get(brick, 0)}
        raise MgmtError(f"unknown brick action {action!r}")

    # -- eventsapi (events/src/peer_eventsapi.py analog) -------------------
    # Webhook config is cluster-wide: the op fans out over the txn and
    # every node forwards to ITS eventsd's ctl port (from the
    # GFTPU_EVENTSD_CTL env, set by whoever runs gftpu-eventsd there).

    async def op_eventsapi(self, action: str, url: str = "") -> dict:
        if action in ("webhook-add", "webhook-del"):
            if not url:
                raise MgmtError(f"{action} needs a url")
            results = await self._cluster_txn(
                "eventsapi", {"action": action, "url": url})
            return {"ok": True,
                    "nodes": [r.get("result", {}) for r in results]}
        if action == "status":
            # cluster-wide view (peer_eventsapi status): the contacted
            # node having no eventsd must not hide everyone else's
            out = {}
            for node in self._all_nodes():
                try:
                    out[node["uuid"][:8]] = await asyncio.wait_for(
                        self._node_call(node, "eventsapi-local",
                                        ctl_method="status"), 10)
                except Exception as e:
                    out[node["uuid"][:8]] = {"error": repr(e)[:120]}
            return {"nodes": out}
        raise MgmtError(f"unknown eventsapi action {action!r}")

    async def op_eventsapi_local(self, ctl_method: str) -> dict:
        return await self._eventsd_ctl(ctl_method, {})

    async def commit_eventsapi(self, action: str, url: str) -> dict:
        return await self._eventsd_ctl(action, {"url": url})

    async def _eventsd_ctl(self, method: str, kwargs: dict) -> dict:
        ep = os.environ.get("GFTPU_EVENTSD_CTL", "")
        if not ep:
            return {"skipped": "no eventsd on this node "
                               "(GFTPU_EVENTSD_CTL unset)"}
        host, _, port = ep.partition(":")
        if not host or not port.isdigit():
            return {"skipped": f"malformed GFTPU_EVENTSD_CTL {ep!r} "
                               "(want host:port)"}
        try:
            reader, writer = await asyncio.wait_for(
                asyncio.open_connection(host, int(port)), 5)
        except (ConnectionError, OSError, asyncio.TimeoutError) as e:
            # a crashed eventsd must degrade like an absent one, not
            # abort the cluster txn half-committed
            return {"skipped": f"eventsd unreachable: {e!r}"[:200]}
        try:
            writer.write(wire.pack(1, wire.MT_CALL, [method, kwargs]))
            await writer.drain()
            rec = await asyncio.wait_for(wire.read_frame(reader), 5)
            _, mtype, payload = wire.unpack(rec)
            if mtype != wire.MT_REPLY:
                raise MgmtError(f"eventsd refused {method}: {payload}")
            return payload
        except (ConnectionError, OSError, asyncio.TimeoutError,
                asyncio.IncompleteReadError) as e:
            return {"skipped": f"eventsd unreachable: {e!r}"[:200]}
        finally:
            writer.close()

    # -- brick ops: add / remove / replace (glusterd-brick-ops.c,
    # glusterd-replace-brick.c) --------------------------------------------

    def _resolve_node(self, nodeid: str) -> dict:
        """'node' in a brick spec -> {uuid, host}: accepts a node uuid
        (or prefix) or a peer's host[:port] — anything else would wire
        a brick NO glusterd ever spawns into the volume."""
        me = self._peer_info()
        cands = [me] + [p for p in self.state["peers"].values()
                        if p["uuid"] != self.uuid]
        for p in cands:
            if p["uuid"] == nodeid or (
                    len(nodeid) >= 8 and p["uuid"].startswith(nodeid)):
                return p
        for p in cands:
            if nodeid in (p["host"], f"{p['host']}:{p['port']}",
                          "localhost"):
                return p
        raise MgmtError(f"brick node {nodeid!r} matches no cluster "
                        "member (peer probe it first)")

    def _parse_new_bricks(self, vol: dict, bricks: list) -> list[dict]:
        start = 1 + max((b["index"] for b in vol["bricks"]), default=-1)
        parsed = []
        for i, b in enumerate(bricks):
            if isinstance(b, str):
                nodeid, _, path = b.partition(":")
                b = {"node": nodeid, "path": path}
            node = self._resolve_node(b["node"]) if b.get("node") \
                else self._peer_info()
            idx = start + i
            parsed.append({
                "index": idx, "node": node["uuid"],
                "host": b.get("host", node["host"]), "path": b["path"],
                "name": f"{vol['name']}-brick-{idx}",
            })
        return parsed

    def _group_size(self, vol: dict) -> int:
        return vol.get("group-size") or len(vol["bricks"])

    async def op_volume_add_brick(self, name: str, bricks: list) -> dict:
        """``volume add-brick`` — grow the volume.  disperse/replicate
        volumes grow by whole groups (the volume becomes / stays
        distributed-X); plain distribute grows brick by brick."""
        vol = self._vol(name)
        if not bricks:
            raise MgmtError("add-brick needs bricks")
        group_size = 0
        if vol["type"] in ("disperse", "replicate"):
            group_size = self._group_size(vol)
            if len(bricks) % group_size:
                raise MgmtError(
                    f"add-brick on a {vol['type']} volume needs a "
                    f"multiple of {group_size} bricks (whole groups)")
        if (vol.get("rebalance") or {}).get("status") == "started":
            # a live rebalance walks the CURRENT layout; growing it
            # mid-run would leave the new brick unstamped by the
            # already-passed fix-layout directories (the reference
            # refuses the same way, glusterd-brick-ops.c)
            raise MgmtError("a rebalance is in progress; stop it "
                            "before add-brick")
        parsed = self._parse_new_bricks(vol, bricks)
        results = await self._cluster_txn(
            "add-brick", {"name": name, "bricks": parsed,
                          "group_size": group_size})
        if vol["status"] == "started":
            ports: dict[str, int] = {}
            for r in results:
                ports.update(r.get("result", {}).get("ports", {}))
            for node in self._all_nodes():
                try:
                    await self._node_call(node, "portmap-update",
                                          name=name, ports=ports)
                except Exception:
                    pass
        return {"ok": True, "added": [b["name"] for b in parsed]}

    def stage_add_brick(self, name: str, bricks: list,
                        group_size: int = 0) -> None:
        vol = self._vol(name)
        have = {b["name"] for b in vol["bricks"]}
        if any(b["name"] in have for b in bricks):
            raise MgmtError("brick name collision")

    async def commit_add_brick(self, name: str, bricks: list,
                               group_size: int = 0) -> dict:
        vol = self._vol(name)
        if group_size and "group-size" not in vol:
            # first growth of a single-group volume fixes the group
            # size so volgen starts emitting the dht aggregate
            vol["group-size"] = group_size
        vol["bricks"].extend(bricks)
        self._bump(vol)
        self._save()
        if vol["status"] == "started":
            await self._start_bricks(
                vol, [b for b in bricks if b["node"] == self.uuid])
            self._notify_subscribers(name)  # topology change: graph swap
        gf_event("VOLUME_ADD_BRICK", name=name,
                 bricks=[b["name"] for b in bricks])
        return {"added": [b["name"] for b in bricks],
                "ports": {b["name"]: self.ports[b["name"]]
                          for b in bricks
                          if b["name"] in self.ports}}

    async def op_volume_remove_brick(self, name: str, bricks: list,
                                     action: str = "start") -> dict:
        """``volume remove-brick start|status|commit`` — shrink the
        volume: start excludes the leaving bricks from the dht layout
        and drains their data (decommission rebalance,
        dht-rebalance.c); commit drops them once drained."""
        vol = self._vol(name)
        rb = vol.get("remove-brick") or {}
        if action == "status":
            return dict(rb) or {"status": "not-started"}
        if action == "start":
            if vol["status"] != "started":
                # the drain migrates THROUGH a mounted client; on a
                # stopped volume it would no-op "completed" and a
                # later commit would silently drop un-drained data
                raise MgmtError("volume must be started to drain "
                                "bricks (remove-brick start)")
            if rb.get("status") == "started":
                raise MgmtError("a remove-brick is already in "
                                "progress; commit or wait first")
            if (vol.get("rebalance") or {}).get("status") == "started":
                # the drain rides the SAME daemon slot: starting it
                # under a live full rebalance would clobber that run's
                # record while the old daemon keeps walking (and its
                # next checkpoint push would flip the mode back,
                # stranding the remove-brick record 'started' forever)
                raise MgmtError("a rebalance is in progress; stop it "
                                "before remove-brick start")
            if self.cluster_op_version() < 13:
                # the drain rides the rebalance daemon machinery
                # (rebalance-start txn + rebalance-update pushes): a
                # v12 peer has neither op, and failing mid-txn-pair
                # would strand remove-brick 'started' with no daemon
                # draining it.  Re-handshake before refusing (the
                # volume-set ladder's pattern).
                await self._refresh_peers()
            if self.cluster_op_version() < 13:
                raise MgmtError(
                    "remove-brick start needs cluster op-version "
                    f">= 13 (cluster is at {self.cluster_op_version()})")
            leaving = set(bricks or ())
            have = {b["name"] for b in vol["bricks"]}
            if not leaving or not leaving <= have:
                raise MgmtError(f"unknown bricks {sorted(leaving - have)}")
            if len(leaving) >= len(have):
                raise MgmtError("cannot remove every brick")
            if vol["type"] in ("disperse", "replicate"):
                g = self._group_size(vol)
                if len(leaving) % g:
                    raise MgmtError(
                        f"remove-brick on a {vol['type']} volume "
                        f"drains whole groups of {g}")
                ordered = [b["name"] for b in vol["bricks"]]
                for j in range(0, len(ordered), g):
                    grp = set(ordered[j:j + g])
                    if grp & leaving and not grp <= leaving:
                        raise MgmtError("partial group in remove-brick")
            await self._cluster_txn("remove-brick-start", {
                "name": name, "bricks": sorted(leaving)})
            # the drain IS a rebalance: the managed daemon walks the
            # namespace in drain mode (decommissioned children are
            # already excluded from placement, dht.py:88-90), so
            # shrink gets status/stop/checkpoints for free
            await self._cluster_txn("rebalance-start", {
                "name": name, "mode": "drain", "node": self.uuid,
                "ts": time.time()})
            return {"ok": True, "status": "started"}
        if action == "stop":
            if rb.get("status") != "started":
                raise MgmtError("no remove-brick in progress")
            await self._cluster_txn("remove-brick-stop", {"name": name})
            gf_event("REBALANCE_STOPPED", name=name, mode="drain")
            return {"ok": True, "status": "stopped"}
        if action in ("commit", "force"):
            if not rb:
                raise MgmtError("no remove-brick in progress")
            if rb.get("status") != "completed" and action != "force":
                raise MgmtError(
                    f"migration {rb.get('status')!r}; wait or use force")
            await self._cluster_txn("remove-brick-commit",
                                    {"name": name})
            return {"ok": True, "removed": rb.get("bricks", [])}
        raise MgmtError(f"unknown remove-brick action {action!r}")

    def commit_remove_brick_start(self, name: str,
                                  bricks: list) -> dict:
        vol = self._vol(name)
        vol["remove-brick"] = {"status": "started", "bricks": bricks}
        self._bump(vol)
        self._save()
        if vol["status"] == "started":
            self._notify_subscribers(name)  # layout excludes leavers
        return {"draining": bricks}

    def commit_remove_brick_stop(self, name: str) -> dict:
        """Abort a shrink: kill the drain daemon and drop the
        decommission so the leavers re-join the layout (the
        reference's remove-brick stop restores the node map)."""
        vol = self._vol(name)
        self._kill_rebalanced(name)
        vol.pop("remove-brick", None)
        reb = vol.get("rebalance")
        if reb is not None and reb.get("mode") == "drain" and \
                reb.get("status") == "started":
            reb["status"] = "stopped"
        self._bump(vol)
        self._save()
        if vol["status"] == "started":
            self._notify_subscribers(name)  # leavers re-enter layout
        return {"stopped": name}

    async def commit_remove_brick_commit(self, name: str) -> dict:
        vol = self._vol(name)
        rb = vol.pop("remove-brick", None) or {}
        leaving = set(rb.get("bricks") or ())
        keep, gone = [], []
        for b in vol["bricks"]:
            (gone if b["name"] in leaving else keep).append(b)
        vol["bricks"] = keep
        self._bump(vol)
        self._save()
        for b in gone:
            if b["node"] == self.uuid:
                await self._stop_brick(vol, b)
        if vol["status"] == "started":
            self._notify_subscribers(name)
        gf_event("VOLUME_REMOVE_BRICK", name=name,
                 bricks=sorted(leaving))
        return {"removed": sorted(leaving)}

    async def op_volume_replace_brick(self, name: str, brick: str,
                                      new_path: str) -> dict:
        """``volume replace-brick ... commit force`` — swap a brick for
        an empty one; the self-heal daemon rebuilds its content from
        the surviving replicas/fragments (glusterd-replace-brick.c +
        full heal)."""
        vol = self._vol(name)
        if vol["type"] not in ("replicate", "disperse"):
            raise MgmtError("replace-brick needs a replicate or "
                            "disperse volume (distribute would lose "
                            "that brick's data)")
        if not any(b["name"] == brick for b in vol["bricks"]):
            raise MgmtError(f"no brick {brick!r} in {name}")
        results = await self._cluster_txn("replace-brick", {
            "name": name, "brick": brick, "new_path": new_path})
        if vol["status"] == "started":
            # the replacement bound a fresh port on its node: broadcast
            # it (volume-start's pmap sync) so peers' volfiles carry it
            ports: dict[str, int] = {}
            for r in results:
                ports.update(r.get("result", {}).get("ports", {}))
            for node in self._all_nodes():
                try:
                    await self._node_call(node, "portmap-update",
                                          name=name, ports=ports)
                except Exception:
                    pass
            # rebuild the empty brick NOW (the reference triggers a
            # full self-heal on replace); shd's crawl also covers it
            self._spawn_task(self._heal_full(name))
        return {"ok": True, "replaced": brick, "path": new_path}

    async def commit_replace_brick(self, name: str, brick: str,
                                   new_path: str) -> dict:
        vol = self._vol(name)
        b = next(x for x in vol["bricks"] if x["name"] == brick)
        if b["node"] == self.uuid and b["name"] in self.bricks:
            await self._stop_brick(vol, b)
        b["path"] = new_path
        b.pop("port", None)
        self._bump(vol)
        self._save()
        if vol["status"] == "started" and b["node"] == self.uuid:
            await self._spawn_brick(vol, b)
            self._notify_subscribers(name)
        gf_event("VOLUME_REPLACE_BRICK", name=name, brick=brick)
        # only the HOSTING node reports a port: peers still hold the
        # old port in self.ports and would overwrite the fresh one in
        # the originator's last-write-wins merge
        ports = {}
        if b["node"] == self.uuid and brick in self.ports:
            ports[brick] = self.ports[brick]
        return {"replaced": brick, "ports": ports}

    async def _heal_full(self, name: str) -> None:
        try:
            from . import shd as shd_mod

            client = await mount_volume(self.host, self.port, name)
            try:
                await shd_mod.full_crawl(
                    client, max_heals=self._shd_max_heals(self._vol(name)))
            finally:
                await client.unmount()
        except Exception as e:
            log.warning(22, "post-replace heal of %s: %r", name, e)

    # -- rebalance daemon lifecycle (glusterd-rebalance.c analog) ----------
    # ``volume rebalance NAME start[ fix-layout]|status|stop`` — a
    # per-volume daemon owned by the starting node, spawned like the
    # gateway/shd service daemons, reporting resumable checkpoints back
    # into the volinfo over the rebalance-update RPC: SIGKILL + respawn
    # CONTINUES the walk from the last completed directory, never
    # restarts it.

    async def op_volume_rebalance(self, name: str,
                                  action: str = "status",
                                  flavor: str = "") -> dict:
        vol = self._vol(name)
        if action == "status":
            return await self._rebalance_status(vol)
        if action not in ("start", "stop"):
            raise MgmtError(f"bad rebalance action {action!r} "
                            "(want start|status|stop)")
        if self.cluster_op_version() < 13:
            # stored versions are probe-time snapshots: re-handshake
            # before refusing (the volume-set ladder's pattern)
            await self._refresh_peers()
        if self.cluster_op_version() < 13:
            raise MgmtError(
                "volume rebalance needs cluster op-version >= 13 "
                f"(cluster is at {self.cluster_op_version()})")
        rb = vol.get("rebalance") or {}
        if action == "stop":
            if rb.get("status") != "started":
                raise MgmtError("no rebalance in progress")
            if rb.get("mode") == "drain":
                # stopping the drain daemon without dropping the
                # decommission would strand remove-brick 'started'
                # with nothing draining it — the remove-brick stop op
                # owns that cleanup
                raise MgmtError("this rebalance is a remove-brick "
                                "drain; use `volume remove-brick ... "
                                "stop`")
            await self._cluster_txn("rebalance-stop", {"name": name})
            gf_event("REBALANCE_STOPPED", name=name,
                     mode=rb.get("mode", "full"))
            return {"ok": True, "status": "stopped",
                    "checkpoint": (self._vol(name).get("rebalance")
                                   or {}).get("checkpoint")}
        if vol["status"] != "started":
            raise MgmtError(f"volume {name} not started")
        if flavor not in ("", "fix-layout"):
            raise MgmtError(f"bad rebalance flavor {flavor!r} "
                            "(only fix-layout)")
        if (vol.get("remove-brick") or {}).get("status") == "started":
            raise MgmtError("a remove-brick drain is in progress; its "
                            "daemon IS a rebalance — wait or stop it")
        mode = flavor or "full"
        if rb.get("status") == "started":
            proc = self.rebalanced.get(name)
            if proc is not None and proc.poll() is None:
                raise MgmtError("rebalance already in progress")
            if rb.get("node") != self.uuid:
                raise MgmtError(
                    "rebalance owned by node "
                    f"{(rb.get('node') or '?')[:8]}; start it there")
            # dead daemon (SIGKILL, crash): respawn — the checkpoint
            # in the volinfo makes this a RESUME, never a restart
            self._spawn_rebalanced(vol)
            return {"ok": True, "status": "resumed",
                    "checkpoint": rb.get("checkpoint")}
        await self._cluster_txn("rebalance-start", {
            "name": name, "mode": mode, "node": self.uuid,
            "ts": time.time()})
        return {"ok": True, "status": "started", "mode": mode}

    @staticmethod
    def _rebal_topology(vol: dict) -> dict:
        """What a rebalance checkpoint is valid AGAINST: the brick set
        and (for drain) which bricks are leaving.  A checkpoint taken
        under one topology must never steer a run under another —
        resuming a pre-add-brick checkpoint skips fix-layout for the
        new leg, and resuming drain-A's checkpoint for drain-B never
        scans B's files and a later commit drops them undrained."""
        return {"bricks": sorted(b["name"] for b in vol["bricks"]),
                "drain": sorted((vol.get("remove-brick") or {})
                                .get("bricks") or ())}

    def commit_rebalance_start(self, name: str, mode: str, node: str,
                               ts: float) -> dict:
        vol = self._vol(name)
        prev = vol.get("rebalance") or {}
        rb = {"status": "started", "mode": mode, "node": node,
              "started": ts, "topology": self._rebal_topology(vol)}
        if prev.get("status") == "stopped" and \
                prev.get("mode") == mode and prev.get("checkpoint") \
                and prev.get("topology") == rb["topology"]:
            # stop -> start continues from the stop's checkpoint (the
            # counters ride inside it) — but ONLY under the same
            # topology it was taken against
            rb["checkpoint"] = prev["checkpoint"]
        vol["rebalance"] = rb
        self._bump(vol)
        self._save()
        if node == self.uuid and vol["status"] == "started":
            self._spawn_rebalanced(vol)
            gf_event("REBALANCE_START", name=name, mode=mode)
        return {"rebalance": mode}

    def commit_rebalance_stop(self, name: str) -> dict:
        vol = self._vol(name)
        rb = vol.get("rebalance") or {}
        if rb.get("node") == self.uuid:
            # SIGTERM: the daemon pushes a final stopped update with
            # its checkpoint before exiting; the stamp below covers a
            # daemon that was already dead
            self._kill_rebalanced(name)
        if rb.get("status") == "started":
            rb["status"] = "stopped"
        self._bump(vol)
        self._save()
        return {"stopped": name}

    async def _rebalance_status(self, vol: dict) -> dict:
        """Per-node daemon state fan-out merged like ``volume status``
        (the defrag status aggregation of glusterd-rebalance.c), with
        unreachable nodes NAMED in ``partial``."""
        name = vol["name"]
        rb = dict(vol.get("rebalance") or {"status": "not-started"})
        nodes = {n["uuid"]: n for n in self._vol_nodes(vol)}
        owner = rb.get("node")
        if owner and owner not in nodes:
            for n in self._all_nodes():
                if n["uuid"] == owner:
                    nodes[owner] = n
        per_node, partial = await self._gather_bricks(
            "volume-rebalance-local", nodes=list(nodes.values()),
            name=name)
        for row in per_node.values():
            if row.get("owner") and row.get("rebalance"):
                # the owner's row carries the freshest pushed state
                rb = row["rebalance"]
        return self._merge_partial(
            {"volume": name, "rebalance": rb, "nodes": per_node},
            partial)

    def op_volume_rebalance_local(self, name: str) -> dict:
        """One node's share of rebalance status: its daemon liveness
        plus its volinfo view (rides the _gather_bricks merge, keyed
        by node id)."""
        vol = self._vol(name)
        rb = vol.get("rebalance") or {}
        proc = self.rebalanced.get(name)
        online = proc is not None and proc.poll() is None
        row: dict[str, Any] = {
            "online": online, "pid": proc.pid if online else 0,
            "owner": bool(rb) and rb.get("node") == self.uuid}
        if rb:
            row["rebalance"] = dict(rb)
        return {"bricks": {self.uuid[:8]: row}}

    async def op_rebalance_update(self, name: str, info: dict) -> dict:
        """The daemon (or the owner's terminal fan-out) pushes
        rebalance progress into the volinfo; CHECKPOINTS land here,
        which is what makes SIGKILL + respawn resume."""
        vol = self._vol(name)
        rb = vol.get("rebalance")
        if rb is None:
            rb = vol["rebalance"] = {}
        rb.update(info)
        terminal = info.get("status") in ("completed", "failed",
                                          "stopped")
        if rb.get("mode") == "drain":
            self._mirror_drain(vol, rb, info)
        if terminal:
            self._bump(vol)
            self._save()
        else:
            # checkpoint pushes can arrive many times a second; the
            # in-memory volinfo is what status ops and a daemon
            # respawn read, so persist at most once a second (a
            # glusterd CRASH resumes from a slightly older checkpoint
            # — the walk is idempotent)
            now = time.monotonic()
            if now - self._rb_saved.get(name, 0.0) >= 1.0:
                self._rb_saved[name] = now
                self._save()
        if terminal and rb.get("node") == self.uuid:
            # propagate terminal state so status/commit addressed to
            # ANY node sees it; peers that miss the push catch up via
            # peer-hello volinfo reconciliation (the generation bumped)
            for node in self._all_nodes():
                if node["uuid"] == self.uuid:
                    continue
                try:
                    await asyncio.wait_for(self._node_call(
                        node, "rebalance-update", name=name,
                        info=dict(rb)), 10)
                except Exception:
                    pass
        return {"ok": True}

    def _mirror_drain(self, vol: dict, rb: dict, info: dict) -> None:
        """A drain-mode rebalance IS the remove-brick migration: its
        progress and terminal state land on the remove-brick record
        that ``remove-brick status``/``commit`` read."""
        rbk = vol.get("remove-brick")
        if rbk is None:
            return
        ctr = rb.get("counters") or {}
        rbk["progress"] = {"phase": rb.get("phase", ""), **ctr}
        status = info.get("status")
        if status == "completed":
            rbk["status"] = "completed"
            rbk["moved"] = ctr.get("moved", 0)
            rbk["scanned"] = ctr.get("scanned", 0)
        elif status == "failed":
            rbk["status"] = "failed"
            rbk["error"] = rb.get("error", "")

    def _spawn_rebalanced(self, vol: dict) -> None:
        name = vol["name"]
        proc = self.rebalanced.get(name)
        if proc is not None and proc.poll() is None:
            return
        rb = vol.get("rebalance") or {}
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        statusfile = os.path.join(self.workdir,
                                  f"rebalanced-{name}.json")
        if not rb.get("checkpoint"):
            # a FRESH run must not inherit a previous run's
            # statusfile: the daemon only writes it at its first push
            # (after the mount settles), and a stop before that would
            # harvest the OLD run's checkpoint into this record —
            # whose topology stamp is this run's own, so the
            # fingerprint guard cannot catch the swap
            try:
                os.unlink(statusfile)
            except OSError:
                pass
        with open(os.path.join(self.workdir, f"rebalanced-{name}.log"),
                  "ab") as logf:
            self.rebalanced[name] = subprocess.Popen(
                [sys.executable, "-m", "glusterfs_tpu.mgmt.rebalanced",
                 "--glusterd", f"{self.host}:{self.port}",
                 "--volname", name,
                 "--mode", rb.get("mode", "full"),
                 "--statusfile", statusfile],
                env=env, stdout=subprocess.DEVNULL, stderr=logf)

    def _kill_rebalanced(self, name: str) -> None:
        proc = self.rebalanced.pop(name, None)
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()
            # the daemon's final rebalance-update cannot land while
            # THIS loop is blocked in wait() (the daemon bounds that
            # push and exits) — its statusfile carries the same final
            # checkpoint, so harvest it here to keep the
            # stop-continues-from-the-stop's-checkpoint contract
            self._harvest_rebal_statusfile(name)

    def _harvest_rebal_statusfile(self, name: str) -> None:
        vol = self.state["volumes"].get(name)
        if vol is None or not (vol.get("rebalance") or {}).get("node"):
            return
        rb = vol["rebalance"]
        if rb.get("node") != self.uuid or \
                rb.get("status") == "completed":
            return
        try:
            with open(os.path.join(
                    self.workdir, f"rebalanced-{name}.json")) as f:
                snap = json.load(f)
        except (OSError, ValueError):
            return
        for k in ("checkpoint", "counters", "phase"):
            if k in snap:
                rb[k] = snap[k]

    def _snap_volinfo_by_name(self, volname: str) -> dict | None:
        for s in self.state.get("snaps", {}).values():
            vi = s.get("volinfo")
            if vi and vi["name"] == volname:
                return vi
        return None

    def op_getspec(self, name: str) -> dict:
        """Serve the client volfile (__server_getspec analog); activated
        snapshots are served like volumes (snapd's volfile)."""
        vol = self.state["volumes"].get(name)
        is_snap = False
        if vol is None:
            vol = self._snap_volinfo_by_name(name)
            is_snap = vol is not None
        if vol is None:
            raise MgmtError(f"no volume {name!r}")
        if vol["status"] != "started":
            raise MgmtError(f"volume {name} not started")
        # no /.snaps inside a snapshot; classification is by identity
        # ('snap-' user volume names are refused at create)
        mgmt = None if is_snap else f"{self.host}:{self.port}"
        return {"volfile": volgen.build_client_volfile(
                    vol, self.ports, mgmt=mgmt),
                "volname": name}

    def _vol(self, name: str) -> dict:
        vol = self.state["volumes"].get(name)
        if vol is None:
            raise MgmtError(f"no volume {name!r}")
        return vol

    # -- snapshots (glusterd-snapshot.c analog, store-level) ---------------
    # The reference snapshots LVM thin volumes; the TPU-build store is a
    # plain directory, so a snapshot is a barriered full copy of each
    # brick store (SURVEY §7's store-level checkpoint), restorable onto
    # a stopped volume.

    async def op_snapshot_create(self, name: str, volume: str) -> dict:
        self._vol(volume)
        if name in self.state.setdefault("snaps", {}):
            raise MgmtError(f"snapshot {name} exists")
        # three cluster-wide phases, reference glusterd-snapshot.c order:
        # barrier EVERY node's bricks, then copy everywhere, then
        # release — a write landing between one node's copy and
        # another's would otherwise make replicas/stripe-groups diverge
        # inside one snapshot
        await self._cluster_txn("snapshot-barrier",
                                {"volume": volume, "on": True})
        try:
            await self._cluster_txn("snapshot-create",
                                    {"name": name, "volume": volume})
        finally:
            await self._cluster_txn("snapshot-barrier",
                                    {"volume": volume, "on": False})
        return {"ok": True, "snapshot": name}

    async def commit_snapshot_barrier(self, volume: str, on: bool) -> dict:
        vol = self._vol(volume)
        if vol["status"] != "started":
            return {"barriered": False}
        if on:
            await self._set_barrier(vol, True)
            await self._await_barrier_drain(vol)
            # eager-window quiesce: clients hold inodelks with DELAYED
            # post-ops (post-op-delay semantics) — data is on the bricks
            # but size/version commit on a timer.  Fire a contention
            # upcall at every held lock (the same signal a conflicting
            # locker sends, ec_lock_release on INODELK_CONTENTION) and
            # wait for the holders to commit + release, so the snapshot
            # captures settled counters, not a crash image needing heal.
            await self._quiesce_client_locks(vol)
        else:
            await self._set_barrier(vol, False, strict=False)
        return {"barriered": on}

    async def _quiesce_client_locks(self, vol: dict,
                                    timeout: float = 5.0) -> None:
        deadline = time.monotonic() + timeout
        for b in vol["bricks"]:
            if b["node"] != self.uuid or b["name"] not in self.bricks:
                continue
            port = self.ports.get(b["name"])
            if not port:
                continue
            try:
                await self._brick_call(vol, port, "contend_held_locks",
                                       [], subvol=b["name"] + "-server")
            except Exception:
                continue  # old/bare brick: crash-consistent copy
            while time.monotonic() < deadline:
                dump = await self._brick_statedump(
                    vol, port, subvol=b["name"] + "-server")
                layers = (dump or {}).get("layers", {})
                granted = [l["private"].get("granted", 0)
                           for l in layers.values()
                           if l.get("type") == "features/locks"]
                if granted and sum(granted) == 0:
                    break
                await asyncio.sleep(0.05)

    def stage_snapshot_create(self, name: str, volume: str) -> None:
        # per-node duplicate check: snapshot state is per-node, and a
        # half-committed earlier attempt must fail the retry here in
        # stage — commit's failure cleanup may only ever delete
        # directories this run created
        if name in self.state.get("snaps", {}):
            raise MgmtError(f"snapshot {name} exists on {self.uuid[:8]}")
        if os.path.exists(os.path.join(self.workdir, "snaps", name)):
            raise MgmtError(f"stale snapshot dir for {name!r}; "
                            "delete the snapshot first")

    async def commit_snapshot_create(self, name: str, volume: str) -> dict:
        import shutil

        from ..storage.posix import snapshot_copy

        vol = self._vol(volume)
        snapdir = os.path.join(self.workdir, "snaps", name)
        os.makedirs(snapdir, exist_ok=True)
        try:
            taken = {}
            for b in vol["bricks"]:
                if b["node"] != self.uuid:
                    continue
                dst = os.path.join(snapdir, b["name"])
                await asyncio.to_thread(snapshot_copy, b["path"], dst)
                taken[b["name"]] = dst
        except BaseException:
            # no partial snapshot may survive: a retry of the same name
            # would hit copytree FileExistsError with no way out.
            # (Safe to remove the whole dir: stage proved it did not
            # pre-exist, so everything under it is ours.)
            await asyncio.to_thread(shutil.rmtree, snapdir,
                                    ignore_errors=True)
            raise
        self.state.setdefault("snaps", {})[name] = {
            "volume": volume, "ts": time.time(), "bricks": taken,
            # the volume's SHAPE at snap time: restore/clone must pair
            # snapped stores with the geometry they were taken under,
            # not whatever the volume grew into afterwards
            "src_volinfo": json.loads(json.dumps(vol)),
        }
        self._save()
        gf_event("SNAPSHOT_CREATED", snapshot=name, volume=volume)
        return {"snapped": sorted(taken)}

    # -- snapshot clone (glusterd-snapshot.c clone: a snapshot becomes a
    # NEW independent writable volume) -------------------------------------

    async def op_snapshot_clone(self, clonename: str,
                                snapname: str) -> dict:
        snap = self.state.get("snaps", {}).get(snapname)
        if snap is None:
            raise MgmtError(f"no snapshot {snapname!r}")
        if clonename in self.state["volumes"]:
            raise MgmtError(f"volume {clonename} exists")
        if clonename.startswith("snap-"):
            raise MgmtError("volume names starting with 'snap-' are "
                            "reserved for activated snapshots")
        base = snap.get("src_volinfo") or self._vol(snap["volume"])
        nodes = {n["uuid"]: n for n in self._all_nodes()}
        bricks, sources = [], {}
        for i, b in enumerate(base["bricks"]):
            node = nodes.get(b["node"])
            if node is None:
                raise MgmtError(f"brick node {b['node'][:8]} unknown")
            bname = f"{clonename}-brick-{i}"
            bricks.append({
                "index": i, "node": b["node"], "host": b["host"],
                "path": os.path.join(node["workdir"], "clones",
                                     clonename, bname),
                "name": bname,
            })
            sources[bname] = b["name"]
        volinfo = _new_volinfo(self.state, clonename, base["type"],
                               bricks, base.get("redundancy", 0))
        volinfo["options"] = dict(base.get("options", {}))
        # systematic rides along: the clone serves the snapped
        # FRAGMENTS, and the fragment format is a property of those
        # bytes — a non-systematic volfile over systematic fragments
        # decodes to garbage (and vice versa)
        for key in ("group-size", "arbiter", "thin-arbiter",
                    "systematic"):
            if key in base:
                volinfo[key] = base[key]
        await self._cluster_txn("snapshot-clone", {
            "snapname": snapname, "volinfo": volinfo,
            "sources": sources})
        return {"ok": True, "volume": clonename}

    def stage_snapshot_clone(self, snapname: str, volinfo: dict,
                             sources: dict) -> None:
        """Per-node validation BEFORE any store copies: a commit-phase
        failure on one node would leave a half-created clone that
        reconciliation then spreads cluster-wide with an empty brick."""
        if volinfo["name"] in self.state["volumes"]:
            raise MgmtError(f"volume {volinfo['name']} exists here")
        snap = self.state.get("snaps", {}).get(snapname) or {}
        for b in volinfo["bricks"]:
            if b["node"] != self.uuid:
                continue
            src = snap.get("bricks", {}).get(sources.get(b["name"], ""))
            if not src or not os.path.isdir(src):
                raise MgmtError(
                    f"no snapped store for {b['name']} on this node")

    async def commit_snapshot_clone(self, snapname: str, volinfo: dict,
                                    sources: dict) -> dict:
        snap = self.state.get("snaps", {}).get(snapname) or {}
        cloned = []
        for b in volinfo["bricks"]:
            if b["node"] != self.uuid:
                continue
            src = snap.get("bricks", {}).get(sources.get(b["name"], ""))
            if not src:
                raise MgmtError(
                    f"no snapped store for {b['name']} on this node")
            await asyncio.to_thread(_copy_store, src, b["path"])
            cloned.append(b["name"])
        self.state["volumes"][volinfo["name"]] = volinfo
        self.state.get("tombstones", {}).pop(volinfo["name"], None)
        self._save()
        gf_event("SNAPSHOT_CLONED", snapshot=snapname,
                 volume=volinfo["name"])
        return {"cloned": cloned}

    async def _set_barrier(self, vol: dict, on: bool,
                           strict: bool = True) -> None:
        """Arm/release the barrier on this node's running bricks via
        live reconfigure (glusterd_snap_brick_barrier analog).  strict:
        a failed arm raises — copying an unquiesced brick would produce
        a torn snapshot reported as success.  Release is best-effort
        (the barrier timeout unwedges a brick we could not reach)."""
        tmp = dict(vol)
        tmp["options"] = dict(vol.get("options", {}))
        tmp["options"]["features.barrier"] = "on" if on else "off"
        for b in vol["bricks"]:
            if b["node"] != self.uuid or b["name"] not in self.bricks:
                continue
            port = self.ports.get(b["name"])
            ok = bool(port) and await self._brick_reconfigure(
                vol, port, volgen.build_brick_volfile(tmp, b),
                subvol=b["name"] + "-server")
            if not ok and strict:
                raise MgmtError(
                    f"could not {'arm' if on else 'release'} barrier on "
                    f"brick {b['name']}")

    async def _await_barrier_drain(self, vol: dict,
                                   timeout: float = 10.0) -> None:
        """Wait until every running brick's barrier layer reports zero
        in-flight gated fops (writes that passed the gate before it was
        armed are still mutating the store; copying under them tears
        the snapshot)."""
        deadline = time.monotonic() + timeout
        for b in vol["bricks"]:
            if b["node"] != self.uuid or b["name"] not in self.bricks:
                continue
            port = self.ports.get(b["name"])
            if not port:
                continue
            while True:
                dump = await self._brick_statedump(
                    vol, port, subvol=b["name"] + "-server")
                layers = (dump or {}).get("layers", {})
                inflight = [l["private"].get("inflight", 0)
                            for l in layers.values()
                            if l.get("type") == "features/barrier"]
                # a dump with no barrier layer would vacuously "drain";
                # treat it as not-quiesced so the bug surfaces as a
                # timeout, not a torn snapshot
                if dump is not None and inflight and \
                        all(n == 0 for n in inflight):
                    break
                if time.monotonic() > deadline:
                    raise MgmtError(
                        f"brick {b['name']} did not quiesce in "
                        f"{timeout:.0f}s")
                await asyncio.sleep(0.02)

    @classmethod
    async def _brick_statedump(cls, vol: dict, port: int,
                               subvol: str = "") -> dict | None:
        try:
            return await cls._brick_call(vol, port, "__statedump__", [],
                                         subvol=subvol)
        except Exception:
            return None

    def op_snapshot_list(self, volume: str | None = None) -> dict:
        snaps = self.state.get("snaps", {})
        out = {n: {"volume": s["volume"], "ts": s["ts"],
                   "bricks": sorted(s["bricks"]),
                   "activated": bool(s.get("volinfo"))}
               for n, s in snaps.items()
               if volume is None or s["volume"] == volume}
        return {"snapshots": out}

    # -- USS: snapshot activate/deactivate (the snapd analog: a
    # snapshot becomes a served read-only volume the snapview layer
    # mounts under /.snaps) ------------------------------------------------

    def _snap_volname(self, name: str) -> str:
        return f"snap-{name}"

    async def op_snapshot_activate(self, name: str) -> dict:
        snap = self.state.get("snaps", {}).get(name)
        if snap is None:
            raise MgmtError(f"no snapshot {name!r}")
        if snap.get("volinfo"):
            return {"ok": True, "already": True}
        parent = self._vol(snap["volume"])
        vi = json.loads(json.dumps(parent))  # deep, store-safe copy
        sv = self._snap_volname(name)
        vi["name"] = sv
        vi["status"] = "started"
        bricks = []
        for b in vi["bricks"]:
            src = snap["bricks"].get(b["name"])
            if src is None:
                continue  # brick lived on another node
            nb = dict(b)
            nb["path"] = src
            nb["name"] = f"{sv}-brick-{b['index']}"
            nb.pop("port", None)
            bricks.append(nb)
        if not bricks:
            raise MgmtError("no local snapshot bricks to activate")
        if len(bricks) < len(parent["bricks"]):
            # partial activation would serve silently-partial history
            # (distribute) or fail every read (disperse < k fragments)
            raise MgmtError(
                "snapshot bricks incomplete on this node: "
                f"{len(bricks)}/{len(parent['bricks'])} "
                "(multi-node snapshot activation is not supported)")
        vi["bricks"] = bricks
        # the snapshot is a file-level copy: rebind the gfid identity
        # store onto the copied inodes before serving (restore does the
        # same; LVM snapshots in the reference keep inodes so skip it)
        from ..storage.posix import rebuild_identity

        for b in bricks:
            await asyncio.to_thread(rebuild_identity, b["path"])
        # a snapshot is immutable history: read-only, no journals or
        # background services
        opts = vi.setdefault("options", {})
        opts["features.read-only"] = "on"
        for k in ("changelog.changelog", "features.bitrot",
                  "features.quota"):
            opts.pop(k, None)
        # a retry after partial failure finds some already serving
        todo = [b for b in bricks
                if self.bricks.get(b["name"]) is None
                or self.bricks[b["name"]].poll() is not None]
        try:
            await self._start_bricks(vi, todo)
        except BaseException:
            # no half-activated snapshot: stop what we started (detach,
            # not kill, when multiplexed — the shared daemon serves
            # other volumes' bricks too)
            for b_ in todo:
                if b_["name"] in self.bricks:
                    await self._stop_brick(vi, b_)
            raise
        snap["volinfo"] = vi
        self._save()
        gf_event("SNAPSHOT_ACTIVATED", snapshot=name)
        return {"ok": True, "volume": sv}

    async def op_snapshot_deactivate(self, name: str) -> dict:
        snap = self.state.get("snaps", {}).get(name)
        if snap is None:
            raise MgmtError(f"no snapshot {name!r}")
        vi = snap.pop("volinfo", None)
        if vi:
            for b in vi["bricks"]:
                await self._stop_brick(vi, b)
                self.ports.pop(b["name"], None)
        self._save()
        return {"ok": True}

    async def op_snapshot_delete(self, name: str) -> dict:
        if name not in self.state.get("snaps", {}):
            raise MgmtError(f"no snapshot {name!r}")
        await self._cluster_txn("snapshot-delete", {"name": name})
        return {"ok": True}

    async def commit_snapshot_delete(self, name: str) -> dict:
        import shutil

        if self.state.get("snaps", {}).get(name, {}).get("volinfo"):
            await self.op_snapshot_deactivate(name)
        snap = self.state.get("snaps", {}).pop(name, None)
        self._save()
        if snap:
            await asyncio.to_thread(
                shutil.rmtree, os.path.join(self.workdir, "snaps", name),
                ignore_errors=True)
        return {"deleted": name}

    async def op_snapshot_restore(self, name: str) -> dict:
        snap = self.state.get("snaps", {}).get(name)
        if snap is None:
            raise MgmtError(f"no snapshot {name!r}")
        vol = self._vol(snap["volume"])
        if vol["status"] == "started":
            raise MgmtError("stop the volume before restore")
        await self._cluster_txn("snapshot-restore", {"name": name})
        return {"ok": True, "restored": snap["volume"]}

    async def commit_snapshot_restore(self, name: str) -> dict:
        snap = self.state.get("snaps", {}).get(name)
        if snap is None:
            return {"restored": []}
        vol = self._vol(snap["volume"])
        # restore rolls the volume's SHAPE back to snap time too (the
        # reference swaps in the snapshot's volinfo wholesale): a volume
        # grown after the snapshot must not end up with bricks from two
        # epochs — snap-time content on the old bricks, post-snap
        # content on the new ones — serving inconsistent stripes
        src_vi = snap.get("src_volinfo")
        if src_vi is not None:
            for key in ("type", "bricks", "redundancy", "group-size",
                        "arbiter", "thin-arbiter"):
                if key in src_vi:
                    vol[key] = json.loads(json.dumps(src_vi[key]))
                else:
                    vol.pop(key, None)
            self._bump(vol)
            self._save()
        restored = []
        for b in vol["bricks"]:
            src = snap["bricks"].get(b["name"])
            if b["node"] != self.uuid or not src:
                continue
            await asyncio.to_thread(_copy_store, src, b["path"])
            restored.append(b["name"])
        return {"restored": restored}

    # -- bit-rot (glusterd-bitrot.c op handlers analog) --------------------

    async def op_volume_bitrot(self, name: str, action: str) -> dict:
        """enable / disable / status / scrub-status for bit-rot
        detection on a volume."""
        vol = self._vol(name)
        if action == "enable":
            await self._cluster_txn("volume-set", {
                "name": name, "key": "features.bitrot", "value": "on"})
            # spawn on EVERY node holding bricks, not just the originator
            await self._cluster_txn("bitrot-ctl",
                                    {"name": name, "action": "spawn"})
            return {"ok": True, "enabled": name}
        if action == "disable":
            await self._cluster_txn("bitrot-ctl",
                                    {"name": name, "action": "kill"})
            await self._cluster_txn("volume-set", {
                "name": name, "key": "features.bitrot", "value": "off"})
            return {"ok": True, "disabled": name}
        if action in ("status", "scrub-status"):
            proc = self.bitd.get(name)
            out = {"online": proc is not None and proc.poll() is None}
            try:
                with open(os.path.join(self.workdir,
                                       f"bitd-{name}.json")) as f:
                    out.update(json.load(f))
            except (FileNotFoundError, ValueError):
                pass
            return out
        raise MgmtError(f"unknown bitrot action {action!r}")

    def commit_bitrot_ctl(self, name: str, action: str) -> dict:
        vol = self._vol(name)
        if action == "spawn":
            if vol["status"] == "started":
                self._spawn_bitd(vol)
        else:
            self._kill_bitd(name)
        return {action: name}

    # -- quota (quota.c enforcement + quotad-aggregator.c) -----------------

    async def op_volume_quota(self, name: str, action: str,
                              path: str = "", limit: int = 0) -> dict:
        """gluster volume quota <v> enable|disable|limit-usage|remove|
        list analog."""
        self._vol(name)
        if action == "enable":
            await self._cluster_txn("volume-set", {
                "name": name, "key": "features.quota", "value": "on"})
            await self._cluster_txn("quota-ctl",
                                    {"name": name, "action": "spawn"})
            return {"ok": True, "enabled": name}
        if action == "disable":
            await self._cluster_txn("quota-ctl",
                                    {"name": name, "action": "kill"})
            await self._cluster_txn("volume-set", {
                "name": name, "key": "features.quota", "value": "off"})
            return {"ok": True, "disabled": name}
        if action == "limit-usage":
            if not path or int(limit) <= 0:
                raise MgmtError("limit-usage needs a path and a "
                                "positive byte limit")
            await self._cluster_txn("quota-limit", {
                "name": name, "path": path, "limit": int(limit)})
            return {"ok": True, "path": path, "limit": int(limit)}
        if action == "remove":
            if not path:
                raise MgmtError("remove needs a path")
            await self._cluster_txn("quota-limit", {
                "name": name, "path": path, "limit": 0})
            return {"ok": True, "removed": path}
        if action == "list":
            if not volgen._bool(self._vol(name).get("options", {}).get(
                    "features.quota", "off")):
                raise MgmtError(f"quota not enabled on {name}")
            port = self._quotad_port(name)
            if port:
                try:
                    reader, writer = await asyncio.wait_for(
                        asyncio.open_connection("127.0.0.1", port), 5)
                    try:
                        writer.write(wire.pack(1, wire.MT_CALL,
                                               ["quota-list"]))
                        await writer.drain()
                        rec = await asyncio.wait_for(
                            wire.read_frame(reader), 10)
                        _, _, payload = wire.unpack(rec)
                        return payload
                    finally:
                        writer.close()
                except Exception:
                    pass
            # quotad unreachable: last persisted aggregate
            try:
                with open(os.path.join(self.workdir,
                                       f"quotad-{name}.json")) as f:
                    return json.load(f).get("usage", {})
            except (FileNotFoundError, ValueError):
                return {}
        raise MgmtError(f"unknown quota action {action!r}")

    async def commit_quota_limit(self, name: str, path: str,
                                 limit: int) -> dict:
        vol = self._vol(name)
        limits = vol.setdefault("quota", {}).setdefault("limits", {})
        p = path.rstrip("/") or "/"
        if limit > 0:
            limits[p] = int(limit)
        else:
            limits.pop(p, None)
        self._bump(vol)
        self._save()
        applied = "stored"
        if vol["status"] == "started" and volgen._bool(
                vol.get("options", {}).get("features.quota", "off")):
            # limits ride the quota layer's `limits` option: live
            # reconfigure, no brick restart
            applied = await self._apply_to_bricks(vol)
        return {"applied": applied}

    def commit_quota_ctl(self, name: str, action: str) -> dict:
        vol = self._vol(name)
        if action == "spawn":
            if vol["status"] == "started":
                self._spawn_quotad(vol)
        else:
            self._kill_quotad(name)
        return {action: name}

    def _quotad_port(self, name: str) -> int:
        try:
            with open(os.path.join(self.workdir,
                                   f"quotad-{name}.port")) as f:
                return int(f.read())
        except (FileNotFoundError, ValueError):
            return 0

    def _spawn_quotad(self, vol: dict) -> None:
        from . import svcutil

        name = vol["name"]
        proc = self.quotad.get(name)
        if proc is not None and proc.poll() is None:
            return
        local = [(b["name"], self.ports.get(b["name"], 0),
                  svcutil.brick_group(vol, b["index"]))
                 for b in vol["bricks"]
                 if b["node"] == self.uuid and self.ports.get(b["name"])]
        if not local:
            return
        env = svcutil.spawn_env(vol, "GFTPU_QUOTAD")
        portfile = os.path.join(self.workdir, f"quotad-{name}.port")
        if os.path.exists(portfile):
            os.unlink(portfile)
        statusfile = os.path.join(self.workdir, f"quotad-{name}.json")
        with open(os.path.join(self.workdir, f"quotad-{name}.log"),
                  "ab") as logf:
            self.quotad[name] = subprocess.Popen(
                [sys.executable, "-m", "glusterfs_tpu.mgmt.quotad",
                 "--bricks", ",".join(f"{n}:{p}:{g}" for n, p, g in local),
                 *svcutil.spawn_ssl_argv(vol.get("options", {})),
                 "--portfile", portfile, "--statusfile", statusfile],
                env=env, stdout=subprocess.DEVNULL, stderr=logf)

    def _kill_quotad(self, name: str) -> None:
        proc = self.quotad.pop(name, None)
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()
        # stale port/status files would make 'quota list' report old
        # numbers as live after a disable
        for suffix in (".port", ".json"):
            try:
                os.unlink(os.path.join(self.workdir,
                                       f"quotad-{name}{suffix}"))
            except FileNotFoundError:
                pass

    def _spawn_bitd(self, vol: dict) -> None:
        name = vol["name"]
        proc = self.bitd.get(name)
        if proc is not None and proc.poll() is None:
            return
        local = [(b["name"], self.ports.get(b["name"], 0))
                 for b in vol["bricks"]
                 if b["node"] == self.uuid and self.ports.get(b["name"])]
        if not local:
            return
        from . import svcutil

        opts = vol.get("options", {})
        scrub_off = str(opts.get("features.scrub", "on")).lower() in (
            "off", "false", "no", "0", "pause")
        # features.scrub-freq maps onto the sweep interval (hourly/
        # daily/... in the reference; seconds here, names accepted)
        freq = opts.get("features.scrub-freq",
                        opts.get("bitrot.scrub-interval", 60))
        freq = {"hourly": 3600, "daily": 86400, "weekly": 604800,
                "biweekly": 1209600, "monthly": 2592000}.get(
                    str(freq).lower(), freq)
        thr = opts.get("features.scrub-throttle",
                       opts.get("bitrot.scrub-throttle",
                                DEFAULT_SCRUB_THROTTLE))
        thr = {"lazy": DEFAULT_SCRUB_THROTTLE / 4,
               "normal": DEFAULT_SCRUB_THROTTLE,
               "aggressive": DEFAULT_SCRUB_THROTTLE * 8}.get(
                   str(thr).lower(), thr)
        env = svcutil.spawn_env(vol, "GFTPU_BITD")
        statusfile = os.path.join(self.workdir, f"bitd-{name}.json")
        with open(os.path.join(self.workdir, f"bitd-{name}.log"),
                  "ab") as logf:
            self.bitd[name] = subprocess.Popen(
                [sys.executable, "-m", "glusterfs_tpu.mgmt.bitd",
                 "--bricks", ",".join(f"{n}:{p}" for n, p in local),
                 *svcutil.spawn_ssl_argv(opts),
                 # features.expiry-time: the signer's quiesce window
                 "--quiesce", str(opts.get("features.expiry-time",
                                           opts.get(
                                               "bitrot.signer-quiesce",
                                               120))),
                 "--scrub-interval", str(freq),
                 "--scrub-throttle", str(thr),
                 *(["--no-scrub"] if scrub_off else []),
                 "--statusfile", statusfile],
                env=env, stdout=subprocess.DEVNULL, stderr=logf)

    def _kill_bitd(self, name: str) -> None:
        proc = self.bitd.pop(name, None)
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()

    # -- HTTP object gateway (gateway/, ISSUE 6) ---------------------------
    # Lifecycle rides the cluster txn like geo-rep: every node stores
    # the started/stopped state and runs (or not) its own gateway
    # daemon — the second front door scales out with the mgmt cluster.

    async def op_volume_gateway(self, name: str,
                                action: str = "status") -> dict:
        vol = self._vol(name)
        if action == "status":
            return self._gateway_status(vol)
        if action not in ("start", "stop"):
            raise MgmtError(f"bad gateway action {action!r} "
                            "(want start|stop|status)")
        if action == "start" and vol["status"] != "started":
            raise MgmtError(f"volume {name} not started")
        if self.cluster_op_version() < 8:
            raise MgmtError(
                "volume gateway needs cluster op-version >= 8 "
                f"(cluster is at {self.cluster_op_version()})")
        await self._cluster_txn(f"gateway-{action}", {"name": name})
        return {"ok": True, **self._gateway_status(vol)}

    def commit_gateway_start(self, name: str) -> dict:
        vol = self._vol(name)
        vol["gateway"] = {"status": "started"}
        self._bump(vol)
        self._save()
        self._spawn_gateway(vol)
        return {"gateway-started": name}

    def commit_gateway_stop(self, name: str) -> dict:
        vol = self._vol(name)
        vol["gateway"] = {"status": "stopped"}
        self._bump(vol)
        self._save()
        self._kill_gateway(name)
        return {"gateway-stopped": name}

    def _gateway_port(self, name: str) -> int:
        try:
            with open(os.path.join(self.workdir,
                                   f"gateway-{name}.port")) as f:
                return int(f.read())
        except (FileNotFoundError, ValueError):
            return 0

    def _gateway_status(self, vol: dict) -> dict:
        name = vol["name"]
        proc = self.gateway.get(name)
        online = proc is not None and proc.poll() is None
        return {"volume": name,
                "gateway": {
                    "status": vol.get("gateway", {}).get("status",
                                                         "stopped"),
                    "online": online,
                    "pid": proc.pid if online else 0,
                    "port": self._gateway_port(name) if online else 0}}

    def _spawn_gateway(self, vol: dict) -> None:
        from . import svcutil

        name = vol["name"]
        proc = self.gateway.get(name)
        if proc is not None and proc.poll() is None:
            return
        opts = vol.get("options", {})
        env = svcutil.spawn_env(vol, "GFTPU_GATEWAY")
        portfile = os.path.join(self.workdir, f"gateway-{name}.port")
        if os.path.exists(portfile):
            os.unlink(portfile)
        argv = [sys.executable, "-m", "glusterfs_tpu.gateway",
                "--glusterd", f"{self.host}:{self.port}",
                "--volume", name,
                "--host", str(opts.get("gateway.listen-host",
                                       "127.0.0.1")),
                "--listen", str(opts.get("gateway.port", 0)),
                "--pool", str(opts.get("gateway.pool-size", 4)),
                "--max-clients", str(opts.get("gateway.max-clients",
                                              512)),
                "--object-cache",
                str(opts.get("gateway.object-cache-size", 0)),
                "--portfile", portfile]
        workers = int(opts.get("gateway.workers", 0) or 0)
        if volgen._bool(opts.get("server.qos", "off")):
            # HTTP clients inherit the volume's QoS plane: the same
            # server.qos-* rates the bricks enforce per wire identity,
            # applied per peer IP at the gateway door (429 +
            # Retry-After instead of EAGAIN + notice).  Spawn-time
            # plumbing: retuning these keys live re-spawns via gateway
            # stop/start (documented in docs/qos.md).  The per-worker
            # buckets are shared-nothing, so the spawn-time rates are
            # DIVIDED across the pool — N workers must enforce the
            # operator's ONE budget, not N of them (the PR-17 ceiling)
            share = max(1, workers)

            def _rate(key):
                # 0 = unlimited stays unlimited at any pool width;
                # bytes-per-sec is a size option ("1MB"), so parse it
                # the way the gateway would before dividing
                from ..core.options import parse_size
                try:
                    v = float(parse_size(opts.get(key, 0) or 0))
                except Exception:
                    v = 0.0
                return v / share if v > 0 else 0

            argv += ["--qos-fops",
                     str(_rate("server.qos-fops-per-sec")),
                     "--qos-bytes",
                     str(_rate("server.qos-bytes-per-sec")),
                     "--qos-burst",
                     str(max(1, int(float(opts.get("server.qos-burst", 1)
                                          or 1) // share)))]
        if workers > 0:
            # the shared-nothing worker pool (op-version 14): the
            # spawned process becomes the supervisor; worker pids land
            # in the statusfile so status/chaos tooling can see them
            argv += ["--workers", str(workers),
                     "--statusfile",
                     os.path.join(self.workdir,
                                  f"gateway-{name}.workers")]
        if opts.get("gateway.metrics-port"):
            # the daemon's gftpu_gateway_* families are in ITS process:
            # without this the managed front door is metrics-blind
            argv += ["--metrics-port",
                     str(opts["gateway.metrics-port"])]
        if opts.get("diagnostics.incident-dir"):
            # the supervisor mounts no volfile, so the diagnostics.*
            # keys never reach it through io-stats — arm its
            # auto-capture (worker-respawn bundles) via argv
            argv += ["--incident-dir",
                     str(opts["diagnostics.incident-dir"])]
        ev = os.environ.get("GFTPU_EVENTSD")
        if ev:
            argv += ["--eventsd", ev]
        with open(os.path.join(self.workdir, f"gateway-{name}.log"),
                  "ab") as logf:
            self.gateway[name] = subprocess.Popen(
                argv, env=env, stdout=subprocess.DEVNULL, stderr=logf)

    def _kill_gateway(self, name: str) -> None:
        proc = self.gateway.pop(name, None)
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()
        try:
            os.unlink(os.path.join(self.workdir,
                                   f"gateway-{name}.port"))
        except FileNotFoundError:
            pass

    # -- geo-replication (glusterd-geo-rep.c session mgmt analog) ----------
    # Session ops run through the cluster txn so every node stores the
    # link and runs a worker over ITS local bricks' changelogs — a
    # change landing on a remote node's brick is journaled and replayed
    # there (workers partition by brick; replay is idempotent so replica
    # overlap across nodes converges).

    async def op_georep_create(self, name: str, secondary: str) -> dict:
        """Create a geo-rep link: secondary is 'host:port:volume' of the
        secondary volume's glusterd."""
        self._vol(name)
        host, port, svol = secondary.rsplit(":", 2)
        if not (host and port.isdigit() and svol):
            raise MgmtError(f"bad secondary spec {secondary!r} "
                            f"(want host:port:volume)")
        await self._cluster_txn("georep-create",
                                {"name": name, "secondary": secondary})
        return {"ok": True, "primary": name, "secondary": secondary}

    async def commit_georep_create(self, name: str, secondary: str) -> dict:
        vol = self._vol(name)
        vol["georep"] = {"secondary": secondary, "status": "created"}
        # the journal feeds gsyncd: enable changelog and respawn local
        # bricks so their graphs pick it up (reference: geo-rep create
        # force-enables changelog + marker)
        vol.setdefault("options", {})["changelog.changelog"] = "on"
        self._bump(vol)
        self._save()
        if vol["status"] == "started":
            for b in vol["bricks"]:
                if b["node"] == self.uuid and b["name"] in self.bricks:
                    port = b.get("port")
                    await self._stop_brick(vol, b)
                    await self._spawn_brick(vol, b, port=port)
        return {"created": name}

    async def op_georep_start(self, name: str) -> dict:
        vol = self._vol(name)
        if not vol.get("georep"):
            raise MgmtError(f"no geo-rep session on {name}")
        if vol["status"] != "started":
            raise MgmtError(f"volume {name} not started")
        await self._cluster_txn("georep-start", {"name": name})
        return {"ok": True}

    def commit_georep_start(self, name: str) -> dict:
        vol = self._vol(name)
        geo = vol["georep"]
        geo["status"] = "started"
        self._bump(vol)
        self._save()
        self._spawn_gsync(vol)
        return {"started": name}

    def _spawn_gsync(self, vol: dict) -> None:
        name = vol["name"]
        geo = vol.get("georep") or {}
        proc = self.gsync.get(name)
        if proc is not None and proc.poll() is None:
            return
        local = [b for b in vol["bricks"] if b["node"] == self.uuid]
        if not local:
            return  # no journals on this node
        # per-brick worker monitor (monitor.py:63-85): brick specs as
        # name=index=path; the subvolume group size drives the
        # Active/Passive election inside replica/disperse sets
        bricks = ",".join(
            f"{b['name']}={b['index']}={b['path']}" for b in local)
        if vol["type"] in ("replicate", "disperse"):
            gsize = int(vol.get("group-size") or len(vol["bricks"]))
        else:
            gsize = 1
        state = os.path.join(self.workdir, f"gsync-{name}.state")
        statusfile = os.path.join(self.workdir, f"gsync-{name}.json")
        interval = float(vol.get("options", {}).get(
            "georep.sync-interval", 3))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        with open(os.path.join(self.workdir, f"gsync-{name}.log"),
                  "ab") as logf:
            self.gsync[name] = subprocess.Popen(
                [sys.executable, "-m", "glusterfs_tpu.mgmt.gsyncd",
                 "--primary", f"{self.host}:{self.port}:{name}",
                 "--secondary", geo["secondary"],
                 "--bricks", bricks, "--group-size", str(gsize),
                 "--state", state,
                 "--interval", str(interval),
                 "--statusfile", statusfile],
                env=env, stdout=subprocess.DEVNULL, stderr=logf)

    def _kill_gsync(self, name: str) -> None:
        proc = self.gsync.pop(name, None)
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()

    async def op_georep_stop(self, name: str) -> dict:
        vol = self._vol(name)
        if not vol.get("georep"):
            raise MgmtError(f"no geo-rep session on {name}")
        await self._cluster_txn("georep-stop", {"name": name})
        return {"ok": True}

    def commit_georep_stop(self, name: str) -> dict:
        vol = self._vol(name)
        self._kill_gsync(name)
        vol["georep"]["status"] = "stopped"
        self._bump(vol)
        self._save()
        return {"stopped": name}

    async def op_georep_checkpoint(self, name: str) -> dict:
        """Stamp a checkpoint on the session (gsyncd checkpoint):
        status reports it reached once the worker has replayed every
        change journaled before this instant (gsyncdstatus.py
        checkpoint completion)."""
        vol = self._vol(name)
        if not vol.get("georep"):
            raise MgmtError(f"no geo-rep session on {name}")
        ts = time.time()
        await self._cluster_txn("georep-checkpoint",
                                {"name": name, "ts": ts})
        return {"ok": True, "checkpoint": ts}

    def commit_georep_checkpoint(self, name: str, ts: float) -> dict:
        vol = self._vol(name)
        vol["georep"]["checkpoint"] = ts
        self._save()
        return {"checkpoint": ts}

    def op_georep_status(self, name: str) -> dict:
        vol = self._vol(name)
        geo = vol.get("georep")
        if not geo:
            return {"sessions": []}
        proc = self.gsync.get(name)
        state_path = os.path.join(self.workdir, f"gsync-{name}.state")
        worker_state = {}
        try:
            with open(state_path) as f:
                worker_state = json.load(f)
        except (FileNotFoundError, ValueError):
            pass
        last_ts = worker_state.get("last_ts", 0)
        synced_through = worker_state.get("synced_through", last_ts)
        sess = {
            "primary": name, "secondary": geo["secondary"],
            "status": geo["status"],
            "online": proc is not None and proc.poll() is None,
            "last_ts": last_ts,
        }
        # per-brick worker states from the monitor (monitor.py model:
        # Active / Passive / Faulty / Offline per brick)
        try:
            with open(os.path.join(self.workdir,
                                   f"gsync-{name}.json")) as f:
                mon = json.load(f)
            if mon.get("workers"):
                sess["workers"] = mon["workers"]
        except (FileNotFoundError, ValueError):
            pass
        cp = geo.get("checkpoint")
        if cp:
            sess["checkpoint"] = cp
            sess["checkpoint_completed"] = synced_through >= cp
        return {"sessions": [sess]}

    # -- brick lifecycle (glusterd-utils.c runner + pmap) ------------------

    async def _start_local_bricks(self, vol: dict,
                                  reuse_ports: bool = False) -> None:
        await self._start_bricks(
            vol, [b for b in vol["bricks"]
                  if b["node"] == self.uuid
                  and b["name"] not in self.bricks], reuse_ports)

    async def _start_bricks(self, vol: dict, bricks: list,
                            reuse_ports: bool = False) -> None:
        """Start ``bricks`` of ``vol`` side by side and wait for all of
        them (glusterd_volume_start_glusterfs forks each brick with
        runner_run_nowait and learns its port at pmap sign-in): every
        process is forked before the first port file is awaited, so the
        call takes about as long as its slowest brick.  Every brick is
        tried; each one that came up is tracked and stored; the error
        raised is that of the first brick in the given order that
        failed.  A multiplexed volume attaches in brick order, one
        ATTACH after another, and stops at the first refusal."""
        if not bricks:
            return
        took: dict[str, float] = {}

        async def boot(b: dict) -> None:
            t = time.monotonic()
            await self._boot_brick(
                vol, b, b.get("port") if reuse_ports else None)
            took[b["name"]] = time.monotonic() - t

        t0 = time.monotonic()
        tasks: list[asyncio.Task] = []
        try:
            if self._mux_enabled(vol):
                for b in bricks:
                    await boot(b)
                return
            tasks = [asyncio.ensure_future(boot(b)) for b in bricks]
            for result in await asyncio.gather(*tasks,
                                               return_exceptions=True):
                if isinstance(result, BaseException):
                    raise result  # the first failure, in brick order
        except asyncio.CancelledError:
            # the caller is gone, and gather has passed that on: each
            # wait under way reaps its own process (_spawn_daemon), and
            # this call outlives them all
            await asyncio.gather(*tasks, return_exceptions=True)
            raise
        finally:
            # the tables in brick order, whatever order the ports came in
            for name in [b["name"] for b in bricks if b["name"] in took]:
                self.bricks[name] = self.bricks.pop(name)
                self.ports[name] = self.ports.pop(name)
            if took:
                self._save()
            wall = time.monotonic() - t0
            slowest = max(took.values(), default=0.0)
            log.info(25, "bricks started: n=%d wall_s=%.2f slowest_s=%.2f",
                     len(took), wall, slowest)
            flight.record("bricks_started", volume=vol["name"],
                          n=len(took), wall_s=round(wall, 3),
                          slowest_s=round(slowest, 3))

    async def _broadcast_local_ports(self, vol: dict) -> None:
        """pmap sync for this node's live bricks: write their current
        ports into volinfo and push them to every peer (the signed-in
        side of glusterd-pmap.c; restart-resume and reconciliation both
        bind fresh ports that peers' volfiles must pick up)."""
        ports = {b["name"]: self.ports[b["name"]]
                 for b in vol["bricks"]
                 if b["node"] == self.uuid and b["name"] in self.ports}
        if not ports:
            return
        changed = False
        for b in vol["bricks"]:
            if b["name"] in ports and b.get("port") != ports[b["name"]]:
                b["port"] = ports[b["name"]]
                changed = True
        if changed:
            self._save()
            self._notify_subscribers(vol["name"])
        for node in self._all_nodes():
            if node["uuid"] == self.uuid:
                continue
            try:
                await asyncio.wait_for(self._node_call(
                    node, "portmap-update", name=vol["name"],
                    ports=ports), 10)
            except Exception:
                continue

    # -- brick multiplexing (glusterfsd-mgmt.c ATTACH / brick-mux) ---------
    # One shared daemon per node anchored on a glusterd-owned stub
    # graph; every brick of a cluster.brick-multiplex volume is
    # attached into it over the ATTACH RPC and served on ONE port,
    # routed by the client's SETVOLUME remote-subvolume.

    def _mux_enabled(self, vol: dict) -> bool:
        if not volgen._bool(vol.get("options", {}).get(
                "cluster.brick-multiplex", "off")):
            return False
        if volgen._bool(vol.get("options", {}).get("server.ssl", "off")):
            # the mux transport carries the anchor's (plaintext) TLS
            # identity; a per-volume-TLS brick needs its own process
            log.warning(19, "%s: server.ssl volume gets a dedicated "
                        "brick process despite brick-multiplex",
                        vol["name"])
            return False
        return True

    def _mux_auth_vol(self) -> dict:
        """Pseudo-volinfo carrying the node's anchor credentials (for
        mgmt calls against the shared daemon's default graph)."""
        auth = self.state.setdefault("mux-auth", {
            "mgmt-username": str(uuid.uuid4()),
            "mgmt-password": str(uuid.uuid4())})
        return {"name": "mux-anchor", "options": {}, "auth": auth}

    async def _spawn_daemon(self, volfile: str, text: str, portfile: str,
                            logfile: str, top: str,
                            port: int | None = None,
                            what: str = "brick",
                            extra_env: dict | None = None
                            ) -> tuple[subprocess.Popen, int]:
        """Shared spawn-and-wait machinery for brick daemons (dedicated
        bricks and the mux anchor use the same path)."""
        with open(volfile, "w") as f:
            f.write(text)
        if os.path.exists(portfile):
            os.unlink(portfile)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        if extra_env:
            env.update(extra_env)
        with open(logfile, "ab") as logf:
            proc = subprocess.Popen(
                [sys.executable, "-m", "glusterfs_tpu.daemon",
                 "--volfile", volfile, "--listen", str(port or 0),
                 "--portfile", portfile, "--top", top],
                env=env, stdout=subprocess.DEVNULL, stderr=logf)
        # generous: a cold interpreter+jax import on a loaded host can
        # take the better part of a minute
        deadline = time.time() + 90
        try:
            while time.time() < deadline:
                if os.path.exists(portfile):
                    with open(portfile) as f:
                        return proc, int(f.read())
                if proc.poll() is not None:
                    with open(logfile, "rb") as f:
                        err = f.read().decode(errors="replace")[-2000:]
                    raise MgmtError(f"{what} failed: {err}")
                await asyncio.sleep(0.05)
            raise MgmtError(f"{what} did not start in time")
        except BaseException:
            # a straggler, or a caller that went away mid-wait
            # (cancelled): no table will ever hold this process, and an
            # orphan that binds its port AFTER we give up would serve a
            # brick glusterd no longer tracks
            await self._reap(proc)
            raise

    @staticmethod
    async def _reap(proc: subprocess.Popen) -> None:
        """terminate -> wait -> kill escalation, off the loop."""
        if proc.poll() is None:
            proc.terminate()
            try:
                await asyncio.to_thread(proc.wait, timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()

    async def _ensure_mux_proc(self) -> int:
        async with self._mux_lock:
            # re-check under the lock: a concurrent caller may have
            # finished the (up to 90s) spawn while we waited — two
            # anchors would strand the first one's attached bricks
            if self._mux and self._mux["proc"].poll() is None:
                return self._mux["port"]
            anchor = self._mux_auth_vol()
            bdir = os.path.join(self.workdir, "bricks")
            os.makedirs(bdir, exist_ok=True)
            adir = os.path.join(self.workdir, "mux-anchor")
            os.makedirs(adir, exist_ok=True)
            text = (
                f"volume mux-anchor-posix\n    type storage/posix\n"
                f"    option directory {adir}\nend-volume\n"
                f"volume mux-anchor-server\n    type protocol/server\n"
                f"    option auth-mgmt-user "
                f"{anchor['auth']['mgmt-username']}\n"
                f"    option auth-mgmt-password "
                f"{anchor['auth']['mgmt-password']}\n"
                # no client credentials exist for the anchor: refuse
                # every non-mgmt handshake outright
                f"    option auth-reject *\n"
                f"    subvolumes mux-anchor-posix\nend-volume\n")
            proc, port = await self._spawn_daemon(
                os.path.join(bdir, "mux-anchor.vol"), text,
                os.path.join(bdir, "mux-anchor.port"),
                os.path.join(bdir, "mux-anchor.log"),
                "mux-anchor-server", what="mux daemon")
            self._mux = {"proc": proc, "port": port, "bricks": set()}
            return port

    async def _attach_brick(self, vol: dict, b: dict) -> None:
        port = await self._ensure_mux_proc()
        text = volgen.build_brick_volfile(vol, b)
        payload = await self._brick_call(
            self._mux_auth_vol(), port, "__attach__",
            [text, b["name"] + "-server"])
        if not (payload and payload.get("ok")):
            raise MgmtError(f"attach of {b['name']} refused: {payload}")
        self._mux["bricks"].add(b["name"])
        self.bricks[b["name"]] = self._mux["proc"]
        self.ports[b["name"]] = port
        b["port"] = port

    async def _stop_brick(self, vol: dict, b: dict) -> None:
        """Stop serving one brick: detach from the shared daemon when
        multiplexed, else kill its dedicated process."""
        name = b["name"]
        if self._mux and name in self._mux["bricks"]:
            try:
                await self._brick_call(
                    self._mux_auth_vol(), self._mux["port"],
                    "__detach__", [name + "-server"])
            except Exception as e:
                log.warning(20, "detach of %s failed: %r", name, e)
            self._mux["bricks"].discard(name)
            self.bricks.pop(name, None)
            self.ports.pop(name, None)
            return
        self._kill_brick(name)

    def _mesh_env(self, vol: dict, b: dict) -> dict | None:
        """``cluster.mesh-distributed`` (op-version 14): each brick
        daemon of the volume is one ``jax.distributed`` process —
        coordinator on brick 0's node, ``num_processes`` = brick
        count, ``process_id`` = brick index.  The daemon's meshd glue
        (parallel/meshd.py) reads these and initializes in the
        BACKGROUND, so brick startup (and glusterd's wait for the
        port files) never blocks on ranks that aren't up yet."""
        opts = vol.get("options", {})
        if not volgen._bool(opts.get("cluster.mesh-distributed",
                                     "off")):
            return None
        port = vol.get("mesh-coordinator-port")
        if not port:
            # DETERMINISTIC from the replicated volume id: every
            # node's glusterd computes the same coordinator port with
            # no cross-node coordination.  (A lazily-bound ephemeral
            # port picked per node diverged across peers — node B's
            # ranks dialed a port nothing on node A listened on.)
            import hashlib

            h = int(hashlib.sha1(
                str(vol.get("id", vol["name"])).encode()).hexdigest(),
                16)
            port = 30000 + (h % 20000)
            vol["mesh-coordinator-port"] = port
            self._save()
        bricks = vol["bricks"]
        hosts = {n["uuid"]: n["host"] for n in self._all_nodes()}
        coord = hosts.get(bricks[0]["node"], self.host)
        rank = next((i for i, x in enumerate(bricks)
                     if x["name"] == b["name"]), 0)
        return {"GFTPU_MESH_COORDINATOR": f"{coord}:{port}",
                "GFTPU_MESH_PROCESSES": str(len(bricks)),
                "GFTPU_MESH_RANK": str(rank)}

    async def _spawn_brick(self, vol: dict, b: dict,
                           port: int | None = None) -> None:
        """Start ONE brick and store its port (several go through
        :meth:`_start_bricks`)."""
        await self._boot_brick(vol, b, port)
        self._save()

    async def _boot_brick(self, vol: dict, b: dict,
                          port: int | None = None) -> None:
        """Bring one brick up and enter it in the tables; the caller
        stores the volinfo."""
        if self._mux_enabled(vol):
            await self._attach_brick(vol, b)
            return
        bdir = os.path.join(self.workdir, "bricks")
        os.makedirs(bdir, exist_ok=True)
        proc, bport = await self._spawn_daemon(
            os.path.join(bdir, b["name"] + ".vol"),
            volgen.build_brick_volfile(vol, b),
            os.path.join(bdir, b["name"] + ".port"),
            os.path.join(bdir, b["name"] + ".log"),
            # serve the auth-carrying protocol/server top, not the
            # io-stats layer underneath it
            b["name"] + "-server", port=port,
            what=f"brick {b['name']}",
            extra_env=self._mesh_env(vol, b))
        self.bricks[b["name"]] = proc
        self.ports[b["name"]] = bport
        b["port"] = bport

    def _kill_brick(self, name: str) -> None:
        proc = self.bricks.pop(name, None)
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()
        self.ports.pop(name, None)

    # -- self-heal daemon lifecycle (glusterd-shd-svc.c analog) -----------

    @staticmethod
    def _shd_max_heals(vol: dict) -> int:
        """Concurrent file heals for this volume (shd-max-threads with
        the reference's fallback ladder) — shared by the spawned shd
        and the mounted-client heal ops so ``heal full`` coalesces the
        same way the daemon does."""
        opts = vol.get("options", {})
        prefix = "disperse." if vol["type"] == "disperse" else "cluster."
        return int(opts.get(prefix + "shd-max-threads",
                            opts.get("cluster.background-self-heal-"
                                     "count",
                                     opts.get("disperse.background-"
                                              "heals", 1))))

    def _spawn_shd(self, vol: dict) -> None:
        """One shd per started heal-capable volume on this node."""
        if vol["type"] not in ("disperse", "replicate"):
            return
        opts = vol.get("options", {})
        gate = "cluster.disperse-self-heal-daemon" \
            if vol["type"] == "disperse" else "cluster.self-heal-daemon"
        if str(opts.get(gate, "on")).lower() in ("off", "false", "no",
                                                 "0", "disable"):
            return  # operator turned the healer off for this volume
        name = vol["name"]
        proc = self.shd.get(name)
        if proc is not None and proc.poll() is None:
            return
        interval = float(opts.get("cluster.heal-timeout", 10))
        prefix = "disperse." if vol["type"] == "disperse" else "cluster."
        max_heals = self._shd_max_heals(vol)
        qlen = int(opts.get(prefix + "shd-wait-qlength",
                            opts.get("cluster.heal-wait-queue-length",
                                     opts.get("disperse.heal-wait-"
                                              "qlength", 1024))))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        statefile = os.path.join(self.workdir, f"shd-{name}.json")
        with open(os.path.join(self.workdir, f"shd-{name}.log"),
                  "ab") as logf:
            self.shd[name] = subprocess.Popen(
                [sys.executable, "-m", "glusterfs_tpu.mgmt.shd",
                 "--glusterd", f"{self.host}:{self.port}",
                 "--volname", name, "--interval", str(interval),
                 "--max-heals", str(max_heals),
                 "--wait-qlength", str(qlen),
                 "--statefile", statefile],
                env=env, stdout=subprocess.DEVNULL, stderr=logf)

    def _kill_shd(self, name: str) -> None:
        proc = self.shd.pop(name, None)
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()


class MgmtClient:
    """Client for the mgmt RPC (CLI + peers + mounts use this)."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self._reader = None
        self._writer = None
        self._xid = 0

    async def __aenter__(self):
        self._reader, self._writer = await asyncio.open_connection(
            self.host, self.port)
        return self

    async def __aexit__(self, *exc):
        if self._writer is not None:
            self._writer.close()
        return False

    async def call(self, method: str, **kwargs) -> Any:
        self._xid += 1
        self._writer.write(wire.pack(self._xid, wire.MT_CALL,
                                     [method, kwargs]))
        await self._writer.drain()
        rec = await wire.read_frame(self._reader)
        _, mtype, payload = wire.unpack(rec)
        if mtype == wire.MT_ERROR:
            raise payload if isinstance(payload, FopError) else \
                MgmtError(str(payload))
        return payload


async def _watch_volfile(client, host: str, port: int,
                         volname: str) -> None:
    """Hold a subscribed mgmt connection and re-fetch + apply the
    volfile on change pushes (glusterfsd-mgmt.c fetch-spec callback)."""
    while True:
        try:
            reader, writer = await asyncio.open_connection(host, port)
            try:
                writer.write(wire.pack(1, wire.MT_CALL,
                                       ["subscribe", {"name": volname}]))
                await writer.drain()
                await wire.read_frame(reader)  # subscribe ack
                while True:
                    rec = await wire.read_frame(reader)
                    _, mtype, payload = wire.unpack(rec)
                    if mtype == wire.MT_EVENT and isinstance(payload, dict) \
                            and payload.get("event") == "volfile-modified":
                        async with MgmtClient(host, port) as c:
                            spec = await c.call("getspec", name=volname)
                        how = await client.reload(spec["volfile"])
                        log.info(12, "volfile for %s applied live (%s)",
                                 volname, how)
            finally:
                writer.close()
        except asyncio.CancelledError:
            return
        except Exception as e:
            log.debug(13, "volfile watcher retry (%r)", e)
            await asyncio.sleep(1.0)


async def mount_volume(host: str, port: int, volname: str,
                       origin: str = ""):
    """Fetch the client volfile from glusterd and build a mounted client
    (the glfs_set_volfile_server + GETSPEC path, api/src/glfs-mgmt.c).
    The mount stays subscribed to volfile changes and applies them live
    (reconfigure or graph swap).  ``origin`` attributes the mount's
    traffic to the bricks' QoS plane ("rebalance" rides the paced
    lane) — set here, BEFORE mount, so the very first handshake
    carries it and every reconnect/graph-swap re-carries it."""
    from ..api.glfs import Client, wait_connected
    from ..core.graph import Graph

    async with MgmtClient(host, port) as c:
        spec = await c.call("getspec", name=volname)
    graph = Graph.construct(spec["volfile"])
    client = Client(graph)
    if origin:
        client.traffic_origin = origin
    await client.mount()
    await wait_connected(graph)
    client.watchers.append(
        asyncio.create_task(_watch_volfile(client, host, port, volname)))
    return client


def main(argv=None) -> int:
    pin_cpu()
    p = argparse.ArgumentParser(prog="gftpu-glusterd")
    p.add_argument("--workdir", required=True)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--listen", type=int, default=24007)
    p.add_argument("--portfile", default="")
    args = p.parse_args(argv)

    async def run():
        from ..core import flight, history
        from ..core.metrics import register_build_info

        flight.set_role("glusterd")
        register_build_info("glusterd")
        history.arm()
        d = Glusterd(args.workdir, args.host, args.listen)
        await d.start()
        if args.portfile:
            with open(args.portfile + ".tmp", "w") as f:
                f.write(str(d.port))
            os.replace(args.portfile + ".tmp", args.portfile)
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        await stop.wait()
        await d.stop()

    asyncio.run(run())
    return 0


if __name__ == "__main__":
    sys.exit(main())
