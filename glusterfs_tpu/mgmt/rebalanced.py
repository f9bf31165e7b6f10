"""Rebalance daemon — the per-volume rebalance process analog.

Reference: rebalance runs as a glusterd-managed glusterfs process per
volume (``gluster volume rebalance <v> start`` spawns it with the
client graph; xlators/cluster/dht/src/dht-rebalance.c gf_defrag_start
drives the two phases, glusterd-rebalance.c owns the lifecycle).  The
old in-process ``DistributeLayer.rebalance()`` walk had no owner, no
persistence and no status story; this daemon is the managed form:

* **Private client graph**: the daemon mounts the volume through
  glusterd's GETSPEC like shd/gsyncd — migration I/O rides the full
  wire stack and live ``volume set`` retunes it (the volfile watcher
  reconfigures the mounted graph, so ``cluster.rebal-throttle``
  changes apply to a RUNNING rebalance between waves).
* **Two phases** (gf_defrag_cmd): *fix-layout* stamps a fresh
  commit-hash layout generation over every directory
  (``DistributeLayer.fix_layout_dir``), then *migrate* walks files and
  moves each to its new hashed subvolume via the torn-read-safe
  temp + compound-chain copy + rename commit in
  ``DistributeLayer._migrate_file``.
* **Resumable checkpoints**: the walk is a canonical preorder DFS with
  sorted children, so directory paths are totally ordered; the
  checkpoint is the LAST COMPLETED DIRECTORY plus the per-phase
  counters, pushed into the volinfo through glusterd's
  ``rebalance-update`` RPC.  SIGKILL + respawn CONTINUES from the
  checkpoint — directories at or before it are skipped (their files
  already sit on their hashed subvolume; migration is idempotent
  anyway), counters carry over, and the status records
  ``resumed_from`` so the operator can see it resumed rather than
  restarted.
* **Throttle**: ``cluster.rebal-throttle`` lazy/normal/aggressive maps
  onto concurrent migrations + a cooperative yield exactly like the
  in-process walk (dht-rebalance.c:3269 migrator thread scaling), read
  per wave so a live retune applies mid-run.
* **Drain mode**: ``remove-brick start`` rides the same daemon with
  ``--mode drain`` — decommissioned children are already excluded from
  the layout, so the same misplaced-file walk empties them, and shrink
  gets status/stop/checkpoints for free.
* **Attribution**: every EC layer in the private graph is tagged
  ``traffic_origin = "rebalance"`` so codec batches, mesh launches and
  the gftpu_mesh_* families attribute migration traffic (the PR-8 heal
  precedent); migration cleanup unlinks carry the internal-op xdata
  flag so features/trash never holds rebalance garbage.
* **Observability**: ``gftpu_rebalance_{files,bytes,failures}_total``
  + ``gftpu_rebalance_phase`` registry families over the live
  Rebalancer set, REBALANCE_FILE_FAILED / REBALANCE_COMPLETE events,
  and a statusfile snapshot for the node-local status fan-out.
"""

from __future__ import annotations

import argparse
import asyncio
import errno
import json
import os
import signal
import sys
import time

from .. import pin_cpu
from ..core import gflog
from ..core.events import gf_event
from ..core.fops import FopError
from ..core.iatt import IAType
from ..core.layer import Loc, walk
from ..core.metrics import REGISTRY
from .svcutil import ThrottleWave

log = gflog.get_logger("rebalanced")

PHASES = ("idle", "fix-layout", "migrate", "done")

#: gftpu_rebalance_phase gauge values (idle=0 .. done=3)
PHASE_GAUGE = {p: i for i, p in enumerate(PHASES)}

_COUNTERS = ("scanned", "moved", "skipped", "failed", "bytes_moved",
             "dirs_fixed", "dirs_walked", "dirs_vanished",
             "temps_swept")


def _samples_files(r: "Rebalancer"):
    for result in ("moved", "skipped", "failed"):
        yield ({"volume": r.volume, "result": result},
               r.counters[result])


_LIVE = REGISTRY.register_objects(
    "gftpu_rebalance_files_total", "counter",
    "files handled by the rebalance walk by result "
    "(moved / skipped / failed)", _samples_files)
REGISTRY.register_objects(
    "gftpu_rebalance_bytes_total", "counter",
    "bytes migrated to their new hashed subvolume",
    lambda r: [({"volume": r.volume}, r.counters["bytes_moved"])],
    live=_LIVE)
REGISTRY.register_objects(
    "gftpu_rebalance_failures_total", "counter",
    "file migrations that failed (REBALANCE_FILE_FAILED events)",
    lambda r: [({"volume": r.volume}, r.counters["failed"])],
    live=_LIVE)
REGISTRY.register_objects(
    "gftpu_rebalance_phase", "gauge",
    "rebalance phase (0 idle, 1 fix-layout, 2 migrate, 3 done)",
    lambda r: [({"volume": r.volume}, PHASE_GAUGE.get(r.phase, 0))],
    live=_LIVE)


def tag_rebalance_origin(graph) -> int:
    """Tag every origin-aware layer of a (private) client graph so its
    codec traffic is attributed ``origin="rebalance"`` on the batch /
    mesh families — the daemon owns the whole graph, so everything it
    pushes through it IS migration traffic.  Re-applied after live
    graph swaps (a volfile change mid-rebalance builds fresh layers).
    Returns how many layers were tagged."""
    n = 0
    for layer in walk(graph.top):
        if hasattr(layer, "traffic_origin"):
            layer.traffic_origin = "rebalance"
            n += 1
    return n


class RebalanceStopped(Exception):
    """Cooperative stop (SIGTERM / ``volume rebalance stop``)."""


class MgmtLink:
    """Persistent mgmt connection with rate-limited reconnect — the
    PR-11 deferred item: checkpoint pushes must survive a glusterd
    restart without hammering a dead endpoint.

    One TCP connection is held across pushes (a multi-hour migration
    making thousands of rate-limited status pushes should not pay a
    connect per push).  A call failing with a TRANSPORT error (the
    glusterd behind it restarted) drops the connection, reconnects,
    and replays that one call — rebalance-update is a state push and
    the checkpoint never regresses, so replay is idempotent.
    Reconnect attempts are rate-limited to one per
    ``rebalance.checkpoint-interval``: while glusterd stays down, at
    most one dial per checkpoint beat fails fast and the push is
    dropped (the statusfile still carries the state; the next push
    retries).  App-level :class:`MgmtError` is NEVER retried — the
    call reached a live glusterd and was refused."""

    _TRANSPORT_ERRORS = (ConnectionError, OSError, EOFError,
                         asyncio.IncompleteReadError)

    def __init__(self, host: str, port: int,
                 min_reconnect_s: float = 1.0):
        self.host, self.port = host, port
        self.min_reconnect_s = float(min_reconnect_s)
        self._client = None
        self._last_attempt = float("-inf")  # first dial never limited

    async def _drop(self) -> None:
        if self._client is not None:
            try:
                await self._client.__aexit__(None, None, None)
            except Exception:  # noqa: BLE001 - already torn
                pass
            self._client = None

    async def _reconnect(self) -> None:
        now = time.monotonic()
        if now - self._last_attempt < self.min_reconnect_s:
            raise ConnectionError(
                f"mgmt reconnect rate-limited "
                f"({self.min_reconnect_s:.1f}s per attempt)")
        self._last_attempt = now
        from .glusterd import MgmtClient

        c = MgmtClient(self.host, self.port)
        await c.__aenter__()
        self._client = c
        # only FAILED dials arm the limiter: after a long-lived healthy
        # connection dies (glusterd restart) the first reconnect must
        # not be charged for the dial that opened it hours ago
        self._last_attempt = float("-inf")

    async def call(self, method: str, **kw):
        if self._client is None:
            await self._reconnect()
        try:
            return await self._client.call(method, **kw)
        except self._TRANSPORT_ERRORS:
            # glusterd restarted under the held connection: one
            # (rate-limited) reconnect, one replay
            await self._drop()
            await self._reconnect()
            return await self._client.call(method, **kw)

    async def close(self) -> None:
        await self._drop()


class Rebalancer:
    """One rebalance run over a mounted client graph.

    The walk is a preorder DFS with children visited in sorted order,
    which makes directory paths totally ordered (parent before child,
    siblings lexicographic) — the property the checkpoint depends on:
    every directory at or before ``last_dir`` in that order is done.
    """

    def __init__(self, client, volume: str, mode: str = "full",
                 checkpoint: dict | None = None,
                 on_checkpoint=None, checkpoint_interval: float = 1.0):
        self.client = client
        self.volume = volume
        self.mode = mode  # full | fix-layout | drain
        self.on_checkpoint = on_checkpoint  # async callback(info dict)
        self.checkpoint_interval = max(0.02, float(checkpoint_interval))
        self.phase = "idle"
        self.counters = {k: 0 for k in _COUNTERS}
        self.note = ""
        self.resumed_from: dict | None = None
        self._resume = dict(checkpoint or {})
        if self._resume.get("counters"):
            self.counters.update({
                k: int(v) for k, v in self._resume["counters"].items()
                if k in self.counters})
            self.resumed_from = {
                "phase": self._resume.get("phase"),
                "last_dir": self._resume.get("last_dir")}
        self.last_dir: str | None = None
        self._last_push = 0.0
        self._stop = False
        self._sweep_temps = True  # main migrate pass only, not settle
        self._tagged_graph = None  # last graph object tag_* walked
        self.throttle = ""
        self.max_inflight = 0
        #: active walk seconds per phase (settle-pass migrate walks
        #: accumulate; the LAYOUT_TTL settle SLEEPS do not) — the
        #: honest denominator for a migration rate
        self.phase_seconds: dict[str, float] = {}
        _LIVE.add(self)

    # -- walk-order math ---------------------------------------------------

    @staticmethod
    def dir_key(path: str) -> tuple:
        """Canonical preorder position of a directory path: its
        component tuple.  Preorder DFS with sorted children emits
        paths exactly in this tuple order ('/a' < '/a/b' < '/a/c' <
        '/b'), so 'done before the checkpoint' is a plain tuple
        comparison."""
        return tuple(p for p in path.split("/") if p)

    def _done_before_resume(self, phase: str, path: str) -> bool:
        """Was ``path`` completed before the checkpoint this run
        resumed from?  Only directories of the checkpointed phase are
        skippable; a checkpoint taken in the migrate phase means the
        whole fix-layout phase finished earlier."""
        ck_phase = self._resume.get("phase")
        last = self._resume.get("last_dir")
        if ck_phase is None or last is None:
            return False
        if phase == "fix-layout" and ck_phase == "migrate":
            return True  # fix-layout completed before migrate began
        if phase != ck_phase:
            return False
        return self.dir_key(path) <= self.dir_key(last)

    # -- status / checkpoint -----------------------------------------------

    @classmethod
    def _ck_pos(cls, phase: str | None, last_dir: str | None) -> tuple:
        """Total order of checkpoint positions: phase first, then the
        walk order of the last completed directory."""
        try:
            pi = PHASES.index(phase)
        except ValueError:
            pi = 0
        return (pi, cls.dir_key(last_dir) if last_dir else ())

    def checkpoint(self) -> dict:
        ck = {"phase": self.phase, "last_dir": self.last_dir,
              "counters": dict(self.counters)}
        # never REGRESS the persisted checkpoint: a resumed run pushes
        # status while it is still catching up (the skipped fix-layout
        # phase ends with last_dir=None, the resumed migrate phase
        # starts behind the marker) — overwriting the volinfo with an
        # earlier position would make a SECOND kill restart the walk
        if self._resume.get("phase") and \
                self._ck_pos(self.phase, self.last_dir) < \
                self._ck_pos(self._resume.get("phase"),
                             self._resume.get("last_dir")):
            ck["phase"] = self._resume["phase"]
            ck["last_dir"] = self._resume.get("last_dir")
        return ck

    def status(self) -> dict:
        out = {"mode": self.mode, "phase": self.phase,
               "counters": dict(self.counters),
               "checkpoint": self.checkpoint(),
               "throttle": self.throttle,
               "max_inflight": self.max_inflight,
               "phase_seconds": {k: round(v, 3) for k, v
                                 in self.phase_seconds.items()}}
        if self.note:
            out["note"] = self.note
        if self.resumed_from:
            out["resumed_from"] = self.resumed_from
        return out

    async def _push(self, force: bool = False) -> None:
        now = time.monotonic()
        if not force and now - self._last_push < self.checkpoint_interval:
            return
        self._last_push = now
        if self.on_checkpoint is not None:
            try:
                await self.on_checkpoint(self.status())
            except Exception as e:  # a mgmt hiccup must not kill the run
                log.warning(3, "checkpoint push failed: %r", e)

    def stop(self) -> None:
        self._stop = True

    # -- graph plumbing ----------------------------------------------------

    def _dht(self):
        from ..cluster.dht import DistributeLayer

        return next((l for l in self.client.graph.by_name.values()
                     if isinstance(l, DistributeLayer)), None)

    # -- phases ------------------------------------------------------------

    async def run(self) -> dict:
        from ..cluster.dht import LAYOUT_TTL

        dht = self._dht()
        if dht is None:
            # single-subvolume volume: nothing to place differently
            self.phase = "done"
            self.note = "volume has a single subvolume; nothing to " \
                        "rebalance"
            await self._push(force=True)
            return self.status()
        try:
            if self.mode != "drain":
                # drain keeps the persisted layouts: decommissioned
                # children are routed around by placement, and a
                # remove-brick stop must be able to fall back to them
                await self._phase("fix-layout", self._fix_dir)
            hazard_end = time.monotonic() + LAYOUT_TTL
            if self.mode != "fix-layout":
                await self._phase("migrate", self._migrate_dir)
                # the checkpoint this run resumed from is consumed;
                # settle passes below must re-walk everything — and
                # they must not repeat the per-child temp sweep the
                # main pass just finished
                self._resume = {}
                self._sweep_temps = False
                await self._settle(hazard_end)
            self.phase = "done"
        finally:
            await self._push(force=True)
        return self.status()

    async def _settle(self, hazard_end: float) -> None:
        """Converge the races the main pass cannot see coming: a
        serving client whose cached parent layout was read up to
        LAYOUT_TTL before fix-layout stamped fresh ranges keeps
        creating files at the OLD range owner — misplaced, with no
        linkto — until its cache expires.  Any such file behind the
        walk is missed by the main pass, so re-walk until a pass that
        STARTED after every stale cache died moves (and fails) nothing.
        Each extra pass is readdir + placement checks when there is
        nothing left to move."""
        for _ in range(8):
            await asyncio.sleep(max(0.0, hazard_end - time.monotonic()))
            before = self.counters["moved"] + self.counters["failed"]
            started = time.monotonic()
            await self._phase("migrate", self._migrate_dir)
            if started >= hazard_end and \
                    self.counters["moved"] + self.counters["failed"] \
                    == before:
                return
        self.note = "settle passes exhausted; namespace still churning"

    async def _phase(self, phase: str, work) -> None:
        self.phase = phase
        self.last_dir = self._resume.get("last_dir") \
            if self._resume.get("phase") == phase else None
        weights = None
        if phase == "fix-layout":
            dht = self._dht()
            if dht.opts["weighted-rebalance"]:
                weights = await dht._capacity_weights()

        async def rec(path: str) -> None:
            if self._stop:
                raise RebalanceStopped()
            # a live volfile swap builds fresh layers: keep them
            # tagged.  Same-graph reconfigures keep the layer objects
            # (tags survive), so only a SWAPPED graph object needs the
            # re-walk — per directory, identity is all that's checked
            graph = self.client.graph
            if graph is not self._tagged_graph:
                tag_rebalance_origin(graph)
                self._tagged_graph = graph
            try:
                if self._done_before_resume(phase, path):
                    subdirs = await self._list_subdirs(path)
                else:
                    subdirs = await work(path, weights)
                    self.counters["dirs_walked"] += 1
                    self.last_dir = path
                    await self._push()
            except FopError as e:
                if path != "/" and e.err in (errno.ENOENT,
                                             errno.ESTALE):
                    # a serving client rmdir'd it between the parent
                    # listing and this descent: skip the subtree — a
                    # multi-hour run must not fail over one vanished
                    # directory
                    self.counters["dirs_vanished"] += 1
                    return
                raise
            for name in sorted(subdirs):
                await rec(path.rstrip("/") + "/" + name)

        t0 = time.monotonic()
        try:
            await rec("/")
        finally:
            self.phase_seconds[phase] = round(
                self.phase_seconds.get(phase, 0.0)
                + time.monotonic() - t0, 3)
        await self._push(force=True)

    async def _list_subdirs(self, path: str) -> list[str]:
        """Subdirectory names only — the checkpoint-skip descent path.
        readdirP: plain readdir entries may carry no iatt, and a
        skipped directory whose children went unlisted would silently
        truncate the resumed walk."""
        dht = self._dht()
        fd = await dht.opendir(Loc(path))
        try:
            entries = await dht.readdirp(fd)
        finally:
            await dht.release(fd)
        return [name for name, ia in entries
                if ia is not None and ia.ia_type is IAType.DIR]

    async def _fix_dir(self, path: str, weights) -> list[str]:
        dht = self._dht()
        subdirs = await dht.fix_layout_dir(path, weights)
        self.counters["dirs_fixed"] += 1
        return subdirs

    async def _migrate_dir(self, path: str, _weights) -> list[str]:
        """Migrate every misplaced file of ONE directory,
        ``cluster.rebal-throttle`` wide; returns the subdirectories.
        The throttle is re-read per wave so ``volume set`` retunes a
        running migration (the reference's defrag throttle reconf)."""
        dht = self._dht()
        if self._sweep_temps:
            # a predecessor SIGKILLed mid-copy left hidden
            # reserved-suffix temps behind; they are filtered from
            # every listing, so only this walk can reclaim them.
            # EVERY main pass sweeps — a fresh (checkpoint-free) run
            # may still follow a crashed one whose checkpoint was
            # dropped (topology change, `rebalance stop` before the
            # restart), and a skipped sweep would leak the hidden
            # bytes forever.  The flag is cleared before the settle
            # re-walks so they don't repeat the per-child listings
            # after the main pass already reclaimed everything
            await self._sweep_orphan_temps(dht, path)
        fd = await dht.opendir(Loc(path))
        try:
            entries = await dht.readdir(fd)
        finally:
            await dht.release(fd)
        subdirs: list[str] = []
        wave = ThrottleWave()
        for name, ia in entries:
            if ia is not None and ia.ia_type is IAType.DIR:
                subdirs.append(name)
                continue
            if self._stop:
                break
            child = path.rstrip("/") + "/" + name
            cloc = Loc(child)
            try:
                # direct everywhere-scan, NOT _cached_idx: a file
                # created through a stale parent layout is misplaced
                # with no linkto, and the pruned path would
                # lookup-optimize it into ENOENT — the walk must see
                # exactly the files serving clients cannot
                idx, fia = await dht._locate_real(cloc)
                if fia.ia_type is IAType.DIR:
                    subdirs.append(name)
                    continue
                self.counters["scanned"] += 1
                hi = await dht._placed(cloc)
            except FopError:
                continue  # vanished mid-walk (a serving unlink)
            if hi == idx:
                self.counters["skipped"] += 1
                continue
            throttle = str(dht.opts["rebal-throttle"])
            self.throttle = throttle
            width, pause = dht._THROTTLE[throttle]
            await wave.admit(
                self._migrate_one(dht, child, cloc, fia, idx, hi),
                width, pause)
            self.max_inflight = max(self.max_inflight,
                                    wave.max_inflight)
        await wave.drain()
        if self._stop:
            raise RebalanceStopped()
        return subdirs

    async def _sweep_orphan_temps(self, dht, path: str) -> None:
        """Reclaim crash-orphaned migration temps in ``path``.  A
        daemon killed between temp create and the rename commit leaves
        `.NAME.rebalance~` on a destination child holding up to the
        whole file's bytes; dht filters the suffix from every listing,
        so nothing else can even see it.  Runs on the main migrate
        pass (resumed or fresh — a fresh run may follow a crashed one
        whose checkpoint was abandoned), per directory BEFORE that
        directory's migrations launch — the daemon is the volume's
        only migrator, so any temp standing at that point is garbage
        (a re-migrated file re-creates its temp from scratch
        anyway)."""
        from ..features.trash import INTERNAL_OP

        for child in dht.children:
            try:
                fd = await child.opendir(Loc(path))
                try:
                    entries = await child.readdir(fd)
                finally:
                    await child.release(fd)
            except FopError:
                continue  # dir absent on this child
            for name, _ia in entries:
                if not name.endswith(dht.MIGRATE_SUFFIX):
                    continue
                tmp = Loc(path.rstrip("/") + "/" + name)
                try:
                    await child.unlink(tmp, {INTERNAL_OP: True})
                    self.counters["temps_swept"] += 1
                    log.warning(4, "reclaimed orphan temp %s", tmp.path)
                except FopError:
                    pass

    async def _migrate_one(self, dht, child: str, cloc: Loc, ia,
                           idx: int, hi: int) -> None:
        try:
            nbytes = await dht._migrate_file(cloc, ia, idx, hi)
        except Exception as e:
            # ANY escape counts as failed — an uncounted exception
            # would report a clean run with the file still misplaced
            self.counters["failed"] += 1
            log.warning(4, "migrate %s failed: %r", child, e)
            gf_event("REBALANCE_FILE_FAILED", volume=self.volume,
                     path=child, error=repr(e)[:200])
            return
        self.counters["moved"] += 1
        self.counters["bytes_moved"] += nbytes


# ---------------------------------------------------------------------------
# daemon entry (glusterd's spawner runs this)
# ---------------------------------------------------------------------------


def _write_statusfile(path: str, info: dict) -> None:
    if not path:
        return
    snap = REGISTRY.snapshot()
    info = dict(info)
    info["pid"] = os.getpid()
    info["families"] = {
        name: snap[name]["samples"] for name in (
            "gftpu_rebalance_files_total",
            "gftpu_rebalance_bytes_total",
            "gftpu_rebalance_failures_total",
            "gftpu_rebalance_phase") if name in snap}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(info, f)
    os.replace(tmp, path)


async def _amain(args) -> int:
    from ..core import flight, history
    from ..core.metrics import register_build_info
    from .glusterd import mount_volume

    flight.set_role("rebalance")
    register_build_info("rebalance")
    history.arm()
    if args.statusfile:
        # incident capture door (no inbound RPC surface): SIGUSR2
        # writes the flight bundle beside the statusfile, where the
        # incident fan-out polls for it
        flight.arm_signal_capture(args.statusfile + ".incident")
    host, _, port = args.glusterd.rpartition(":")
    host, port = host or "127.0.0.1", int(port)
    link = MgmtLink(host, port)
    mgmt_call = link.call

    # the volinfo carries the resume checkpoint + the daemon's knobs
    info = await mgmt_call("volume-info", name=args.volname)
    vol = info[args.volname]
    rb = vol.get("rebalance") or {}
    opts = vol.get("options", {})
    try:
        interval = float(opts.get("rebalance.checkpoint-interval",
                                  args.checkpoint_interval))
    except (TypeError, ValueError):
        # volume-set stores the raw string; a malformed value must not
        # crash-loop every (re)spawn with the record wedged 'started'
        log.warning(2, "bad rebalance.checkpoint-interval %r; using %s",
                    opts.get("rebalance.checkpoint-interval"),
                    args.checkpoint_interval)
        interval = args.checkpoint_interval
    # reconnect attempts ride the same beat as checkpoint pushes: one
    # dial per interval while glusterd is down
    link.min_reconnect_s = max(0.02, interval)
    mode = args.mode or rb.get("mode") or "full"

    client = None
    while client is None:
        try:
            # origin rides the handshake creds (QoS plane): the brick
            # routes this daemon's fops into the paced rebalance lane
            # from the FIRST post-handshake frame
            client = await mount_volume(host, port, args.volname,
                                        origin="rebalance")
        except Exception as e:
            log.warning(2, "rebalanced mount %s failed (%r), retrying",
                        args.volname, e)
            await asyncio.sleep(1.0)
    tag_rebalance_origin(client.graph)

    async def push(status: dict) -> None:
        _write_statusfile(args.statusfile, status)
        await mgmt_call("rebalance-update", name=args.volname,
                        info=status)

    reb = Rebalancer(client, args.volname, mode=mode,
                     checkpoint=rb.get("checkpoint"),
                     on_checkpoint=push, checkpoint_interval=interval)

    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, reb.stop)

    rc = 0
    try:
        final = await reb.run()
        final["status"] = "completed"
        gf_event("REBALANCE_COMPLETE", volume=args.volname,
                 mode=mode, **{k: reb.counters[k] for k in
                               ("scanned", "moved", "failed",
                                "bytes_moved")})
    except RebalanceStopped:
        final = reb.status()
        final["status"] = "stopped"
    except Exception as e:
        log.error(1, "rebalance of %s failed: %r", args.volname, e)
        final = reb.status()
        final["status"] = "failed"
        final["error"] = repr(e)[:300]
        rc = 1
    try:
        _write_statusfile(args.statusfile, final)
        # bounded: on `rebalance stop` the glusterd that SIGTERMed us
        # is blocked reaping this very process, so the push cannot be
        # answered — it harvests the statusfile instead.  An external
        # SIGTERM (operator kill) still lands the push normally.
        await asyncio.wait_for(
            mgmt_call("rebalance-update", name=args.volname,
                      info=final), 2.0)
    except asyncio.TimeoutError:
        log.warning(2, "final rebalance-update timed out "
                       "(statusfile carries the final state)")
    except Exception as e:
        log.error(1, "final rebalance-update failed: %r", e)
        rc = rc or 1
    await link.close()
    await client.unmount()
    return rc


def main(argv=None) -> int:
    pin_cpu()
    p = argparse.ArgumentParser(prog="gftpu-rebalanced")
    p.add_argument("--glusterd", required=True, help="host:port")
    p.add_argument("--volname", required=True)
    p.add_argument("--mode", default="",
                   choices=("", "full", "fix-layout", "drain"))
    p.add_argument("--statusfile", default="")
    p.add_argument("--checkpoint-interval", type=float, default=1.0)
    args = p.parse_args(argv)
    return asyncio.run(_amain(args))


if __name__ == "__main__":
    sys.exit(main())
