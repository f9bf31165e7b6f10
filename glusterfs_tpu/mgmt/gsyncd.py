"""Geo-replication monitor + per-brick workers — the gsyncd analog.

Reference: geo-replication/syncdaemon (monitor.py:63-85 Monitor spawns
and supervises one gsyncd worker PER BRICK, respawning on death;
monitor.py:299 distribute() maps bricks to workers with Active/Passive
election inside each replica set; primary.py:90-135 crawl/changelog
consumption; resource.py rsync/tar transport).

TPU-build shape: one monitor process per node per (primary volume ->
secondary volume) link.  The monitor runs one worker per LOCAL brick
of the primary volume; each worker tails ITS brick's journal segments
by (segment, offset) cursor (features/changelog.py) with its own
persisted state, coalesces the batch (one data-sync per path — the
copy reads the CURRENT primary state through the mounted client, so
intermediate writes are free), replays entry ops in order, and
persists cursors only after a fully-applied batch — replay is
idempotent, so re-applying after a crash converges.

Supervision model (monitor.py:63-85): a worker that dies is respawned
with exponential backoff and its status surfaces per worker — one
wedged brick's worker never stalls the other bricks' replication.
Election (monitor.py:299): replica/disperse bricks journal the same
logical ops, so only ONE worker per subvolume group is Active; the
monitor polls brick liveness through glusterd and fails over to a
peer brick's worker when the active brick dies.
"""

from __future__ import annotations

import argparse
import asyncio
import errno
import json
import os
import signal
import sys

from .. import pin_cpu
from ..core.fops import FopError
from ..core import gflog

log = gflog.get_logger("gsyncd")

COPY_WINDOW = 1 << 20


class GeoRepWorker:
    def __init__(self, primary, secondary, changelog_dirs: list[str],
                 state_path: str, interval: float = 5.0,
                 floor=None):
        self.primary = primary      # mounted Client on the primary vol
        self.secondary = secondary  # mounted Client on the secondary vol
        self.dirs = changelog_dirs
        self.state_path = state_path
        self.interval = interval
        # failover fast-forward: records at or before the session's
        # synced_through AT PROMOTION TIME were already replayed by a
        # peer brick's worker (the reference tracks the equivalent
        # stime xattr) — skip them instead of re-replaying a whole
        # journal history.  Snapshotted ONCE: a live floor would race
        # the idle-tick synced_through stamp against records whose
        # journal line lands after the scan that stamped it, silently
        # dropping them from the active worker's own stream.
        self._floor_ts = float(floor() if callable(floor) else 0.0)
        self.state = self._load_state()
        self.synced = 0
        self.batches = 0
        self._task: asyncio.Task | None = None
        # supervised workers (under GeoRepMonitor) die on persistent
        # failure and get respawned with backoff; the legacy standalone
        # worker has NO supervisor, so it must retry forever instead
        self.supervised = False

    # -- checkpoint ---------------------------------------------------------

    def _load_state(self) -> dict:
        try:
            with open(self.state_path) as f:
                return json.load(f)
        except (FileNotFoundError, ValueError):
            return {"cursors": {}, "last_ts": 0.0}

    def _save_state(self) -> None:
        tmp = self.state_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.state, f)
        os.replace(tmp, self.state_path)

    # -- journal tailing ----------------------------------------------------

    def _collect_new(self) -> tuple[list[dict], dict]:
        """Read records past each brick's (segment, offset) cursor.
        Returns (records, advanced-cursor-proposal) WITHOUT touching
        self.state — cursors move only after the batch fully applies,
        so a failed replay is re-read next tick (replay is idempotent)."""
        out: list[dict] = []
        proposal = {d: dict(c)
                    for d, c in self.state["cursors"].items()}
        for d in self.dirs:
            cur = proposal.setdefault(d, {})
            try:
                segs = sorted(int(n.rsplit(".", 1)[1])
                              for n in os.listdir(d)
                              if n.startswith("CHANGELOG."))
            except OSError:
                continue
            for seq in segs:
                if seq < cur.get("segment", 0):
                    continue
                off = cur.get("offset", 0) \
                    if seq == cur.get("segment", 0) else 0
                path = os.path.join(d, f"CHANGELOG.{seq}")
                try:
                    with open(path) as f:
                        f.seek(off)
                        data = f.read()
                except OSError:
                    continue
                # consume only complete lines (a record may be mid-write)
                complete = data.rfind("\n") + 1
                floor_ts = self._floor_ts
                for line in data[:complete].splitlines():
                    try:
                        r = json.loads(line)
                    except ValueError:
                        continue
                    if r.get("ts", 0) > floor_ts:
                        out.append(r)
                cur["segment"] = seq
                cur["offset"] = off + complete
        out.sort(key=lambda r: r.get("ts", 0))
        return out, proposal

    # -- replay -------------------------------------------------------------

    async def _copy_file(self, path: str, strict: bool = False) -> bool:
        """Sync the CURRENT primary state of path to the secondary.

        ``strict`` is the initial-crawl mode: pre-session data has no
        journal records, so a transient primary-side failure (ENOTCONN,
        EIO) must re-raise and retry the walk — only a genuinely
        vanished entry may be skipped.  Journal replay passes False:
        there a vanished source is benign because a later record
        covers the final state."""
        _gone = (errno.ENOENT, errno.ESTALE)
        try:
            ia = await self.primary.stat(path)
        except FopError as e:
            if strict and e.err not in _gone:
                raise
            return False  # vanished since the record; a later E handles it
        try:
            f_in = await self.primary.open(path)
        except FopError as e:
            if strict and e.err not in _gone:
                raise
            return False  # vanished on primary: benign
        try:
            try:
                f_out = await self.secondary.create(path)
            except FopError as e:
                if e.err != errno.EEXIST:
                    raise  # secondary trouble is a REAL failure: retry batch
                f_out = await self.secondary.open(path, os.O_RDWR)
            try:
                off = 0
                while off < ia.size:
                    chunk = await f_in.read(
                        min(COPY_WINDOW, ia.size - off), off)
                    if not chunk:
                        break
                    await f_out.write(chunk, off)
                    off += len(chunk)
                await self.secondary.truncate(path, ia.size)
            finally:
                await f_out.close()
        finally:
            await f_in.close()
        return True

    async def _ensure_parents(self, path: str) -> None:
        parts = [p for p in path.strip("/").split("/")[:-1] if p]
        cur = ""
        for p in parts:
            cur += "/" + p
            try:
                await self.secondary.mkdir(cur)
            except FopError:
                pass

    async def _replay(self, rec: dict) -> bool:
        """Apply one record to the secondary; False = hard failure (the
        caller must NOT advance the cursors; the batch re-applies next
        tick)."""
        op, path = rec.get("op", ""), rec.get("path", "")
        if not path:
            return True
        try:
            if op in ("unlink",):
                try:
                    await self.secondary.unlink(path)
                except FopError as e:
                    if e.err != errno.ENOENT:
                        raise
            elif op == "rmdir":
                try:
                    await self.secondary.rmdir(path)
                except FopError as e:
                    if e.err not in (errno.ENOENT, errno.ENOTEMPTY):
                        raise
            elif op == "mkdir":
                await self._ensure_parents(path)
                try:
                    await self.secondary.mkdir(path)
                except FopError as e:
                    if e.err != errno.EEXIST:
                        raise
            elif op == "rename":
                dst = rec.get("path2", "")
                if dst:
                    await self._ensure_parents(dst)
                    try:
                        await self.secondary.rename(path, dst)
                    except FopError:
                        # source absent on secondary: materialize dst
                        await self._copy_file(dst)
                    try:
                        await self.secondary.unlink(path)
                    except FopError:
                        pass
            elif op == "link":
                dst = rec.get("path2", "")
                if dst:
                    await self._ensure_parents(dst)
                    try:
                        await self.secondary.link(path, dst)
                    except FopError:
                        # source missing on secondary: materialize dst
                        await self._copy_file(dst)
            elif op == "symlink":
                try:
                    target = await self.primary.readlink(path)
                    await self._ensure_parents(path)
                    await self.secondary.symlink(target, path)
                except FopError:
                    pass
            elif rec.get("type") in ("D", "E"):
                # create/write/truncate/...: sync current file state
                await self._ensure_parents(path)
                if await self._copy_file(path):
                    self.synced += 1
            elif rec.get("type") == "M":
                try:
                    ia = await self.primary.stat(path)
                    await self.secondary.setattr(
                        path, {"mode": ia.mode & 0o7777})
                except FopError:
                    pass
        except FopError as e:
            log.warning(1, "replay %s %s failed: %s", op, path, e)
            return False
        return True

    _SYNC_OPS = {"create", "icreate", "put"}

    @classmethod
    def _is_sync(cls, r: dict) -> bool:
        """Records whose replay is 'copy current file state'."""
        return r.get("type") == "D" or r.get("op") in cls._SYNC_OPS

    @classmethod
    def _coalesce(cls, recs: list[dict]) -> list[dict]:
        """One data-sync per path per batch: create + N writev records
        collapse to the LAST such record (the copy reads the current
        primary state anyway)."""
        last: dict[str, int] = {}
        for i, r in enumerate(recs):
            if cls._is_sync(r):
                last[r.get("path", "")] = i
        return [r for i, r in enumerate(recs)
                if not cls._is_sync(r) or last.get(r.get("path", "")) == i]

    async def process_once(self) -> int:
        import time as _t

        # stamp BEFORE the scan: a record journaled between scan and
        # stamp must not fall inside a "synced through" window
        scan_started = _t.time()
        recs, proposal = self._collect_new()
        if not recs:
            # caught up THROUGH the scan start: checkpoint completion
            # must not wait for new traffic on an idle session
            # (gsyncdstatus checkpoint semantics)
            self.state["synced_through"] = scan_started
            self._save_state()
            return 0
        batch = self._coalesce(recs)
        ok = True
        for rec in batch:
            ok = await self._replay(rec) and ok
        if not ok:
            # leave the cursors where they were: the whole batch is
            # re-read and re-applied (idempotently) next tick
            return 0
        self.state["cursors"] = proposal
        self.state["last_ts"] = recs[-1].get("ts", 0)
        self.state["synced_through"] = self.state["last_ts"]
        self.batches += 1
        self._save_state()
        self._prune_consumed()
        return len(batch)

    def _prune_consumed(self) -> None:
        """Delete journal segments fully behind the persisted cursor —
        the consumed changelog would otherwise grow without bound (the
        reference archives processed changelogs the same way)."""
        for d, cur in self.state["cursors"].items():
            current = cur.get("segment", 0)
            try:
                names = os.listdir(d)
            except OSError:
                continue
            for n in names:
                if not n.startswith("CHANGELOG."):
                    continue
                try:
                    seq = int(n.rsplit(".", 1)[1])
                except ValueError:
                    continue
                if seq < current:
                    try:
                        os.unlink(os.path.join(d, n))
                    except OSError:
                        pass

    async def initial_crawl(self) -> int:
        """Hybrid/xsync crawl (reference primary.py XCrawlMixin): data
        written BEFORE the session existed has no journal records —
        walk the primary namespace once and materialize everything on
        the secondary, then hand over to changelog tailing.  Runs
        before the first journal batch; idempotent (copy reads current
        primary state), so a crash mid-crawl just re-walks."""
        from ..core.iatt import IAType

        synced = 0

        # pre-session data has NO journal records, so a transiently
        # failing secondary op here (ENOTCONN, EIO) loses the entry
        # forever if swallowed — only the benign races (entry already
        # there / vanished under live churn) may pass; everything else
        # re-raises so run() retries the walk, same as the listdir path.
        _benign = (errno.EEXIST, errno.ENOENT, errno.ESTALE)

        async def meta(child: str, ia) -> None:
            # pre-session data has no 'M' journal records: carry
            # mode/ownership in the crawl itself
            try:
                await self.secondary.setattr(
                    child, {"mode": ia.mode & 0o7777,
                            "uid": ia.uid, "gid": ia.gid})
            except FopError as e:
                if e.err not in _benign:
                    raise

        async def walk(path: str) -> int:
            n = 0
            try:
                entries = await self.primary.listdir_with_stat(path)
            except FopError as e:
                if e.err in (errno.ENOENT, errno.ESTALE):
                    # directory vanished mid-crawl (live churn): its
                    # removal IS journaled, so skipping is safe
                    return 0
                # transient trouble (ENOTCONN, EIO): pre-session data
                # has NO journal records — finishing the crawl now
                # would mark initial_done with this subtree missing
                # forever; re-raise so run() retries the whole walk
                raise
            for name, ia in entries:
                child = path.rstrip("/") + "/" + name
                if ia is not None and ia.is_dir():
                    try:
                        await self.secondary.mkdir(child)
                    except FopError as e:
                        if e.err not in _benign:
                            raise
                    await meta(child, ia)
                    n += await walk(child)
                elif ia is not None and ia.ia_type is IAType.LNK:
                    # symlinks must stay symlinks (journal replay's
                    # op=='symlink' path does the same)
                    try:
                        target = await self.primary.readlink(child)
                        await self.secondary.symlink(target, child)
                        n += 1
                    except FopError as e:
                        if e.err not in _benign:
                            raise
                else:
                    if await self._copy_file(child, strict=True):
                        if ia is not None:
                            await meta(child, ia)
                        n += 1
            return n

        synced = await walk("/")
        self.state["initial_done"] = True
        self._save_state()
        log.info(3, "initial crawl synced %d files", synced)
        return synced

    async def run(self) -> None:
        failures = 0
        while not self.state.get("initial_done"):
            try:
                await self.initial_crawl()
            except Exception as e:
                log.error(4, "initial crawl failed (will retry): %r", e)
                await asyncio.sleep(self.interval)
        while True:
            try:
                await self.process_once()
                failures = 0
            except Exception as e:  # a bad batch must not kill the link
                log.error(2, "gsyncd batch failed: %r", e)
                failures += 1
                if self.supervised and failures >= 3:
                    # persistently failing worker: die and let the
                    # monitor respawn it with backoff (the reference
                    # worker exits on persistent faults the same way,
                    # monitor.py respawn loop); unsupervised legacy
                    # workers have nobody to respawn them — retry on
                    raise
            await asyncio.sleep(self.interval)

    def start(self) -> None:
        self._task = asyncio.get_running_loop().create_task(self.run())

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None

    def status(self) -> dict:
        return {"batches": self.batches, "files_synced": self.synced,
                "last_ts": self.state.get("last_ts", 0)}


class GeoRepMonitor:
    """Per-brick worker supervision + Active/Passive election
    (monitor.py:63-85 spawn/respawn, monitor.py:299 distribute()).

    One worker per local brick of the primary volume, each with its own
    journal cursors and state file.  Replica/disperse bricks journal
    the same logical ops, so per subvolume group exactly one brick's
    worker is ACTIVE (lowest-indexed brick that is online, cluster
    wide); the rest stay Passive.  The monitor polls brick liveness
    through glusterd every tick — when the active brick dies, the next
    online brick's worker takes over, fast-forwarded past everything
    the session already replayed (``floor``).  A worker that exits is
    respawned with exponential backoff and reported Faulty meanwhile.
    """

    BACKOFF0 = 1.0
    BACKOFF_MAX = 30.0

    def __init__(self, primary, secondary, *, glusterd: tuple[str, int],
                 volume: str, bricks: list[dict], group_size: int,
                 state_dir: str, session_state: str,
                 interval: float = 5.0, statusfile: str = ""):
        self.primary = primary
        self.secondary = secondary
        self.glusterd = glusterd
        self.volume = volume
        self.bricks = bricks  # [{name, path, index}] local bricks
        self.group_size = max(1, group_size)
        self.state_dir = state_dir
        self.session_state = session_state
        self.interval = interval
        self.statusfile = statusfile
        self.workers: dict[str, GeoRepWorker] = {}
        self._tasks: dict[str, asyncio.Task] = {}
        self._backoff: dict[str, float] = {}
        self._down_until: dict[str, float] = {}
        self.status: dict[str, dict] = {
            b["name"]: {"state": "Initializing", "restarts": 0}
            for b in bricks}
        self.state = self._load_session()

    # -- session-level state (the gsync-<vol>.state file the status op
    # reads): initial_done + aggregated synced_through ------------------

    def _load_session(self) -> dict:
        try:
            with open(self.session_state) as f:
                return json.load(f)
        except (FileNotFoundError, ValueError):
            return {"initial_done": False, "synced_through": 0.0,
                    "last_ts": 0.0}

    def _save_session(self) -> None:
        tmp = self.session_state + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.state, f)
        os.replace(tmp, self.session_state)

    def floor(self) -> float:
        return self.state.get("synced_through", 0.0)

    # -- liveness -------------------------------------------------------

    async def _volume_bricks(self) -> list[tuple[str, bool]] | None:
        """EVERY brick of the primary volume in index order with its
        online flag, or None when glusterd is unreachable (keep the
        current election).  The election must run over the full
        cluster-wide brick list: a replica group spanning nodes has ONE
        active worker total, not one per node."""
        from .glusterd import MgmtClient

        try:
            async with MgmtClient(*self.glusterd) as c:
                st = await asyncio.wait_for(
                    c.call("volume-status", name=self.volume), 5)
            return [(b["name"], bool(b.get("online")))
                    for b in st.get("bricks", ())]
        except Exception:
            return None

    def _elect(self, allbricks: list[tuple[str, bool]]) -> set[str]:
        """Active brick names cluster-wide: per subvolume group, the
        lowest-indexed ONLINE brick (monitor.py:299 distribute; the
        reference breaks ties by node-uuid — volume brick order is
        already total here).  This monitor then starts only the
        winners that are LOCAL; peers' monitors reach the same answer
        from the same volume-status."""
        active: set[str] = set()
        for g0 in range(0, len(allbricks), self.group_size):
            group = allbricks[g0:g0 + self.group_size]
            alive = [name for name, online in group if online]
            if alive:
                active.add(alive[0])
        return active

    # -- worker lifecycle ----------------------------------------------

    def _worker_for(self, brick: dict) -> GeoRepWorker:
        w = self.workers.get(brick["name"])
        if w is None:
            d = os.path.join(brick["path"], ".glusterfs_tpu",
                             "changelog")
            sp = os.path.join(self.state_dir,
                              f"worker-{brick['name']}.state")
            w = GeoRepWorker(self.primary, self.secondary, [d], sp,
                             self.interval, floor=self.floor)
            w.supervised = True  # monitor respawns on death
            # the monitor ran (or will run) the volume-level initial
            # crawl; per-brick workers only tail journals
            w.state["initial_done"] = True
            self.workers[brick["name"]] = w
        return w

    def _start(self, brick: dict) -> None:
        name = brick["name"]
        t = self._tasks.get(name)
        if t is not None and not t.done():
            return
        now = asyncio.get_running_loop().time()
        if now < self._down_until.get(name, 0):
            return  # still backing off
        w = self._worker_for(brick)
        task = asyncio.get_running_loop().create_task(w.run())

        def died(t: asyncio.Task, _name=name) -> None:
            if t.cancelled():
                return
            st = self.status[_name]
            st["state"] = "Faulty"
            st["restarts"] += 1
            back = min(self._backoff.get(_name, self.BACKOFF0) * 2,
                       self.BACKOFF_MAX)
            self._backoff[_name] = back
            self._down_until[_name] = \
                asyncio.get_running_loop().time() + back
            exc = t.exception()
            log.error(5, "worker %s died (%r); respawn in %.1fs",
                      _name, exc, back)

        task.add_done_callback(died)
        self._tasks[name] = task
        self.status[name]["state"] = "Active"

    async def _stop(self, name: str, state: str) -> None:
        t = self._tasks.pop(name, None)
        if t is not None and not t.done():
            t.cancel()
            try:
                await t
            except (asyncio.CancelledError, Exception):
                pass
        if self.status[name]["state"] != "Faulty" or t is None:
            self.status[name]["state"] = state

    # -- aggregation ----------------------------------------------------

    def _aggregate(self, active: set[str]) -> None:
        """Session synced_through = the slowest ACTIVE worker (every
        group's changes up to that instant are on the secondary)."""
        vals = []
        for name in active:
            w = self.workers.get(name)
            t = self._tasks.get(name)
            if w is None or t is None or t.done():
                return  # a group has no live active worker: no claim
            vals.append(w.state.get("synced_through", 0.0))
        if vals:
            agg = min(vals)
            if agg > self.state.get("synced_through", 0.0):
                self.state["synced_through"] = agg
                self.state["last_ts"] = max(
                    w.state.get("last_ts", 0.0)
                    for w in self.workers.values())
                self._save_session()

    def _write_status(self) -> None:
        if not self.statusfile:
            return
        body = {"pid": os.getpid(),
                "workers": {n: dict(s)
                            for n, s in self.status.items()},
                "synced_through": self.state.get("synced_through", 0.0)}
        tmp = self.statusfile + ".tmp"
        with open(tmp, "w") as f:
            json.dump(body, f)
        os.replace(tmp, self.statusfile)

    async def run(self) -> None:
        # volume-level initial crawl once per session (pre-session data
        # has no journal records anywhere)
        while not self.state.get("initial_done"):
            crawler = GeoRepWorker(self.primary, self.secondary, [],
                                   os.path.join(self.state_dir,
                                                "crawl.state"),
                                   self.interval)
            try:
                await crawler.initial_crawl()
                self.state["initial_done"] = True
                self._save_session()
            except Exception as e:
                log.error(4, "initial crawl failed (will retry): %r", e)
                await asyncio.sleep(self.interval)
        allbricks = [(b["name"], True) for b in self.bricks]
        while True:
            got = await self._volume_bricks()
            if got is not None:
                allbricks = got
            active = self._elect(allbricks)
            online = {n for n, up in allbricks if up}
            for b in self.bricks:
                if b["name"] in active:
                    self._start(b)
                else:
                    await self._stop(
                        b["name"],
                        "Passive" if b["name"] in online else "Offline")
            self._aggregate(active & {b["name"] for b in self.bricks})
            self._write_status()
            await asyncio.sleep(min(self.interval, 1.0))

    async def stop(self) -> None:
        for name in list(self._tasks):
            await self._stop(name, "Stopped")


def _parse_endpoint(spec: str) -> tuple[str, int, str]:
    host, port, vol = spec.rsplit(":", 2)
    return host, int(port), vol


async def _amain(args) -> None:
    from .glusterd import mount_volume

    # fail FAST on malformed endpoints: a retry loop cannot fix a typo,
    # and in broker mode it would respawn a doomed agent forever
    ph, pp, pv = _parse_endpoint(args.primary)
    _parse_endpoint(args.secondary)
    primary = secondary = None
    broker = args.transport == "broker"
    while primary is None or secondary is None:
        try:
            if primary is None:
                primary = await mount_volume(ph, pp, pv)
            if secondary is None:
                if broker:
                    # the "geo" in geo-rep: the secondary site is only
                    # reachable through a spawned agent (repce/ssh
                    # analog) — THIS process holds no secondary client
                    from .repce import RepceClient

                    secondary = RepceClient(args.secondary)
                    await secondary._call("__ping__")  # spawn + mount
                else:
                    sh, sp, sv = _parse_endpoint(args.secondary)
                    secondary = await mount_volume(sh, sp, sv)
        except Exception as e:
            log.warning(3, "gsyncd mount retry: %r", e)
            if broker and secondary is not None:
                await secondary.close()
                secondary = None
            await asyncio.sleep(1.0)
    if args.bricks:
        bricks = []
        for i, spec in enumerate(args.bricks.split(",")):
            name, _, rest = spec.partition("=")
            idx, _, path = rest.partition("=")
            bricks.append({"name": name, "index": int(idx),
                           "path": path})
        worker = GeoRepMonitor(
            primary, secondary, glusterd=(ph, pp), volume=pv,
            bricks=bricks, group_size=args.group_size,
            state_dir=os.path.dirname(args.state) or ".",
            session_state=args.state, interval=args.interval,
            statusfile=args.statusfile)
        run_task = asyncio.ensure_future(worker.run())
    else:  # legacy single-worker mode (--changelogs)
        worker = GeoRepWorker(primary, secondary,
                              args.changelogs.split(","),
                              args.state, args.interval)
        worker.start()
        run_task = None
    if args.statusfile and not args.bricks:
        with open(args.statusfile + ".tmp", "w") as f:
            json.dump({"pid": os.getpid()}, f)
        os.replace(args.statusfile + ".tmp", args.statusfile)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    if run_task is not None:
        run_task.cancel()
        try:
            await run_task
        except (asyncio.CancelledError, Exception):
            pass
    await worker.stop()
    await primary.unmount()
    # broker: only proxy the unmount into an agent that is still alive
    # (unmounting through a respawned agent would mount the secondary
    # just to unmount it — or hang shutdown when the site is down), and
    # bound it so a wedged agent can't stop gsyncd from exiting
    if not broker or secondary.alive:
        try:
            await asyncio.wait_for(secondary.unmount(), 15)
        except Exception:
            pass
    if broker:
        await secondary.close()


def main(argv=None) -> int:
    pin_cpu()
    p = argparse.ArgumentParser(prog="gftpu-gsyncd")
    p.add_argument("--primary", required=True, help="host:port:volume")
    p.add_argument("--secondary", required=True, help="host:port:volume")
    p.add_argument("--changelogs", default="",
                   help="(legacy) comma-separated brick changelog dirs")
    p.add_argument("--bricks", default="",
                   help="local bricks as name=index=path,... — enables "
                        "the per-brick monitor (monitor.py model)")
    p.add_argument("--group-size", type=int, default=1,
                   help="bricks per replica/disperse subvolume group")
    p.add_argument("--state", required=True)
    p.add_argument("--interval", type=float, default=5.0)
    p.add_argument("--statusfile", default="")
    p.add_argument("--transport", choices=("broker", "direct"),
                   default="broker",
                   help="broker (default): reach the secondary only "
                        "through a spawned agent process (repce/ssh "
                        "analog); direct: mount it in-process")
    args = p.parse_args(argv)
    if not args.bricks and not args.changelogs:
        p.error("one of --bricks or --changelogs is required")
    asyncio.run(_amain(args))
    return 0


if __name__ == "__main__":
    sys.exit(main())
