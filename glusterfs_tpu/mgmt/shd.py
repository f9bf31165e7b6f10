"""Self-heal daemon — the glustershd analog.

Reference: glustershd is a glusterfsd process running the client graph
minus performance layers, with healer threads per subvolume that crawl
the brick-side pending index and heal by gfid
(xlators/cluster/ec/src/ec-heald.c:282 ec_shd_index_healer,
ec-heald.c:390 ec_shd_index_sweep; afr-self-heald.c similarly).

Same split here:

* :func:`crawl_once` — one index sweep over every heal-capable cluster
  layer in a mounted graph: list each brick's pending gfids through the
  index layer's virtual xattr, resolve gfid -> path through posix's
  ``glusterfs_tpu.gfid2path``, call the layer's ``heal_file`` /
  ``heal_entry``; entries whose gfid no longer resolves anywhere are
  pruned (the unlinked-while-pending case).
* :class:`SelfHealDaemon` — the crawl on a ``heal-timeout`` interval.
* :func:`main` — the process entry glusterd spawns per started volume
  (one shd per volume here; the reference multiplexes volumes into one
  shd per node).
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
from ..core.iatt import IAType
from ..core.layer import Loc
from ..core import gflog
from ..features.index import XA_INDEX_LIST, XA_INDEX_PRUNE
from ..storage.posix import XA_GFID2PATH as GFID2PATH

log = gflog.get_logger("shd")


def _heal_layers(graph):
    """Cluster layers that know how to heal (disperse / replicate)."""
    return [l for l in graph.by_name.values()
            if callable(getattr(l, "heal_file", None))
            and callable(getattr(l, "heal_info", None))]


async def list_pending(layer) -> dict[str, list]:
    """gfid-hex -> [children that have it indexed] for one cluster layer."""
    pending: dict[str, list] = {}
    for child in layer.children:
        try:
            r = await child.getxattr(Loc("/"), XA_INDEX_LIST)
            hexes = r[XA_INDEX_LIST].decode().split()
        except FopError:
            continue
        for h in hexes:
            pending.setdefault(h, []).append(child)
    return pending


async def _resolve(layer, gfid: bytes) -> str | None:
    for child in layer.children:
        try:
            r = await child.getxattr(Loc("", gfid=gfid), GFID2PATH)
            return r[GFID2PATH].decode()
        except FopError:
            continue
    return None


async def full_crawl(client, max_heals: int = 1) -> dict:
    """``heal full``: walk the whole namespace and heal every entry —
    the reference's full sweep (ec-heald.c:418 ec_shd_full_sweep /
    afr full crawl).  Unlike the index sweep, this repairs bricks with
    NO pending record — a replaced (empty) brick, a wiped backend —
    because heal_info re-derives good/bad from the live lookups.

    ``max_heals`` file heals run CONCURRENTLY (the shd-max-threads
    analog the index sweep already honors): directory entry-heals
    happen in walk order (they create missing files on replaced
    bricks); file heals stream out under one semaphore as the walk
    discovers them, backlog-bounded.  On a
    ``cluster.mesh-codec`` volume this is the heal half of the mesh
    data plane — concurrent heals' window re-encodes coalesce in the
    stripe-cache batching window, so many files' dirty stripes land in
    ONE (dp, frag) mesh launch and heal throughput scales with the
    mesh instead of one device (ec-heal.c:2048's rebuild, batched)."""
    from ..cluster.dht import DistributeLayer

    report = {"healed": [], "skipped": [], "failed": []}
    layers = _heal_layers(client.graph)
    # distributed-X: a file lives in exactly ONE group — route its heal
    # to the owning group layer, or every group wastes a fan-out and
    # reports spurious failures for files it does not hold
    dht = next((l for l in client.graph.by_name.values()
                if isinstance(l, DistributeLayer)), None)

    async def owners(path: str) -> list:
        if dht is None or not all(l in dht.children for l in layers):
            return layers
        try:
            child = dht.children[await dht._cached_idx(Loc(path))]
        except FopError:
            return layers
        return [child] if child in layers else layers

    async def one(layer, path: str, is_dir: bool) -> None:
        try:
            if is_dir:
                if callable(getattr(layer, "heal_entry", None)):
                    await layer.heal_entry(path)
                return
            res = await layer.heal_file(path)
        except FopError as e:
            report["failed"].append({"path": path, "error": str(e)})
            return
        key = "skipped" if res.get("skipped") else "healed"
        report[key].append({"path": path,
                            "bricks": res.get("healed", [])})
        if key == "healed" and res.get("healed"):
            # the index sweep already announces its completions; the
            # full sweep repairs bricks with no pending record and must
            # show on the same event stream
            from ..core.events import gf_event

            gf_event("HEAL_COMPLETE", path=path,
                     bricks=res.get("healed", []))

    sem = asyncio.Semaphore(max(1, max_heals))
    # STREAMING dispatch, not collect-then-heal: file heals start while
    # the walk is still running (a multi-million-file namespace must
    # not buffer O(files) jobs — and a walk error must not zero out
    # heals already in flight), with the task backlog bounded so the
    # pending set stays O(max_heals)
    pending: set[asyncio.Task] = set()
    backlog = max(4, 2 * max(1, max_heals))

    async def one_file(layer, path: str) -> None:
        async with sem:
            await one(layer, path, False)

    async def walk(path: str) -> None:
        for layer in layers:  # directories exist in every group
            await one(layer, path, True)
        for name, ia in await client.listdir_with_stat(path):
            child = path.rstrip("/") + "/" + name
            if ia is not None and ia.is_dir():
                await walk(child)
            else:
                for layer in await owners(child):
                    t = asyncio.ensure_future(one_file(layer, child))
                    pending.add(t)
                    t.add_done_callback(pending.discard)
                while len(pending) > backlog:
                    await asyncio.wait(
                        pending, return_when=asyncio.FIRST_COMPLETED)

    try:
        await walk("/")
    finally:
        if pending:  # drain in-flight heals even when the walk errors
            await asyncio.gather(*pending, return_exceptions=True)
    return report


async def crawl_once(client, max_heals: int = 1,
                     wait_qlength: int = 1024) -> dict:
    """One full index sweep; returns a heal report.

    ``max_heals`` concurrent file heals (cluster/disperse
    shd-max-threads: the reference scales healer threads); entries past
    ``max_heals + wait_qlength`` defer to the next sweep
    (heal-wait-queue-length: bound the in-memory heal backlog)."""
    report = {"healed": [], "skipped": [], "failed": [], "pruned": [],
              "deferred": 0}
    sem = asyncio.Semaphore(max(1, max_heals))
    for layer in _heal_layers(client.graph):
        pending = await list_pending(layer)
        if pending:
            # events.h EVENT_HEAL_START: a sweep found damage to repair
            # (paired with the per-file HEAL_COMPLETE below)
            from ..core.events import gf_event

            gf_event("HEAL_START", layer=layer.name,
                     pending=len(pending))
        cap = max(1, max_heals) + max(0, wait_qlength)
        items = list(pending.items())
        if len(items) > cap:
            report["deferred"] += len(items) - cap
            items = items[:cap]
        tasks = []
        for hexgfid, holders in items:
            async def one(hexgfid=hexgfid, holders=holders,
                          layer=layer) -> None:
                async with sem:
                    gfid = bytes.fromhex(hexgfid)
                    path = await _resolve(layer, gfid)
                    if path is None:
                        # object is gone everywhere: stale entry, prune
                        for child in holders:
                            try:
                                await child.setxattr(
                                    Loc("/"),
                                    {XA_INDEX_PRUNE: hexgfid.encode()})
                            except FopError:
                                pass
                        report["pruned"].append(hexgfid)
                        return
                    try:
                        ia, _ = await layer.lookup(Loc(path))
                        if ia.ia_type is IAType.DIR and \
                                callable(getattr(layer, "heal_entry",
                                                 None)):
                            await layer.heal_entry(path)
                            res = {"healed": [], "skipped": False}
                        else:
                            res = await layer.heal_file(path)
                    except FopError as e:
                        report["failed"].append({"path": path,
                                                 "error": str(e)})
                        return
                    key = "skipped" if res.get("skipped") else "healed"
                    report[key].append({"path": path, "gfid": hexgfid,
                                        "bricks": res.get("healed", [])})
                    if key == "healed":
                        from ..core.events import gf_event

                        gf_event("HEAL_COMPLETE", path=path,
                                 gfid=hexgfid,
                                 bricks=res.get("healed", []))

            tasks.append(asyncio.ensure_future(one()))
        if tasks:
            await asyncio.gather(*tasks)
    return report


async def gather_heal_info(client) -> dict:
    """``volume heal <v> info``: pending entries with per-file status
    (heal info via the index, not a volume walk — glfs-heal.c analog)."""
    out = []
    for layer in _heal_layers(client.graph):
        pending = await list_pending(layer)
        for hexgfid in pending:
            gfid = bytes.fromhex(hexgfid)
            path = await _resolve(layer, gfid)
            entry = {"gfid": hexgfid, "path": path, "layer": layer.name}
            if path is not None:
                try:
                    info = await layer.heal_info(Loc(path))
                    entry["bad_bricks"] = info["bad"]
                    entry["dirty"] = info.get("dirty", False)
                except FopError as e:
                    entry["error"] = str(e)
            out.append(entry)
    return {"entries": out, "count": len(out)}


class SelfHealDaemon:
    """Periodic index healer over one mounted client graph."""

    def __init__(self, client, interval: float = 10.0,
                 max_heals: int = 1, wait_qlength: int = 1024):
        self.client = client
        self.interval = interval
        self.max_heals = max_heals
        self.wait_qlength = wait_qlength
        self.sweeps = 0
        self.last_report: dict = {}
        self._task: asyncio.Task | None = None
        self._wake = asyncio.Event()

    async def run(self) -> None:
        while True:
            # clear BEFORE the sweep: a poke() that lands mid-sweep must
            # not be lost — it means damage this sweep may have missed
            self._wake.clear()
            try:
                self.last_report = await crawl_once(
                    self.client, self.max_heals, self.wait_qlength)
            except Exception as e:  # a sweep must never kill the daemon
                log.error(1, "shd sweep failed: %r", e)
            self.sweeps += 1
            try:
                await asyncio.wait_for(self._wake.wait(), self.interval)
            except asyncio.TimeoutError:
                pass

    def poke(self) -> None:
        """Trigger an immediate sweep (heal <v> full analog)."""
        self._wake.set()

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


async def _amain(args) -> None:
    from ..core import flight, history, slo
    from ..core.metrics import register_build_info
    from .glusterd import mount_volume

    flight.set_role("shd")
    register_build_info("shd")
    history.arm()
    host, _, port = args.glusterd.rpartition(":")
    client = None
    while client is None:
        try:
            client = await mount_volume(host, int(port), args.volname)
        except Exception as e:
            log.warning(2, "shd mount %s failed (%r), retrying", args.volname, e)
            await asyncio.sleep(1.0)
    if args.statefile:
        with open(args.statefile + ".tmp", "w") as f:
            json.dump({"pid": os.getpid(), "volume": args.volname}, f)
        os.replace(args.statefile + ".tmp", args.statefile)
        # incident capture door for a daemon with no inbound RPC:
        # SIGUSR2 writes the flight bundle beside the statefile, where
        # glusterd's incident fan-out polls for it
        flight.arm_signal_capture(args.statefile + ".incident")
        # alerts door, same shape: the local SLO engine's status is
        # mirrored beside the statefile on every sampler tick (only
        # once rules are configured), where glusterd's volume-alerts
        # fan-out reads it
        alerts_path = args.statefile + ".alerts"

        def _mirror_alerts() -> None:
            if not slo.ENGINE.rules:
                return
            try:
                with open(alerts_path + ".tmp", "w") as f:
                    json.dump(slo.ENGINE.status(), f, default=repr)
                os.replace(alerts_path + ".tmp", alerts_path)
            except OSError:
                pass

        history.add_tick_hook(_mirror_alerts)
    shd = SelfHealDaemon(client, args.interval,
                         args.max_heals, args.wait_qlength)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    shd.start()
    await stop.wait()
    await shd.stop()
    await client.unmount()


def main(argv=None) -> int:
    pin_cpu()
    p = argparse.ArgumentParser(prog="gftpu-shd")
    p.add_argument("--glusterd", required=True, help="host:port")
    p.add_argument("--volname", required=True)
    p.add_argument("--interval", type=float, default=10.0)
    p.add_argument("--max-heals", type=int, default=1)
    p.add_argument("--wait-qlength", type=int, default=1024)
    p.add_argument("--statefile", default="")
    args = p.parse_args(argv)
    asyncio.run(_amain(args))
    return 0


if __name__ == "__main__":
    sys.exit(main())
