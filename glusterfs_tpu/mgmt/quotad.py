"""quotad — the quota aggregator daemon.

Reference: xlators/features/quota/src/quotad.c + quotad-aggregator.c:
one daemon aggregates per-brick marker sizes so 'quota list' (and soft
limit alerting) can report volume-wide usage.  Here: a per-volume
process (spawned by glusterd when features.quota is on, like bitd) that
polls every brick's quota layer over the brick RPC (``quota_usage``
extra), aggregates, persists a statusfile, and answers ``quota-list``
queries on its own wire port.

Aggregation is **sum over groups of max within a group**: bricks in
one replica/disperse group all hold the same logical files (each
already reports logical bytes — the layer scales fragments by K), so
within a group the max is the truth; distinct DHT groups hold disjoint
subtrees, so groups add.  glusterd tags each brick with its group in
``--bricks name:port:group``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

from .. import pin_cpu
from ..core import gflog
from ..rpc import wire

log = gflog.get_logger("mgmt.quotad")


class Quotad:
    def __init__(self, layers, groups, interval: float = 2.0):
        self.layers = layers
        self.groups = groups  # layer -> group id
        self.interval = interval
        self.usage: dict[str, dict] = {}  # path -> {used, limit}

    async def poll_once(self) -> dict:
        # path -> group -> max logical bytes seen in that group
        per_group: dict[str, dict[int, int]] = {}
        limits: dict[str, int] = {}
        for l in self.layers:
            if not l.connected:
                continue
            try:
                per = await l.remote("quota_usage")
            except Exception as e:
                log.debug(1, "quota_usage from %s failed: %r", l.name, e)
                continue
            grp = self.groups.get(l.name, 0)
            for d, ent in (per or {}).items():
                g = per_group.setdefault(d, {})
                g[grp] = max(g.get(grp, 0), ent["used"])
                limits[d] = ent["limit"]
        agg = {d: {"used": sum(groups.values()), "limit": limits[d],
                   "available": max(0, limits[d] - sum(groups.values()))}
               for d, groups in per_group.items()}
        self.usage = agg
        return agg

    async def serve(self, reader, writer) -> None:
        try:
            while True:
                try:
                    rec = await wire.read_frame(reader)
                except (asyncio.IncompleteReadError, ConnectionError):
                    break
                xid, _, payload = wire.unpack(rec)
                method = payload[0] if isinstance(payload, list) else payload
                if method == "quota-list":
                    await self.poll_once()  # serve fresh numbers
                    resp = self.usage
                else:
                    resp = {"error": f"unknown {method!r}"}
                writer.write(wire.pack(xid, wire.MT_REPLY, resp))
                await writer.drain()
        finally:
            try:
                writer.close()
            except Exception:
                pass


async def _amain(args) -> None:
    from ..protocol.client import ClientLayer
    from . import svcutil

    layers = []
    groups = {}
    for spec in args.bricks.split(","):
        parts = spec.rsplit(":", 2)
        if len(parts) == 2:
            parts.append("0")
        name, port, group = parts
        lname = f"quotad-{name}"
        layers.append(ClientLayer(lname, svcutil.client_opts(
            args, "GFTPU_QUOTAD", args.host, int(port), name)))
        groups[lname] = int(group)
    for l in layers:
        await l.init()
    qd = Quotad(layers, groups, args.interval)
    server = await asyncio.start_server(qd.serve, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    if args.portfile:
        tmp = args.portfile + ".tmp"
        with open(tmp, "w") as f:
            f.write(str(port))
        os.replace(tmp, args.portfile)
    log.info(2, "quotad serving on %d over %d bricks", port, len(layers))
    while True:
        try:
            await qd.poll_once()
        except Exception as e:
            log.error(3, "quotad poll failed: %r", e)
        if args.statusfile:
            tmp = args.statusfile + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"pid": os.getpid(), "usage": qd.usage}, f)
            os.replace(tmp, args.statusfile)
        await asyncio.sleep(args.interval)


def main(argv=None) -> int:
    pin_cpu()
    p = argparse.ArgumentParser(prog="gftpu-quotad")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--bricks", required=True,
                   help="comma list of brickname:port")
    p.add_argument("--interval", type=float, default=2.0)
    p.add_argument("--portfile", default="")
    p.add_argument("--statusfile", default="")
    from . import svcutil
    svcutil.add_ssl_args(p)
    args = p.parse_args(argv)
    try:
        asyncio.run(_amain(args))
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
