"""``gftpu`` — the gluster CLI analog.

Reference: cli/ (30k LoC — readline shell, parser, RPC to glusterd).
Command surface kept (cli-cmd-volume.c vocabulary):

    gftpu volume create NAME [disperse N | replica N] BRICK...
    gftpu volume start|stop|delete NAME
    gftpu volume info [NAME]
    gftpu volume status NAME [detail|clients|fds|inodes|callpool|mem]
    gftpu volume set NAME KEY VALUE
    gftpu volume heal NAME [info] [PATH] | statistics heal-count
    gftpu volume clear-locks NAME PATH kind {blocked|granted|all}
    gftpu volume quota NAME enable|disable|list|limit-usage PATH BYTES|remove PATH
    gftpu volume rebalance NAME start [fix-layout]|status|stop
    gftpu volume profile NAME
    gftpu volume metrics NAME
    gftpu volume gateway NAME start|stop|status
    gftpu volume incident NAME capture|list|show [BUNDLE]
    gftpu volume alerts NAME list|history|rules
    gftpu peer probe HOST:PORT | peer status

Talks to glusterd over the mgmt wire RPC (--server host:port, default
127.0.0.1:24007).  ``--json`` prints machine-readable output (the
reference's --xml).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
from typing import Any

from .. import pin_cpu
from ..protocol.server import STATUS_KINDS
from .glusterd import MgmtClient, mount_volume


def _fmt(v: Any, as_json: bool, as_xml: bool = False) -> str:
    if as_xml:
        return _xml_output(v)
    if as_json:
        return json.dumps(v, indent=1, default=repr)
    return _pretty(v)


def _table(headers: list[str], rows: list[list]) -> str:
    """Fixed-width text table (cli-cmd-volume.c's human status
    rendering analog)."""
    cells = [[str(c) for c in r] for r in rows]
    widths = [max(len(h), *(len(r[i]) for r in cells)) if cells
              else len(h) for i, h in enumerate(headers)]
    lines = ["  ".join(h.ljust(w) for h, w in zip(headers, widths)),
             "  ".join("-" * w for w in widths)]
    lines += ["  ".join(c.ljust(w) for c, w in zip(r, widths))
              for r in cells]
    return "\n".join(lines)


def _human_bytes(n) -> str:
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if n < 1024 or unit == "TiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}TiB"


def _status_human(what: str, out: dict) -> str:
    """Human tables for the deep-status kinds that are naturally
    tabular; the rest fall back to the generic tree rendering."""
    parts = []
    if out.get("partial"):
        parts.append("WARNING: partial answer — missing nodes: "
                     + ", ".join(out["partial"]))
    bricks = out.get("bricks", {})
    if what == "clients":
        rows = []
        for bname in sorted(bricks):
            payload = bricks[bname] or {}
            for c in payload.get("clients", ()):
                qos = c.get("qos") or {}
                if not qos.get("enabled"):
                    shaped = "-"
                elif qos.get("shaped"):
                    # inside a throttle window right now (reason =
                    # rate / soft-quota), with the lifetime shed count
                    shaped = (f"{qos.get('reason', '')}"
                              f"({qos.get('shed_fops', 0)})")
                else:
                    shaped = "no"
                rows.append([bname, c["client"][:16], c["addr"],
                             f"{c['uptime']:.0f}s",
                             _human_bytes(c["bytes_rx"]),
                             _human_bytes(c["bytes_tx"]),
                             c["fops"], c["opened_fds"], shaped,
                             "mgmt" if c.get("mgmt") else
                             f"op-{c.get('op_version', 0)}"])
            if payload.get("offline"):
                rows.append([bname, "-", "-", "-", "-", "-", "-", "-",
                             "-", "OFFLINE"])
        parts.append(_table(["BRICK", "CLIENT", "ADDR", "UPTIME", "RX",
                             "TX", "FOPS", "FDS", "SHAPED", "KIND"],
                            rows))
        return "\n".join(parts)
    if what == "fds":
        rows = []
        for bname in sorted(bricks):
            payload = bricks[bname] or {}
            for tab in payload.get("fd_tables", ()):
                for fd in tab["fds"]:
                    rows.append([bname, tab["client"][:16], fd["fd"],
                                 fd["path"] or fd["gfid"][:16],
                                 fd["flags"]])
            if payload.get("offline"):
                rows.append([bname, "-", "-", "OFFLINE", "-"])
        parts.append(_table(["BRICK", "CLIENT", "FD", "PATH", "FLAGS"],
                            rows))
        return "\n".join(parts)
    if what == "detail":
        rows = []
        for bname in sorted(bricks):
            payload = bricks[bname] or {}
            for be in payload.get("backends", ()):
                bs = be.get("block_size", 0)
                rows.append([
                    bname, be["path"], be["health"],
                    _human_bytes(be.get("blocks_avail", 0) * bs),
                    _human_bytes(be.get("blocks_total", 0) * bs),
                    be.get("inodes_free", "-"),
                    "yes" if be.get("reserve_limited") else "no"])
            if payload.get("offline"):
                rows.append([bname, "-", "OFFLINE", "-", "-", "-", "-"])
        parts.append(_table(["BRICK", "PATH", "HEALTH", "FREE", "TOTAL",
                             "INODES-FREE", "RESERVE-LIMITED"], rows))
        return "\n".join(parts)
    parts.append(_pretty(out))
    return "\n".join(parts)


_NCNAME = None


def _xml_output(v: Any, op_ret: int = 0, op_errno: int = 0,
                op_errstr: str = "") -> str:
    """Machine-readable XML in the reference's cli-xml-output.c
    envelope: <cliOutput><opRet/><opErrno/><opErrstr/>payload."""
    import re
    import xml.etree.ElementTree as ET

    global _NCNAME
    if _NCNAME is None:
        _NCNAME = re.compile(r"^[A-Za-z_][\w.-]*$")

    def build(parent, val, key=None):
        if key is None or not _NCNAME.match(str(key)):
            el = ET.SubElement(parent, "entry")
            if key is not None:
                el.set("name", str(key))
        else:
            el = ET.SubElement(parent, str(key))
        if isinstance(val, dict):
            for k, x in val.items():
                build(el, x, k)
        elif isinstance(val, (list, tuple)):
            for x in val:
                build(el, x, "item")
        elif val is not None:
            el.text = str(val)
        return el

    root = ET.Element("cliOutput")
    ET.SubElement(root, "opRet").text = str(op_ret)
    ET.SubElement(root, "opErrno").text = str(op_errno)
    ET.SubElement(root, "opErrstr").text = op_errstr
    if isinstance(v, dict):
        for k, x in v.items():
            build(root, x, k)
    elif v is not None:
        build(root, v, "output")
    ET.indent(root)
    return ET.tostring(root, encoding="unicode",
                       xml_declaration=True)


def _pretty(v: Any, indent: int = 0) -> str:
    pad = "  " * indent
    if isinstance(v, dict):
        return "\n".join(f"{pad}{k}: " + (
            "\n" + _pretty(val, indent + 1)
            if isinstance(val, (dict, list)) and val else _pretty(val))
            for k, val in v.items())
    if isinstance(v, list):
        return "\n".join(f"{pad}- " + (_pretty(x).lstrip()
                                       if not isinstance(x, (dict, list))
                                       else "\n" + _pretty(x, indent + 1))
                         for x in v)
    return f"{pad}{v}" if indent else str(v)


async def _run(args) -> Any:
    host, _, port = args.server.partition(":")
    port = int(port or 24007)

    if args.cmd == "peer":
        async with MgmtClient(host, port) as c:
            if args.sub == "probe":
                ph, _, pp = args.target.partition(":")
                return await c.call("peer-probe", host=ph, port=int(pp))
            return await c.call("peer-status")

    if args.cmd == "eventsapi":
        async with MgmtClient(host, port) as c:
            return await c.call("eventsapi", action=args.sub,
                                url=args.args[0] if args.args else "")

    if args.cmd == "georep":
        # georep PRIMARY create SECONDARY | start|stop|status PRIMARY
        async with MgmtClient(host, port) as c:
            if args.sub == "create":
                if not args.args:
                    raise SystemExit("usage: georep NAME create "
                                     "host:port:volume")
                return await c.call("georep-create", name=args.name,
                                    secondary=args.args[0])
            return await c.call(f"georep-{args.sub}", name=args.name)

    if args.cmd == "snapshot":
        # snapshot create NAME VOLUME | list [VOLUME] |
        #          clone CLONENAME SNAPNAME |
        #          delete|restore|activate|deactivate NAME
        need = {"create": 2, "clone": 2, "list": 0}.get(args.sub, 1)
        if len(args.args) < need:
            raise SystemExit(
                "usage: snapshot create NAME VOLUME | list [VOLUME] | "
                "clone CLONENAME SNAPNAME | "
                "delete|restore|activate|deactivate NAME")
        async with MgmtClient(host, port) as c:
            if args.sub == "create":
                return await c.call("snapshot-create", name=args.args[0],
                                    volume=args.args[1])
            if args.sub == "clone":
                return await c.call("snapshot-clone",
                                    clonename=args.args[0],
                                    snapname=args.args[1])
            if args.sub == "list":
                return await c.call(
                    "snapshot-list",
                    volume=args.args[0] if args.args else None)
            return await c.call(f"snapshot-{args.sub}",
                                name=args.args[0])

    if args.cmd == "volume":
        sub = args.sub
        if sub == "create":
            vtype = "distribute"
            redundancy = 0
            group = 0
            rest = list(args.args)
            if rest and rest[0] == "disperse":
                vtype = "disperse"
                redundancy = int(rest[1])
                rest = rest[2:]
            elif rest and rest[0] == "replica":
                vtype = "replicate"
                group = int(rest[1])
                rest = rest[2:]
            arbiter = thin = 0
            systematic = -1  # unset: disperse defaults systematic at
            # cluster op-version >= 12 (explicit opt-out below)
            if rest and rest[0] == "arbiter":
                arbiter = int(rest[1])
                rest = rest[2:]
            if rest and rest[0] == "thin-arbiter":
                thin = int(rest[1])
                rest = rest[2:]
            if rest and rest[0] == "systematic":
                # fragment format flag (create-time only; see
                # cluster/disperse "systematic")
                systematic = 1
                rest = rest[1:]
            elif rest and rest[0] == "non-systematic":
                # explicit opt-out of the systematic default (the
                # mesh codec tier has no systematic mode yet)
                systematic = 0
                rest = rest[1:]
            bricks = [{"path": b.split(":", 1)[-1],
                       "host": "127.0.0.1"} for b in rest]
            async with MgmtClient(host, port) as c:
                return await c.call("volume-create", name=args.name,
                                    vtype=vtype, bricks=bricks,
                                    redundancy=redundancy,
                                    group_size=group, arbiter=arbiter,
                                    thin_arbiter=thin,
                                    systematic=systematic)
        if sub == "status":
            # volume status NAME [detail|clients|fds|inodes|callpool|mem]
            what = args.args[0] if args.args else ""
            async with MgmtClient(host, port) as c:
                if not what:
                    return await c.call("volume-status", name=args.name)
                if what not in STATUS_KINDS:
                    raise SystemExit(
                        "usage: volume status NAME "
                        "[detail|clients|fds|inodes|callpool|mem]")
                return await c.call("volume-status-deep",
                                    name=args.name, what=what)
        if sub in ("start", "stop", "delete"):
            async with MgmtClient(host, port) as c:
                return await c.call(f"volume-{sub}", name=args.name)
        if sub == "info":
            async with MgmtClient(host, port) as c:
                return await c.call("volume-info",
                                    name=args.name or None)
        if sub == "set":
            async with MgmtClient(host, port) as c:
                return await c.call("volume-set", name=args.name,
                                    key=args.args[0], value=args.args[1])
        if sub == "heal":
            if args.args and args.args[0] == "statistics":
                # volume heal NAME statistics heal-count — answered
                # from the bricks' index counters through glusterd, no
                # temporary client graph mounted
                if len(args.args) > 1 and args.args[1] != "heal-count":
                    raise SystemExit("usage: volume heal NAME "
                                     "statistics heal-count")
                async with MgmtClient(host, port) as c:
                    return await c.call("volume-heal-count",
                                        name=args.name)
            client = await mount_volume(host, port, args.name)
            try:
                top = _find_cluster_layer(client.graph)
                from ..core.layer import Loc

                if args.args and args.args[0] == "split-brain":
                    # heal NAME split-brain bigger-file|latest-mtime PATH
                    #                      |source-brick IDX PATH
                    usage = ("usage: volume heal NAME split-brain "
                             "{bigger-file|latest-mtime} PATH | "
                             "source-brick IDX PATH")
                    rest = args.args[1:]
                    if not rest:
                        raise SystemExit(usage)
                    policy = rest[0]
                    if not hasattr(top, "split_brain_resolve"):
                        raise SystemExit(
                            "split-brain resolution is a replicate-"
                            "volume operation")
                    if policy == "source-brick":
                        if len(rest) < 3:
                            raise SystemExit(usage)
                        return await top.split_brain_resolve(
                            rest[2], policy, int(rest[1]))
                    if len(rest) < 2:
                        raise SystemExit(usage)
                    return await top.split_brain_resolve(rest[1], policy)
                path = args.args[1] if len(args.args) > 1 else \
                    (args.args[0] if args.args and
                     args.args[0] != "info" else "/")
                if args.args and args.args[0] == "info":
                    if path == "/":
                        return await _heal_info_all(client, top)
                    return await top.heal_info(Loc(path))
                if path == "/":
                    return await _heal_all(client, top)
                return await top.heal_file(path)
            finally:
                await client.unmount()
        if sub == "clear-locks":
            # volume clear-locks NAME PATH kind {blocked|granted|all}
            # (the literal "kind" keyword mirrors the reference's
            # syntax; tolerated absent).  Rides the brick-side
            # revocation machinery; --json prints the per-brick
            # cleared counts
            usage = ("usage: volume clear-locks NAME PATH kind "
                     "{blocked|granted|all}")
            rest = list(args.args)
            if not rest:
                raise SystemExit(usage)
            path = rest.pop(0)
            if rest and rest[0] == "kind":
                rest.pop(0)
            kind = rest.pop(0) if rest else "all"
            if kind not in ("blocked", "granted", "all") or rest:
                raise SystemExit(usage)
            async with MgmtClient(host, port) as c:
                return await c.call("volume-clear-locks",
                                    name=args.name, path=path,
                                    kind=kind)
        if sub == "quota":
            # gftpu volume quota NAME enable|disable|list
            #                        |limit-usage PATH BYTES|remove PATH
            action = args.args[0] if args.args else "list"
            kw = {"name": args.name, "action": action}
            if action == "limit-usage":
                kw.update(path=args.args[1], limit=int(args.args[2]))
            elif action == "remove":
                kw.update(path=args.args[1])
            async with MgmtClient(host, port) as c:
                return await c.call("volume-quota", **kw)
        if sub == "add-brick":
            # raw "node:path" (or bare path) strings: glusterd's
            # _parse_new_bricks resolves the node part
            bricks = [b if ":" in b else {"path": b, "host": "127.0.0.1"}
                      for b in args.args]
            async with MgmtClient(host, port) as c:
                return await c.call("volume-add-brick", name=args.name,
                                    bricks=bricks)
        if sub == "remove-brick":
            # volume remove-brick NAME BRICK...
            #                     start|status|stop|commit|force
            actions = ("start", "status", "stop", "commit", "force")
            action = args.args[-1] if args.args and \
                args.args[-1] in actions else "start"
            named = [a for a in args.args if a not in actions]
            async with MgmtClient(host, port) as c:
                return await c.call("volume-remove-brick",
                                    name=args.name, bricks=named,
                                    action=action)
        if sub == "replace-brick":
            if len(args.args) < 2:
                raise SystemExit("usage: volume replace-brick NAME "
                                 "BRICK NEWPATH [commit force]")
            async with MgmtClient(host, port) as c:
                return await c.call("volume-replace-brick",
                                    name=args.name, brick=args.args[0],
                                    new_path=args.args[1])
        if sub == "bitrot":
            action = args.args[0] if args.args else "status"
            async with MgmtClient(host, port) as c:
                return await c.call("volume-bitrot", name=args.name,
                                    action=action)
        if sub == "rebalance":
            # volume rebalance NAME start [fix-layout] | status | stop
            # — the glusterd-managed per-volume daemon (checkpointed,
            # throttleable, resumable; op-version 13).  Legacy direct
            # forms stay: `fix-layout [child=weight ...]` rewrites the
            # persisted hash ranges in-process; bare `rebalance NAME`
            # runs the one-shot in-process walk.
            if args.args and args.args[0] in ("start", "status",
                                              "stop"):
                action = args.args[0]
                flavor = args.args[1] if len(args.args) > 1 else ""
                async with MgmtClient(host, port) as c:
                    return await c.call("volume-rebalance",
                                        name=args.name, action=action,
                                        flavor=flavor)
            # the daemon's temp handling assumes it is the volume's
            # ONLY migrator (both walks target the same deterministic
            # `.NAME.rebalance~` temps) — refuse the legacy in-process
            # forms while a managed run is live
            async with MgmtClient(host, port) as c:
                info = await c.call("volume-info", name=args.name)
            if (info.get(args.name, {}).get("rebalance") or {}) \
                    .get("status") == "started":
                return {"error": "a managed rebalance is running on "
                                 f"{args.name}; the in-process walk "
                                 "would race its migrator (`volume "
                                 f"rebalance {args.name} stop` first)"}
            client = await mount_volume(host, port, args.name)
            try:
                from ..cluster.dht import DistributeLayer

                dht = _find_layer(client.graph, DistributeLayer)
                if dht is None:
                    return {"error": "not a distributed volume"}
                if args.args and args.args[0] == "fix-layout":
                    weights = {}
                    for spec in args.args[1:]:
                        child, sep, w = spec.partition("=")
                        try:
                            if not sep:
                                raise ValueError
                            weights[child] = float(w)
                        except ValueError:
                            return {"error": f"bad weight {spec!r} "
                                             "(want child=NUMBER)"}
                    return await dht.fix_layout("/", weights or None)
                return await dht.rebalance("/")
            finally:
                await client.unmount()
        if sub == "profile":
            # BRICK-side cumulative stats (volume profile info): the
            # bricks have been counting since they started — a freshly
            # mounted client's own io-stats would be empty
            async with MgmtClient(host, port) as c:
                return await c.call("volume-profile", name=args.name)
        if sub == "metrics":
            # per-brick unified-registry scrape (counters/gauges/
            # histograms from every subsystem; core/metrics.py)
            async with MgmtClient(host, port) as c:
                return await c.call("volume-metrics", name=args.name)
        if sub == "gateway":
            # volume gateway NAME start|stop|status — the HTTP object
            # front door (gateway/); status reports pid + bound port
            action = args.args[0] if args.args else "status"
            async with MgmtClient(host, port) as c:
                return await c.call("volume-gateway", name=args.name,
                                    action=action)
        if sub == "top":
            # volume top NAME [open|read|write|read-bytes|write-bytes]
            # [COUNT] — ranked per-path counters from each BRICK's
            # io-stats layer (gluster volume top)
            metric = args.args[0] if args.args else "open"
            cnt = int(args.args[1]) if len(args.args) > 1 else 10
            async with MgmtClient(host, port) as c:
                return await c.call("volume-top", name=args.name,
                                    metric=metric, count=cnt)
        if sub == "incident":
            # volume incident NAME capture|list|show [BUNDLE] — the
            # flight-recorder plane: capture fans a snapshot across
            # bricks + gateway + service daemons into one cluster
            # bundle; list/show read the incident dir
            action = args.args[0] if args.args else "list"
            if action not in ("capture", "list", "show"):
                raise SystemExit("usage: volume incident NAME "
                                 "capture|list|show [BUNDLE]")
            async with MgmtClient(host, port) as c:
                if action == "show":
                    bundle = args.args[1] if len(args.args) > 1 else ""
                    return await c.call("volume-incident-show",
                                        name=args.name, bundle=bundle)
                return await c.call(f"volume-incident-{action}",
                                    name=args.name)
        if sub == "alerts":
            # volume alerts NAME list|history|rules — the SLO plane:
            # list unions every process's currently-raised alerts,
            # history shows recent RAISED/CLEARED transition edges,
            # rules echoes the configured diagnostics.slo-rules set
            # (with validation errors)
            action = args.args[0] if args.args else "list"
            if action not in ("list", "history", "rules"):
                raise SystemExit("usage: volume alerts NAME "
                                 "list|history|rules")
            async with MgmtClient(host, port) as c:
                return await c.call("volume-alerts", name=args.name,
                                    action=action)
    raise SystemExit(f"unknown command {args.cmd} {args.sub}")


def _find_layer(graph, klass):
    for layer in graph.by_name.values():
        if isinstance(layer, klass):
            return layer
    return None


def _find_cluster_layer(graph):
    from ..cluster.afr import ReplicateLayer
    from ..cluster.ec import DisperseLayer

    for klass in (DisperseLayer, ReplicateLayer):
        layer = _find_layer(graph, klass)
        if layer is not None:
            return layer
    raise SystemExit("volume has no replicate/disperse layer to heal")


async def _walk_files(client, path="/"):
    out = []
    for name, ia in await client.listdir_with_stat(path):
        child = path.rstrip("/") + "/" + name
        if ia is not None and ia.is_dir():
            out.extend(await _walk_files(client, child))
        else:
            out.append(child)
    return out


async def _heal_info_all(client, top):
    from ..core.layer import Loc

    out = {}
    for f in await _walk_files(client):
        info = await top.heal_info(Loc(f))
        if info["bad"]:
            out[f] = info["bad"]
    return {"files_needing_heal": out, "count": len(out)}


async def _heal_all(client, top):
    healed = {}
    for f in await _walk_files(client):
        res = await top.heal_file(f)
        if res.get("healed"):
            healed[f] = res["healed"]
    return {"healed": healed, "count": len(healed)}


def _shell(server: str, flags: list[str]) -> int:
    """Interactive command shell (the reference's readline UI,
    cli-rl.c): `gftpu` with no command drops into `gftpu> ` and runs
    each line through the normal parser against --server, keeping the
    outer --json/--xml formatting."""
    import shlex

    try:
        import readline  # noqa: F401  (line editing + history)
    except ImportError:
        pass
    print("gftpu interactive shell — 'exit' to quit")
    while True:
        try:
            line = input("gftpu> ").strip()
        except (EOFError, KeyboardInterrupt):
            print()
            return 0
        if not line:
            continue
        if line in ("exit", "quit", "q"):
            return 0
        try:
            words = shlex.split(line)  # quoted args survive
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            continue
        if not any(not w.startswith("-") for w in words) and \
                not {"-h", "--help"} & set(words):
            # flag-only line would recurse into a nested shell
            # (--help is fine: argparse SystemExits before the shell)
            print("error: missing command", file=sys.stderr)
            continue
        try:
            main(["--server", server, *flags, *words])
        except SystemExit:
            pass  # argparse usage error: printed; the shell continues
        except KeyboardInterrupt:
            print()  # Ctrl-C aborts the command, not the shell


def main(argv=None) -> int:
    pin_cpu()
    p = argparse.ArgumentParser(prog="gftpu")
    p.add_argument("--server", default="127.0.0.1:24007")
    p.add_argument("--json", action="store_true")
    p.add_argument("--xml", action="store_true",
                   help="cli-xml-output.c style machine output")
    sp = p.add_subparsers(dest="cmd")  # no cmd -> interactive shell

    vol = sp.add_parser("volume")
    vol.add_argument("sub", choices=["create", "start", "stop", "delete",
                                     "info", "status", "set", "heal",
                                     "rebalance", "profile", "metrics",
                                     "quota", "bitrot", "add-brick",
                                     "remove-brick", "replace-brick",
                                     "top", "gateway", "clear-locks",
                                     "incident", "alerts"])
    vol.add_argument("name", nargs="?", default="")
    vol.add_argument("args", nargs="*")

    geo = sp.add_parser("georep")
    geo.add_argument("name")
    geo.add_argument("sub", choices=["create", "start", "stop",
                                     "status", "checkpoint"])
    geo.add_argument("args", nargs="*")

    snap = sp.add_parser("snapshot")
    snap.add_argument("sub", choices=["create", "clone", "list",
                                      "delete", "restore", "activate",
                                      "deactivate"])
    snap.add_argument("args", nargs="*")

    peer = sp.add_parser("peer")
    peer.add_argument("sub", choices=["probe", "status"])
    peer.add_argument("target", nargs="?", default="")

    ev = sp.add_parser("eventsapi")
    ev.add_argument("sub", choices=["webhook-add", "webhook-del",
                                    "status"])
    ev.add_argument("args", nargs="*")

    args = p.parse_args(argv)
    if args.cmd is None:
        if not sys.stdin.isatty():
            # scripts/cron piping into `gftpu` must get the usage
            # error they always got, not an accidental shell
            p.error("a command is required (interactive shell needs "
                    "a tty)")
        flags = [f for f, on in (("--json", args.json),
                                 ("--xml", args.xml)) if on]
        return _shell(args.server, flags)
    try:
        out = asyncio.run(_run(args))
    except Exception as e:
        if args.xml:
            err = getattr(e, "err", 1)
            print(_xml_output(None, op_ret=-1, op_errno=int(err),
                              op_errstr=str(e)))
        else:
            print(f"error: {e}", file=sys.stderr)
        return 1
    if not args.json and not args.xml and args.cmd == "volume" and \
            args.sub == "status" and args.args and \
            args.args[0] in STATUS_KINDS and isinstance(out, dict):
        print(_status_human(args.args[0], out))
        return 0
    print(_fmt(out, args.json, args.xml))
    return 0


if __name__ == "__main__":
    sys.exit(main())
