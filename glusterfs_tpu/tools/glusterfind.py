"""glusterfind — incremental "what changed since session X" file lists.

Reference: tools/glusterfind (main.py subcommands create/pre/post/
list/delete/query) driven by the changelog history API
(changelog/lib/src/gf-history-changelog.c).  Sessions persist a
timestamp; ``pre`` emits every namespace/data/metadata change recorded
by the bricks' changelog journals since that timestamp, coalesced per
path into NEW / MODIFY / DELETE / RENAME lines; ``post`` commits the
new timestamp so the next ``pre`` is incremental.

TPU-build mechanisms: the brick journals are JSON-line segments
(features/changelog); sessions live under ``<session-dir>/<session>/
<volume>/status`` holding the committed timestamp, with a ``pending``
file between pre and post (the reference keeps the same split under
/var/lib/glusterd/glusterfind).  Brick locations come from glusterd's
volume-info; ``create`` force-enables changelog exactly like the
reference does.

Usage:
    gftpu-find create  SESSION VOLUME [--server H:P]
    gftpu-find pre     SESSION VOLUME OUTFILE
    gftpu-find post    SESSION VOLUME
    gftpu-find list
    gftpu-find delete  SESSION VOLUME
    gftpu-find query   VOLUME OUTFILE --since-time TS
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

from .. import pin_cpu

DEFAULT_SESSION_DIR = os.path.expanduser("~/.gftpu/glusterfind")

# ops -> emitted change class (the reference's NEW/MODIFY/DELETE split)
_NEW_OPS = {"create", "mknod", "mkdir", "symlink", "link", "icreate",
            "put"}
_DEL_OPS = {"unlink", "rmdir"}


def _session_path(base: str, session: str, volume: str) -> str:
    return os.path.join(base, session, volume)


def _read_ts(path: str) -> float | None:
    try:
        with open(path) as f:
            return float(f.read().strip())
    except (FileNotFoundError, ValueError):
        return None


def _write_ts(path: str, ts: float) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(repr(ts))
    os.replace(tmp, path)


async def _volinfo(server: str, volume: str) -> dict:
    from ..mgmt.glusterd import MgmtClient

    host, _, port = server.partition(":")
    async with MgmtClient(host, int(port or 24007)) as c:
        info = await c.call("volume-info", name=volume)
    if volume not in info:
        raise SystemExit(f"no volume {volume!r}")
    return info[volume]


def _brick_journal_dirs(vol: dict) -> list[str]:
    out = []
    for b in vol.get("bricks", []):
        d = os.path.join(b["path"], ".glusterfs_tpu", "changelog")
        if os.path.isdir(d):
            out.append(d)
    return out


async def _brick_history(vol: dict, brick: dict, since: float,
                         until: float) -> dict | None:
    """Query one brick's changelog history over its RPC (the
    gf-history-changelog.c consumer contract served by
    changelog-rpc.c): handshake with the volume's generated
    credentials, call ``changelog_history``, return its payload.
    None when the brick is unreachable (caller falls back to reading
    the journal directory locally, if it can)."""
    from ..rpc import wire

    port = brick.get("port")
    if not port:
        return None
    host = brick.get("host", "127.0.0.1")
    try:
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection(host, port), 5)
    except (OSError, asyncio.TimeoutError):
        return None
    try:
        auth = vol.get("auth") or {}
        creds = {"username": auth.get("mgmt-username",
                                      auth.get("username", "")),
                 "password": auth.get("mgmt-password",
                                      auth.get("password", ""))}
        writer.write(wire.pack(1, wire.MT_CALL, [
            "__handshake__", [b"glusterfind", brick.get("name", ""),
                              creds], {}]))
        await writer.drain()
        rec = await asyncio.wait_for(wire.read_frame(reader), 5)
        _, mtype, payload = wire.unpack(rec)
        if mtype != wire.MT_REPLY or not payload.get("ok"):
            return None
        writer.write(wire.pack(2, wire.MT_CALL, [
            "changelog_history", [since, until], {}]))
        await writer.drain()
        rec = await asyncio.wait_for(wire.read_frame(reader), 30)
        _, mtype, payload = wire.unpack(rec)
        if mtype != wire.MT_REPLY:
            return None
        return payload
    except (OSError, asyncio.TimeoutError, wire.WireError):
        return None
    finally:
        try:
            writer.close()
        except Exception:
            pass


async def _collect(server: str, vol: dict, since: float,
                   until: float) -> tuple[list[dict], bool]:
    """(records, covered): per-brick history via RPC first — a brick on
    another node is reachable over the wire only — falling back to
    reading its journal directory when the brick process is down but
    its path is local.  ``covered`` is False when any brick's journal
    epoch postdates ``since`` (window not fully recorded: the caller
    must full-crawl, reference brickfind.py)."""
    recs: list[dict] = []
    covered = True
    for b in vol.get("bricks", []):
        payload = await _brick_history(vol, b, since, until)
        if payload is not None:
            recs.extend(payload.get("records", ()))
            start = payload.get("start_ts")
            if start is None or start > since:
                covered = False
            while payload.get("truncated"):
                last = payload["records"][-1]["ts"]
                payload = await _brick_history(vol, b, last, until)
                if payload is None:
                    break
                recs.extend(payload.get("records", ()))
            continue
        d = os.path.join(b["path"], ".glusterfs_tpu", "changelog")
        if os.path.isdir(d):
            recs.extend(_scan([d], since, until))
            htime = os.path.join(d, "HTIME")
            try:
                with open(htime) as f:
                    if float(f.read().strip() or 0) > since:
                        covered = False
            except (OSError, ValueError):
                covered = False
        else:
            covered = False
    recs.sort(key=lambda r: r.get("ts", 0))
    return recs, covered


async def _full_crawl(server: str, volume: str) -> list[tuple[str, ...]]:
    """Namespace walk emitting NEW for every entry (the brickfind.py
    fallback for sessions/windows predating changelogs) — done through
    a mounted client so distribution/EC layouts are walked exactly
    once, not once per brick."""
    from ..mgmt.glusterd import mount_volume

    host, _, port = server.partition(":")
    client = await mount_volume(host or "127.0.0.1", int(port or 24007),
                                volume)
    out: list[tuple[str, ...]] = []
    try:
        stack = ["/"]
        while stack:
            d = stack.pop()
            for name, ia in await client.listdir_with_stat(d):
                path = (d if d != "/" else "") + "/" + name
                out.append(("NEW", path))
                if getattr(ia.ia_type, "name", "") == "DIR":
                    stack.append(path)
    finally:
        await client.unmount()
    return out


def _scan(dirs: list[str], since: float, until: float) -> list[dict]:
    """All journal records with since < ts <= until, time-ordered."""
    recs: list[dict] = []
    for d in dirs:
        for name in sorted(os.listdir(d)):
            if not name.startswith("CHANGELOG."):
                continue
            try:
                with open(os.path.join(d, name)) as f:
                    for line in f:
                        try:
                            r = json.loads(line)
                        except ValueError:
                            continue
                        if since < r.get("ts", 0) <= until:
                            recs.append(r)
            except OSError:
                continue
    recs.sort(key=lambda r: r.get("ts", 0))
    return recs


def coalesce(recs: list[dict]) -> list[tuple[str, ...]]:
    """Per-path final outcome, reference glusterfind semantics:
    NEW+changes = NEW, NEW+DELETE = nothing, changes+DELETE = DELETE,
    RENAME tracked to the final name (a NEW file renamed stays NEW at
    its final path).  Replica bricks journal the same logical op;
    identical outcomes dedupe naturally."""
    # path -> NEW | MODIFY | DELETE | DROPPED (born-and-died tombstone:
    # replica bricks echo every op, so a second unlink of a dropped
    # path must not resurrect it as DELETE)
    state: dict[str, str] = {}
    renames: dict[str, str] = {}  # final path -> original path
    applied_renames: set[tuple[str, str]] = set()  # replica-echo filter
    order: list[str] = []

    def touch(path: str, kind: str) -> None:
        cur = state.get(path)
        if cur is None:
            order.append(path)
        if kind == "NEW":
            # replica echo of a create we saw, or re-create after
            # delete: re-created files are NEW again
            if cur in (None, "NEW", "DELETE", "DROPPED"):
                state[path] = "NEW"
            if cur == "DELETE":
                renames.pop(path, None)
        elif kind == "MODIFY":
            if cur in (None, "MODIFY"):
                state[path] = "MODIFY"
            # NEW + modify stays NEW; DROPPED is an echo, keep dropped
        elif kind == "DELETE":
            if cur == "DROPPED":
                return  # replica echo of the delete we already folded
            if cur == "NEW" and path not in renames:
                state[path] = "DROPPED"  # born and died in the window
            else:
                state[path] = "DELETE"

    for r in recs:
        op = r.get("op", "")
        path = r.get("path", "")
        if not path:
            continue
        if op == "rename":
            dst = r.get("path2", "")
            if not dst:
                continue
            if (path, dst) in applied_renames and path not in state:
                continue  # a replica's echo of a rename already folded
            applied_renames.add((path, dst))
            prev = state.pop(path, None)
            if path in order:
                order.remove(path)
            origin = renames.pop(path, path)
            if prev == "NEW":
                touch(dst, "NEW")
            else:
                if dst not in state:
                    order.append(dst)
                state[dst] = "RENAME"
                renames[dst] = origin
        elif op in _NEW_OPS:
            touch(path, "NEW")
        elif op in _DEL_OPS:
            touch(path, "DELETE")
        else:
            touch(path, "MODIFY")

    out = []
    for path in order:
        kind = state.get(path)
        if kind in (None, "DROPPED"):
            continue
        if kind == "RENAME":
            out.append(("RENAME", renames.get(path, path), path))
        else:
            out.append((kind, path))
    return out


def _emit(outfile: str, changes: list[tuple[str, ...]]) -> None:
    with open(outfile, "w") as f:
        for c in changes:
            f.write(" ".join(c) + "\n")


async def cmd_create(args) -> dict:
    from ..mgmt.glusterd import MgmtClient

    await _volinfo(args.server, args.volume)  # existence check
    host, _, port = args.server.partition(":")
    async with MgmtClient(host, int(port or 24007)) as c:
        # the reference's create also force-enables changelog
        await c.call("volume-set", name=args.volume,
                     key="changelog.changelog", value="on")
    sp = _session_path(args.session_dir, args.session, args.volume)
    _write_ts(os.path.join(sp, "status"), time.time())
    return {"created": args.session, "volume": args.volume}


async def cmd_pre(args) -> dict:
    vol = await _volinfo(args.server, args.volume)
    sp = _session_path(args.session_dir, args.session, args.volume)
    since = _read_ts(os.path.join(sp, "status"))
    if since is None:
        raise SystemExit(f"session {args.session!r} not created for "
                         f"{args.volume!r} (run create first)")
    now = time.time()
    recs, covered = await _collect(args.server, vol, since, now)
    if covered:
        changes = coalesce(recs)
        mode = "changelog"
    else:
        # window predates the journals (session created after data
        # already existed, or changelog enabled late): full namespace
        # crawl, everything NEW (reference brickfind fallback)
        changes = await _full_crawl(args.server, args.volume)
        mode = "full-crawl"
    _emit(args.outfile, changes)
    _write_ts(os.path.join(sp, "pending"), now)
    return {"changes": len(changes), "outfile": args.outfile,
            "since": since, "mode": mode}


async def cmd_post(args) -> dict:
    sp = _session_path(args.session_dir, args.session, args.volume)
    pend = _read_ts(os.path.join(sp, "pending"))
    if pend is None:
        raise SystemExit("no pending pre to commit (run pre first)")
    _write_ts(os.path.join(sp, "status"), pend)
    os.unlink(os.path.join(sp, "pending"))
    return {"committed": pend}


async def cmd_query(args) -> dict:
    vol = await _volinfo(args.server, args.volume)
    recs, covered = await _collect(args.server, vol, args.since_time,
                                   time.time())
    if covered or not args.full_fallback:
        changes = coalesce(recs)
        mode = "changelog"
    else:
        changes = await _full_crawl(args.server, args.volume)
        mode = "full-crawl"
    _emit(args.outfile, changes)
    return {"changes": len(changes), "outfile": args.outfile,
            "mode": mode}


async def cmd_list(args) -> dict:
    out = {}
    base = args.session_dir
    if not os.path.isdir(base):
        return out
    for session in sorted(os.listdir(base)):
        for volume in sorted(os.listdir(os.path.join(base, session))):
            ts = _read_ts(os.path.join(base, session, volume, "status"))
            if ts is not None:
                out.setdefault(session, {})[volume] = ts
    return out


async def cmd_delete(args) -> dict:
    import shutil

    sp = _session_path(args.session_dir, args.session, args.volume)
    shutil.rmtree(sp, ignore_errors=True)
    return {"deleted": args.session}


def main(argv=None) -> int:
    pin_cpu()
    p = argparse.ArgumentParser(prog="gftpu-find")
    p.add_argument("--server", default="127.0.0.1:24007")
    p.add_argument("--session-dir", default=DEFAULT_SESSION_DIR)
    sub = p.add_subparsers(dest="cmd", required=True)
    for name, need in (("create", "sv"), ("pre", "svo"), ("post", "sv"),
                       ("delete", "sv"), ("list", ""), ("query", "vo")):
        sp = sub.add_parser(name)
        if "s" in need:
            sp.add_argument("session")
        if "v" in need:
            sp.add_argument("volume")
        if "o" in need:
            sp.add_argument("outfile")
        if name == "query":
            sp.add_argument("--since-time", type=float, required=True)
            sp.add_argument("--full-fallback", action="store_true",
                            help="namespace-crawl when the window "
                                 "predates the changelogs")
    args = p.parse_args(argv)
    fn = globals()[f"cmd_{args.cmd}"]
    out = asyncio.run(fn(args))
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
