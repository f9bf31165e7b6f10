"""Brick identity ops tools: setgfid2path + gfind_missing_files.

Reference: tools/setgfid2path (main.c — stamp the gfid2path metadata
onto pre-existing brick files so gfid-keyed consumers can resolve
them) and tools/gfind_missing_files (gfind_missing_files.sh +
gcrawler.c — crawl a brick, emit files absent on a geo-rep secondary
so an out-of-band sync can repair the gap).

TPU-build mechanisms: a brick's identity lives in the
``.glusterfs_tpu`` sidecar store (gfid records + dev:ino bindings +
handle hardlinks, storage/posix.py) instead of on-file xattrs, so

* ``setgfid2path`` walks the data tree, mints bindings for files the
  store does not know (legacy/side-loaded data), repairs records whose
  dev:ino went stale, and prunes records whose object is gone;
* ``gfind_missing_files`` walks the brick's files and looks each path
  up on a mounted secondary volume, writing the missing ones to the
  output file (one path per line, newline-escaped like the
  reference's output encoding).

Usage:
    gftpu-gfid-tool setgfid2path BRICKPATH
    gftpu-gfid-tool gfind-missing BRICKPATH OUTFILE \\
        --server H:P --volume SECONDARY
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys

from .. import pin_cpu
from ..core.iatt import gfid_new
from ..storage.posix import META_DIR, split_gfid_record


def _walk_data(root: str):
    """Yield brick-relative paths of every data object (files,
    symlinks, dirs), skipping the sidecar store."""
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != META_DIR]
        rel = os.path.relpath(dirpath, root)
        rel = "" if rel == "." else rel
        for d in dirnames:
            yield "/" + os.path.join(rel, d) if rel else "/" + d
        for f in filenames:
            yield "/" + os.path.join(rel, f) if rel else "/" + f


def setgfid2path(root: str) -> dict:
    """Repair/complete the identity store of a brick in place."""
    root = os.path.abspath(root)
    meta = os.path.join(root, META_DIR)
    gfid_dir = os.path.join(meta, "gfid")
    xattr_dir = os.path.join(meta, "xattr")
    handle_dir = os.path.join(meta, "handle")
    for d in (gfid_dir, xattr_dir, handle_dir):
        os.makedirs(d, exist_ok=True)

    known: dict[str, str] = {}  # relpath -> gfid hex
    pruned = 0
    for hexg in os.listdir(gfid_dir):
        if hexg.endswith(".tmp"):
            continue
        rec = os.path.join(gfid_dir, hexg)
        try:
            with open(rec) as f:
                _, relpath = split_gfid_record(f.read())
        except OSError:
            continue
        ap = os.path.join(root, relpath.lstrip("/"))
        if not os.path.lexists(ap):
            # object gone: prune the orphan identity (the reference
            # tool skips these; stale records would shadow reuse)
            for p in (rec, os.path.join(xattr_dir, hexg + ".json"),
                      os.path.join(handle_dir, hexg)):
                try:
                    os.unlink(p)
                except OSError:
                    pass
            pruned += 1
            continue
        known[relpath if relpath.startswith("/") else "/" + relpath] \
            = hexg

    stamped = rebound = 0
    for rel in _walk_data(root):
        ap = os.path.join(root, rel.lstrip("/"))
        try:
            st = os.lstat(ap)
        except OSError:
            continue
        key = f"{st.st_dev}:{st.st_ino}"
        binding = os.path.join(xattr_dir, "ino-" + key)
        hexg = known.get(rel)
        if hexg is None:
            # side-loaded object: mint identity (posix_gfid_set heal,
            # done offline)
            hexg = gfid_new().hex()
            with open(os.path.join(gfid_dir, hexg), "w") as f:
                f.write(key + "\n" + rel)
            stamped += 1
        elif not os.path.exists(binding):
            # record exists but dev:ino binding is stale/missing
            with open(os.path.join(gfid_dir, hexg), "w") as f:
                f.write(key + "\n" + rel)
            rebound += 1
        else:
            continue
        with open(binding + ".tmp", "wb") as f:
            f.write(bytes.fromhex(hexg))
        os.replace(binding + ".tmp", binding)
        hp = os.path.join(handle_dir, hexg)
        if not os.path.isdir(ap) and not os.path.lexists(hp):
            try:
                os.link(ap, hp, follow_symlinks=False)
            except OSError:
                pass
    return {"stamped": stamped, "rebound": rebound, "pruned": pruned,
            "known": len(known)}


async def gfind_missing_paths(root: str, top) -> tuple[int, list[str]]:
    """Crawl brick files; return (scanned, paths absent on `top`, a
    mounted secondary volume's top layer)."""
    from ..core.fops import FopError
    from ..core.layer import Loc

    missing = []
    scanned = 0
    for rel in _walk_data(os.path.abspath(root)):
        ap = os.path.join(root, rel.lstrip("/"))
        if os.path.isdir(ap):
            continue
        scanned += 1
        try:
            await top.lookup(Loc(rel))
        except FopError:
            missing.append(rel)
    return scanned, missing


def write_missing(outfile: str, missing: list[str]) -> None:
    with open(outfile, "w") as f:
        for p in missing:
            # newline-escape: paths are the one field per line
            f.write(p.replace("\\", "\\\\").replace("\n", "\\n") + "\n")


async def gfind_missing(root: str, server: str, volume: str,
                        outfile: str) -> dict:
    """CLI surface: mount the secondary via glusterd, crawl, write."""
    from ..mgmt.glusterd import mount_volume

    host, _, port = server.partition(":")
    client = await mount_volume(host, int(port or 24007), volume)
    try:
        scanned, missing = await gfind_missing_paths(root,
                                                     client.graph.top)
    finally:
        await client.unmount()
    write_missing(outfile, missing)
    return {"scanned": scanned, "missing": len(missing),
            "outfile": outfile}


def main(argv=None) -> int:
    pin_cpu()
    p = argparse.ArgumentParser(prog="gftpu-gfid-tool")
    sp = p.add_subparsers(dest="cmd", required=True)

    s1 = sp.add_parser("setgfid2path")
    s1.add_argument("brick")

    s2 = sp.add_parser("gfind-missing")
    s2.add_argument("brick")
    s2.add_argument("outfile")
    s2.add_argument("--server", default="127.0.0.1:24007")
    s2.add_argument("--volume", required=True)

    args = p.parse_args(argv)
    if args.cmd == "setgfid2path":
        out = setgfid2path(args.brick)
    else:
        out = asyncio.run(gfind_missing(args.brick, args.server,
                                        args.volume, args.outfile))
    print(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
