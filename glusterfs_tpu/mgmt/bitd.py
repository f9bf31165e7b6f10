"""Bit-rot daemon — the bitd signer + scrubber analog.

Reference: xlators/features/bit-rot/src/bitd (bit-rot.c signer,
bit-rot-scrub.c scrubber): one daemon per node signs quiescent objects
with a content checksum and periodically re-hashes them; a mismatch on
an object that has NOT changed since signing is silent disk corruption —
the object is quarantined (bad-file marker, enforced by the brick's
bit-rot-stub) and flagged for heal.

TPU-build shape: one worker per brick, talking to the brick over its
normal RPC port (any Layer works — tests drive in-process brick tops
directly).  Signing condition: no signature newer than mtime AND the
object has been quiet for ``signer-quiesce`` seconds.  Scrub condition:
a signature newer than mtime (content unchanged since signing) whose
hash no longer matches.  On corruption the worker also zeroes the
brick's cluster version xattr and raises its dirty marker, which drops
the brick out of the heal-source group and feeds the pending index —
the shd then rebuilds the object from the healthy bricks, and the
rewrite lifts the quarantine (stub writev path).
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import json
import os
import signal
import struct
import sys
import time

from .. import pin_cpu
from ..core.fops import FopError
from ..core.iatt import IAType
from ..core.layer import Layer, Loc
from ..core import gflog
from ..features.bit_rot_stub import XA_BAD, XA_SIG

log = gflog.get_logger("bitd")

HASH_WINDOW = 1 << 20
# one default, referenced by glusterd's spawner and the argparse flag
DEFAULT_SCRUB_THROTTLE = 64 * (1 << 20)  # bytes/s


async def _release(layer: Layer, fd) -> None:
    rel = getattr(layer, "release", None)
    if rel is not None:
        try:
            await rel(fd)
        except Exception:
            pass


async def walk_files(layer: Layer, path: str = "/"):
    """Yield (path, iatt) for every regular file under path."""
    try:
        fd = await layer.opendir(Loc(path))
    except FopError:
        return
    try:
        entries = await layer.readdir(fd)
    except FopError:
        return
    finally:
        await _release(layer, fd)
    for name, _ in entries:
        child = (path.rstrip("/") + "/" + name)
        try:
            ia = await layer.stat(Loc(child))
        except FopError:
            continue
        if ia.ia_type is IAType.DIR:
            async for item in walk_files(layer, child):
                yield item
        elif ia.ia_type is IAType.REG:
            yield child, ia


async def content_hash(layer: Layer, path: str, gfid: bytes,
                       size: int) -> str:
    """sha256 of the object through ONE held fd (an anonymous fd per
    chunk would open/leak an OS fd per chunk brick-side)."""
    h = hashlib.sha256()
    fd = await layer.open(Loc(path, gfid=gfid), os.O_RDONLY)
    try:
        off = 0
        while off < size:
            chunk = await layer.readv(fd, min(HASH_WINDOW, size - off), off)
            if not chunk:
                break
            h.update(chunk)
            off += len(chunk)
    finally:
        await _release(layer, fd)
    return h.hexdigest()


# Scrub bandwidth cap: the shared throttle-tbf analog now lives in
# svcutil (the QoS plane uses the same bucket); re-exported here so
# `bitd.TokenBucket` keeps resolving for existing callers.
from .svcutil import TokenBucket  # noqa: E402


class BrickBitd:
    """Signer + scrubber over one brick graph top."""

    def __init__(self, layer: Layer, quiesce: float = 120.0,
                 throttle: float = DEFAULT_SCRUB_THROTTLE):
        self.layer = layer
        self.quiesce = quiesce
        self.tbf = TokenBucket(throttle)
        self.signed = 0
        self.scrubbed = 0
        self.corrupted: list[str] = []

    async def _xattrs(self, path: str) -> dict:
        try:
            return await self.layer.getxattr(Loc(path), None)
        except FopError:
            return {}

    def _sig(self, xattrs: dict) -> dict | None:
        raw = xattrs.get(XA_SIG)
        if not raw:
            return None
        try:
            return json.loads(raw.decode())
        except (ValueError, UnicodeDecodeError):
            return None

    async def sign_pass(self) -> int:
        """Sign quiescent objects lacking a current signature
        (bit-rot.c br_sign_object)."""
        n = 0
        now = time.time()
        async for path, ia in walk_files(self.layer):
            x = await self._xattrs(path)
            if XA_BAD in x:
                continue
            sig = self._sig(x)
            if sig is not None and sig.get("ts", 0) >= ia.mtime:
                continue  # signature current
            if now - ia.mtime < self.quiesce:
                continue  # still hot; sign once it goes quiet
            await self.tbf.take(ia.size)  # signer paces like the scrubber
            try:
                digest = await content_hash(self.layer, path, ia.gfid,
                                            ia.size)
                # re-stat: a write that landed mid-hash makes the digest
                # torn — signing it would fabricate corruption later
                ia2 = await self.layer.stat(Loc(path))
                if ia2.mtime != ia.mtime or ia2.size != ia.size:
                    continue
                await self.layer.setxattr(Loc(path), {XA_SIG: json.dumps(
                    {"sha256": digest, "ts": time.time()}).encode()})
                n += 1
            except FopError:
                continue
        self.signed += n
        return n

    async def scrub_pass(self) -> list[str]:
        """Re-hash signed, unmodified objects; mismatch = silent disk
        corruption -> quarantine + heal trigger (bit-rot-scrub.c
        br_scrubber_scrub_begin)."""
        bad: list[str] = []
        async for path, ia in walk_files(self.layer):
            x = await self._xattrs(path)
            if XA_BAD in x:
                continue
            sig = self._sig(x)
            if sig is None or sig.get("ts", 0) < ia.mtime:
                continue  # changed since signing: the signer's job
            await self.tbf.take(ia.size)  # throttle-tbf pacing
            try:
                digest = await content_hash(self.layer, path, ia.gfid,
                                            ia.size)
                # a write that landed mid-hash is a legitimate change,
                # not corruption — quarantining it would zero a healthy
                # brick's version
                ia2 = await self.layer.stat(Loc(path))
            except FopError:
                continue
            if ia2.mtime != ia.mtime or ia2.size != ia.size:
                continue
            self.scrubbed += 1
            if digest == sig.get("sha256"):
                continue
            marks: dict = {XA_BAD: b"1"}
            # feed the heal machinery: this brick must drop out of the
            # source group (zero version) and land in the pending index
            # (raise dirty)
            for ns in ("trusted.ec.", "trusted.afr."):
                # any counter in the namespace marks this as a cluster
                # object; with a delayed post-op the version xattr may
                # not exist YET (only the pre-op dirty does) — zero it
                # anyway so this brick can never join the source group
                if any(k.startswith(ns) for k in x):
                    marks[ns + "version"] = struct.pack(">QQ", 0, 0)
                    marks[ns + "dirty"] = struct.pack(">QQ", 1, 0)
            try:
                await self.layer.setxattr(Loc(path), marks)
            except FopError:
                continue
            bad.append(path)
            log.warning(3, "CORRUPTION on %s (%s)", path,
                        self.layer.name)
            from ..core.events import gf_event

            gf_event("BITROT_BAD_FILE", path=path,
                     brick=self.layer.name)
        self.corrupted += bad
        return bad

    def status(self) -> dict:
        return {"signed": self.signed, "scrubbed": self.scrubbed,
                "corrupted": list(self.corrupted)}


async def _amain(args) -> None:
    from ..protocol.client import ClientLayer
    from . import svcutil

    layers = []
    for spec in args.bricks.split(","):
        name, port = spec.rsplit(":", 1)
        layers.append(ClientLayer(f"bitd-{name}", svcutil.client_opts(
            args, "GFTPU_BITD", args.host, int(port), name)))
    for l in layers:
        await l.init()
    # the connect loop runs in the background; a pass against
    # unconnected bricks would silently no-op on ENOTCONN
    deadline = asyncio.get_running_loop().time() + 30
    while asyncio.get_running_loop().time() < deadline:
        if all(l.connected for l in layers):
            break
        await asyncio.sleep(0.1)
    workers = [BrickBitd(l, args.quiesce, args.scrub_throttle)
               for l in layers]

    async def loop_fn():
        while True:
            for w in workers:
                try:
                    await w.sign_pass()
                    if not args.no_scrub:
                        # features.scrub off/pause stops SCRUBBING only;
                        # signing continues so the pause window stays
                        # verifiable once scrubbing resumes
                        await w.scrub_pass()
                except Exception as e:
                    log.error(4, "bitd pass failed: %r", e)
            if args.statusfile:
                tmp = args.statusfile + ".tmp"
                with open(tmp, "w") as f:
                    json.dump({"pid": os.getpid(),
                               "bricks": {w.layer.name: w.status()
                                          for w in workers}}, f)
                os.replace(tmp, args.statusfile)
            await asyncio.sleep(args.scrub_interval)

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    task = loop.create_task(loop_fn())
    await stop.wait()
    task.cancel()
    for l in layers:
        await l.fini()


def main(argv=None) -> int:
    pin_cpu()
    p = argparse.ArgumentParser(prog="gftpu-bitd")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--bricks", required=True,
                   help="comma-separated brickname:port")
    from . import svcutil
    svcutil.add_ssl_args(p)
    p.add_argument("--quiesce", type=float, default=120.0)
    p.add_argument("--no-scrub", action="store_true",
                   help="sign only (features.scrub off/pause)")
    p.add_argument("--scrub-interval", type=float, default=60.0)
    p.add_argument("--scrub-throttle", type=float,
                   default=DEFAULT_SCRUB_THROTTLE,
                   help="scrub bandwidth cap, bytes/s (0 = unlimited)")
    p.add_argument("--statusfile", default="")
    args = p.parse_args(argv)
    asyncio.run(_amain(args))
    return 0


if __name__ == "__main__":
    sys.exit(main())
