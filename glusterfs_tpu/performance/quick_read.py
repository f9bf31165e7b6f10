"""performance/quick-read — small-file content cache.

Reference: xlators/performance/quick-read (1.8k LoC): content of files
under ``max-file-size`` is cached whole so repeated small-file reads
skip the data path (the reference piggybacks content on lookup; here it
is filled on first read and invalidated on writes).  A file found over
the limit is remembered as such (docs/read_path.md "The size probe")."""

from __future__ import annotations

import collections
import time

from ..core.layer import FdObj, Layer, Loc, register
from ..core.options import Option
from . import cache_metrics

#: most files remembered as too big; the oldest go first
TOO_BIG_MAX = 4096


@register("performance/quick-read")
class QuickReadLayer(Layer):
    OPTIONS = (
        Option("max-file-size", "size", default="64KB", min=0),
        Option("cache-size", "size", default="16MB"),
        Option("cache-timeout", "time", default="1"),
        Option("cache-invalidation", "bool", default="on",
               description="drop a cached file on a server upcall "
                           "(performance.quick-read-cache-invalidation) "
                           "instead of waiting out the timeout"),
    )

    def notify(self, event, source=None, data=None):
        from ..core.layer import Event

        if event is Event.UPCALL and isinstance(data, dict) and \
                data.get("gfid") and self.opts["cache-invalidation"]:
            self._forget(data["gfid"])
        super().notify(event, source, data)

    CACHE_KIND = "quick-read"  # the gftpu_cache_* {cache=...} label

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self._files: collections.OrderedDict[bytes, tuple[float, bytes]] = \
            collections.OrderedDict()
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        self.hit_bytes = 0
        # held-lease registry (api/glfs HeldLeases): leased content
        # never times out — a recall drops it via the upcall path
        self._lease_reg = None
        # gfids found over max-file-size, oldest first: a large file
        # must not pay a size probe (an fstat through every layer
        # below, a lookup wave on a disperse volume) to learn, again,
        # that it doesn't qualify.  A hint, and it needs no clock and
        # survives writes: all it ever does is send a read on to the
        # child, which is always a right answer.  A stale one costs a
        # file that shrank behind this mount its place in this cache
        # until something seen here says it may be small: a truncate
        # or an upcall (_forget), or a forwarded read that met EOF
        # inside the limit (readv).  Never a wrong byte.
        self._too_big: dict[bytes, None] = {}
        self.size_probes = 0
        self.forwarded_too_big = 0
        cache_metrics.track(self)

    def set_lease_registry(self, reg) -> None:
        self._lease_reg = reg

    def _leased(self, gfid) -> bool:
        return self._lease_reg is not None and self._lease_reg.held(gfid)

    def _drop_content(self, gfid: bytes) -> None:
        ent = self._files.pop(gfid, None)
        if ent is not None:
            self._bytes -= len(ent[1])

    def _forget(self, gfid: bytes) -> None:
        """The file may have become small: content and hint go."""
        self._drop_content(gfid)
        self._too_big.pop(gfid, None)

    def _store(self, gfid: bytes, content: bytes) -> None:
        self._drop_content(gfid)  # replace, don't double-count
        self._files[gfid] = (time.monotonic(), content)
        self._bytes += len(content)
        while self._bytes > self.opts["cache-size"] and self._files:
            _, (_, old) = self._files.popitem(last=False)
            self._bytes -= len(old)

    async def readv(self, fd: FdObj, size: int, offset: int,
                    xdata: dict | None = None):
        maxsz = self.opts["max-file-size"]
        ent = self._files.get(fd.gfid)
        if ent is not None and \
                (self._leased(fd.gfid) or
                 time.monotonic() - ent[0] < self.opts["cache-timeout"]):
            self.hits += 1
            self._files.move_to_end(fd.gfid)
            out = ent[1][offset: offset + size]
            self.hit_bytes += len(out)
            return out
        self.misses += 1
        if fd.gfid in self._too_big:
            self.forwarded_too_big += 1
            data = await self.children[0].readv(fd, size, offset, xdata)
            if len(data) < size and offset + len(data) <= maxsz:
                # EOF inside the limit: it shrank and nobody said so
                self._too_big.pop(fd.gfid, None)
            return data
        if size > maxsz:
            # a request larger than any qualifying file needs no size
            # probe — but it says nothing about the FILE's size (the
            # kernel and read_file read small files with big buffers),
            # so no blacklisting here.  If the EOF-truncated answer
            # turns out to BE a whole small file, cache it in passing.
            data = await self.children[0].readv(fd, size, offset, xdata)
            if offset == 0 and len(data) <= maxsz:
                self._store(fd.gfid, bytes(data))
            return data
        self.size_probes += 1
        ia = await self.children[0].fstat(fd)
        if ia.size > maxsz:
            self._too_big[fd.gfid] = None
            if len(self._too_big) > TOO_BIG_MAX:
                del self._too_big[next(iter(self._too_big))]
            return await self.children[0].readv(fd, size, offset, xdata)
        # bytes() copy: a memoryview off the wire blob lane would
        # pin its whole RPC frame for the cache's lifetime
        content = bytes(await self.children[0].readv(fd, maxsz + 1, 0))
        self._store(fd.gfid, content)
        return content[offset: offset + size]

    async def writev(self, fd: FdObj, data, offset: int,
                     xdata: dict | None = None):
        # content only: a write cannot make a file small
        self._drop_content(fd.gfid)
        return await self.children[0].writev(fd, data, offset, xdata)

    async def ftruncate(self, fd: FdObj, size: int,
                        xdata: dict | None = None):
        self._drop_content(fd.gfid)
        ia = await self.children[0].ftruncate(fd, size, xdata)
        # after it, as truncate does: a probe beside it saw the old size
        self._too_big.pop(fd.gfid, None)
        return ia

    async def truncate(self, loc: Loc, size: int, xdata: dict | None = None):
        ia = await self.children[0].truncate(loc, size, xdata)
        self._forget(ia.gfid)
        return ia

    async def compound(self, links, xdata: dict | None = None) -> list:
        """Forward chains intact; replay the whole-file-cache
        invalidation the per-fop write overrides would have done.  The
        replay does not say which fop a link was, so a chain's writev
        drops the too-big hint as its truncate must: one probe more
        after a chain, where no mount sends chains by default."""
        from ..rpc import compound as cfop

        replies = await self.children[0].compound(links, xdata)
        cfop.replay_write_invalidation(links, replies, self._forget)
        return replies

    def dump_private(self) -> dict:
        return {"files": len(self._files), "bytes": self._bytes,
                "hits": self.hits, "misses": self.misses,
                "size_probes": self.size_probes,
                "forwarded_too_big": self.forwarded_too_big,
                "too_big_entries": len(self._too_big)}
