"""performance/write-behind — async write aggregation.

Reference: xlators/performance/write-behind (3.3k LoC; doc
doc/developer-guide/write-behind.md): acknowledge writes immediately,
coalesce adjacent ones in a per-fd window, flush on fsync/flush/read
overlap or window pressure, surface deferred errors on the next fop.

**What ``window-size`` bounds** (ISSUE 33; upstream's words: "the
size of the write-behind buffer for a single file", the bytes already
answered to the application and not yet landed on the child): the
bytes absorbed *and* the bytes in flight.  A write that fills the
window (``window-size`` absorbed, or ``aggregate-size`` in one chunk)
sets off a *pressure* drain, which no longer holds the write: the
flushable chunks are cut (stripe-aligned, the sub-stripe tail kept)
and handed to a background drain of that fd, and the write is
answered at once unless the bytes in flight before its own exceed
``window-size`` (``__wb_pick_unwinds``: skipped only when
``window_current > window_conf``); then it parks, in the phase
``wb.wait``, until the oldest drain in flight has landed.  With 1 MiB
writes under the 1 MiB default a sequential writer has two writes
under way below this layer: one in its wire legs, one in its codec
leg.  Background drains of one fd whose byte ranges do not touch run
side by side; one whose range touches a drain in flight lands after
it (``wb_liability_has_conflict``).  A write smaller than the window
is absorbed and drained exactly as before, and every full drain
(flush, fsync, readv, fstat, ftruncate, release, compound, the
``strict-o-direct`` bypass) is awaited and serial as before, after
everything in flight has landed.  The error of a background drain is
deferred to the fd's next fop, and stops the drains waiting behind it.
"""

from __future__ import annotations

import asyncio
import errno

from ..core import gflog, tracing
from ..core.fops import FopError
from ..core.layer import FdObj, Layer, register
from ..core.options import Option
from ..core import metrics as _metrics

log = gflog.get_logger("performance.write-behind")

#: live write-behind layers, scraped by the unified registry
_LIVE_WB_LAYERS = _metrics.REGISTRY.register_objects(
    "gftpu_write_behind_window_bytes", "gauge",
    "bytes absorbed into write-behind windows and not yet landed "
    "(absorbed and in flight)",
    lambda l: [({"layer": l.name}, l.window_bytes)])


class _Behind:
    """One background drain in flight: the chunks it carries, their
    bytes, its task, and the error that stopped it, if any."""

    __slots__ = ("chunks", "bytes", "task", "error")

    def __init__(self, chunks: list[tuple[int, bytearray]]):
        self.chunks = chunks
        self.bytes = sum(len(b) for _, b in chunks)
        self.task: asyncio.Task | None = None
        self.error: FopError | None = None

    def touches(self, chunks) -> bool:
        return any(off < o + len(b) and o < off + len(buf)
                   for off, buf in chunks for o, b in self.chunks)


class _WbFd:
    def __init__(self):
        self.chunks: list[tuple[int, bytearray]] = []  # (offset, data)
        self.bytes = 0
        self.error: FopError | None = None
        self.lock = asyncio.Lock()
        self.last_iatt = None
        self.logical_end = 0  # high-water mark incl. absorbed writes
        # background drains in flight, oldest first (the strong
        # reference to their tasks), and their bytes
        self.behind: list[_Behind] = []
        self.behind_bytes = 0


@register("performance/write-behind")
class WriteBehindLayer(Layer):
    OPTIONS = (
        Option("window-size", "size", default="1MB", min=512,
               description="size of the write-behind buffer for a "
                           "single file (performance.write-behind-"
                           "window-size): bounds the bytes answered to "
                           "the application and not yet landed on the "
                           "child, absorbed and in flight.  A write that "
                           "fills it is answered while its drain runs "
                           "below, unless the bytes already in flight "
                           "exceed it: then it waits for the oldest "
                           "drain to land"),
        Option("flush-behind", "bool", default="on"),
        Option("trickling-writes", "bool", default="on"),
        Option("aggregate-size", "size", default="0", min=0,
               description="flush once a single coalesced chunk reaches "
                           "this size (performance.aggregate-size; "
                           "reference default 128KB): bounds how large "
                           "one merged child writev grows.  0 = only "
                           "the window bounds it (this framework's "
                           "historical behavior — EC mounts want whole "
                           "stripes aggregated)"),
        Option("strict-o-direct", "bool", default="off",
               description="O_DIRECT fds bypass the window entirely "
                           "(performance.strict-o-direct): the app asked "
                           "for unbuffered semantics"),
        Option("strict-write-ordering", "bool", default="off",
               description="never acknowledge a write before every "
                           "prior one reached the child: each write "
                           "drains the window first "
                           "(performance.strict-write-ordering)"),
        Option("compound-fops", "bool", default="off",
               description="emit flushed windows as compound chains "
                           "(cluster.use-compound-fops): a multi-chunk "
                           "drain is one fused writev chain, and flush "
                           "rides the same frame as the final drain "
                           "instead of its own round trip"),
        Option("stripe-size", "int", default=0, min=0,
               description="align window flush cut points to this "
                           "stripe size (volgen sets the EC stripe "
                           "when the window sits above a disperse "
                           "graph): PRESSURE drains cut at the last "
                           "stripe boundary and keep the sub-stripe "
                           "TAIL absorbed, so a streamed writer (the "
                           "gateway's chunked PUT) hits the aligned "
                           "encode path instead of paying a tail "
                           "read-modify-write per chunk.  A stream "
                           "that STARTS unaligned still pays its one "
                           "intrinsic head partial on the first drain "
                           "(holding the head back could never align "
                           "it).  flush/fsync/read/release still "
                           "drain everything; 0 = cut anywhere"),
    )

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        # window occupancy across all fds (registry gauge + statedump):
        # absorbed and in-flight bytes, maintained by delta in
        # _absorb/_cut/_behind, never recomputed by walking fd contexts
        self.window_bytes = 0
        self.in_flight_bytes = 0  # the background drains' part of it
        # how often the pressure path engages: writes answered with a
        # drain of theirs in flight, writes that parked in ``wb.wait``,
        # background drains begun while another of the fd was in flight
        self.answered_behind = 0
        self.waited_on_window = 0
        self.drains_overlapped = 0
        self.phases: dict = {}  # tracing.phase sums: ``wb.wait``
        _LIVE_WB_LAYERS.add(self)

    def _ctx(self, fd: FdObj) -> _WbFd:
        ctx = fd.ctx_get(self)
        if ctx is None:
            ctx = _WbFd()
            fd.ctx_set(self, ctx)
        return ctx

    def _absorb(self, ctx: _WbFd, data: bytes, offset: int) -> None:
        """Coalesce every overlapping/adjacent chunk into one, newest data
        last.  Merging ALL touching chunks (not just the first) keeps the
        chunk list disjoint, so drain order can never replay stale bytes
        over newer ones.  The union is gap-free because each absorbed
        chunk touches the new write's interval."""
        end = offset + len(data)
        touching, rest = [], []
        for coff, cbuf in ctx.chunks:
            if offset <= coff + len(cbuf) and end >= coff:
                touching.append((coff, cbuf))
            else:
                rest.append((coff, cbuf))
        if not touching:  # a chunk of its own: the one copy
            start, merged = offset, bytearray(data)
        else:
            start = min([offset] + [c for c, _ in touching])
            stop = max([end] + [c + len(b) for c, b in touching])
            merged = bytearray(stop - start)
            for coff, cbuf in touching:  # disjoint among themselves
                merged[coff - start: coff - start + len(cbuf)] = cbuf
            merged[offset - start: end - start] = data
        rest.append((start, merged))
        ctx.chunks = rest
        before = ctx.bytes
        ctx.bytes = sum(len(b) for _, b in ctx.chunks)
        self.window_bytes += ctx.bytes - before

    def _cut(self, ctx: _WbFd, partial: bool) -> list:
        """Take the chunks to flush out of the window.

        ``partial`` (pressure drains only) with ``stripe-size`` set:
        the flush cuts at the last stripe boundary of each chunk and
        RETAINS the sub-stripe tail in the window — the next absorbed
        write extends it, so a streamed sequential writer below a
        disperse graph pays no TAIL partial per chunk (every retained
        cut is stripe-aligned, so all drains after a stream's first
        start aligned too; an unaligned stream START keeps its one
        intrinsic head partial — holding it back could never align
        it).  Ordering is safe: the retained tail stays newest-data
        in the window, and every full-drain site (flush/fsync/read/
        fstat/release/compound) still empties it."""
        chunks = ctx.chunks
        keep: list[tuple[int, bytearray]] = []
        s = self.opts["stripe-size"]
        if partial and s:
            flushable = []
            for off, buf in chunks:
                cut = (off + len(buf)) // s * s
                if cut <= off:
                    keep.append((off, buf))  # all sub-stripe: hold
                    continue
                if cut - off < len(buf):
                    flushable.append((off, buf[: cut - off]))
                    keep.append((cut, buf[cut - off:]))
                else:
                    flushable.append((off, buf))  # ends on a boundary
            if flushable:
                chunks = flushable
            else:
                keep = []  # nothing aligned: flush everything —
                # the window must stay bounded even for pathological
                # all-sub-stripe patterns
        ctx.chunks = keep
        before = ctx.bytes
        ctx.bytes = sum(len(b) for _, b in keep)
        self.window_bytes -= before - ctx.bytes
        return chunks

    async def _send(self, fd: FdObj, ctx: _WbFd, chunks: list,
                    tail: tuple = ()) -> tuple[FopError | None, list | None]:
        """Chunks that left the window go to the child.  With
        compound-fops on, several chunks (or any with a ``tail`` of
        extra links, e.g. the flush that triggered the drain) go down
        as ONE fused chain; otherwise the historical per-chunk writev
        loop runs and the tail is the caller's business.  Returns the
        error that is now deferred on the fd, if any, and the tail's
        reply entries when a chain carried them, else None."""
        if self.opts["compound-fops"] and chunks and \
                (len(chunks) + len(tail)) > 1:
            links = [("writev", (fd, bytes(buf), off), {})
                     for off, buf in sorted(chunks)]
            try:
                replies = await self.children[0].compound(
                    links + list(tail))
            except FopError as e:
                # transport-level failure (ENOTCONN mid-drain): the
                # window is already popped — defer like the singles
                # loop would, never let it escape an absorbing
                # writev as a spurious hard error
                ctx.error = e
                return e, ([("err", e)] if tail else None)
            err = None
            for st, val in replies[:len(links)]:
                if st == "ok" and val is not None:
                    ctx.last_iatt = val
                elif st == "err":
                    ctx.error = err = val  # deferred (wb_fd error analog)
            return err, replies[len(links):]
        for off, buf in sorted(chunks):
            try:
                ctx.last_iatt = await self.children[0].writev(
                    fd, bytes(buf), off)
            except FopError as e:
                ctx.error = e  # deferred error (wb_fd error analog)
                return e, None
        return None, None

    async def _drain(self, fd: FdObj, ctx: _WbFd,
                     tail: tuple = ()) -> list | None:
        """Flush the whole window and wait for it, serially, under the
        fd's lock (:meth:`_cut`, :meth:`_send`; returns the tail's
        reply entries when a chain carried them, else None).
        Everything in flight lands first: the window's bytes are newer
        than what a background drain carries, a flush on the chain's
        tail must not pass them, and no background drain begins while
        the lock is held, so what the caller does next finds nothing
        of this fd under way."""
        async with ctx.lock:
            await self._landed(ctx)
            _err, replies = await self._send(
                fd, ctx, self._cut(ctx, partial=False), tail)
            return replies

    async def _landed(self, ctx: _WbFd, over: int = 0) -> None:
        """Wait, for the oldest background drain in flight first,
        until no more than ``over`` bytes are in flight."""
        while ctx.behind_bytes > over:
            await asyncio.shield(ctx.behind[0].task)

    def _pressed(self, ctx: _WbFd) -> bool:
        agg = self.opts["aggregate-size"]
        return ctx.bytes >= self.opts["window-size"] or \
            bool(agg and any(len(b) >= agg for _, b in ctx.chunks))

    async def _drain_behind(self, fd: FdObj, ctx: _WbFd) -> None:
        """A pressure drain: stripe-aligned cut points (the sub-stripe
        tail stays absorbed for the next write to extend), sent by a
        task of the fd's while the write that set it off is answered.
        The window bounds what is outstanding: while the bytes in
        flight exceed ``window-size`` the write waits for the oldest
        drain to land."""
        if ctx.behind_bytes > self.opts["window-size"]:
            self.waited_on_window += 1
            with tracing.phase(self.name, "wb.wait", self.phases):
                await self._landed(ctx, self.opts["window-size"])
        self._raise_deferred(ctx)  # no further drain after a failed one
        async with ctx.lock:
            if not self._pressed(ctx):
                return  # a write beside this one has cut the window
            drain = _Behind(self._cut(ctx, partial=True))
            after = [d for d in ctx.behind if d.touches(drain.chunks)]
            if ctx.behind:
                self.drains_overlapped += 1
            self.answered_behind += 1
            ctx.behind.append(drain)
            ctx.behind_bytes += drain.bytes
            self.in_flight_bytes += drain.bytes
            self.window_bytes += drain.bytes  # absorbed -> in flight
            drain.task = asyncio.create_task(
                self._behind(fd, ctx, drain, after))

    async def _behind(self, fd: FdObj, ctx: _WbFd, drain: _Behind,
                      after: list) -> None:
        """The task of one background drain.  It lands after every
        drain in flight whose bytes it touches (the overwrite after
        what it overwrites), and is dropped if one of them failed:
        the fd's error is deferred, and the application learns of it
        on its next fop.  Its spans are a tree of their own, rooted at
        the child's ``writev``: the door write that cut it has
        returned, so its root span cannot hold what runs now."""
        tracing.CURRENT.set(None)  # this task's copy of the context
        try:
            for d in after:
                await asyncio.shield(d.task)
                if d.error is not None:
                    drain.error = d.error
                    return
            try:
                drain.error, _ = await self._send(fd, ctx, drain.chunks)
            except Exception as e:  # nobody awaits this: defer it too
                log.error(1, "%s: background drain of %s failed: %r",
                          self.name, fd.gfid.hex(), e)
                ctx.error = drain.error = FopError(errno.EIO, repr(e))
        finally:
            ctx.behind.remove(drain)
            ctx.behind_bytes -= drain.bytes
            self.in_flight_bytes -= drain.bytes
            self.window_bytes -= drain.bytes

    def _raise_deferred(self, ctx: _WbFd) -> None:
        if ctx.error is not None:
            err, ctx.error = ctx.error, None
            raise err

    async def create(self, loc, flags: int = 0, mode: int = 0o644,
                     xdata: dict | None = None):
        fd, ia = await self.children[0].create(loc, flags, mode, xdata)
        # seed the window's postbuf with the create iatt: without it,
        # EVERY write absorbed on a fresh fd pays a wire fstat just to
        # fabricate its reply iatt (a streaming writer — the object
        # gateway's chunked PUT — burned one round trip per chunk,
        # which is exactly what the window exists to avoid)
        self._ctx(fd).last_iatt = ia
        return fd, ia

    async def writev(self, fd: FdObj, data, offset: int,
                     xdata: dict | None = None):
        import os as _os

        ctx = self._ctx(fd)
        self._raise_deferred(ctx)
        if self.opts["strict-o-direct"] and \
                getattr(fd, "flags", 0) & getattr(_os, "O_DIRECT", 0):
            # unbuffered semantics: drain anything pending, then write
            # through (wb_enqueue bypass on O_DIRECT)
            if ctx.chunks or ctx.behind:
                await self._drain(fd, ctx)
                self._raise_deferred(ctx)
            return await self.children[0].writev(fd, data, offset, xdata)
        if self.opts["strict-write-ordering"] and \
                (ctx.chunks or ctx.behind):
            await self._drain(fd, ctx)
            self._raise_deferred(ctx)
        async with ctx.lock:
            self._absorb(ctx, data, offset)
            ctx.logical_end = max(ctx.logical_end, offset + len(data))
        if self._pressed(ctx):
            await self._drain_behind(fd, ctx)
        ia = ctx.last_iatt
        if ia is None:
            ia = await self.children[0].fstat(fd)
        # the postbuf must reflect absorbed-but-unflushed bytes too:
        # upper caches (md-cache) absorb this iatt, and a stale size
        # there would corrupt a stat-after-write
        if hasattr(ia, "size") and ia.size < ctx.logical_end:
            from ..core.iatt import Iatt

            ia = Iatt(**{**ia.__dict__})
            ia.size = ctx.logical_end
        return ia

    async def readv(self, fd: FdObj, size: int, offset: int,
                    xdata: dict | None = None):
        ctx = self._ctx(fd)
        if ctx.chunks or ctx.behind:  # read sees pending writes: flush
            await self._drain(fd, ctx)
        self._raise_deferred(ctx)
        return await self.children[0].readv(fd, size, offset, xdata)

    async def flush(self, fd: FdObj, xdata: dict | None = None):
        ctx = self._ctx(fd)
        if self.opts["compound-fops"] and ctx.chunks:
            # the flush rides the drain's frame: window + flush is one
            # chain (one round trip) instead of N writevs + a flush
            tail = await self._drain(
                fd, ctx, tail=(("flush", (fd,),
                                {"xdata": xdata} if xdata else {}),))
            self._raise_deferred(ctx)
            if tail:  # ("ok", ret) | ("skip", None) — err raised above
                st, val = tail[0]
                if st == "err":
                    raise val
                return val
            return await self.children[0].flush(fd, xdata)
        await self._drain(fd, ctx)
        self._raise_deferred(ctx)
        return await self.children[0].flush(fd, xdata)

    async def fsync(self, fd: FdObj, datasync: int = 0,
                    xdata: dict | None = None):
        ctx = self._ctx(fd)
        await self._drain(fd, ctx)
        self._raise_deferred(ctx)
        return await self.children[0].fsync(fd, datasync, xdata)

    async def fstat(self, fd: FdObj, xdata: dict | None = None):
        ctx = self._ctx(fd)
        if ctx.chunks or ctx.behind:
            await self._drain(fd, ctx)
        self._raise_deferred(ctx)
        return await self.children[0].fstat(fd, xdata)

    async def ftruncate(self, fd: FdObj, size: int,
                        xdata: dict | None = None):
        ctx = self._ctx(fd)
        await self._drain(fd, ctx)
        self._raise_deferred(ctx)
        ctx.logical_end = size
        ia = await self.children[0].ftruncate(fd, size, xdata)
        # refresh the cached postbuf: the drain's predates the truncate
        # and a later absorbed write would reply with the stale size
        ctx.last_iatt = ia if hasattr(ia, "size") else None
        return ia

    async def compound(self, links, xdata: dict | None = None) -> list:
        """Chains pass through write-through: any involved fd's pending
        window drains first (ordering), its deferred error surfaces,
        then the chain forwards INTACT — the point of a fused
        create+writev is that it skips the window entirely.  FdRef
        links (fds the chain itself creates) have no window by
        definition."""
        for _fop, args, kwargs in links:
            for a in list(args) + list((kwargs or {}).values()):
                if isinstance(a, FdObj):
                    ctx: _WbFd | None = a.ctx_get(self)
                    if ctx is not None:
                        if ctx.chunks or ctx.behind:
                            await self._drain(a, ctx)
                        self._raise_deferred(ctx)
        replies = await self.children[0].compound(links, xdata)
        # replay the per-fop bookkeeping the forwarded links skipped:
        # a fused ftruncate must reset the absorbed-bytes high-water
        # mark or later write replies inflate a shrunk file's size
        for (fop, args, _kw), (st, val) in zip(links, replies):
            if fop == "ftruncate" and st == "ok" and \
                    isinstance(args[0], FdObj) and len(args) > 1:
                ctx = args[0].ctx_get(self)
                if ctx is not None:
                    ctx.logical_end = args[1]
                    # the drain's postbuf predates the truncate: keep
                    # the truncated iatt or later writes reply stale
                    ctx.last_iatt = val if hasattr(val, "size") else None
        return replies

    async def release(self, fd: FdObj):
        ctx: _WbFd | None = fd.ctx_get(self)
        if ctx is not None and (ctx.chunks or ctx.behind):
            await self._drain(fd, ctx)
        fd.ctx_del(self)
        await super().release(fd)

    def dump_private(self) -> dict:
        return {"window_size": self.opts["window-size"],
                "window_bytes": self.window_bytes,
                "in_flight_bytes": self.in_flight_bytes,
                "answered_behind": self.answered_behind,
                "waited_on_window": self.waited_on_window,
                "drains_overlapped": self.drains_overlapped,
                "phases": tracing.phase_sums(self.phases)}
