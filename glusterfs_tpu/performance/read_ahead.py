"""performance/read-ahead — sequential read prefetch.

Reference: xlators/performance/read-ahead (2.1k LoC): detect sequential
access per fd and prefetch ``page-count`` pages ahead, dropping the
cache on writes/seeks.

Two additions over the reference shape (ISSUE 3 read pipeline):

* **Fused demand+prefetch chains** (``compound-fops on``): the demand
  readv and the look-ahead window ride ONE compound frame
  (readv+readv), so a sequential stream pays one round trip where the
  task-based prefetch paid two — this is the fusion site behind both
  the fuse READ path and api reads (both flow through this layer).
  Mixed-version peers and mid-graph decomposition fall back to plain
  serial readvs with identical results (rpc/compound semantics).
* **Adaptive window doubling** (``adaptive-window on``): the window
  starts at one page and doubles per sustained-sequential prefetch up
  to ``page-count``, so a short sequential burst never pays a full
  window of wasted reads while a long stream converges on the
  operator's ceiling (the read-ahead-page-count semantics, grown
  adaptively).

Cache hits are served as scatter-gather page views (wire.SGBuf): the
pages are immutable bytes, so the reply crosses the stack — and
/dev/fuse — without a join copy.

**Which pages an fd keeps** follows the stream (ra_readv's
flush_region calls), not a count: a prefetched page stays until the
stream has read it; the read that passes a page drops it (io-cache,
above, is the cache); a write, a truncate or a read at an unexpected
offset drops them all, and a fetch that lands after that is discarded
(``_RaFd.gen``).  Pages already held ahead count against the window,
so an fd holds at most one window plus the pages of the read in hand.
"""

from __future__ import annotations

import asyncio

from ..core.layer import FdObj, Layer, register
from ..core.options import Option
from ..rpc.compound import WRITE_INVALIDATING
from ..rpc.wire import as_single_buffer, serve_pages
from . import cache_metrics


class _RaFd:
    __slots__ = ("next_offset", "pages", "unread", "gen", "task",
                 "task_range", "window")

    def __init__(self):
        self.next_offset = 0
        self.pages: dict[int, bytes] = {}
        self.unread: set[int] = set()  # held pages no read has touched
        self.gen = 0  # bumped when the pages are invalidated
        self.task: asyncio.Task | None = None
        self.task_range = (0, 0)  # [first, last] page of the in-flight fetch
        self.window = 1  # adaptive look-ahead pages (doubles, capped)


def _write_fop(fop: str):
    """A fop that changes the fd's bytes drops the fd's pages before it
    goes down and again when it has come back: a fetch begun beside it
    may hold bytes of either side, and the second drop discards it."""

    async def method(self, *args, **kwargs):
        self._dirty(args[0])
        try:
            return await getattr(self.children[0], fop)(*args, **kwargs)
        finally:
            self._dirty(args[0])

    method.__name__ = fop
    return method


@register("performance/read-ahead")
class ReadAheadLayer(Layer):
    OPTIONS = (
        Option("page-count", "int", default=8, min=1, max=64),
        Option("page-size", "size", default="128KB", min=4096),
        Option("adaptive-window", "bool", default="on",
               description="grow the look-ahead window from 1 page, "
                           "doubling per sustained-sequential prefetch "
                           "up to page-count (performance.read-ahead-"
                           "adaptive); off = always page-count pages"),
        Option("compound-fops", "bool", default="off",
               description="fuse the demand readv and its look-ahead "
                           "window into one compound frame "
                           "(cluster.use-compound-fops read half): a "
                           "sequential stream costs one round trip per "
                           "window instead of two.  Decomposes "
                           "harmlessly below mixed-version or "
                           "non-transparent layers"),
    )

    CACHE_KIND = "read-ahead"  # the gftpu_cache_* {cache=...} label

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.hits = 0  # reads served from prefetched pages
        self.misses = 0  # reads sent to the child
        self.hit_bytes = 0
        self.prefetch_bytes = 0  # what the child returned, by kind
        self.demand_bytes = 0
        self.waited_on_prefetch = 0
        # prefetched pages discarded before any read touched them
        self.dropped_unread = dict.fromkeys(
            ("seek", "write", "stale_fetch", "passed", "release"), 0)
        cache_metrics.track(self)

    def _ctx(self, fd: FdObj) -> _RaFd:
        ctx = fd.ctx_get(self)
        if ctx is None:
            ctx = _RaFd()
            fd.ctx_set(self, ctx)
        return ctx

    def _look_ahead(self, ctx: _RaFd, end: int) -> tuple[int, int]:
        """(first page, pages) of the NEXT look-ahead fetch after a
        read that ended at ``end``: the current window from the page
        after it (doubling for the one after; the adaptive ramp starts
        at 1 page) less the pages already held there, so the stream
        never has more than one window ahead of it.  No pages while
        more than half the window is still held: a stream of small
        reads then asks the child once per half window, not once per
        page."""
        count = self.opts["page-count"]
        nxt = -(-end // self.opts["page-size"])
        window = count
        if self.opts["adaptive-window"]:
            window = min(count, max(1, ctx.window))
        ahead = 0
        while nxt + ahead in ctx.pages:
            ahead += 1
        if 2 * ahead > window:
            return nxt, 0
        ctx.window = min(count, window * 2)
        return nxt + ahead, window - ahead

    def _invalidate(self, ctx: _RaFd, cause: str,
                    fetch_too: bool = True) -> None:
        """Drop the fd's pages; with ``fetch_too`` whatever a fetch in
        flight brings is discarded when it lands, and no reader parks
        on it any more."""
        self.dropped_unread[cause] += len(ctx.unread)
        ctx.pages.clear()
        ctx.unread.clear()
        if fetch_too:
            ctx.gen += 1
            ctx.task_range = (0, -1)

    def _pass(self, ctx: _RaFd, end: int) -> None:
        """Drop the pages that lie wholly below ``end``: the stream
        has passed them (a demand that re-read a partly prefetched
        range passes pages nobody read)."""
        upto = end // self.opts["page-size"]
        for i in [i for i in ctx.pages if i < upto]:
            del ctx.pages[i]
            if i in ctx.unread:
                ctx.unread.discard(i)
                self.dropped_unread["passed"] += 1

    def _store_window(self, ctx: _RaFd, gen: int, start_page: int,
                      data) -> None:
        """Split a fetched window into owned page copies (a memoryview
        off the wire blob lane must not be pinned by the cache).  A
        window fetched before the fd's pages were invalidated holds
        bytes from before that and is discarded."""
        psz = self.opts["page-size"]
        view = memoryview(as_single_buffer(data))
        self.prefetch_bytes += len(view)
        pages = (len(view) + psz - 1) // psz or 1  # b"" is the EOF page
        if gen != ctx.gen:
            self.dropped_unread["stale_fetch"] += pages
            return
        for i in range(start_page, start_page + pages):
            ctx.pages[i] = bytes(view[(i - start_page) * psz:
                                      (i - start_page + 1) * psz])
            ctx.unread.add(i)

    async def _prefetch(self, fd: FdObj, ctx: _RaFd, gen: int,
                        start_page: int, window: int) -> None:
        """Fetch the whole look-ahead window in ONE child readv (the
        reference pipelines its pages; issuing them as serial fops
        would pay the cluster read-txn latency page-count times).
        ``gen`` is the fd's when the fetch was decided on: a task's
        body starts later, and a seek may have come by then."""
        psz = self.opts["page-size"]
        try:
            data = await self.children[0].readv(fd, window * psz,
                                                start_page * psz)
        except Exception:
            return
        self._store_window(ctx, gen, start_page, data)

    async def _demand(self, fd: FdObj, size: int, offset: int,
                      xdata: dict | None):
        data = await self.children[0].readv(fd, size, offset, xdata)
        self.misses += 1
        self.demand_bytes += len(data)
        return data

    async def _chain_readv(self, fd: FdObj, ctx: _RaFd, gen: int,
                           size: int, offset: int, nxt: int,
                           window: int, xdata: dict | None):
        """Demand + look-ahead window as ONE compound frame.  Returns
        the demand data; window data lands in the page cache.  A failed
        window link is ignored (prefetch is advisory); a failed demand
        link raises exactly like the unchained read."""
        psz = self.opts["page-size"]
        kw = {"xdata": xdata} if xdata else {}
        replies = await self.children[0].compound([
            ("readv", (fd, size, offset), kw),
            ("readv", (fd, window * psz, nxt * psz), {})])
        st, demand = replies[0]
        if st != "ok":
            raise demand
        self.misses += 1
        self.demand_bytes += len(demand)
        wst, wdata = replies[1]
        if wst == "ok" and wdata is not None:
            self._store_window(ctx, gen, nxt, wdata)
        return demand

    async def readv(self, fd: FdObj, size: int, offset: int,
                    xdata: dict | None = None):
        ctx = self._ctx(fd)
        psz = self.opts["page-size"]
        idx = offset // psz
        end = offset + size
        last = (end - 1) // psz
        # an in-flight prefetch is fetching (part of) this range
        fetching = ctx.task is not None and not ctx.task.done() and \
            idx <= ctx.task_range[1] and last >= ctx.task_range[0]
        sequential = offset == ctx.next_offset
        if not sequential:
            # the stream moved: what was fetched for the old place
            # goes (ra_readv flushes the file's pages at an unexpected
            # offset), but a fetch this very read will park on stays,
            # as the reference keeps a page that has waiters
            self._invalidate(ctx, "seek", fetch_too=not fetching)
            if self.opts["adaptive-window"]:
                ctx.window = 1  # a seek restarts the doubling ramp
        ctx.next_offset = end

        # serve from prefetched pages when fully covered
        def _covered():
            return all(i in ctx.pages for i in range(idx, last + 1))

        covered = _covered()
        if not covered and fetching:
            # wait for the fetch instead of issuing a DUPLICATE
            # cluster read (the reference parks readers on the page's
            # wait queue, page.c ioc/ra waitq semantics).
            # Non-overlapping reads (a seek elsewhere) don't wait —
            # they'd pay the whole window's latency for zero hit-rate
            # benefit.
            self.waited_on_prefetch += 1
            try:
                await asyncio.shield(ctx.task)
            except asyncio.CancelledError:
                raise  # OUR fop was cancelled: honor it
            except Exception:
                pass
            covered = _covered()
        chain = 0  # pages of the window to fuse with this demand
        if not covered and sequential and self.opts["compound-fops"] \
                and size <= self.opts["page-count"] * psz and \
                (ctx.task is None or ctx.task.done()):
            # window-shaped (streaming) demands only: a huge one-shot
            # read truncates at EOF, where the task path would never
            # have prefetched — chaining a past-EOF window readv onto
            # it would serialize a wasted cluster read wave in front
            # of the reply
            nxt, chain = self._look_ahead(ctx, end)
        if covered:
            # zero-copy page views (SGBuf) — shared serve loop
            data = serve_pages(ctx.pages, offset, end, psz)
            self.hits += 1
            self.hit_bytes += len(data)
            ctx.unread.difference_update(range(idx, last + 1))
        elif chain:
            # fused demand+window: one frame on the wire.  The chain
            # runs as a task so concurrent overlapping readers park on
            # it (task_range) instead of duplicating the window.
            ctx.task_range = (nxt, nxt + chain - 1)
            ctx.task = asyncio.create_task(self._chain_readv(
                fd, ctx, ctx.gen, size, offset, nxt, chain, xdata))
            try:
                data = await asyncio.shield(ctx.task)
            except asyncio.CancelledError:
                if ctx.task.cancelled():
                    # release() cancelled the chain under us (close
                    # racing a read): the fd is going away but OUR fop
                    # must still answer — serve the demand directly
                    return await self._demand(fd, size, offset, xdata)
                raise  # our own fop was cancelled: honor it
            self._pass(ctx, end)
            return data
        else:
            data = await self._demand(fd, size, offset, xdata)
        self._pass(ctx, end)
        if sequential and len(data) == size and \
                (ctx.task is None or ctx.task.done()):
            nxt, window = self._look_ahead(ctx, end)
            if window:
                ctx.task_range = (nxt, nxt + window - 1)
                ctx.task = asyncio.create_task(
                    self._prefetch(fd, ctx, ctx.gen, nxt, window))
        return data

    def _dirty(self, fd: FdObj) -> None:
        ctx: _RaFd | None = fd.ctx_get(self)
        if ctx is not None:
            self._invalidate(ctx, "write")

    writev = _write_fop("writev")
    ftruncate = _write_fop("ftruncate")
    discard = _write_fop("discard")
    zerofill = _write_fop("zerofill")
    fallocate = _write_fop("fallocate")

    async def release(self, fd: FdObj):
        ctx: _RaFd | None = fd.ctx_del(self)
        if ctx is not None:
            self._invalidate(ctx, "release")
            if ctx.task is not None:
                ctx.task.cancel()
        await super().release(fd)

    async def compound(self, links, xdata: dict | None = None) -> list:
        """Forward chains intact; drop the read-ahead pages of any fd a
        write link touches (the per-fop write overrides' job)."""
        fds = [a for fop, args, _kw in links if fop in WRITE_INVALIDATING
               for a in args if isinstance(a, FdObj)]
        for fd in fds:
            self._dirty(fd)
        try:
            return await self.children[0].compound(links, xdata)
        finally:
            for fd in fds:
                self._dirty(fd)

    def dump_private(self) -> dict:
        return {"prefetch_bytes": self.prefetch_bytes,
                "demand_bytes": self.demand_bytes,
                "served_from_pages_bytes": self.hit_bytes,
                "hits": self.hits, "misses": self.misses,
                "waited_on_prefetch": self.waited_on_prefetch,
                "dropped_unread_pages": dict(self.dropped_unread)}

