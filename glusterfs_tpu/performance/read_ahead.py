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
(``_Fetch.live``).  Pages held ahead **and pages of fetches in
flight** count against the window, so an fd holds or awaits at most
one window plus the pages of the read in hand.

**When the next fetch begins** (ISSUE 31).  A sequential read that
finds its pages still on their way decides on the look-ahead *before*
it parks (ra_readv calls ``read_ahead()`` when a read arrives, not
when it is answered): the fetch of the window after it leaves while
the one it waits for is still below, so a stream has up to two child
reads in flight, the one it is parked on and one ahead.  More than two
cannot be: a fetch begins only while at most half a window is held or
awaited ahead.  A read served from held pages, or sent down as a
demand, decides when its bytes are there, as before.
"""

from __future__ import annotations

import asyncio

from ..core import tracing
from ..core.layer import FdObj, Layer, register
from ..core.options import Option
from ..rpc.compound import WRITE_INVALIDATING
from ..rpc.wire import as_single_buffer, serve_pages
from . import cache_metrics


class _Fetch:
    """One child read in flight for the pages [first, last] of an fd.
    ``live`` falls when the fd's pages are invalidated: what the fetch
    brings is then discarded, and no reader parks on it."""

    __slots__ = ("first", "pages", "live", "task")

    def __init__(self, first: int, pages: int):
        self.first, self.pages, self.live = first, pages, True
        self.task: asyncio.Task | None = None

    def overlaps(self, first: int, last: int) -> bool:
        return self.live and first < self.first + self.pages \
            and last >= self.first


class _RaFd:
    __slots__ = ("next_offset", "pages", "unread", "fetches", "window")

    def __init__(self):
        self.next_offset = 0
        self.pages: dict[int, bytes] = {}
        self.unread: set[int] = set()  # held pages no read has touched
        # fetches in flight, dead ones too until they land: a stream
        # has two at most, the one it is parked on and one ahead
        self.fetches: list[_Fetch] = []
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
        # fetches begun while another of the fd was in flight
        self.fetches_overlapped = 0
        # prefetched pages discarded before any read touched them
        self.dropped_unread = dict.fromkeys(
            ("seek", "write", "stale_fetch", "passed", "release"), 0)
        self.phases: dict = {}  # tracing.phase sums: ``ra.wait``
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
        at 1 page) less the pages already held or on their way there,
        so the stream never has more than one window ahead of it.  No
        pages while more than half the window is still ahead: a stream
        of small reads then asks the child once per half window, not
        once per page."""
        count = self.opts["page-count"]
        nxt = -(-end // self.opts["page-size"])
        window = count
        if self.opts["adaptive-window"]:
            window = min(count, max(1, ctx.window))
        ahead = 0
        while nxt + ahead in ctx.pages or \
                any(f.overlaps(nxt + ahead, nxt + ahead)
                    for f in ctx.fetches):
            ahead += 1
        if 2 * ahead > window:
            return nxt, 0
        ctx.window = min(count, window * 2)
        return nxt + ahead, window - ahead

    def _invalidate(self, ctx: _RaFd, cause: str, keep=()) -> None:
        """Drop the fd's pages; whatever a fetch in flight brings is
        discarded when it lands, and no reader parks on it any more,
        but for the fetches in ``keep``."""
        self.dropped_unread[cause] += len(ctx.unread)
        ctx.pages.clear()
        ctx.unread.clear()
        for f in ctx.fetches:
            f.live = f.live and f in keep

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

    def _store_window(self, ctx: _RaFd, fetch: _Fetch, data) -> None:
        """Split a fetched window into owned page copies (a memoryview
        off the wire blob lane must not be pinned by the cache).  A
        window fetched before the fd's pages were invalidated holds
        bytes from before that and is discarded."""
        psz = self.opts["page-size"]
        view = memoryview(as_single_buffer(data))
        self.prefetch_bytes += len(view)
        pages = (len(view) + psz - 1) // psz or 1  # b"" is the EOF page
        if not fetch.live:
            self.dropped_unread["stale_fetch"] += pages
            return
        for i in range(fetch.first, fetch.first + pages):
            ctx.pages[i] = bytes(view[(i - fetch.first) * psz:
                                      (i - fetch.first + 1) * psz])
            ctx.unread.add(i)

    def _start(self, ctx: _RaFd, fetch: _Fetch, coro) -> asyncio.Task:
        """Run ``coro`` as the task of ``fetch``, which is in flight
        (readers park on it, its pages count as ahead) until the task
        ends."""
        if ctx.fetches:
            self.fetches_overlapped += 1
        ctx.fetches.append(fetch)
        fetch.task = asyncio.create_task(coro)
        fetch.task.add_done_callback(lambda _t: ctx.fetches.remove(fetch))
        return fetch.task

    def _fetch_ahead(self, fd: FdObj, ctx: _RaFd, end: int) -> None:
        """Begin the look-ahead fetch after a read that ends at
        ``end``, if the window has room for one."""
        nxt, window = self._look_ahead(ctx, end)
        if window:
            fetch = _Fetch(nxt, window)
            self._start(ctx, fetch, self._prefetch(fd, ctx, fetch))

    async def _prefetch(self, fd: FdObj, ctx: _RaFd,
                        fetch: _Fetch) -> None:
        """Fetch the whole look-ahead window in ONE child readv (the
        reference pipelines its pages; issuing them as serial fops
        would pay the cluster read-txn latency page-count times)."""
        psz = self.opts["page-size"]
        try:
            data = await self.children[0].readv(fd, fetch.pages * psz,
                                                fetch.first * psz)
        except Exception:
            return
        self._store_window(ctx, fetch, data)

    async def _demand(self, fd: FdObj, size: int, offset: int,
                      xdata: dict | None):
        data = await self.children[0].readv(fd, size, offset, xdata)
        self.misses += 1
        self.demand_bytes += len(data)
        return data

    async def _chain_readv(self, fd: FdObj, ctx: _RaFd, fetch: _Fetch,
                           size: int, offset: int, xdata: dict | None):
        """Demand + look-ahead window as ONE compound frame.  Returns
        the demand data; window data lands in the page cache.  A failed
        window link is ignored (prefetch is advisory); a failed demand
        link raises exactly like the unchained read."""
        psz = self.opts["page-size"]
        kw = {"xdata": xdata} if xdata else {}
        replies = await self.children[0].compound([
            ("readv", (fd, size, offset), kw),
            ("readv", (fd, fetch.pages * psz, fetch.first * psz), {})])
        st, demand = replies[0]
        if st != "ok":
            raise demand
        self.misses += 1
        self.demand_bytes += len(demand)
        wst, wdata = replies[1]
        if wst == "ok" and wdata is not None:
            self._store_window(ctx, fetch, wdata)
        return demand

    async def readv(self, fd: FdObj, size: int, offset: int,
                    xdata: dict | None = None):
        ctx = self._ctx(fd)
        psz = self.opts["page-size"]
        idx = offset // psz
        end = offset + size
        last = (end - 1) // psz
        # the fetches in flight that bring (part of) this range
        coming = [f for f in ctx.fetches if f.overlaps(idx, last)]
        sequential = offset == ctx.next_offset
        if not sequential:
            # the stream moved: what was fetched for the old place
            # goes (ra_readv flushes the file's pages at an unexpected
            # offset), but a fetch this very read will park on stays,
            # as the reference keeps a page that has waiters
            self._invalidate(ctx, "seek", keep=coming)
            if self.opts["adaptive-window"]:
                ctx.window = 1  # a seek restarts the doubling ramp
        ctx.next_offset = end

        # serve from prefetched pages when fully covered
        def _covered():
            return all(i in ctx.pages for i in range(idx, last + 1))

        covered = _covered()
        looked = False  # this read has decided on the look-ahead
        if not covered and coming:
            # wait for the fetch instead of issuing a DUPLICATE
            # cluster read (the reference parks readers on the page's
            # wait queue, page.c ioc/ra waitq semantics).
            # Non-overlapping reads (a seek elsewhere) don't wait —
            # they'd pay the whole window's latency for zero hit-rate
            # benefit.
            if sequential:
                # the stream is running and its bytes are on their
                # way: the window after this read leaves NOW, beside
                # the fetch we park on, not when that one has landed
                self._fetch_ahead(fd, ctx, end)
                looked = True
            self.waited_on_prefetch += 1
            with tracing.phase(self.name, "ra.wait", self.phases):
                for f in coming:
                    try:
                        await asyncio.shield(f.task)
                    except asyncio.CancelledError:
                        if not f.task.cancelled():
                            raise  # OUR fop was cancelled: honor it
                    except Exception:
                        pass
            covered = _covered()
        chain = 0  # pages of the window to fuse with this demand
        if not covered and sequential and self.opts["compound-fops"] \
                and size <= self.opts["page-count"] * psz and \
                not ctx.fetches:
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
            # it instead of duplicating the window.
            fetch = _Fetch(nxt, chain)
            task = self._start(ctx, fetch, self._chain_readv(
                fd, ctx, fetch, size, offset, xdata))
            try:
                data = await asyncio.shield(task)
            except asyncio.CancelledError:
                if task.cancelled():
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
        if sequential and len(data) == size and not looked:
            self._fetch_ahead(fd, ctx, end)
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
            for f in ctx.fetches:
                f.task.cancel()
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
                "fetches_overlapped": self.fetches_overlapped,
                "dropped_unread_pages": dict(self.dropped_unread),
                "phases": tracing.phase_sums(self.phases)}
