"""Trace-context propagation: per-request spans across the whole stack.

The reference threads a ``frame->root`` through every STACK_WIND so a
statedump can show which xlator a call is parked in (stack.h:283,
call-stub.c pending frames) — but it never crosses the wire: a slow
client readv cannot say whether the time went to the client graph, the
transport, the brick graph or the disk.  Here every OUTERMOST fop call
on a graph mints a 16-hex-char trace id; each timed layer method
(``core.layer._timed``) records a span ``(trace, depth, layer, op,
start, duration, err, span_id, parent_id, start_ns)`` into a bounded
per-process ring; and
protocol/client ships the id as a trailing wire-frame field that
protocol/server re-arms before dispatching into the brick graph — so
the brick's spans carry the CLIENT's trace id and the two statedumps
join into one tree.  One trace per compound chain: the chain's
outermost ``compound`` call is the root and every link is a child span.

The carrier is a :mod:`contextvars` ContextVar (the asyncio-idiomatic
``frame->root``): awaits and ``asyncio.gather`` fan-outs inherit it,
tasks copy it, and nothing in the fop signatures changes.  The io-stats
layer owns the operator knobs (``diagnostics.slow-fop-threshold``,
``diagnostics.span-ring-size``, and the master ``ENABLED`` gate rides
metrics-off bench runs / ``GFTPU_NO_OBSERVABILITY``).

A root span exceeding ``SLOW_FOP_THRESHOLD`` logs the full span tree —
a slow wire readv finally says WHERE the time went.

Below the fop boundary the same primitive times a PHASE: ``with
phase(layer, "ec.lock"):`` around a piece of one fop's work, on the
loop or in a pool thread.  Every span (fop or phase) has a small
integer id and its parent's id, so siblings under one ``gather`` are
told from nested calls and self time can be computed, and a start on
``time.perf_counter_ns()``.  Three sinks:

* always, per phase: count, seconds, maximum, in a dict that the
  owning codec or layer hands in and shows in its dump
  (:func:`phase_sums`);
* while ``ENABLED``: the ring, so a slow fop's tree shows its phases
  and the codec flush it waited for;
* while ``ANNOTATE`` is set (ops/batch sets it to
  ``jax.profiler.TraceAnnotation`` in the process that owns the chip;
  this package imports no jax): one annotation per span, named
  ``gftpu:<layer type>.<fop>`` or ``gftpu:<phase>``, with ``trace``,
  ``span`` and ``parent`` as metadata.  It is inert until somebody
  starts ``jax.profiler``; then the program's spans sit on the host
  planes of the trace that also holds the device ops (whose planes
  have a clock of their own: docs/observability.md).

A span that is open is not a thread that is running: tasks interleave
on one loop, and the loop shares the interpreter with the codec's pool
thread.  :class:`LoopMeter` counts what the loop's own thread does with
its time (in ``select``, in callbacks, on a CPU), and a pool-thread
phase opened with ``cpu=True`` reads its thread's CPU clock; both reach
the profiler's trace only (docs/observability.md "The loop's clock").
"""

from __future__ import annotations

import collections
import contextvars
import itertools
import os
import threading
import time

from . import gflog
from .metrics import REGISTRY

log = gflog.get_logger("core.trace")

#: process darkening (GFTPU_NO_OBSERVABILITY / bench metrics-off):
#: while True, observability stays off no matter what volume options
#: say — io-stats' latency-measurement default must not re-arm the
#: histograms on a deliberately darkened process (the bench's off
#: pass mounts volumes whose io-stats init would otherwise undo it)
DARK = os.environ.get("GFTPU_NO_OBSERVABILITY", "") == "1"

#: master gate: False skips ALL span work in the fop hot path (set by
#: bench metrics-off passes and the GFTPU_NO_OBSERVABILITY env, which
#: brick subprocesses inherit so a whole served volume can run dark);
#: a phase then still feeds its sums and nothing else
ENABLED = not DARK

#: root spans slower than this (seconds) log their full tree; 0 = off
#: (diagnostics.slow-fop-threshold)
SLOW_FOP_THRESHOLD = 0.0

#: sink three: a context-manager class called as ``ANNOTATE(name,
#: **metadata)`` once per span while ``ANNOTATE.is_enabled()``.
#: ``None`` here; ops/batch sets ``jax.profiler.TraceAnnotation`` when
#: a codec is built on a jax backend.  Such an annotation records its
#: own start and end (it is closed by whichever thread ends the span,
#: and interleaved tasks need no nesting); while no profiler session
#: runs, ``is_enabled()`` is one C call and no annotation is made.
#: An open one takes more metadata through ``set_metadata(**kw)``
#: (:func:`tag`)
ANNOTATE = None

_RING_DEFAULT = 4096

#: the bounded per-process span ring (circ-buff.c event-history analog);
#: span = (trace_id, depth, layer, op, start_ts, duration_s, err,
#: span_id, parent_id, start_ns): ``start_ts`` is wall clock, derived
#: from ``start_ns`` (``time.perf_counter_ns()``) by one offset taken
#: at import; ``parent_id`` 0 = no parent in this process
SPANS: collections.deque = collections.deque(maxlen=_RING_DEFAULT)

#: (trace_id, depth, span_id, layer) of the span currently open in
#: this context, and fifth, where the span itself opened it, its
#: profiler annotation or ``None`` (:func:`tag`)
CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "gftpu_trace", default=None)

#: span ids: small integers, one sequence per process.  ``next`` on a
#: count is a single C call, so the loop and the codec's pool threads
#: draw from it without a lock
_IDS = itertools.count(1)

#: per thread: the ``(layer name, sums)`` it works for (:func:`adopt`)
_THREAD = threading.local()

_WALL_NS = time.time_ns() - time.perf_counter_ns()

#: per-(layer, op) slow-fop counts — the {layer,op} labels say WHICH
#: door and verb keeps blowing the threshold, not just that one did
SLOW_FOP_COUNTS: dict[tuple[str, str], int] = {}

REGISTRY.register(
    "gftpu_slow_fops_total", "counter",
    "root fops that exceeded diagnostics.slow-fop-threshold, "
    "by layer and op",
    lambda: [({"layer": l, "op": o}, v)
             for (l, o), v in sorted(SLOW_FOP_COUNTS.items())])


def set_ring_size(n: int) -> None:
    """Rebound the span ring (diagnostics.span-ring-size), keeping the
    newest entries."""
    global SPANS
    n = max(64, int(n))
    if SPANS.maxlen != n:
        SPANS = collections.deque(list(SPANS)[-n:], maxlen=n)


def new_trace_id() -> str:
    return os.urandom(8).hex()


def current_id() -> str | None:
    cur = CURRENT.get()
    return cur[0] if cur is not None else None


def arm(trace_id: str) -> None:
    """Adopt a wire-carried trace id for the rest of this context (the
    protocol/server re-arm: brick-graph spans join the client's trace
    instead of minting their own)."""
    CURRENT.set((str(trace_id), 0, 0, "", None))


def enter(layer_name: str, op: str, annot: str | None = None,
          parent: tuple | None = None, push: bool = True,
          fop: bool = True, meta: dict | None = None):
    """Open a span: mint a trace at the outermost call, else nest
    under ``parent`` (a ``CURRENT`` tuple handed over from another
    thread) or under this context's open span.  ``push`` False leaves
    ``CURRENT`` alone: the span is then ended elsewhere, and nothing
    nests under it by context.  Only a ``fop`` span can be a root
    whose slowness is logged; it brings its annotation's name
    (``annot``), a phase is named by :func:`_phase_annot`.  Returns
    the token tuple ``exit_span`` needs."""
    cur = parent if parent is not None else CURRENT.get()
    if cur is None:
        tid, depth, pid, root = new_trace_id(), 0, 0, fop
    else:
        tid, depth, pid, root = cur[0], cur[1] + 1, cur[2], False
    sid = next(_IDS)
    ann = None
    if ANNOTATE is not None and ANNOTATE.is_enabled():
        ann = ANNOTATE(annot or _phase_annot(layer_name, op), trace=tid,
                       span=sid, parent=pid, **(meta or {}))
        ann.__enter__()
    tok = CURRENT.set((tid, depth, sid, layer_name, ann)) if push else None
    return (tid, depth, root, tok, layer_name, op,
            time.perf_counter_ns(), sid, pid, ann)


def tag(**meta) -> None:
    """Metadata that a fop's code learns after its span has begun
    (cluster/dht: the subvolume the fop is routed to), put on the span
    open in this context.  Only sink three carries metadata, so this
    reaches the profiler's annotation and nothing else; while no
    profiler runs it is one context read."""
    cur = CURRENT.get()
    if cur is not None and cur[4] is not None:
        cur[4].set_metadata(**meta)


def _phase_annot(layer_name: str, name: str) -> str:
    """A dotted name is a phase of the table in docs/observability.md
    and stands alone; the one older label (``mesh-codec`` + op) keeps
    its layer's name in front."""
    return "gftpu:" + name if "." in name \
        else f"gftpu:{layer_name}.{name}"


def exit_span(span, duration: float, err: bool) -> None:
    tid, depth, root, tok, layer_name, op, start_ns, sid, pid, ann = span
    if ann is not None:
        ann.__exit__(None, None, None)
    if tok is not None:
        try:
            CURRENT.reset(tok)
        except ValueError:
            pass  # context migrated (sync facade thread hop): root-only
    SPANS.append((tid, depth, layer_name, op, (start_ns + _WALL_NS) * 1e-9,
                  duration, err, sid, pid, start_ns))
    if not root:
        return
    if SLOW_FOP_THRESHOLD and duration >= SLOW_FOP_THRESHOLD:
        key = (layer_name, op)
        SLOW_FOP_COUNTS[key] = SLOW_FOP_COUNTS.get(key, 0) + 1
        tree = render_tree(tid)
        log.warning(7, "slow fop: %s.%s took %.1fms (threshold %.1fms) "
                    "trace %s\n%s", layer_name, op, duration * 1e3,
                    SLOW_FOP_THRESHOLD * 1e3, tid, tree)
        _flight().record("slow_fop", trace=tid, layer=layer_name, op=op,
                         ms=round(duration * 1e3, 3), tree=tree)
    elif err:
        # an error ROOT fop is flight-notable even when fast: its span
        # tree names which layer failed (the bundle's "what broke")
        _flight().record("error_fop", trace=tid, layer=layer_name,
                         op=op, ms=round(duration * 1e3, 3),
                         tree=render_tree(tid))


class phase:
    """A piece of one fop's work below the fop boundary, as a span.

    ``with phase(layer, "ec.fanout", sums):`` on the loop (across
    ``await`` in one task) or in a pool thread.  ``layer`` labels the
    ring entry (the owning layer's instance name).  ``sums`` is sink
    one, a dict the owner keeps on itself: ``(phase, thread id) ->
    [count, seconds, max seconds]``.  The loop and a codec's pool
    threads all end phases; each updates only the rows of its own
    thread id, so no update needs a lock (inserting a key is atomic
    under the GIL), and :func:`phase_sums` adds the threads up.
    ``layer`` ``None`` is for code that does not know who called it
    (a device entry): the thread's owner (:func:`adopt`), else the
    enclosing span's layer and no sums.  ``parent`` is the ``origin``
    of a phase of another thread, for work whose cause is not this
    thread's context.  A phase that begins on one thread and ends on
    another is opened with ``start(push=False)`` and closed with
    ``stop()``: nothing nests under it by context.  ``cpu`` is for a
    phase that begins and ends on one pool thread with no ``await``
    between: where its span has a profiler annotation, the thread's CPU
    clock is read at both ends and the difference put on it as
    ``cpu_ns``, so that the span's time less that is what the thread
    spent off a CPU.  A phase on the loop crosses ``await`` (a thread
    clock over it would count other tasks) and never has it.  Other
    keyword arguments are metadata of the profiler annotation."""

    __slots__ = ("layer", "name", "sums", "parent", "meta", "cpu", "_span",
                 "_t0", "_c0")

    def __init__(self, layer: str | None, name: str,
                 sums: dict | None = None, parent: tuple | None = None,
                 cpu: bool = False, **meta):
        self.layer, self.name, self.sums = layer, name, sums
        self.parent, self.meta, self.cpu = parent, meta, cpu
        self._span = None

    def start(self, push: bool = True) -> "phase":
        if self.layer is None:
            owner = getattr(_THREAD, "owner", None)
            if owner is not None:
                self.layer, self.sums = owner
            else:
                cur = CURRENT.get()
                self.layer = cur[3] if cur is not None else ""
        if ENABLED:
            self._span = enter(self.layer, self.name, None, self.parent,
                               push, False, self.meta)
            self._t0 = self._span[6]
            if self.cpu and self._span[9] is not None:
                self._c0 = time.thread_time_ns()
        else:
            self._t0 = time.perf_counter_ns()
        return self

    __enter__ = start

    @property
    def origin(self) -> tuple | None:
        """The ``CURRENT`` tuple this phase was opened under: what a
        sibling on another thread passes as ``parent``."""
        s = self._span
        return None if s is None else (s[0], s[1] - 1, s[8], s[4])

    def __exit__(self, et=None, ev=None, tb=None) -> bool:
        dt = (time.perf_counter_ns() - self._t0) * 1e-9
        sums = self.sums
        if sums is not None:
            key = (self.name, threading.get_ident())
            s = sums.get(key)
            if s is None:
                s = sums[key] = [0, 0.0, 0.0]
            s[0] += 1
            s[1] += dt
            if dt > s[2]:
                s[2] = dt
        if self._span is not None:
            if self.cpu and self._span[9] is not None:
                self._span[9].set_metadata(
                    cpu_ns=time.thread_time_ns() - self._c0)
            exit_span(self._span, dt, et is not None)
        return False

    def stop(self, err: bool = False) -> None:
        self.__exit__(err or None)


def adopt(layer: str, sums: dict) -> None:
    """This thread works for one owner from now on (a codec's pool
    thread, as the pool's initializer): a ``phase(None, ...)`` opened
    on it takes that layer name and feeds those sums."""
    _THREAD.owner = (layer, sums)


def phase_sums(sums: dict) -> dict[str, dict]:
    """Sink one as a dump shows it: ``{phase: {count, seconds,
    max_ms}}``, the threads' rows added up."""
    out: dict[str, list] = {}
    for (name, _thread), (c, secs, mx) in list(sums.items()):
        cur = out.setdefault(name, [0, 0.0, 0.0])
        cur[:] = cur[0] + c, cur[1] + secs, max(cur[2], mx)
    return {name: {"count": c, "seconds": round(secs, 6),
                   "max_ms": round(mx * 1e3, 3)}
            for name, (c, secs, mx) in sorted(out.items())}


#: seconds between two samples of a metered loop: a constant, not an
#: option (ten a second cost nothing, and a reader of the trace gets
#: 300 of them beside a 30 s window's device ops)
SAMPLE_PERIOD = 0.1

#: the sample's annotation; it carries no ``span`` key, so it enters no
#: span tree (an always-open span would own every idle gap)
SAMPLE = "gftpu:loop.sample"

#: what a reading holds, and a sample's metadata beside
#: ``slowest_pass_ns``: each the period's delta
SAMPLE_KEYS = ("passes", "busy_ns", "select_ns", "busy_sq", "cpu_ns",
               "polls", "poll_ns")


class LoopMeter:
    """What one event loop's thread does with its time: counters,
    always on, updated once a PASS of the loop by a wrapper around its
    selector's ``select`` (two ``perf_counter_ns`` reads, integer adds).

    A pass is what the loop does between two calls of ``select``: it
    runs every callback that was ready.  ``select_ns`` is time inside
    ``select`` (nothing runnable, or a poll), ``busy_ns`` the rest (the
    loop running callbacks, or wanting to).  ``busy_sq`` sums each
    pass's length squared, so ``busy_sq / busy_ns`` is the
    length-weighted mean pass: the pass that an answer arriving at a
    random moment lands in, and half of it is what the answer waits
    before the loop looks at its socket.  ``polls`` and ``poll_ns`` are
    the part of ``passes`` and ``select_ns`` in which ``select`` was
    called with no time to wait (callbacks were ready): a loop whose
    ``select_ns`` is all polls has no spare time, whatever its share
    of a CPU says, and only ``select_ns - poll_ns`` is the loop with
    nothing to do.  The thread's CPU clock is a system call and is not
    read per pass: the loop reads it for each sample (below), a dump
    reads it when asked, so ``busy_ns`` less ``cpu_ns`` is the loop in
    a callback and off a CPU (waiting for the interpreter a pool thread
    holds, or the whole process frozen).

    Every :data:`SAMPLE_PERIOD` a timer on the loop asks whether a
    profiler session runs (``ANNOTATE.is_enabled()``), and does no more
    while none does.  While one runs every tick closes one
    :data:`SAMPLE` annotation, open since the tick before, with the
    period's deltas as its metadata, and opens the next: the loop's
    state lies on the host plane of the trace that holds the device ops
    and the program's spans.

    One meter a loop, found again through the wrapper itself
    (:meth:`install`); each user takes it off again (:meth:`remove`) and
    the last one restores ``select`` and cancels the timer.  Only the
    loop's thread writes the counters."""

    __slots__ = ("loop", "selector", "thread", "users", "passes", "busy_ns",
                 "select_ns", "busy_sq", "polls", "poll_ns",
                 "slowest_pass_ns", "_select", "_since", "_slow_ns",
                 "_cpu0", "_last", "_ann", "_timer")

    @classmethod
    def install(cls, loop) -> "LoopMeter | None":
        """The meter of ``loop`` (the running loop: the caller is on its
        thread), made on first use.  ``None`` for a loop that polls
        through no selector of its own: it stays unmetered."""
        selector = getattr(loop, "_selector", None)
        select = getattr(selector, "select", None)
        if select is None:
            return None
        meter = getattr(select, "__self__", None)
        if not isinstance(meter, cls):
            meter = cls(loop, selector, select)
        meter.users += 1
        return meter

    def __init__(self, loop, selector, select):
        self.loop, self.selector, self._select = loop, selector, select
        self.thread = threading.get_ident()
        self.users = 0
        self.passes = self.busy_ns = self.select_ns = self.busy_sq = 0
        self.polls = self.poll_ns = 0
        self.slowest_pass_ns = self._slow_ns = 0
        self._ann = self._last = None
        self._cpu0 = time.thread_time_ns()
        self._since = time.perf_counter_ns()
        selector.select = self._pass
        self._timer = loop.call_later(SAMPLE_PERIOD, self._tick)

    def remove(self) -> None:
        """One user less; the last leaves nothing on the loop."""
        self.users -= 1
        if self.users > 0:
            return
        self._timer.cancel()
        self._sample(False)
        del self.selector.select

    def _pass(self, timeout=None):
        t0 = time.perf_counter_ns()
        busy = t0 - self._since
        self.passes += 1
        self.busy_ns += busy
        self.busy_sq += busy * busy
        if busy > self._slow_ns:
            self._slow_ns = busy
        ready = self._select(timeout)
        self._since = t1 = time.perf_counter_ns()
        self.select_ns += t1 - t0
        if timeout == 0:
            self.polls += 1
            self.poll_ns += t1 - t0
        return ready

    def _reading(self) -> tuple:
        """:data:`SAMPLE_KEYS` as of now, on the loop's thread: the pass
        under way counts as busy up to here, so ``busy_ns + select_ns``
        of two readings differ by the time between them."""
        return (self.passes,
                self.busy_ns + time.perf_counter_ns() - self._since,
                self.select_ns, self.busy_sq,
                time.thread_time_ns() - self._cpu0,
                self.polls, self.poll_ns)

    def _tick(self) -> None:
        self._timer = self.loop.call_later(SAMPLE_PERIOD, self._tick)
        self._sample(ANNOTATE is not None and ANNOTATE.is_enabled())

    def _sample(self, on: bool) -> None:
        """The open sample (begun at the tick before) gets the deltas of
        its period and that period's slowest pass, and ends; while
        ``on`` the next one begins."""
        ann, self._ann = self._ann, None
        if ann is None and not on:
            return
        before, self._last = self._last, self._reading()
        slow, self._slow_ns = self._slow_ns, 0
        if slow > self.slowest_pass_ns:
            self.slowest_pass_ns = slow
        if ann is not None:
            ann.set_metadata(slowest_pass_ns=slow, **{
                k: b - a for k, a, b in zip(SAMPLE_KEYS, before,
                                            self._last)})
            ann.__exit__(None, None, None)
        if on:
            self._ann = ANNOTATE(SAMPLE)
            self._ann.__enter__()

    def dump(self) -> dict:
        """The statedump's ``loop`` section: is this loop full
        (``select_s`` less ``poll_s`` is all the time it had nothing to
        do), and is it computing (``cpu_s`` near ``busy_s``) or waiting
        for the interpreter.  Since the meter was installed, the
        counters as of the loop's last call of ``select``; any thread
        may ask (the CPU clock is read as the loop's thread's own)."""
        cpu = time.clock_gettime_ns(
            time.pthread_getcpuclockid(self.thread)) - self._cpu0
        return {"metered": True, "passes": self.passes,
                "busy_s": round(self.busy_ns * 1e-9, 6),
                "select_s": round(self.select_ns * 1e-9, 6),
                "polls": self.polls, "poll_s": round(self.poll_ns * 1e-9, 6),
                "cpu_s": round(cpu * 1e-9, 6),
                "weighted_pass_ms": round(
                    self.busy_sq / max(self.busy_ns, 1) * 1e-6, 6),
                "slowest_pass_ms": round(
                    max(self.slowest_pass_ns, self._slow_ns) * 1e-6, 6)}


def _flight():
    """Late import: flight imports tracing at module top (for the span
    ring in its snapshot) — this side of the cycle resolves lazily."""
    from . import flight
    return flight


def spans_for(trace_id: str) -> list[tuple]:
    return [s for s in list(SPANS) if s[0] == trace_id]


def recent_spans(limit: int = 200) -> list[dict]:
    """Newest spans as dicts (statedump's trace_spans section)."""
    out = []
    for s in list(SPANS)[-limit:]:
        tid, depth, layer_name, op, start, dur, err = s[:7]
        sid, pid = s[7:9] if len(s) > 8 else (0, 0)
        out.append({"trace": tid, "depth": depth, "layer": layer_name,
                    "op": op, "start": round(start, 6),
                    "ms": round(dur * 1e3, 3), "err": err,
                    "span": sid, "parent": pid})
    return out


def render_tree(trace_id: str) -> str:
    """The trace's spans as an indented tree (slow-fop log format:
    one line per span, two spaces per depth, duration in ms).  Each
    span follows its parent, siblings by start; a span whose parent is
    not in the ring (the other side of the wire, or evicted) stands
    where its start puts it among the roots."""
    spans = sorted(spans_for(trace_id), key=lambda s: (s[4], s[1]))
    ids = {s[7] for s in spans if len(s) > 8}
    kids: dict[int, list] = {}
    for s in spans:
        pid = s[8] if len(s) > 8 and s[8] in ids else 0
        kids.setdefault(pid, []).append(s)
    lines = []
    stack = kids.get(0, [])[::-1]
    while stack:
        s = stack.pop()
        mark = " !!" if s[6] else ""
        lines.append(f"{'  ' * s[1]}{s[2]}.{s[3]} "
                     f"{s[5] * 1e3:.2f}ms{mark}")
        if len(s) > 8:
            stack.extend(kids.get(s[7], [])[::-1])
    return "\n".join(lines)


__all__ = ["ENABLED", "SLOW_FOP_THRESHOLD", "SLOW_FOP_COUNTS", "SPANS",
           "CURRENT", "ANNOTATE", "adopt", "arm",
           "enter", "exit_span", "phase", "phase_sums", "tag", "current_id",
           "new_trace_id", "recent_spans", "render_tree",
           "set_ring_size", "spans_for"]
