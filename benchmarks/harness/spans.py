"""The program's own spans, read from the profiler's trace of the
window.  ``glusterfs_tpu/core/tracing.py`` writes every fop span and
every phase as a ``TraceAnnotation`` named ``gftpu:<layer type>.<fop>``
or ``gftpu:<phase>`` with ``trace``, ``span`` and ``parent`` as its
metadata, so they sit on the host planes of the ``.xplane.pb`` that
also holds the device ops.  The spans are read against each other
(tree, self time); from the device planes only durations are taken,
because their clock is not the host planes' (:meth:`Spans.device_busy`).
A program without spans (an older commit) leaves nothing to read, and
every reader here returns ``None``.

Two steps, kept apart as ``devtrace`` keeps them: :func:`events_of`
turns the file into plain lists, :class:`Spans` turns lists into
seconds, so that the second is held to a recorded file in the tests.
"""

from __future__ import annotations

import bisect
import glob
import heapq
import os

from benchmarks.harness import devtrace

PREFIX = "gftpu:"
#: a door operation's kind, by the fop of its tree's root span
KIND = {"write": ".writev", "read": ".readv"}
ROOT = "root"  # in a ``whole`` list: each tree's root span


def events_of(path: str) -> list:
    """``[[name, start_ns, dur_ns, trace, span, parent, {other
    metadata}], ...]``: every ``gftpu:*`` event of every host plane."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                meta = dict(e.stats)
                if "span" not in meta:
                    continue
                out.append([e.name, e.start_ns, e.duration_ns,
                            str(meta.pop("trace", "")),
                            int(meta.pop("span")),
                            int(meta.pop("parent", 0)), meta])
    return out


def load(path: str) -> dict:
    """``devtrace.events_of`` (the window, the benchmark's in-flight
    annotations, the device ops) with the program's spans beside them
    under ``"spans"``: what :class:`Spans` takes, and the shape of
    ``tests/benchmarks/data/recorded_spans.json``."""
    events = devtrace.events_of(path)
    events["spans"] = events_of(path)
    return events


def of_run(run):
    """The traced run's :class:`Spans`, read once; ``None`` where the
    run was not traced or the program wrote no span."""
    if "_spans" not in run.__dict__:
        found = glob.glob(os.path.join(
            run.volume.workdir, "trace", "plugins", "profile", "*",
            "*.xplane.pb")) if run.args.trace else []
        run.__dict__["_spans"] = Spans.of(load(found[0])) \
            if len(found) == 1 else None
    return run.__dict__["_spans"]


def _match(name: str, names) -> bool:
    return any(name == n or (n.endswith("*") and name.startswith(n[:-1]))
               for n in names)


def _covered(a: float, b: float, pieces) -> float:
    """Length of [a, b] that the intervals ``pieces`` cover."""
    return sum(min(y, b) - max(x, a) for x, y in devtrace._union(
        [(max(x, a), min(y, b)) for x, y in pieces
         if min(y, b) > max(x, a)]))


class Spans:
    """The spans of one traced window as a forest: each span clipped
    to the window, hung under its ``parent``; a span whose parent is
    not there (it began before the window, or is the other side of the
    wire) is a root.  Times are nanoseconds of the profiler's clock."""

    @classmethod
    def of(cls, events: dict) -> "Spans | None":
        win = [e for e in events["host"] if e[0] == devtrace.WINDOW]
        if len(win) != 1 or not events.get("spans"):
            return None
        return cls(events, win[0][1], win[0][1] + win[0][2])

    def __init__(self, events: dict, w0: float, w1: float):
        self.w0, self.w1 = w0, w1
        self.name, self.a, self.b, self.whole_in = [], [], [], []
        ids, pids = {}, []
        # in the order they began: a parent stands before its children
        for name, start, dur, _trace, sid, pid, _meta in sorted(
                events["spans"], key=lambda e: e[1]):
            a, b = max(start, w0), min(start + dur, w1)
            if b <= a:
                continue
            ids[sid] = len(self.name)
            pids.append(pid)
            self.name.append(name)
            self.a.append(a)
            self.b.append(b)
            self.whole_in.append(start >= w0 and start + dur <= w1)
        #: index of each span's parent, -1 for none
        self.parent = [ids.get(p, -1) for p in pids]
        self.kids: dict[int, list[int]] = {}
        self.depth = [0] * len(self.name)
        self.root = list(range(len(self.name)))
        for i, p in enumerate(self.parent):
            if 0 <= p < i:
                self.kids.setdefault(p, []).append(i)
                self.depth[i] = self.depth[p] + 1
                self.root[i] = self.root[p]
            else:
                self.parent[i] = -1
        self._kind = [next((k for k, suffix in KIND.items()
                            if self.name[r].endswith(suffix)), "")
                      for r in self.root]
        self.device = {plane: sorted((s, s + d) for _n, s, d in ops
                                     if s + d > w0 and s < w1)
                       for plane, ops in events["device"].items()}
        self.in_flight = [e for e in events["host"]
                          if e[0] != devtrace.WINDOW]

    # -- one span ----------------------------------------------------------

    def dur(self, i: int) -> float:
        return self.b[i] - self.a[i]

    def self_time(self, i: int) -> float:
        """Its duration less what its children cover of it."""
        return self.dur(i) - _covered(
            self.a[i], self.b[i],
            [(self.a[c], self.b[c]) for c in self.kids.get(i, [])])

    def under(self, i: int, names) -> bool:
        p = self.parent[i]
        while p >= 0:
            if _match(self.name[p], names):
                return True
            p = self.parent[p]
        return False

    def of_kind(self, kind: str):
        """Every span of a tree whose root is a fop of that kind."""
        return (i for i, k in enumerate(self._kind) if k == kind)

    def door_ops(self, kind: str) -> list:
        """The benchmark's own in-flight annotations of that kind
        inside the window (one per operation while one job runs)."""
        return [e for e in self.in_flight if e[0] == kind + "_in_flight"
                and e[1] >= self.w0 and e[1] + e[2] <= self.w1]

    # -- sums over the window ----------------------------------------------

    def total(self, kind: str, self_time=(), whole=(), not_under=(),
              minus=()) -> float:
        """Nanoseconds, over the trees of one kind of door operation:
        the self time of the spans named in ``self_time``, plus the
        whole duration of those in ``whole`` (the topmost of nested
        matches; ``"root"`` is each tree's root), less the whole
        duration of the topmost in ``minus``; spans beneath one named
        in ``not_under`` are left out.  A name ending in ``*`` is a
        prefix."""
        named = {n: (_match(n, self_time), _match(n, whole),
                     _match(n, minus)) for n in set(self.name)}
        total = 0.0
        for i in self.of_kind(kind):
            in_self, in_whole, in_minus = named[self.name[i]]
            is_root = self.root[i] == i
            if not (in_self or in_whole or in_minus or is_root) or \
                    (not_under and self.under(i, not_under)):
                continue
            if in_self:
                total += self.self_time(i)
            for hit, names, sign in ((in_whole, whole, 1),
                                     (in_minus, minus, -1)):
                if (hit and not self.under(i, names)) or \
                        (is_root and ROOT in names):
                    total += sign * self.dur(i)
        return total

    def device_busy(self) -> float | None:
        """Nanoseconds in which an op ran on a device inside the
        window (union per device, summed); ``None`` without a device
        plane.  Only durations are taken from the device planes: their
        clock stands up to a millisecond off the host planes' (PERF.md
        §6, PR 24), so no host span is laid against a device op."""
        if not self.device:
            return None
        return sum(b - a for ops in self.device.values()
                   for a, b in devtrace._union(
                       [(max(s, self.w0), min(e, self.w1)) for s, e in ops]))

    def means(self) -> dict[str, list]:
        """``{name: [count, mean ms]}`` over the spans that lie whole
        inside the window: beside ``ec_*_ms`` and ``wire_*_ms``, which
        time the same calls from outside."""
        acc: dict[str, list] = {}
        for i, name in enumerate(self.name):
            if self.whole_in[i]:
                cur = acc.setdefault(name, [0, 0.0])
                cur[0] += 1
                cur[1] += self.dur(i)
        return {n: [c, t / c * 1e-6] for n, (c, t) in sorted(acc.items())}

    # -- the device's idle time, by what the program was doing -------------

    def timeline(self) -> list[tuple[float, float, int]]:
        """The window cut into pieces ``(from, to, span)``, each owned
        by the innermost span open over it: the deepest in the causal
        tree, so a flush's phases in the pool thread win over the
        ``ec.codec_wait`` that covers them on the loop; of equals, the
        one that began last.  Pieces no span covers are left out."""
        edges = sorted([(self.a[i], 1, i) for i in range(len(self.name))]
                       + [(self.b[i], 0, i) for i in range(len(self.name))],
                       key=lambda e: (e[0], e[1]))
        live, heap, out = set(), [], []
        at = self.w0
        for t, opens, i in edges:
            while heap and heap[0][2] not in live:
                heapq.heappop(heap)
            if heap and t > at:
                out.append((at, t, heap[0][2]))
            at = t
            if opens:
                live.add(i)
                heapq.heappush(heap, (-self.depth[i], -self.a[i], i))
            else:
                live.discard(i)
        return out

    def idle_by_span(self) -> tuple[float, dict[str, float]] | None:
        """(the device's idle nanoseconds in the window, the part of
        them under each span name by :meth:`timeline`); several
        devices add up.  ``None`` without a device plane."""
        if not self.device:
            return None
        pieces = self.timeline()
        starts = [p[0] for p in pieces]
        idle, by = 0.0, {}
        for ops in self.device.values():
            busy = devtrace._union([(max(s, self.w0), min(e, self.w1))
                                    for s, e in ops])
            edges = [self.w0] + [x for span in busy for x in span] \
                + [self.w1]
            for a, b in zip(edges[::2], edges[1::2]):
                if b <= a:
                    continue
                idle += b - a
                k = max(bisect.bisect_right(starts, a) - 1, 0)
                while k < len(pieces) and pieces[k][0] < b:
                    x, y, i = pieces[k]
                    if min(y, b) > max(x, a):
                        by[self.name[i]] = by.get(self.name[i], 0.0) \
                            + min(y, b) - max(x, a)
                    k += 1
        return idle, by

    def slowest_tree(self, limit: int = 80) -> list[str]:
        """The tree of the window's slowest door operation: one line a
        span, two spaces a level, milliseconds."""
        roots = [i for i in range(len(self.name)) if self.root[i] == i
                 and self.name[i].endswith(tuple(KIND.values()))]
        if not roots:
            return []
        lines, stack = [], [max(roots, key=self.dur)]
        while stack and len(lines) < limit:
            i = stack.pop()
            lines.append(f"{'  ' * self.depth[i]}{self.name[i][len(PREFIX):]}"
                         f" {self.dur(i) * 1e-6:.3f}")
            stack += sorted(self.kids.get(i, []),
                            key=self.a.__getitem__, reverse=True)
        return lines
