"""The profiler's trace of one window, reduced to what the metrics
read.  This process owns the chip and is the gfapi client, so it starts
and stops ``jax.profiler`` itself; nothing in the program does.

Two steps, kept apart so that the second can be held to a recorded
trace in the tests: :func:`events_of` turns an ``.xplane.pb`` into plain
lists, :func:`reduce` turns those lists into seconds.  Neither looks
for a kernel by name.
"""

from __future__ import annotations

import glob
import os
import re
import shutil

WINDOW = "bench_window"
#: a device plane's line that holds one event per executed operation;
#: its other lines (modules, steps) cover the same time again
OPS_LINE = "XLA Ops"


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # no Python frames: they slow the host
    opts.host_tracer_level = 2    # TraceAnnotations
    shutil.rmtree(log_dir, ignore_errors=True)
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop(log_dir: str) -> str:
    """Stop the profiler; the path of the trace it wrote."""
    import jax

    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}: {found}")
    return found[0]


def annotate():
    import jax

    return jax.profiler.TraceAnnotation


def events_of(path: str) -> dict:
    """``{"device": {plane: [[name, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...], "lines": {plane: [line
    names]}}``: every operation of every device plane's operations
    line, and the host's window and in-flight annotations."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out: dict = {"device": {}, "host": [], "lines": {}}
    for plane in data.planes:
        lines = list(plane.lines)
        out["lines"][plane.name] = [l.name for l in lines]
        if plane.name.startswith("/device:"):
            for line in lines:
                if line.name == OPS_LINE:
                    out["device"][plane.name] = [
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events
                                if e.name == WINDOW
                                or e.name.endswith("_in_flight")]
    return out


def _union(spans: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(spans):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


_HEAD = re.compile(
    r"^%?([\w.\-]+) = (\w+)\[([\d,]*)\](?:\{[^}]*\})? ?([\w\-]*)")


def op_label(name: str) -> str:
    """An operation as the breakdown prints it: the HLO instruction's
    name, its opcode where that says more, element type and shape
    (``run.1_custom-call_u8_2_512_512``, ``copy_u8_1_2_512_512``), or
    the text cut short where it is no HLO.  A name in the ledger has no
    spaces or brackets."""
    m = _HEAD.match(name)
    if m:
        what, dtype, shape, opcode = m.groups()
        if opcode and opcode not in what:
            what += "_" + opcode
        name = "_".join(x for x in (what, dtype,
                                    shape.replace(",", "_")) if x)
    return re.sub(r"[^A-Za-z0-9.\-]+", "_", name)[:64]


def reduce(events: dict, top: int = 10) -> dict | None:
    """Seconds of the traced window: its length, the time in which an
    operation ran on the device (union of intervals, mean over the
    devices), the sum of every device operation's own time (over all
    devices), the operations that took most, and the idle gaps by what
    the host had in flight.  ``None`` where the trace holds no window
    or no device."""
    win = [e for e in events["host"] if e[0] == WINDOW]
    if len(win) != 1 or not events["device"]:
        return None
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    busy, op_s, custom_s = [], 0.0, 0.0
    by_op: dict[str, float] = {}
    gaps: list[tuple[float, float]] = []
    for ops in events["device"].values():
        spans = []
        for name, start, dur in ops:
            a, b = max(start, w0), min(start + dur, w1)
            if b <= a:
                continue
            spans.append((a, b))
            op_s += b - a
            if "custom-call" in name:
                custom_s += b - a
            label = op_label(name)
            by_op[label] = by_op.get(label, 0.0) + (b - a)
        merged = _union(spans)
        busy.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for span in merged for x in span] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    # an idle gap belongs to whatever the host had in flight over it,
    # piece by piece; what no annotation covers is the host's own
    host = sorted((s, s + d, n) for n, s, d in events["host"]
                  if n != WINDOW)
    by_gap: dict[str, float] = {}
    first = 0  # annotations follow one another, so one pass serves
    for a, b in sorted(gaps):
        while first < len(host) and host[first][1] <= a:
            first += 1
        covered, i = 0.0, first
        while i < len(host) and host[i][0] < b:
            s, e, name = host[i]
            piece = min(e, b) - max(s, a)
            if piece > 0:
                by_gap[name] = by_gap.get(name, 0.0) + piece
                covered += piece
            i += 1
        if b - a - covered > 0:
            by_gap["nothing_in_flight"] = by_gap.get(
                "nothing_in_flight", 0.0) + (b - a - covered)
    ns = 1e-9

    def ranked(d: dict) -> list:
        return [[k, v * ns] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"window_s": (w1 - w0) * ns,
            "busy_s": sum(busy) / len(busy) * ns,
            "op_s": op_s * ns,
            # for the reader only: hand-written kernels alone, by name
            "custom_call_s": custom_s * ns,
            "device_ops": ranked(by_op), "idle_gaps": ranked(by_gap)}
