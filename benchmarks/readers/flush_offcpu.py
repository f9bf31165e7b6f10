"""Milliseconds per ``codec.flush`` of one coding operation that the
codec's pool thread spent on no CPU outside its wait for the device.
The pool-thread phases (``codec.flush`` and, beneath it, ``codec.gather``,
``codec.h2d``, ``codec.launch``, ``codec.d2h``, ``codec.scatter``) begin
and end on one thread with no ``await`` between, and carry that
thread's CPU time as ``cpu_ns`` (``glusterfs_tpu/core/tracing.py``
``phase(cpu=True)``), so a span's duration less its ``cpu_ns`` is what
the thread spent off a CPU under it.  Over the flushes of ``op`` that
began inside the window: that difference of ``codec.gather``,
``codec.scatter``, ``codec.h2d``, ``codec.launch`` and of the flush's
self time, which is the flush's own difference less its ``codec.d2h``
children's (found by ``parent``): the wait for the interpreter the
loop holds, plus whatever ``jnp.asarray`` and the dispatch block on;
the wait in ``np.asarray`` for the kernel and the copy back is left
out.

For the reader, on an earlier line (``flush_offcpu``): per phase name
the count, the mean duration and the mean CPU, ms.  A program whose
flush spans carry no ``cpu_ns`` (an older commit), an untraced run and
a window without such a flush leave nothing to read, and nothing is
returned."""

import glob
import os

from benchmarks.harness import spans

FLUSH = spans.PREFIX + "codec.flush"
D2H = spans.PREFIX + "codec.d2h"


def offcpu(events: list, w0: float, w1: float, op: str):
    """``events`` as ``spans.events_of`` gives them.  (ms per flush,
    ``{phase: [count, mean ms, mean CPU ms]}``); ``None`` where no
    flush of ``op`` that began in [w0, w1) carries ``cpu_ns``."""
    flushes = {sid for name, start, _d, _t, sid, _p, meta in events
               if name == FLUSH and meta.get("op") == op
               and "cpu_ns" in meta and w0 <= start < w1}
    if not flushes:
        return None
    off, phases = 0.0, {}
    for name, _start, dur, _trace, sid, parent, meta in events:
        if "cpu_ns" not in meta or not (
                sid in flushes or parent in flushes):
            continue
        cpu = float(meta["cpu_ns"])
        acc = phases.setdefault(name[len(spans.PREFIX):], [0, 0.0, 0.0])
        acc[:] = acc[0] + 1, acc[1] + dur, acc[2] + cpu
        if sid in flushes:
            off += dur - cpu
        elif name == D2H:
            off -= dur - cpu
    return off * 1e-6 / len(flushes), {
        n: [c, wall * 1e-6 / c, cpu * 1e-6 / c]
        for n, (c, wall, cpu) in sorted(phases.items())}


def read(run, op: str):
    sp = spans.of_run(run)
    if sp is None:
        return None
    found = glob.glob(os.path.join(
        run.volume.workdir, "trace", "plugins", "profile", "*",
        "*.xplane.pb"))
    if len(found) != 1:
        return None
    got = offcpu(spans.events_of(found[0]), sp.w0, sp.w1, op)
    if got is None:
        return None
    run.note("flush_offcpu", op=op, ms_per_flush=got[0], phases=got[1])
    return got[0]
