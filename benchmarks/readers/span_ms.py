"""Milliseconds per door operation of one kind that the program spent
in a set of its own spans (``harness/spans.py``: the spans the program
writes into the profiler's trace).  The window's total of the self time
of the spans named ``self_time`` and of the whole duration of those
named ``whole`` (``minus`` is taken off, whole; ``not_under`` leaves
out what lies beneath another span; ``less_device_busy`` takes off the
time in which the device ran an op, for a span that waits for it), over
the number of operations of that kind the window completed.  Work in
the background (a read-ahead's fop) and no work (an io-cache hit) then
count as what they are per user operation."""

from benchmarks.harness import spans
from benchmarks.harness.traffic import READ, WRITE

KIND = {"read": READ, "write": WRITE}


def read(run, kind: str, less_device_busy: bool = False, **lists):
    sp = spans.of_run(run)
    n = sum(1 for op in run.ops if op[2] == KIND[kind])
    if sp is None or n == 0:
        return None
    ns = sp.total(kind, **lists)
    if less_device_busy:
        busy = sp.device_busy()
        if busy is None:
            return None
        ns -= busy
    return ns * 1e-6 / n
