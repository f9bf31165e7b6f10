"""A percentile over every operation of the given kinds in the window,
each timed at the door from call to return by the benchmark itself
(``_FopStats``' percentiles are log2 bucket edges and move in powers of
two).  A failed operation keeps its time: it was waited for."""

import statistics

from benchmarks.harness.traffic import READ, WRITE

KIND = {"read": READ, "write": WRITE}


def read(run, kinds: list, q: int = 99):
    want = {KIND[k] for k in kinds}
    times = [op[1] - op[0] for op in run.ops if op[2] in want]
    if len(times) < 100:
        return None  # no 99th percentile of fewer
    return statistics.quantiles(times, n=100, method="inclusive")[q - 1] * 1e3
