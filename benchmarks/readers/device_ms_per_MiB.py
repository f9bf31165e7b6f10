"""Device-busy time (union of the intervals in which an operation ran
on the chip, from the profiler's trace) per user MiB of one kind moved
in the traced window."""

from benchmarks.harness.traffic import READ, WRITE

KIND = {"read": READ, "write": WRITE}


def read(run, kind: str):
    mib = run.user_bytes(KIND[kind]) / 2**20
    if not run.trace or mib <= 0 or run.trace["busy_s"] <= 0:
        return None
    return run.trace["busy_s"] * 1e3 / mib
