"""Device-busy time (union of the intervals in which an operation ran
on the chip, from the profiler's trace) per user MiB the heal window
rebuilt."""


def read(run):
    if not run.trace or not run.rebuilt_bytes or run.trace["busy_s"] <= 0:
        return None
    return run.trace["busy_s"] * 1e3 / (run.rebuilt_bytes / 2**20)
