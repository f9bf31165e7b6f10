"""User bytes of the given kinds acknowledged in the window, over the
window: from its start to the return of the last job's last call (its
closing ``fsync`` where it wrote).  A failed or short operation is no
bytes."""

from benchmarks.harness.traffic import READ, WRITE

KIND = {"read": READ, "write": WRITE}


def read(run, kinds: list):
    if run.elapsed <= 0:
        return None
    return sum(run.user_bytes(KIND[k]) for k in kinds) / 2**20 / run.elapsed
