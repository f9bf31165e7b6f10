"""One counter of the mounted graph over another, both as the window's
deltas (paths into ``snapshot.counters``).  Nothing counted below is
nothing returned."""


def read(run, num: list, den: list):
    d = run.delta(*den)
    if d <= 0:
        return None
    return run.delta(*num) / d
