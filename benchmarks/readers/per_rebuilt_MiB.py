"""One counter of the mounted graph (a path into ``snapshot.counters``)
as the window's delta, per user MiB the heal window rebuilt."""


def read(run, num: list):
    if not run.rebuilt_bytes:
        return None
    return run.delta(*num) / (run.rebuilt_bytes / 2**20)
