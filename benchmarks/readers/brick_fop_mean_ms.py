"""Mean time of one fop on the bricks (``volume profile``, io-stats on
each brick), over the bricks that are up, as the window's delta."""


def read(run, fop: str):
    n = run.delta(fop, "count", of="profile")
    if n <= 0:
        return None
    return run.delta(fop, "seconds", of="profile") / n * 1e3
