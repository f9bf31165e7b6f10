"""Mean time of one fop at the layers of one type in the mounted graph
(``core/layer._FopStats``: ``latency_sum`` over ``count``), as the
window's delta."""


def read(run, layer: str, fop: str):
    n = run.delta("fops", layer, fop, "count")
    if n <= 0:
        return None
    return run.delta("fops", layer, fop, "seconds") / n * 1e3
