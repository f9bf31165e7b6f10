"""User MiB a heal window brought back to full redundancy, over the
window: from the sweep's call to its return, or to ``--seconds`` where
that comes first.  What was rebuilt is read off the returning bricks'
fragment files at the window's two edges (``run.py returned_bytes``),
not from a counter of the program's.  A window that healed nothing is
nothing returned."""


def read(run):
    if not run.rebuilt_bytes or run.elapsed <= 0:
        return None
    return run.rebuilt_bytes / 2**20 / run.elapsed
