"""Share of the memory roofline at which the heal window's coding ran:
the bytes a rebuild of what the window rebuilt has to move (shapes only,
``harness/work.py heal_bytes``: k chunks in and one out for each
returning brick, per stripe) at the chip's peak, over the summed device
time of every operation in the traced window.  It counts what the
rebuild needs, not what is launched: a launch that codes rows the heal
throws away reads as waste.  No operation's name is looked at."""

from benchmarks.harness import work


def read(run):
    if not run.trace or run.trace["op_s"] <= 0 or not run.rebuilt_bytes:
        return None
    k = run.config["geometry"]["data"]
    moved = work.heal_bytes(run.rebuilt_bytes, k, len(run.traffic.returning))
    return work.roofline_share(moved, run.device["kind"], run.trace["op_s"])
