"""Share of the memory roofline at which the window's coding ran: the
bytes the code has to move for what the benchmark sent (shapes only,
``harness/work.py``) at the chip's peak, over the summed device time of
every operation in the traced window.  This process puts nothing else
on the chip, so that sum is the coding launches whole: layout copies,
reshapes and kernel.  No operation's name is looked at."""

from benchmarks.harness import work
from benchmarks.harness.traffic import READ, WRITE


def read(run, op: str):
    if not run.trace or run.trace["op_s"] <= 0:
        return None
    g = run.config["geometry"]
    k, r = g["data"], g["redundancy"]
    n = k + r
    if op == "parity":
        moved = work.parity_bytes(run.user_bytes(WRITE), k, r)
    else:
        missing = sum(1 for d in run.mix.get("bricks_down", [])
                      if d % n < k)
        if not missing:
            return None
        moved = work.reconstruct_bytes(run.user_bytes(READ), k, missing)
    return work.roofline_share(moved, run.device["kind"], run.trace["op_s"])
