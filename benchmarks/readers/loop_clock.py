"""What the client's one loop did with the traced window, from the
samples its meter wrote into the profiler's trace
(``glusterfs_tpu/core/tracing.py`` ``LoopMeter``): ten ``gftpu:loop.sample``
annotations a second on the loop's own host line, each open for one
period and carrying the period's deltas: ``passes``, ``busy_ns`` (from
``select``'s return to its next call: callbacks), ``select_ns``,
``busy_sq`` (each pass's length squared, summed), ``cpu_ns`` (the loop
thread's CPU clock), ``slowest_pass_ns``, and ``polls`` and ``poll_ns``
(the calls of ``select`` with no time to wait, callbacks being ready,
and their part of ``select_ns``).  They carry no
``span`` key, so ``harness/spans.py events_of`` drops them and no span
tree holds them; this reader takes them from the ``.xplane.pb`` itself.

Over the samples that overlap the window (``bench_window``, as
``harness/spans.py`` finds it), a sample that straddles an edge
weighted by its overlap, ``what`` chooses one number:

* ``cpu``: ``cpu_ns`` over the time the samples cover: the loop's
  thread on a CPU (the poll's own system time included);
* ``offcpu``: ``busy_ns - cpu_ns`` over the same, the window's sum
  floored at 0: the loop in a callback and on no CPU (waiting for the
  interpreter a pool thread holds, or the process frozen).  The sum and
  not each sample is floored: where the kernel keeps a thread's CPU
  time by a 10 ms tick (the chip machine's does) one sample's
  ``cpu_ns`` is a multiple of it, and a floor a sample would count the
  clock's grain as waiting;
* ``pass``: ``sum(busy_sq) / sum(busy_ns)`` in ms: the length-weighted
  mean pass, the one an answer arriving at a random moment lands in.

For the reader, once a run, on an earlier line (``loop_clock``): the
three shares (on a CPU, off it, in ``select``; the poll's own CPU is
in the first and the third, so they sum to 1 plus that where the loop
is never off a CPU in a callback), ``busy_less_cpu`` (the second share
before its floor: where it is negative the thread's CPU, the polls'
included, ran ahead of the busy time, and the 0 of ``off_cpu`` is a
floor, not a measured nought), ``accounted`` (``busy_ns + select_ns``
over the covered time: 1 where the meter's clock and the profiler's
agree), the share spent polling with callbacks ready (``in_select``
less it is all the time the loop had nothing to do: a loop at 0.84 of a
CPU that polls for the rest is full) and the mean such poll in us, the
weighted and the plain mean pass, passes a second, the slowest pass
with the offset of its sample from the window's start, and the five
samples in which the loop was least on a CPU or in ``select`` (a pause
of the machine is a sample
whose ``busy_ns`` is the whole period and whose ``cpu_ns`` is nearly
none; a wait for a brick is ``select_ns``).  A program without the
meter (an older commit), an untraced run and a window without a sample
leave nothing to read, and nothing is returned."""

import glob
import os

from benchmarks.harness import spans

SAMPLE = spans.PREFIX + "loop.sample"
KEYS = ("passes", "busy_ns", "select_ns", "busy_sq", "cpu_ns",
        "slowest_pass_ns", "polls", "poll_ns")


def samples_of(path: str) -> list:
    """``[[start_ns, dur_ns, {key: number}], ...]``: every whole sample
    of every host plane, in the order they began."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name != SAMPLE:
                    continue
                meta = dict(e.stats)
                if all(k in meta for k in KEYS):
                    out.append([e.start_ns, e.duration_ns,
                                {k: float(meta[k]) for k in KEYS}])
    return sorted(out, key=lambda s: s[0])


def reduce(samples: list, w0: float, w1: float) -> dict | None:
    """The window's sums, each sample by the share of it that lies in
    [w0, w1]; ``None`` where none does."""
    acc = dict.fromkeys(("covered", "cpu", "select", "busy", "busy_sq",
                         "passes", "polls", "poll"), 0.0)
    slowest, rows = (0.0, 0.0), []
    for start, dur, m in samples:
        overlap = min(start + dur, w1) - max(start, w0)
        if overlap <= 0:
            continue
        part = overlap / dur
        acc["covered"] += overlap
        for key in ("cpu", "select", "busy", "poll"):
            acc[key] += part * m[key + "_ns"]
        for key in ("busy_sq", "passes", "polls"):
            acc[key] += part * m[key]
        slowest = max(slowest, (m["slowest_pass_ns"], start - w0))
        rows.append(((m["cpu_ns"] + m["select_ns"]) / dur, start - w0, m))
    if not rows or acc["busy"] <= 0:
        return None
    ms, covered = 1e-6, acc["covered"]
    return {
        "samples": len(rows), "covered_s": covered * 1e-9,
        "cpu": acc["cpu"] / covered,
        "offcpu": max(acc["busy"] - acc["cpu"], 0.0) / covered,
        "busy_less_cpu": (acc["busy"] - acc["cpu"]) / covered,
        "select": acc["select"] / covered,
        "accounted": (acc["busy"] + acc["select"]) / covered,
        "polling": acc["poll"] / covered,
        "poll_us": acc["poll"] / max(acc["polls"], 1.0) * 1e-3,
        "polls_per_s": acc["polls"] / covered * 1e9,
        "pass": acc["busy_sq"] / acc["busy"] * ms,
        "mean_pass_ms": acc["busy"] / max(acc["passes"], 1.0) * ms,
        "passes_per_s": acc["passes"] / covered * 1e9,
        "slowest_pass_ms": slowest[0] * ms, "slowest_at_s": slowest[1] * 1e-9,
        # [offset s, busy ms, cpu ms, select ms] of the five samples
        # with the least of their period on a CPU or in select
        "least_accounted": [
            [at * 1e-9, m["busy_ns"] * ms, m["cpu_ns"] * ms,
             m["select_ns"] * ms]
            for _share, at, m in sorted(rows, key=lambda r: r[:2])[:5]]}


def of_run(run) -> dict | None:
    """The traced run's reduction, made and printed once."""
    if "_loop_clock" not in run.__dict__:
        sp = spans.of_run(run)
        found = glob.glob(os.path.join(
            run.volume.workdir, "trace", "plugins", "profile", "*",
            "*.xplane.pb")) if sp is not None else []
        got = reduce(samples_of(found[0]), sp.w0, sp.w1) \
            if len(found) == 1 else None
        if got is not None:
            run.note("loop_clock", on_cpu=got["cpu"], off_cpu=got["offcpu"],
                     in_select=got["select"],
                     shares_sum=got["cpu"] + got["offcpu"] + got["select"],
                     weighted_pass_ms=got["pass"], **{
                         k: got[k] for k in (
                             "busy_less_cpu", "accounted", "polling", "poll_us",
                             "polls_per_s", "mean_pass_ms", "passes_per_s",
                             "slowest_pass_ms", "slowest_at_s", "samples",
                             "covered_s", "least_accounted")})
        run.__dict__["_loop_clock"] = got
    return run.__dict__["_loop_clock"]


def read(run, what: str):
    got = of_run(run)
    return None if got is None else got[what]
