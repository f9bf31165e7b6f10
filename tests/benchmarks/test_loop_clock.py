"""The metrics of the layer ``client loop and interpreter`` (ISSUE 34):
``readers/loop_clock.py`` over the loop meter's samples and
``readers/flush_offcpu.py`` over the pool-thread phases' ``cpu_ns``, on
hand-made lists and end to end at tiny size on the CPU."""

import json
import types

import pytest

from benchmarks.harness.manifest import Manifest
from benchmarks.readers import flush_offcpu, loop_clock
from tests.benchmarks.test_rehearsal import CELLS, rehearse
from tests.benchmarks.test_spans import G, _metric, span

MS = 1_000_000
NEW = {"write_loop_cpu_share": "cpu", "read_loop_cpu_share": "cpu",
       "write_loop_offcpu_share": "offcpu",
       "read_loop_offcpu_share": "offcpu",
       "write_loop_pass_ms": "pass", "read_loop_pass_ms": "pass"}


def sample(start_ms, busy_ms, cpu_ms, passes=10, slowest_ms=None,
           dur_ms=100, pass_ms=None):
    """One period: ``passes`` equal passes unless ``pass_ms`` lists
    them; what is not busy is in ``select``."""
    lengths = pass_ms or [busy_ms / passes] * passes
    return [start_ms * MS, dur_ms * MS, {
        "passes": float(len(lengths)), "busy_ns": busy_ms * MS,
        "select_ns": (dur_ms - busy_ms) * MS, "cpu_ns": cpu_ms * MS,
        "busy_sq": sum((x * MS) ** 2 for x in lengths),
        "slowest_pass_ns": (slowest_ms or max(lengths)) * MS,
        "polls": len(lengths) / 2,
        "poll_ns": (dur_ms - busy_ms) * MS / 4}]


# -- loop_clock ----------------------------------------------------------------

def test_three_shares_sum_to_one_and_the_pass_is_length_weighted():
    got = loop_clock.reduce(
        [sample(0, 80, 60), sample(100, 100, 90), sample(200, 20, 20)],
        0, 300 * MS)
    assert got["cpu"] == pytest.approx(170 / 300)
    assert got["offcpu"] == pytest.approx(30 / 300)
    assert got["select"] == pytest.approx(100 / 300)
    assert got["cpu"] + got["offcpu"] + got["select"] == pytest.approx(1)
    assert got["accounted"] == pytest.approx(1)
    # half the passes polled, for a quarter of the time in select
    assert got["polling"] == pytest.approx(25 / 300)
    assert got["poll_us"] == pytest.approx(25e3 / 15)
    # 10 passes of 8 ms, 10 of 10 ms, 10 of 2 ms: by length, not count
    assert got["pass"] == pytest.approx((640 + 1000 + 40) / 200)
    assert got["mean_pass_ms"] == pytest.approx(200 / 30)
    assert got["passes_per_s"] == pytest.approx(100)
    assert got["samples"] == 3 and got["covered_s"] == pytest.approx(0.3)


def test_a_sample_across_an_edge_counts_by_its_overlap():
    """The window cuts the first sample at a quarter and the last at a
    half; one before it and one after it count nothing."""
    samples = [sample(-200, 100, 100), sample(-75, 40, 20),
               sample(25, 100, 50), sample(125, 60, 60),
               sample(300, 100, 100)]
    got = loop_clock.reduce(samples, 0, 175 * MS)
    covered = 25 + 100 + 50
    assert got["samples"] == 3
    assert got["covered_s"] == pytest.approx(covered * 1e-3)
    assert got["cpu"] == pytest.approx((5 + 50 + 30) / covered)
    assert got["offcpu"] == pytest.approx((10 + 100 + 30 - 85) / covered)
    assert got["select"] == pytest.approx((15 + 0 + 20) / covered)
    assert got["cpu"] + got["offcpu"] + got["select"] == pytest.approx(1)


def test_the_pause_is_the_least_accounted_sample_and_the_slowest_pass():
    """A pause of the machine: the loop is in a callback for the whole
    period and on no CPU.  It heads ``least_accounted`` and its pass is
    the slowest, with its sample's offset from the window's start."""
    samples = [sample(1000 + 100 * i, 30, 28) for i in range(10)]
    samples[6] = sample(1600, 100, 1, passes=1)
    got = loop_clock.reduce(samples, 1000 * MS, 2000 * MS)
    at, busy, cpu, select = got["least_accounted"][0]
    assert (at, busy, cpu, select) == pytest.approx((0.6, 100, 1, 0))
    assert len(got["least_accounted"]) == 5
    assert got["slowest_pass_ms"] == pytest.approx(100)
    assert got["slowest_at_s"] == pytest.approx(0.6)
    # the CPU clock may run ahead of the busy time by the poll's own:
    # the difference is floored, never negative; and the window's sum
    # is floored, not each sample (a 10 ms grain of the thread clock
    # makes one sample read 60 for 50 and the next 40 for 50)
    ahead = loop_clock.reduce([sample(0, 50, 55)], 0, 100 * MS)
    assert ahead["offcpu"] == 0.0
    # and the note shows the floor for what it is
    assert ahead["busy_less_cpu"] == pytest.approx(-0.05)
    assert loop_clock.reduce([sample(0, 50, 60), sample(100, 50, 40)], 0,
                             200 * MS)["offcpu"] == 0.0


def test_no_sample_in_the_window_is_nothing_to_read():
    """The parent's program writes no sample: the metric is left out,
    not reported as 0.0 (PERF.md section 7 (13d))."""
    assert loop_clock.reduce([], 0, 100 * MS) is None
    assert loop_clock.reduce([sample(500, 50, 50)], 0, 100 * MS) is None
    run = types.SimpleNamespace(_loop_clock=None)
    assert all(loop_clock.read(run, what) is None
               for what in ("cpu", "offcpu", "pass"))


# -- flush_offcpu --------------------------------------------------------------

def flushes():
    """An encode flush of two fops, a decode flush of one, an encode
    flush that began before the window, and a loop-side phase."""
    return [
        span("codec.flush", 10, 3, 100, 1100, op="encode", cpu_ns=700),
        span("codec.gather", 11, 10, 110, 310, cpu_ns=150),
        span("codec.h2d", 12, 10, 320, 420, cpu_ns=80),
        span("codec.launch", 13, 10, 430, 480, cpu_ns=40),
        span("codec.d2h", 14, 10, 500, 900, cpu_ns=100),
        span("codec.scatter", 15, 10, 910, 1010, cpu_ns=90),
        span("codec.flush", 20, 4, 2000, 2600, op="decode", cpu_ns=200),
        span("codec.h2d", 22, 20, 2010, 2110, cpu_ns=20),
        span("codec.launch", 23, 20, 2120, 2170, cpu_ns=30),
        span("codec.d2h", 24, 20, 2200, 2500, cpu_ns=50),
        span("codec.flush", 30, 5, -50, 500, op="encode", cpu_ns=1),
        span("codec.h2d", 32, 30, 0, 100, cpu_ns=1),
        span("codec.resume", 40, 3, 1100, 1200),
    ]


def test_a_flush_is_its_phases_off_the_cpu_without_the_d2h():
    """Children by ``parent``: gather 50 + h2d 20 + launch 10 + scatter
    10 + the flush's self (1000 - 850 of children, less 700 - 460 of
    CPU: -90, a clock's grain) = 0 off the CPU; the d2h's 300 are left
    out, and that is the flush's own 300 less the d2h's 300."""
    ms, phases = flush_offcpu.offcpu(flushes(), 0, 5000, "encode")
    assert ms == pytest.approx((1000 - 700 - (400 - 100)) * 1e-6)
    assert phases["codec.gather"] == [1, pytest.approx(200e-6),
                                      pytest.approx(150e-6)]
    assert phases["codec.flush"][0] == 1 and "codec.resume" not in phases
    assert set(phases) == {"codec.flush", "codec.gather", "codec.h2d",
                           "codec.launch", "codec.d2h", "codec.scatter"}


def test_op_keeps_encode_and_decode_apart():
    ms, phases = flush_offcpu.offcpu(flushes(), 0, 5000, "decode")
    assert ms == pytest.approx((600 - 200 - (300 - 50)) * 1e-6)
    assert "codec.gather" not in phases and phases["codec.h2d"][0] == 1
    assert flush_offcpu.offcpu(flushes(), 0, 5000, "delta") is None


def test_no_cpu_clock_on_the_flushes_is_nothing_to_read():
    """An older program's flush spans carry no ``cpu_ns``."""
    old = [[n, s, d, t, i, p, {k: v for k, v in m.items()
                               if k != "cpu_ns"}]
           for n, s, d, t, i, p, m in flushes()]
    assert flush_offcpu.offcpu(old, 0, 5000, "encode") is None
    # nor does a window that holds no flush's beginning
    assert flush_offcpu.offcpu(flushes(), 3000, 5000, "encode") is None


# -- the manifest and one traced rehearsal -------------------------------------

def test_the_manifest_holds_the_eight_metrics():
    m = Manifest()
    assert m.problems() == []
    for name, what in NEW.items():
        assert _metric(name) == {"what": what}
        assert m.metrics[name]["source"] == "program_counter"
    assert _metric("write_flush_offcpu_ms") == {"op": "encode"}
    assert _metric("read_flush_offcpu_ms") == {"op": "decode"}
    new = [x for x in m.doc["per_layer"]
           if x["layer"] == "client loop and interpreter"]
    assert len(new) == 8 and m.doc["per_layer"][-8:] == new
    for cell in m.cells:
        mine = [x["name"] for x in m.cell_metrics(cell, "per_layer")
                if x in [dict(n, **m.metric_file(n["name"])) for n in new]]
        assert len(mine) == 4, (cell, mine)


@pytest.mark.parametrize("cell, kind", [
    (CELLS["sequential-write"], "write"),
    (CELLS["sequential-read-degraded"], "read")])
def test_the_tiny_rehearsal_reports_the_loops_clock(tmp_path, capsys, cell,
                                                    kind):
    m, result = rehearse(tmp_path, cell, trace=1)
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    cpu, off = (got[f"{kind}_loop_{w}_share"]["value"]
                for w in ("cpu", "offcpu"))
    assert 0 < cpu <= 1.05 and 0 <= off < 1
    assert got[f"{kind}_loop_pass_ms"]["value"] > 0
    assert got[f"{kind}_flush_offcpu_ms"]["unit"] == "ms"
    notes = {l.split(" ", 1)[0]: json.loads(l.split(" ", 1)[1])
             for l in capsys.readouterr().out.splitlines()
             if l.startswith(("loop_clock ", "flush_offcpu ", "span_means ",
                              "idle_by_span "))}
    clock = notes["loop_clock"]
    assert clock["shares_sum"] == pytest.approx(1.0, abs=0.02)
    assert clock["off_cpu"] == max(clock["busy_less_cpu"], 0.0)
    # (the tiny read cell serves its one MiB from io-cache without ever
    # yielding to the loop: one pass of most of the window, one sample)
    assert clock["on_cpu"] == cpu and clock["samples"] >= 1
    assert clock["weighted_pass_ms"] >= clock["mean_pass_ms"] > 0
    op = {"write": "encode", "read": "decode"}[kind]
    assert notes["flush_offcpu"]["op"] == op
    assert {"codec.flush", "codec.h2d", "codec.launch", "codec.d2h"} <= set(
        notes["flush_offcpu"]["phases"])
    # the sample enters no span tree: the span readers name it nowhere
    assert not any("loop.sample" in n for n in notes["span_means"]["spans"])
    assert G + "codec.flush" in notes["span_means"]["spans"]
