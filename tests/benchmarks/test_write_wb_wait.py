"""``write_wb_wait_ms`` (ISSUE 33): the park of a door write on
``performance/write-behind``'s window, as its metric file defines it
and through its reader: on a synthetic list, on the recorded W of a
program without the phase, and end to end at tiny size on the CPU."""

import json
import os
import types

import pytest

from benchmarks.harness import spans
from benchmarks.harness.traffic import WRITE
from benchmarks.readers import span_ms
from tests.benchmarks.test_rehearsal import CELLS, rehearse
from tests.benchmarks.test_spans import DATA, G, _metric, span, synthetic

PARAMS = {"kind": "write", "whole": [G + "wb.wait"]}


def _run(sp, writes: int):
    return types.SimpleNamespace(ops=[(0.0, 0.0, WRITE)] * writes,
                                 _spans=sp)


def test_the_metric_file_reads_the_park_whole():
    assert _metric("write_wb_wait_ms") == PARAMS


def test_a_program_without_the_phase_reads_nothing():
    """The recorded W is a program of PR 24, where a write that filled
    the window was held for its whole drain and no such phase existed:
    ``span_ms`` finds no span of that name and answers 0.0 (PERF.md §7
    (13d)); a program that wrote no spans at all leaves the metric
    out."""
    with open(os.path.join(DATA, "recorded_spans.json")) as f:
        recorded = spans.Spans.of(json.load(f)["W"])
    assert G + "wb.wait" not in recorded.name
    assert span_ms.read(_run(recorded, 24), **PARAMS) == 0.0
    assert span_ms.read(_run(None, 24), **PARAMS) is None


def test_the_park_counts_once_under_a_write_and_a_drain_is_a_write_tree():
    """A door write that parks, and the drain it then starts as a tree
    of its own rooted at ``cluster/disperse.writev``: the park counts
    under its door write, the drain's spans count as ``write`` (a
    tree's kind is its root's fop), and the door's time above
    ``cluster/ec`` is the door's root, park included."""
    ev = synthetic()
    ev["spans"] += [
        span("performance/write-behind.writev", 40, 1, 110, 140),
        span("wb.wait", 41, 40, 115, 135),
        span("cluster/disperse.writev", 60, 0, 400, 905),  # a drain
        span("ec.fanout", 61, 60, 500, 900),
        span("meta.readv", 50, 0, 905, 945),
        span("wb.wait", 51, 50, 910, 940),  # a read's tree: not a write's
    ]
    sp = spans.Spans.of(ev)
    run = _run(sp, 2)
    assert span_ms.read(run, **PARAMS) == pytest.approx(20e-6 / 2)
    # the drain's fan-out is read as a write's: 200 of the door
    # write's own tree and 400 of the drain's
    assert sp.total("write", whole=[G + "ec.fanout"]) == 200 + 400
    # root less the topmost cluster/disperse.*: the drain's tree is
    # its own root and takes itself off again
    above = sp.total("write", **{k: v for k, v in _metric(
        "write_above_ec_ms").items() if k != "kind"})
    assert above == (800 + 50) - 700


def test_the_tiny_rehearsal_of_w_reports_it(tmp_path):
    m, result = rehearse(tmp_path, CELLS["sequential-write"], trace=1)
    assert result["correct"] is True and result["failed"] == 0
    got = result["metrics"]
    assert got["write_wb_wait_ms"]["unit"] == "ms"
    # the park lies inside the door's write, and what the door spends
    # above cluster/ec holds it: a drain is a tree of its own
    assert 0 <= got["write_wb_wait_ms"]["value"] \
        < got["write_above_ec_ms"]["value"]
