"""The heal cell (``BASELINE.json`` config 4, upstream ``ec-heal.t``) and
the four-writer cell: the manifest as cut, the heal metrics held by a
rule that a later heal cell meets as data, the heal phase rehearsed on
the CPU at tiny size, and a mix without the heal keys taking the set-up
it took before them."""

import os

import numpy as np
import pytest

from benchmarks import run as bench
from benchmarks.harness import check, reference, work
from benchmarks.harness.manifest import Manifest
from benchmarks.harness.traffic import Traffic
from benchmarks.harness.volume import Volume
from tests.benchmarks.test_rehearsal import CELLS, rehearse

HEAL, J4 = CELLS["heal-full"], CELLS["sequential-write-four"]
W, D = CELLS["sequential-write"], CELLS["sequential-read-degraded"]
SIX = ["heal_roofline", "heal_device_ms_per_MiB", "heal_launches_per_MiB",
       "heal_wire_readv_ms", "heal_wire_writev_ms", "heal_brick_writev_ms"]
SWEEP_CAP = 30.0


def reporters(m: Manifest, metric: str) -> set:
    kind = m.metrics[metric]["kind"]
    return {name for name in m.cells
            if any(x["name"] == metric for x in m.cell_metrics(name, kind))}


def heal_metric_problems(m: Manifest) -> list:
    """The heal metrics by membership, never by a cell's name: the cells
    that report ``heal_MiB_s`` are exactly those whose mix has a heal
    window, and each of the six is reported by none but them."""
    bad = []
    heal = {name for name, cell in m.cells.items()
            if m.traffic(cell).get("window") == "heal"}
    rate = reporters(m, "heal_MiB_s")
    if rate != heal:
        bad.append(f"heal_MiB_s is reported by {sorted(rate)}, the heal "
                   f"windows are {sorted(heal)}")
    for name in SIX:
        if not reporters(m, name) <= rate:
            bad.append(f"{name} is reported by "
                       f"{sorted(reporters(m, name) - rate)}, which have "
                       "no heal rate")
    return bad


def test_the_manifest_has_both_cells_as_cut_here():
    m = Manifest()
    assert m.problems() == []
    for name in (HEAL, J4):
        cell = m.cell(name)
        assert (cell["config"], cell["chips"]) == ("ec-4p2-tpu", 1)
        assert len(cell["why"]) <= 200
    mix = m.traffic(m.cell(HEAL))
    assert (mix["bricks_returning"], mix["window"], mix["jobs"],
            mix["file_MiB"], mix["read_share"], mix["verify_extents"],
            mix["layout_jobs"], mix["warm_seconds"]) == \
        ([1], "heal", 8, 256, 1.0, 3, 1, 0)
    assert "bricks_down" not in mix
    assert {"healer", "bricks_returning", "warm"} <= set(mix["assumed"])
    assert {x["name"] for x in m.cell_metrics(HEAL, "end_to_end")} == \
        {"heal_MiB_s", "setup_s"}
    # H reports at least the six: a later heal metric may list it too
    assert set(SIX) <= {x["name"] for x in m.cell_metrics(HEAL, "per_layer")}
    rate = m.metrics["heal_MiB_s"]
    assert (rate["unit"], rate["better"], rate["source"]) == \
        ("MiB/s", "higher", "host_clock")
    assert HEAL in rate["workloads"]
    assert 0.01 <= rate["bound"] <= 0.25
    for name in SIX:
        assert m.metrics[name]["moves"] == "heal_MiB_s"
        assert HEAL in m.metrics[name]["workloads"]
    assert heal_metric_problems(m) == []
    # four writers: W's mix but for the writers, and what W reports
    w, four = m.traffic(m.cell(W)), m.traffic(m.cell(J4))
    keys = ("jobs", "layout_jobs", "verify_files", "why", "assumed")
    assert {k: v for k, v in four.items() if k not in keys} == \
        {k: v for k, v in w.items() if k not in keys}
    assert (four["jobs"], four["layout_jobs"]) == (4, 4)
    assert {x["name"] for x in m.cell_metrics(J4, "end_to_end")} == \
        {"write_MiB_s", "setup_s"}
    assert [x["name"] for x in m.cell_metrics(J4, "per_layer")] == \
        [x["name"] for x in m.cell_metrics(W, "per_layer")]


def test_heal_bytes_are_shapes_only():
    """k chunks in and one out for each returning brick, per stripe: a
    rebuild's needs, whatever is launched for it."""
    assert work.heal_bytes(1 << 20, 4, 1) == 512 * (4 + 1) * 512
    assert work.heal_bytes(1 << 20, 4, 2) == 512 * (4 + 2) * 512


def returning_bricks_compared(monkeypatch) -> dict:
    """Before the harness's own check, each returning brick's file of
    each of the mix's files, every byte, against the reference encoding
    of the model: ``{(file, brick): equal}``, filled by the run."""
    whole = {}
    real = check.compare

    async def compare(run):
        t, g = run.traffic, run.config["geometry"]
        k, n = g["data"], g["data"] + g["redundancy"]
        for j, name in enumerate(t.names):
            want = reference.encode(t.contents(j), k, n)
            for brick in t.returning:
                with open(os.path.join(run.volume.bricks[brick],
                                       name.lstrip("/")), "rb") as f:
                    got = np.frombuffer(f.read(), dtype=np.uint8)
                whole[name, brick] = np.array_equal(got, want[brick])
        return await real(run)

    monkeypatch.setattr(check, "compare", compare)
    return whole


def test_the_heal_rehearsal_is_correct_and_the_brick_comes_back_whole(
        tmp_path, monkeypatch):
    whole = returning_bricks_compared(monkeypatch)
    # the window closes when the sweep returns; the cap only has to be
    # past that on a loaded test machine
    _m, result = rehearse(tmp_path, HEAL, seconds=SWEEP_CAP)
    assert result["correct"] is True, result
    assert (result["attempted"], result["failed"]) == (3, 0)
    assert result["metrics"]["heal_MiB_s"]["value"] > 0
    assert all(v == 0 for v, _limit in result["checks"].values())
    assert len(whole) == 3 and all(whole.values()), whole


def test_the_heal_rehearsal_traced(tmp_path):
    """What needs a device plane is left out, not 0; the counters and
    the bricks' profile are read over the sweep."""
    m, result = rehearse(tmp_path, HEAL, trace=1, seconds=SWEEP_CAP)
    assert result["correct"] is True, result
    got = {name: v["value"] for name, v in result["metrics"].items()}
    assert set(got) == set(SIX) - {"heal_roofline", "heal_device_ms_per_MiB"}
    assert all(v > 0 for v in got.values()), got


@pytest.mark.parametrize("cell, want", [
    (D, ["layout", "stop_brick 1", "run", "run"]),
    (HEAL, ["create", "stop_brick 1", "layout", "start_brick 1", "run",
            "heal"]),
])
def test_set_up_and_window_calls(tmp_path, monkeypatch, cell, want):
    """A mix without the heal keys stops its bricks after the layout and
    loops in the window, as before them; a heal mix creates its files,
    stops the returning brick, lays out, starts it, and sweeps."""
    calls = []

    def recorded(owner, name):
        real = getattr(owner, name)

        async def wrapper(self, *args, **kwargs):
            calls.append(f"{name} {args[0]}" if name.endswith("_brick")
                         else name)
            return await real(self, *args, **kwargs)

        monkeypatch.setattr(owner, name, wrapper)

    for owner, name in ((Traffic, "create"), (Traffic, "layout"),
                        (Traffic, "run"), (Volume, "stop_brick"),
                        (Volume, "start_brick"), (bench.Run, "heal")):
        recorded(owner, name)
    _m, result = rehearse(tmp_path, cell)
    assert result["correct"] is True, result
    assert calls == want
