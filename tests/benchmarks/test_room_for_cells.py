"""Room for cells.  A second heal cell on a configuration of its own is
added to a tiny copy of the manifest as data alone (``data/heal_room``:
disperse 8+4, one server's four bricks healed eight files at a time) and
meets every rule that the manifest's tests hold; each rule fails on a
manifest that breaks it; and the added cell is rehearsed whole on the
CPU, its returning bricks compared byte for byte."""

import asyncio
import json
import os

import pytest

from benchmarks import run as bench
from benchmarks.harness.manifest import Manifest
from tests.benchmarks import tiny
from tests.benchmarks.test_heal_cell import (SIX, SWEEP_CAP,
                                             heal_metric_problems,
                                             returning_bricks_compared)
from tests.benchmarks.test_loop_clock import (EIGHT, FLUSH, door_kinds,
                                              loop_metric_problems)

CELL = tiny.HEAL_ROOM_CELL
#: one rehearsal of twelve bricks, start to end, beside five other
#: test workers
LIMIT_S = 600


def _manifest(root: str) -> Manifest:
    return Manifest(root, os.path.join(root, "benchmarks"))


def _door_cell(m: Manifest, kinds: set) -> str:
    """A committed cell whose mix does exactly these door operations."""
    return next(name for name, cell in m.cells.items()
                if door_kinds(m.traffic(cell)) == kinds)


def test_a_heal_cell_is_added_by_data_alone(tmp_path):
    root = tiny.tiny_root(str(tmp_path), add=("heal_room",))
    m = _manifest(root)
    assert m.problems() == []
    assert loop_metric_problems(m) == []
    assert heal_metric_problems(m) == []
    cell = m.cell(CELL)
    cfg, mix = m.config(cell), m.traffic(cell)
    before = Manifest()
    assert cfg["geometry"] == before.config({"config": "ec-8p4-tpu"})[
        "geometry"]
    assert int(cfg["options"]["disperse.shd-max-threads"]) > 1
    assert mix["window"] == "heal" and len(mix["bricks_returning"]) == \
        cfg["geometry"]["redundancy"]
    assert {x["name"] for x in m.cell_metrics(CELL, "end_to_end")} == \
        {"heal_MiB_s", "setup_s"}
    got = {x["name"] for x in m.cell_metrics(CELL, "per_layer")}
    assert set(SIX) <= got and not got & set(EIGHT)
    # the cells that were there report what they did before
    for name in before.cells:
        for kind in ("end_to_end", "per_layer"):
            assert [x["name"] for x in m.cell_metrics(name, kind)] == \
                [x["name"] for x in before.cell_metrics(name, kind)]


def _on(doc: dict, metric: str, cell: str) -> None:
    next(m for m in doc["end_to_end"] + doc["per_layer"]
         if m["name"] == metric)["workloads"].append(cell)


def _off(doc: dict, metric: str, cell: str) -> None:
    next(m for m in doc["end_to_end"] + doc["per_layer"]
         if m["name"] == metric)["workloads"].remove(cell)


def _unlist(doc: dict, metric: str) -> None:
    next(m for m in doc["per_layer"] if m["name"] == metric).pop(
        "workloads")


# each breakage: (what it does to the document, given a write-only door
# cell and a read-only one; the rule that has to find it)
BREAKAGES = {
    "heal-cell-on-a-loop-share": (
        lambda d, w, r: _on(d, "write_loop_cpu_share", CELL), "loop"),
    "door-cell-short-of-a-loop-share": (
        lambda d, w, r: _off(d, "write_loop_pass_ms", w), "loop"),
    "door-cell-told-in-both-kinds": (
        lambda d, w, r: _on(d, "write_loop_offcpu_share", r), "loop"),
    "flush-metric-of-a-kind-not-done": (
        lambda d, w, r: _on(d, FLUSH["write"], r), "loop"),
    "door-cell-off-every-flush-metric": (
        lambda d, w, r: _off(d, FLUSH["write"], w), "loop"),
    "flush-metric-without-a-list": (
        lambda d, w, r: _unlist(d, FLUSH["write"]), "loop"),
    "heal-cell-off-the-heal-rate": (
        lambda d, w, r: _off(d, "heal_MiB_s", CELL), "heal"),
    "door-cell-on-the-heal-rate": (
        lambda d, w, r: _on(d, "heal_MiB_s", w), "heal"),
    "door-cell-on-a-heal-metric": (
        lambda d, w, r: _on(d, "heal_wire_readv_ms", w), "heal"),
}


@pytest.mark.parametrize("breakage", BREAKAGES.values(), ids=BREAKAGES)
def test_each_rule_fails_on_a_manifest_that_breaks_it(tmp_path, breakage):
    change, rule = breakage
    root = tiny.tiny_root(str(tmp_path), add=("heal_room",))
    m = _manifest(root)
    writer, reader = _door_cell(m, {"write"}), _door_cell(m, {"read"})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    change(doc, writer, reader)
    with open(path, "w") as f:
        json.dump(doc, f)
    broken = _manifest(root)
    found = {"loop": loop_metric_problems,
             "heal": heal_metric_problems}[rule](broken)
    assert found, rule


def test_the_added_heal_cell_rehearsed(tmp_path, monkeypatch):
    """``correct``, a heal rate, nothing left pending, and every
    returning brick holding every file byte for byte as the reference
    encodes it."""
    whole = returning_bricks_compared(monkeypatch)
    root = tiny.tiny_root(str(tmp_path), add=("heal_room",))
    tmp = os.path.join(root, "tmp")
    os.makedirs(tmp)
    m = _manifest(root)
    mix = m.traffic(m.cell(CELL))

    async def limited():
        return await asyncio.wait_for(bench.run_cell(
            tiny.args(CELL, seconds=SWEEP_CAP), m,
            {"backend": "xla", "tmp": tmp}), LIMIT_S)

    result = json.loads(json.dumps(asyncio.run(limited())))
    assert not os.listdir(tmp), "the run left files behind"
    assert result["correct"] is True, result
    assert (result["attempted"], result["failed"]) == (mix["jobs"], 0)
    assert result["metrics"]["heal_MiB_s"]["value"] > 0
    assert result["checks"]["heal_pending"][0] == 0
    assert all(v == 0 for v, _limit in result["checks"].values())
    assert len(whole) == mix["jobs"] * len(mix["bricks_returning"])
    assert all(whole.values()), whole
