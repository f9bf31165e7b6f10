"""BENCHMARK.json and the files it names agree, and a cell, a
configuration, a mix and a metric are each added by files alone."""

import json
import os

import pytest

from benchmarks.harness.manifest import HERE, ROOT, Manifest
from tests.benchmarks import tiny


def test_manifest_and_files_agree():
    assert Manifest().problems() == []


def test_contract_shapes():
    doc = Manifest().doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in doc["end_to_end"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in doc["end_to_end"])
    assert 1 <= doc["run_seconds"] <= 51
    four = sum(w["chips"] == 4 for w in doc["workloads"])
    assert four <= max(1, len(doc["workloads"]) // 2)
    for entry in doc["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == entry["reduced"]
        assert all(key in cfg for key in entry["reduced"])
        assert cfg["guarantees"] and cfg["assumed"]["brick_root"]
    for entry in doc["workloads"] + doc["configs"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]


def test_every_file_under_paths_has_a_contract_name():
    import re
    import subprocess

    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    paths = Manifest().doc["paths"]
    assert paths == ["benchmarks", "tests/benchmarks"]
    listed = subprocess.run(["git", "ls-files", "--"] + paths, cwd=ROOT,
                            capture_output=True, text=True).stdout.split()
    found = listed or [
        os.path.relpath(os.path.join(d, f), ROOT)
        for p in paths for d, _dirs, files in os.walk(os.path.join(ROOT, p))
        for f in files if "__pycache__" not in d]
    assert found and all(ok.match(p) for p in found)
    for entry in Manifest().doc["command"]:
        assert not entry.startswith("/") and ".." not in entry


def test_a_cell_is_added_by_files_and_entries_alone(tmp_path):
    """A later PR's whole diff for a new cell: one configuration file,
    one mix, metric files over readers that are there, and entries
    (``data/extra``; ``test_rehearsal`` runs that cell)."""
    root = tiny.tiny_root(str(tmp_path), add=("extra",))
    m = Manifest(root, os.path.join(root, "benchmarks"))
    assert m.problems() == []
    cell = m.cell(tiny.EXTRA_CELL)
    assert m.traffic(cell)["pattern"] == "random"
    assert m.config(cell)["geometry"]["groups"] == 2
    assert {x["name"] for x in m.cell_metrics(tiny.EXTRA_CELL,
                                              "end_to_end")} == \
        {"rw_MiB_s", "op_p99_ms", "setup_s"}
    assert [x["name"] for x in m.cell_metrics(tiny.EXTRA_CELL,
                                              "per_layer")] == \
        ["rw_read_op_p99_ms"]
    # and the cells that were there report what they did before
    before = Manifest()
    for name in before.cells:
        for kind in ("end_to_end", "per_layer"):
            assert [x["name"] for x in m.cell_metrics(name, kind)] == \
                [x["name"] for x in before.cell_metrics(name, kind)]


def test_the_traffic_files_say_what_they_assumed():
    """Every parameter of a mix that its source does not give is listed
    under ``assumed`` with its reason (REVIEW of PR 23)."""
    m = Manifest()
    for cell in m.cells.values():
        mix = m.traffic(cell)
        assert "BASELINE.json config" in mix["why"]
        assert {"jobs", "file_MiB", "loop"} <= set(mix["assumed"])
        assert all(len(v) > 20 for v in mix["assumed"].values())


@pytest.mark.parametrize("breakage, said", [
    (lambda d: d["workloads"][0].update(traffic="nowhere"), "missing"),
    (lambda d: d["per_layer"][0].update(moves="nothing"), "moves unknown"),
    (lambda d: d["per_layer"][0].update(name="has space"), "characters"),
    (lambda d: d["end_to_end"][0].update(unit="MiB per s"), "unit"),
    (lambda d: d["per_layer"][0].update(workloads=["nocell"]), "unknown cell"),
])
def test_problems_are_found(tmp_path, breakage, said):
    root = tiny.tiny_root(str(tmp_path))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    breakage(doc)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    found = Manifest(root, os.path.join(root, "benchmarks")).problems()
    assert any(said in p for p in found), found
