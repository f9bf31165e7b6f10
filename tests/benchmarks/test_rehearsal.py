"""One rehearsal of a whole run per kind of traffic, on the CPU at tiny
size (the ``xla`` backend stands in for the chip, as in
``tests/test_chip_smoke.py``).  The random engine has no committed cell
yet; it runs the throw-away extra cell of ``data/extra``."""

import asyncio
import json
import os
import subprocess
import sys

import pytest

from benchmarks import run as bench
from benchmarks.harness.manifest import ROOT, Manifest
from tests.benchmarks import tiny

CELLS = {
    "sequential-write": "ec-4p2-tpu.seq-write-1m",
    "sequential-read-degraded": "ec-4p2-tpu.seq-read-1m-1down",
    "random-readwrite-extra": tiny.EXTRA_CELL,
    "sequential-write-four": "ec-4p2-tpu.seq-write-1m-j4",
    "heal-full": "ec-4p2-tpu.heal-full-1brick",
}
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def rehearse(tmp_path, cell, trace=0, fault=None, seed=3000000019,
             seconds=1.0):
    add = ("extra",) if cell == tiny.EXTRA_CELL else ()
    root = tiny.tiny_root(str(tmp_path), add=add)
    tmp = os.path.join(root, "tmp")
    os.makedirs(tmp)
    m = Manifest(root, os.path.join(root, "benchmarks"))
    result = asyncio.run(bench.run_cell(
        tiny.args(cell, seed=seed, seconds=seconds, trace=trace), m,
        {"backend": "xla", "tmp": tmp}, fault=fault))
    assert not os.listdir(tmp), "the run left files behind"
    return m, json.loads(json.dumps(result))


@pytest.mark.parametrize("cell", CELLS.values(), ids=CELLS.keys())
def test_run_end_to_end(tmp_path, cell):
    m, result = rehearse(tmp_path, cell)
    assert list(result) == KEYS and result["correct"] is True, result
    assert result["attempted"] > 0 and result["failed"] == 0
    want = {x["name"] for x in m.cell_metrics(cell, "end_to_end")}
    assert set(result["metrics"]) == want and "setup_s" in want
    for name, got in result["metrics"].items():
        assert got["value"] > 0 and got["unit"] == m.metrics[name]["unit"]
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert all(limit == 0 for _v, limit in result["checks"].values())


def test_traced_run_reports_per_layer_metrics(tmp_path):
    """With ``--trace 1`` the metrics are the cell's per-layer ones; a
    reader that finds nothing to read (no device plane on the CPU)
    leaves its metric out, it does not report 0."""
    cell = CELLS["sequential-write"]
    # the p99 reader wants 100 writes: a loaded test machine makes fewer
    # than that in a second
    m, result = rehearse(tmp_path, cell, trace=1, seconds=3.0)
    assert result["correct"] is True
    names = {x["name"] for x in m.cell_metrics(cell, "per_layer")}
    got = set(result["metrics"])
    assert got <= names
    assert {"write_op_p99_ms", "ec_writev_ms", "write_fops_per_flush",
            "write_device_flush_ratio", "wire_writev_ms",
            "brick_writev_ms", "ec_rmw_ratio"} <= got
    assert not {"parity_roofline", "write_device_ms_per_MiB"} & got
    assert result["metrics"]["write_device_flush_ratio"]["value"] >= 1.0


def test_no_accelerator_no_result(tmp_path):
    """Held to the CPU the command exits non-zero in seconds, prints no
    result and leaves no process and no directory behind."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELLS["sequential-write"], "--seed", "2200000011",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
    assert "no accelerator" in p.stderr
    assert not os.listdir(tmp_path)


def test_beside_nothing_else_of_the_repo(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` there is nothing to measure."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         CELLS["sequential-write"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0 and not p.stdout.strip()
