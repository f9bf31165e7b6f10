"""A throw-away copy of the manifest at a size the CPU can hold: the
same cells, configurations, metrics and readers, with every traffic
mix shrunk (files of a MiB, a second of window).  With ``extra`` it
also gets what a later PR would bring for one more cell, as files and
entries alone (``data/extra``): a second configuration (distributed
disperse 2x(4+2)), a random mix (fio ``randrw`` at fio's defaults), two
end-to-end metrics and a per-layer metric over readers that are there."""

from __future__ import annotations

import argparse
import json
import os
import shutil

from benchmarks.harness.manifest import HERE, ROOT

EXTRA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "data", "extra")
EXTRA_CELL = "dist-ec-2x4p2.randrw-4k-c64"

SHRINK = {"file_MiB": 1, "pool_MiB": 1, "warm_seconds": 0.3,
          "layout_block_KiB": 64, "verify_extents": 1}


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _dump(doc: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(doc, f)


def add_extra(root: str) -> None:
    bench = os.path.join(root, "benchmarks")
    shutil.copy(os.path.join(EXTRA, "dist-ec-2x4p2.json"),
                os.path.join(bench, "configs"))
    shutil.copy(os.path.join(EXTRA, "randrw-4k-c64.json"),
                os.path.join(bench, "traffic"))
    _dump({"reader": "rate_MiB_s", "params": {"kinds": ["read", "write"]}},
          os.path.join(bench, "metrics", "rw_MiB_s.json"))
    for name, kinds in (("op_p99_ms", ["read", "write"]),
                        ("rw_read_op_p99_ms", ["read"])):
        _dump({"reader": "op_percentile_ms",
               "params": {"kinds": kinds, "q": 99}},
              os.path.join(bench, "metrics", name + ".json"))
    doc = _load(os.path.join(root, "BENCHMARK.json"))
    doc["configs"].append({
        "name": "dist-ec-2x4p2", "source": "BASELINE.json config 5",
        "file": "benchmarks/configs/dist-ec-2x4p2.json",
        "reduced": ["data_set_GiB"], "why": "a test's extra configuration"})
    doc["workloads"].append({
        "name": EXTRA_CELL, "config": "dist-ec-2x4p2",
        "traffic": "randrw-4k-c64", "chips": 1, "why": "a test's extra cell"})
    doc["end_to_end"] += [
        {"name": "rw_MiB_s", "unit": "MiB/s", "better": "higher",
         "bound": 0.1, "source": "host_clock", "workloads": [EXTRA_CELL]},
        {"name": "op_p99_ms", "unit": "ms", "better": "lower",
         "bound": 0.1, "source": "host_clock", "workloads": [EXTRA_CELL]}]
    doc["per_layer"].append({
        "name": "rw_read_op_p99_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "door (api/glfs)",
        "moves": "op_p99_ms"})
    _dump(doc, os.path.join(root, "BENCHMARK.json"))


def tiny_root(tmp: str, jobs: int = 3, extra: bool = False) -> str:
    """``tmp`` gets BENCHMARK.json and benchmarks/ with shrunk mixes."""
    bench = os.path.join(tmp, "benchmarks")
    for sub in ("configs", "metrics", "readers", "traffic"):
        shutil.copytree(os.path.join(HERE, sub), os.path.join(bench, sub))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
    if extra:
        add_extra(tmp)
    tdir = os.path.join(bench, "traffic")
    for name in os.listdir(tdir):
        mix = _load(os.path.join(tdir, name))
        mix.update(SHRINK, jobs=min(mix["jobs"], jobs))
        mix["block_KiB"] = [min(b, 64) for b in mix["block_KiB"]]
        if mix.get("window") != "heal":  # a heal window is 1 MiB still
            mix["warm_stripes_max"] = min(mix["warm_stripes_max"], 64)
        mix["verify_files"] = mix["jobs"]
        mix["sample_reads_every"] = 2
        _dump(mix, os.path.join(tdir, name))
    return tmp


def args(workload: str, seed: int = 3000000019, seconds: float = 1.0,
         trace: int = 0):
    return argparse.Namespace(workload=workload, seed=seed,
                              seconds=seconds, trace=trace)
