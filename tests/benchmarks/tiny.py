"""A throw-away copy of the manifest at a size the CPU can hold: the
same cells, configurations, metrics and readers, with every traffic
mix shrunk (files of a MiB, a second of window).  ``add`` names cells
that a later PR would bring as files and entries alone, each a directory
under ``data``: ``extra``, a second configuration (distributed disperse
2x(4+2)), a random mix (fio ``randrw`` at fio's defaults), two
end-to-end metrics and a per-layer metric over readers that are there;
``heal_room``, a second heal cell, disperse 8+4 healing one server's
four bricks eight files at a time, on the heal metrics that are there."""

from __future__ import annotations

import argparse
import json
import os
import shutil

from benchmarks.harness.manifest import HERE, ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
EXTRA_CELL = "dist-ec-2x4p2.randrw-4k-c64"
HEAL_ROOM_CELL = "ec-8p4-shd8-tpu.heal-full-1server"

#: a pool of 8 MiB: the payload walk (``Traffic._payload_offset``) over
#: a pool of the file's one MiB repeats every 256 writes, and a control
#: that stores half of each write could then leave bytes equal to the
#: model's on a run that stops on such a boundary
SHRINK = {"file_MiB": 1, "pool_MiB": 8, "warm_seconds": 0.3,
          "layout_block_KiB": 64, "verify_extents": 1}


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _dump(doc: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(doc, f)


def add_cell(root: str, data: str) -> None:
    """What a PR that brings a cell as data adds, and nothing else: the
    files of ``data/<data>/{configs,traffic,metrics}``, the entries of
    its ``entries.json``, and, where that names a cell to ``join_like``,
    the new cells on every metric list that names that one.  What the
    repo has already stands."""
    src, bench = os.path.join(DATA, data), os.path.join(root, "benchmarks")
    for sub in ("configs", "traffic", "metrics"):
        here = os.path.join(src, sub)
        for name in os.listdir(here) if os.path.isdir(here) else ():
            path = os.path.join(bench, sub, name)
            if not os.path.exists(path):
                shutil.copy(os.path.join(here, name), path)
    entries = _load(os.path.join(src, "entries.json"))
    doc = _load(os.path.join(root, "BENCHMARK.json"))
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        have = {x["name"] for x in doc[key]}
        doc[key] += [x for x in entries.get(key, []) if x["name"] not in have]
    new = [w["name"] for w in entries["workloads"]]
    for m in doc["end_to_end"] + doc["per_layer"]:
        cells = m.get("workloads", [])
        if entries.get("join_like") in cells:
            cells += [c for c in new if c not in cells]
    _dump(doc, os.path.join(root, "BENCHMARK.json"))


def tiny_root(tmp: str, jobs: int = 3, add: tuple = ()) -> str:
    """``tmp`` gets BENCHMARK.json and benchmarks/ with shrunk mixes,
    and the cells of each directory under ``data`` that ``add`` names."""
    bench = os.path.join(tmp, "benchmarks")
    for sub in ("configs", "metrics", "readers", "traffic"):
        shutil.copytree(os.path.join(HERE, sub), os.path.join(bench, sub))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
    for data in add:
        add_cell(tmp, data)
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
