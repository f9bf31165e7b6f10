"""BENCHMARK.json and the data files it names.  A cell, a configuration,
a traffic mix and a metric are each found by name: a later PR adds
files and entries and edits nothing that is here."""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class ManifestError(Exception):
    pass


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Manifest:
    """``root`` holds BENCHMARK.json; ``bench_dir`` holds configs/,
    traffic/, metrics/ and readers/ (a test points both elsewhere)."""

    def __init__(self, root: str = ROOT, bench_dir: str = HERE):
        self.root, self.bench_dir = root, bench_dir
        self.doc = _load(os.path.join(root, "BENCHMARK.json"))
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.metrics = {m["name"]: dict(m, kind=kind)
                        for kind in ("end_to_end", "per_layer")
                        for m in self.doc[kind]}

    def cell(self, name: str) -> dict:
        try:
            return self.cells[name]
        except KeyError:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json "
                f"(has: {sorted(self.cells)})") from None

    def config(self, cell: dict) -> dict:
        entry = next(c for c in self.doc["configs"]
                     if c["name"] == cell["config"])
        return _load(os.path.join(self.root, entry["file"]))

    def traffic(self, cell: dict) -> dict:
        return _load(os.path.join(self.bench_dir, "traffic",
                                  cell["traffic"] + ".json"))

    def cell_metrics(self, cell_name: str, kind: str) -> list[dict]:
        """The metrics of one kind that this cell reports, each with its
        own file's reader and parameters."""
        return [dict(m, **self.metric_file(m["name"]))
                for m in self.doc[kind]
                if cell_reports(self.doc, cell_name, m, kind)]

    def metric_file(self, name: str) -> dict:
        spec = _load(os.path.join(self.bench_dir, "metrics", name + ".json"))
        return {"reader": spec["reader"], "params": spec.get("params", {})}

    def reader(self, name: str):
        """``benchmarks/readers/<name>.py``'s ``read(run, **params)``."""
        path = os.path.join(self.bench_dir, "readers", name + ".py")
        if not os.path.exists(path):
            raise ManifestError(f"no reader {name!r} ({path})")
        spec = importlib.util.spec_from_file_location(
            f"benchmarks_reader_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def problems(self) -> list[str]:
        """Every way in which the manifest and the files disagree."""
        bad: list[str] = []
        doc = self.doc
        names = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
        for n in names + list(self.cells) + [c["name"] for c in
                                             doc["configs"]]:
            if not NAME.match(n):
                bad.append(f"name {n!r} has characters outside the contract")
        for n in sorted(set(x for x in names if names.count(x) > 1)):
            bad.append(f"metric {n!r} appears twice")
        for m in doc["end_to_end"] + doc["per_layer"]:
            if not UNIT.match(m["unit"]):
                bad.append(f"unit {m['unit']!r} of {m['name']}")
            for w in m.get("workloads", []):
                if w not in self.cells:
                    bad.append(f"{m['name']} lists unknown cell {w!r}")
            try:
                self.reader(self.metric_file(m["name"])["reader"])
            except (OSError, KeyError, ManifestError) as e:
                bad.append(f"{m['name']}: {e}")
        e2e = {m["name"] for m in doc["end_to_end"]}
        for m in doc["per_layer"]:
            if m["moves"] not in e2e:
                bad.append(f"{m['name']} moves unknown {m['moves']!r}")
        cfgs = {c["name"]: c for c in doc["configs"]}
        for w in doc["workloads"]:
            if w["config"] not in cfgs:
                bad.append(f"{w['name']}: unknown config {w['config']!r}")
                continue
            for what, path in (
                    ("config", os.path.join(self.root,
                                            cfgs[w["config"]]["file"])),
                    ("traffic", os.path.join(self.bench_dir, "traffic",
                                             w["traffic"] + ".json"))):
                if not os.path.exists(path):
                    bad.append(f"{w['name']}: {what} file {path} missing")
            if w["name"] != f"{w['config']}.{w['traffic']}":
                bad.append(f"{w['name']} is not <config>.<traffic>")
            for kind in ("end_to_end", "per_layer"):
                if not any(cell_reports(doc, w["name"], m, kind)
                           for m in doc[kind]
                           if m["name"] != "setup_s"):
                    bad.append(f"{w['name']} reports no {kind} metric")
        for c in doc["configs"]:
            if not any(w["config"] == c["name"] for w in doc["workloads"]):
                bad.append(f"config {c['name']} is used by no cell")
        return bad


def cell_reports(doc: dict, cell: str, m: dict, kind: str) -> bool:
    if "workloads" in m:
        return cell in m["workloads"]
    if kind == "end_to_end":
        return True
    return any(e["name"] == m["moves"] and
               cell in e.get("workloads", [cell])
               for e in doc["end_to_end"])
