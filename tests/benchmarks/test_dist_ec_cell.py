"""The committed cell of the distributed-disperse deployment,
``dist-ec-2x4p2-tpu.randrw-4k-c64``, rehearsed whole on the CPU at tiny
size with eight jobs: files in both disperse groups, more than one fop
in a flush, the two per-layer metrics the cell brought, and the
controls that have to come out not correct on it."""

import asyncio
import json
import os

import pytest

from benchmarks import control
from benchmarks import run as bench
from benchmarks.harness import check
from benchmarks.harness.manifest import Manifest
from tests.benchmarks import tiny
from tests.benchmarks.test_rehearsal import KEYS

CELL = "dist-ec-2x4p2-tpu.randrw-4k-c64"
JOBS = 8
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def rehearse(tmp_path, trace=0, fault=None, seen=None, seconds=1.0):
    """One whole run of the cell with eight of its 64 jobs; ``seen``
    collects what only the live run can say (where the files fell,
    what ``cluster/dht`` counted)."""

    def look(run):
        n = run.config["geometry"]["data"] + \
            run.config["geometry"]["redundancy"]
        groups = (check.group_of(name, run.volume.bricks, n)
                  for name in run.traffic.names)
        if seen is not None:
            seen["groups"] = {run.volume.bricks.index(g[0]) // n
                              for g in groups if g}
            seen["dht"] = run.volume.layers("cluster/distribute")
        if fault is not None:
            fault(run)

    root = tiny.tiny_root(str(tmp_path), jobs=JOBS)
    tmp = os.path.join(root, "tmp")
    os.makedirs(tmp)
    m = Manifest(root, os.path.join(root, "benchmarks"))
    result = asyncio.run(bench.run_cell(
        tiny.args(CELL, trace=trace, seconds=seconds), m, {"backend": "xla", "tmp": tmp},
        fault=look))
    assert not os.listdir(tmp), "the run left files behind"
    return m, json.loads(json.dumps(result))


def test_the_manifest_has_the_cell_as_the_issue_cut_it():
    m = Manifest()
    cell = m.cell(CELL)
    assert cell["chips"] == 1
    mix, cfg = m.traffic(cell), m.config(cell)
    assert (mix["pattern"], mix["jobs"], mix["block_KiB"],
            mix["read_share"], mix["align_KiB"], mix["file_MiB"],
            mix["fsync"]) == ("random", 64, [4], 0.5, 4, 32, "close")
    assert {"jobs", "file_MiB", "loop", "fsync", "payload"} <= \
        set(mix["assumed"])
    assert (cfg["bricks"], cfg["geometry"]["groups"]) == (12, 2)
    one = m.config(m.cell("ec-4p2-tpu.seq-write-1m"))
    assert cfg["options"] == one["options"]
    assert cfg["guarantees"] == one["guarantees"]
    assert {x["name"] for x in m.cell_metrics(CELL, "end_to_end")} == \
        {"write_MiB_s", "read_MiB_s", "setup_s"}


def test_run_end_to_end(tmp_path):
    seen = {}
    m, result = rehearse(tmp_path, seen=seen)
    assert list(result) == KEYS and result["correct"] is True, result
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"write_MiB_s", "read_MiB_s",
                                      "setup_s"}
    assert all(got["value"] > 0 for got in result["metrics"].values())
    assert all(v == 0 and limit == 0
               for v, limit in result["checks"].values())
    assert seen["groups"] == {0, 1}, "every file fell into one group"


def test_traced_run_reports_the_cells_own_metrics(tmp_path):
    """More than one fop in a flush, the launches' fill and dht's time
    are there; what needs a device plane is left out, not 0."""
    seen = {}
    # 3 s: the tails' reader wants 100 reads and 100 writes, which one
    # second of eight jobs misses on a loaded test machine
    m, result = rehearse(tmp_path, trace=1, seen=seen, seconds=3.0)
    assert result["correct"] is True and result["failed"] == 0, result
    got = {name: v["value"] for name, v in result["metrics"].items()}
    assert set(got) <= {x["name"] for x in m.cell_metrics(CELL,
                                                          "per_layer")}
    assert got["write_fops_per_flush"] > 1
    assert 0 < got["write_launch_fill"] <= 1
    assert got["read_dht_ms"] > 0
    assert got["write_device_flush_ratio"] >= 1.0
    assert {"read_op_p99_ms", "write_op_p99_ms", "ec_writev_ms",
            "ec_readv_ms", "ec_rmw_ratio", "ec_read_lock_ms",
            "ec_read_fanout_ms", "write_above_ec_ms"} <= set(got)
    # left off the cell's list: it reads below zero here (PERF.md §3)
    assert "read_above_ec_ms" not in got
    assert not {"parity_roofline", "write_device_ms_per_MiB",
                "read_d2h_ms", "read_idle_attributed"} & set(got)
    assert got["ec_rmw_ratio"] == 0
    (dht,) = seen["dht"]
    routed = dht.dump_private()["routed"]
    assert len(routed) == 2 and all(n > 0 for n in routed.values())


@pytest.mark.parametrize("fault, number", [
    ("codec_answer_altered", "fragment_bad_bytes"),
    ("acked_write_half_stored", "door_bad_bytes"),
])
def test_a_broken_guarantee_is_not_correct(tmp_path, fault, number):
    _m, result = rehearse(tmp_path, fault=control.FAULTS[fault])
    assert result["correct"] is False
    assert result["checks"][number][0] > result["checks"][number][1]


def test_the_fill_reader_on_recorded_and_on_older_spans():
    """``flush_fill`` adds up the flushes of one op that began in the
    window; on flush spans without ``stripes`` (the parent commit's, as
    recorded under ``data/``) it returns nothing and does not raise."""
    import importlib

    fill = importlib.import_module("benchmarks.readers.flush_fill").fill
    with open(os.path.join(DATA, "recorded_spans.json")) as f:
        older = json.load(f)["W"]["spans"]
    assert any(e[0] == "gftpu:codec.flush" for e in older)
    assert fill(older, 0, float("inf"), "encode") is None
    flush = ["gftpu:codec.flush", 10, 5, "t", 1, 0]
    events = [flush + [{"op": "encode", "stripes": 6, "bucket_stripes": 16}],
              flush + [{"op": "encode", "stripes": "26",
                        "bucket_stripes": "32"}],
              flush + [{"op": "decode", "stripes": 1, "bucket_stripes": 16}],
              ["gftpu:codec.flush", 99, 5, "t", 2, 0,
               {"op": "encode", "stripes": 1, "bucket_stripes": 16}],
              ["gftpu:ec.fanout", 10, 5, "t", 3, 0, {"stripes": 9}]]
    assert fill(events, 0, 50, "encode") == 32 / 48
    assert fill(events, 0, 5, "encode") is None
    assert fill([], 0, 50, "encode") is None
