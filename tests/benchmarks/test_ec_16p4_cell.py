"""The committed cell of the widest disperse deployment,
``ec-16p4-tpu.seq-write-1m``, rehearsed whole on the CPU at tiny size:
twenty managed bricks, 8 KiB stripes, a write wave of sixteen data
calls and four parity calls, the ``wire.send`` metric the cell brought,
and a control that has to come out not correct on it.  Twenty bricks
take about a minute to start one after another, so there are three
rehearsals and no more."""

import asyncio
import json
import os

from benchmarks import control
from benchmarks import run as bench
from benchmarks.harness import spans
from benchmarks.harness.manifest import Manifest
from tests.benchmarks import tiny
from tests.benchmarks.test_rehearsal import KEYS

CELL = "ec-16p4-tpu.seq-write-1m"
W = "ec-4p2-tpu.seq-write-1m"
#: one rehearsal, start to end, under the driver's six workers
LIMIT_S = 600


def rehearse(tmp_path, monkeypatch, trace=0, fault=None, seen=None):
    """One whole run of the cell; ``seen`` collects what only the live
    run can say (the brick files compared, the spans with their
    metadata, which ``Spans`` drops)."""
    fragments, load = bench.check.fragments_on_bricks, spans.load

    def counted(brick_dirs, *rest):
        seen["bricks_compared"] = len(brick_dirs)
        return fragments(brick_dirs, *rest)

    def loaded(path):
        events = load(path)
        seen["spans"] = events["spans"]
        return events

    if seen is not None:
        monkeypatch.setattr(bench.check, "fragments_on_bricks", counted)
        monkeypatch.setattr(spans, "load", loaded)
    root = tiny.tiny_root(str(tmp_path))
    tmp = os.path.join(root, "tmp")
    os.makedirs(tmp)
    m = Manifest(root, os.path.join(root, "benchmarks"))

    async def limited():
        return await asyncio.wait_for(bench.run_cell(
            tiny.args(CELL, trace=trace), m, {"backend": "xla", "tmp": tmp},
            fault=fault), LIMIT_S)

    result = asyncio.run(limited())
    assert not os.listdir(tmp), "the run left files behind"
    return m, json.loads(json.dumps(result))


def test_the_manifest_has_the_cell_as_the_issue_cut_it():
    m = Manifest()
    assert m.problems() == []
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("ec-16p4-tpu", "seq-write-1m", 1)
    cfg, one = m.config(cell), m.config(m.cell(W))
    assert cfg["geometry"] == {"data": 16, "redundancy": 4, "groups": 1,
                               "chunk_bytes": 512, "stripe_bytes": 8192,
                               "systematic": True}
    assert cfg["bricks"] == 20 and cfg["reduced"] == []
    assert cfg["options"] == one["options"] and cfg["door"] == one["door"]
    assert [g.replace("any 16 of the 20", "any 4 of a group's 6")
            for g in cfg["guarantees"]] == one["guarantees"]
    assert cfg["guarantees"] != one["guarantees"]
    assert set(one["assumed"]) < set(cfg["assumed"])
    assert "from memory" in cfg["assumed"]["layout"]
    entry = next(c for c in m.doc["configs"] if c["name"] == "ec-16p4-tpu")
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]
    assert len(cfg["source"]) <= 200
    assert m.traffic(cell) == m.traffic(m.cell(W))
    assert {x["name"] for x in m.cell_metrics(CELL, "end_to_end")} == \
        {"write_MiB_s", "setup_s"}
    # what W reports per layer, the new cell reports too
    assert [x["name"] for x in m.cell_metrics(CELL, "per_layer")] == \
        [x["name"] for x in m.cell_metrics(W, "per_layer")]
    assert m.metrics["write_wire_send_ms"]["workloads"] == [CELL, W]


def test_run_end_to_end(tmp_path, monkeypatch):
    seen = {}
    _m, result = rehearse(tmp_path, monkeypatch, seen=seen)
    assert list(result) == KEYS and result["correct"] is True, result
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"write_MiB_s", "setup_s"}
    assert all(got["value"] > 0 for got in result["metrics"].values())
    assert all(v == 0 and limit == 0
               for v, limit in result["checks"].values())
    assert {"fragment_bad_bytes", "flushes_off_device",
            "door_bad_bytes"} <= set(result["checks"])
    assert seen["bricks_compared"] == 20


def test_traced_run_reports_the_wire_send_and_the_waves_width(
        tmp_path, monkeypatch):
    seen = {}
    m, result = rehearse(tmp_path, monkeypatch, trace=1, seen=seen)
    assert result["correct"] is True and result["failed"] == 0, result
    got = {name: v["value"] for name, v in result["metrics"].items()}
    assert set(got) <= {x["name"] for x in m.cell_metrics(CELL,
                                                          "per_layer")}
    assert got["write_wire_send_ms"] > 0
    assert got["ec_write_fanout_ms"] > got["write_wire_send_ms"]
    # (no write_op_p99_ms here: under the driver's six workers a one
    # second window may hold fewer than the hundred writes it needs)
    assert {"ec_writev_ms", "wire_writev_ms", "brick_writev_ms",
            "ec_write_lock_ms", "write_codec_wait_ms"} <= set(got)
    assert got["ec_rmw_ratio"] == 0 and got["write_fops_per_flush"] == 1
    # what needs a device plane is left out, not 0
    assert not {"parity_roofline", "write_device_ms_per_MiB"} & set(got)
    waves = {(e[6].get("part"), int(e[6]["width"]))
             for e in seen["spans"] if e[0] == "gftpu:ec.fanout"
             and e[6].get("op") == "writev"}
    assert waves == {("data", 16), ("parity", 4)}
    sends = [e[6] for e in seen["spans"] if e[0] == "gftpu:wire.send"]
    assert sends and all(int(s["bytes"]) > 0 and s["fop"] for s in sends)
    # (bytes on the socket: with the shm lane armed a payload rides the
    # arena and its descriptor crosses)
    assert {"writev", "inodelk", "xattrop"} <= {s["fop"] for s in sends}


def test_a_broken_guarantee_is_not_correct(tmp_path, monkeypatch):
    _m, result = rehearse(tmp_path, monkeypatch,
                          fault=control.FAULTS["codec_answer_altered"])
    assert result["correct"] is False
    value, limit = result["checks"]["fragment_bad_bytes"]
    assert value > limit
