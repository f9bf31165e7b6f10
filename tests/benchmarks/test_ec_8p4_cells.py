"""The two committed cells of the 8+4 deployment with one of its three
servers down, ``ec-8p4-tpu.seq-read-1m-4down`` and
``ec-8p4-tpu.randread-4k-c64-4down``: the manifest holds them as ISSUE
32 cut them (data files beside their originals, entries appended), and
each is rehearsed whole on the CPU at tiny size: twelve managed bricks,
bricks 4-7 stopped, every read an eight-way wave of which four calls go
to parity bricks, every decode flush a k = 8 launch that rebuilds four
rows, and a control that has to come out not correct.  Twelve bricks
take about half a minute to start one after another, so there are four
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

SEQ = "ec-8p4-tpu.seq-read-1m-4down"
RAND = "ec-8p4-tpu.randread-4k-c64-4down"
D = "ec-4p2-tpu.seq-read-1m-1down"
R = "dist-ec-2x4p2-tpu.randrw-4k-c64"
#: one rehearsal, start to end, under the driver's six workers
LIMIT_S = 600
#: the read-side per-layer metrics that list D, which both cells join
JOINED = ("read_fops_per_flush", "read_device_ms_per_MiB",
          "reconstruct_roofline", "read_above_ec_ms", "ec_read_lock_ms",
          "ec_read_self_ms", "read_codec_wait_ms", "read_flush_host_ms",
          "read_h2d_ms", "read_d2h_ms", "ec_read_fanout_ms",
          "read_idle_attributed")


def rehearse(tmp_path, monkeypatch, cell, jobs=3, trace=0, fault=None,
             seen=None):
    """One whole run of ``cell``; ``seen`` collects what only the live
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
    root = tiny.tiny_root(str(tmp_path), jobs=jobs)
    tmp = os.path.join(root, "tmp")
    os.makedirs(tmp)
    m = Manifest(root, os.path.join(root, "benchmarks"))

    async def limited():
        return await asyncio.wait_for(bench.run_cell(
            tiny.args(cell, trace=trace), m, {"backend": "xla", "tmp": tmp},
            fault=fault), LIMIT_S)

    result = asyncio.run(limited())
    assert not os.listdir(tmp), "the run left files behind"
    return m, json.loads(json.dumps(result))


def _but_for(mix: dict, *keys) -> dict:
    return {k: v for k, v in mix.items() if k not in keys + ("why", "assumed")}


def test_the_manifest_has_the_cells_as_the_issue_cut_them():
    m = Manifest()
    assert m.problems() == []
    for name, traffic in ((SEQ, "seq-read-1m-4down"),
                          (RAND, "randread-4k-c64-4down")):
        cell = m.cell(name)
        assert (cell["config"], cell["traffic"], cell["chips"]) == \
            ("ec-8p4-tpu", traffic, 1)
        assert len(cell["why"]) <= 200
        assert {x["name"] for x in m.cell_metrics(name, "end_to_end")} == \
            {"read_MiB_s", "setup_s"}
    cfg, one = m.config(m.cell(SEQ)), m.config(m.cell(D))
    assert cfg["geometry"] == {"data": 8, "redundancy": 4, "groups": 1,
                               "chunk_bytes": 512, "stripe_bytes": 4096,
                               "systematic": True}
    assert cfg["bricks"] == 12 and cfg["reduced"] == ["data_set_GiB"]
    assert cfg["options"] == one["options"] and cfg["door"] == one["door"]
    assert [g.replace("any 8 of the 12", "any 4 of a group's 6")
            for g in cfg["guarantees"]] == one["guarantees"]
    assert cfg["guarantees"] != one["guarantees"]
    assert set(one["assumed"]) | {"servers", "layout"} <= set(cfg["assumed"])
    assert "from memory" in cfg["assumed"]["layout"]
    assert "server 2 holds bricks 4-7" in cfg["assumed"]["servers"]
    entry = next(c for c in m.doc["configs"] if c["name"] == "ec-8p4-tpu")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    # the traffic files are their originals but for the keys named
    seq, rand = m.traffic(m.cell(SEQ)), m.traffic(m.cell(RAND))
    assert seq["bricks_down"] == rand["bricks_down"] == [4, 5, 6, 7]
    assert _but_for(seq, "bricks_down") == \
        _but_for(m.traffic(m.cell(D)), "bricks_down")
    assert (rand["read_share"], rand["verify_extents"]) == (1.0, 1)
    assert _but_for(rand, "bricks_down", "read_share", "verify_extents") \
        == _but_for(m.traffic(m.cell(R)), "read_share", "verify_extents")
    # the metrics' lists hold both cells, beside what later PRs add
    for name in JOINED + ("read_MiB_s",):
        got = m.metrics[name]["workloads"]
        assert {SEQ, RAND, D} <= set(got), name
    assert {D, SEQ} <= set(m.metrics["read_ra_wait_ms"]["workloads"])
    assert RAND not in m.metrics["read_ra_wait_ms"]["workloads"]
    assert {SEQ, RAND} <= set(m.metrics["read_launch_fill"]["workloads"])
    assert {SEQ, RAND, D} <= set(
        m.metrics["read_wire_send_ms"]["workloads"])
    assert m.metric_file("read_launch_fill") == {
        "reader": "flush_fill", "params": {"op": "decode"}}
    assert m.metric_file("read_wire_send_ms") == {
        "reader": "span_ms", "params": {"kind": "read",
                                        "whole": ["gftpu:wire.send"]}}
    seq_layers = [x["name"] for x in m.cell_metrics(SEQ, "per_layer")]
    assert [x for x in seq_layers if x != "read_ra_wait_ms"] == \
        [x["name"] for x in m.cell_metrics(RAND, "per_layer")]
    assert set(x["name"] for x in m.cell_metrics(D, "per_layer")) == \
        set(seq_layers) - {"read_launch_fill"}


def _assert_the_degraded_wave_and_launch(m, cell, result, seen):
    assert result["correct"] is True and result["failed"] == 0, result
    assert all(v == 0 and limit == 0
               for v, limit in result["checks"].values())
    assert seen["bricks_compared"] == 12
    got = {name: v["value"] for name, v in result["metrics"].items()}
    assert set(got) <= {x["name"] for x in m.cell_metrics(cell,
                                                          "per_layer")}
    assert got["read_wire_send_ms"] > 0
    assert got["ec_read_fanout_ms"] > got["read_wire_send_ms"]
    assert 0 < got["read_launch_fill"] <= 1
    assert got["read_fops_per_flush"] >= 1
    assert {"ec_readv_ms", "wire_readv_ms", "brick_readv_ms",
            "ec_read_lock_ms", "read_codec_wait_ms", "read_flush_host_ms",
            "read_h2d_ms"} <= set(got)
    # what needs a device plane is left out, not 0
    assert not {"reconstruct_roofline", "read_device_ms_per_MiB",
                "read_d2h_ms", "read_idle_attributed"} & set(got)
    waves = {(int(e[6]["width"]), int(e[6]["parity"]))
             for e in seen["spans"] if e[0] == "gftpu:ec.fanout"
             and e[6].get("op") == "readv"}
    assert waves == {(8, 4)}
    flushes = [e[6] for e in seen["spans"] if e[0] == "gftpu:codec.flush"]
    assert flushes and all(
        (f["op"], int(f["rows_in"]), int(f["rows_out"])) == ("decode", 8, 4)
        and int(f["stripes"]) <= int(f["bucket_stripes"]) for f in flushes)
    return got, flushes


def test_the_sequential_cell_traced(tmp_path, monkeypatch):
    seen = {}
    m, result = rehearse(tmp_path, monkeypatch, SEQ, trace=1, seen=seen)
    got, _flushes = _assert_the_degraded_wave_and_launch(
        m, SEQ, result, seen)
    assert "read_ra_wait_ms" in got


def test_the_random_cell_traced(tmp_path, monkeypatch):
    """Eight of its 64 readers: a page is 32 stripes whatever the read,
    and the flushes of several fops carry them all."""
    seen = {}
    m, result = rehearse(tmp_path, monkeypatch, RAND, jobs=8, trace=1,
                         seen=seen)
    got, flushes = _assert_the_degraded_wave_and_launch(
        m, RAND, result, seen)
    assert "read_ra_wait_ms" not in got
    assert all(int(f["stripes"]) % 32 == 0 and
               int(f["stripes"]) >= 32 * int(f["fops"]) for f in flushes)


def test_the_random_cell_end_to_end(tmp_path, monkeypatch):
    seen = {}
    _m, result = rehearse(tmp_path, monkeypatch, RAND, seen=seen)
    assert list(result) == KEYS and result["correct"] is True, result
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"read_MiB_s", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert all(v == 0 and limit == 0
               for v, limit in result["checks"].values())
    assert seen["bricks_compared"] == 12


def test_a_broken_guarantee_is_not_correct(tmp_path, monkeypatch):
    _m, result = rehearse(tmp_path, monkeypatch, SEQ,
                          fault=control.FAULTS["codec_answer_altered"])
    assert result["correct"] is False
    value, limit = result["checks"]["door_bad_bytes"]
    assert value > limit
