"""The reduction from the program's spans in the profiler's trace to
the span metrics (``benchmarks/harness/spans.py``): by hand on a
synthetic list, on a cut of real traced runs on the chip, and end to
end at tiny size on the CPU."""

import json
import os

import pytest

from benchmarks.harness import devtrace, spans
from benchmarks.harness.manifest import HERE
from tests.benchmarks.test_rehearsal import CELLS, rehearse

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
G = spans.PREFIX


def span(name, sid, parent, a, b, **meta):
    return [G + name, a, b - a, "t", sid, parent, meta]


def synthetic():
    """One write whose flush runs on another thread, two wire calls
    side by side, and a second write that the window cuts."""
    return {
        "host": [[devtrace.WINDOW, 0, 1000], ["write_in_flight", 90, 820],
                 ["write_in_flight", 940, 200]],
        "device": {"/device:TPU:0": [["copy", 350, 50], ["k", 410, 40]]},
        "spans": [
            span("meta.writev", 1, 0, 100, 900),
            span("cluster/disperse.writev", 2, 1, 150, 850),
            span("ec.codec_wait", 3, 2, 200, 600),
            span("codec.queue", 4, 3, 200, 250),
            span("codec.flush", 5, 3, 250, 550, route="device"),
            span("codec.h2d", 6, 5, 260, 300),
            span("codec.launch", 7, 5, 300, 320),
            span("codec.d2h", 8, 5, 320, 500),
            span("codec.resume", 9, 3, 550, 600),
            span("ec.fanout", 10, 2, 600, 800),
            span("protocol/client.writev", 11, 10, 610, 700),
            span("protocol/client.writev", 12, 10, 620, 790),
            span("meta.writev", 20, 0, 950, 1100),
            span("meta.fsync", 30, 0, 910, 940),
        ]}


def test_self_time_whole_time_and_clipping():
    sp = spans.Spans.of(synthetic())
    by_id = {name: i for i, name in enumerate(sp.name)}
    ec = by_id[G + "cluster/disperse.writev"]
    assert sp.self_time(ec) == 700 - (400 + 200)
    assert sp.self_time(by_id[G + "ec.codec_wait"]) == 0  # three children
    assert sp.self_time(by_id[G + "codec.flush"]) == 300 - (40 + 20 + 180)
    # two children side by side cover their union, not their sum
    assert sp.self_time(by_id[G + "ec.fanout"]) == 200 - 180
    total = lambda **lists: sp.total("write", **lists)  # noqa: E731
    # the second write is cut at the window's end: 50 of its 150
    assert total(whole=["root"]) == 800 + 50
    assert total(whole=["root"],
                 minus=[G + "cluster/disperse.*"]) == 850 - 700
    assert total(self_time=[G + "cluster/disperse.*"]) == 100
    assert total(whole=[G + "ec.fanout"]) == 200
    assert total(whole=[G + "ec.fanout"],
                 not_under=[G + "cluster/disperse.writev"]) == 0
    # nested matches of one list count once, at the topmost
    assert total(whole=[G + "ec.codec_wait", G + "codec.flush"]) == 400
    # an fsync's tree is no write's and no read's
    assert sp.total("read", whole=["root"]) == 0
    assert [len(sp.door_ops(k)) for k in ("write", "read")] == [1, 0]
    # spans the window cuts are in no mean
    assert sp.means()[G + "meta.writev"] == [1, pytest.approx(800e-6)]


def test_device_time_is_a_duration_never_a_place():
    """Only the device ops' durations are read (their clock is up to a
    millisecond off the host planes'): no span is laid against them."""
    sp = spans.Spans.of(synthetic())
    assert sp.device_busy() == 50 + 40
    ev = synthetic()
    ev["device"] = {}
    assert spans.Spans.of(ev).device_busy() is None
    assert spans.Spans.of(ev).idle_by_span() is None


def test_idle_goes_to_the_innermost_span_across_two_threads():
    """``codec.d2h`` (pool thread) wins over the ``ec.codec_wait``
    that covers it on the loop; of two wire calls side by side the
    later one; what no span covers is not attributed."""
    sp = spans.Spans.of(synthetic())
    owner = {(a, b): sp.name[i][len(G):] for a, b, i in sp.timeline()}
    assert owner[(320, 500)] == "codec.d2h"
    assert owner[(250, 260)] == owner[(500, 550)] == "codec.flush"
    assert owner[(200, 250)] == "codec.queue"
    assert owner[(610, 620)] == owner[(620, 700)] == "protocol/client.writev"
    assert owner[(790, 800)] == "ec.fanout"
    assert owner[(800, 850)] == "cluster/disperse.writev"
    assert (0, 100) not in owner and min(owner) == (100, 150)
    idle, by = sp.idle_by_span()
    assert idle == 1000 - 90
    assert by[G + "codec.d2h"] == 30 + 10 + 50
    assert by[G + "meta.fsync"] == 30
    assert sum(by.values()) == idle - (100 + 10 + 10)


def test_no_spans_is_nothing_to_read():
    """An older program writes none: every reader then returns None."""
    ev = synthetic()
    ev["spans"] = []
    assert spans.Spans.of(ev) is None
    ev = synthetic()
    ev["host"] = ev["host"][1:]  # no window
    assert spans.Spans.of(ev) is None


def test_events_of_reads_the_metadata_from_a_real_trace(tmp_path):
    """Step one on a trace made here: an annotation that ends on
    another thread keeps its own start, and the metadata come back as
    the integers and strings they were."""
    import threading

    import jax

    devtrace.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(devtrace.WINDOW):
        q = jax.profiler.TraceAnnotation(G + "codec.queue", trace="ab12",
                                         span=7, parent=3)
        q.__enter__()
        t = threading.Thread(target=q.__exit__, args=(None, None, None))
        t.start()
        t.join(10)
        with jax.profiler.TraceAnnotation(G + "codec.flush", trace="ab12",
                                          span=8, parent=3, route="cpu",
                                          fops=2):
            pass
        with jax.profiler.TraceAnnotation("somebody_elses"):
            pass
    events = spans.load(devtrace.stop(str(tmp_path)))
    got = {e[0]: e for e in events["spans"]}
    assert set(got) == {G + "codec.queue", G + "codec.flush"}
    assert got[G + "codec.queue"][3:] == ["ab12", 7, 3, {}]
    assert got[G + "codec.flush"][3:] == ["ab12", 8, 3,
                                          {"route": "cpu", "fops": 2}]
    assert got[G + "codec.queue"][2] > 0
    assert len([e for e in events["host"] if e[0] == devtrace.WINDOW]) == 1
    sp = spans.Spans.of(events)
    assert sp is not None and len(sp.name) == 2 and sp.idle_by_span() is None


def _metric(name):
    with open(os.path.join(HERE, "metrics", name + ".json")) as f:
        return json.load(f)["params"]


PARTS = ("{k}_above_ec_ms", "ec_{k}_lock_ms", "ec_{k}_self_ms",
         "{k}_codec_wait_ms", "{k}_flush_host_ms", "{k}_h2d_ms",
         "{k}_d2h_ms", "ec_{k}_fanout_ms")


@pytest.mark.parametrize("cell,kind", [("W", "write"), ("D", "read")])
def test_recorded_parts_add_up_to_the_doors_mean(cell, kind):
    """On a cut of one real traced run of each cell on the chip (24
    door operations around one close of the eager window, from the
    middle of the run, ``recorded_spans.json``, my chip run, PR 24):
    the cell's eight millisecond metrics, as their files define
    them, plus the device's busy time are the door's mean operation to
    3%, and the flush's five children cover it to what the host
    reshapes around the launch."""
    with open(os.path.join(DATA, "recorded_spans.json")) as f:
        events = json.load(f)[cell]
    sp = spans.Spans.of(events)
    ops = sp.door_ops(kind)
    assert len(ops) == 24
    door = sum(e[2] for e in ops)
    parts = {}
    for name in PARTS:
        params = dict(_metric(name.format(k=kind)))
        assert params.pop("kind") == kind
        less = sp.device_busy() if params.pop("less_device_busy", 0) else 0
        parts[name] = sp.total(kind, **params) - less
    assert all(v > 0 for v in parts.values()), parts
    total = sum(parts.values()) + sp.device_busy()
    assert abs(total - door) / door < 0.03, (total, door, parts)
    flush = sp.total(kind, whole=[G + "codec.flush"])
    kids = sp.total(kind, whole=[G + "codec." + p for p in (
        "gather", "h2d", "launch", "d2h", "scatter")])
    assert 0.6 * flush < kids < flush
    # and of the device's idle time nearly all has a span to its name
    idle, by = sp.idle_by_span()
    assert sum(by.values()) / idle > 0.95
    assert sp.slowest_tree()[0].startswith("meta." + spans.KIND[kind][1:])


def test_traced_rehearsal_prints_the_span_metrics(tmp_path, capsys):
    """At tiny size on the CPU the metrics that need only the host's
    spans print a value; those that need a device op are left out."""
    cell = CELLS["sequential-write"]
    m, result = rehearse(tmp_path, cell, trace=1)
    assert result["correct"] is True
    got = result["metrics"]
    host_only = {n.format(k="write") for n in PARTS} - {"write_d2h_ms"}
    assert host_only <= set(got), sorted(got)
    assert all(got[n]["value"] > 0 and got[n]["unit"] == "ms"
               for n in host_only)
    assert not {"write_d2h_ms", "write_idle_attributed"} & set(got)
    out = capsys.readouterr().out
    means = json.loads(next(l for l in out.splitlines()
                            if l.startswith("span_means "))[11:])["spans"]
    # inside and outside agree: the span and the counter time one call
    n, mean_ms = means[G + "cluster/disperse.writev"]
    assert n > 0 and mean_ms == pytest.approx(
        got["ec_writev_ms"]["value"], rel=0.05)
    assert any(l.startswith("slowest_span_tree ") for l in out.splitlines())
