"""The reduction from a profiler trace to seconds, on a trace made by
hand and on one recorded on the chip (``data/recorded_events.json``:
``devtrace.events_of`` of the first seconds of a traced run of
``ec-4p2-tpu.seq-write-1m`` on a TPU v5 lite, PR 23)."""

import json
import os

import pytest

from benchmarks.harness import devtrace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "recorded_events.json")


def by_hand():
    # window 1000..2000 ns; two operations overlap (one stream would
    # not, two chips' planes can), one lies outside the window
    return {
        "device": {"/device:TPU:0": [
            ["%copy.1 = u8[2,512]{1,0} copy(%p)", 1100, 100],
            ["%fusion.2 = u8[4] fusion(%x), kind=kLoop", 1150, 100],
            ["%copy.1 = u8[2,512]{1,0} copy(%p)", 1500, 50],
            ["%late = u8[4] copy(%p)", 1990, 100],
            ["%before = u8[4] copy(%p)", 10, 100],
        ]},
        "host": [[devtrace.WINDOW, 1000, 1000],
                 ["write_in_flight", 1000, 300],
                 ["fsync_write_in_flight", 1300, 400]],
        "lines": {},
    }


def test_reduce_by_hand():
    r = devtrace.reduce(by_hand())
    ns = 1e-9
    assert r["window_s"] == pytest.approx(1000 * ns)
    # union: 1100-1250, 1500-1550, 1990-2000
    assert r["busy_s"] == pytest.approx((150 + 50 + 10) * ns)
    # sum of every operation's own time inside the window
    assert r["op_s"] == pytest.approx((100 + 100 + 50 + 10) * ns)
    ops = dict(r["device_ops"])
    assert ops["copy.1_u8_2_512"] == pytest.approx(150 * ns)
    gaps = dict(r["idle_gaps"])
    # idle: 1000-1100 and 1250-1300 under write, 1300-1500 and
    # 1550-1700 under fsync+write, 1700-1990 under nothing
    assert gaps["write_in_flight"] == pytest.approx(150 * ns)
    assert gaps["fsync_write_in_flight"] == pytest.approx(350 * ns)
    assert gaps["nothing_in_flight"] == pytest.approx(290 * ns)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_two_devices_are_averaged_for_busy_and_summed_for_work():
    ev = by_hand()
    ev["device"]["/device:TPU:1"] = [["%x = copy(%p)", 1000, 1000]]
    r = devtrace.reduce(ev)
    assert r["busy_s"] == pytest.approx((210 + 1000) / 2 * 1e-9)
    assert r["op_s"] == pytest.approx((260 + 1000) * 1e-9)


@pytest.mark.parametrize("spoil", [
    lambda ev: ev["host"].pop(0),              # no window annotation
    lambda ev: ev["device"].clear(),           # no device plane
])
def test_nothing_to_read_is_none(spoil):
    ev = by_hand()
    spoil(ev)
    assert devtrace.reduce(ev) is None


def test_reduce_recorded_trace():
    with open(RECORDED) as f:
        rec = json.load(f)
    r = devtrace.reduce(rec["events"])
    want = rec["expect"]
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert r["op_s"] == pytest.approx(want["op_s"])
    assert 0 < r["busy_s"] <= r["op_s"] * 1.0000001 <= r["window_s"]
    assert [n for n, _ in r["device_ops"]] == \
        [n for n, _ in want["device_ops"]]
    assert sum(s for _n, s in devtrace.reduce(
        rec["events"], top=10**6)["idle_gaps"]) + r["busy_s"] == \
        pytest.approx(r["window_s"])


def test_in_flight_names(monkeypatch):
    """One annotation at a time, named by what is in flight."""
    from benchmarks.harness.traffic import InFlight

    seen = []

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("open", self.name))

        def __exit__(self, *exc):
            seen.append(("close", self.name))

    f = InFlight(Note)
    f.enter("write")
    f.enter("write")
    f.enter("fsync")
    f.exit("write")
    f.exit("write")
    f.exit("fsync")
    assert seen == [
        ("open", "write_in_flight"), ("close", "write_in_flight"),
        ("open", "fsync_write_in_flight"),
        ("close", "fsync_write_in_flight"),
        ("open", "fsync_in_flight"), ("close", "fsync_in_flight")]
    quiet = InFlight()
    quiet.enter("read")
    quiet.exit("read")
    assert quiet.current is None
