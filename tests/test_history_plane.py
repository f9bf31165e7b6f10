"""Time-series metrics history + SLO burn-rate alerting (ISSUE 20):
the delta-compressed ring and its carry-forward reconstruction,
counter-reset-aware increase()/rate(), percentile trajectories over
synthetic bucket rings (monotone counters, respawn resets, sampler
gaps), burn-rate fast/slow edge cases, rule-grammar validation, live
reconfigure of every v19 key, the brick daemon's /metrics/history.json
endpoint, and the managed end-to-end storm: error-gen trips an
error-ratio rule -> ALERT_RAISED over real UDP eventsd -> an
auto-captured incident bundle whose history section shows the ramp ->
CLEARED once the storm stops."""

import asyncio
import json
import os

import pytest

from glusterfs_tpu.core import history, slo
from glusterfs_tpu.core.history import (HistoryRing, increase,
                                        merge_series,
                                        percentile_trajectory, rate)
from glusterfs_tpu.core.metrics import LogHistogram
from glusterfs_tpu.core.slo import SloEngine, parse_rules


def snap(families: dict[str, tuple[str, list]]) -> dict:
    """Synthetic REGISTRY.snapshot() shape:
    ``{family: (type, [(labels, value), ...])}`` -> snapshot dict."""
    return {name: {"type": mtype, "help": "", "samples": samples}
            for name, (mtype, samples) in families.items()}


def counter_snap(errors: float, total: float) -> dict:
    return snap({
        "gftpu_fop_errors_total": ("counter", [({"op": "readv"}, errors)]),
        "gftpu_fops_total": ("counter", [({"op": "readv"}, total)]),
    })


# -- ring storage + reconstruction -----------------------------------------

def test_ring_delta_compression_and_carry_forward():
    """Only changed keys are stored per tick; series() rebuilds a
    DENSE series by carrying unchanged values forward."""
    r = HistoryRing(interval=1.0, retention=1000.0)
    r.sample(snap({"a_total": ("counter", [({}, 1)]),
                   "g": ("gauge", [({}, 5)])}), now=100.0)
    r.sample(snap({"a_total": ("counter", [({}, 2)]),
                   "g": ("gauge", [({}, 5)])}), now=101.0)  # g unchanged
    r.sample(snap({"a_total": ("counter", [({}, 2)]),
                   "g": ("gauge", [({}, 7)])}), now=102.0)
    # stored deltas: tick 2 carries only a_total, tick 3 only g
    stored = list(r._samples)
    assert set(stored[1][1]) == {"a_total"}
    assert set(stored[2][1]) == {"g"}
    s = r.series(now=102.0)
    assert s["g"] == [[100.0, 5], [101.0, 5], [102.0, 7]]
    assert s["a_total"] == [[100.0, 1], [101.0, 2], [102.0, 2]]
    d = r.dump()
    assert d["samples"] == 3
    assert (d["first_ts"], d["last_ts"]) == (100.0, 102.0)
    assert "a_total" in d["rates"]  # counters get derived rates
    assert "g" not in d["rates"]    # gauges don't


def test_ring_retention_and_windowed_series():
    r = HistoryRing(interval=1.0, retention=10.0)
    import time as _t
    now = _t.time()
    for i in range(30):
        r.sample(snap({"x": ("gauge", [({}, i)])}), now=now - 30 + i)
    assert len(r) <= 11  # retention trimmed the old ticks
    recent = r.series(window=5.0, now=now)
    assert all(ts >= now - 5.0 for ts, _ in recent["x"])
    # non-numeric samples never enter the ring
    r.sample(snap({"s": ("gauge", [({}, "stately")]),
                   "x": ("gauge", [({}, 99)])}), now=now)
    assert "s" not in r.series(now=now)


# -- counter math ----------------------------------------------------------

def test_increase_monotone_reset_and_window():
    mono = [[0.0, 10], [1.0, 15], [2.0, 25]]
    assert increase(mono) == 15
    # counter reset (daemon respawn): the drop contributes the
    # post-reset ABSOLUTE value, not a negative delta
    reset = [[0.0, 100], [1.0, 110], [2.0, 4], [3.0, 9]]
    assert increase(reset) == 10 + 4 + 5
    # window edges: the point before t0 is the carried baseline, so
    # the delta landing ON the window's first in-range point counts
    assert increase(mono, t0=1.0, t1=2.0) == 15
    assert increase(mono, t0=1.5) == 10
    assert increase(mono, t0=0.5) == 15


def test_rate_handles_gaps_and_sparse_windows():
    pts = [[0.0, 0], [10.0, 100]]
    assert rate(pts) == pytest.approx(10.0)
    # window shorter than the gap -> one point -> 0.0, never a div/0
    assert rate(pts, window=5.0) == 0.0
    assert rate([], window=5.0) == 0.0
    assert rate([[3.0, 7]]) == 0.0


def test_percentile_trajectory_monotone_reset_and_gap():
    """p99 per tick from windowed bucket-counter increments: monotone
    growth tracks the hot bucket, a counter reset (respawn) still
    yields sane values, and a tick with an empty window (sampler gap /
    no traffic) reports an explicit 0.0 point."""
    # buckets 4 (~16us) and 10 (~1ms): all early increments land in 4,
    # later ones in 10 -> the p99 trajectory climbs bucket bounds
    bs = {4: [[0.0, 0], [1.0, 100], [2.0, 100]],
          10: [[0.0, 0], [1.0, 1], [2.0, 200]]}
    traj = percentile_trajectory(bs, 99.0, window=1.5)
    by_ts = dict((ts, v) for ts, v in traj)
    assert by_ts[1.0] == pytest.approx(LogHistogram.bound(4))
    assert by_ts[2.0] == pytest.approx(LogHistogram.bound(10))
    # p50 at t=2: 100 in bucket 4 vs 199 in bucket 10 within window
    p50 = dict((ts, v) for ts, v in
               percentile_trajectory(bs, 50.0, window=1.5))
    assert p50[2.0] == pytest.approx(LogHistogram.bound(10))
    # counter reset mid-series: the post-reset absolute value counts
    bs_reset = {4: [[0.0, 50], [1.0, 60], [2.0, 3]]}
    t = dict((ts, v) for ts, v in
             percentile_trajectory(bs_reset, 99.0, window=1.5))
    assert t[2.0] == pytest.approx(LogHistogram.bound(4))
    # gap: no increments inside the window -> explicit 0.0, never
    # interpolated away
    bs_gap = {4: [[0.0, 0], [1.0, 10], [50.0, 10]]}
    t = dict((ts, v) for ts, v in
             percentile_trajectory(bs_gap, 99.0, window=2.0))
    assert t[50.0] == 0.0


def test_merge_series_sums_counters_maxes_quantiles():
    """The gateway supervisor's per-worker merge: union time grid,
    carry-forward per worker, counters/gauges SUM, quantile-labeled
    gauges take the MAX."""
    d1 = {"series": {"c_total": [[1.0, 10], [3.0, 20]],
                     'lat{quantile="p99"}': [[1.0, 0.5]]}}
    d2 = {"series": {"c_total": [[2.0, 100]],
                     'lat{quantile="p99"}': [[2.0, 0.2]]}}
    m = merge_series([d1, d2])
    assert m["workers"] == 2
    # t=1: only worker1 (10); t=2: 10 carried + 100; t=3: 20 + 100
    assert m["series"]["c_total"] == [[1.0, 10], [2.0, 110], [3.0, 120]]
    q = dict((ts, v) for ts, v in m["series"]['lat{quantile="p99"}'])
    assert q[2.0] == 0.5  # max, not 0.7 (summing a p99 is meaningless)


# -- SLO engine ------------------------------------------------------------

def _fed_engine(feeds: list[tuple[float, float, float]]) -> SloEngine:
    """Engine over a private ring fed (now, errors, total) ticks."""
    ring = HistoryRing(interval=1.0, retention=100000.0)
    for now, errs, total in feeds:
        ring.sample(counter_snap(errs, total), now=now)
    return SloEngine(ring=ring)


def test_error_ratio_rule_raises_and_clears_on_edges():
    eng = _fed_engine([(t, 0.0, 10.0 * t) for t in range(1, 11)])
    eng.set_rules([{"name": "errs", "kind": "error-ratio",
                    "errors": "gftpu_fop_errors_total",
                    "total": "gftpu_fops_total",
                    "target": 0.05, "window": 5}])
    assert eng.evaluate(now=10.0) == {}
    # the storm: errors ramp to 50% of traffic
    for t in range(11, 16):
        eng.ring.sample(counter_snap(5.0 * (t - 10), 10.0 * t), now=t)
    active = eng.evaluate(now=15.0)
    assert "errs" in active and active["errs"]["observed"] > 0.05
    # a second breaching evaluation is NOT a second transition
    eng.evaluate(now=15.5)
    assert [e["edge"] for e in eng.transitions] == ["RAISED"]
    # recovery: healthy traffic pushes the errors out of the window
    for t in range(16, 26):
        eng.ring.sample(counter_snap(25.0, 10.0 * t), now=t)
    assert eng.evaluate(now=25.0) == {}
    assert [e["edge"] for e in eng.transitions] == ["RAISED", "CLEARED"]
    assert eng.transitions[-1]["duration"] > 0


def test_error_ratio_zero_traffic_never_breaches():
    eng = _fed_engine([(1.0, 7.0, 100.0), (2.0, 7.0, 100.0),
                       (50.0, 7.0, 100.0)])
    eng.set_rules([{"name": "idle", "kind": "error-ratio",
                    "errors": "gftpu_fop_errors_total",
                    "total": "gftpu_fops_total",
                    "target": 0.01, "window": 10}])
    # no increase in total inside the window: no budget burned
    assert eng.evaluate(now=50.0) == {}


def test_burn_rate_slow_window_vetoes_a_blip():
    """A fast-window spike with a healthy slow window must NOT raise —
    the multiwindow contract — while sustained burn over BOTH raises,
    and recovery in the fast window alone clears."""
    rule = {"name": "burn", "kind": "burn-rate",
            "errors": "gftpu_fop_errors_total",
            "total": "gftpu_fops_total",
            "slo": 0.99, "fast": 10, "slow": 100, "factor": 5}
    # 95s of clean heavy traffic, then a 5s blip at 10% errors
    eng2 = _fed_engine([(float(t), 0.0, 100.0 * t)
                        for t in range(1, 96)]
                       + [(float(t), 10.0 * (t - 95), 100.0 * t)
                          for t in range(96, 101)])
    eng2.set_rules([rule])
    # fast: 50 errs / 500 total = 10% -> burn 10 >= 5;
    # slow: 50 / 10000 = 0.5% -> burn 0.5 < 5 -> VETO
    assert eng2.evaluate(now=100.0) == {}
    # sustained: the same ratio over the whole slow window raises
    eng3 = _fed_engine([(float(t), 2.0 * t, 10.0 * t)
                        for t in range(1, 101)])
    eng3.set_rules([rule])
    active = eng3.evaluate(now=100.0)
    assert "burn" in active  # both windows burn at 20/1 percent
    assert active["burn"]["observed"] >= 5  # fast-window burn rate
    # recovery: clean fast window clears even while slow still burns
    for t in range(101, 121):
        eng3.ring.sample(counter_snap(200.0, 10.0 * t), now=float(t))
    assert eng3.evaluate(now=120.0) == {}
    assert [e["edge"] for e in eng3.transitions] == ["RAISED", "CLEARED"]


def test_burn_rate_zero_traffic_windows_never_breach():
    eng = _fed_engine([(1.0, 0.0, 0.0), (2.0, 0.0, 0.0)])
    eng.set_rules([{"name": "b", "kind": "burn-rate",
                    "errors": "gftpu_fop_errors_total",
                    "total": "gftpu_fops_total", "slo": 0.999}])
    assert eng.evaluate(now=2.0) == {}


def test_latency_threshold_and_absence_rules():
    ring = HistoryRing(interval=1.0, retention=100000.0)
    ring.sample(snap({"gftpu_gateway_request_seconds":
                      ("gauge", [({"quantile": "p99"}, 0.01)])}),
                now=1.0)
    eng = SloEngine(ring=ring)
    eng.set_rules([
        {"name": "lat", "kind": "latency-threshold",
         "metric": "gftpu_gateway_request_seconds",
         "labels": {"quantile": "p99"}, "target": 0.5, "window": 30},
        {"name": "gone", "kind": "absence",
         "metric": "app_heartbeat", "window": 10},
    ])
    # absence: app_heartbeat never produced a point, so once the
    # window has elapsed the rule breaches (newest defaults to 0.0)
    active = eng.evaluate(now=15.0)
    assert "gone" in active and "lat" not in active
    # latency: a p99 spike over target raises; the fresh heartbeat
    # clears the absence alert on the same pass
    ring.sample(snap({"gftpu_gateway_request_seconds":
                      ("gauge", [({"quantile": "p99"}, 0.9)]),
                      "app_heartbeat": ("gauge", [({}, 1)])}),
                now=16.0)
    active = eng.evaluate(now=17.0)
    assert "lat" in active and "gone" not in active
    # far future: every point is stale -> latency goes silent (no
    # observation is not a breach) while absence flips back on
    assert eng.evaluate(now=500.0).keys() == {"gone"}


def test_rule_removal_clears_its_active_alert():
    eng = _fed_engine([(t, 5.0 * t, 10.0 * t) for t in range(1, 11)])
    rule = {"name": "r", "kind": "error-ratio",
            "errors": "gftpu_fop_errors_total",
            "total": "gftpu_fops_total", "target": 0.1, "window": 5}
    eng.set_rules([rule])
    assert "r" in eng.evaluate(now=10.0)
    eng.set_rules([])  # volume reset / rules removed
    assert eng.active == {}
    assert eng.transitions[-1]["reason"] == "rule-removed"


def test_parse_rules_grammar_and_validation():
    ok, errs = parse_rules("")
    assert (ok, errs) == ([], [])
    _, errs = parse_rules("{not json")
    assert errs and "JSON" in errs[0]
    _, errs = parse_rules('{"name": "x"}')
    assert errs == ["slo-rules must be a JSON array of rule objects"]
    rules, errs = parse_rules(json.dumps([
        {"name": "good", "kind": "absence", "metric": "m"},
        {"name": "good", "kind": "absence", "metric": "m"},  # dup
        {"name": "nokind", "kind": "windmill", "metric": "m"},
        {"name": "missing", "kind": "error-ratio"},
        {"name": "badslo", "kind": "burn-rate", "errors": "e",
         "total": "t", "slo": 2.0},
        {"name": "nan", "kind": "absence", "metric": "m",
         "window": "soon"},
    ]))
    assert [r["name"] for r in rules] == ["good"]
    assert len(errs) == 5


# -- live v19 reconfigure ---------------------------------------------------

def test_iostats_reconfigure_every_v19_key(tmp_path):
    """Every op-version-19 key applies LIVE through io-stats
    reconfigure: the ring retunes interval/retention in place (keeping
    its samples) and the SLO engine swaps rule sets."""
    from glusterfs_tpu.api.glfs import Client
    from glusterfs_tpu.core.graph import Graph

    saved = (history.HISTORY.interval, history.HISTORY.retention,
             slo.ENGINE.rules, slo.ENGINE.rule_errors)
    vf = f"""
volume posix
    type storage/posix
    option directory {tmp_path}/b
end-volume
volume stats
    type debug/io-stats
    option history-interval 2
    option history-retention 77
    subvolumes posix
end-volume
"""
    async def run():
        g = Graph.construct(vf)
        c = Client(g)
        await c.mount()
        try:
            st = g.by_name["stats"]
            assert history.HISTORY.interval == 2.0
            assert history.HISTORY.retention == 77.0
            history.HISTORY.sample(counter_snap(0, 1))
            kept = len(history.HISTORY)
            rules = json.dumps([{"name": "live", "kind": "absence",
                                 "metric": "app_heartbeat_gone"}])
            st.reconfigure({"history-interval": "5",
                            "history-retention": "123",
                            "slo-rules": rules})
            assert history.HISTORY.interval == 5.0
            assert history.HISTORY.retention == 123.0
            assert len(history.HISTORY) >= kept  # retune kept samples
            assert [r["name"] for r in slo.ENGINE.rules] == ["live"]
            # a bad rule set loses itself, never the daemon
            st.reconfigure({"slo-rules": "{broken"})
            assert slo.ENGINE.rules == []
            assert slo.ENGINE.rule_errors
        finally:
            await c.unmount()

    try:
        asyncio.run(run())
    finally:
        history.HISTORY.configure(interval=saved[0], retention=saved[1])
        slo.ENGINE.set_rules(saved[2], saved[3])


# -- the daemon endpoint ----------------------------------------------------

@pytest.mark.slow
def test_brick_history_endpoint_serves_windows(tmp_path):
    """A SPAWNED brick daemon samples its own registry and serves
    /metrics/history.json with >=2 sampler windows of real series,
    derived counter rates, and the build-info identity row."""
    import subprocess
    import sys
    import time as _t

    vf = tmp_path / "b.vol"
    vf.write_text(f"""
volume posix
    type storage/posix
    option directory {tmp_path}/b
end-volume
volume locks
    type features/locks
    subvolumes posix
end-volume
volume stats
    type debug/io-stats
    option history-interval 0.2
    subvolumes locks
end-volume
""")
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        mport = s.getsockname()[1]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    portfile = tmp_path / "b.port"
    proc = subprocess.Popen(
        [sys.executable, "-m", "glusterfs_tpu.daemon",
         "--volfile", str(vf), "--listen", "0",
         "--portfile", str(portfile), "--metrics-port", str(mport)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)

    async def get_json(path):
        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       mport)
        writer.write(f"GET {path} HTTP/1.0\r\n\r\n".encode())
        await writer.drain()
        body = await reader.read()
        writer.close()
        assert b"200" in body.split(b"\r\n", 1)[0], body[:200]
        return json.loads(body.split(b"\r\n\r\n", 1)[1])

    async def run():
        deadline = _t.time() + 30
        while not portfile.exists():
            assert proc.poll() is None, proc.stderr.read().decode()[-2000:]
            assert _t.time() < deadline, "brick never reported a port"
            await asyncio.sleep(0.05)
        # the sampler is armed by the daemon: wait out >=2 windows
        doc = None
        deadline = _t.time() + 30
        while _t.time() < deadline:
            doc = await get_json("/metrics/history.json")
            if doc["samples"] >= 3 and \
                    doc["last_ts"] - doc["first_ts"] >= 2 * 0.2:
                break
            await asyncio.sleep(0.2)
        assert doc["interval"] == pytest.approx(0.2)
        assert doc["samples"] >= 3, doc["samples"]
        assert doc["last_ts"] - doc["first_ts"] >= 2 * 0.2
        # real sampled series from the live registry, with the ticker
        # counter ramping and a derived rate
        tick_keys = [k for k in doc["series"]
                     if k.startswith("gftpu_history_samples_total")
                     and 'outcome="sampled"' in k]
        assert tick_keys, sorted(doc["series"])[:10]
        pts = doc["series"][tick_keys[0]]
        assert len(pts) >= 2 and pts[-1][1] > pts[0][1]
        assert doc["rates"].get(tick_keys[0], 0) > 0
        # build-info identity rides the same registry (satellite 1)
        snap_doc = await get_json("/metrics.json")
        bi = snap_doc["gftpu_build_info"]["samples"]
        assert bi and bi[0][0]["role"] == "brick"
        assert bi[0][0]["op_version"] == "19"
        # the alerts surface answers (no rules -> empty shape)
        alerts = await get_json("/alerts.json")
        assert alerts["active"] == [] and alerts["rules"] == []

    try:
        asyncio.run(run())
    finally:
        proc.terminate()
        proc.wait(timeout=10)


# -- the managed end-to-end storm ------------------------------------------

@pytest.mark.slow
def test_alert_storm_end_to_end(tmp_path):
    """The acceptance chain: an injected error-gen storm on a managed
    volume trips an error-ratio rule inside a brick daemon ->
    ALERT_RAISED arrives over real UDP eventsd -> the brick
    auto-captures an incident bundle whose history section shows the
    error-rate ramp and whose alerts section names the rule -> `volume
    alerts` lists the RAISED alert cluster-wide (and `volume status`
    grows an alerts block) -> the alert CLEARS after the storm and the
    CLEARED edge lands in `volume alerts history`."""
    from glusterfs_tpu.core import events as gf_events
    from glusterfs_tpu.core.fops import FopError
    from glusterfs_tpu.mgmt.eventsd import EventsDaemon
    from glusterfs_tpu.mgmt.glusterd import (Glusterd, MgmtClient,
                                             mount_volume)

    # the op label scopes the ratio to readv: benign errno traffic on
    # other ops (ENODATA getxattrs ride every write) must not pollute
    # the signal, and a quiet readv plane (total increase 0) must read
    # as "no observation", not as breach or clear noise
    rules = json.dumps([{
        "name": "readv-errors", "kind": "error-ratio",
        "errors": "gftpu_fop_errors_total",
        "total": "gftpu_fops_total",
        "labels": {"op": "readv"},
        "target": 0.05, "window": 4,
    }], separators=(",", ":"))
    inc_dir = str(tmp_path / "incidents")

    async def run():
        ev = EventsDaemon()
        udp, _ctl = await ev.start()
        os.environ["GFTPU_EVENTSD"] = f"127.0.0.1:{udp}"
        gf_events.configure(f"127.0.0.1:{udp}")
        d = Glusterd(str(tmp_path / "gd"))
        try:
            await d.start()
            async with MgmtClient(d.host, d.port) as c:
                await c.call("volume-create", name="av",
                             vtype="replicate",
                             bricks=[{"path": str(tmp_path / "b0")},
                                     {"path": str(tmp_path / "b1")}])
                await c.call("volume-start", name="av")
                for k, v in (("diagnostics.history-interval", "0.25"),
                             ("diagnostics.slo-rules", rules),
                             ("diagnostics.incident-dir", inc_dir),
                             ("diagnostics.incident-min-interval", "0")):
                    await c.call("volume-set", name="av", key=k, value=v)
            # `volume alerts NAME rules` answers from the option alone
            shown = await d.op_volume_alerts("av", "rules")
            assert [r["name"] for r in shown["rules"]] == \
                ["readv-errors"]
            m = await mount_volume(d.host, d.port, "av")
            try:
                await m.write_file("/f", b"x" * 8192)
                assert await m.read_file("/f") == b"x" * 8192
                # no storm, traffic flowing: no alert
                out = await d.op_volume_alerts("av")
                assert out["active"] == []
                # ARM THE STORM: every readv on every brick fails
                async with MgmtClient(d.host, d.port) as c:
                    await c.call("volume-set", name="av",
                                 key="debug.error-gen", value="on")
                    await c.call("volume-set", name="av",
                                 key="debug.error-fops", value="readv")
                    await c.call("volume-set", name="av",
                                 key="debug.error-failure", value="100")
                deadline = asyncio.get_event_loop().time() + 60
                active = []
                while asyncio.get_event_loop().time() < deadline:
                    try:
                        await m.read_file("/f")
                    except FopError:
                        pass
                    out = await d.op_volume_alerts("av")
                    active = [a for a in out["active"]
                              if a["rule"] == "readv-errors"]
                    if active:
                        break
                    await asyncio.sleep(0.3)
                assert active, "storm never raised the alert"
                assert active[0]["observed"] > 0.05
                assert active[0]["process"].startswith("av-brick-")
                # the RAISED edge arrived over REAL UDP
                raised = [e for e in ev.recent
                          if e.get("event") == "ALERT_RAISED"]
                assert raised and \
                    raised[0]["rule"] == "readv-errors"
                # ...and auto-captured an incident bundle whose
                # history section shows the error-rate ramp
                caps = []
                deadline = asyncio.get_event_loop().time() + 20
                while asyncio.get_event_loop().time() < deadline:
                    caps = [f for f in
                            (os.listdir(inc_dir)
                             if os.path.isdir(inc_dir) else [])
                            if "ALERT_RAISED" in f]
                    if caps:
                        break
                    await asyncio.sleep(0.3)
                assert caps, "ALERT_RAISED never auto-captured"
                bundle = json.load(
                    open(os.path.join(inc_dir, sorted(caps)[0])))
                hist = bundle["history"]
                err_series = [pts for k, pts in hist["series"].items()
                              if k.startswith("gftpu_fop_errors_total")]
                assert err_series, sorted(hist["series"])[:10]
                ramp = max(pts[-1][1] - pts[0][1]
                           for pts in err_series)
                assert ramp > 0, "history section shows no error ramp"
                assert bundle["alerts"]["active"][0]["rule"] == \
                    "readv-errors"
                # volume status grew an alerts block (fan-out cached)
                st = d.op_volume_status("av")
                assert st["alerts"]["rules"] == 1
                assert st["alerts"]["active"][0]["rule"] == \
                    "readv-errors"
                # STOP THE STORM by shifting traffic to writes (only
                # readv is error-gen'd) — NOT by volume-set, which
                # would restart the bricks and lose the raising
                # process's transition history.  Healthy writes push
                # the error ratio under target and the alert clears
                # in the same process that raised it.
                deadline = asyncio.get_event_loop().time() + 60
                while asyncio.get_event_loop().time() < deadline:
                    await m.write_file("/f", b"y" * 4096)
                    out = await d.op_volume_alerts("av")
                    if not out["active"]:
                        break
                    await asyncio.sleep(0.3)
                assert out["active"] == [], "alert never cleared"
                hist_out = await d.op_volume_alerts("av", "history")
                edges = [t["edge"] for t in hist_out["history"]
                         if t["rule"] == "readv-errors"]
                assert "RAISED" in edges and "CLEARED" in edges
                cleared = [e for e in ev.recent
                           if e.get("event") == "ALERT_CLEARED"]
                assert cleared, "CLEARED edge never reached eventsd"
            finally:
                await m.unmount()
        finally:
            await d.stop()
            os.environ.pop("GFTPU_EVENTSD", None)
            gf_events.configure(None)
            await ev.stop()

    asyncio.run(run())
