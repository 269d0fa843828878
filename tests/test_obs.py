"""Observability plane: registry semantics, SLO monitor parity, trace
schema, Prometheus round-trip, and the event-loop tie-order regression.

The default registry is process-global and *disabled* — every test that
enables it must restore the disabled/empty state so instrumentation
stays free for the rest of the suite.
"""

import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.core.events import EventLoop
from repro.obs import export, slo
from repro.obs.registry import Registry


@pytest.fixture
def default_obs():
    """Enable the process-global registry, restore disabled+empty after."""
    reg = obs.enable()
    reg.reset()
    tracer0 = obs.get_tracer()
    yield reg
    obs.set_tracer(tracer0)
    obs.disable()
    reg.reset()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = Registry()
    c = reg.counter("c_total", "help", labels=("k",))
    c.labels(k="a").inc()
    c.labels(k="a").inc(2.5)
    c.labels(k="b").inc()
    assert reg.value("c_total", k="a") == 3.5
    assert reg.value("c_total", k="b") == 1.0
    assert reg.value("c_total", k="missing") == 0.0
    with pytest.raises(ValueError):
        c.labels(k="a").inc(-1)

    g = reg.gauge("g")
    g.set(7.0)
    g.dec(2.0)
    assert reg.value("g") == 5.0

    h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 10.0):
        h.observe(v)
    row = [r for r in reg.collect() if r["name"] == "h_seconds"][0]
    assert row["count"] == 4
    assert row["sum"] == pytest.approx(11.05)
    # bucket counts are CUMULATIVE and the +Inf bucket equals count
    assert row["buckets"] == [[0.1, 1], [1.0, 3], [float("inf"), 4]]


def test_registry_get_or_create_and_kind_conflicts():
    reg = Registry()
    a = reg.counter("x_total", "first", labels=("k",))
    b = reg.counter("x_total", "ignored", labels=("k",))
    assert a is b
    with pytest.raises(ValueError):
        reg.gauge("x_total")                     # kind redefinition
    with pytest.raises(ValueError):
        reg.counter("x_total", labels=("other",))  # label redefinition


def test_disabled_registry_is_noop():
    reg = Registry(enabled=False)
    c = reg.counter("c_total")
    c.inc(100)
    reg.gauge("g").set(4)
    reg.histogram("h").observe(1.0)
    assert reg.value("c_total") == 0.0
    assert reg.value("g") == 0.0
    for row in reg.collect():
        assert row.get("value", 0.0) == 0.0
        assert row.get("count", 0) == 0


def test_default_registry_helpers_free_when_off(default_obs):
    obs.disable()
    obs.inc("ufa_sweep_runs_total")
    obs.set_gauge("ufa_sweep_scenarios_per_s", 123.0)
    assert obs.value("ufa_sweep_runs_total") == 0.0
    obs.enable()
    obs.inc("ufa_sweep_runs_total")
    obs.inc("ufa_ingest_records_total", 10, backend="numpy")
    assert obs.value("ufa_sweep_runs_total") == 1.0
    assert obs.value("ufa_ingest_records_total", backend="numpy") == 10.0
    kind, help_, _ = obs.describe("ufa_ingest_records_total")
    assert kind == "counter" and help_


def test_helpers_allow_label_literally_named_name(default_obs):
    # a gauge whose label IS "name" — the helpers take their
    # metric-name/value arguments positional-only so this cannot collide
    default_obs.gauge("t_rows", labels=("name",))
    obs.set_gauge("t_rows", 12.5, name="row_a")
    assert obs.value("t_rows", name="row_a") == 12.5


def test_registry_thread_reentrancy():
    reg = Registry()
    c = reg.counter("t_total", labels=("k",))

    def worker(k):
        for _ in range(2000):
            c.labels(k=k).inc()

    threads = [threading.Thread(target=worker, args=(f"w{i % 3}",))
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = sum(reg.value("t_total", k=f"w{i}") for i in range(3))
    assert total == 6 * 2000


# ---------------------------------------------------------------------------
# event loop: deferred re-push keeps the original tie order
# ---------------------------------------------------------------------------

def test_event_loop_deferred_event_keeps_tie_order():
    loop = EventLoop()
    order = []
    loop.schedule(10.0, lambda: order.append("A"))
    loop.schedule(10.0, lambda: order.append("B"))
    # partial run defers A (popped, beyond the horizon, re-pushed)
    assert loop.run(until=5.0) == 0
    # a later-scheduled same-time event must still fire AFTER A and B
    loop.schedule(10.0, lambda: order.append("C"))
    loop.run()
    assert order == ["A", "B", "C"]
    assert loop.now == 10.0


def test_event_loop_counts_events_when_obs_on(default_obs):
    loop = EventLoop()
    loop.schedule(1.0, lambda: None, label="wave")
    loop.schedule(2.0, lambda: None, label="wave")
    loop.run()
    assert obs.value("ufa_orch_events_total", label="wave") == 2.0


# ---------------------------------------------------------------------------
# SLO burn-rate monitor: jitted path == numpy reference, exact alert times
# ---------------------------------------------------------------------------

def _trace(dips, n=240, dt=30.0):
    """Availability trace: 1.0 except [i0, i1) steps pinned to `avail`."""
    ts = np.arange(n) * dt
    avail = np.ones(n)
    for i0, i1, a in dips:
        avail[i0:i1] = a
    return avail, ts


def test_slo_alerts_np_fires_on_deep_dip_only():
    # deep long dip: burn = (1-0.99)/(0.0003) = 33x >> both thresholds
    avail, ts = _trace([(10, 120, 0.99)])
    v = slo.alerts_np(avail, ts)
    assert bool(v["alert"])
    assert np.isfinite(v["t_first_alert"])
    assert v["burn_peak"] > 14.4
    # healthy trace at exactly the target burns at 1x: no alert
    avail2 = np.full(240, slo.DEFAULT_TARGET)
    v2 = slo.alerts_np(avail2, ts)
    assert not bool(v2["alert"])
    assert v2["t_first_alert"] == float("inf")
    assert int(v2["rule_first_alert"]) == -1


def test_slo_sweep_alerts_matches_numpy_reference_exactly():
    traces = [
        _trace([])[0],                          # clean
        _trace([(10, 120, 0.99)])[0],           # deep sustained dip
        _trace([(5, 12, 0.95)])[0],             # short sharp spike
        _trace([(0, 240, 0.9995)])[0],          # mild burn, never alerts
        _trace([(200, 240, 0.98)])[0],          # late dip
    ]
    ts = _trace([])[1]
    out = slo.sweep_alerts(np.stack(traces), ts)
    assert out["alert"].shape == (5,)
    for i, tr in enumerate(traces):
        ref = slo.alerts_np(tr, ts)
        assert bool(out["alert"][i]) == bool(ref["alert"]), i
        # exact alert-time agreement (well-separated thresholds)
        assert float(out["t_first_alert"][i]) == float(ref["t_first_alert"])
        assert int(out["rule_first_alert"][i]) == int(ref["rule_first_alert"])
    assert bool(out["alert"][0]) is False and bool(out["alert"][1]) is True


def test_slo_sweep_alerts_records_metrics(default_obs):
    avail, ts = _trace([(10, 120, 0.99)])
    out = slo.sweep_alerts(np.stack([avail, np.ones_like(avail)]), ts)
    assert int(out["alert"].sum()) == 1
    assert obs.value("ufa_slo_scenarios_alerting") == 1.0
    ri = int(out["rule_first_alert"][0])
    rule = slo.DEFAULT_RULES[ri]
    assert obs.value("ufa_slo_alerts_total", rule=rule.name) == 1.0


def test_rolling_mean_partial_prefixes():
    x = np.array([4.0, 2.0, 6.0, 8.0])
    got = slo._rolling_mean_np(x, 2)
    assert np.allclose(got, [4.0, 3.0, 4.0, 7.0])


# ---------------------------------------------------------------------------
# Chrome trace schema
# ---------------------------------------------------------------------------

def test_tracer_chrome_schema_valid():
    tr = obs.Tracer()
    tr.sim_span("mbb-wave", 10.0, 40.0, args={"n": 3})
    tr.sim_instant("slo-alert", 25.0)
    with tr.span("host-phase"):
        pass
    doc = tr.to_chrome()
    assert export is not None  # silence linters about unused import chains
    assert obs.validate_chrome_trace(doc) == []
    evs = doc["traceEvents"]
    span = [e for e in evs if e["ph"] == "X" and e["name"] == "mbb-wave"][0]
    # sim time maps 1 s -> 1e6 trace us, spanning scheduled-at -> fired-at
    assert span["ts"] == 10.0 * 1e6 and span["dur"] == 30.0 * 1e6
    names = {e["name"] for e in evs if e["ph"] == "M"}
    assert "process_name" in names and "thread_name" in names


def test_validate_chrome_trace_flags_bad_events():
    bad = {"traceEvents": [
        {"ph": "Z", "name": "x", "pid": 1, "tid": 0, "ts": 0},
        {"ph": "X", "name": "y", "pid": 1, "tid": 0, "ts": 5},   # no dur
    ]}
    problems = obs.validate_chrome_trace(bad)
    assert len(problems) >= 2


def test_event_loop_tracer_emits_spans():
    tr = obs.Tracer()
    loop = EventLoop()
    loop.tracer = tr
    loop.schedule(3.0, lambda: None, label="bbm-evict")
    loop.log("checkpoint")
    loop.run()
    doc = tr.to_chrome()
    assert obs.validate_chrome_trace(doc) == []
    spans = [e for e in doc["traceEvents"]
             if e["ph"] == "X" and e["name"] == "bbm-evict"]
    assert len(spans) == 1
    assert spans[0]["ts"] == 0.0 and spans[0]["dur"] == 3.0 * 1e6


# ---------------------------------------------------------------------------
# Prometheus exposition round-trip
# ---------------------------------------------------------------------------

def test_prometheus_round_trip(tmp_path):
    reg = Registry()
    c = reg.counter("rt_total", 'help with "quotes"\nand newline',
                    labels=("backend",))
    c.labels(backend="numpy").inc(5)
    c.labels(backend='we"ird\\nm\ne').inc(2)
    reg.gauge("rt_gauge").set(2.5)
    h = reg.histogram("rt_seconds", buckets=(0.5, 2.0))
    h.observe(0.1)
    h.observe(1.0)

    text = export.to_prometheus(reg)
    assert export.validate_prometheus(text) == []
    fams = export.parse_prometheus(text)
    assert fams["rt_total"]["type"] == "counter"
    vals = {tuple(sorted(lab.items())): v
            for _, lab, v in fams["rt_total"]["samples"]}
    assert vals[(("backend", "numpy"),)] == 5.0
    assert vals[(("backend", 'we"ird\\nm\ne'),)] == 2.0
    assert fams["rt_gauge"]["samples"][0][2] == 2.5
    hsamp = {(s, tuple(sorted(lab.items()))): v
             for s, lab, v in fams["rt_seconds"]["samples"]}
    assert hsamp[("rt_seconds_count", ())] == 2.0
    assert hsamp[("rt_seconds_sum", ())] == pytest.approx(1.1)
    assert hsamp[("rt_seconds_bucket", (("le", "0.5"),))] == 1.0
    assert hsamp[("rt_seconds_bucket", (("le", "+Inf"),))] == 2.0

    # jsonl snapshot appends strict-JSON lines
    p = tmp_path / "m.jsonl"
    export.write_jsonl(str(p), reg, meta={"run": 1})
    export.write_jsonl(str(p), reg, meta={"run": 2})
    lines = p.read_text().strip().splitlines()
    assert len(lines) == 2
    snap = json.loads(lines[1])
    assert snap["meta"]["run"] == 2
    assert any(m["name"] == "rt_total" for m in snap["metrics"])


def test_validate_prometheus_catches_violations():
    bad = ('# TYPE bad_total counter\n'
           'bad_total -1\n')
    errs = export.validate_prometheus(bad)
    assert any("negative" in e for e in errs)
    bad_hist = ('# TYPE h histogram\n'
                'h_bucket{le="1.0"} 5\n'
                'h_bucket{le="+Inf"} 3\n'
                'h_sum 1.0\n'
                'h_count 3\n')
    errs2 = export.validate_prometheus(bad_hist)
    assert errs2                     # non-cumulative buckets flagged


def test_export_cli_validator(tmp_path, default_obs):
    obs.inc("ufa_sweep_runs_total")
    prom = tmp_path / "m.prom"
    export.write_prometheus(str(prom))
    tr = obs.Tracer()
    tr.sim_instant("x", 1.0)
    trace = tmp_path / "t.json"
    tr.save(str(trace))
    assert export._main(["--validate", str(prom),
                         "--validate-trace", str(trace)]) == 0
    trace.write_text('{"traceEvents": [{"ph": "Q"}]}')
    assert export._main(["--validate-trace", str(trace)]) != 0


# ---------------------------------------------------------------------------
# profiler
# ---------------------------------------------------------------------------

def test_profiler_phase_records_and_traces(default_obs):
    from repro.obs.profiler import Profiler
    tr = obs.Tracer()
    prof = Profiler(tr)
    with prof.phase("unit-test-phase"):
        pass
    assert "unit-test-phase" in prof.phases
    assert prof.phases["unit-test-phase"] >= 0.0
    labels = {r["labels"]["phase"]
              for r in obs.default_registry().collect()
              if r["name"] == "ufa_phase_seconds"}
    assert "unit-test-phase" in labels
    assert any(e["ph"] == "X" and e["name"] == "unit-test-phase"
               for e in tr.to_chrome()["traceEvents"])


# ---------------------------------------------------------------------------
# availability_during_failover: swept rescan stays faithful
# ---------------------------------------------------------------------------

def test_availability_sweep_matches_bruteforce_window_lookup():
    from repro.core.capacity import RegionCapacity
    from repro.core.metrics import availability_during_failover
    from repro.core.omg import Orchestrator
    from repro.core.service import synthesize_fleet

    fleet = synthesize_fleet(scale=0.02, seed=1)
    orch = Orchestrator(fleet, RegionCapacity.for_fleet("r", fleet),
                        scale=0.02)
    orch.failover()
    samples = availability_during_failover(fleet, orch, n_samples=64, seed=3)
    assert len(samples) == 64
    ts = [t for t, _ in samples]
    assert ts == sorted(ts)
    assert all(0.0 <= a <= 1.0 for _, a in samples)

    # the single-pointer sweep must agree with the brute-force "last
    # window at or before t" lookup it replaced
    tl = orch.timeline
    down = tl.series.get("rl_not_bursted", [0] * len(tl.t))
    windows = list(zip(tl.t, down))
    t_end = tl.t[-1]
    j = -1
    for i in range(64):
        t = t_end * i / 63
        while j + 1 < len(windows) and windows[j + 1][0] <= t:
            j += 1
        swept = windows[j][1] if j >= 0 else 0.0
        brute = 0.0
        for wt, wd in windows:
            if wt <= t:
                brute = wd
        assert swept == brute, (i, t)


def test_monitor_orchestrator_end_to_end(default_obs):
    from repro.core.capacity import RegionCapacity
    from repro.core.omg import Orchestrator
    from repro.core.service import synthesize_fleet

    fleet = synthesize_fleet(scale=0.02, seed=1)
    orch = Orchestrator(fleet, RegionCapacity.for_fleet("r", fleet),
                        scale=0.02)
    orch.failover()
    rep = slo.monitor_orchestrator(fleet, orch, n_samples=48)
    assert rep["ts"].shape == rep["availability"].shape == (48,)
    assert rep["target"] == slo.DEFAULT_TARGET
    assert isinstance(rep["alert"], bool)
    if rep["alert"]:
        assert np.isfinite(rep["t_first_alert"])
        assert 0 <= rep["rule_first_alert"] < len(slo.DEFAULT_RULES)
