"""Program spans on the profiler's clock, and the named device scopes of
the fused sweep.

``obs.span`` writes a ``jax.profiler.TraceAnnotation``: under an active
``jax.profiler`` trace the span lands in the same ``.xplane.pb`` as the
device's operations, with its counts as the event's stats.  These tests
take a CPU trace around the three entry points the benchmark drives and
read the spans back; the device side is the lowered program's metadata.
"""

import glob
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro import obs
from repro.core.dependency import runtime_analysis
from repro.core.scenarios import FleetAggregates, scenario_grid
from repro.core.service import synthesize_fleet
from repro.core.sweep_engine import SweepEngine, _run_chunks_dep, tile_grid
from repro.core.timeline_sim import config_for_fleet, default_ts
from repro.graph import CallGraph, plan_hardening

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TS = default_ts(7200.0, 48)
SCOPES = ("ufa_dependency", "ufa_analytic", "ufa_timeline")


@pytest.fixture(scope="module")
def fleet():
    fs = synthesize_fleet(scale=0.02, seed=7, as_arrays=True)
    fs.apply_ufa_target_classes()
    return fs


@pytest.fixture(scope="module")
def engine(fleet):
    graph = CallGraph.from_fleet_state(fleet)
    return SweepEngine(FleetAggregates.from_fleet_state(fleet),
                       config_for_fleet(fleet), graph=graph, ts=TS, chunk=256,
                       devices=1)


def _grid():
    return tile_grid(scenario_grid(evict_fraction=(1.0, 0.5)), 600)


def _spans(log_dir):
    """Host events of the trace whose names start ``ufa.``, with their
    thread, start and end (ns) and stats."""
    path = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("ufa."):
                    out.append((e.name, line.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory, fleet, engine):
    """One CPU trace around a sweep, a detection pass and a hardening
    plan; returns the ``ufa.*`` spans and the sweep's output."""
    legacy = synthesize_fleet(scale=0.02, seed=7, as_arrays=True)
    graph = CallGraph.from_fleet_state(legacy)
    # compile outside the trace
    engine.run(_grid())
    runtime_analysis(legacy, n_records=30_000, seed=1, chunk_records=8192)
    plan_hardening(graph, batch=4)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(log_dir)
    try:
        with jax.profiler.TraceAnnotation("test.outer"):
            out = engine.run(_grid())
            runtime_analysis(legacy, n_records=30_000, seed=1,
                             chunk_records=8192)
            plan = plan_hardening(graph, batch=4)
    finally:
        jax.profiler.stop_trace()
    return _spans(log_dir), out, plan


def _one(spans, name):
    found = [s for s in spans if s[0] == name]
    assert len(found) == 1, (name, len(found))
    return found[0]


def _inside(child, parent):
    assert child[1] == parent[1], (child[0], parent[0])    # same thread
    assert parent[2] <= child[2] and child[3] <= parent[3], (child[0],
                                                             parent[0])


def test_sweep_spans_nest_with_counts(traced):
    spans, out, _ = traced
    run = _one(spans, "ufa.sweep.run")
    assert run[4] == {"scenarios": 600, "padded": 1024, "chunks": 4,
                      "sharded": 0}
    stages = [_one(spans, f"ufa.sweep.{k}")
              for k in ("prepare", "dispatch", "fetch")]
    for child in stages:
        _inside(child, run)
    # in program order, one after the other
    assert stages[0][3] <= stages[1][2] and stages[1][3] <= stages[2][2]
    assert stages[2][4]["columns"] == len(out) - len(_grid()) == 39
    # the result comes back packed, one device array per dtype
    assert stages[2][4]["transfers"] <= 3


def test_detect_spans_nest_with_counts(traced):
    spans = traced[0]
    run = _one(spans, "ufa.detect.run")
    for name in ("tables", "mask", "verdicts"):
        _inside(_one(spans, f"ufa.detect.{name}"), run)
    samples = [s for s in spans if s[0] == "ufa.detect.sample"]
    ingests = [s for s in spans if s[0] == "ufa.detect.ingest"]
    # 30,000 records in chunks of 8,192: four of each
    assert [s[4]["records"] for s in samples] == [8192] * 3 + [5424]
    assert [s[4]["records"] for s in ingests] == [8192] * 3 + [5424]
    for s in samples + ingests:
        _inside(s, run)
    # the next chunk is drawn before the last one is folded in
    assert samples[1][3] <= ingests[0][2]
    assert _one(spans, "ufa.detect.tables")[4]["edges"] > 0


def test_planner_spans_nest_with_counts(traced):
    spans, _, plan = traced
    top = _one(spans, "ufa.planner.plan")
    assert top[4] == {"batch": 4}
    rounds = [s for s in spans if s[0] == "ufa.planner.round"]
    # every hardening round, and the round that certifies
    assert plan.certified and plan.rounds >= 1
    assert len(rounds) == plan.rounds + 1
    for r in rounds:
        _inside(r, top)
    assert [r[4]["broken_critical"] for r in rounds] == [
        t["n_broken_critical"] for t in plan.trajectory]
    assert all(r[4]["picked"] <= 4 and r[4]["frontier"] >= r[4]["picked"]
               for r in rounds[:-1])
    assert "picked" not in rounds[-1][4]
    # only indices and counts cross: no (width, n) dark matrix, and no
    # (n,) broken vector
    n = plan.graph.n
    for r in rounds[:-1]:
        assert r[4]["width"] > 0 and r[4]["width"] % 128 == 0
        assert 0 < r[4]["moved_bytes"] < r[4]["width"] * n
    assert rounds[-1][4]["width"] == 0
    assert 0 < rounds[-1][4]["moved_bytes"] < n


def test_graph_spans_count_the_layout(tmp_path, monkeypatch):
    """Certification and the ensemble carry what a propagation round walks
    (edges, slot loads, split rows) and the rounds of their fixed point, on
    a trace-shaped fleet whose hubs split rows of the kernel's layout."""
    from repro.core.fleet_shapes import alibaba_fleet_state
    from repro.graph import blackhole_ensemble, certify
    from repro.graph.propagation import layout_counts
    monkeypatch.setenv("REPRO_UFA_KERNELS", "1")
    graph = CallGraph.from_fleet_state(alibaba_fleet_state(scale=0.05,
                                                           seed=7))
    certify(graph)                      # compile outside the trace
    blackhole_ensemble(graph, n_scenarios=16, seed=1)
    jax.profiler.start_trace(str(tmp_path))
    try:
        cert = certify(graph)
        ens = blackhole_ensemble(graph, n_scenarios=16, seed=1)
    finally:
        jax.profiler.stop_trace()
    spans = _spans(str(tmp_path))
    counts = layout_counts(graph)
    assert counts["edges"] == graph.n_edges and counts["split_rows"] > 0
    assert counts["slots"] > counts["edges"]
    assert _one(spans, "ufa.graph.certify")[4] == dict(
        counts, services=graph.n, rounds=cert.rounds)
    assert _one(spans, "ufa.graph.ensemble")[4] == dict(
        counts, scenarios=16, rounds=int(ens["rounds"]))


def test_sweep_output_identical_under_trace(traced, engine):
    _, traced_out, _ = traced
    plain = engine.run(_grid())
    assert set(plain) == set(traced_out)
    for k, v in plain.items():
        assert v.dtype == traced_out[k].dtype, k
        assert np.array_equal(v, traced_out[k], equal_nan=True), k


def test_fused_program_names_its_stages(engine):
    fn, args, kw = engine._pipeline(_grid())
    assert fn is _run_chunks_dep
    text = fn.lower(*args, **kw).as_text(debug_info=True)
    for scope in SCOPES:
        assert scope in text, scope


def test_span_records_on_attached_tracer():
    tr = obs.Tracer()
    prev = obs.set_tracer(tr)
    try:
        with obs.span("ufa.unit", rows=3) as sp:
            sp.set(picked=1)
    finally:
        obs.set_tracer(prev)
    (ev,) = [e for e in tr.to_chrome()["traceEvents"]
             if e["name"] == "ufa.unit"]
    assert ev["ph"] == "X" and ev["args"] == {"rows": 3, "picked": 1}


def test_importing_obs_imports_no_jax():
    code = ("import sys; import repro.obs; from repro import obs; "
            "\nwith obs.span('ufa.x', n=1): pass"
            "\nassert 'jax' not in sys.modules, 'jax imported'")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)
