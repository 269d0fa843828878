"""Chaos-campaign engine tests: fault library + correlated sampler,
frontier bisection (property-tested against synthetic oracles), storm /
degradation model equivalences, N-region topologies, stage-seed stream
independence, and the end-to-end campaign + bit-exact re-verification
on a small fleet."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.chaos import (FAMILIES, FAULT_LIBRARY, Campaign, Ray,
                         RegionTopology, campaign_for_fleet,
                         correlation_matrix, default_rays, expand_failures,
                         reduce_pattern_verdicts, sample_faults,
                         severity_grid, verify_report)
from repro.chaos.faults import ray_severities
from repro.core.scenarios import stage_seed
from repro.core.timeline_sim import default_ts

TS = default_ts(7200.0, 240)


# ---------------------------------------------------------------------------
# fault library
# ---------------------------------------------------------------------------

def test_family_value_severity_roundtrip():
    for fam in FAULT_LIBRARY.values():
        s = np.linspace(0.0, 1.0, 9)
        np.testing.assert_allclose(fam.severity(fam.value(s)), s, atol=1e-12)
        # severity 0 is the operating point, severity 1 the worst case
        assert fam.value(0.0) == fam.base
        assert fam.value(1.0) == fam.worst


def test_severity_grid_emits_every_knob():
    sev = np.zeros((3, len(FAMILIES)))
    sev[1, 0] = 0.5
    grid = severity_grid(sev)
    assert len(grid) == len(FAMILIES)         # constant grid signature
    for name in FAMILIES:
        fam = FAULT_LIBRARY[name]
        assert fam.knob in grid
        assert grid[fam.knob][0] == fam.base  # zero severity -> base knob
    fam0 = FAULT_LIBRARY[FAMILIES[0]]
    assert grid[fam0.knob][1] == pytest.approx(fam0.value(0.5))


def test_ray_validation():
    with pytest.raises(ValueError):
        Ray("empty", {})
    with pytest.raises(KeyError):
        Ray("bad", {"not_a_family": 1.0})
    with pytest.raises(ValueError):
        Ray("bad", {"traffic_spike": 1.5})
    with pytest.raises(KeyError):
        ray_severities({"nope": 1.0}, [0.5])


# ---------------------------------------------------------------------------
# correlated sampler (property tests)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module", autouse=True)
def _compiled_sampler():
    """Compile the jitted copula once per draw shape the property tests
    below use, so that compilation is not timed as one of hypothesis's
    examples against its deadline."""
    sample_faults(0, 1024, max_severity=0.8)
    sample_faults(0, 2048)


@given(seed=st.integers(min_value=0, max_value=2**20))
@settings(max_examples=5)
def test_sampler_marginals_and_reproducibility(seed):
    """Marginals are Uniform(0, max_sev) and one campaign seed
    reproduces the draw exactly."""
    out = sample_faults(seed, 1024, max_severity=0.8)
    sev = out["severity"]
    assert sev.shape == (1024, len(FAMILIES))
    assert (sev >= 0.0).all() and (sev <= 0.8).all()
    # Uniform(0, 0.8): mean 0.4, sd 0.8/sqrt(12) ~ 0.23 -> sem ~ 0.0072
    np.testing.assert_allclose(sev.mean(axis=0), 0.4, atol=0.05)
    again = sample_faults(seed, 1024, max_severity=0.8)
    assert np.array_equal(sev, again["severity"])


@given(seed=st.integers(min_value=0, max_value=2**20))
@settings(max_examples=5)
def test_sampler_correlation_sign(seed):
    """Requested positive correlations show up with the right sign (and
    roughly the right magnitude) in the realized draws; unrequested
    pairs stay near zero."""
    out = sample_faults(seed, 2048)
    sev = out["severity"]
    idx = {name: j for j, name in enumerate(out["families"])}
    c = np.corrcoef(sev.T)
    r = c[idx["evict_shortfall"], idx["traffic_spike"]]
    assert 0.4 < r < 0.8, r                 # requested 0.6 (copula ~0.59)
    r2 = c[idx["traffic_spike"], idx["quota_shortfall"]]
    assert 0.3 < r2 < 0.7, r2               # requested 0.5
    r0 = c[idx["preheat_stall"], idx["burst_shortfall"]]
    assert abs(r0) < 0.15, r0               # independent pair


def test_sampler_seed_stream_independent_of_engine_stages():
    """The fault sampler and the engine's blackhole/storm stages derive
    DIFFERENT streams from the same campaign seed."""
    stages = ["faults", "sweep-engine", "blackhole-ensemble", "storm"]
    seeds = [stage_seed(12345, s) for s in stages]
    assert len(set(seeds)) == len(seeds)


def test_correlation_matrix_rejects_invalid():
    with pytest.raises(np.linalg.LinAlgError):
        correlation_matrix(pairs={("evict_shortfall", "traffic_spike"): 1.2})


# ---------------------------------------------------------------------------
# stage_seed regression (satellite: seed reuse across pipeline stages)
# ---------------------------------------------------------------------------

def test_stage_seed_deterministic_and_distinct():
    assert stage_seed(3, "sweep-engine") == stage_seed(3, "sweep-engine")
    assert stage_seed(3, "sweep-engine") != stage_seed(3,
                                                       "blackhole-ensemble")
    assert stage_seed(3, "sweep-engine") != stage_seed(4, "sweep-engine")


def test_dependency_ensemble_stages_draw_different_blackholes():
    """Regression: ``sweep_with_dependency_ensemble`` used to feed the
    SAME integer seed to both ``blackhole_ensemble`` and ``SweepEngine``
    — identical uniform draws in two supposedly independent stages.  The
    derived per-stage streams must produce different dark sets for the
    same campaign seed."""
    from repro.core.service import synthesize_fleet
    from repro.graph import CallGraph
    from repro.graph.propagation import shared_blackhole_draws

    fs = synthesize_fleet(scale=0.02, seed=7, as_arrays=True)
    graph = CallGraph.from_fleet_state(fs)
    fr = np.asarray([0.6, 0.6, 0.6, 0.6])
    dark_a, _ = shared_blackhole_draws(
        graph, fr, seed=stage_seed(0, "sweep-engine"))
    dark_b, _ = shared_blackhole_draws(
        graph, fr, seed=stage_seed(0, "blackhole-ensemble"))
    assert dark_a.shape == dark_b.shape
    assert not np.array_equal(np.asarray(dark_a), np.asarray(dark_b))


# ---------------------------------------------------------------------------
# frontier bisection against synthetic oracles (property tests)
# ---------------------------------------------------------------------------

def _threshold_oracle(thresholds):
    """Monotone synthetic oracle: a row fails iff its (single active)
    family severity reaches that family's threshold."""

    def oracle(grid):
        n = len(next(iter(grid.values())))
        ok = np.ones(n, bool)
        for i in range(n):
            worst_name, worst_s = None, 0.0
            for name in FAMILIES:
                fam = FAULT_LIBRARY[name]
                s = float(fam.severity(grid[fam.knob][i]))
                if s > worst_s:
                    worst_name, worst_s = name, s
            if worst_name is not None and worst_s >= thresholds[worst_name]:
                ok[i] = False
        return ok, {"sla_ok": ok}

    return oracle


@given(t1=st.floats(min_value=0.05, max_value=0.95),
       t2=st.floats(min_value=0.05, max_value=0.95),
       t3=st.floats(min_value=0.05, max_value=0.95))
@settings(max_examples=10)
def test_bisection_brackets_straddle_thresholds(t1, t2, t3):
    """For a monotone oracle the localized bracket must straddle the
    true threshold, be narrower than tol, and put the frontier estimate
    within tol of the truth."""
    names = ("traffic_spike", "quota_shortfall", "dependency_storm")
    thresholds = dict.fromkeys(FAMILIES, 2.0)    # others never fail
    thresholds.update(dict(zip(names, (t1, t2, t3))))
    tol = 1.0 / 128.0
    camp = Campaign(oracle=_threshold_oracle(thresholds),
                    rays=[Ray(n, {n: 1.0}) for n in names], tol=tol, seed=1)
    rep = camp.run()
    assert rep.op_ok
    assert rep.n_localized == 3
    for name in names:
        r = rep.ray(name)
        t = thresholds[name]
        assert r.status == "localized"
        assert r.hi - r.lo <= tol
        assert r.lo < t <= r.hi + 1e-12, (name, r.lo, r.hi, t)
        assert abs(r.frontier_severity - t) <= tol
        assert r.counterexample is not None
        fam = FAULT_LIBRARY[name]
        # minimal counterexample: the knob at the lowest KNOWN-failing
        # severity
        assert r.counterexample[fam.knob] == pytest.approx(fam.value(r.hi))


@given(t1=st.floats(min_value=0.1, max_value=0.9),
       budget=st.integers(min_value=1, max_value=3))
@settings(max_examples=5)
def test_bisection_probe_log_is_monotone(t1, budget):
    """Every pass-severity observed on a ray is strictly below every
    fail-severity (monotone oracle -> monotone probe record), under any
    bandit round budget."""
    names = ("traffic_spike", "evict_shortfall", "burst_shortfall")
    thresholds = dict.fromkeys(FAMILIES, 2.0)
    thresholds.update({n: t1 for n in names})
    camp = Campaign(oracle=_threshold_oracle(thresholds),
                    rays=[Ray(n, {n: 1.0}) for n in names],
                    tol=1.0 / 64.0, round_budget=budget, seed=2)
    rep = camp.run()
    assert rep.n_localized == 3
    for name in names:
        probes = [p for p in rep.probe_log if p["ray"] == name
                  and p["severity"] > 0.0]
        passed = [p["severity"] for p in probes if p["ok"]]
        failed = [p["severity"] for p in probes if not p["ok"]]
        assert failed, name
        if passed:
            assert max(passed) < min(failed), name
    # a budget of k probes at most k rays per bisection round
    assert rep.n_rounds >= int(np.ceil((rep.n_evals - len(names) - 1)
                                       / budget))


def test_campaign_no_violation_and_degenerate():
    rays = [Ray("traffic_spike", {"traffic_spike": 1.0})]
    rep = Campaign(oracle=lambda g: (
        np.ones(len(next(iter(g.values()))), bool),
        {"sla_ok": np.ones(len(next(iter(g.values()))), bool)}),
        rays=rays, seed=0).run()
    assert rep.rays[0].status == "no_violation"
    assert rep.n_evals == 2                  # op probe + severity-1 probe
    assert rep.rays[0].counterexample is None

    rep = Campaign(oracle=lambda g: (
        np.zeros(len(next(iter(g.values()))), bool),
        {"sla_ok": np.zeros(len(next(iter(g.values()))), bool)}),
        rays=rays, seed=0).run()
    assert not rep.op_ok
    assert rep.rays[0].status == "degenerate"
    assert rep.render()                      # renders without crashing


def test_campaign_rejects_bad_config():
    with pytest.raises(ValueError):
        Campaign(oracle=None, engine=None)
    with pytest.raises(ValueError):
        Campaign(oracle=lambda g: None, tol=0.0)
    with pytest.raises(ValueError):
        Campaign(oracle=lambda g: None, rays=[])


# ---------------------------------------------------------------------------
# N-region topologies
# ---------------------------------------------------------------------------

def test_two_region_single_failure_is_paper_operating_point():
    topo = RegionTopology.uniform(2)
    grid, pid, rid = expand_failures(topo, topo.single_failures())
    np.testing.assert_allclose(grid["traffic_mult"], [2.0, 2.0])
    assert grid["region_degradation"].tolist() == [0.0, 0.0]
    assert pid.tolist() == [0, 1] and rid.tolist() == [1, 0]


def test_three_region_multipliers_and_reduction():
    topo = RegionTopology.uniform(3)
    failed = np.concatenate([topo.single_failures(),
                             [[True, True, False]]])
    degr = np.zeros(failed.shape)
    degr[3, 2] = 0.4                        # last survivor also degraded
    grid, pid, rid = expand_failures(topo, failed, degr)
    # single failure: each of 2 survivors absorbs half the shed third
    np.testing.assert_allclose(grid["traffic_mult"][:6], 1.5)
    # double failure: lone survivor takes all traffic
    np.testing.assert_allclose(grid["traffic_mult"][6:], 3.0)
    assert grid["region_degradation"][6] == pytest.approx(0.4)

    # verdict reduction: a pattern passes iff EVERY survivor passes
    res = {"sla_ok": np.array([1, 1, 1, 0, 1, 1, 1], bool),
           "availability": np.array([.999, .999, .999, .9, .999, .999, .99])}
    red = reduce_pattern_verdicts(res, pid, topo, rid, n_patterns=4)
    assert red["sla_ok"].tolist() == [True, False, True, True]
    assert red["worst_region"][1] == rid[3]
    np.testing.assert_allclose(red["availability"][1], (.9 + .999) / 2)


def test_weighted_topology_and_validation():
    topo = RegionTopology(weights=(3.0, 1.0), names=("big", "small"))
    # big region fails: the small region absorbs 3x its own traffic
    grid, _, _ = expand_failures(topo, [[True, False]])
    np.testing.assert_allclose(grid["traffic_mult"], [4.0])
    with pytest.raises(ValueError):
        expand_failures(topo, [[True, True]])     # no survivor
    with pytest.raises(ValueError):
        RegionTopology(weights=(1.0,), names=("solo",))
    with pytest.raises(ValueError):
        RegionTopology(weights=(1.0, -1.0), names=("a", "b"))


# ---------------------------------------------------------------------------
# event-loop runaway guard (satellite)
# ---------------------------------------------------------------------------

def test_event_loop_max_events_guard():
    from repro.core.events import EventLoop

    loop = EventLoop()

    def rearm():
        loop.schedule(1.0, rearm, label="storm-rearm")

    loop.schedule(0.0, rearm, label="storm-rearm")
    with pytest.raises(RuntimeError, match="max_events=50.*storm-rearm"):
        loop.run(max_events=50)
    # a bounded workload under the cap still completes normally
    loop2 = EventLoop()
    for i in range(10):
        loop2.schedule(float(i), lambda: None)
    assert loop2.run(max_events=50) == 10


# ---------------------------------------------------------------------------
# storm / degradation model against the engine (small fleet)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_fleet():
    from repro.core.service import synthesize_fleet
    fs = synthesize_fleet(scale=0.05, seed=7, as_arrays=True)
    fs.apply_ufa_target_classes()
    return fs


@pytest.fixture(scope="module")
def engine(small_fleet):
    from repro.core.capacity import RegionCapacity
    from repro.core.omg import Orchestrator
    from repro.graph import CallGraph
    graph = CallGraph.from_fleet_state(small_fleet)
    orch = Orchestrator(small_fleet,
                        RegionCapacity.for_fleet("chaos-test", small_fleet),
                        scale=1.0)
    return orch.sweep_engine(graph=graph, seed=5, ts=TS)


def test_storm_stage_matches_composed_passthrough(engine):
    """The in-pipeline cascade-storm stage (combined dark uniques, one
    fixed point) is bit-identical to composing the engine with
    host-computed dep/storm fractions (two separate fixed points)."""
    grid = {"evict_fraction": np.array([1.0, 0.8, 0.6, 1.0]),
            "storm_refrac": np.array([0.0, 0.5, 1.0, 1.0]),
            "traffic_mult": np.array([2.0, 2.0, 2.0, 2.2])}
    fused = engine.run(dict(grid))

    dep_frac, _, _ = engine.dep_fractions(grid["evict_fraction"])
    storm_frac = engine.storm_fractions(grid["storm_refrac"])
    composed = engine.run({**grid, "storm_broken_frac": storm_frac},
                          dep_broken_frac=dep_frac)
    for k in fused:
        if k.startswith("dep_n"):
            continue                      # propagation diagnostics only
        if k in composed:
            assert np.array_equal(np.asarray(fused[k]),
                                  np.asarray(composed[k]),
                                  equal_nan=np.asarray(
                                      fused[k]).dtype.kind == "f"), k
    # the storm actually propagated something at refrac 1.0
    assert fused["storm_broken_frac"][2] > 0.0
    assert not fused["storm_ok"][2]


def test_storm_degrades_timeline_and_analytic_availability(engine):
    """A cascade storm re-darkens restored capacity: temporal mean
    availability and the analytic verdict must both degrade relative to
    the storm-free scenario; zero-refrac rows are exact no-ops."""
    grid = {"evict_fraction": np.array([1.0, 1.0]),
            "storm_refrac": np.array([0.0, 1.0])}
    res = engine.run(grid)
    base = engine.run({"evict_fraction": np.array([1.0, 1.0])})
    # refrac 0 row identical to a grid that never mentions the storm
    for k in ("sla_ok", "t_sla_ok", "availability", "t_availability_mean"):
        assert np.asarray(res[k])[0] == np.asarray(base[k])[0], k
    assert res["availability"][1] < res["availability"][0]
    assert res["t_availability_mean"][1] < res["t_availability_mean"][0]
    assert not res["storm_ok"][1]
    assert not res["sla_ok"][1]


def test_region_degradation_raises_utilization(engine):
    grid = {"region_degradation": np.array([0.0, 0.5])}
    res = engine.run(grid)
    assert res["util_peak"][1] > res["util_peak"][0]
    # peak transient utilization saturates at 1.0 either way on this
    # fleet; the steady post-restore utilization shows the lost capacity
    assert res["t_util_post"][1] > res["t_util_post"][0]


# ---------------------------------------------------------------------------
# end-to-end campaign on the engine + bit-exact re-verification
# ---------------------------------------------------------------------------

def test_campaign_on_engine_reproducible_and_reverifiable(small_fleet):
    from repro.graph import CallGraph
    from repro.graph.planner import plan_hardening

    graph = CallGraph.from_fleet_state(small_fleet)
    plan = plan_hardening(graph)
    small_fleet.edges.fail_open[
        graph.input_edge_indices(plan.hardened_edges)] = True

    rays = [Ray("preheat_stall", {"preheat_stall": 1.0}),
            Ray("burst_shortfall", {"burst_shortfall": 1.0}),
            Ray("dependency_storm", {"dependency_storm": 1.0})]
    camp = campaign_for_fleet(small_fleet, seed=11, rays=rays, tol=1 / 32.0)
    rep = camp.run()
    assert rep.op_ok, "hardened small fleet must pass its operating point"
    assert rep.n_localized >= 2
    assert rep.n_evals < rep.grid_equiv_evals / 3

    # single-seed reproducibility: a fresh campaign is byte-identical
    rep2 = campaign_for_fleet(small_fleet, seed=11, rays=rays,
                              tol=1 / 32.0).run()
    assert rep.to_json(sort_keys=True) == rep2.to_json(sort_keys=True)

    # bit-exact replay of every probe on a fresh engine, in one batch
    fresh = campaign_for_fleet(small_fleet, seed=11, rays=rays,
                               tol=1 / 32.0)
    out = verify_report(rep, fresh.engine)
    assert out["n_probes"] == rep.n_evals
    assert not out["mismatches"]

    # and verify_report actually detects drift
    tampered = rep.probe_log[-1]["verdict"]
    key = "availability" if "availability" in tampered else "sla_ok"
    orig = tampered[key]
    tampered[key] = (not orig) if isinstance(orig, bool) else orig + 0.5
    with pytest.raises(AssertionError):
        verify_report(rep, fresh.engine)
    tampered[key] = orig


def test_campaign_default_rays_beat_grid_tenfold(small_fleet):
    """On the hardened fleet at 1/64 severity resolution the default rays
    localize at least three frontiers with at least ten times fewer
    engine evaluations than a per-ray grid; the metered evaluation count
    is the report's, and every probe replays on a fresh engine."""
    from repro import obs
    from repro.graph import CallGraph
    from repro.graph.planner import plan_hardening

    graph = CallGraph.from_fleet_state(small_fleet)
    plan = plan_hardening(graph)
    small_fleet.edges.fail_open[
        graph.input_edge_indices(plan.hardened_edges)] = True

    tol = 1.0 / 64.0
    obs.enable().reset()
    try:
        rep = campaign_for_fleet(small_fleet, seed=7, tol=tol).run()
        evals_metered = obs.value("ufa_chaos_evals_total")
    finally:
        obs.disable()
    assert evals_metered == rep.n_evals
    assert rep.op_ok
    assert rep.n_localized >= 3, [(r.name, r.status) for r in rep.rays]
    assert rep.speedup_vs_grid is not None and rep.speedup_vs_grid >= 10.0

    fresh = campaign_for_fleet(small_fleet, seed=7, tol=tol)
    out = verify_report(rep, fresh.engine)
    assert out["n_probes"] == rep.n_evals and not out["mismatches"]
