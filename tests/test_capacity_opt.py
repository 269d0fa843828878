"""Capacity-optimizer suite: gradient correctness (finite differences
through the soft-relaxed fused pipeline), soft->hard verdict agreement at
low temperature, the optimizer itself (grad + CEM improve on the legacy
start and hard-verify), and the sweep-input-validation / failure-mode
bugfix regressions that rode along (unknown grid keys, empty grids, the
``recommend_factor`` safe flag + exact grid endpoint, the hardening
planner's vanished-under-``-O`` stall assert)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.fleet_state import FleetState
from repro.core.scenarios import (FleetAggregates, scenario_grid,
                                  scenario_outcome, sweep_scenarios)
from repro.core.service import apply_ufa_target_classes, synthesize_fleet
from repro.core.sweep_engine import SweepEngine
from repro.core.timeline_sim import (config_for_fleet, default_ts,
                                     sweep_timeline, validate_grid)
from repro.optim.capacity import (DesignBase, _design_params, _grid_cols,
                                  certification_grid, design_consts,
                                  eviction_deltas, hardening_weights,
                                  knob_design, legacy_knobs, make_knobs,
                                  optimize_capacity, provisioning,
                                  soft_loss, ufa_knobs, verify_design)

SCALE, SEED = 0.02, 7


@pytest.fixture(scope="module")
def fs():
    fleet = synthesize_fleet(scale=SCALE, seed=SEED)
    apply_ufa_target_classes(fleet)
    return FleetState.from_specs(fleet)


@pytest.fixture(scope="module")
def engine(fs):
    agg = FleetAggregates.from_fleet_state(fs)
    return SweepEngine(agg, config_for_fleet(fs), reducer="scan")


@pytest.fixture(scope="module")
def base(fs):
    return DesignBase.from_fleet_state(fs).as_arrays()


# ---------------------------------------------------------------------------
# gradient correctness: jax.grad vs central finite differences
# ---------------------------------------------------------------------------


def _fd(f, knobs, key, idx, eps):
    def bump(s):
        k2 = dict(knobs)
        k2[key] = (knobs[key] + s if idx is None
                   else knobs[key].at[idx].add(s))
        return k2
    return (f(bump(eps)) - f(bump(-eps))) / (2.0 * eps)


def test_grad_matches_finite_differences(base):
    """jax.grad through the FULL soft pipeline (analytic + timeline scan)
    matches central differences on the smooth knobs: the buffer fraction
    and all three tier-mix promotion flows (4 knobs >= the required 3)."""
    cols = _grid_cols(certification_grid())
    ts = jnp.asarray(default_ts(), jnp.float32)
    tau = jnp.asarray(1.0, jnp.float32)
    pen = jnp.asarray(200.0, jnp.float32)
    knobs = make_knobs(buffer=0.6, promote=(0.4, 0.3, 0.2), overcommit=1.4,
                       ramp=0.9, evict_lambda=0.2)
    g = jax.grad(soft_loss)(knobs, base, cols, ts, tau, pen)
    f = lambda k: float(soft_loss(k, base, cols, ts, tau, pen))
    for key, idx in (("buffer", None), ("promote", 0), ("promote", 1),
                     ("promote", 2)):
        a = float(g[key]) if idx is None else float(g[key][idx])
        n = _fd(f, knobs, key, idx, eps=0.05)
        assert abs(a - n) <= 0.08 * max(abs(n), abs(a), 1e-3), \
            (key, idx, a, n)


def test_grad_ramp_matches_fd_analytic(base):
    """The burst-ramp knob checked on the analytic (closed-form) stage,
    where the path is smooth — the timeline stage quantizes wave counts
    through ceil(), which finite differences see as steps and autodiff
    correctly treats as flat."""
    cols = _grid_cols(certification_grid())
    tau = jnp.asarray(1.0, jnp.float32)

    def loss(knobs):
        design = knob_design(base, knobs)
        consts = design_consts(design)
        params = _design_params(design, cols)
        out = jax.vmap(lambda q: scenario_outcome(consts["a"], q, tau)
                       )(params)
        return (100.0 * (1.0 - jnp.mean(out["sla_ok"]))
                + 10.0 * (1.0 - jnp.mean(out["rl_ok"])))

    knobs = make_knobs(buffer=0.1, promote=(0.05, 0.05, 0.05),
                       overcommit=1.2, ramp=0.7, evict_lambda=0.3)
    a = float(jax.grad(loss)(knobs)["ramp"])
    n = _fd(lambda k: float(loss(k)), knobs, "ramp", None, eps=0.05)
    assert abs(a) > 1e-4                      # the knob has real signal
    assert abs(a - n) <= 0.08 * max(abs(n), abs(a)), (a, n)


# ---------------------------------------------------------------------------
# soft -> hard agreement
# ---------------------------------------------------------------------------


def test_low_tau_soft_reproduces_hard_verdicts(engine):
    """At tau -> 0 every sigmoid indicator saturates: thresholding the
    soft verdicts at 0.5 must reproduce the bit-exact hard verdicts on
    the full default grid (256 scenarios, brutal corners included)."""
    hard = engine.run()
    soft = engine.run(soft_tau=1e-3)
    for k in hard:
        if hard[k].dtype == bool:
            assert ((soft[k] >= 0.5) == hard[k]).all(), k


def test_soft_runs_leave_hard_path_bit_identical(engine):
    """Interleaving soft runs must not perturb the hard program: the
    hard pipeline and the soft pipeline are separate jit cache entries
    (tau=None vs a traced scalar have different pytree structures)."""
    before = engine.run()
    engine.run(soft_tau=0.5)
    after = engine.run()
    for k in before:
        np.testing.assert_array_equal(before[k], after[k], err_msg=k)


def test_zero_eviction_deltas_are_bitwise_noop(engine):
    """Explicit rl/tm_evict_delta = 0 columns trace the same program
    state as an un-extended grid — additive delta forms are exact."""
    grid = scenario_grid()
    n = len(next(iter(grid.values())))
    plain = engine.run(grid)
    padded = engine.run(dict(grid, rl_evict_delta=np.zeros(n),
                             tm_evict_delta=np.zeros(n)))
    for k in plain:
        np.testing.assert_array_equal(plain[k], padded[k], err_msg=k)


def test_eviction_deltas_conserve_budget(base):
    """The order knob only re-mixes eviction across classes: for any
    lambda and depth, rl*d_rl + tm*d_tm == 0 and both per-class evicted
    fractions stay in [0, 1]."""
    e = jnp.asarray([0.3, 0.7, 1.0], jnp.float32)
    for lam in (-1.0, -0.4, 0.0, 0.5, 1.0):
        design = {"rl": jnp.asarray(1500.0), "tm": jnp.asarray(400.0),
                  "evict_lambda": jnp.asarray(lam)}
        d_rl, d_tm = eviction_deltas(design, e)
        budget = 1500.0 * np.asarray(d_rl) + 400.0 * np.asarray(d_tm)
        np.testing.assert_allclose(budget, 0.0, atol=1e-3)
        assert ((np.asarray(e) + np.asarray(d_rl) >= -1e-6).all()
                and (np.asarray(e) + np.asarray(d_rl) <= 1 + 1e-6).all())
        assert ((np.asarray(e) + np.asarray(d_tm) >= -1e-6).all()
                and (np.asarray(e) + np.asarray(d_tm) <= 1 + 1e-6).all())


# ---------------------------------------------------------------------------
# the optimizer
# ---------------------------------------------------------------------------


def test_optimizer_grad_improves_and_verifies(fs):
    res = optimize_capacity(fs, mode="grad", grad_steps=25,
                            taus=(1.0, 0.1, 0.03))
    assert res.improved
    assert res.provisioning_multiple <= 1.4
    v = res.verification
    assert v["all_ok"], v
    assert v["n_t_avail_ok"] == v["n_scenarios"]


def test_optimizer_cem_improves_and_verifies(fs):
    res = optimize_capacity(fs, mode="cem", cem_generations=5,
                            cem_population=24, seed=3)
    assert res.improved
    v = res.verification
    assert v["n_sla_ok"] == v["n_scenarios"], v
    assert v["n_t_sla_ok"] == v["n_scenarios"], v


def test_optimizer_both_modes_hold_availability_floor(fs):
    """Grad anneal then CEM polish: the certified optimum keeps every
    scenario's analytic availability at the 99.97 % floor."""
    res = optimize_capacity(fs, mode="both")
    assert res.improved
    assert res.provisioning_multiple <= 1.4
    v = res.verification
    assert v["all_ok"], v
    assert v["availability_min"] >= 0.9997 - 1e-9, v["availability_min"]


def test_hand_tuned_ufa_design_verifies(fs):
    """The paper's hand-tuned operating point passes the certification
    ensemble through the real hard engine — the optimizer's constraint
    set is anchored to a known-feasible design."""
    base = DesignBase.from_fleet_state(fs).as_arrays()
    design = knob_design(base, ufa_knobs())
    assert provisioning(design) < 1.1
    assert verify_design(design)["all_ok"]
    assert provisioning(knob_design(base, legacy_knobs())) > 1.8


def test_hardening_weights_feed_planner(fs):
    from repro.graph import CallGraph, plan_hardening
    fsa = synthesize_fleet(scale=SCALE, seed=SEED, as_arrays=True)
    fsa.apply_ufa_target_classes()
    graph = CallGraph.from_fleet_state(fsa)
    w = hardening_weights(fsa, graph)
    assert w.shape == (fsa.n,) and (w >= 0).all()
    crit = np.asarray(graph.critical, bool)
    np.testing.assert_allclose(w[crit].mean(), 1.0, rtol=1e-3)
    plan = plan_hardening(graph, service_weights=w)
    assert plan.certified


# ---------------------------------------------------------------------------
# sweep-input validation (unknown keys, empty grids)
# ---------------------------------------------------------------------------


def test_unknown_grid_key_raises(engine, fs):
    """A misspelled axis used to be silently dropped — every real axis
    fell back to its default and the sweep returned verdicts for the
    wrong ensemble."""
    bad = {"trafic_mult": np.asarray([1.8, 2.0])}        # sic
    with pytest.raises(ValueError, match="trafic_mult"):
        engine.run(bad)
    agg = FleetAggregates.from_fleet_state(fs)
    with pytest.raises(ValueError, match="unknown scenario grid key"):
        sweep_scenarios(agg, bad)
    with pytest.raises(ValueError, match="trafic_mult"):
        sweep_timeline(config_for_fleet(fs), bad)


def test_empty_grid_raises(engine):
    with pytest.raises(ValueError, match="empty scenario grid"):
        engine.run({})
    with pytest.raises(ValueError, match="empty scenario grid"):
        engine.run({"traffic_mult": np.asarray([])})
    with pytest.raises(ValueError, match="ragged"):
        validate_grid({"traffic_mult": np.ones(3),
                       "evict_fraction": np.ones(2)})


# ---------------------------------------------------------------------------
# recommend_factor: explicit safe flag + exact grid endpoint
# ---------------------------------------------------------------------------


def test_recommend_factor_reports_unsafe():
    """When NO factor clears the violation budget the old code returned
    grid_lo with nothing marking it unsafe — callers acted on a factor
    that failed its own acceptance test."""
    from repro.core.overcommit_sim import OvercommitSimConfig, \
        recommend_factor
    cfg = OvercommitSimConfig(n_hosts=64, n_trials=16, critical_fill=0.95,
                              critical_demand_mean=0.95,
                              preempt_demand_mean=0.95,
                              max_violation_rate=0.0)
    rec = recommend_factor(cfg, grid_lo=1.2, grid_hi=1.6, grid_step=0.1)
    assert rec["safe"] is False
    assert rec["recommended"] == 1.2          # fallback, flagged unsafe
    ok = recommend_factor(OvercommitSimConfig(n_hosts=64, n_trials=16))
    assert ok["safe"] is True


def test_factor_grid_exact_endpoint():
    """np.arange(lo, hi + 1e-9, step) drifts and can drop the endpoint;
    the linspace grid keeps every factor and the endpoint exact."""
    from repro.core.overcommit_sim import factor_grid
    for lo, hi, step in ((1.0, 2.0, 0.05), (1.0, 1.3, 0.1),
                         (1.1, 1.66, 0.07), (1.0, 1.65, 0.05)):
        g = factor_grid(lo, hi, step)
        assert g[0] == lo and g[-1] == hi, (lo, hi, step, g)
        np.testing.assert_allclose(np.diff(g), step, atol=1e-9)


# ---------------------------------------------------------------------------
# planner stall: labeled error instead of a bare assert
# ---------------------------------------------------------------------------


def test_plan_hardening_stall_raises(monkeypatch):
    """Broken criticals with no fail-close frontier (propagation verdicts
    inconsistent with the edge mask) used to trip a bare ``assert`` that
    vanishes under ``python -O``, leaving the loop spinning to
    max_rounds — it must raise a labeled RuntimeError."""
    from repro.graph import planner
    from repro.graph.callgraph import _build_csr
    src = np.array([0], np.int32)
    dst = np.array([1], np.int32)
    g = _build_csr(2, src, dst, np.array([True]),      # edge is fail-OPEN
                   np.ones(1, np.float32),
                   np.array([True, False]),            # 0 critical, live
                   np.array([False, True]),            # 1 preemptible
                   ["crit", "pre"])
    monkeypatch.setattr(
        planner, "fixed_point",
        lambda dark, consts: (jnp.ones_like(dark), jnp.asarray(0)))
    with pytest.raises(RuntimeError, match="no fail-close"):
        planner.plan_hardening(g)
