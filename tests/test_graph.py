"""Dependency-graph engine: scalar-BFS equivalence (exact), blast radius,
blackhole ensembles, the hardening planner, the regression gate, and the
drills/scenarios integration."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.drills import certify_fleet_state
from repro.core.fleet_state import synthesize_fleet_state
from repro.core.scenarios import (scenario_grid, summarize_sweep,
                                  sweep_with_dependency_ensemble)
from repro.core.service import synthesize_fleet, unsafe_edges
from repro.graph import (CallGraph, blackhole_ensemble, blast_radius,
                         certify, plan_hardening, propagate, propagate_many,
                         regression_gate)
from repro.graph import planner, propagation
from repro.graph.callgraph import _build_csr
from repro.graph.propagation import edge_consts, fixed_point


# ---------------------------------------------------------------------------
# scalar reference: worklist BFS over reversed fail-close edges
# ---------------------------------------------------------------------------


def bfs_propagate(n, edges, dark):
    """Reference fixed point: edges = [(caller, callee, fail_open)], dark =
    iterable of dark nodes.  Failure flows callee -> caller along
    fail-close edges only."""
    callers_of = {}                      # callee -> [callers via fail-close]
    for u, v, fo in edges:
        if not fo:
            callers_of.setdefault(v, []).append(u)
    broken = set(dark)
    frontier = list(broken)
    while frontier:
        v = frontier.pop()
        for u in callers_of.get(v, ()):
            if u not in broken:
                broken.add(u)
                frontier.append(u)
    return broken


def random_graph(rng, n=None, p_edge=0.15, p_close=0.5):
    """Random digraph with cycles, self-loop-free, mixed fail-open/close
    boundaries, random critical/preemptible masks."""
    n = n if n is not None else rng.integers(4, 60)
    m = rng.random((n, n)) < p_edge
    np.fill_diagonal(m, False)
    src, dst = np.nonzero(m)
    fail_open = rng.random(len(src)) >= p_close
    critical = rng.random(n) < 0.4
    preemptible = ~critical & (rng.random(n) < 0.7)
    g = _build_csr(n, src.astype(np.int32), dst.astype(np.int32),
                   fail_open, np.ones(len(src), np.float32),
                   critical, preemptible, [f"svc-{i}" for i in range(n)])
    edges = list(zip(src.tolist(), dst.tolist(), fail_open.tolist()))
    return g, edges


def test_kernel_matches_bfs_randomized():
    """Property-style: random graphs (cycles included) x random preemption
    sets — the CSR fixed-point kernel must match the BFS reference
    EXACTLY, node for node."""
    for seed in range(20):
        rng = np.random.default_rng(seed)
        g, edges = random_graph(rng)
        for _ in range(4):
            dark = rng.random(g.n) < rng.uniform(0.0, 0.6)
            want = bfs_propagate(g.n, edges, np.flatnonzero(dark))
            got = propagate(g, dark)
            assert set(np.flatnonzero(got)) == want, (seed, dark)


def test_kernel_matches_bfs_batched():
    rng = np.random.default_rng(42)
    g, edges = random_graph(rng, n=40)
    dark = rng.random((16, g.n)) < 0.3
    broken, rounds = propagate_many(g, dark)
    assert rounds >= 1
    for s in range(16):
        want = bfs_propagate(g.n, edges, np.flatnonzero(dark[s]))
        assert set(np.flatnonzero(broken[s])) == want, s


def test_cycle_and_fail_open_boundary():
    # a -> b -> c -> a all fail-close (a cycle), c -> d fail-CLOSE,
    # b -> e fail-OPEN; darkening d must break the whole cycle but spare e's
    # side of the boundary
    names = list("abcde")
    src = np.array([0, 1, 2, 2, 1], np.int32)
    dst = np.array([1, 2, 0, 3, 4], np.int32)
    fo = np.array([False, False, False, False, True])
    g = _build_csr(5, src, dst, fo, np.ones(5, np.float32),
                   np.array([True, True, True, False, False]),
                   np.array([False, False, False, True, True]), names)
    broken = propagate(g, np.array([False, False, False, True, False]))
    assert broken.tolist() == [True, True, True, True, False]
    # darkening e (fail-open caller side) breaks nothing else
    broken2 = propagate(g, np.array([False, False, False, False, True]))
    assert broken2.tolist() == [False, False, False, False, True]


def test_fleet_certification_matches_bfs():
    """The real synthesized fleet (with relay chains): multi-hop certify
    equals the BFS reference on the full preemption blackhole."""
    fs = synthesize_fleet_state(scale=0.05, seed=11,
                                unsafe_chain_fraction=0.06)
    g = CallGraph.from_fleet_state(fs)
    edges = list(zip(g.src.tolist(), g.dst.tolist(),
                     g.fail_open.tolist()))
    want = bfs_propagate(g.n, edges, np.flatnonzero(g.preemptible))
    cert = certify(g)
    assert set(np.flatnonzero(cert.broken)) == want
    assert cert.n_broken_critical > 0
    # chains present: some criticals broke with no direct unsafe cause
    assert cert.multi_hop.sum() > 0


def test_blast_radius_matches_bfs():
    for seed in (1, 5, 9):
        rng = np.random.default_rng(seed)
        g, edges = random_graph(rng, n=35)
        sources = np.arange(g.n)
        radius = blast_radius(g, sources=sources)
        for j in sources:
            want = bfs_propagate(g.n, edges, [j])
            assert radius[j] == sum(g.critical[u] for u in want), (seed, j)


def test_blackhole_ensemble_nested_monotone():
    """Shared uniform draws + sorted fractions -> nested dark sets -> the
    broken counts must be monotone in the blackhole fraction."""
    fs = synthesize_fleet_state(scale=0.05, seed=3,
                                unsafe_chain_fraction=0.05)
    g = CallGraph.from_fleet_state(fs)
    fr = np.linspace(0.0, 1.0, 64)
    ens = blackhole_ensemble(g, seed=0, fractions=fr)
    assert (np.diff(ens["n_dark"]) >= 0).all()
    assert (np.diff(ens["n_broken_critical"]) >= 0).all()
    assert ens["n_broken_critical"][0] == 0        # empty blackhole
    assert not ens["ok"][-1]                       # full blackhole breaks
    assert len(ens["ok"]) == 64


def test_planner_hardens_until_certified():
    fs = synthesize_fleet_state(scale=0.05, seed=7,
                                unsafe_chain_fraction=0.06)
    g = CallGraph.from_fleet_state(fs)
    assert not certify(g).ok
    plan = plan_hardening(g, batch=16)
    assert plan.certified
    assert certify(plan.graph).ok
    assert 0 < plan.n_hardened <= g.n_unsafe
    # trajectory: broken criticals decrease monotonically to zero
    broken = [t["n_broken_critical"] for t in plan.trajectory]
    assert broken[-1] == 0
    assert all(b1 >= b2 for b1, b2 in zip(broken, broken[1:]))
    # relay chains mean certification needs fewer conversions than there
    # are unsafe edges (chains die once their entry edges are hardened)
    assert plan.n_hardened < g.n_unsafe


# ---------------------------------------------------------------------------
# reference planner: the greedy loop with every round on the host
# ---------------------------------------------------------------------------


@jax.jit
def _ref_radius(dark, consts, crit):
    broken, _ = fixed_point(dark, consts)
    return (broken & crit[None, :]).sum(axis=1).astype(jnp.int32)


@jax.jit
def _ref_weighted_radius(dark, consts, weights):
    broken, _ = fixed_point(dark, consts)
    return (broken * weights[None, :]).sum(axis=1).astype(jnp.float32)


def _ref_radius_counts(sources, n, consts, crit_d, weights_d):
    """Blast radius of each source from host-built (width, n) one-hot
    batches, padded as the planner pads them."""
    out = np.zeros(len(sources),
                   np.int32 if weights_d is None else np.float32)
    for lo in range(0, len(sources), 512):
        chunk = sources[lo:lo + 512]
        width = min(512, 128 * -(-len(chunk) // 128))
        pad = np.full(width, chunk[-1], np.int64)
        pad[:len(chunk)] = chunk
        dark = np.zeros((width, n), bool)
        dark[np.arange(width), pad] = True
        counts = (_ref_radius(jnp.asarray(dark), consts, crit_d)
                  if weights_d is None else
                  _ref_weighted_radius(jnp.asarray(dark), consts,
                                       weights_d))
        out[lo:lo + len(chunk)] = np.asarray(counts)[:len(chunk)]
    return out


def _ref_harden(consts, pick):
    pick = jnp.asarray(pick)
    out = dict(consts, closed=consts["closed"].at[pick].set(False))
    if "ell_closed" in consts:
        rows = consts.get("ell_row", consts["src"])
        out["ell_closed"] = consts["ell_closed"].at[
            rows[pick], consts["ell_slot"][pick]].set(False)
    return out


def reference_plan(graph, batch=64, max_rounds=10_000,
                   service_weights=None):
    """``plan_hardening`` with the broken set, the frontier, the one-hot
    radius batches and the mask on the host each round: the plan's
    ``(hardened_edges, trajectory, certified, rounds)``."""
    dark = np.asarray(graph.preemptible, bool)
    crit_live = graph.critical & ~dark
    closed = ~graph.fail_open.copy()
    consts = edge_consts(graph)
    crit_d = jnp.asarray(graph.critical)
    weights_d = (None if service_weights is None
                 else jnp.asarray(np.asarray(service_weights, np.float32)))
    dark_d = jnp.asarray(dark[None, :])
    hardened, trajectory, rounds, certified = [], [], 0, False
    while rounds < max_rounds:
        broken_d, _ = fixed_point(dark_d, consts)
        broken = np.asarray(broken_d[0])
        n_bc = int(np.count_nonzero(broken & crit_live))
        trajectory.append({"n_hardened": len(hardened),
                           "n_broken_critical": n_bc})
        if n_bc == 0:
            certified = True
            break
        rounds += 1
        frontier = np.flatnonzero(closed & broken[graph.dst]
                                  & ~dark[graph.src])
        if len(frontier) == 0:
            raise RuntimeError("reference stalled: no fail-close frontier")
        callers = np.unique(graph.src[frontier])
        counts = _ref_radius_counts(callers, graph.n, consts, crit_d,
                                    weights_d)
        radius = np.zeros(graph.n, counts.dtype)
        radius[callers] = counts
        score = radius[graph.src[frontier]].astype(np.float64)
        w = graph.weight[frontier].astype(np.float64)
        score += w / (w.max() + 1.0)
        pick = frontier[np.argsort(-score, kind="stable")[:batch]]
        hardened.extend(int(i) for i in pick)
        closed[pick] = False
        consts = _ref_harden(consts, pick)
    if not certified:
        certified = certify(graph.harden(hardened), dark).ok
    return hardened, trajectory, certified, rounds


def _paper_graph():
    return CallGraph.from_fleet_state(synthesize_fleet_state(
        scale=0.1, seed=7, unsafe_chain_fraction=0.06))


def _alibaba_graph():
    from repro.core.fleet_shapes import alibaba_fleet_state
    return CallGraph.from_fleet_state(alibaba_fleet_state(scale=0.05,
                                                          seed=7))


def _weights(g):
    return np.random.default_rng(3).random(g.n) * g.critical


@pytest.mark.parametrize("case,kernels,batch,max_rounds,weighted", [
    ("paper", "0", 4, 10_000, False),
    ("alibaba", "1", 4, 10_000, False),
    ("weighted", "0", 4, 10_000, True),
    ("cut_short", "0", 4, 1, False),
])
def test_planner_matches_host_loop(monkeypatch, case, kernels, batch,
                                   max_rounds, weighted):
    """The planner whose rounds stay on the device gives the host loop's
    plan in every field: on the paper-shaped fleet, on a trace-shaped one
    (hub rows split in the kernel's layout), on the weighted ranking, and
    cut short by ``max_rounds`` (certification then re-runs on the plan)."""
    monkeypatch.setenv("REPRO_UFA_KERNELS", kernels)
    g = _alibaba_graph() if case == "alibaba" else _paper_graph()
    w = _weights(g) if weighted else None
    plan = plan_hardening(g, batch=batch, max_rounds=max_rounds,
                          service_weights=w)
    hardened, trajectory, certified, rounds = reference_plan(
        g, batch=batch, max_rounds=max_rounds, service_weights=w)
    assert plan.hardened_edges == hardened
    assert plan.trajectory == trajectory
    assert (plan.certified, plan.rounds) == (certified, rounds)
    assert plan.hardened_edge_names == g.edge_names(hardened)
    assert np.array_equal(plan.graph.fail_open, g.harden(hardened).fail_open)
    if case == "cut_short":
        assert rounds == 1 and not certified
    else:
        assert certified and rounds > 1


def test_planner_stall_raises(monkeypatch):
    """Broken criticals whose only fail-close edge has a dark caller: the
    frontier is empty from the first round (hardening that edge changes
    nothing), and the planner raises instead of spinning, as the host loop
    does."""
    # 0 critical -> 1 preemptible fail-open; 2 preemptible -> 1 fail-close
    g = _build_csr(3, np.array([0, 2], np.int32), np.array([1, 1], np.int32),
                   np.array([True, False]), np.ones(2, np.float32),
                   np.array([True, False, False]),
                   np.array([False, True, True]), ["crit", "pre", "pre2"])
    def everything_breaks(dark, consts):
        return jnp.ones_like(dark), jnp.asarray(0)

    monkeypatch.setattr(planner, "fixed_point", everything_breaks)
    monkeypatch.setitem(globals(), "fixed_point", everything_breaks)
    with pytest.raises(RuntimeError, match="after 0 hardened edge"):
        plan_hardening(g)
    with pytest.raises(RuntimeError, match="no fail-close"):
        reference_plan(g)


def test_planner_compiles_once_per_graph():
    """A second plan on the same graph adds no entry to the jit caches of
    a round's programs: the fixed point and its verdict, the radius batch
    and the mask update."""
    g = _paper_graph()
    programs = (propagation._fixed_point, planner._frontier,
                propagation._radius_kernel, propagation._harden_masks)
    first = plan_hardening(g, batch=16)
    sizes = [p._cache_size() for p in programs]
    assert min(sizes) >= 1
    second = plan_hardening(g, batch=16)
    assert [p._cache_size() for p in programs] == sizes
    assert second.hardened_edges == first.hardened_edges


@pytest.mark.parametrize("k", [1, 128, 513])
def test_blast_radius_widths_match_bfs(k):
    """Source batches of one row, one whole bucket, and a full chunk plus
    one: the radius from index batches equals the BFS reference."""
    rng = np.random.default_rng(k)
    g, edges = random_graph(rng, n=600, p_edge=0.004, p_close=0.6)
    sources = np.sort(rng.choice(g.n, size=k, replace=False))
    radius = blast_radius(g, sources=sources)
    want = np.zeros(g.n, np.int64)
    for j in sources:
        want[j] = sum(g.critical[u]
                      for u in bfs_propagate(g.n, edges, [j]))
    assert np.array_equal(radius, want)


def test_regression_gate_flags_planted_edge():
    fs = synthesize_fleet_state(scale=0.05, seed=7,
                                unsafe_chain_fraction=0.06)
    hardened = plan_hardening(CallGraph.from_fleet_state(fs)).graph
    # hardened fleet passes its own gate
    assert regression_gate(hardened, hardened).ok
    # plant a new unsafe edge critical -> preemptible: flagged
    crit = int(np.flatnonzero(hardened.critical)[0])
    pre = int(np.flatnonzero(hardened.preemptible)[0])
    bad = hardened.with_edge(hardened.names[crit], hardened.names[pre],
                             fail_open=False)
    gate = regression_gate(hardened, bad)
    assert not gate.ok
    assert (hardened.names[crit], hardened.names[pre]) in [
        (c, d) for c, d, _ in gate.violations]
    # a new unsafe edge between preemptible services with no critical
    # fail-close callers reaches nothing critical: gate passes
    pre2 = int(np.flatnonzero(hardened.preemptible)[1])
    benign = hardened.with_edge(hardened.names[pre],
                                hardened.names[pre2], fail_open=False)
    gate2 = regression_gate(hardened, benign)
    assert gate2.ok and gate2.new_unsafe_edges


def test_detections_build_equivalent_graph():
    """Static analysis has perfect recall/precision on the synthesized IR,
    so the graph built from its detections certifies identically to the
    ground-truth graph."""
    from repro.core.static_analysis import static_analysis
    fleet = synthesize_fleet(scale=0.05, seed=3)
    sa = static_analysis(fleet, seed=2)
    g_det, g_truth = sa["graph"], CallGraph.from_specs(fleet)
    assert g_det.unsafe_edge_keys() == g_truth.unsafe_edge_keys()
    assert (certify(g_det).broken == certify(g_truth).broken).all()


def test_drills_flag_multi_hop_chain():
    """A critical service with NO direct unsafe dependency but a fail-close
    edge onto a broken critical callee must be flagged by the drill — the
    case the one-hop error model could not see."""
    fs = synthesize_fleet_state(scale=0.05, seed=11,
                                unsafe_chain_fraction=0.06)
    cert = certify_fleet_state(fs, seed=0)
    assert cert["n_multi_hop"] > 0
    assert cert["propagation_rounds"] >= 2
    g = CallGraph.from_fleet_state(fs)
    relay_only = certify(g).multi_hop
    assert (cert["flagged_mask"] & relay_only).sum() == relay_only.sum()
    # hardening everything un-flags everyone
    fs.edges.fail_open[:] = True
    cert2 = certify_fleet_state(fs, seed=0)
    assert cert2["n_flagged"] == 0 and cert2["n_multi_hop"] == 0


def test_scenario_sweep_with_dependency_ensemble():
    fs = synthesize_fleet_state(scale=0.05, seed=7,
                                unsafe_chain_fraction=0.05)
    fs.apply_ufa_target_classes()
    grid = scenario_grid(evict_fraction=(1.0, 0.75, 0.5, 0.25))
    res = sweep_with_dependency_ensemble(fs, grid, seed=0)
    n = len(grid["evict_fraction"])
    assert len(res["dep_ok"]) == n
    # un-hardened fleet: full-eviction scenarios must fail the dep check
    full = res["evict_fraction"] == 1.0
    assert not res["dep_ok"][full].any()
    assert not res["sla_ok"][full].any()
    summary = summarize_sweep(res)
    assert summary["n_dep_ok"] < n
    assert summary["worst_dep_broken_frac"] > 0
    # hardened fleet: dep check passes everywhere and availability is
    # pointwise >= the un-hardened sweep's
    fs.edges.fail_open[:] = True
    res2 = sweep_with_dependency_ensemble(fs, grid, seed=0)
    assert res2["dep_ok"].all()
    assert (res2["availability"] >= res["availability"] - 1e-9).all()


def test_unsafe_edges_object_path_with_chains():
    """Object-path synthesis with relay chains: every unsafe edge is
    either tier-inverted (critical -> preemptible) or a critical ->
    critical relay, and relays actually occur."""
    with_chains = synthesize_fleet(scale=0.05, seed=3,
                                   unsafe_chain_fraction=0.3)
    relays = 0
    for c, d in unsafe_edges(with_chains):
        assert with_chains[c].failure_class.survives_failover
        if with_chains[d].failure_class.survives_failover:
            relays += 1
        else:
            assert with_chains[d].failure_class.preemptible
    assert relays > 0
    # and relays feed multi-hop breakage the drill can see
    g = CallGraph.from_specs(with_chains)
    assert certify(g).multi_hop.sum() > 0
