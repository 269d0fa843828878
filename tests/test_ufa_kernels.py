"""UFA Pallas kernels (interpret mode on CPU): exact parity of the three
device kernels — ELL frontier propagation, scatter-add histogram ingest,
segmented verdict reduction — against their XLA references and scalar
ground truth, the ``REPRO_UFA_KERNELS`` backend dispatch end to end
(graph layer, planner, detector, sweep engine), edge cases (empty
frontier, edge-free graph, zero records), and the x64 dtype pins."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.dependency import RuntimeFailCloseDetector, runtime_analysis
from repro.core.fleet_state import synthesize_fleet_state
from repro.core.scenarios import FleetAggregates, scenario_grid
from repro.core.service import synthesize_fleet
from repro.core.sweep_engine import SweepEngine
from repro.core.timeline_sim import config_for_fleet, default_ts
from repro.graph import (CallGraph, blackhole_ensemble, blast_radius,
                         certify, plan_hardening, propagate, propagate_many)
from repro.graph.callgraph import _build_csr
from repro.graph.propagation import (edge_consts, fixed_point, harden_consts,
                                     layout_counts)
from repro.kernels.backend import default_interpret, use_ufa_kernels
from repro.kernels.ufa.ingest import (N_CODES, ingest_hist, ref_ingest_hist)
from repro.kernels.ufa.propagation import (ell_from_csr, ell_slots,
                                           ell_split_from_csr, ell_width,
                                           fixed_point_ell, ref_fixed_point)
from repro.kernels.ufa.reduce import ref_timeline_reduce, timeline_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ALLOWED = (np.float32, np.bool_, np.int32)


# ---------------------------------------------------------------------------
# scalar references (standalone — no coupling to other test modules)
# ---------------------------------------------------------------------------


def bfs_broken(n, src, dst, closed, dark):
    """Worklist BFS fixed point: failure flows callee -> caller along
    fail-close (closed) edges only."""
    callers_of = {}
    for u, v, c in zip(src.tolist(), dst.tolist(), closed.tolist()):
        if c:
            callers_of.setdefault(v, []).append(u)
    broken = set(np.flatnonzero(dark).tolist())
    frontier = list(broken)
    while frontier:
        v = frontier.pop()
        for u in callers_of.get(v, ()):
            if u not in broken:
                broken.add(u)
                frontier.append(u)
    out = np.zeros(n, bool)
    out[list(broken)] = True
    return out


def random_csr(rng, n=None, p_edge=0.15, p_close=0.5):
    """Random digraph with cycles in CSR order (nonzero scan is row-major,
    so ``src`` is already sorted)."""
    n = n if n is not None else int(rng.integers(4, 60))
    m = rng.random((n, n)) < p_edge
    np.fill_diagonal(m, False)
    src, dst = np.nonzero(m)
    closed = rng.random(len(src)) < p_close
    indptr = np.searchsorted(src, np.arange(n + 1)).astype(np.int64)
    return n, indptr, src.astype(np.int32), dst.astype(np.int32), closed


def scalar_reduce(avail, util, cloud, frac, ts, thresh):
    """Step-by-step float32 replica of ``timeline_sim._carry_step`` (the
    sequential scan the kernel replaces): dt[0] = 0, first-crossing
    restore times, cumulative below_seen."""
    S, T = avail.shape
    R = frac.shape[2]
    avail_int = np.zeros(S, np.float32)
    avail_min = np.ones(S, np.float32)
    util_peak = np.zeros(S, np.float32)
    cloud_peak = np.zeros(S, np.float32)
    below_seen = np.zeros((S, R), bool)
    restore_t = np.full((S, R), np.inf, np.float32)
    prev_t = np.float32(ts[0])
    for t in range(T):
        dt = np.float32(max(np.float32(ts[t]) - prev_t, 0.0))
        prev_t = np.float32(ts[t])
        avail_int = np.float32(avail_int + avail[:, t] * dt)
        avail_min = np.minimum(avail_min, avail[:, t])
        util_peak = np.maximum(util_peak, util[:, t])
        cloud_peak = np.maximum(cloud_peak, cloud[:, t])
        below = frac[:, t, :] < thresh
        seen = below_seen | below
        cross = seen & ~below & np.isinf(restore_t)
        restore_t = np.where(cross, np.float32(ts[t]), restore_t)
        below_seen = seen
    return {"avail_int": avail_int, "avail_min": avail_min,
            "util_peak": util_peak, "cloud_peak": cloud_peak,
            "restore_t": restore_t, "below_seen": below_seen}


# ---------------------------------------------------------------------------
# backend dispatch helpers
# ---------------------------------------------------------------------------


def test_backend_helpers(monkeypatch):
    # this suite runs on CPU: interpret mode must be the default
    assert default_interpret() is True
    monkeypatch.delenv("REPRO_UFA_KERNELS", raising=False)
    assert use_ufa_kernels() is False          # CPU default: host paths
    monkeypatch.setenv("REPRO_UFA_KERNELS", "1")
    assert use_ufa_kernels() is True
    monkeypatch.setenv("REPRO_UFA_KERNELS", "0")
    assert use_ufa_kernels() is False
    monkeypatch.setenv("REPRO_UFA_KERNELS", "definitely")  # junk -> default
    assert use_ufa_kernels() is False


# ---------------------------------------------------------------------------
# kernel 1: ELL frontier propagation
# ---------------------------------------------------------------------------


def test_ell_from_csr_roundtrip():
    rng = np.random.default_rng(0)
    n, indptr, src, dst, closed = random_csr(rng, n=40)
    ell_dst, ell_closed, slot = ell_from_csr(n, indptr, dst, closed)
    K = ell_dst.shape[1]
    assert K % 8 == 0 and K >= np.diff(indptr).max()
    # every edge lands at (src, slot); pad slots are closed=False
    assert (ell_dst[src, slot] == dst).all()
    assert (ell_closed[src, slot] == closed).all()
    filled = np.zeros((n, K), bool)
    filled[src, slot] = True
    assert not ell_closed[~filled].any()


def test_propagation_matches_ref_and_bfs():
    """Random cyclic graphs x random dark batches: the Pallas fixed point
    must match the XLA scatter-max reference EXACTLY — broken matrix and
    round count — and the BFS scalar reference node for node."""
    for seed in range(8):
        rng = np.random.default_rng(seed)
        n, indptr, src, dst, closed = random_csr(rng)
        ell_dst, ell_closed, _ = ell_from_csr(n, indptr, dst, closed)
        dark = rng.random((5, n)) < rng.uniform(0.05, 0.5)
        got, rounds = fixed_point_ell(
            jnp.asarray(dark), jnp.asarray(ell_dst), jnp.asarray(ell_closed))
        want, ref_rounds = ref_fixed_point(
            jnp.asarray(dark), jnp.asarray(src), jnp.asarray(dst),
            jnp.asarray(closed))
        assert np.array_equal(np.asarray(got), np.asarray(want)), seed
        assert int(rounds) == int(ref_rounds), seed
        for s in range(5):
            assert np.array_equal(np.asarray(got[s]),
                                  bfs_broken(n, src, dst, closed, dark[s]))


def test_propagation_blocking_and_padding():
    """Non-multiple S and n against small block sizes: the pad rows/cols
    must never leak into (or corrupt) the live region."""
    rng = np.random.default_rng(3)
    n, indptr, src, dst, closed = random_csr(rng, n=37, p_edge=0.2)
    ell_dst, ell_closed, _ = ell_from_csr(n, indptr, dst, closed)
    dark = rng.random((5, n)) < 0.3
    got, rounds = fixed_point_ell(
        jnp.asarray(dark), jnp.asarray(ell_dst), jnp.asarray(ell_closed),
        block_s=2, block_r=8)
    want, ref_rounds = ref_fixed_point(
        jnp.asarray(dark), jnp.asarray(src), jnp.asarray(dst),
        jnp.asarray(closed))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert int(rounds) == int(ref_rounds)


def test_propagation_default_blocks_ragged():
    """Default blocks with a service count that is not a multiple of
    ``block_r`` and a scenario count past one 128-lane block: several
    row and lane tiles, both ragged."""
    rng = np.random.default_rng(11)
    n, indptr, src, dst, closed = random_csr(rng, n=300, p_edge=0.01,
                                             p_close=0.7)
    ell_dst, ell_closed, _ = ell_from_csr(n, indptr, dst, closed)
    dark = rng.random((130, n)) < 0.02
    got, rounds = fixed_point_ell(
        jnp.asarray(dark), jnp.asarray(ell_dst), jnp.asarray(ell_closed),
        block_r=128)
    want, ref_rounds = ref_fixed_point(
        jnp.asarray(dark), jnp.asarray(src), jnp.asarray(dst),
        jnp.asarray(closed))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert int(rounds) == int(ref_rounds)


def test_propagation_cycle_and_fail_open_boundary():
    # a->b, b->c, c->a all fail-close (a cycle), c->d fail-close,
    # b->e fail-OPEN; darkening d breaks the whole cycle but spares e
    src = np.array([0, 1, 1, 2, 2], np.int32)
    dst = np.array([1, 2, 4, 0, 3], np.int32)
    closed = np.array([True, True, False, True, True])
    indptr = np.searchsorted(src, np.arange(6)).astype(np.int64)
    ell_dst, ell_closed, _ = ell_from_csr(5, indptr, dst, closed)
    dark = np.zeros((2, 5), bool)
    dark[0, 3] = True                   # d dark: cycle breaks, e survives
    dark[1, 4] = True                   # e dark: fail-open edge relays nothing
    got, _ = fixed_point_ell(
        jnp.asarray(dark), jnp.asarray(ell_dst), jnp.asarray(ell_closed))
    assert np.asarray(got[0]).tolist() == [True, True, True, True, False]
    assert np.asarray(got[1]).tolist() == [False, False, False, False, True]


def test_propagation_empty_cases():
    # edge-free graph: K == 0, one no-change round, broken == dark
    ell_dst, ell_closed, slot = ell_from_csr(
        6, np.zeros(7, np.int64), np.zeros(0, np.int64), np.zeros(0, bool))
    assert ell_dst.shape == (6, 0) and slot.shape == (0,)
    dark = np.eye(6, dtype=bool)[:3]
    got, rounds = fixed_point_ell(
        jnp.asarray(dark), jnp.asarray(ell_dst), jnp.asarray(ell_closed))
    assert np.array_equal(np.asarray(got), dark) and int(rounds) == 1
    # empty scenario batch
    rng = np.random.default_rng(1)
    n, indptr, _, dst, closed = random_csr(rng, n=10)
    ed, ec, _ = ell_from_csr(n, indptr, dst, closed)
    got0, rounds0 = fixed_point_ell(
        jnp.zeros((0, n), bool), jnp.asarray(ed), jnp.asarray(ec))
    assert got0.shape == (0, n) and int(rounds0) == 1


@pytest.fixture(scope="module")
def fleet_graph():
    fs = synthesize_fleet_state(scale=0.05, seed=7,
                                unsafe_chain_fraction=0.06)
    return CallGraph.from_fleet_state(fs)


@pytest.mark.parametrize("env", ["0", "1"])
def test_graph_layer_backends_agree(monkeypatch, fleet_graph, env):
    """certify / blast radius / ensembles / batched propagation return the
    same answers whichever backend ``edge_consts`` dispatches to (the
    Pallas path is compared against fixed expectations computed on the
    default path by the sibling parametrization)."""
    monkeypatch.setenv("REPRO_UFA_KERNELS", env)
    g = fleet_graph
    rng = np.random.default_rng(5)
    dark = rng.random((8, g.n)) < 0.2
    broken, rounds = propagate_many(g, dark)
    cert = certify(g)
    sources = np.flatnonzero(g.preemptible)[:64]
    radius = blast_radius(g, sources=sources)
    ens = blackhole_ensemble(g, seed=0, fractions=np.linspace(0, 1, 16))
    state = (broken, int(rounds), cert.broken, cert.n_broken_critical,
             radius, ens["n_broken_critical"], ens["n_dark"])
    cache = getattr(test_graph_layer_backends_agree, "_state", None)
    if cache is None:
        test_graph_layer_backends_agree._state = state
    else:
        for a, b in zip(cache, state):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    # single-scenario path vs BFS stays exact under either backend
    one = propagate(g, dark[0])
    assert np.array_equal(
        one, bfs_broken(g.n, g.src, g.dst, ~g.fail_open, dark[0]))


def test_planner_backends_agree(monkeypatch, fleet_graph):
    """The greedy hardening planner (frontier batches + in-place mask
    updates through ``harden_consts``) picks the identical edge sequence
    on both backends."""
    monkeypatch.setenv("REPRO_UFA_KERNELS", "0")
    plan_cpu = plan_hardening(fleet_graph, batch=16)
    monkeypatch.setenv("REPRO_UFA_KERNELS", "1")
    plan_dev = plan_hardening(fleet_graph, batch=16)
    assert plan_cpu.certified and plan_dev.certified
    assert plan_cpu.hardened_edges == plan_dev.hardened_edges
    assert plan_cpu.trajectory == plan_dev.trajectory


# ---------------------------------------------------------------------------
# kernel 1, split layout: graphs whose out-degrees are not all alike
# ---------------------------------------------------------------------------


def hub_graph(seed, n=120, hubs=(70, 45), hops=4):
    """A seeded call graph with hubs far wider than the base width beside
    callers of a few callees, and a fail-close relay chain ``hops`` deep
    whose first link is the widest hub's last (spilled) callee: darkening
    the chain's end breaks it one hop a round, through a split row."""
    rng = np.random.default_rng(seed)
    deg = rng.poisson(2.0, n)
    deg[:len(hubs)] = hubs
    src, dst = [], []
    for u in range(n):
        callees = rng.choice(np.delete(np.arange(n), u), deg[u],
                             replace=False)
        src += [u] * len(callees)
        dst += callees.tolist()
    src, dst = np.asarray(src), np.asarray(dst)
    closed = rng.random(len(src)) < 0.3
    chain = list(range(n - hops, n))             # chain[0] <- ... <- end
    # no shortcut: the chain's links are its only fail-close edges
    closed[np.isin(src, [0] + chain) & np.isin(dst, chain)] = False
    for a, b in zip([0] + chain[:-1], chain):
        keep = ~((src == a) & (dst == b))
        src, dst, closed = src[keep], dst[keep], closed[keep]
        src = np.append(src, a)
        dst = np.append(dst, b)
        closed = np.append(closed, True)
    critical = np.zeros(n, bool)
    critical[[0] + chain[:-1]] = True
    g = _build_csr(n, src.astype(np.int32), dst.astype(np.int32), ~closed,
                   np.ones(len(src), np.float32), critical, ~critical,
                   [f"s{i}" for i in range(n)])
    return g, chain[-1]


def test_ell_width_follows_the_degrees():
    """Like degrees keep the largest degree, rounded up (the paper fleet's
    13 gives 16); a hub past 32 gives the width of fewest slots."""
    assert ell_width(np.array([0, 3, 13, 6])) == 16
    assert ell_width(np.array([32, 1])) == 32
    deg = np.r_[np.full(500, 3), 600, 300]
    k = ell_width(deg)
    assert k % 8 == 0 and k <= 32
    assert ell_slots(deg, k) == min(ell_slots(deg, w)
                                    for w in range(8, 601, 8))
    assert ell_slots(deg, k) < ell_slots(deg, 600) / 10


def test_split_layout_places_every_edge():
    g, _ = hub_graph(0)
    K = ell_width(np.diff(g.indptr))
    ell_dst, ell_closed, row, slot, tag = ell_split_from_csr(
        g.n, g.indptr, g.dst, ~g.fail_open, K)
    assert K == 8 and ell_dst.shape[0] > g.n
    assert (ell_dst[row, slot] == g.dst).all()
    assert (ell_closed[row, slot] == ~g.fail_open).all()
    # each position holds one edge; pad slots are fail-open
    assert len(np.unique(row.astype(np.int64) * K + slot)) == g.n_edges
    filled = np.zeros(ell_dst.shape, bool)
    filled[row, slot] = True
    assert not ell_closed[~filled].any()
    # a split caller names its last aux row; its aux rows chain in order
    for u in np.flatnonzero(tag[:g.n] >= 0):
        mine = np.unique(row[g.out_edges(u)])
        aux = mine[mine >= g.n] - g.n
        assert tag[g.n + aux].tolist() == [0] + [1] * (len(aux) - 1)
        assert tag[u] == aux[-1] and (np.diff(aux) == 1).all()
    deg = np.diff(g.indptr)
    assert ell_dst.size + np.count_nonzero(tag[:g.n] >= 0) == ell_slots(deg,
                                                                        K)


@pytest.mark.parametrize("kernels", ["0", "1"], ids=["xla", "pallas"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_split_layout_fixed_point_matches_ref(monkeypatch, kernels, seed):
    """On a graph with split rows and a relay chain through a spilled slot,
    the dispatched fixed point (XLA, or the Pallas kernel on the split
    layout) equals the edge-list reference: booleans and rounds exact; the
    chain's end reaches the hub in its four hops."""
    monkeypatch.setenv("REPRO_UFA_KERNELS", kernels)
    g, end = hub_graph(seed)
    consts = edge_consts(g)
    assert ("ell_tag" in consts) == (kernels == "1")
    rng = np.random.default_rng(seed)
    dark = rng.random((6, g.n)) < 0.05
    dark[0] = False
    dark[0, end] = True
    got, rounds = fixed_point(jnp.asarray(dark), consts)
    want, ref_rounds = ref_fixed_point(
        jnp.asarray(dark), jnp.asarray(g.src), jnp.asarray(g.dst),
        jnp.asarray(~g.fail_open))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert int(rounds) == int(ref_rounds)
    one, r1 = fixed_point(jnp.asarray(dark[:1]), consts)
    assert np.asarray(one)[0, 0] and int(r1) >= 4 + 1
    if kernels == "1":
        # small blocks: aux chains cross row blocks, lanes cross blocks
        small, r = fixed_point_ell(
            jnp.asarray(dark), consts["ell_dst"], consts["ell_closed"],
            consts["ell_tag"], block_s=2, block_r=8)
        assert np.array_equal(np.asarray(small), np.asarray(want))
        assert int(r) == int(ref_rounds)


def test_harden_consts_on_split_rows(monkeypatch):
    """The planner's in-place flips land on the edges' own rows, aux rows
    included: the same consts as a rebuild from the hardened graph."""
    monkeypatch.setenv("REPRO_UFA_KERNELS", "1")
    g, _ = hub_graph(1)
    consts = edge_consts(g)
    row = np.asarray(consts["ell_row"])
    closed = ~g.fail_open
    pick = np.r_[np.flatnonzero(closed & (row >= g.n))[:5],
                 np.flatnonzero(closed & (row < g.n))[:2]]
    assert (row[pick] >= g.n).sum() == 5
    got = harden_consts(consts, jnp.asarray(pick))
    want = edge_consts(g.harden(pick))
    for k in ("closed", "ell_closed", "ell_dst", "ell_row", "ell_tag"):
        assert np.array_equal(np.asarray(got[k]), np.asarray(want[k])), k


def test_paper_fleet_layout_unchanged(monkeypatch):
    """The paper fleet (largest out-degree 13) keeps K = 16 and splits no
    row: the consts are a straight ``ell_from_csr`` build, key for key."""
    monkeypatch.setenv("REPRO_UFA_KERNELS", "1")
    g = CallGraph.from_fleet_state(synthesize_fleet_state(scale=1.0, seed=7))
    consts = edge_consts(g)
    ell_dst, ell_closed, slot = ell_from_csr(g.n, g.indptr, g.dst,
                                             ~g.fail_open)
    assert ell_dst.shape == (g.n, 16)
    assert set(consts) == {"src", "dst", "closed", "ell_dst", "ell_closed",
                           "ell_slot"}
    for k, v in (("ell_dst", ell_dst), ("ell_closed", ell_closed),
                 ("ell_slot", slot)):
        assert consts[k].dtype == v.dtype
        assert np.array_equal(np.asarray(consts[k]), v), k
    assert layout_counts(g) == {"edges": 120_827, "slots": g.n * 16,
                                "split_rows": 0}


def test_alibaba_fleet_layout_walks_few_slots(monkeypatch):
    """The trace-shaped fleet at full size: a largest out-degree of 256 or
    more, which the straight layout would walk 43 times over per edge or
    more; the split layout walks at most 4 slots per edge."""
    from repro.core.fleet_shapes import alibaba_fleet_state
    monkeypatch.setenv("REPRO_UFA_KERNELS", "1")
    g = CallGraph.from_fleet_state(alibaba_fleet_state(scale=1.0, seed=7))
    deg = np.diff(g.indptr)
    assert g.n == 21_979 and deg.max() >= 256
    assert ell_slots(deg, -(-deg.max() // 8) * 8) >= 43 * g.n_edges
    counts = layout_counts(g)
    assert counts["edges"] == g.n_edges and counts["split_rows"] > 0
    assert counts["slots"] <= 4 * g.n_edges


def test_sweep_dependency_stage_on_split_layout(monkeypatch):
    """The fused sweep's dependency stage over a small trace-shaped fleet:
    the Pallas kernel on the split layout gives the XLA path's
    broken-critical counts."""
    from repro.core.fleet_shapes import alibaba_fleet_state
    fs = alibaba_fleet_state(scale=0.05, seed=7, unsafe_chain_fraction=0.3)
    graph = CallGraph.from_fleet_state(fs)
    agg, cfg = FleetAggregates.from_fleet_state(fs), config_for_fleet(fs)
    grid = scenario_grid(evict_fraction=(1.0, 0.6, 0.3, 0.1))
    out = {}
    for kernels in ("0", "1"):
        monkeypatch.setenv("REPRO_UFA_KERNELS", kernels)
        eng = SweepEngine(agg, cfg, graph=graph, ts=default_ts(7200.0, 24),
                          reducer="scan")
        assert ("ell_tag" in eng.dep) == (kernels == "1")
        out[kernels] = eng.run(grid)
    assert out["0"]["dep_n_broken_critical"].max() > 0
    for k in ("dep_n_broken_critical", "dep_n_dark", "dep_broken_frac"):
        assert np.array_equal(out["0"][k], out["1"][k]), k


# ---------------------------------------------------------------------------
# kernel 2: scatter-add histogram ingest
# ---------------------------------------------------------------------------


def _random_records(rng, n_records, n_edges):
    eid = rng.integers(0, n_edges, n_records)
    failed = rng.random(n_records) < 0.3
    errored = rng.random(n_records) < 0.4
    return eid, failed, errored


def _np_hist(eid, failed, errored, n_edges):
    code = failed.astype(np.int64) * 2 + errored.astype(np.int64)
    return np.bincount(eid * N_CODES + code,
                       minlength=n_edges * N_CODES).reshape(-1, N_CODES)


@pytest.mark.parametrize("n_records,n_edges,block_n", [
    (10_000, 257, 4096),       # multi-block grid, padded rows
    (5_000, 256, 4096),        # n_edges already a multiple of 8: e_pad ==
                               # n_edges, the pad-sentinel regression case
    (999, 8, 256),             # tiny universe, heavy duplicates
    (4096, 1000, 4096),        # single block, no record padding
])
def test_ingest_hist_exact(n_records, n_edges, block_n):
    rng = np.random.default_rng(n_records)
    eid, failed, errored = _random_records(rng, n_records, n_edges)
    got = np.asarray(ingest_hist(
        jnp.asarray(eid), jnp.asarray(failed), jnp.asarray(errored),
        n_edges, block_n=block_n))
    ref = np.asarray(ref_ingest_hist(
        jnp.asarray(eid), jnp.asarray(failed), jnp.asarray(errored),
        n_edges))
    want = _np_hist(eid, failed, errored, n_edges)
    assert np.array_equal(got, want)
    assert np.array_equal(ref, want)
    assert got.sum() == n_records          # pads never counted


@pytest.mark.parametrize("n_records,n_edges,block_n,block_e", [
    (10_000, 1100, 4096, 256),   # 5 edge tiles, the last one ragged
    (3_000, 256, 1024, 256),     # exactly one tile
    (999, 1000, 100, 96),        # tile rounded up to whole row groups;
                                 # block_n rounded up to the unroll
])
def test_ingest_hist_edge_tiles(n_records, n_edges, block_n, block_e):
    rng = np.random.default_rng(n_edges)
    eid, failed, errored = _random_records(rng, n_records, n_edges)
    got = np.asarray(ingest_hist(
        jnp.asarray(eid), jnp.asarray(failed), jnp.asarray(errored),
        n_edges, block_n=block_n, block_e=block_e))
    assert np.array_equal(got, _np_hist(eid, failed, errored, n_edges))


def test_ingest_hist_empty():
    z = jnp.zeros(0, jnp.int32)
    assert np.asarray(ingest_hist(z, z, z, 16)).sum() == 0
    assert ingest_hist(z, z, z, 0).shape == (0, N_CODES)
    eid = jnp.zeros(5, jnp.int32)
    assert ingest_hist(eid, eid, eid, 0).shape == (0, N_CODES)


@pytest.mark.parametrize("env", ["0", "1"])
def test_detector_backends_agree(monkeypatch, env):
    """``ingest_batch`` folds identical counts through either backend, so
    the full runtime analysis (sampled stream -> detection graph) must be
    bit-identical."""
    monkeypatch.setenv("REPRO_UFA_KERNELS", env)
    fleet = synthesize_fleet(scale=0.02, seed=3, as_arrays=True)
    res = runtime_analysis(fleet, n_records=60_000, seed=0)
    det = res["detector"]
    state = (det.calls, det.callee_failures, det.errors_given_failure,
             det.errors_given_ok, sorted(res["found"]), res["precision"],
             res["recall"])
    cache = getattr(test_detector_backends_agree, "_state", None)
    if cache is None:
        test_detector_backends_agree._state = state
    else:
        for a, b in zip(cache, state):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    assert det.calls.dtype == np.int64
    assert det.n_records == 60_000


def test_ingest_overflow_guard():
    det = RuntimeFailCloseDetector()
    det.ingest([type("R", (), {"caller": "a", "callee": "b",
                               "callee_failed": True,
                               "caller_errored": True})()])
    assert det.calls.tolist() == [1]
    det.calls[:] = 1 << 62                 # evidence near the int64 ceiling
    with pytest.raises(AssertionError, match="overflow"):
        det.ingest_batch(np.zeros(1, np.int64), np.ones(1, bool),
                         np.ones(1, bool))


# ---------------------------------------------------------------------------
# kernel 3: segmented verdict reduction
# ---------------------------------------------------------------------------


def _random_series(rng, S=19, T=33, R=3):
    avail = rng.random((S, T), dtype=np.float32)
    util = rng.random((S, T), dtype=np.float32) * 1.5
    cloud = rng.random((S, T), dtype=np.float32) * 1e5
    # tier fractions hovering around the threshold so every scenario mixes
    # never-below / below-then-restored / still-below tiers
    frac = (0.995 + 0.01 * rng.random((S, T, R))).astype(np.float32)
    ts = np.cumsum(rng.random(T).astype(np.float32) * 30.0)
    return avail, util, cloud, frac, ts


def test_timeline_reduce_matches_scalar_and_ref():
    thresh = 0.999
    for seed in range(4):
        rng = np.random.default_rng(seed)
        avail, util, cloud, frac, ts = _random_series(rng)
        got = {k: np.asarray(v) for k, v in timeline_reduce(
            jnp.asarray(avail), jnp.asarray(util), jnp.asarray(cloud),
            jnp.asarray(frac), jnp.asarray(ts), thresh=thresh,
            block_s=8).items()}
        ref = {k: np.asarray(v) for k, v in ref_timeline_reduce(
            jnp.asarray(avail), jnp.asarray(util), jnp.asarray(cloud),
            jnp.asarray(frac), jnp.asarray(ts), thresh=thresh).items()}
        want = scalar_reduce(avail, util, cloud, frac, ts, thresh)
        for k in want:
            # selections (min/max/first-crossing/cumulative-OR) are exact;
            # the availability integral is a reordered float32 sum
            if k == "avail_int":
                np.testing.assert_allclose(got[k], want[k], rtol=3e-6)
                np.testing.assert_allclose(ref[k], want[k], rtol=3e-6)
            else:
                assert np.array_equal(got[k], want[k]), (seed, k)
                assert np.array_equal(ref[k], want[k]), (seed, k)
        assert np.array_equal(got["restore_t"] < np.inf,
                              got["below_seen"] & (got["restore_t"] < np.inf))


def test_timeline_reduce_crossing_semantics():
    """Hand-built tier trajectories: never below -> inf/False; dip then
    restore -> the FIRST timestamp at-threshold; below at the end -> inf
    restore but below_seen True (time_to_restore reports 0 downstream)."""
    ts = np.arange(6, dtype=np.float32) * 10.0
    frac = np.ones((1, 6, 3), np.float32)
    frac[0, 1:3, 1] = 0.5                  # tier 1: below at t=10,20
    frac[0, 2:, 2] = 0.5                   # tier 2: below from t=20 onward
    z = np.zeros((1, 6), np.float32)
    got = timeline_reduce(jnp.asarray(z), jnp.asarray(z), jnp.asarray(z),
                          jnp.asarray(frac), jnp.asarray(ts), thresh=0.999)
    assert np.asarray(got["below_seen"])[0].tolist() == [False, True, True]
    restore = np.asarray(got["restore_t"])[0]
    assert np.isinf(restore[0])            # never below
    assert restore[1] == 30.0              # first step back at full strength
    assert np.isinf(restore[2])            # never restored
    # single-step edge case: T == 1, dt[0] == 0 -> zero integral
    one = timeline_reduce(
        jnp.ones((2, 1)), jnp.zeros((2, 1)), jnp.zeros((2, 1)),
        jnp.ones((2, 1, 3)), jnp.asarray(ts[:1]), thresh=0.999)
    assert np.asarray(one["avail_int"]).tolist() == [0.0, 0.0]


def test_timeline_reduce_crossing_at_edges():
    """Crossings at the first and last steps, per tier: below at t=0 and
    restored at t=1; below from t=0 on, never restored; below only at
    the last step; restored only at the last step; never below."""
    ts = np.arange(5, dtype=np.float32) * 60.0
    frac = np.ones((1, 5, 5), np.float32)
    frac[0, 0, 0] = 0.5
    frac[0, :, 1] = 0.5
    frac[0, 4, 2] = 0.5
    frac[0, :4, 3] = 0.5
    z = np.zeros((1, 5), np.float32)
    args = [jnp.asarray(z)] * 3 + [jnp.asarray(frac), jnp.asarray(ts)]
    got = {k: np.asarray(v) for k, v in timeline_reduce(
        *args, thresh=0.999).items()}
    ref = {k: np.asarray(v) for k, v in ref_timeline_reduce(
        *args, thresh=0.999).items()}
    want = scalar_reduce(z, z, z, frac, ts, 0.999)
    assert got["restore_t"][0].tolist() == [60.0, np.inf, np.inf, 240.0,
                                            np.inf]
    assert got["below_seen"][0].tolist() == [True, True, True, True, False]
    for k in want:
        assert np.array_equal(got[k], want[k]), k
        assert np.array_equal(ref[k], want[k]), k


@pytest.fixture(scope="module")
def engine_parts():
    fs = synthesize_fleet(scale=0.02, seed=1, as_arrays=True)
    fs.apply_ufa_target_classes()
    return (FleetAggregates.from_fleet_state(fs), config_for_fleet(fs),
            CallGraph.from_fleet_state(fs))


def test_sweep_engine_reducer_parity(engine_parts):
    """reducer="pallas" vs the bit-exact scan path on a full 256-scenario
    grid (dependency stage fused in): every verdict identical except the
    availability integral, which is float32-tight."""
    agg, cfg, graph = engine_parts
    ts = default_ts(7200.0, 120)
    grid = scenario_grid(evict_fraction=(1.0, 0.5))
    scan = SweepEngine(agg, cfg, graph=graph, ts=ts, reducer="scan").run(grid)
    pal = SweepEngine(agg, cfg, graph=graph, ts=ts,
                      reducer="pallas").run(grid)
    assert set(scan) == set(pal)
    for k in scan:
        if k == "t_availability_mean":
            np.testing.assert_allclose(pal[k], scan[k], rtol=1e-5)
        else:
            assert np.array_equal(pal[k], scan[k], equal_nan=True), k
        if k not in grid:
            assert pal[k].dtype in _ALLOWED, (k, pal[k].dtype)


def test_sweep_engine_reducer_dispatch(monkeypatch, engine_parts):
    agg, cfg, _ = engine_parts
    monkeypatch.setenv("REPRO_UFA_KERNELS", "1")
    assert SweepEngine(agg, cfg).reducer == "pallas"
    monkeypatch.setenv("REPRO_UFA_KERNELS", "0")
    assert SweepEngine(agg, cfg).reducer == "scan"
    with pytest.raises(AssertionError):
        SweepEngine(agg, cfg, reducer="fancy")


# ---------------------------------------------------------------------------
# dtype pins under x64
# ---------------------------------------------------------------------------


def test_kernels_no_float64_under_x64():
    """JAX_ENABLE_X64=1 must not leak float64/int64 out of any of the
    three kernels (or their refs): a weak Python scalar in kernel code
    would promote here."""
    code = textwrap.dedent("""
        import numpy as np
        import jax.numpy as jnp
        from repro.kernels.ufa.ingest import ingest_hist, ref_ingest_hist
        from repro.kernels.ufa.propagation import (ell_from_csr,
                                                   fixed_point_ell,
                                                   ref_fixed_point)
        from repro.kernels.ufa.reduce import (ref_timeline_reduce,
                                              timeline_reduce)
        allowed = (np.float32, np.bool_, np.int32)
        rng = np.random.default_rng(0)
        n = 30
        m = rng.random((n, n)) < 0.2
        np.fill_diagonal(m, False)
        src, dst = np.nonzero(m)
        closed = rng.random(len(src)) < 0.5
        indptr = np.searchsorted(src, np.arange(n + 1))
        ed, ec, _ = ell_from_csr(n, indptr, dst, closed)
        dark = rng.random((4, n)) < 0.3
        broken, rounds = fixed_point_ell(jnp.asarray(dark),
                                         jnp.asarray(ed), jnp.asarray(ec))
        ref, rref = ref_fixed_point(
            jnp.asarray(dark), jnp.asarray(src.astype(np.int32)),
            jnp.asarray(dst.astype(np.int32)), jnp.asarray(closed))
        assert broken.dtype == np.bool_ and rounds.dtype == np.int32
        assert np.array_equal(np.asarray(broken), np.asarray(ref))
        assert int(rounds) == int(rref)
        eid = rng.integers(0, 100, 5000)
        f = rng.random(5000) < 0.3
        e = rng.random(5000) < 0.4
        h = ingest_hist(jnp.asarray(eid), jnp.asarray(f), jnp.asarray(e),
                        100)
        hr = ref_ingest_hist(jnp.asarray(eid), jnp.asarray(f),
                             jnp.asarray(e), 100)
        assert h.dtype == np.int32 and hr.dtype == np.int32
        assert np.array_equal(np.asarray(h), np.asarray(hr))
        S, T, R = 9, 17, 3
        a = rng.random((S, T), dtype=np.float32)
        fr = (0.99 + 0.02 * rng.random((S, T, R))).astype(np.float32)
        ts = np.cumsum(rng.random(T).astype(np.float32))
        out = timeline_reduce(jnp.asarray(a), jnp.asarray(a),
                              jnp.asarray(a), jnp.asarray(fr),
                              jnp.asarray(ts), thresh=0.999)
        for k, v in out.items():
            assert v.dtype in allowed, (k, v.dtype)
        print("OK")
    """)
    env = dict(os.environ)
    env["JAX_ENABLE_X64"] = "1"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
