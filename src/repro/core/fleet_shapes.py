"""Fleets of other shapes than the paper's, for deployments that users run.

The paper's fleet (``fleet_state.synthesize_fleet_state``) draws every
service's callees from one normal distribution, so every caller row looks
alike.  Published microservice traces do not: Luo et al., "Characterizing
Microservice Dependency and Performance: Alibaba Trace Analysis" (SoCC
2021, trace ``cluster-trace-microservices-v2021``) describe heavy-tailed
call graphs, hub services (gateways, shared caches and databases) with
very wide fan-out and fan-in, and heavily skewed per-edge call rates.
``alibaba_fleet_state`` writes that shape onto the paper's services: the
tiers, cores, replicas and failure classes of Tables 1-3 stay as the
paper fleet has them, and only the call graph changes.  The paper names
the shape, not the numbers: every parameter here is set by the caller
(the configuration file lists them under ``assumed``).
"""

from __future__ import annotations

import numpy as np

from repro.core.fleet_state import (AM, RL, EdgeArrays, FleetState,
                                    synthesize_fleet_state)
from repro.core.tiers import Tier

_T = list(Tier)


def _power_law_exponent(mean: float, cap: int, shift: float) -> float:
    """Exponent ``g`` of ``P(d) ~ (d + shift)**-g`` on ``1..cap`` whose
    mean is ``mean`` (bisection; the mean falls as ``g`` grows)."""
    d = np.arange(1, cap + 1, dtype=np.float64)
    lo, hi = 0.0, 10.0
    for _ in range(100):
        g = (lo + hi) / 2
        p = (d + shift) ** -g
        if (d * p).sum() / p.sum() > mean:
            lo = g
        else:
            hi = g
    return (lo + hi) / 2


def alibaba_fleet_state(scale: float = 1.0, seed: int = 0,
                        demand_fraction: float = 0.25,
                        mean_deps: float = 6.7, max_deps: int = 1000,
                        deps_shift: float = 2.0,
                        callee_zipf: float = 0.9, weight_zipf: float = 1.1,
                        unsafe_fraction: float = 0.08,
                        unsafe_chain_fraction: float = 0.15) -> FleetState:
    """The paper fleet's services with a trace-shaped call graph.

    * out-degree: a power law ``P(d) ~ (d + deps_shift)**-g`` on
      ``1..max_deps`` (capped at the fleet's size), ``g`` solved for the
      mean ``mean_deps``, so most services call a handful of callees and a
      few gateways call hundreds;
    * callee: its tier from the caller's Table 2 row, as in the paper
      fleet; within the tier, popularity is Zipf(``callee_zipf``) over a
      random order of the tier's services, so shared callees gather
      fan-in in the hundreds; a caller calls each callee once;
    * edge weight: each (caller tier, callee tier) cell keeps its Table 2
      volume, split over the cell's edges by Zipf(``weight_zipf``) over a
      random order of all edges, so a few edges carry most of the calls;
    * fail-close: ``unsafe_fraction`` of the tier-inverted (critical ->
      preemptible) edges, as in the paper fleet, and
      ``unsafe_chain_fraction`` of the critical -> critical edges, which
      relay breakage over several hops.

    Tiers, cores, replicas and classes are ``synthesize_fleet_state``'s at
    the same ``scale``, ``seed`` and ``demand_fraction``; the call graph
    is drawn from a stream of its own, fixed by ``seed``."""
    from repro.core.service import _TABLE2   # single source for Table 2
    fs = synthesize_fleet_state(scale=scale, seed=seed,
                                demand_fraction=demand_fraction,
                                with_edges=False)
    rng = np.random.default_rng([seed, 2021])
    n = fs.n
    tier = fs.tier.astype(np.int64)

    cap = int(min(max_deps, n - 1))
    d = np.arange(1, cap + 1)
    p = (d + deps_shift) ** -_power_law_exponent(mean_deps, cap, deps_shift)
    n_deps = rng.choice(d, size=n, p=p / p.sum())
    src = np.repeat(np.arange(n, dtype=np.int64), n_deps)

    # callee tier ~ the caller tier's Table 2 row
    row_cdf = np.cumsum(np.asarray([_TABLE2[t] for t in _T], np.float64),
                        axis=1)
    row_cdf /= row_cdf[:, -1:]
    u = rng.random(len(src))
    callee_tier = np.minimum(
        (u[:, None] > row_cdf[tier[src]]).sum(axis=1), len(_T) - 1)
    # callee within its tier: Zipf popularity over a random order
    dst = np.empty(len(src), np.int64)
    for t in range(len(_T)):
        members = rng.permutation(np.flatnonzero(tier == t))
        pick = callee_tier == t
        pop = np.arange(1, len(members) + 1, dtype=np.float64) ** -callee_zipf
        dst[pick] = members[rng.choice(len(members), size=int(pick.sum()),
                                       p=pop / pop.sum())]
    # one edge per (caller, callee), no self-calls; sorted by caller
    keep = src != dst
    key = np.unique(src[keep] * n + dst[keep])
    src, dst = key // n, key % n

    critical = fs.fclass <= AM
    inverted = critical[src] & (fs.fclass[dst] >= RL)
    chain = critical[src] & critical[dst]
    draw = rng.random(len(src))
    fail_close = ((inverted & (draw < unsafe_fraction))
                  | (chain & (draw < unsafe_chain_fraction)))

    n_tiers = len(_T)
    cell = tier[src] * n_tiers + tier[dst]
    share = rng.permutation(len(src)).astype(np.float64) + 1.0
    share **= -weight_zipf
    cell_share = np.bincount(cell, share, minlength=n_tiers * n_tiers)
    volume = np.asarray([_TABLE2[t] for t in _T], np.float64).ravel()
    weight = volume[cell] * share / cell_share[cell]

    fs.edges = EdgeArrays(src=src.astype(np.int32), dst=dst.astype(np.int32),
                          fail_open=~fail_close,
                          weight=weight.astype(np.float32))
    return fs
