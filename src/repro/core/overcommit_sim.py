"""Vectorized (JAX) Monte-Carlo overcommit simulator (paper §4.4).

The paper: "We built a simulator that models cluster configurations and
workloads, which recommended a 1.5x overcommit factor."  This is that
simulator, jax.vmap'd over candidate factors x trials x hosts:

  - each host packs critical pods to ~stateless fill plus preemptible pods
    filling (factor-1) x capacity of extended resource;
  - per-pod demand is a correlated diurnal level + lognormal noise;
  - a factor is SAFE if P(host busy > evict threshold) stays under a target
    violation rate (QoS evictions are disruptive, so they must stay rare).

The recommendation is the largest safe factor on the grid, additionally
clamped by the analytic O_max memory bound (= 1.66x with paper constants).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.tiers import QOS_EVICT_UTILIZATION, o_max


@dataclasses.dataclass(frozen=True)
class OvercommitSimConfig:
    n_hosts: int = 512
    n_trials: int = 64
    host_cores: float = 100.0
    critical_fill: float = 0.45       # fraction of physical cores requested
    critical_demand_mean: float = 0.40  # demand per requested core
    preempt_demand_mean: float = 0.40
    demand_sigma: float = 0.38        # lognormal sigma
    diurnal_amp: float = 0.30         # correlated load swing
    evict_threshold: float = QOS_EVICT_UTILIZATION
    max_violation_rate: float = 0.02  # hosts-over-threshold budget
    seed: int = 0


def _host_busy(key, cfg: OvercommitSimConfig, factor: jnp.ndarray):
    """Busy-core fraction for (n_trials, n_hosts) hosts at one factor."""
    k1, k2, k3 = jax.random.split(key, 3)
    shape = (cfg.n_trials, cfg.n_hosts)
    # correlated diurnal phase per trial (cluster-wide load level)
    phase = jax.random.uniform(k1, (cfg.n_trials, 1)) * 2 * jnp.pi
    diurnal = 1.0 + cfg.diurnal_amp * jnp.sin(phase)
    ln = lambda k: jnp.exp(cfg.demand_sigma * jax.random.normal(k, shape)
                           - 0.5 * cfg.demand_sigma ** 2)
    crit_req = cfg.critical_fill * cfg.host_cores
    pre_req = (factor - 1.0) * cfg.host_cores
    crit_busy = crit_req * cfg.critical_demand_mean * ln(k2) * diurnal
    pre_busy = pre_req * cfg.preempt_demand_mean * ln(k3) * diurnal
    return (crit_busy + pre_busy) / cfg.host_cores


def violation_rate(cfg: OvercommitSimConfig, factor: float) -> float:
    key = jax.random.PRNGKey(cfg.seed)
    busy = _host_busy(key, cfg, jnp.asarray(factor))
    return float(jnp.mean(busy > cfg.evict_threshold))


@partial(jax.jit, static_argnames=("cfg",))
def _grid_violation_rates(cfg: OvercommitSimConfig,
                          factors: jnp.ndarray) -> jnp.ndarray:
    """Per-factor violation rates: the whole factors x trials x hosts
    Monte-Carlo grid is one jitted vmap (the frozen config is a static
    argument, so each config compiles once and re-runs in microseconds)."""
    key = jax.random.PRNGKey(cfg.seed)

    def rate(f):
        busy = _host_busy(key, cfg, f)
        return jnp.mean(busy > cfg.evict_threshold)

    return jax.vmap(rate)(factors)


def factor_grid(grid_lo: float, grid_hi: float,
                grid_step: float) -> np.ndarray:
    """Candidate-factor grid with an exact endpoint: ``np.arange(lo,
    hi + 1e-9, step)`` accumulates float error and drops ``hi`` for many
    (lo, hi, step) triples (e.g. 1.0..1.3 by 0.1 ends at 1.2000000000000002
    > 1.3 + 1e-9's predecessor) — rounding a ``linspace`` over the rounded
    step count keeps every factor and the endpoint exact."""
    n = max(0, int(round((grid_hi - grid_lo) / grid_step)))
    return np.round(np.linspace(grid_lo, grid_lo + n * grid_step, n + 1), 9)


def recommend_factor(cfg: OvercommitSimConfig = OvercommitSimConfig(),
                     grid_lo: float = 1.0, grid_hi: float = 2.0,
                     grid_step: float = 0.05) -> Dict[str, object]:
    """Sweep the factor grid (one jitted vmap) and pick the largest safe
    factor, clamped by O_max — an argmax over the safe mask, no host loop.

    The result carries an explicit ``safe`` flag: when NO factor on the
    grid clears the violation budget and the O_max bound, ``recommended``
    falls back to ``grid_lo`` *without* implying it is safe — callers
    (the capacity planner example) must check ``safe`` before acting on
    the recommendation."""
    factors = factor_grid(grid_lo, grid_hi, grid_step)
    rates = np.asarray(_grid_violation_rates(cfg, jnp.asarray(factors)))
    omax = o_max()
    valid = (rates <= cfg.max_violation_rate) & (factors <= omax)
    safe = bool(valid.any())
    # grid is ascending: the argmax over the reversed mask is the largest
    # safe factor
    best = (float(factors[len(valid) - 1 - int(np.argmax(valid[::-1]))])
            if safe else grid_lo)
    return {
        "factors": [round(float(f), 3) for f in factors],
        "violation_rates": [float(r) for r in rates],
        "o_max": omax,
        "safe": safe,
        "recommended": round(best, 3),
    }
