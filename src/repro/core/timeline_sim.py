"""Array-native discrete-time failover timeline simulator.

The paper's headline claims are *temporal* — full-peak failovers preempt
Restore-Later services and restore them under differentiated SLAs while
the fleet sustains 99.97% availability — but the vmapped sweep in
``scenarios.py`` scores each scenario with a closed-form outcome, and the
event-driven ``omg.Orchestrator`` produces a real timeline only one
scenario at a time.  This module closes that gap: a ``jax.lax.scan`` over
time steps evolves the per-tier live cores, placed-pool accounting,
burst-conversion ramp, Always-On upscale, Active-Migrate migration waves,
Restore-Later eviction and the delayed cloud restore (honoring
``CloudPool.provision_time`` semantics: a cloud batch activates only after
``grant / provision_rate`` seconds), emitting availability / utilization /
SLA traces per step.  ``vmap`` over the existing ``scenario_grid`` runs
thousands of temporal drills per second — scenario diversity the scalar
orchestrator cannot reach (Basiri et al.: dependability claims must be
validated by executing failure timelines against an SLA model).

Equivalence contract (pinned by ``tests/test_timeline_sim.py``):

  * the kernel's per-step traces match the scalar reference stepper in
    ``tests/scalar_reference.py`` (same spec, independent Python-loop
    implementation) to float32 precision, env counts and verdicts exactly;
  * on a config extracted from an ``Orchestrator`` (via
    ``Orchestrator.timeline_config()``) the traces match the
    orchestrator's ``Timeline`` snapshots at the snapshot times, for
    fleets where the aggregate view is exact (single migration/restore
    waves, no pool overflow) — which covers every small-fleet test mix.

Aggregation semantics (documented deviations from the event loop):

  * multi-wave migrations/restores move ``total / n_waves`` cores per
    wave (the orchestrator first-fits concrete SEs in array order);
  * all cloud spill is treated as one provisioning batch that activates
    at ``first_spill_wave + grant / rate`` (the orchestrator provisions
    per wave; exact when the spill is confined to one wave);
  * a cloud-quota shortfall leaves the remainder down for the whole
    horizon (``rl_done_s = inf``) — the seed orchestrator stops retrying
    but still stamps a completion time.

All time comparisons use a ``EPS_T`` = 1e-3 s tolerance so float32 event
arithmetic cannot miss a boundary the float64 event loop hits exactly.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.fleet_state import (AM, AO, POOL_OVERCOMMIT, POOL_STATELESS,
                                    RL, TM)
from repro.core.tiers import (QOS_EVICT_UTILIZATION, RTO_SECONDS,
                              FailureClass, Tier)

EPS_T = 1e-3                    # time-comparison tolerance (seconds)
N_TIERS = len(Tier)
N_CLASSES = 4
BASE_AVAILABILITY = 0.9997      # ambient (paper Fig 8)
AVAIL_SLA_TOL = 5e-5            # integral may dip this far below ambient
RESTORE_THRESH = 0.999          # tier counts as restored above this frac

_DEMAND_CRIT = 0.62             # demand per live core, critical classes
_DEMAND_PRE = 0.35              # demand per live core, preemptible classes

# ---------------------------------------------------------------------------
# Soft relaxation (opt-in): sigmoid-smoothed SLA indicators
# ---------------------------------------------------------------------------
#
# The capacity optimizer (repro.optim.capacity) differentiates through
# the fused pipeline, but the SLA verdicts are hard booleans (step
# functions — zero gradient).  Passing a temperature ``tau`` to
# ``timeline_verdicts`` / ``scenario_outcome`` replaces every hard
# comparison with a sigmoid of the *signed margin*, in units of a
# per-quantity scale times tau, so the verdicts become floats in (0, 1)
# that tend to the exact booleans as tau -> 0 (an annealing schedule
# recovers the hard model; pinned by tests/test_capacity_opt.py).
# ``tau=None`` (the default) traces the ORIGINAL ops — a literal no-op,
# so the bit-exactness contract of the fused engine is untouched.

SOFT_TIME_SCALE = 60.0          # seconds: deadline margins
SOFT_FRAC_SCALE = 0.02          # utilization / fraction margins
SOFT_AVAIL_SCALE = 2.0e-5       # availability-integral margins
SOFT_CORES_FRAC = 0.01          # cores margins, as a fraction of fleet total
SOFT_DEP_SCALE = 1e-6           # broken-critical fractions (quantized at
                                # 1/n_crit, so the pass threshold sits at
                                # 1e-7 — below one broken service)


def soft_ge(x, y, scale, tau):
    """Soft indicator of ``x >= y``: sigmoid of the margin in units of
    ``scale * tau``.  Tends to the hard boolean as ``tau -> 0`` (the
    razor's-edge case ``x == y`` saturates to 0.5 instead of True —
    measure zero for the continuous margins this is applied to)."""
    return jax.nn.sigmoid((x - y) / (scale * tau))


def _cores_scale(c: Dict):
    """Cores-margin scale for one fleet: 1% of the class total."""
    return SOFT_CORES_FRAC * (c["ao"] + c["am"] + c["rl"] + c["tm"])


# ---------------------------------------------------------------------------
# Config extraction — the scan kernel and the Orchestrator consume
# identical inputs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TimelineConfig:
    """Aggregate fleet/region state the timeline kernel simulates over.

    Produced by ``extract_timeline_config`` from a steady-state
    ``Orchestrator`` (post-placement), so pool occupancy — including the
    overcommit-spill split the orchestrator tracks per SE — is identical
    between the event loop and the scan kernel."""
    # class aggregates (spec cores; live == spec in steady state)
    ao_cores: float
    am_cores: float
    rl_cores: float
    tm_cores: float
    am_envs: float
    rl_envs: float
    tm_envs: float
    # (n_tiers, n_classes) spec cores — per-tier live-core traces
    tier_class_cores: np.ndarray
    # steady pools, post-placement
    stateless_cap: float
    overcommit_cap: float
    steady_used0: float
    overcommit_used0: float
    oc_preempt_cores: float     # preemptible cores accounted in overcommit
    sl_preempt_cores: float     # preemptible overflow spilled to stateless
    am_stateless_cores: float   # AM cores accounted in the stateless pool
    # batch -> burst conversion
    burst_cap_full: float
    spawn_rate: float           # cores/s once conversion starts
    # cloud (§4.6)
    cloud_quota: float
    cloud_rate: float
    phys_cores: float
    # orchestrator tunables (single-sourced from Orchestrator at extract)
    kill_s: float = 5.0
    preheat_s: float = 270.0
    mbb_wave_s: float = 45.0
    mbb_parallelism: float = 2000.0
    rl_wave_s: float = 120.0
    rl_rto_s: float = float(RTO_SECONDS[FailureClass.RESTORE_LATER])

    def tier_totals(self) -> np.ndarray:
        """Per-tier spec cores summed over failure classes — the
        denominator turning the kernel's ``tier_live`` traces into live
        fractions (``serving.failover`` actuates replicas from these)."""
        return np.asarray(self.tier_class_cores, np.float64).sum(axis=1)

    def as_consts(self) -> Dict[str, jnp.ndarray]:
        """float32 device constants for the jitted kernel."""
        f = lambda v: jnp.asarray(v, jnp.float32)
        return {
            "ao": f(self.ao_cores), "am": f(self.am_cores),
            "rl": f(self.rl_cores), "tm": f(self.tm_cores),
            "am_envs": f(self.am_envs), "rl_envs": f(self.rl_envs),
            "tm_envs": f(self.tm_envs),
            "tier_class": f(self.tier_class_cores),
            "stateless_cap": f(self.stateless_cap),
            "overcommit_cap": f(self.overcommit_cap),
            "steady_used0": f(self.steady_used0),
            "overcommit_used0": f(self.overcommit_used0),
            "oc_preempt_cores": f(self.oc_preempt_cores),
            "sl_preempt_cores": f(self.sl_preempt_cores),
            "am_stateless_cores": f(self.am_stateless_cores),
            "burst_cap_full": f(self.burst_cap_full),
            "spawn_rate": f(self.spawn_rate),
            "cloud_quota": f(self.cloud_quota),
            "cloud_rate": f(self.cloud_rate),
            "phys_cores": f(self.phys_cores),
            "kill_s": f(self.kill_s), "preheat_s": f(self.preheat_s),
            "mbb_wave_s": f(self.mbb_wave_s),
            "mbb_parallelism": f(self.mbb_parallelism),
            "rl_wave_s": f(self.rl_wave_s), "rl_rto_s": f(self.rl_rto_s),
        }


def extract_timeline_config(orch) -> TimelineConfig:
    """Read a steady-state ``Orchestrator`` into a ``TimelineConfig``.

    Must run before ``failover()``: it captures the post-placement,
    pre-eviction pool occupancy the event loop starts from."""
    fs, region = orch.fs, orch.region
    cores = fs.spec_cores
    cls = [float(cores[fs.fclass == c].sum()) for c in (AO, AM, RL, TM)]
    tier_class = np.zeros((N_TIERS, N_CLASSES), np.float64)
    for t in range(N_TIERS):
        tmask = fs.tier == t
        for c in range(N_CLASSES):
            tier_class[t, c] = float(cores[tmask & (fs.fclass == c)].sum())
    pre = fs.preemptible
    return TimelineConfig(
        ao_cores=cls[0], am_cores=cls[1], rl_cores=cls[2], tm_cores=cls[3],
        am_envs=float(np.count_nonzero(fs.fclass == AM)),
        rl_envs=float(np.count_nonzero(fs.fclass == RL)),
        tm_envs=float(np.count_nonzero(fs.fclass == TM)),
        tier_class_cores=tier_class,
        stateless_cap=float(region.steady.stateless.capacity),
        overcommit_cap=float(region.steady.overcommit.capacity),
        steady_used0=float(region.steady.stateless.used),
        overcommit_used0=float(region.steady.overcommit.used),
        oc_preempt_cores=float(
            cores[pre & (fs.pool == POOL_OVERCOMMIT)].sum()),
        sl_preempt_cores=float(
            cores[pre & (fs.pool == POOL_STATELESS)].sum()),
        am_stateless_cores=float(
            cores[(fs.fclass == AM) & (fs.pool == POOL_STATELESS)].sum()),
        burst_cap_full=float(region.batch.convertible_cores),
        spawn_rate=float(orch.SPAWN_CORES_PER_HOST_S * region.batch.n_hosts),
        cloud_quota=float(region.cloud.quota_cores),
        cloud_rate=float(region.cloud.provision_rate_cores_per_s),
        phys_cores=float(region.steady.physical_cores),
        kill_s=float(orch.KILL_LATENCY_S),
        preheat_s=float(orch.BATCH_EVICT_S + orch.PREFETCH_S),
        mbb_wave_s=float(orch.MBB_WAVE_S),
        mbb_parallelism=float(orch.MBB_PARALLELISM),
        rl_wave_s=float(orch.RL_RESTORE_WAVE_S),
    )


def config_for_fleet(fleet, region=None) -> TimelineConfig:
    """Build a ``TimelineConfig`` for a fleet (dict of ``ServiceSpec`` or a
    ``FleetState``): sizes a fresh region (unless given), performs the
    orchestrator's steady-state placement, extracts.

    Side-effect free for the caller: placement runs against a *copy* of
    the region (pool counters zeroed first, so a region that already had
    an orchestrator placed into it is not double-counted) and a
    ``FleetState``'s ``pool`` column is restored afterwards.  To extract
    from live orchestrator state instead, use
    ``Orchestrator.timeline_config()``."""
    import copy

    from repro.core.capacity import RegionCapacity
    from repro.core.omg import Orchestrator
    if region is None:
        region = RegionCapacity.for_fleet("timeline", fleet)
    else:
        region = copy.deepcopy(region)
        region.steady.stateless.used = 0.0
        region.steady.overcommit.used = 0.0
    pool_save = fleet.pool.copy() if hasattr(fleet, "pool") else None
    try:
        return extract_timeline_config(Orchestrator(fleet, region))
    finally:
        if pool_save is not None:
            fleet.pool[:] = pool_save


# ---------------------------------------------------------------------------
# Scenario parameters
# ---------------------------------------------------------------------------

PARAM_KEYS = ("traffic_mult", "burst_delay_s", "burst_availability",
              "cloud_quota_frac", "overcommit_factor", "evict_fraction",
              "dep_broken_frac",
              # chaos fault families (repro.chaos): partial-region
              # degradation + the cascading dependency-storm schedule.
              # All are exact no-ops at the defaults below, so legacy
              # grids keep bit-identical verdicts.
              "region_degradation", "storm_refrac", "storm_t0_s",
              "storm_period_s", "storm_recover_s", "storm_broken_frac",
              # eviction-order knobs (repro.optim.capacity): per-class
              # shifts of the evicted fraction — RL is evicted at
              # ``evict_fraction + rl_evict_delta``, TM at ``+
              # tm_evict_delta``.  Budget-conserving orders keep
              # rl*d_rl + tm*d_tm == 0 (same total cores evicted, a
              # different mix).  Additive forms, exact no-ops at 0.
              "rl_evict_delta", "tm_evict_delta")


def default_scenario(**overrides) -> Dict[str, float]:
    """The paper's operating point (2x traffic, full burst, full quota).

    The chaos knobs default to "no fault": zero capacity degradation and
    a storm with zero re-darkening amplitude (``storm_refrac``) — the
    finite schedule constants are inert until the amplitude is raised.
    The eviction-order deltas default to 0: pro-rata class eviction."""
    p = {"traffic_mult": 2.0, "burst_delay_s": 270.0,
         "burst_availability": 1.0, "cloud_quota_frac": 1.0,
         "overcommit_factor": 1.5, "evict_fraction": 1.0,
         "dep_broken_frac": 0.0,
         "region_degradation": 0.0, "storm_refrac": 0.0,
         "storm_t0_s": 1800.0, "storm_period_s": 1800.0,
         "storm_recover_s": 600.0, "storm_broken_frac": 0.0,
         "rl_evict_delta": 0.0, "tm_evict_delta": 0.0}
    p.update(overrides)
    return p


def validate_grid(grid) -> int:
    """Validate a scenario grid (dict of parallel axis columns) and
    return the scenario count.

    Raises a labeled ``ValueError`` on the two silent-failure modes that
    used to pass straight through the sweep paths: an *unknown* key (a
    typo like ``trafic_mult`` swept nothing — every real axis fell back
    to its default and the run returned plausible-looking verdicts for
    the wrong ensemble) and an *empty* grid (crashed deep inside the
    engine's bucket padding with an obscure reshape error).  Ragged axis
    lengths are rejected for the same reason."""
    if not grid:
        raise ValueError("empty scenario grid: no axes given (pass at "
                         "least one PARAM_KEYS column, or None for the "
                         "default grid)")
    unknown = sorted(set(grid) - set(PARAM_KEYS))
    if unknown:
        raise ValueError(
            f"unknown scenario grid key(s) {unknown}: a misspelled axis "
            "would silently sweep nothing (defaults would be used "
            f"instead); valid axes: {sorted(PARAM_KEYS)}")
    n = len(next(iter(grid.values())))
    if n == 0:
        raise ValueError("empty scenario grid: zero-length scenario axes")
    ragged = {k: len(v) for k, v in grid.items() if len(v) != n}
    if ragged:
        raise ValueError(f"ragged scenario grid: axis lengths {ragged} "
                         f"differ from {n}")
    return n


def default_ts(horizon_s: float = 7200.0, n_steps: int = 240) -> np.ndarray:
    """Uniform step grid from 0: long enough to see the RL RTO expire."""
    return np.arange(n_steps, dtype=np.float64) * (horizon_s / n_steps)


# ---------------------------------------------------------------------------
# The kernel: schedule arithmetic + per-step state + lax.scan
# ---------------------------------------------------------------------------


def _schedule(c: Dict, p: Dict, tau=None) -> Dict:
    """Scenario-level event times and capacity splits (scalar, traceable).

    ``tau`` (opt-in): soft-relaxation temperature — the hard feasibility
    booleans (``ao_ok``) become sigmoid indicators and the infinite
    ``rl_done_t`` sentinel on a cloud-quota shortfall becomes a smooth
    finite overrun, so gradients flow; ``None`` traces the original
    ops."""
    mult = p["traffic_mult"]
    evict = p["evict_fraction"]

    # partial-region degradation: a fraction of the surviving region's
    # hosts (stateless capacity and physical cores alike) is lost for the
    # whole horizon.  ``x * (1 - 0)`` is exact in float32, so the default
    # is a bitwise no-op.
    cap_scale = 1.0 - p.get("region_degradation", 0.0)
    stateless_eff = c["stateless_cap"] * cap_scale

    burst_cap = c["burst_cap_full"] * p["burst_availability"]
    ramp_total = burst_cap / jnp.maximum(c["spawn_rate"], 1e-9)
    tick_s = ramp_total / 10.0
    burst_full_t = p["burst_delay_s"] + ramp_total

    n_am_waves = jnp.ceil(c["am_envs"] / c["mbb_parallelism"])
    am_done_t = burst_full_t + n_am_waves * c["mbb_wave_s"]
    am_in_burst = jnp.minimum(c["am"], burst_cap)

    ao_need = c["ao"] * (mult - 1.0)
    # steady free once the preemptible spill is evicted and AM released
    am_release_frac = c["am_stateless_cores"] / jnp.maximum(c["am"], 1e-9)
    am_released = am_in_burst * am_release_frac
    free_at_am_done = (stateless_eff
                       - (c["steady_used0"] - evict * c["sl_preempt_cores"]
                          - am_released))
    if tau is None:
        ao_ok = ao_need <= free_at_am_done + 1e-6
    else:
        ao_ok = soft_ge(free_at_am_done + 1e-6, ao_need, _cores_scale(c),
                        tau)
    ao_short = jnp.maximum(0.0, ao_need - free_at_am_done)

    # eviction-order deltas shift the per-class evicted fraction (additive
    # forms: ``x + rl*0.0`` is exact in float32, so default grids keep
    # bit-identical verdicts)
    d_rl = p.get("rl_evict_delta", 0.0)
    rl_need = c["rl"] * evict + c["rl"] * d_rl
    rl_envs_evicted = c["rl_envs"] * evict + c["rl_envs"] * d_rl
    n_rl_waves = jnp.maximum(
        1.0, jnp.ceil(rl_envs_evicted / c["mbb_parallelism"]))
    rl_last_wave_t = burst_full_t + n_rl_waves * c["rl_wave_s"]
    burst_free_rl = jnp.maximum(0.0, burst_cap - am_in_burst)
    quota_eff = c["cloud_quota"] * p["cloud_quota_frac"]
    total_cloud = jnp.minimum(
        jnp.maximum(0.0, rl_need - burst_free_rl), quota_eff)
    per_wave = rl_need / n_rl_waves
    k_star = jnp.minimum(
        jnp.floor(burst_free_rl / jnp.maximum(per_wave, 1e-9)) + 1.0,
        n_rl_waves)
    cloud_start_t = burst_full_t + k_star * c["rl_wave_s"]
    cloud_arrival_t = cloud_start_t + total_cloud / jnp.maximum(
        c["cloud_rate"], 1e-9)
    rl_shortfall = jnp.maximum(0.0, rl_need - burst_free_rl - quota_eff)
    rl_ok_soft = None
    if tau is None:
        rl_done_t = jnp.where(
            rl_shortfall > 1e-6, jnp.inf,
            jnp.maximum(rl_last_wave_t,
                        jnp.where(total_cloud > 1e-6, cloud_arrival_t, 0.0)))
    else:
        # smooth relaxation of the infinite-shortfall sentinel: the
        # beyond-quota remainder provisions at the same cloud rate (a
        # finite, monotone overrun past the RTO), and the "any cloud at
        # all" gate softens over ~1 core
        cloud_gate = soft_ge(total_cloud, 0.5, 0.25, tau)
        rl_done_t = (jnp.maximum(rl_last_wave_t,
                                 cloud_gate * cloud_arrival_t)
                     + rl_shortfall / jnp.maximum(c["cloud_rate"], 1e-2))
        # the signed no-shortfall margin (the hard verdict gates on
        # rl_shortfall > 1e-6, whose one-sided max(0, .) has no sign to
        # smooth) — _finalize folds this into rl_rto_met
        rl_ok_soft = soft_ge(0.0, rl_need - burst_free_rl - quota_eff,
                             _cores_scale(c), tau)

    return {"burst_cap": burst_cap, "tick_s": tick_s,
            "rl_ok_soft": rl_ok_soft,
            "cap_scale": cap_scale, "stateless_eff": stateless_eff,
            "storm_refrac": p.get("storm_refrac", 0.0),
            "storm_t0": p.get("storm_t0_s", 1800.0),
            "storm_period": p.get("storm_period_s", 1800.0),
            "storm_recover": p.get("storm_recover_s", 600.0),
            "burst_full_t": burst_full_t,
            "n_am_waves": n_am_waves, "am_done_t": am_done_t,
            "am_in_burst": am_in_burst,
            "am_release_frac": am_release_frac,
            "ao_need": ao_need, "ao_ok": ao_ok, "ao_short": ao_short,
            "rl_need": rl_need, "rl_envs_evicted": rl_envs_evicted,
            "n_rl_waves": n_rl_waves, "rl_last_wave_t": rl_last_wave_t,
            "burst_free_rl": burst_free_rl, "quota_eff": quota_eff,
            "total_cloud": total_cloud, "cloud_start_t": cloud_start_t,
            "cloud_arrival_t": cloud_arrival_t,
            "rl_shortfall": rl_shortfall, "rl_done_t": rl_done_t}


def _storm_darkness(s: Dict, t):
    """Cascading-storm re-darkening envelope at time ``t``: from
    ``storm_t0`` on, a pulse of amplitude ``storm_refrac`` fires every
    ``storm_period`` seconds and linearly re-restores over
    ``storm_recover`` seconds — a sawtooth dark mask that re-darkens
    already-restored capacity mid-timeline (seed failures cascading
    back).  Identically 0.0 when ``storm_refrac`` is 0 (every factor is
    finite, so no 0*inf hazard), which keeps default scenarios bitwise
    unchanged."""
    k = jnp.clip(jnp.floor((t - s["storm_t0"] + EPS_T)
                           / jnp.maximum(s["storm_period"], 1e-9)),
                 0.0, 1e6)
    since = t - s["storm_t0"] - k * s["storm_period"]
    env = jnp.clip(1.0 - since / jnp.maximum(s["storm_recover"], 1e-9),
                   0.0, 1.0)
    gate = jnp.where(t >= s["storm_t0"] - EPS_T, 1.0, 0.0)
    return s["storm_refrac"] * env * gate


def _instant_core(c: Dict, p: Dict, s: Dict, t, tau=None) -> Dict:
    """Per-step series the scan *carry* consumes (availability, the
    demand-model utilization, the cloud draw, per-tier live cores) plus
    the intermediates the trace-only extras derive from.  This is the
    summary-only hot path — ``timeline_verdicts`` scans exactly this, the
    trace path layers ``_instant`` on top, so summary outputs are the
    same ops (hence bit-identical) in both.  ``tau`` softens the
    knob-dependent time gates and the QoS penalty step (see
    ``_schedule``); ``None`` traces the original ops."""
    mult = p["traffic_mult"]
    evicted = (t >= c["kill_s"] - EPS_T)
    e = jnp.where(evicted, p["evict_fraction"], 0.0)
    # per-class eviction-order shifts, gated like ``e`` (zero before the
    # kill): additive forms keep default grids bit-identical
    d_rl_t = jnp.where(evicted, p.get("rl_evict_delta", 0.0), 0.0)
    d_tm_t = jnp.where(evicted, p.get("tm_evict_delta", 0.0), 0.0)

    # Active-Migrate MBB waves into burst
    am_waves_done = jnp.clip(
        jnp.floor((t - s["burst_full_t"] + EPS_T) / c["mbb_wave_s"]),
        0.0, s["n_am_waves"])
    am_envs_moved = jnp.minimum(c["am_envs"],
                                c["mbb_parallelism"] * am_waves_done)
    am_attempt = c["am"] * am_envs_moved / jnp.maximum(c["am_envs"], 1.0)
    am_moved = jnp.minimum(am_attempt, s["burst_cap"])

    # Always-On in-place upscale at migration completion
    if tau is None:
        ao_scaled = s["ao_ok"] & (t >= s["am_done_t"] - EPS_T)
        ao_live = c["ao"] * jnp.where(ao_scaled, mult, 1.0)
    else:
        ao_scaled = s["ao_ok"] * soft_ge(t, s["am_done_t"] - EPS_T,
                                         SOFT_TIME_SCALE, tau)
        ao_live = c["ao"] * (1.0 + ao_scaled * (mult - 1.0))

    # Restore-Later waves: burst first, the cloud batch after provisioning
    rl_waves_done = jnp.clip(
        jnp.floor((t - s["burst_full_t"] + EPS_T) / c["rl_wave_s"]),
        0.0, s["n_rl_waves"])
    processed = s["rl_need"] * rl_waves_done / s["n_rl_waves"]
    rl_burst = jnp.minimum(processed, s["burst_free_rl"])
    cloud_req = processed - rl_burst
    cloud_prov = jnp.minimum(cloud_req, s["quota_eff"])
    if tau is None:
        cloud_arrived = jnp.where(t >= s["cloud_arrival_t"] - EPS_T,
                                  s["total_cloud"], 0.0)
    else:
        cloud_arrived = s["total_cloud"] * soft_ge(
            t, s["cloud_arrival_t"] - EPS_T, SOFT_TIME_SCALE, tau)
    cloud_live = jnp.minimum(cloud_arrived, cloud_prov)
    # the cascade storm re-darkens a fraction of whatever has been
    # restored so far (burst conversions and cloud grants alike) — the
    # time-varying dark mask of a dependency storm, not a new eviction
    storm_dark = _storm_darkness(s, t)
    rl_restored = (rl_burst + cloud_live) * (1.0 - storm_dark)
    rl_live = c["rl"] - (e + d_rl_t) * c["rl"] + rl_restored
    tm_live = c["tm"] * (1.0 - e - d_tm_t)

    # demand-model utilization (drives the SLA verdict / QoS penalty):
    # Always-On busy is constant — the upscale spreads 2x demand over 2x
    # cores — while unmigrated AM absorbs the multiplier on 1x cores
    am_steady_cores = c["am"] - am_moved
    pre_steady = ((c["rl"] + c["tm"]) * (1.0 - e)
                  - (c["rl"] * d_rl_t + c["tm"] * d_tm_t))
    busy_model = (c["ao"] * _DEMAND_CRIT * mult
                  + am_steady_cores * _DEMAND_CRIT * mult
                  + pre_steady * _DEMAND_PRE)
    util_model = jnp.minimum(
        1.0, busy_model / jnp.maximum(s["stateless_eff"], 1.0))

    # availability: AO shortfall bites from the eviction, overdue RL after
    # the RTO expires, broken criticals (propagation verdict) while their
    # dark dependencies stay dark, QoS stress while the model runs hot
    crit = jnp.maximum(c["ao"] + c["am"], 1.0)
    rl_down = c["rl"] - rl_live
    tm_down = c["tm"] - tm_live
    ao_pen = jnp.where(evicted, 0.5 * s["ao_short"] / crit, 0.0)
    overdue = jnp.where(t > c["rl_rto_s"] + EPS_T, 1.0, 0.0)
    rl_pen = 0.1 * rl_down / jnp.maximum(c["rl"], 1.0) * overdue
    dark_tot = jnp.maximum(
        s["rl_need"] + (p["evict_fraction"]
                        + p.get("tm_evict_delta", 0.0)) * c["tm"], 1e-9)
    dark_frac = (rl_down + tm_down) / dark_tot
    dep_pen = 0.5 * p["dep_broken_frac"] * dark_frac
    if tau is None:
        util_pen = jnp.where(util_model > QOS_EVICT_UTILIZATION, 1e-4, 0.0)
    else:
        util_pen = 1e-4 * soft_ge(util_model, QOS_EVICT_UTILIZATION,
                                  SOFT_FRAC_SCALE, tau)
    # criticals the STORM's dark set breaks (its own propagation verdict)
    # are down exactly while the storm mask holds capacity dark
    storm_pen = 0.5 * p.get("storm_broken_frac", 0.0) * storm_dark
    availability = jnp.clip(
        BASE_AVAILABILITY - ao_pen - rl_pen - dep_pen - util_pen
        - storm_pen, 0.0, 1.0)

    # per-tier live cores: class live-fraction applied to the tier x class
    # core composition
    class_live = jnp.stack([ao_live, c["am"], rl_live, tm_live])
    class_total = jnp.stack([c["ao"], c["am"], c["rl"], c["tm"]])
    frac = class_live / jnp.maximum(class_total, 1e-9)
    tier_live = (c["tier_class"] * frac[None, :]).sum(axis=1)

    return {"e": e, "evicted": evicted, "am_envs_moved": am_envs_moved,
            "am_moved": am_moved, "ao_scaled": ao_scaled,
            "ao_live": ao_live, "rl_restored": rl_restored,
            "rl_burst": rl_burst, "rl_live": rl_live, "tm_live": tm_live,
            "am_steady_cores": am_steady_cores,
            "cloud_used": cloud_prov, "util_model": util_model,
            "availability": availability, "tier_live": tier_live}


def _instant(c: Dict, p: Dict, s: Dict, t) -> Dict:
    """All per-step series at time ``t`` (pure function of the schedule —
    the scan carry layers accumulators/first-crossings on top): the
    summary core plus the trace-only extras (pool accounting, env counts,
    the conversion ramp, physical utilization)."""
    k = _instant_core(c, p, s, t)
    mult = p["traffic_mult"]
    e = k["e"]

    # burst conversion ramp (10 spawner ticks, orchestrator semantics)
    ticks = jnp.clip(jnp.floor((t - p["burst_delay_s"] + EPS_T)
                               / jnp.maximum(s["tick_s"], 1e-9)), 0.0, 10.0)
    burst_online = s["burst_cap"] * ticks / 10.0
    burst_capacity = jnp.where(t >= p["burst_delay_s"] - EPS_T,
                               s["burst_cap"], 0.0)

    ao_extra = jnp.where(k["ao_scaled"], s["ao_need"], 0.0)

    # placed-pool accounting
    steady_used = (c["steady_used0"] - e * c["sl_preempt_cores"]
                   - k["am_moved"] * s["am_release_frac"] + ao_extra)
    overcommit_used = c["overcommit_used0"] - e * c["oc_preempt_cores"]
    burst_used = k["am_moved"] + k["rl_burst"]

    # env-count series (orchestrator snapshot names); the eviction-order
    # deltas shift the per-class counts (additive, exact no-ops at 0)
    d_rl_t = jnp.where(k["evicted"], p.get("rl_evict_delta", 0.0), 0.0)
    d_tm_t = jnp.where(k["evicted"], p.get("tm_evict_delta", 0.0), 0.0)
    am_bursted = k["am_envs_moved"]
    am_steady = c["am_envs"] - am_bursted
    rl_bursted = jnp.round(s["rl_envs_evicted"] * k["rl_restored"]
                           / jnp.maximum(s["rl_need"], 1e-9))
    rl_not_bursted = jnp.round((e + d_rl_t) * c["rl_envs"]) - rl_bursted
    rl_t_steady = jnp.round((1.0 - e) * (c["rl_envs"] + c["tm_envs"])
                            - (d_rl_t * c["rl_envs"]
                               + d_tm_t * c["tm_envs"]))
    terminated = jnp.round((e + d_tm_t) * c["tm_envs"])

    # utilization, orchestrator-mirror (traffic multiplier on survivors)
    pre_steady = ((c["rl"] + c["tm"]) * (1.0 - e)
                  - (c["rl"] * d_rl_t + c["tm"] * d_tm_t))
    busy = (k["ao_live"] * _DEMAND_CRIT * mult
            + k["am_steady_cores"] * _DEMAND_CRIT * mult
            + pre_steady * _DEMAND_PRE)
    utilization = jnp.minimum(
        1.0, busy / jnp.maximum(c["phys_cores"] * s["cap_scale"], 1.0))

    return {"steady_used": steady_used, "overcommit_used": overcommit_used,
            "burst_capacity": burst_capacity, "burst_online": burst_online,
            "burst_used": burst_used, "cloud_used": k["cloud_used"],
            "ao_live": k["ao_live"], "am_live": c["am"] + 0.0 * t,
            "rl_live": k["rl_live"], "tm_live": k["tm_live"],
            "am_steady": am_steady, "am_bursted": am_bursted,
            "rl_bursted": rl_bursted, "rl_not_bursted": rl_not_bursted,
            "rl_t_steady": rl_t_steady, "terminated": terminated,
            "utilization": utilization, "util_model": k["util_model"],
            "availability": k["availability"],
            "tier_live": k["tier_live"]}


def _carry0(ts) -> Dict:
    """Initial scan carry — every leaf pinned to a strong float32/bool so
    no Python-scalar weak type (or x64-mode float64) leaks into the scan
    carry (regression-tested by ``tests/test_sweep_engine.py``)."""
    f32 = jnp.float32
    return {
        "prev_t": jnp.asarray(ts[0], f32),
        "avail_int": jnp.asarray(0.0, f32),
        "avail_min": jnp.asarray(1.0, f32),
        "util_peak": jnp.asarray(0.0, f32),
        "cloud_peak": jnp.asarray(0.0, f32),
        "below_seen": jnp.zeros(N_TIERS, bool),
        "restore_t": jnp.full(N_TIERS, jnp.inf, f32),
    }


def _carry_step(carry: Dict, core: Dict, t, tier_total) -> Dict:
    """Fold one step's core series into the running accumulators /
    first-crossing trackers (shared by the trace and summary-only scans)."""
    dt = jnp.maximum(t - carry["prev_t"], 0.0)
    frac = core["tier_live"] / tier_total
    below = frac < RESTORE_THRESH
    below_seen = carry["below_seen"] | below
    restore_t = jnp.where(
        below_seen & ~below & jnp.isinf(carry["restore_t"]),
        t, carry["restore_t"])
    return {
        "prev_t": jnp.asarray(t, jnp.float32),
        "avail_int": carry["avail_int"] + core["availability"] * dt,
        "avail_min": jnp.minimum(carry["avail_min"],
                                 core["availability"]),
        "util_peak": jnp.maximum(carry["util_peak"],
                                 core["util_model"]),
        "cloud_peak": jnp.maximum(carry["cloud_peak"],
                                  core["cloud_used"]),
        "below_seen": below_seen, "restore_t": restore_t,
    }


def _finalize(c: Dict, p: Dict, s: Dict, carry: Dict, ts, tau=None) -> Dict:
    """Per-scenario summary/verdicts from the final carry (shared by the
    trace and summary-only paths — identical ops, identical bits).
    ``tau`` replaces the hard verdicts with sigmoid margins and the
    boolean AND with a product of indicators (see the soft-relaxation
    block at the top of the module); ``None`` traces the original ops."""
    span = jnp.maximum(ts[-1] - ts[0], 1e-9)
    availability_mean = carry["avail_int"] / span
    time_to_restore = jnp.where(carry["below_seen"], carry["restore_t"], 0.0)
    oc_cap_s = s["stateless_eff"] * (p["overcommit_factor"] - 1.0)
    preempt_resident = ((c["rl"] + c["tm"]) * (1.0 - p["evict_fraction"])
                        - (c["rl"] * p.get("rl_evict_delta", 0.0)
                           + c["tm"] * p.get("tm_evict_delta", 0.0)))
    # the SLA verdict scores the post-migration steady point (stranded AM
    # only), like the analytic model: the pre-migration transient — 2x
    # traffic on Active-Migrate before burst absorbs it — stays visible in
    # the trace and in util_peak, but is not an SLA breach by itself
    am_stranded = c["am"] - s["am_in_burst"]
    busy_post = (c["ao"] * _DEMAND_CRIT * p["traffic_mult"]
                 + am_stranded * _DEMAND_CRIT * p["traffic_mult"]
                 + preempt_resident * _DEMAND_PRE)
    util_post = jnp.minimum(
        1.0, busy_post / jnp.maximum(s["stateless_eff"], 1.0))
    if tau is None:
        preempt_fit = preempt_resident <= oc_cap_s + 1e-6
        dep_ok = p["dep_broken_frac"] <= 0.0
        avail_ok = availability_mean >= BASE_AVAILABILITY - AVAIL_SLA_TOL
        util_ok = util_post <= QOS_EVICT_UTILIZATION
        rl_rto_met = s["rl_done_t"] <= c["rl_rto_s"] + EPS_T
        sla_ok = (s["ao_ok"] & rl_rto_met & preempt_fit & dep_ok & avail_ok
                  & util_ok & (s["am_done_t"] <= 30.0 * 60.0)
                  & (s["burst_full_t"] <= 20.0 * 60.0))
    else:
        cs = _cores_scale(c)
        preempt_fit = soft_ge(oc_cap_s + 1e-6, preempt_resident, cs, tau)
        dep_ok = soft_ge(1e-7, p["dep_broken_frac"], SOFT_DEP_SCALE, tau)
        avail_ok = soft_ge(availability_mean,
                           BASE_AVAILABILITY - AVAIL_SLA_TOL,
                           SOFT_AVAIL_SCALE, tau)
        util_ok = soft_ge(QOS_EVICT_UTILIZATION, util_post,
                          SOFT_FRAC_SCALE, tau)
        rl_rto_met = (soft_ge(c["rl_rto_s"] + EPS_T, s["rl_done_t"],
                              SOFT_TIME_SCALE, tau) * s["rl_ok_soft"])
        sla_ok = (s["ao_ok"] * rl_rto_met * preempt_fit * dep_ok
                  * avail_ok * util_ok
                  * soft_ge(30.0 * 60.0, s["am_done_t"],
                            SOFT_TIME_SCALE, tau)
                  * soft_ge(20.0 * 60.0, s["burst_full_t"],
                            SOFT_TIME_SCALE, tau))
    summary = {
        "burst_full_s": s["burst_full_t"], "am_done_s": s["am_done_t"],
        "rl_done_s": s["rl_done_t"], "rl_rto_met": rl_rto_met,
        "ao_ok": s["ao_ok"], "ao_short_cores": s["ao_short"],
        "rl_shortfall_cores": s["rl_shortfall"],
        "cloud_grant_cores": s["total_cloud"],
        "cloud_arrival_s": s["cloud_arrival_t"],
        "peak_cloud_cores": carry["cloud_peak"],
        "availability_mean": availability_mean,
        "availability_min": carry["avail_min"],
        "util_peak": carry["util_peak"], "util_post": util_post,
        "time_to_restore_s": time_to_restore,
        "preempt_fit": preempt_fit, "dep_ok": dep_ok,
        "avail_ok": avail_ok, "util_ok": util_ok, "sla_ok": sla_ok,
    }
    return summary


def timeline_verdicts_batch(c: Dict, p: Dict, ts: jnp.ndarray, *,
                            interpret=None) -> Dict:
    """Summary verdicts for a BATCH of scenarios (every param leaf
    ``(S,)``) with the scan carry replaced by the segmented Pallas
    verdict-reduction kernel (``repro.kernels.ufa.reduce``): the
    schedule/instant ops are the identical ``_schedule``/``_instant_core``
    functions vmapped over (scenario, step), so the per-step series are
    bit-identical to the scan path — but the T sequential carry steps
    become one blocked reduction over the whole (S, T) slab.  Min/max and
    first-crossing outputs are exact vs ``timeline_verdicts``; the
    availability integral is a reordered float32 sum (float32-tight, not
    bitwise), which is why the sweep engine selects this path per backend
    (``reducer="pallas"``) rather than by default."""
    from repro.kernels.ufa.reduce import timeline_reduce

    def series_one(q):
        sch = _schedule(c, q)
        core = jax.vmap(lambda t: _instant_core(c, q, sch, t))(ts)
        return sch, core

    s, core = jax.vmap(series_one)(p)
    tier_total = jnp.maximum(c["tier_class"].sum(axis=1), 1e-9)
    carry = timeline_reduce(
        core["availability"], core["util_model"], core["cloud_used"],
        core["tier_live"] / tier_total, ts,
        thresh=RESTORE_THRESH, interpret=interpret)
    return jax.vmap(lambda q, sch, cr: _finalize(c, q, sch, cr, ts))(
        p, s, carry)


def _simulate(c: Dict, p: Dict, ts: jnp.ndarray) -> Tuple[Dict, Dict]:
    """One scenario: scan the step function over ``ts``; returns
    (per-step traces, per-scenario summary/verdicts)."""
    s = _schedule(c, p)
    tier_total = jnp.maximum(c["tier_class"].sum(axis=1), 1e-9)

    def body(carry, t):
        out = _instant(c, p, s, t)      # superset of the core series
        return _carry_step(carry, out, t, tier_total), out

    carry, traces = jax.lax.scan(body, _carry0(ts), ts)
    return traces, _finalize(c, p, s, carry, ts)


def timeline_verdicts(c: Dict, p: Dict, ts: jnp.ndarray, tau=None) -> Dict:
    """Summary-only timeline kernel for ONE scenario (scalar params): the
    same ``lax.scan`` as ``_simulate`` but with no per-step trace outputs,
    so the compiled program never materializes the (T, series) stack —
    the fused sweep engine vmaps this over bucket-padded scenario chunks.
    Summary outputs are op-for-op identical to ``_simulate``'s (pinned by
    ``tests/test_sweep_engine.py``).

    ``tau`` (opt-in soft relaxation): a traced temperature scalar turns
    the boolean verdicts into differentiable sigmoid indicators — the
    capacity optimizer's ``jax.grad`` path; ``tau=None`` (the default)
    traces the original hard ops, bit-identical to before."""
    s = _schedule(c, p, tau)
    tier_total = jnp.maximum(c["tier_class"].sum(axis=1), 1e-9)

    def body(carry, t):
        core = _instant_core(c, p, s, t, tau)
        return _carry_step(carry, core, t, tier_total), None

    carry, _ = jax.lax.scan(body, _carry0(ts), ts)
    return _finalize(c, p, s, carry, ts, tau)


_simulate_jit = jax.jit(_simulate)
# vmap over the scenario axis only: consts and the time grid are shared.
# The trace variant materializes the full (S, T, series) stack; the
# summary variant is the default sweep path (verdicts only).
_sweep_jit = jax.jit(jax.vmap(_simulate, in_axes=(None, 0, None)))
_sweep_summary_jit = jax.jit(jax.vmap(timeline_verdicts,
                                      in_axes=(None, 0, None)))


def _as_params(p: Dict[str, float]) -> Dict[str, jnp.ndarray]:
    return {k: jnp.asarray(p[k], jnp.float32) for k in PARAM_KEYS}


def simulate_timeline(cfg: TimelineConfig,
                      params: Optional[Dict[str, float]] = None,
                      ts: Optional[np.ndarray] = None
                      ) -> Dict[str, np.ndarray]:
    """Run ONE scenario timeline; returns ``{"t": ts, traces..., summary
    scalars...}`` as numpy.  ``ts`` may be any increasing grid — pass the
    orchestrator's snapshot times to compare against its ``Timeline``."""
    base = default_scenario(burst_delay_s=cfg.preheat_s)
    params = dict(base, **(params or {}))
    ts = default_ts() if ts is None else np.asarray(ts, np.float64)
    traces, summary = _simulate_jit(cfg.as_consts(), _as_params(params),
                                    jnp.asarray(ts, jnp.float32))
    out = {"t": ts}
    out.update({k: np.asarray(v) for k, v in traces.items()})
    out.update({k: np.asarray(v) for k, v in summary.items()})
    return out


def sweep_timeline(cfg: TimelineConfig,
                   grid: Optional[Dict[str, np.ndarray]] = None,
                   ts: Optional[np.ndarray] = None,
                   dep_broken_frac: Optional[np.ndarray] = None,
                   return_traces: bool = False) -> Dict[str, np.ndarray]:
    """Temporal verdicts for every scenario in the grid, in one vmapped
    scan: per-scenario time-to-restore per tier, availability integral vs
    99.97%, peak on-demand cloud draw, and the SLA verdict — plus the full
    per-step traces when ``return_traces``.

    ``grid`` defaults to ``scenarios.scenario_grid()`` (the same axes the
    analytic sweep uses); ``dep_broken_frac`` folds the dependency-graph
    propagation verdicts into the availability trace (see
    ``scenarios.sweep_with_dependency_ensemble``)."""
    from repro.core.scenarios import scenario_grid
    grid = scenario_grid() if grid is None else grid
    n = validate_grid(grid)
    params = {k: jnp.asarray(np.asarray(grid[k]), jnp.float32)
              for k in PARAM_KEYS if k in grid}
    if dep_broken_frac is None:
        dep_broken_frac = grid.get("dep_broken_frac", np.zeros(n))
    params["dep_broken_frac"] = jnp.asarray(
        np.asarray(dep_broken_frac), jnp.float32)
    defaults = default_scenario(burst_delay_s=cfg.preheat_s)
    for k in PARAM_KEYS:                       # missing axes -> defaults
        if k not in params:
            params[k] = jnp.full(n, defaults[k], jnp.float32)
    ts = default_ts() if ts is None else np.asarray(ts, np.float64)
    tsj = jnp.asarray(ts, jnp.float32)
    if return_traces:
        traces, summary = _sweep_jit(cfg.as_consts(), params, tsj)
        out = {k: np.asarray(v) for k, v in summary.items()}
        out["t"] = ts
        out.update({f"trace_{k}": np.asarray(v) for k, v in traces.items()})
    else:
        # summary-only kernel: same ops for the verdicts, but the (S, T,
        # series) trace stack is never materialized
        summary = _sweep_summary_jit(cfg.as_consts(), params, tsj)
        out = {k: np.asarray(v) for k, v in summary.items()}
    return out


def summarize_timeline_sweep(result: Dict[str, np.ndarray]
                             ) -> Dict[str, object]:
    """Ensemble-level digest of a ``sweep_timeline`` result."""
    n = len(result["sla_ok"])
    finite_rl = result["rl_done_s"][np.isfinite(result["rl_done_s"])]
    return {
        "n_scenarios": n,
        "n_sla_ok": int(result["sla_ok"].sum()),
        "n_rl_rto_met": int(result["rl_rto_met"].sum()),
        "availability_mean_min": float(result["availability_mean"].min()),
        "availability_floor": float(result["availability_min"].min()),
        "worst_finite_rl_done_min": (float(finite_rl.max() / 60.0)
                                     if len(finite_rl) else float("nan")),
        "n_rl_never_restored": int(np.isinf(result["rl_done_s"]).sum()),
        "peak_cloud_cores_max": float(result["peak_cloud_cores"].max()),
        "worst_util_peak": float(result["util_peak"].max()),
    }
