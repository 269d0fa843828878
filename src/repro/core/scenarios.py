"""Scenario-ensemble failover analysis (vmapped analytic capacity model).

Resilience claims need *ensembles* of failure scenarios, not one trace
(Basiri et al., chaos engineering): this module closes the loop by
evaluating the UFA failover capacity model over a grid of scenario
parameters in one ``jax.vmap`` — per-scenario SLA verdicts and an
availability estimate for hundreds/thousands of variants in milliseconds.

The analytic model mirrors the discrete-event orchestrator's arithmetic
(same sizing rules, same wave/ramp constants) but collapses time to the
closed-form completion points, which is what makes it vmappable.

Scenario axes:
  traffic_mult        surviving-region traffic multiplier (paper: 2.0)
  burst_delay_s       preheat delay before burst capacity starts ramping
  burst_availability  fraction of batch capacity actually convertible
  cloud_quota_frac    multiplier on the region's cloud quota
  overcommit_factor   host-level overcommit (paper: 1.5, O_max 1.66)
  evict_fraction      fraction of preemptible demand actually evicted
"""

from __future__ import annotations

import dataclasses
import itertools
import zlib
from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import capacity as C
from repro.core.fleet_state import FleetState
from repro.core.omg import Orchestrator
from repro.core.tiers import QOS_EVICT_UTILIZATION, RTO_SECONDS, FailureClass

# single-source constants: orchestrator tunables + region-sizing rules —
# retuning either automatically retunes the scenario certification
_SLACK = C.DEFAULT_SLACK
_SPAWN_CORES_PER_HOST_S = Orchestrator.SPAWN_CORES_PER_HOST_S
_BATCH_CORES_PER_HOST = C.BATCH_CORES_PER_HOST
_MBB_WAVE_S = Orchestrator.MBB_WAVE_S
_MBB_PARALLELISM = Orchestrator.MBB_PARALLELISM
_RL_WAVE_S = Orchestrator.RL_RESTORE_WAVE_S
_PREHEAT_S = Orchestrator.BATCH_EVICT_S + Orchestrator.PREFETCH_S
_RL_RTO_S = RTO_SECONDS[FailureClass.RESTORE_LATER]
_QOS_EVICT = QOS_EVICT_UTILIZATION
_BASE_AVAILABILITY = 0.9997


def stage_seed(seed: int, stage: str) -> int:
    """Derive an independent integer seed for a named pipeline stage from
    one campaign seed.

    A single chaos-campaign/ensemble ``seed`` parameterizes several
    random stages (the blackhole draws, the cascade-storm draws, the
    correlated fault sampler).  Reusing the raw integer for each stage
    correlates their streams — e.g. the dependency ensemble's uniform
    draws and the sweep engine's draws used to be the SAME stream.  This
    folds the crc32 of the stage name into a ``jax.random`` key, so every
    (seed, stage) pair maps to an independent stream while the whole
    campaign stays reproducible from the one seed."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed),
                             zlib.crc32(stage.encode()) & 0x7FFFFFFF)
    return int(jax.random.randint(key, (), 0, np.iinfo(np.int32).max))


@dataclasses.dataclass(frozen=True)
class FleetAggregates:
    """Class-level core/env totals — all the analytic model needs."""
    ao_cores: float
    am_cores: float
    rl_cores: float
    tm_cores: float
    am_envs: int
    rl_envs: int

    @property
    def total_cores(self) -> float:
        return self.ao_cores + self.am_cores + self.rl_cores + self.tm_cores

    @classmethod
    def from_fleet_state(cls, fs: FleetState) -> "FleetAggregates":
        from repro.core.fleet_state import AM, RL
        ao, am, rl, tm = fs.class_core_totals()
        return cls(ao_cores=ao, am_cores=am, rl_cores=rl, tm_cores=tm,
                   am_envs=int(np.count_nonzero(fs.fclass == AM)),
                   rl_envs=int(np.count_nonzero(fs.fclass == RL)))

    @classmethod
    def from_fleet(cls, fleet: Dict[str, "object"]) -> "FleetAggregates":
        fs = FleetState.from_specs(fleet)
        return cls.from_fleet_state(fs)


def scenario_grid(traffic_mult=(1.6, 1.8, 2.0, 2.2),
                  burst_delay_s=(180.0, 270.0, 360.0, 600.0),
                  burst_availability=(1.0, 0.85, 0.7, 0.5),
                  cloud_quota_frac=(1.0, 0.5, 0.25, 0.0),
                  overcommit_factor=(1.5,),
                  evict_fraction=(1.0,)) -> Dict[str, np.ndarray]:
    """Cartesian scenario grid, flattened to parallel parameter arrays
    (defaults: 4^4 = 256 variants around the paper's operating point)."""
    axes = dict(traffic_mult=traffic_mult, burst_delay_s=burst_delay_s,
                burst_availability=burst_availability,
                cloud_quota_frac=cloud_quota_frac,
                overcommit_factor=overcommit_factor,
                evict_fraction=evict_fraction)
    rows = list(itertools.product(*axes.values()))
    cols = np.asarray(rows, np.float64).T
    return {k: cols[i] for i, k in enumerate(axes)}


def operating_point_mask(grid: Dict[str, np.ndarray]) -> np.ndarray:
    """Boolean mask selecting the paper's operating point in a scenario
    grid: 2x traffic, normal preheat, full burst, full cloud quota, full
    eviction — the single scenario the event-driven orchestrator runs."""
    return ((np.asarray(grid["traffic_mult"]) == 2.0)
            & (np.asarray(grid["burst_delay_s"]) == 270.0)
            & (np.asarray(grid["burst_availability"]) == 1.0)
            & (np.asarray(grid["cloud_quota_frac"]) == 1.0)
            & (np.asarray(grid["evict_fraction"]) == 1.0))


def _scenario_outcome(consts: Dict[str, jnp.ndarray],
                      p: Dict[str, jnp.ndarray], tau=None):
    """SLA outcome of ONE scenario (all scalars — vmapped over the grid).

    ``tau`` (opt-in soft relaxation, see ``timeline_sim.soft_ge``): the
    hard boolean verdicts become sigmoid indicators of the signed margins
    and ``sla_ok`` their product, so ``jax.grad`` flows through the
    closed-form model — the capacity optimizer's analytic stage.
    ``tau=None`` (the default) traces the original ops, bit-identical."""
    from repro.core.timeline_sim import (SOFT_DEP_SCALE, SOFT_FRAC_SCALE,
                                         SOFT_TIME_SCALE, soft_ge)
    ao, am = consts["ao"], consts["am"]
    rl, tm = consts["rl"], consts["tm"]
    am_envs, rl_envs = consts["am_envs"], consts["rl_envs"]

    mult = p["traffic_mult"]
    oc = p["overcommit_factor"]
    evict = p["evict_fraction"]
    # eviction-order shifts (optim.capacity): per-class deltas on the
    # evicted fraction, additive so a present-but-zero knob is exact
    d_rl = p.get("rl_evict_delta", 0.0)
    d_tm = p.get("tm_evict_delta", 0.0)
    cs = 0.01 * (ao + am + rl + tm)          # cores-margin scale (soft)

    # region sizing (same rule as RegionCapacity.for_fleet, model="ufa");
    # the optimizer overrides the hand-tuned 2x Always-On buffer via the
    # optional ``ao_buffer`` const (1 + buffer fraction) — key-conditional
    # so legacy consts trace the identical program
    buf = consts["ao_buffer"] if "ao_buffer" in consts else 2.0
    stateless = (buf * ao + am) * _SLACK
    # partial-region degradation (chaos fault family): a fraction of the
    # surviving region's serving capacity is lost — not a binary
    # blackhole.  Conditional on key presence so legacy grids trace the
    # identical program; x * (1 - 0) is exact in float32, so a present-
    # but-zero knob is a bitwise no-op.
    if "region_degradation" in p:
        stateless = stateless * (1.0 - p["region_degradation"])
    oc_cap = stateless * (oc - 1.0)
    preempt_resident = ((rl + tm) * (1.0 - evict)
                        - (rl * d_rl + tm * d_tm))
    if tau is None:
        preempt_fit = preempt_resident <= oc_cap + 1e-6
    else:
        preempt_fit = soft_ge(oc_cap + 1e-6, preempt_resident, cs, tau)

    # batch -> burst conversion (same sizing rule as for_fleet); the
    # optional ``spawn_mult`` const is the optimizer's burst-conversion
    # ramp knob (spawner throughput multiplier)
    batch_cores = (am + rl) * C.BATCH_BURST_HEADROOM \
        / C.BATCH_PREEMPTIBLE_FRACTION
    burst_cap = (batch_cores * C.BATCH_PREEMPTIBLE_FRACTION
                 * p["burst_availability"])
    spawn_rate = _SPAWN_CORES_PER_HOST_S * batch_cores / _BATCH_CORES_PER_HOST
    if "spawn_mult" in consts:
        spawn_rate = spawn_rate * consts["spawn_mult"]
    burst_full_s = p["burst_delay_s"] + burst_cap / jnp.maximum(spawn_rate,
                                                                1e-9)

    # Active-Migrate MBB into burst
    am_in_burst = jnp.minimum(am, burst_cap)
    am_waves = jnp.ceil(am_envs / _MBB_PARALLELISM)
    am_done_s = burst_full_s + am_waves * _MBB_WAVE_S
    am_stranded = am - am_in_burst            # stays in steady if burst full

    # Always-On in-place scale-up into freed headroom
    free_after_am = stateless - ao - am + am_in_burst
    ao_need = ao * (mult - 1.0)
    ao_short = jnp.maximum(0.0, ao_need - free_after_am)
    if tau is None:
        ao_ok = ao_short <= 1e-6
    else:
        # signed margin (ao_short is one-sided: 0 exactly at the boundary
        # would read 0.5 through the sigmoid)
        ao_ok = soft_ge(free_after_am + 1e-6, ao_need, cs, tau)

    # Restore-Later: burst first, cloud (with provisioning latency) last
    burst_left = jnp.maximum(0.0, burst_cap - am_in_burst)
    rl_need = rl * evict + rl * d_rl          # evicted RL demand to restore
    rl_in_burst = jnp.minimum(rl_need, burst_left)
    cloud_need = rl_need - rl_in_burst
    quota = C.default_cloud_quota(rl) * p["cloud_quota_frac"]
    cloud_grant = jnp.minimum(cloud_need, quota)
    rl_down = cloud_need - cloud_grant
    # default_cloud_rate via its constants (python max() is not trace-safe)
    cloud_rate = jnp.maximum(C.CLOUD_RATE_FLOOR,
                             rl / C.CLOUD_RATE_RL_DIVISOR)
    cloud_delay = cloud_grant / cloud_rate
    rl_waves = jnp.ceil(rl_envs / _MBB_PARALLELISM)
    rl_done_s = burst_full_s + rl_waves * _RL_WAVE_S + cloud_delay
    if tau is None:
        rl_ok = (rl_down <= 1e-6) & (rl_done_s <= _RL_RTO_S)
    else:
        # signed fit margin: quota vs. what must come from the cloud
        # (rl_down is one-sided, same boundary problem as ao_short); the
        # +1.0-core shift keeps the fully-served point deep in the "ok"
        # tail instead of on the 0.5 knife edge
        rl_ok = (soft_ge(quota + 1.0, cloud_need, cs, tau)
                 * soft_ge(_RL_RTO_S, rl_done_s, SOFT_TIME_SCALE, tau))

    # surviving-region utilization at the post-migration peak
    busy = (ao * mult * 0.62 + am_in_burst * 0.0
            + am_stranded * 0.62 * mult + preempt_resident * 0.35)
    util_peak = busy / jnp.maximum(stateless, 1.0)
    if tau is None:
        util_ok = util_peak <= _QOS_EVICT
    else:
        util_ok = soft_ge(_QOS_EVICT, util_peak, SOFT_FRAC_SCALE, tau)

    # availability estimate: AO shortfall bites immediately; unrestored RL
    # degrades the fraction of critical flows that (safely) depend on it;
    # every critical service the dependency-graph propagation says *breaks*
    # under this scenario's blackhole is hard-down for the failover window
    crit = jnp.maximum(ao + am, 1.0)
    rl_exposure = 0.1 * rl_down / jnp.maximum(rl, 1.0)
    window_frac = jnp.minimum(1.0, rl_done_s / _RL_RTO_S)
    dep_broken = p["dep_broken_frac"]
    if tau is None:
        dep_ok = dep_broken <= 0.0
        availability = (_BASE_AVAILABILITY
                        - 0.5 * ao_short / crit
                        - rl_exposure * window_frac
                        - 0.5 * dep_broken
                        - jnp.where(util_ok, 0.0, 1e-4))
        availability = jnp.clip(availability, 0.0, 1.0)
        sla_ok = (ao_ok & rl_ok & preempt_fit & dep_ok
                  & (am_done_s <= 30.0 * 60.0)
                  & (burst_full_s <= 20.0 * 60.0) & util_ok)
    else:
        # broken-critical fractions are quantized at 1/n_crit (~2e-4 for
        # paper-scale fleets): a 1e-7 threshold with a 1e-6 scale keeps
        # "nothing broken" (exactly 0) in the ok tail and the smallest
        # nonzero fraction firmly refused
        dep_ok = soft_ge(1e-7, dep_broken, SOFT_DEP_SCALE, tau)
        availability = (_BASE_AVAILABILITY
                        - 0.5 * ao_short / crit
                        - rl_exposure * window_frac
                        - 0.5 * dep_broken
                        - 1e-4 * (1.0 - util_ok))
        availability = jnp.clip(availability, 0.0, 1.0)
        sla_ok = (ao_ok * rl_ok * preempt_fit * dep_ok
                  * soft_ge(30.0 * 60.0, am_done_s, SOFT_TIME_SCALE, tau)
                  * soft_ge(20.0 * 60.0, burst_full_s, SOFT_TIME_SCALE, tau)
                  * util_ok)
    # cascading dependency storm (chaos fault family): the storm's dark
    # set re-breaks ``storm_broken_frac`` of criticals with pulse
    # amplitude ``storm_refrac`` while the timeline kernel re-darkens the
    # restored capacity; the closed-form mirror charges the exposure
    # once.  Conditional-key + exact-at-zero, like degradation above.
    if "storm_refrac" in p:
        storm_frac = p.get("storm_broken_frac", 0.0)
        storm_exposure = storm_frac * p["storm_refrac"]
        availability = jnp.clip(availability - 0.5 * storm_exposure,
                                0.0, 1.0)
        if tau is None:
            storm_ok = storm_exposure <= 1e-6
            sla_ok = sla_ok & storm_ok
        else:
            storm_ok = soft_ge(1e-7, storm_exposure, SOFT_DEP_SCALE, tau)
            sla_ok = sla_ok * storm_ok
    out = {
        "dep_broken_frac": dep_broken,
        "dep_ok": dep_ok,
        "burst_full_s": burst_full_s,
        "am_done_s": am_done_s,
        "rl_done_s": rl_done_s,
        "rl_down_cores": rl_down,
        "cloud_grant_cores": cloud_grant,
        "cloud_delay_s": cloud_delay,
        "util_peak": util_peak,
        "ao_ok": ao_ok,
        "rl_ok": rl_ok,
        "preempt_fit": preempt_fit,
        "util_ok": util_ok,
        "availability": availability,
        "sla_ok": sla_ok,
    }
    if "storm_refrac" in p and "storm_broken_frac" in p:
        # emitted only when the storm stage supplied a traced verdict (a
        # vmapped output must not be a trace-time constant)
        out["storm_ok"] = storm_ok
        out["storm_broken_frac"] = storm_frac
    return out


# public kernel entry point: the fused sweep engine vmaps this (one
# scalar scenario) fused with the timeline scan and the dependency
# penalty — same ops as the standalone sweep, hence bit-identical
scenario_outcome = _scenario_outcome


def analytic_consts(agg: FleetAggregates, *, ao_buffer=None,
                    spawn_mult=None) -> Dict[str, jnp.ndarray]:
    """f32 device constants for ``scenario_outcome`` (precomputed once,
    passed as traced arguments so the jit cache is keyed on shapes, not
    fleet values).

    ``ao_buffer`` / ``spawn_mult`` (optional floats, the capacity
    optimizer's hooks): when given, they are added as consts keys and
    ``scenario_outcome`` replaces the hand-tuned 2x Always-On sizing
    coefficient / scales the burst spawner throughput.  Absent keys trace
    the original program — the historical sweeps stay bit-identical."""
    out = {"ao": jnp.asarray(agg.ao_cores, jnp.float32),
           "am": jnp.asarray(agg.am_cores, jnp.float32),
           "rl": jnp.asarray(agg.rl_cores, jnp.float32),
           "tm": jnp.asarray(agg.tm_cores, jnp.float32),
           "am_envs": jnp.asarray(agg.am_envs, jnp.float32),
           "rl_envs": jnp.asarray(agg.rl_envs, jnp.float32)}
    if ao_buffer is not None:
        out["ao_buffer"] = jnp.asarray(ao_buffer, jnp.float32)
    if spawn_mult is not None:
        out["spawn_mult"] = jnp.asarray(spawn_mult, jnp.float32)
    return out


# compiled once per (grid-shape, consts-structure); reused across sweeps
_sweep_jit = jax.jit(jax.vmap(_scenario_outcome, in_axes=(None, 0)))


def sweep_scenarios(agg: FleetAggregates,
                    grid: Optional[Dict[str, np.ndarray]] = None,
                    dep_broken_frac: Optional[np.ndarray] = None,
                    timeline: Optional[object] = None,
                    ts: Optional[np.ndarray] = None
                    ) -> Dict[str, np.ndarray]:
    """Evaluate the failover model over every scenario in one vmap.

    dep_broken_frac: optional per-scenario fraction of critical services
    the dependency-graph blackhole propagation says break (see
    ``sweep_with_dependency_ensemble``); defaults to 0 everywhere (a fully
    hardened fleet).

    timeline: optional ``timeline_sim.TimelineConfig`` — also runs the
    vmapped discrete-time timeline kernel over the same grid and merges
    its *temporal* verdicts (per-tier time-to-restore, availability
    integral vs 99.97%, peak on-demand cloud draw, temporal SLA) under
    ``t_``-prefixed keys alongside the analytic ones.  ``ts`` overrides
    the default 2h/240-step grid."""
    from repro.core.timeline_sim import validate_grid
    grid = grid if grid is not None else scenario_grid()
    n = validate_grid(grid)
    if timeline is not None:
        # one fused, sharded, jitted pipeline: analytic model + timeline
        # scan in a single vmap (the t_-prefixed temporal verdicts come
        # from the same compiled program, no host round-trip between
        # stages) — see repro.core.sweep_engine
        from repro.core.sweep_engine import SweepEngine
        eng = SweepEngine(agg, timeline, ts=ts)
        return eng.run(grid, dep_broken_frac=dep_broken_frac)
    consts = analytic_consts(agg)
    params = {k: jnp.asarray(v, jnp.float32) for k, v in grid.items()}
    if dep_broken_frac is None:
        dep_broken_frac = np.zeros(n)
    params["dep_broken_frac"] = jnp.asarray(dep_broken_frac, jnp.float32)
    out = _sweep_jit(consts, params)
    result = {k: np.asarray(v) for k, v in out.items()}
    result.update({k: np.asarray(v) for k, v in grid.items()})
    return result


def sweep_with_dependency_ensemble(fs: FleetState,
                                   grid: Optional[Dict[str, np.ndarray]]
                                   = None,
                                   seed: int = 0,
                                   temporal: bool = False,
                                   region: Optional[object] = None,
                                   ts: Optional[np.ndarray] = None,
                                   devices: Optional[object] = None
                                   ) -> Dict[str, np.ndarray]:
    """Scenario sweep with the dependency layer closed in: each scenario's
    ``evict_fraction`` sets its blackhole intensity — that fraction of
    preemptible services goes dark, with the uniform draws shared across
    scenarios, so equal fractions share one dark set and differing
    fractions give *nested* sets (vary the grid's ``evict_fraction`` axis
    for ensemble diversity).  One batched multi-hop propagation certifies
    the whole ensemble and the per-scenario broken-critical fractions feed
    the availability estimate/SLA verdicts.

    temporal=True additionally runs the discrete-time timeline kernel
    over the grid (sizing a region for ``fs`` unless ``region`` is given)
    and folds the same propagation verdicts into the availability
    *trace*: a broken critical's penalty decays as its dark dependencies
    restore, and the ``t_``-prefixed temporal verdicts land next to the
    analytic ones; ``devices`` goes to the engine (see ``SweepEngine``)."""
    from repro.graph import CallGraph
    grid = grid if grid is not None else scenario_grid()
    graph = CallGraph.from_fleet_state(fs)
    agg = FleetAggregates.from_fleet_state(fs)
    # one campaign seed, independent per-stage streams: the ensemble
    # stage and the fused engine stage used to consume the SAME raw
    # integer — identical uniform draws, so any analysis comparing the
    # two paths saw perfectly correlated "independent" ensembles.  Each
    # stage now folds its name into the campaign seed (``stage_seed``),
    # keeping the whole run reproducible from the one integer.
    if temporal:
        # the fused engine: propagation + analytic model + timeline scan
        # in ONE jitted, device-parallel pipeline (sweep_engine) — the
        # per-scenario broken-critical verdicts never touch the host
        # before the availability trace consumes them
        from repro.core.sweep_engine import SweepEngine
        from repro.core.timeline_sim import config_for_fleet
        timeline = config_for_fleet(fs, region=region)
        eng = SweepEngine(agg, timeline, graph=graph,
                          seed=stage_seed(seed, "sweep-engine"), ts=ts,
                          devices=devices)
        return eng.run(grid)
    from repro.graph import blackhole_ensemble
    ens = blackhole_ensemble(graph, seed=stage_seed(seed, "blackhole-ensemble"),
                             fractions=np.asarray(grid["evict_fraction"]))
    result = sweep_scenarios(agg, grid,
                             dep_broken_frac=ens["broken_critical_frac"])
    # int32, matching the fused temporal path's device-computed counts
    result["dep_n_broken_critical"] = np.asarray(ens["n_broken_critical"],
                                                 np.int32)
    result["dep_n_dark"] = np.asarray(ens["n_dark"], np.int32)
    return result


def summarize_sweep(result: Dict[str, np.ndarray]) -> Dict[str, object]:
    n = len(result["sla_ok"])
    ok = int(result["sla_ok"].sum())
    out = {
        "n_scenarios": n,
        "n_sla_ok": ok,
        "sla_ok_fraction": ok / max(1, n),
        "availability_min": float(result["availability"].min()),
        "availability_mean": float(result["availability"].mean()),
        "worst_rl_done_min": float(result["rl_done_s"].max() / 60.0),
        "worst_util_peak": float(result["util_peak"].max()),
    }
    if "dep_ok" in result:
        out["n_dep_ok"] = int(result["dep_ok"].sum())
        out["worst_dep_broken_frac"] = float(
            result["dep_broken_frac"].max())
    if "t_sla_ok" in result:        # temporal verdicts present
        finite = result["t_rl_done_s"][np.isfinite(result["t_rl_done_s"])]
        out["n_t_sla_ok"] = int(result["t_sla_ok"].sum())
        out["n_analytic_temporal_agree"] = int(
            (result["sla_ok"] == result["t_sla_ok"]).sum())
        out["t_availability_mean_min"] = float(
            result["t_availability_mean"].min())
        out["t_worst_finite_rl_done_min"] = (
            float(finite.max() / 60.0) if len(finite) else float("nan"))
        out["t_n_rl_never_restored"] = int(
            np.isinf(result["t_rl_done_s"]).sum())
        out["t_peak_cloud_cores_max"] = float(
            result["t_peak_cloud_cores"].max())
    return out


def scenario_records(result: Dict[str, np.ndarray]) -> list:
    """Per-scenario verdict rows (JSON-serializable) for the bench log."""
    keys = ["traffic_mult", "burst_delay_s", "burst_availability",
            "cloud_quota_frac", "overcommit_factor", "evict_fraction",
            "burst_full_s", "rl_done_s", "util_peak", "availability",
            "ao_ok", "rl_ok", "util_ok", "sla_ok"]
    n = len(result["sla_ok"])
    return [{k: (bool(result[k][i]) if result[k].dtype == bool
                 else round(float(result[k][i]), 6)) for k in keys}
            for i in range(n)]
