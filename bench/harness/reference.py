"""Plain references for the benchmark's correctness check.

Written from the documented semantics of the UFA failover model (arXiv
2603.07345, Sections 4-6) in plain numpy.  Nothing here imports the system
under test, and nothing takes a table, constant or intermediate that it
computed: the inputs are the fleet's own columns (tiers, failure classes,
cores, call edges) and the traffic the benchmark drew.

  * ``fleet_aggregates`` / ``timeline_config``: class totals, region
    sizing and the steady-state first-fit placement the failover model
    starts from;
  * ``analytic``: the closed-form scenario verdicts;
  * ``timeline``: the failover timeline, stepped in a plain loop over the
    time grid (vectorised over scenarios only) and folded into verdicts;
  * ``fixed_point``: blackhole propagation to the least fixed point;
  * ``sampling_tables`` / ``stream_counts`` / ``detect_mask``: the sampled
    RPC stream, its per-edge outcome counts and the fail-close thresholds.

The float routines take ``dt``: float64 for the reference, a lower
precision for the control (every intermediate is rounded to ``dt``).
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import numpy as np

N_TIERS = 7                       # T0..T5 and non-production
AO, AM, RL, TM = 0, 1, 2, 3       # failure-class codes of the fleet columns

# region sizing (Section 4.4-4.6)
SLACK = 1.06
STEADY_CORES_PER_HOST = 100.0
OVERCOMMIT_FACTOR = 1.5
BATCH_CORES_PER_HOST = 120.0
BATCH_BURST_HEADROOM = 1.35
BATCH_PREEMPTIBLE_FRACTION = 0.9
CLOUD_RATE_FLOOR = 10.0
CLOUD_RATE_RL_DIVISOR = 1200.0

# orchestration tunables (Section 4.3, Fig. 7)
KILL_S = 5.0
PREHEAT_S = 90.0 + 180.0          # batch drain + image prefetch
SPAWN_CORES_PER_HOST_S = 0.45
MBB_WAVE_S = 45.0
MBB_PARALLELISM = 2000.0
RL_WAVE_S = 120.0
RL_RTO_S = 3600.0

# SLA model
QOS_EVICT = 0.75
BASE_AVAILABILITY = 0.9997
AVAIL_SLA_TOL = 5e-5
RESTORE_THRESH = 0.999
EPS_T = 1e-3
DEMAND_CRIT = 0.62
DEMAND_PRE = 0.35

# Table 2: RPC volume by (caller tier, callee tier)
TABLE2 = np.asarray([
    [47.1, 940, 2300, 1820, 144, 100, 1770],
    [10.7, 21800, 2240, 387, 6.07, 70.4, 18600],
    [25.3, 2020, 663, 77.0, 0.0309, 1.17, 2700],
    [7.95, 288, 119, 16.9, 0.192, 6.09, 1060],
    [0.788, 11.5, 0.599, 0.228, 1.19, 0.0121, 22.1],
    [0.29, 76.1, 0.266, 0.849, 0.0013, 4.52, 14.1],
    [107, 1530, 471, 126, 12.8, 18.3, 3130],
], np.float64)

# runtime detection (Section 6): trace mix and thresholds
AMBIENT_CALLEE_FAILURE = 0.025
AMBIENT_CALLER_ERROR = 0.003
PROPAGATION_PROB = 0.92
COLD_PATH_FRACTION = 0.18
COLD_TRAFFIC_FACTOR = 0.01
MIN_FAILURES = 5
PROPAGATION_THRESHOLD = 0.5
LIFT_THRESHOLD = 5.0


# ---------------------------------------------------------------------------
# fleet -> aggregates, region, placement
# ---------------------------------------------------------------------------


def fleet_aggregates(fclass: np.ndarray, cores: np.ndarray) -> Dict:
    """Class core totals and environment counts."""
    out = {k: float(cores[fclass == c].sum())
           for k, c in (("ao", AO), ("am", AM), ("rl", RL), ("tm", TM))}
    for k, c in (("am_envs", AM), ("rl_envs", RL), ("tm_envs", TM)):
        out[k] = float(np.count_nonzero(fclass == c))
    return out


def _first_fit(cores: np.ndarray, capacity: float) -> np.ndarray:
    """Place items in array order while they fit; returns the take mask."""
    taken = np.zeros(len(cores), bool)
    used = 0.0
    for i, c in enumerate(cores.tolist()):
        if c <= capacity - used + 1e-9:
            taken[i] = True
            used += c
    return taken


def timeline_config(tier: np.ndarray, fclass: np.ndarray,
                    cores: np.ndarray) -> Dict:
    """The surviving region in steady state: sized for the fleet (2x
    Always-On, 1x Active-Migrate, preemptibles in a 1.5x overcommit pool),
    preemptibles placed first-fit into overcommit, the rest (and any
    overflow) into the stateless pool."""
    c = fleet_aggregates(fclass, cores)
    ao, am, rl = c["ao"], c["am"], c["rl"]
    stateless = (2.0 * ao + am) * SLACK
    n_hosts = max(4, math.ceil(stateless / STEADY_CORES_PER_HOST))
    phys = n_hosts * STEADY_CORES_PER_HOST
    oc_cap = phys * (OVERCOMMIT_FACTOR - 1.0)
    batch_cores = (am + rl) * BATCH_BURST_HEADROOM / BATCH_PREEMPTIBLE_FRACTION
    batch_hosts = max(2, math.ceil(batch_cores / BATCH_CORES_PER_HOST))

    pre = fclass >= RL
    pre_idx = np.flatnonzero(pre)
    in_oc = np.zeros(len(cores), bool)
    in_oc[pre_idx[_first_fit(cores[pre_idx], oc_cap)]] = True
    rest_idx = np.flatnonzero(~in_oc)
    in_sl = np.zeros(len(cores), bool)
    in_sl[rest_idx[_first_fit(cores[rest_idx], phys)]] = True

    tier_class = np.zeros((N_TIERS, 4), np.float64)
    for t in range(N_TIERS):
        for k in range(4):
            tier_class[t, k] = cores[(tier == t) & (fclass == k)].sum()
    c.update(
        tier_class=tier_class,
        stateless_cap=phys,
        steady_used0=float(cores[in_sl].sum()),
        overcommit_used0=float(cores[in_oc].sum()),
        oc_preempt_cores=float(cores[pre & in_oc].sum()),
        sl_preempt_cores=float(cores[pre & in_sl].sum()),
        am_stateless_cores=float(cores[(fclass == AM) & in_sl].sum()),
        burst_cap_full=batch_hosts * BATCH_CORES_PER_HOST
        * BATCH_PREEMPTIBLE_FRACTION,
        spawn_rate=SPAWN_CORES_PER_HOST_S * batch_hosts,
        cloud_quota=0.5 * rl + 100.0,
        cloud_rate=max(CLOUD_RATE_FLOOR, rl / CLOUD_RATE_RL_DIVISOR),
    )
    return c


# ---------------------------------------------------------------------------
# scenario verdicts
# ---------------------------------------------------------------------------


def analytic(c: Dict, g: Dict, dt=np.float64) -> Dict[str, np.ndarray]:
    """Closed-form verdicts of each scenario (columns of ``g``), with the
    dependency penalty ``g["dep_broken_frac"]`` from propagation."""
    q = lambda x: np.asarray(x, dt)
    ao, am, rl, tm = (q(c[k]) for k in ("ao", "am", "rl", "tm"))
    mult = q(g["traffic_mult"])
    evict = q(g["evict_fraction"])
    dep = q(g["dep_broken_frac"])

    stateless = q(q(q(2.0) * ao + am) * q(SLACK))
    oc_cap = q(stateless * q(q(g["overcommit_factor"]) - q(1.0)))
    preempt_resident = q(q(rl + tm) * q(q(1.0) - evict))
    preempt_fit = preempt_resident <= q(oc_cap + q(1e-6))

    batch_cores = q(q(am + rl) * q(BATCH_BURST_HEADROOM)
                    / q(BATCH_PREEMPTIBLE_FRACTION))
    burst_cap = q(q(batch_cores * q(BATCH_PREEMPTIBLE_FRACTION))
                  * q(g["burst_availability"]))
    spawn_rate = q(q(SPAWN_CORES_PER_HOST_S) * batch_cores
                   / q(BATCH_CORES_PER_HOST))
    burst_full_s = q(q(g["burst_delay_s"])
                     + q(burst_cap / np.maximum(spawn_rate, q(1e-9))))
    am_in_burst = q(np.minimum(am, burst_cap))
    am_waves = q(np.ceil(q(c["am_envs"]) / q(MBB_PARALLELISM)))
    am_done_s = q(burst_full_s + q(am_waves * q(MBB_WAVE_S)))
    am_stranded = q(am - am_in_burst)

    free_after_am = q(q(q(stateless - ao) - am) + am_in_burst)
    ao_need = q(ao * q(mult - q(1.0)))
    ao_short = q(np.maximum(q(0.0), q(ao_need - free_after_am)))
    ao_ok = ao_short <= q(1e-6)

    burst_left = q(np.maximum(q(0.0), q(burst_cap - am_in_burst)))
    rl_need = q(rl * evict)
    rl_in_burst = q(np.minimum(rl_need, burst_left))
    cloud_need = q(rl_need - rl_in_burst)
    quota = q(q(q(0.5) * rl + q(100.0)) * q(g["cloud_quota_frac"]))
    cloud_grant = q(np.minimum(cloud_need, quota))
    rl_down = q(cloud_need - cloud_grant)
    cloud_rate = q(np.maximum(q(CLOUD_RATE_FLOOR),
                              q(rl / q(CLOUD_RATE_RL_DIVISOR))))
    cloud_delay = q(cloud_grant / cloud_rate)
    rl_waves = q(np.ceil(q(c["rl_envs"]) / q(MBB_PARALLELISM)))
    rl_done_s = q(q(burst_full_s + q(rl_waves * q(RL_WAVE_S)))
                  + cloud_delay)
    rl_ok = (rl_down <= q(1e-6)) & (rl_done_s <= q(RL_RTO_S))

    busy = q(q(q(ao * mult) * q(DEMAND_CRIT))
             + q(q(am_stranded * q(DEMAND_CRIT)) * mult)
             + q(preempt_resident * q(DEMAND_PRE)))
    util_peak = q(busy / np.maximum(stateless, q(1.0)))
    util_ok = util_peak <= q(QOS_EVICT)

    crit = q(np.maximum(q(ao + am), q(1.0)))
    rl_exposure = q(q(q(0.1) * rl_down) / np.maximum(rl, q(1.0)))
    window_frac = q(np.minimum(q(1.0), q(rl_done_s / q(RL_RTO_S))))
    dep_ok = dep <= q(0.0)
    availability = q(q(BASE_AVAILABILITY)
                     - q(q(q(0.5) * ao_short) / crit)
                     - q(rl_exposure * window_frac)
                     - q(q(0.5) * dep)
                     - q(np.where(util_ok, q(0.0), q(1e-4))))
    availability = q(np.clip(availability, q(0.0), q(1.0)))
    sla_ok = (ao_ok & rl_ok & preempt_fit & dep_ok
              & (am_done_s <= q(1800.0)) & (burst_full_s <= q(1200.0))
              & util_ok)
    storm_frac = q(g["storm_broken_frac"])
    storm_exposure = q(storm_frac * q(g["storm_refrac"]))
    availability = q(np.clip(q(availability - q(q(0.5) * storm_exposure)),
                             q(0.0), q(1.0)))
    storm_ok = storm_exposure <= q(1e-6)
    sla_ok = sla_ok & storm_ok
    return {
        "dep_broken_frac": dep, "dep_ok": dep_ok,
        "burst_full_s": burst_full_s, "am_done_s": am_done_s,
        "rl_done_s": rl_done_s, "rl_down_cores": rl_down,
        "cloud_grant_cores": cloud_grant, "cloud_delay_s": cloud_delay,
        "util_peak": util_peak, "ao_ok": ao_ok, "rl_ok": rl_ok,
        "preempt_fit": preempt_fit, "util_ok": util_ok,
        "availability": availability, "sla_ok": sla_ok,
        "storm_ok": storm_ok, "storm_broken_frac": storm_frac,
    }


def timeline(c: Dict, g: Dict, ts: np.ndarray,
             dt=np.float64) -> Dict[str, np.ndarray]:
    """Failover timeline of each scenario over the time grid ``ts``: the
    eviction at ``KILL_S``, the batch-to-burst ramp (10 spawner ticks),
    Active-Migrate waves into burst, the Always-On upscale once migration
    is done, Restore-Later waves (burst first, then one cloud batch that
    arrives after ``grant / rate`` seconds), the availability and
    utilization model per step, and the verdicts folded from the steps.
    Defined for grids without storms, region degradation or per-class
    eviction shifts (the benchmark's traffic sets none)."""
    q = lambda x: np.asarray(x, dt)
    one, zero = q(1.0), q(0.0)
    eps = q(EPS_T)
    ao, am, rl, tm = (q(c[k]) for k in ("ao", "am", "rl", "tm"))
    am_envs, rl_envs = q(c["am_envs"]), q(c["rl_envs"])
    mult = q(g["traffic_mult"])
    evict = q(g["evict_fraction"])
    delay = q(g["burst_delay_s"])
    dep = q(g["dep_broken_frac"])
    stateless = q(c["stateless_cap"])

    # schedule
    burst_cap = q(q(c["burst_cap_full"]) * q(g["burst_availability"]))
    ramp_total = q(burst_cap / np.maximum(q(c["spawn_rate"]), q(1e-9)))
    burst_full_t = q(delay + ramp_total)
    n_am_waves = q(np.ceil(am_envs / q(MBB_PARALLELISM)))
    am_done_t = q(burst_full_t + q(n_am_waves * q(MBB_WAVE_S)))
    am_in_burst = q(np.minimum(am, burst_cap))
    ao_need = q(ao * q(mult - one))
    am_release_frac = q(q(c["am_stateless_cores"]) / np.maximum(am, q(1e-9)))
    am_released = q(am_in_burst * am_release_frac)
    free_at_am_done = q(stateless - q(q(q(c["steady_used0"])
                                        - q(evict * q(c["sl_preempt_cores"])))
                                      - am_released))
    ao_ok = ao_need <= q(free_at_am_done + q(1e-6))
    ao_short = q(np.maximum(zero, q(ao_need - free_at_am_done)))
    rl_need = q(rl * evict)
    rl_envs_evicted = q(rl_envs * evict)
    n_rl_waves = q(np.maximum(one, np.ceil(q(rl_envs_evicted
                                             / q(MBB_PARALLELISM)))))
    rl_last_wave_t = q(burst_full_t + q(n_rl_waves * q(RL_WAVE_S)))
    burst_free_rl = q(np.maximum(zero, q(burst_cap - am_in_burst)))
    quota_eff = q(q(c["cloud_quota"]) * q(g["cloud_quota_frac"]))
    total_cloud = q(np.minimum(np.maximum(zero, q(rl_need - burst_free_rl)),
                               quota_eff))
    per_wave = q(rl_need / n_rl_waves)
    k_star = q(np.minimum(q(np.floor(q(burst_free_rl
                                       / np.maximum(per_wave, q(1e-9))))
                            + one), n_rl_waves))
    cloud_start_t = q(burst_full_t + q(k_star * q(RL_WAVE_S)))
    cloud_arrival_t = q(cloud_start_t
                        + q(total_cloud / np.maximum(q(c["cloud_rate"]),
                                                     q(1e-9))))
    rl_shortfall = q(np.maximum(zero, q(q(rl_need - burst_free_rl)
                                        - quota_eff)))
    rl_done_t = q(np.where(rl_shortfall > q(1e-6), q(np.inf),
                           np.maximum(rl_last_wave_t,
                                      np.where(total_cloud > q(1e-6),
                                               cloud_arrival_t, zero))))

    tier_class = q(c["tier_class"])                     # (R, 4)
    tier_total = q(np.maximum(tier_class.sum(axis=1), q(1e-9)))
    crit = q(np.maximum(q(ao + am), one))
    dark_tot = q(np.maximum(q(rl_need + q(evict * tm)), q(1e-9)))

    S = len(mult)
    avail_int = np.zeros(S, dt)
    avail_min = np.ones(S, dt)
    util_peak = np.zeros(S, dt)
    cloud_peak = np.zeros(S, dt)
    below_seen = np.zeros((S, N_TIERS), bool)
    restore_t = np.full((S, N_TIERS), np.inf, dt)
    prev_t = q(ts[0])
    for t_host in np.asarray(ts, np.float64).tolist():
        t = q(t_host)
        evicted = t >= q(q(KILL_S) - eps)
        e = evict if evicted else np.zeros(S, dt)

        am_waves = q(np.clip(np.floor(q(q(q(t - burst_full_t) + eps)
                                        / q(MBB_WAVE_S))),
                             zero, n_am_waves))
        am_envs_moved = q(np.minimum(am_envs,
                                     q(q(MBB_PARALLELISM) * am_waves)))
        am_attempt = q(q(am * am_envs_moved) / np.maximum(am_envs, one))
        am_moved = q(np.minimum(am_attempt, burst_cap))

        ao_scaled = ao_ok & (t >= q(am_done_t - eps))
        ao_live = q(ao * np.where(ao_scaled, mult, one))

        rl_waves = q(np.clip(np.floor(q(q(q(t - burst_full_t) + eps)
                                        / q(RL_WAVE_S))),
                             zero, n_rl_waves))
        processed = q(q(rl_need * rl_waves) / n_rl_waves)
        rl_burst = q(np.minimum(processed, burst_free_rl))
        cloud_prov = q(np.minimum(q(processed - rl_burst), quota_eff))
        cloud_arrived = q(np.where(t >= q(cloud_arrival_t - eps),
                                   total_cloud, zero))
        cloud_live = q(np.minimum(cloud_arrived, cloud_prov))
        rl_restored = q(rl_burst + cloud_live)
        rl_live = q(q(rl - q(e * rl)) + rl_restored)
        tm_live = q(tm * q(one - e))

        am_steady_cores = q(am - am_moved)
        pre_steady = q(q(rl + tm) * q(one - e))
        busy = q(q(q(q(ao * q(DEMAND_CRIT)) * mult)
                   + q(q(am_steady_cores * q(DEMAND_CRIT)) * mult))
                 + q(pre_steady * q(DEMAND_PRE)))
        util_model = q(np.minimum(one, q(busy / np.maximum(stateless, one))))

        rl_down = q(rl - rl_live)
        tm_down = q(tm - tm_live)
        ao_pen = (q(q(q(0.5) * ao_short) / crit) if evicted
                  else np.zeros(S, dt))
        overdue = q(1.0 if t_host > RL_RTO_S + EPS_T else 0.0)
        rl_pen = q(q(q(q(0.1) * rl_down) / np.maximum(rl, one)) * overdue)
        dep_pen = q(q(q(0.5) * dep) * q(q(rl_down + tm_down) / dark_tot))
        util_pen = q(np.where(util_model > q(QOS_EVICT), q(1e-4), zero))
        availability = q(np.clip(
            q(q(q(q(BASE_AVAILABILITY) - ao_pen) - rl_pen) - dep_pen)
            - util_pen, zero, one))

        class_live = (ao_live, np.broadcast_to(am, (S,)), rl_live, tm_live)
        class_total = (ao, am, rl, tm)
        tier_live = np.zeros((S, N_TIERS), dt)
        for k in range(4):
            frac = q(class_live[k] / np.maximum(class_total[k], q(1e-9)))
            tier_live = q(tier_live + q(tier_class[None, :, k]
                                        * frac[:, None]))

        step = q(np.maximum(q(t - prev_t), zero))
        avail_int = q(avail_int + q(availability * step))
        avail_min = q(np.minimum(avail_min, availability))
        util_peak = q(np.maximum(util_peak, util_model))
        cloud_peak = q(np.maximum(cloud_peak, cloud_prov))
        below = q(tier_live / tier_total[None, :]) < q(RESTORE_THRESH)
        restore_t = np.where(~below & below_seen & np.isinf(restore_t),
                             t, restore_t).astype(dt)
        below_seen = below_seen | below
        prev_t = t

    span = q(np.maximum(q(q(ts[-1]) - q(ts[0])), q(1e-9)))
    availability_mean = q(avail_int / span)
    oc_cap_s = q(stateless * q(q(g["overcommit_factor"]) - one))
    preempt_resident = q(q(rl + tm) * q(one - evict))
    preempt_fit = preempt_resident <= q(oc_cap_s + q(1e-6))
    dep_ok = dep <= zero
    avail_ok = availability_mean >= q(BASE_AVAILABILITY - AVAIL_SLA_TOL)
    am_stranded = q(am - am_in_burst)
    busy_post = q(q(q(q(ao * q(DEMAND_CRIT)) * mult)
                    + q(q(am_stranded * q(DEMAND_CRIT)) * mult))
                  + q(preempt_resident * q(DEMAND_PRE)))
    util_post = q(np.minimum(one, q(busy_post / np.maximum(stateless, one))))
    util_ok = util_post <= q(QOS_EVICT)
    rl_rto_met = rl_done_t <= q(q(RL_RTO_S) + eps)
    sla_ok = (ao_ok & rl_rto_met & preempt_fit & dep_ok & avail_ok & util_ok
              & (am_done_t <= q(1800.0)) & (burst_full_t <= q(1200.0)))
    return {
        "burst_full_s": burst_full_t, "am_done_s": am_done_t,
        "rl_done_s": rl_done_t, "rl_rto_met": rl_rto_met, "ao_ok": ao_ok,
        "ao_short_cores": ao_short, "rl_shortfall_cores": rl_shortfall,
        "cloud_grant_cores": total_cloud, "cloud_arrival_s": cloud_arrival_t,
        "peak_cloud_cores": cloud_peak,
        "availability_mean": availability_mean, "availability_min": avail_min,
        "util_peak": util_peak, "util_post": util_post,
        "time_to_restore_s": np.where(below_seen, restore_t,
                                      zero).astype(dt),
        "preempt_fit": preempt_fit, "dep_ok": dep_ok, "avail_ok": avail_ok,
        "util_ok": util_ok, "sla_ok": sla_ok,
    }


# ---------------------------------------------------------------------------
# blackhole propagation
# ---------------------------------------------------------------------------


def fixed_point(n: int, src: np.ndarray, dst: np.ndarray, closed: np.ndarray,
                dark: np.ndarray, max_rounds: int = 0
                ) -> Tuple[np.ndarray, int]:
    """Least fixed point of ``broken = dark | {caller of a fail-close edge
    whose callee is broken}`` for each row of ``dark`` (S, n).  Rounds count
    every sweep, the last (unchanged) one included; ``max_rounds`` > 0 stops
    after that many sweeps."""
    src = np.asarray(src, np.int64)[closed]
    dst = np.asarray(dst, np.int64)[closed]
    order = np.argsort(src, kind="stable")
    src, dst = src[order], dst[order]
    callers, starts = np.unique(src, return_index=True)
    broken = np.array(dark, bool, copy=True)
    limit = n + 1 if max_rounds <= 0 else max_rounds
    rounds = 0
    while rounds < limit:
        rounds += 1
        if len(src) == 0:
            break
        hit = np.logical_or.reduceat(broken[:, dst], starts, axis=1)
        new = broken.copy()
        new[:, callers] |= hit
        changed = bool((new != broken).any())
        broken = new
        if not changed:
            break
    return broken, rounds


def blackhole_draws(n: int, preemptible: np.ndarray, seed: int,
                    n_scenarios: int):
    """The ensemble's draws: fractions uniform on [0.05, 1), one shared
    uniform per service, and scenario s darkens every preemptible service
    whose uniform lies below its fraction."""
    rng = np.random.default_rng(seed)
    fractions = rng.uniform(0.05, 1.0, n_scenarios)
    u = rng.random(n)
    return fractions, (u[None, :] < fractions[:, None]) & preemptible[None, :]


# ---------------------------------------------------------------------------
# runtime fail-close detection
# ---------------------------------------------------------------------------


def edge_weights(tier: np.ndarray, src: np.ndarray,
                 dst: np.ndarray) -> np.ndarray:
    """Per-edge RPC volume: the Table 2 cell volume split evenly over the
    edges of that (caller tier, callee tier) cell, stored as float32."""
    cell = tier[src].astype(np.int64) * N_TIERS + tier[dst]
    counts = np.bincount(cell, minlength=N_TIERS * N_TIERS)
    return (TABLE2.ravel()[cell] / np.maximum(counts[cell], 1)
            ).astype(np.float32)


def _alias(p: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Vose alias tables: two stacks of under- and over-full buckets,
    popped from the end; leftovers keep probability 1."""
    n = len(p)
    scaled = [float(v) * n for v in p]
    prob = np.ones(n, np.float32)
    alias = np.arange(n, dtype=np.int32)
    small = [i for i in range(n) if scaled[i] < 1.0]
    large = [i for i in range(n) if scaled[i] >= 1.0]
    while small and large:
        s = small.pop()
        g = large.pop()
        prob[s] = scaled[s]
        alias[s] = g
        scaled[g] = scaled[g] - (1.0 - scaled[s])
        if scaled[g] < 1.0:
            small.append(g)
        else:
            large.append(g)
    return prob, alias


def sampling_tables(weight: np.ndarray, unsafe: np.ndarray, seed: int):
    """The sampler's edge distribution for one job: cold paths (a random
    18% of the fail-close edges, drawn from ``seed``) carry 1% of their
    traffic.  Returns ``(prob, alias, cold)``."""
    rng = np.random.default_rng(seed)
    cold = unsafe & (rng.random(len(unsafe)) < COLD_PATH_FRACTION)
    w = np.asarray(weight, np.float64)
    w = np.where(cold, w * COLD_TRAFFIC_FACTOR, w)
    prob, alias = _alias(w / w.sum())
    return prob, alias, cold


def stream_counts(bits: np.ndarray, prob: np.ndarray, alias: np.ndarray,
                  unsafe: np.ndarray) -> np.ndarray:
    """Outcome counts ``(n_edges, 4)`` of one chunk of records drawn from
    ``bits`` (4, n) uint32: lane 0 picks a bucket, lane 1 accepts it or
    takes its alias, lane 2 draws the callee failure (low 16 bits) and its
    propagation over a fail-close edge (high 16 bits), lane 3 the ambient
    caller error.  Column ``2 * failed + errored``."""
    n_edges = len(prob)
    scale = np.float32(1.0 / (1 << 24))
    u0 = (bits[0] >> 8).astype(np.float32) * scale
    i = np.minimum((u0 * np.float32(n_edges)).astype(np.int32), n_edges - 1)
    v = (bits[1] >> 8).astype(np.float32) * scale
    eid = np.where(v < prob[i], i, alias[i])
    failed = ((bits[2] & 0xFFFF).astype(np.int32)
              < int(AMBIENT_CALLEE_FAILURE * 65536))
    prop = (bits[2] >> 16).astype(np.int32) < int(PROPAGATION_PROB * 65536)
    amb = ((bits[3] >> 8).astype(np.float32) * scale
           < np.float32(AMBIENT_CALLER_ERROR))
    errored = (unsafe[eid] & failed & prop) | amb
    code = failed.astype(np.int64) * 2 + errored
    return np.bincount(eid.astype(np.int64) * 4 + code,
                       minlength=4 * n_edges).reshape(n_edges, 4)


def detect_mask(counts: np.ndarray, tol: float = 1e-5
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Fail-close verdict per edge from its counts (columns: clean, error
    only, failure absorbed, failure propagated): enough failures, errors
    under failure at or above the threshold, and a lift over the error
    rate without failure.  Returns ``(mask, tie)``; ``tie`` marks edges
    whose verdict sits within ``tol`` (relative) of a threshold, where any
    rounding of the ratios decides."""
    c = np.asarray(counts, np.float64)
    calls = c.sum(axis=1)
    failures = c[:, 2] + c[:, 3]
    p_fail = c[:, 3] / np.maximum(failures, 1.0)
    p_ok = c[:, 1] / np.maximum(calls - failures, 1.0)
    floor = np.maximum(p_ok, 1e-4)
    lift = LIFT_THRESHOLD * floor
    mask = ((failures >= MIN_FAILURES) & (p_fail >= PROPAGATION_THRESHOLD)
            & (p_fail >= lift))
    near = lambda a, b: np.abs(a - b) <= tol * np.maximum(np.abs(b), 1e-12)
    tie = ((failures >= MIN_FAILURES)
           & (near(p_fail, PROPAGATION_THRESHOLD) | near(p_fail, lift)
              | near(p_ok, 1e-4)))
    return mask, tie
