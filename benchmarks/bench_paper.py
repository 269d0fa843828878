"""One benchmark per paper table/figure (UFA, CS.DC 2026).

Each function reproduces the table/figure's quantity from this repo's
implementation and returns CSV rows (name, us_per_call, derived) where
``derived`` carries the reproduced numbers next to the paper's claims.

``fleet_scale`` and ``scenario_sweep`` exercise the vectorized FleetState
engine: full paper scale (~22k service-environments) and a vmapped
scenario ensemble with per-scenario SLA verdicts (recorded into the
benchmark JSON via ``record_extra``).
"""

from __future__ import annotations

import time
from typing import List

import numpy as np

from benchmarks.common import Row, record_extra, timed

PAPER_SCALE = 0.05          # fleet synthesized at 5% of Uber's service count
SEED = 7


def _fleet(remediated: bool = True):
    from repro.core.drills import remediate
    from repro.core.service import synthesize_fleet, unsafe_edges
    fleet = synthesize_fleet(scale=PAPER_SCALE, seed=SEED)
    if remediated:
        remediate(fleet, set(unsafe_edges(fleet)))
    return fleet


def bench_table1_tiers() -> List[Row]:
    """Table 1: per-tier baseline core counts."""
    from repro.core.service import fleet_cores, synthesize_fleet
    from repro.core.tiers import BASELINE_CORES

    us, fleet = timed(synthesize_fleet, PAPER_SCALE, SEED)
    cores = fleet_cores(fleet)
    # fleet carries per-region demand = alloc * 0.25 (see service.py)
    errs = []
    for t, c in cores.items():
        target = BASELINE_CORES[t] * PAPER_SCALE * 0.25
        errs.append(abs(c - target) / max(1.0, target))
    derived = (f"tiers=7 total_demand={sum(cores.values()):,.0f} "
               f"max_tier_err={max(errs):.3f} (target shape: Table 1 x "
               f"{PAPER_SCALE} scale x 0.25 demand)")
    return [("table1_tier_capacity", us, derived)]


def bench_table2_rpc_matrix() -> List[Row]:
    """Table 2: cross-tier RPC volume shape + ~50% tier-inverted traffic.
    Array-native trace sampling: one vectorized draw returning
    (edge_id, callee_failed, caller_errored) arrays."""
    from repro.core.dependency import sample_traces, trace_edges

    fleet = _fleet()
    edges = trace_edges(fleet, seed=SEED)
    n = 4_000_000
    us, (eid, _, _) = timed(sample_traces, edges, n, SEED)
    down = edges.callee_tier[eid] > edges.caller_tier[eid]
    frac = float(down.mean())
    rate = n / max(1e-9, us / 1e6)
    derived = (f"rpcs={n} sampled_at={rate:,.0f}/s "
               f"to_lower_tier={frac:.2f} (paper: ~0.5 of 62T/wk)")
    return [("table2_rpc_matrix", us, derived)]


def bench_table4_failover_classes() -> List[Row]:
    """Table 4: per-failure-class behavior and RTO during a peak failover."""
    from repro.core.capacity import RegionCapacity
    from repro.core.omg import Orchestrator

    fleet = _fleet()

    def run():
        region = RegionCapacity.for_fleet("bench", fleet)
        orch = Orchestrator(fleet, region, scale=PAPER_SCALE)
        rep = orch.failover(tv_failover=1.0)
        return orch, rep

    us, (orch, rep) = timed(run, repeat=1)
    derived = (f"always_on=uninterrupted({rep.always_on_ok}) "
               f"active_migrate=0s_downtime(MBB,window={rep.am_migrated_at_s:.0f}s) "
               f"restore_later={rep.rl_restored_at_s:.0f}s(rto_1h_met={rep.rl_rto_met}) "
               f"terminate=down_until_failback (paper Table 4: secs/secs/1hr/none)")
    return [("table4_failover_classes", us, derived)]


def bench_table5_phased_rollout() -> List[Row]:
    """Table 5: phased cores returned via readiness reviews."""
    from repro.core.metrics import phased_rollout

    us, r = timed(phased_rollout)
    derived = (f"total_returned={r['total_returned']:,} "
               f"bbm={r['bbm_cores']:,}({r['bbm_fraction']:.0%}) "
               f"mbb={r['mbb_cores']:,}({r['mbb_fraction']:.0%}) "
               f"(paper: 1.025M at 54/46; Table 5 classes sum to 484K BBM "
               f"- the 66K delta sits in partially-BBM AM phases)")
    return [("table5_phased_cores", us, derived)]


def bench_table6_failclose() -> List[Row]:
    """Table 6: fail-close violations found by runtime vs static analysis."""
    from repro.core.dependency import runtime_analysis
    from repro.core.service import synthesize_fleet, unsafe_edges
    from repro.core.static_analysis import static_analysis

    fleet = synthesize_fleet(scale=0.15, seed=SEED,
                             unsafe_fraction=0.10)  # un-remediated
    us_rt, ra = timed(runtime_analysis, fleet, None, SEED, repeat=1)
    us_st, sa = timed(static_analysis, fleet, SEED, repeat=1)
    truth = set(unsafe_edges(fleet))
    static_extra = (sa["found"] - ra["found"]) & truth
    combined = (ra["found"] | sa["found"]) & truth
    rt_share = len(ra["found"] & truth) / max(1, len(combined))
    rate = ra["n_records"] / max(1e-9, us_rt / 1e6)
    derived = (f"total={len(truth)} runtime={len(ra['found'] & truth)} "
               f"static_extra={len(static_extra)} "
               f"runtime_share={rt_share:.2f} combined_recall="
               f"{len(combined)/max(1,len(truth)):.2f} "
               f"records={ra['n_records']} at {rate:,.0f}/s "
               f"(paper: 4155 total = 3041 runtime 73% + 1114 static)")
    return [("table6_runtime_analysis", us_rt, derived),
            ("table6_static_analysis", us_st,
             f"precision={sa['precision']:.2f} recall={sa['recall']:.2f}")]


def bench_fig2_3_failover_history() -> List[Row]:
    """Figs 2/3: failover minutes fraction + yearly counts."""
    from repro.core.metrics import (failover_counts_history,
                                    failover_minutes_history)

    us, mins = timed(failover_minutes_history)
    counts = failover_counts_history()
    avg_hours = sum(mins.values()) / len(mins) / 60.0
    worst_frac = max(mins.values()) / (365 * 24 * 60)
    derived = (f"avg_full_peak_hours_per_year={avg_hours:.1f} "
               f"worst_year_fraction={worst_frac:.4f} counts={list(counts.values())} "
               f"(paper: <20h/yr avg, 0.23% at the 2021 anomaly, declining)")
    return [("fig2_3_failover_history", us, derived)]


def bench_fig7_burst_conversion() -> List[Row]:
    """Fig 7: batch->burst conversion speed (paper: full in ~8 min;
    240K cores / 2,000 hosts < 20 min)."""
    from repro.core.capacity import RegionCapacity
    from repro.core.omg import Orchestrator

    fleet = _fleet()

    def run():
        region = RegionCapacity.for_fleet("bench", fleet)
        orch = Orchestrator(fleet, region, scale=PAPER_SCALE)
        rep = orch.failover(tv_failover=1.0)
        return region, rep

    us, (region, rep) = timed(run, repeat=1)
    rate_cores_per_s = region.batch.convertible_cores / max(
        1.0, rep.burst_full_at_s - (Orchestrator.BATCH_EVICT_S
                                    + Orchestrator.PREFETCH_S))
    # paper-scale equivalent: 0.25 cores/host/s * 2000 hosts
    paper_20min_ok = (240_000 / (0.25 * 2000)) / 60 < 20
    derived = (f"burst_full_min={rep.burst_full_at_s/60:.1f} "
               f"spawn_rate={rate_cores_per_s:,.0f}cores/s "
               f"paper_scale_240k_under_20min={paper_20min_ok} "
               f"(paper: ~8 min full)")
    return [("fig7_burst_conversion", us, derived)]


def bench_fig8_availability() -> List[Row]:
    """Fig 8: availability holds at 99.97% through failover+failback."""
    from repro.core.capacity import RegionCapacity
    from repro.core.metrics import availability_during_failover
    from repro.core.omg import Orchestrator

    fleet = _fleet(remediated=True)

    def run():
        region = RegionCapacity.for_fleet("bench", fleet)
        orch = Orchestrator(fleet, region, scale=PAPER_SCALE)
        orch.failover(tv_failover=1.0)
        series = availability_during_failover(fleet, orch)
        orch.failback()
        return series

    us, series = timed(run, repeat=1)
    mn = min(a for _, a in series)
    avg = sum(a for _, a in series) / len(series)
    derived = (f"min_availability={mn:.4f} avg={avg:.4f} "
               f"(paper: 99.97% held throughout)")
    return [("fig8_availability", us, derived)]


def bench_fig9_container_conversion() -> List[Row]:
    """Fig 9: container class counts through failover/failback."""
    from repro.core.capacity import RegionCapacity
    from repro.core.omg import Orchestrator
    from repro.core.tiers import FailureClass

    fleet = _fleet()

    def run():
        region = RegionCapacity.for_fleet("bench", fleet)
        orch = Orchestrator(fleet, region, scale=PAPER_SCALE)
        rep = orch.failover(tv_failover=1.0)
        am_b = orch.class_envs(FailureClass.ACTIVE_MIGRATE, "burst")
        rl_b = (orch.class_envs(FailureClass.RESTORE_LATER, "burst")
                + orch.class_envs(FailureClass.RESTORE_LATER, "cloud"))
        term_down = sum(1 for s in orch.se.values()
                        if s.spec.failure_class == FailureClass.TERMINATE
                        and s.placement == "down")
        orch.failback()
        restored = sum(1 for s in orch.se.values() if s.placement == "steady")
        return am_b, rl_b, term_down, restored, len(orch.se)

    us, (am_b, rl_b, term_down, restored, total) = timed(run, repeat=1)
    derived = (f"am_bursted={am_b} rl_bursted={rl_b} "
               f"terminate_down_during_failover={term_down} "
               f"restored_after_failback={restored}/{total} "
               f"(paper Fig 9 shape: AM converts ~15min, RL restores, "
               f"Terminate stays down, all back at failback)")
    return [("fig9_container_conversion", us, derived)]


def bench_fig10_region_utilization() -> List[Row]:
    """Fig 10: surviving-region utilization peaks ~50.2%, within safety."""
    from repro.core.capacity import RegionCapacity
    from repro.core.metrics import regional_utilization_series
    from repro.core.omg import Orchestrator

    fleet = _fleet()

    def run():
        region = RegionCapacity.for_fleet("bench", fleet)
        orch = Orchestrator(fleet, region, scale=PAPER_SCALE)
        orch.failover(tv_failover=1.0)
        return regional_utilization_series(orch)

    us, series = timed(run, repeat=1)
    peak = max(u for _, u in series)
    steady = series[0][1]
    derived = (f"steady_util={steady:.3f} failover_peak_util={peak:.3f} "
               f"under_75pct_threshold={peak < 0.75} (paper: 50.2% peak)")
    return [("fig10_region_utilization", us, derived)]


def bench_fig11_fleet_utilization() -> List[Row]:
    """Fig 11: fleet utilization 20% -> ~31% while returning 1.025M cores."""
    from repro.core.metrics import phased_rollout

    us, r = timed(phased_rollout)
    derived = (f"utilization {0.20:.0%} -> {r['final_utilization']:.1%} "
               f"provisioning {r['provisioning_multiple_before']:.1f}x -> "
               f"{r['provisioning_multiple_after']:.2f}x "
               f"(paper: 20%->31%, 2x->1.5x attained, 1.3x goal)")
    return [("fig11_fleet_utilization", us, derived)]


def bench_eviction_rates() -> List[Row]:
    """§8 eviction analysis: 312/hr failover peak vs 160/hr baseline peak."""
    from repro.core.eviction import failover_eviction_trace

    us, t = timed(failover_eviction_trace, repeat=1)
    derived = (f"failover_peak={t['peak']}/hr baseline_peak={t['baseline_peak']}/hr "
               f"ratio={t['peak_over_baseline']:.2f} (paper: 312 vs 160, ~2x)")
    return [("eviction_rates", us, derived)]


def bench_overcommit() -> List[Row]:
    """§4.4: O_max = 1.66x analytic; simulator recommends 1.5x."""
    from repro.core.overcommit_sim import recommend_factor
    from repro.core.tiers import o_max

    us, r = timed(recommend_factor, repeat=1)
    assert r["safe"], "default config must yield a certified-safe factor"
    derived = (f"o_max={o_max():.2f} recommended={r['recommended']} "
               f"safe={r['safe']} (paper: O_max=1.66, "
               f"simulator-recommended 1.5)")
    return [("overcommit_simulator", us, derived)]


def bench_canary_gate() -> List[Row]:
    """§6: canary gate over a 45-day window of ~8k deployments/week."""
    from repro.core.canary import CanaryRegressionGate

    fleet = _fleet()
    gate = CanaryRegressionGate(fleet, seed=11)
    us, w = timed(gate.run_window, 8000 * 6, repeat=1)
    derived = (f"deployments={w['deployments']} caught={w['regressions_caught']} "
               f"shipped={w['regressions_shipped']} (paper: ~3 caught/45d, 0 shipped)")
    return [("canary_gate", us, derived)]


def bench_fleet_scale() -> List[Row]:
    """Paper scale: ~22k service-environments (Table 3) synthesize and run
    a full peak failover on the vectorized FleetState engine."""
    from repro.core.capacity import RegionCapacity, provisioning_multiple
    from repro.core.drills import certify_fleet_state
    from repro.core.omg import Orchestrator
    from repro.core.service import synthesize_fleet

    def synth():
        fs = synthesize_fleet(scale=1.0, seed=SEED, as_arrays=True)
        fs.apply_ufa_target_classes()
        return fs

    us_synth, fs = timed(synth, repeat=1)

    def run():
        region = RegionCapacity.for_fleet("paper-scale", fs)
        orch = Orchestrator(fs, region, scale=1.0)
        rep = orch.failover(tv_failover=1.0)
        orch.failback()
        return region, rep

    us_fo, (region, rep) = timed(run, repeat=1)
    cert = certify_fleet_state(fs, seed=SEED)
    total = float(fs.spec_cores.sum())
    mult = provisioning_multiple(2 * total, region.steady.physical_cores)
    under_30s = (us_synth + us_fo) / 1e6 < 30.0
    derived = (f"services={fs.n} edges={fs.edges.n} "
               f"synth+failover_s={(us_synth + us_fo)/1e6:.2f} "
               f"under_30s={under_30s} ufa_mult={mult:.2f} "
               f"ao_ok={rep.always_on_ok} rl_rto={rep.rl_rto_met} "
               f"drill_flagged={cert['n_flagged']}/{cert['n_critical']} "
               f"(paper: 22k SEs, 2x->1.3x goal)")
    return [("fleet_scale_synthesis", us_synth,
             f"services={fs.n} array-native path"),
            ("fleet_scale_failover", us_fo, derived)]


def bench_scenario_sweep() -> List[Row]:
    """Scenario-ensemble driver: >= 256 failover variants (traffic mult x
    preheat delay x burst availability x cloud quota) in one vmapped
    sweep; per-scenario SLA verdicts land in the benchmark JSON."""
    from repro.core.scenarios import (FleetAggregates, scenario_grid,
                                      scenario_records, summarize_sweep,
                                      sweep_scenarios)
    from repro.core.service import synthesize_fleet

    fs = synthesize_fleet(scale=1.0, seed=SEED, as_arrays=True)
    fs.apply_ufa_target_classes()
    agg = FleetAggregates.from_fleet_state(fs)
    grid = scenario_grid()
    # compile (cold) vs steady-state (warm) reported as separate rows:
    # the first call pays tracing+XLA compilation, the warm row is the
    # per-sweep marginal cost the ensembles actually run at
    us_cold, _ = timed(sweep_scenarios, agg, grid, repeat=1)
    us, res = timed(sweep_scenarios, agg, grid, repeat=3)
    s = summarize_sweep(res)
    record_extra("scenario_sweep", {"summary": s,
                                    "cold_us": us_cold, "warm_us": us,
                                    "scenarios": scenario_records(res)})
    derived = (f"scenarios={s['n_scenarios']} sla_ok={s['n_sla_ok']} "
               f"avail_min={s['availability_min']:.4f} "
               f"avail_mean={s['availability_mean']:.4f} "
               f"worst_rl_min={s['worst_rl_done_min']:.1f} "
               f"(ensemble certification, Basiri-style)")
    return [("scenario_sweep_cold", us_cold,
             f"first call, includes jit compile"),
            ("scenario_sweep_vmap", us, derived)]


def bench_runtime_detection_scale() -> List[Row]:
    """Paper-scale runtime layer acceptance: the array-native telemetry
    engine samples + ingests ~48M RPCs (default ~400 obs/edge over ~120k
    edges, the regime of the paper's 62T RPCs/week) and detects fail-close
    edges end to end at scale=1.0.  Asserts >10M records/s sustained
    through generation+ingest and single-digit-second end-to-end
    detection."""
    from repro.core.dependency import runtime_analysis
    from repro.core.service import synthesize_fleet

    fs = synthesize_fleet(scale=1.0, seed=SEED, as_arrays=True,
                          unsafe_fraction=0.10)
    us, ra = timed(runtime_analysis, fs, None, SEED, repeat=1)
    total_s = us / 1e6
    rate = ra["records_per_s"]
    assert rate > 10e6, f"gen+ingest {rate:,.0f} rec/s (need >10M/s)"
    assert total_s < 10.0, f"end-to-end {total_s:.1f}s (need <10s)"
    record_extra("runtime_detection_scale", {
        "services": fs.n, "edges": fs.edges.n,
        "n_records": ra["n_records"],
        "gen_ingest_s": ra["gen_ingest_s"],
        "records_per_s": rate,
        "end_to_end_s": total_s,
        "precision": ra["precision"], "recall": ra["recall"],
        "missed": ra["missed"], "missed_cold": ra["missed_cold"],
    })
    derived = (f"backend=cpu-numpy-fused services={fs.n} "
               f"edges={fs.edges.n} records={ra['n_records']/1e6:.1f}M "
               f"gen+ingest={rate/1e6:.1f}M/s end_to_end_s={total_s:.2f} "
               f"precision={ra['precision']:.2f} recall={ra['recall']:.2f} "
               f"missed_cold={ra['missed_cold']}/{ra['missed']} "
               f"(acceptance: >10M rec/s, <10s at scale=1.0)")
    rows = [("runtime_detection_scale", us, derived)]

    # backend-labelled ingest rows: the same chunk through the fused
    # single-pass host bincount (the CPU production path behind
    # ``ingest_batch``) and the Pallas histogram kernel in
    # interpret mode (the accelerator path; interpret wall clock tracks
    # the trajectory, it is not a device projection)
    import jax.numpy as jnp

    from repro.kernels.ufa.ingest import ingest_hist

    rng = np.random.default_rng(SEED)
    n_edges = fs.edges.n
    n_rec = 4_000_000
    eid = rng.integers(0, n_edges, n_rec)
    code = ((rng.random(n_rec) < 0.3).astype(np.uint8) << 1) \
        | (rng.random(n_rec) < 0.4)

    def numpy_fused():
        return np.bincount(eid.astype(np.int32) * 4 + code,
                           minlength=4 * n_edges).reshape(-1, 4)

    us_np, counts_np = timed(numpy_fused, repeat=3)
    rows.append(("runtime_ingest_fused_numpy", us_np,
                 f"backend=cpu {n_rec/1e6:.0f}M records x {n_edges} edges, "
                 f"{n_rec/(us_np/1e6)/1e6:.1f}M rec/s"))

    eid_d = jnp.asarray(eid)
    failed_d = jnp.asarray(code >= 2)
    errored_d = jnp.asarray((code & 1).astype(bool))

    def pallas_ingest():
        return np.asarray(ingest_hist(eid_d, failed_d, errored_d, n_edges,
                                      interpret=True))

    us_cold, _ = timed(pallas_ingest, repeat=1)
    us_warm, counts_pl = timed(pallas_ingest, repeat=3)
    assert np.array_equal(counts_pl, counts_np)       # exact, both paths
    rows.append(("runtime_ingest_pallas_interp_cold", us_cold,
                 "backend=cpu-interpret, includes jit compile"))
    rows.append(("runtime_ingest_pallas_interp", us_warm,
                 f"backend=cpu-interpret {n_rec/1e6:.0f}M records, "
                 f"{n_rec/(us_warm/1e6)/1e6:.1f}M rec/s, bit-equal to "
                 f"the numpy path"))
    return rows


def bench_graph_propagation() -> List[Row]:
    """Graph engine acceptance: full-fleet multi-hop blackhole
    certification at paper scale (~22k SEs, with relay chains) PLUS a
    256-scenario vmapped blackhole ensemble in < 5 s on CPU; then the
    greedy hardening planner runs the fleet to certified."""
    from repro.core.fleet_state import synthesize_fleet_state
    from repro.graph import (CallGraph, blackhole_ensemble, certify,
                             plan_hardening)

    fs = synthesize_fleet_state(scale=1.0, seed=SEED,
                                unsafe_chain_fraction=0.05)
    graph = CallGraph.from_fleet_state(fs)

    def cert_plus_ensemble():
        cert = certify(graph)
        ens = blackhole_ensemble(graph, n_scenarios=256, seed=SEED)
        return cert, ens

    # first call in this process; earlier benches may already have
    # compiled the (1, n) certify shape, so this is an upper bound on the
    # warm path and a lower bound on a truly fresh-process cold start —
    # the ensemble's (256, n) shape does compile here
    us_cert, (cert, ens) = timed(cert_plus_ensemble, repeat=1)
    us_warm, _ = timed(cert_plus_ensemble, repeat=3)
    under_5s = us_cert / 1e6 < 5.0
    us_plan, plan = timed(plan_hardening, graph, repeat=1)
    record_extra("graph_propagation", {
        "services": graph.n, "edges": graph.n_edges,
        "unsafe_edges": graph.n_unsafe,
        "broken_critical": cert.n_broken_critical,
        "multi_hop_only": int(cert.multi_hop.sum()),
        "propagation_rounds": cert.rounds,
        "first_call_cert_plus_256_ensemble_s": us_cert / 1e6,
        "warm_cert_plus_256_ensemble_s": us_warm / 1e6,
        "under_5s": under_5s,
        "ensemble_ok_fraction": float(ens["ok"].mean()),
        "hardened_edges": plan.n_hardened,
        "planner_rounds": plan.rounds,
        "planner_certified": plan.certified,
        "hardening_trajectory": plan.trajectory,
    })
    derived = (f"services={graph.n} edges={graph.n_edges} "
               f"unsafe={graph.n_unsafe} broken_crit={cert.n_broken_critical} "
               f"multi_hop={int(cert.multi_hop.sum())} "
               f"rounds={cert.rounds} first_call_s={us_cert/1e6:.2f} "
               f"under_5s={under_5s} (acceptance: cert + 256-ensemble < 5s)")
    derived_plan = (f"hardened={plan.n_hardened} rounds={plan.rounds} "
                    f"certified={plan.certified} "
                    f"(paper: 4,000+ hardened before dropping the 2x buffer)")
    return [("graph_certify_plus_ensemble", us_cert, derived),
            ("graph_certify_plus_ensemble_warm", us_warm,
             f"warm path, jit cached"),
            ("graph_hardening_planner", us_plan, derived_plan)]


def bench_timeline_ensemble() -> List[Row]:
    """Temporal-drill acceptance: the discrete-time failover kernel
    (lax.scan over 240 steps x vmap over 256 scenarios) runs a full-peak
    temporal ensemble for the paper-scale fleet in < 5 s on CPU,
    including compilation — per-scenario time-to-restore per tier,
    availability integral vs the 99.97% SLA, and peak on-demand draw."""
    from repro.core.capacity import RegionCapacity
    from repro.core.omg import Orchestrator
    from repro.core.scenarios import operating_point_mask, scenario_grid
    from repro.core.service import synthesize_fleet
    from repro.core.timeline_sim import (default_ts,
                                         summarize_timeline_sweep,
                                         sweep_timeline)

    fs = synthesize_fleet(scale=1.0, seed=SEED, as_arrays=True)
    fs.apply_ufa_target_classes()
    region = RegionCapacity.for_fleet("timeline", fs)
    orch = Orchestrator(fs, region, scale=1.0)
    cfg = orch.timeline_config()
    grid = scenario_grid()
    ts = default_ts(7200.0, 240)

    us_cold, res = timed(sweep_timeline, cfg, grid, ts, repeat=1)
    under_5s = us_cold / 1e6 < 5.0
    assert under_5s, (f"temporal ensemble first call {us_cold/1e6:.1f}s "
                      f"(acceptance: 256x240 < 5s)")
    us_warm, res = timed(sweep_timeline, cfg, grid, ts, repeat=3)
    s = summarize_timeline_sweep(res)
    # temporal vs event-loop cross-check: the orchestrator's single
    # trajectory must agree with the kernel's operating-point scenario
    rep = orch.failover(tv_failover=1.0)
    op = operating_point_mask(grid)
    op_rl_done = float(res["rl_done_s"][op][0])
    agree = abs(op_rl_done - rep.rl_restored_at_s) <= max(
        60.0, 0.05 * rep.rl_restored_at_s)
    assert agree, (f"kernel op-point rl_done {op_rl_done:.0f}s vs "
                   f"orchestrator {rep.rl_restored_at_s:.0f}s")
    record_extra("timeline_ensemble", {
        "scenarios": s["n_scenarios"], "steps": len(ts),
        "first_call_s": us_cold / 1e6, "warm_s": us_warm / 1e6,
        "under_5s": under_5s, "summary": s,
        "orchestrator_rl_done_s": rep.rl_restored_at_s,
        "kernel_op_rl_done_s": op_rl_done,
        "orchestrator_agreement": agree,
    })
    derived = (f"scenarios={s['n_scenarios']}x{len(ts)}steps "
               f"first_call_s={us_cold/1e6:.2f} under_5s={under_5s} "
               f"sla_ok={s['n_sla_ok']} rl_stranded={s['n_rl_never_restored']} "
               f"avail_floor={s['availability_floor']:.4f} "
               f"peak_cloud={s['peak_cloud_cores_max']:,.0f} "
               f"orch_agree={agree} (acceptance: 256x240 temporal "
               f"ensemble < 5s)")
    return [("timeline_ensemble", us_cold, derived),
            ("timeline_ensemble_warm", us_warm,
             f"warm path, jit cached, {s['n_scenarios']} scenarios")]


def bench_fused_sweep_scale() -> List[Row]:
    """Fused sweep engine acceptance: the single-jit analytic + timeline
    + dependency pipeline sweeps paper-scale temporal ensembles at grid
    sizes {256, 4k, 64k}, reporting compile (cold) and steady-state
    (warm) separately per size.  Asserts (a) no recompilation across
    sizes within a padding bucket, and (b) >= 10x the per-scenario warm
    rate of the PR-4 composed path (separate jits, trace
    materialization, host round-trips) measured in-process at 256 — the
    BENCH_4 ``timeline_ensemble`` configuration."""
    from repro.core.capacity import RegionCapacity
    from repro.core.omg import Orchestrator
    from repro.core.scenarios import (FleetAggregates, scenario_grid,
                                      sweep_scenarios)
    from repro.core.service import synthesize_fleet
    from repro.core.sweep_engine import (bucket_shape, compiled_variants,
                                         tile_grid)
    from repro.core.timeline_sim import default_ts, sweep_timeline
    from repro.graph import CallGraph, blackhole_ensemble

    fs = synthesize_fleet(scale=1.0, seed=SEED, as_arrays=True)
    fs.apply_ufa_target_classes()
    graph = CallGraph.from_fleet_state(fs)
    region = RegionCapacity.for_fleet("fused", fs)
    orch = Orchestrator(fs, region, scale=1.0)
    eng = orch.sweep_engine(graph=graph, seed=SEED)
    agg = FleetAggregates.from_fleet_state(fs)
    cfg = orch.timeline_config()
    base = scenario_grid()
    ts = default_ts(7200.0, 240)

    # baseline: the composed PR-4 pipeline at 256 scenarios — three
    # separate jitted stages with host round-trips, the timeline stage
    # materializing the full (S, T, series) trace stack
    def composed():
        ens = blackhole_ensemble(graph, seed=SEED,
                                 fractions=np.asarray(
                                     base["evict_fraction"]))
        res = sweep_scenarios(agg, base,
                              dep_broken_frac=ens["broken_critical_frac"])
        tres = sweep_timeline(cfg, grid=base, ts=ts,
                              dep_broken_frac=np.asarray(
                                  ens["broken_critical_frac"]),
                              return_traces=True)
        return res, tres

    composed()                                   # warm the composed jits
    us_composed, _ = timed(composed, repeat=3)
    composed_rate = 256 / (us_composed / 1e6)

    rows: List[Row] = []
    scaling = []
    rates = {}
    for n in (256, 4096, 65536):
        grid = tile_grid(base, n)
        us_cold, _ = timed(eng.run, grid, repeat=1)
        us_warm, res = timed(eng.run, grid, repeat=3)
        rate = n / (us_warm / 1e6)
        rates[n] = rate
        scaling.append({"scenarios": n, "cold_s": us_cold / 1e6,
                        "warm_s": us_warm / 1e6, "scenarios_per_s": rate,
                        "bucket": bucket_shape(n),
                        "n_sla_ok": int(res["sla_ok"].sum()),
                        "n_t_sla_ok": int(res["t_sla_ok"].sum())})
        rows.append((f"fused_sweep_{n}_cold", us_cold,
                     f"first call at this bucket, includes jit compile"))
        rows.append((f"fused_sweep_{n}", us_warm,
                     f"warm, {rate:,.0f} scen/s, bucket={bucket_shape(n)}"))

    # (a) bucket reuse: 40960 pads to the same (16, 4096) bucket as 64k —
    # must NOT add a compiled variant
    variants = compiled_variants()
    eng.run(tile_grid(base, 40960))
    no_recompile = compiled_variants() == variants
    assert no_recompile, "re-compiled within a padding bucket"

    # (b) the paper-scale acceptance: >= 64k-scenario temporal+dependency
    # ensemble with warm throughput >= 10x the composed per-scenario rate
    speedup = rates[65536] / composed_rate
    assert speedup >= 10.0, (
        f"fused 64k rate {rates[65536]:,.0f}/s is only {speedup:.1f}x the "
        f"composed 256-scenario rate {composed_rate:,.0f}/s (need >=10x)")

    # backend-labelled reducer rows: the same 256-scenario grid with the
    # timeline carry through the segmented Pallas verdict-reduction
    # kernel (interpret mode on CPU — trajectory only; the dispatch
    # default keeps plain CPU on the bit-exact scan path)
    eng_pal = orch.sweep_engine(graph=graph, seed=SEED, reducer="pallas")
    grid256 = tile_grid(base, 256)
    us_pcold, _ = timed(eng_pal.run, grid256, repeat=1)
    us_pwarm, pres = timed(eng_pal.run, grid256, repeat=3)
    pal_rate = 256 / (us_pwarm / 1e6)
    rows.append(("fused_sweep_256_pallas_cold", us_pcold,
                 "reducer=pallas backend=cpu-interpret, includes compile"))
    rows.append(("fused_sweep_256_pallas", us_pwarm,
                 f"reducer=pallas backend=cpu-interpret, "
                 f"{pal_rate:,.0f} scen/s"))

    record_extra("fused_sweep_scale", {
        "composed_256_rate_per_s": composed_rate,
        "composed_256_warm_s": us_composed / 1e6,
        "fused_scaling": scaling,
        "speedup_vs_composed_64k": speedup,
        "no_recompile_within_bucket": no_recompile,
        "devices": len(eng.devices),
        "pallas_reducer_256": {"cold_s": us_pcold / 1e6,
                               "warm_s": us_pwarm / 1e6,
                               "scenarios_per_s": pal_rate,
                               "n_t_sla_ok": int(pres["t_sla_ok"].sum())},
    })
    rows.append(("fused_sweep_composed_baseline", us_composed,
                 f"PR-4 composed path, 256 scen, "
                 f"{composed_rate:,.0f} scen/s"))
    rows.append(("fused_sweep_speedup", 0.0,
                 f"64k fused at {rates[65536]:,.0f} scen/s = "
                 f"{speedup:.1f}x composed (assert >=10x) on "
                 f"{len(eng.devices)} device(s)"))

    # (c) observability agreement: with the metrics plane ON, the
    # engine's self-reported interior throughput (the
    # ufa_sweep_scenarios_per_s gauge) must agree with the harness's
    # exterior wall-clock measurement of the SAME warm call within 5% —
    # i.e. the plane reports the truth and costs ~nothing
    from repro import obs
    grid4k = tile_grid(base, 4096)
    eng.run(grid4k)                       # warm this bucket with obs off
    was_on = obs.enabled()
    obs.enable()
    try:
        t0 = time.perf_counter()
        eng.run(grid4k)
        ext_s = time.perf_counter() - t0
        ext_rate = 4096 / ext_s
        int_rate = obs.value("ufa_sweep_scenarios_per_s")
        rel = abs(int_rate - ext_rate) / ext_rate
    finally:
        if not was_on:
            obs.disable()
    assert rel <= 0.05, (
        f"obs-reported rate {int_rate:,.0f}/s disagrees with measured "
        f"{ext_rate:,.0f}/s by {rel:.1%} (need <=5%)")
    record_extra("fused_sweep_obs_agreement", {
        "interior_scen_per_s": int_rate, "exterior_scen_per_s": ext_rate,
        "relative_error": rel})
    rows.append(("fused_sweep_obs_agreement", ext_s * 1e6,
                 f"metrics on: gauge {int_rate:,.0f} scen/s vs measured "
                 f"{ext_rate:,.0f} scen/s ({rel:.2%} apart, assert <=5%)"))
    return rows


def bench_chaos_campaign() -> List[Row]:
    """Adversarial chaos-campaign acceptance: on the paper-scale
    hardened fleet, bandit-allocated bisection localizes the
    SLA-violating frontier along >= 3 fault-severity rays to 1/64
    severity resolution with >= 10x fewer engine scenario-evaluations
    than an exhaustive per-ray grid at the same resolution; every
    logged probe verdict replays bit-identically on an independent
    engine; the whole campaign is reproducible from one seed."""
    from repro import obs
    from repro.chaos import campaign_for_fleet, verify_report
    from repro.core.service import synthesize_fleet
    from repro.graph import CallGraph
    from repro.graph.planner import plan_hardening

    fs = synthesize_fleet(scale=PAPER_SCALE, seed=SEED, as_arrays=True)
    fs.apply_ufa_target_classes()
    # harden the critical call paths first — the chaos campaign probes
    # the fleet the paper actually certifies (the unhardened fleet
    # already fails dep_ok at its own operating point)
    graph = CallGraph.from_fleet_state(fs)
    plan = plan_hardening(graph)
    fs.edges.fail_open[graph.input_edge_indices(plan.hardened_edges)] = True

    tol = 1.0 / 64.0
    obs.enable()
    try:
        us_cold, rep = timed(
            lambda: campaign_for_fleet(fs, seed=SEED, tol=tol).run(),
            repeat=1)
        evals_metered = obs.value("ufa_chaos_evals_total")
    finally:
        obs.disable()
    # warm pass doubles as the single-seed reproducibility check: the
    # jit cache is hot, and a fresh campaign from the same seed must
    # produce a byte-identical report
    us_warm, rep2 = timed(
        lambda: campaign_for_fleet(fs, seed=SEED, tol=tol).run(), repeat=1)
    assert rep.to_json(sort_keys=True) == rep2.to_json(sort_keys=True), \
        "campaign is not reproducible from its seed"
    assert evals_metered == rep.n_evals, (
        f"obs metered {evals_metered} evals, report says {rep.n_evals}")

    assert rep.op_ok, "hardened paper fleet must pass its operating point"
    assert rep.n_localized >= 3, (
        f"only {rep.n_localized} rays localized (need >=3): "
        f"{[(r.name, r.status) for r in rep.rays]}")
    speedup = rep.speedup_vs_grid
    assert speedup is not None and speedup >= 10.0, (
        f"{rep.n_evals} evals vs grid-equivalent {rep.grid_equiv_evals} "
        f"is only {speedup:.1f}x (need >=10x)")

    # bit-exact audit: replay EVERY probe (frontiers, counterexamples,
    # brackets) through an independent engine in one batch
    fresh = campaign_for_fleet(fs, seed=SEED, tol=tol)
    us_verify, audit = timed(lambda: verify_report(rep, fresh.engine),
                             repeat=1)
    assert audit["n_probes"] == rep.n_evals and not audit["mismatches"]

    frontier = {r.name: round(r.frontier_severity, 6) for r in rep.rays
                if r.frontier_severity is not None}
    record_extra("chaos_campaign", {
        "tol": tol, "seed": SEED, "op_ok": rep.op_ok,
        "n_evals": rep.n_evals, "n_rounds": rep.n_rounds,
        "grid_equiv_evals": rep.grid_equiv_evals,
        "speedup_vs_grid": speedup, "n_localized": rep.n_localized,
        "frontier_severity": frontier,
        "rays": {r.name: r.status for r in rep.rays},
        "counterexamples": {r.name: r.counterexample for r in rep.rays
                            if r.status == "localized"},
        "reverified_probes": audit["n_probes"],
    })
    return [
        ("chaos_campaign_cold", us_cold,
         f"first campaign incl. jit compile; {rep.n_evals} evals over "
         f"{rep.n_rounds} rounds"),
        ("chaos_campaign", us_warm,
         f"{rep.n_localized} rays localized to 1/{round(1 / tol)}, "
         f"{rep.n_evals} evals vs {rep.grid_equiv_evals} grid "
         f"({speedup:.1f}x, assert >=10x)"),
        ("chaos_verify", us_verify,
         f"bit-exact replay of {audit['n_probes']} probes on an "
         f"independent engine"),
    ]


def bench_capacity_opt() -> List[Row]:
    """Capacity-optimizer acceptance: on the paper-scale hardened fleet
    the two-mode search (grad anneal + CEM polish) must come in at
    <= 1.4x provisioned/steady while the hard engine certifies every
    scenario of the 48-point ensemble at >= 99.97 % availability, and
    the soft gradient must agree with central finite differences."""
    import jax
    import jax.numpy as jnp

    from repro.core.service import synthesize_fleet
    from repro.core.timeline_sim import default_ts
    from repro.graph import CallGraph
    from repro.graph.planner import plan_hardening
    from repro.optim import hardening_weights, optimize_capacity
    from repro.optim.capacity import (DesignBase, _grid_cols,
                                      certification_grid, make_knobs,
                                      soft_loss)

    fs = synthesize_fleet(scale=PAPER_SCALE, seed=SEED, as_arrays=True)
    fs.apply_ufa_target_classes()
    graph = CallGraph.from_fleet_state(fs)
    plan = plan_hardening(graph)
    fs.edges.fail_open[graph.input_edge_indices(plan.hardened_edges)] = True

    us_opt, res = timed(lambda: optimize_capacity(fs, mode="both"),
                        repeat=1)
    v = res.verification
    assert res.improved, (res.start_multiple, res.provisioning_multiple)
    assert res.provisioning_multiple <= 1.4, res.provisioning_multiple
    assert v["all_ok"], v
    assert v["availability_min"] >= 0.9997 - 1e-9, v["availability_min"]

    # gradient spot-check vs central differences (buffer knob, tau=1)
    base = DesignBase.from_fleet_state(fs).as_arrays()
    cols = _grid_cols(certification_grid())
    ts = jnp.asarray(default_ts(), jnp.float32)
    tau = jnp.asarray(1.0, jnp.float32)
    pen = jnp.asarray(200.0, jnp.float32)
    knobs = make_knobs(buffer=0.6, promote=(0.4, 0.3, 0.2),
                       overcommit=1.4, ramp=0.9, evict_lambda=0.2)
    g = float(jax.grad(soft_loss)(knobs, base, cols, ts, tau, pen)
              ["buffer"])
    eps = 0.05
    hi = dict(knobs, buffer=knobs["buffer"] + eps)
    lo = dict(knobs, buffer=knobs["buffer"] - eps)
    fd = float((soft_loss(hi, base, cols, ts, tau, pen)
                - soft_loss(lo, base, cols, ts, tau, pen)) / (2 * eps))
    assert abs(g - fd) <= 0.08 * max(abs(fd), abs(g)), (g, fd)

    us_w, w = timed(lambda: hardening_weights(fs, graph, knobs=res.knobs),
                    repeat=1)
    wplan = plan_hardening(graph, service_weights=w)
    assert wplan.certified

    record_extra("capacity_opt", {
        "start_multiple": round(res.start_multiple, 4),
        "optimized_multiple": round(res.provisioning_multiple, 4),
        "design": {k: (round(float(x), 4) if not getattr(x, "ndim", 0)
                       else None) for k, x in res.design.items()
                   if not getattr(x, "ndim", 0)},
        "n_scenarios": v["n_scenarios"], "all_ok": v["all_ok"],
        "availability_min": round(v["availability_min"], 6),
        "grad_vs_fd": {"grad": round(g, 5), "fd": round(fd, 5)},
        "weighted_plan_edges": len(wplan.hardened_edges),
        "weighted_plan_certified": wplan.certified,
    })
    return [
        ("capacity_opt", us_opt,
         f"{res.start_multiple:.2f}x -> {res.provisioning_multiple:.2f}x "
         f"(assert <=1.4x), {v['n_scenarios']} scenarios hard-certified "
         f"at min avail {v['availability_min']:.4f}"),
        ("capacity_hardening_weights", us_w,
         f"availability-gradient blast-radius weights; weighted plan "
         f"{len(wplan.hardened_edges)} edges certified={wplan.certified}"),
    ]


def bench_serving_failover() -> List[Row]:
    """Live-workload failover acceptance: the timeline kernel's capacity
    traces actuate a real serving pool through a scripted full-peak
    failover under open-loop Poisson load, and the *measured request*
    verdicts show §4.2's differentiated SLAs — critical availability
    >= 99.97 % with no burn-rate alert, the preemptible tier preempted,
    blacked out (user-visible alert) and restored within its RTO.  The
    drill is bit-deterministic per spec, and a chaos campaign over the
    request-plane fault families localizes the SLA frontier with a
    bit-exact oracle replay."""
    import dataclasses

    from repro.chaos import verify_report
    from repro.core.tiers import FailureClass, RTO_SECONDS
    from repro.serving import (DrillSpec, drill_oracle, request_campaign,
                               run_drill)

    spec = DrillSpec()
    rto = RTO_SECONDS[FailureClass.RESTORE_LATER]
    us_cold, rep = timed(lambda: run_drill(spec), repeat=1)
    # warm pass doubles as the determinism check: pooled engines and a
    # hot jit cache must reproduce every verdict bit for bit
    us_warm, rep2 = timed(lambda: run_drill(spec), repeat=1)
    assert all(rep.tiers[t].as_dict() == rep2.tiers[t].as_dict()
               for t in rep.tiers), "drill is not deterministic"

    crit, pre = rep.crit, rep.pre
    assert rep.sla_ok, "drill SLA verdict failed"
    assert crit.availability >= 0.9997, crit.availability
    assert not crit.slo_alert
    assert crit.p99_s <= spec.crit_p99_slo_s, crit.p99_s
    assert pre.preempted > 0 and pre.requeued > 0
    assert pre.slo_alert, "blackout must be user-visible on the pre tier"
    assert pre.time_to_restore_s <= rto, pre.time_to_restore_s

    # request-plane chaos: a cheaper drill spec keeps the campaign tight
    small = dataclasses.replace(spec, n_steps=48, ticks_per_step=4,
                                crit_rps=0.03, pre_rps=0.02,
                                max_new_tokens=2, seed=11)
    us_camp, crep = timed(
        lambda: request_campaign(small, tol=1.0 / 8.0, max_rounds=5).run(),
        repeat=1)
    assert crep.op_ok and crep.n_localized >= 1, (
        [(r.name, r.status) for r in crep.rays])
    us_verify, audit = timed(
        lambda: verify_report(crep, oracle=drill_oracle(small)), repeat=1)
    assert audit["n_probes"] == crep.n_evals and not audit["mismatches"]

    record_extra("serving_failover", {
        "spec_seed": spec.seed, "horizon_s": spec.horizon_s,
        "users_served": round(rep.users_served),
        "actuation_log": [(t, tier.name, tgt)
                          for t, tier, tgt in rep.actuation_log],
        "tiers": {v.tier: v.as_dict() for v in rep.tiers.values()},
        "campaign": {
            "n_evals": crep.n_evals, "n_localized": crep.n_localized,
            "rays": {r.name: r.status for r in crep.rays},
            "frontiers": {r.name: r.frontier_knobs() for r in crep.rays
                          if r.status == "localized"},
            "reverified_probes": audit["n_probes"],
        },
    })
    return [
        ("serving_failover_cold", us_cold,
         f"first live drill incl. jit compile; ~{rep.users_served / 1e6:.1f}M "
         f"users, crit avail {crit.availability:.4f}"),
        ("serving_failover", us_warm,
         f"crit {crit.tier} avail {crit.availability:.4f} (assert >=0.9997) "
         f"p99 {crit.p99_s:.0f}s; pre {pre.tier} preempted {pre.preempted}, "
         f"restored in {pre.time_to_restore_s:.0f}s <= RTO {rto:.0f}s"),
        ("serving_request_campaign", us_camp,
         f"{crep.n_localized} request-plane rays localized in "
         f"{crep.n_evals} drills; frontier "
         + str({r.name: round(r.frontier_severity, 3) for r in crep.rays
                if r.frontier_severity is not None})),
        ("serving_campaign_verify", us_verify,
         f"bit-exact oracle replay of {audit['n_probes']} drill probes"),
    ]


ALL = [
    bench_table1_tiers,
    bench_table2_rpc_matrix,
    bench_table4_failover_classes,
    bench_table5_phased_rollout,
    bench_table6_failclose,
    bench_fig2_3_failover_history,
    bench_fig7_burst_conversion,
    bench_fig8_availability,
    bench_fig9_container_conversion,
    bench_fig10_region_utilization,
    bench_fig11_fleet_utilization,
    bench_eviction_rates,
    bench_overcommit,
    bench_canary_gate,
    bench_fleet_scale,
    bench_scenario_sweep,
    bench_runtime_detection_scale,
    bench_graph_propagation,
    bench_timeline_ensemble,
    bench_fused_sweep_scale,
    bench_chaos_campaign,
    bench_capacity_opt,
    bench_serving_failover,
]
