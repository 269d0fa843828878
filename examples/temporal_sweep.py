"""Temporal scenario ensemble: execute failure *timelines*, not point
estimates.

Synthesizes a Tables-1-3 fleet, then runs the fused sweep engine
(``repro.core.sweep_engine``: analytic model + the discrete-time failover
kernel + dependency propagation in one jitted, device-parallel pipeline)
over the scenario grid — per-scenario time-to-restore per tier, the
availability integral against the 99.97% SLA, and the peak on-demand
cloud draw, alongside the analytic closed-form verdicts.

  PYTHONPATH=src python examples/temporal_sweep.py
  # 64k-scenario ensemble, sharded over 8 virtual host devices:
  PYTHONPATH=src python examples/temporal_sweep.py --grid-size 65536 \\
      --devices 8
  # with the observability plane on: Chrome trace (load in Perfetto),
  # Prometheus + JSONL metric snapshots, SLO burn-rate verdicts
  PYTHONPATH=src python examples/temporal_sweep.py --trace --metrics-out
"""

import argparse
import os
import sys
import time
from contextlib import nullcontext

import numpy as np

from repro.core.scenarios import (operating_point_mask, scenario_grid,
                                  summarize_sweep,
                                  sweep_with_dependency_ensemble)
from repro.core.service import synthesize_fleet
from repro.core.sweep_engine import tile_grid
from repro.core.tiers import Tier
from repro.graph import CallGraph, plan_hardening
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import take_devices


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid-size", type=int, default=256,
                    help="scenario count (the 256-point base grid is "
                         "tiled out; the fused engine bucket-pads)")
    ap.add_argument("--devices", type=int, default=1,
                    help="devices to shard the scenario axis over (on "
                         "CPU: virtual host devices, re-executes under "
                         "XLA_FLAGS)")
    ap.add_argument("--trace", nargs="?", const="failover_trace.json",
                    default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the run "
                         "(host pipeline phases + a traced event-loop "
                         "failover); open in https://ui.perfetto.dev")
    ap.add_argument("--metrics-out", nargs="?", const="metrics.prom",
                    default=None, metavar="PATH",
                    help="enable the metrics registry and write a "
                         "Prometheus snapshot (+ JSONL next to it)")
    args = ap.parse_args()
    devices = take_devices(args.devices, sys.argv)

    tracer, prof = None, None
    if args.trace or args.metrics_out:
        from repro import obs
        from repro.obs.profiler import Profiler
        obs.enable()
        if args.trace:
            tracer = obs.Tracer()
            obs.set_tracer(tracer)
        prof = Profiler(tracer)

    def phase(name):
        return prof.phase(name) if prof is not None else nullcontext()

    fs = synthesize_fleet(scale=0.1, seed=7, as_arrays=True,
                          unsafe_chain_fraction=0.02)
    fs.apply_ufa_target_classes()
    print(f"fleet: {fs.n} service-environments, "
          f"{float(fs.spec_cores.sum()):,.0f} cores | "
          f"grid={args.grid_size} devices={len(devices)}")

    grid = tile_grid(scenario_grid(), args.grid_size)

    # 1. the un-remediated fleet: fail-close chains break criticals in
    #    every blackhole scenario, sinking the availability trace
    with phase("sweep-unhardened"):
        res0 = sweep_with_dependency_ensemble(fs, grid=grid, temporal=True,
                                              devices=devices)
    print(f"\nbefore hardening: t_sla_ok="
          f"{int(res0['t_sla_ok'].sum())}/{len(res0['t_sla_ok'])} "
          f"worst avail integral "
          f"{float(res0['t_availability_mean'].min()):.5f}")

    # 2. harden: greedily fail-open the highest-blast-radius unsafe edges
    #    until the full blackhole certifies (paper's 4,000+ conversions)
    graph = CallGraph.from_fleet_state(fs)
    with phase("plan-hardening"):
        plan = plan_hardening(graph)
    # plan indices are CSR positions; map back to FleetState edge order
    fs.edges.fail_open[graph.input_edge_indices(plan.hardened_edges)] = True
    print(f"hardened {plan.n_hardened} edges in {plan.rounds} rounds "
          f"(certified={plan.certified})")

    # 3. the hardened fleet, same temporal ensemble (fused engine path —
    #    warm after step 1 compiled the bucket)
    t0 = time.time()
    with phase("sweep-hardened"):
        res = sweep_with_dependency_ensemble(fs, grid=grid, temporal=True,
                                             devices=devices)
    dt = time.time() - t0
    print(f"fused sweep: {len(res['sla_ok'])} scenarios in {dt:.2f}s "
          f"({len(res['sla_ok'])/dt:,.0f} scenarios/s)")
    summary = summarize_sweep(res)
    print("\n== ensemble digest (analytic + temporal, hardened fleet) ==")
    for k, v in summary.items():
        print(f"  {k:32s} {v}")

    print("\n== analytic vs temporal disagreements ==")
    diff = np.flatnonzero(res["sla_ok"] != res["t_sla_ok"])
    print(f"  {len(diff)} of {len(res['sla_ok'])} scenarios differ")
    for i in diff[:5]:
        print(f"  mult={res['traffic_mult'][i]:.1f} "
              f"burst_avail={res['burst_availability'][i]:.2f} "
              f"quota={res['cloud_quota_frac'][i]:.2f} "
              f"evict={res['evict_fraction'][i]:.2f}: "
              f"analytic={bool(res['sla_ok'][i])} "
              f"temporal={bool(res['t_sla_ok'][i])} "
              f"t_rl_done={res['t_rl_done_s'][i]/60.0:.1f}min")

    print("\n== worst temporal scenarios (availability integral) ==")
    order = np.argsort(res["t_availability_mean"])[:5]
    for i in order:
        ttr = res["t_time_to_restore_s"][i]
        t3 = ttr[int(Tier.T3)]
        print(f"  avail_mean={res['t_availability_mean'][i]:.5f} "
              f"mult={res['traffic_mult'][i]:.1f} "
              f"burst_avail={res['burst_availability'][i]:.2f} "
              f"quota={res['cloud_quota_frac'][i]:.2f} "
              f"dep_broken={res['dep_broken_frac'][i]:.3f} "
              f"T3_restore={'never' if np.isinf(t3) else f'{t3/60:.0f}min'} "
              f"peak_cloud={res['t_peak_cloud_cores'][i]:,.0f}")

    op = operating_point_mask(res)
    i = int(np.flatnonzero(op)[0])
    print("\n== paper operating point, per-tier time-to-restore ==")
    for t in Tier:
        v = res["t_time_to_restore_s"][i][int(t)]
        label = ("never (until failback)" if np.isinf(v)
                 else "no interruption" if v == 0.0 else f"{v/60.0:.1f} min")
        print(f"  {t.name:3s} {label}")
    print(f"  availability integral: "
          f"{res['t_availability_mean'][i]:.5f} (SLA 0.9997) "
          f"temporal_sla_ok={bool(res['t_sla_ok'][i])}")

    if args.trace or args.metrics_out:
        from repro import obs
        from repro.core.timeline_sim import config_for_fleet, sweep_timeline
        from repro.obs import export, slo

        # SLO burn-rate monitor over full per-step availability traces
        # (multi-window multi-burn-rate against the 99.97% target),
        # verdict quality judged against the kernel's own avail_ok
        with phase("slo-monitor"):
            cfg = config_for_fleet(fs)
            n_slo = min(args.grid_size, 256)
            tr = sweep_timeline(cfg, grid=tile_grid(scenario_grid(), n_slo),
                                return_traces=True)
            verd = slo.sweep_alerts(tr["trace_availability"], tr["t"])
            quality = slo.alert_quality(verd["alert"], ~tr["avail_ok"],
                                        verd["t_first_alert"])
        print("\n== SLO burn-rate monitor (99.97% target) ==")
        print(f"  rules: {[r.name for r in slo.DEFAULT_RULES]}")
        print(f"  alerts on {quality['n_alerts']}/{quality['n_scenarios']} "
              f"scenarios ({quality['n_violations']} true SLA violations): "
              f"precision={quality['precision']:.2f} "
              f"recall={quality['recall']:.2f} "
              f"median time-to-first-alert="
              f"{quality['median_t_first_alert']:.0f}s")

        if args.trace:
            # one traced event-loop failover: the orchestration waves
            # (BBM evict, burst conversion, MBB/RL waves, cloud grants)
            # render as sim-time spans alongside the host phases above
            from repro.core.capacity import RegionCapacity
            from repro.core.omg import Orchestrator
            with phase("traced-failover"):
                orch = Orchestrator(fs, RegionCapacity.for_fleet("tr", fs),
                                    tracer=tracer)
                orch.failover()
            tracer.save(args.trace)
            print(f"\nwrote {args.trace} ({len(tracer)} events; load in "
                  f"https://ui.perfetto.dev)")
        if args.metrics_out:
            export.write_prometheus(args.metrics_out)
            jsonl = os.path.splitext(args.metrics_out)[0] + ".jsonl"
            export.write_jsonl(jsonl, meta={"example": "temporal_sweep",
                                            "grid_size": args.grid_size})
            print(f"wrote {args.metrics_out} + {jsonl}")
        obs.set_tracer(None)
        obs.disable()


if __name__ == "__main__":
    enable_compile_cache()
    main()
