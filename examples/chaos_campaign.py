"""Adversarial chaos campaign: hunt the SLA-violating frontier.

Synthesizes and hardens a Tables-1-3 fleet, then runs a chaos campaign
(``repro.chaos``): bandit-allocated bisection along fault-severity rays
— traffic spikes, preheat stalls, burst/quota/eviction shortfalls,
partial-region degradation, cascading dependency storms and the paper's
correlated compound incident — with every probe round evaluated as ONE
batched call into the fused sweep engine.  Prints the frontier report
(max survivable severity per fault family, minimal counterexamples),
replays every probe bit-exactly on an independent engine, and finishes
with a correlated Monte-Carlo fault sample scored in a single sweep.

  PYTHONPATH=src python examples/chaos_campaign.py
  # coarser/faster: localize to 1/32 with at most 8 bisection rounds
  PYTHONPATH=src python examples/chaos_campaign.py --tol 32 --max-rounds 8
  # with the observability plane on: Chrome trace + Prometheus snapshot
  PYTHONPATH=src python examples/chaos_campaign.py --trace --metrics-out
"""

import argparse
import os
import time
from contextlib import nullcontext

import numpy as np

from repro.chaos import campaign_for_fleet, sample_faults, verify_report
from repro.core.service import synthesize_fleet
from repro.graph import CallGraph, plan_hardening
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.05,
                    help="fleet synthesis scale (0.05 = paper bench fleet)")
    ap.add_argument("--seed", type=int, default=7,
                    help="ONE campaign seed: engine blackhole draws, "
                         "storm draws and fault sampling all derive "
                         "independent streams from it")
    ap.add_argument("--tol", type=float, default=64,
                    help="frontier resolution as 1/TOL severity units")
    ap.add_argument("--max-rounds", type=int, default=64,
                    help="bisection round cap")
    ap.add_argument("--round-budget", type=int, default=None,
                    help="max rays probed per round (bandit budget; "
                         "default probes every active ray)")
    ap.add_argument("--samples", type=int, default=512,
                    help="correlated Monte-Carlo faults scored at the end")
    ap.add_argument("--trace", nargs="?", const="chaos_trace.json",
                    default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the campaign "
                         "phases; open in https://ui.perfetto.dev")
    ap.add_argument("--metrics-out", nargs="?", const="metrics.prom",
                    default=None, metavar="PATH",
                    help="enable the metrics registry and write a "
                         "Prometheus snapshot (+ JSONL next to it)")
    args = ap.parse_args()

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

    fs = synthesize_fleet(scale=args.scale, seed=7, as_arrays=True)
    fs.apply_ufa_target_classes()
    graph = CallGraph.from_fleet_state(fs)
    with phase("plan-hardening"):
        plan = plan_hardening(graph)
    fs.edges.fail_open[graph.input_edge_indices(plan.hardened_edges)] = True
    print(f"fleet: {fs.n} service-environments, hardened "
          f"{plan.n_hardened} edges (certified={plan.certified})")

    tol = 1.0 / args.tol
    t0 = time.time()
    camp = campaign_for_fleet(fs, seed=args.seed, tol=tol,
                              max_rounds=args.max_rounds,
                              round_budget=args.round_budget,
                              profiler=prof)
    report = camp.run()
    dt = time.time() - t0
    print(f"\ncampaign: {report.n_evals} engine evals in {dt:.1f}s "
          f"({report.n_rounds} bisection rounds)\n")
    print(report.render())

    print("\n== frontier in knob coordinates ==")
    for r in report.rays:
        knobs = r.frontier_knobs()
        if knobs is None:
            continue
        active = {_knob_of(f): round(knobs[_knob_of(f)], 4)
                  for f in sorted(r.direction)}
        print(f"  {r.name:22s} severity {r.frontier_severity:.4f} -> "
              f"{active}")

    # bit-exact audit: replay every probe on an independent engine
    with phase("chaos-verify"):
        fresh = campaign_for_fleet(fs, seed=args.seed, tol=tol)
        audit = verify_report(report, fresh.engine)
    print(f"\nre-verification: {audit['n_probes']} probes replayed on an "
          f"independent engine, bit-identical")

    # correlated Monte-Carlo: joint fault draws (Gaussian copula — the
    # compound incidents the paper worries about), scored in ONE sweep
    with phase("chaos-sample"):
        sample = sample_faults(args.seed, args.samples)
        ok, _ = camp.oracle(sample["grid"])
    sev = sample["severity"]
    fail = ~ok
    print(f"\n== correlated Monte-Carlo ({args.samples} joint faults) ==")
    print(f"  SLA violations: {int(fail.sum())}/{args.samples} "
          f"({fail.mean():.1%})")
    if fail.any():
        worst = sev[fail].max(axis=0)
        mild = sev[fail].sum(axis=1).argmin()
        print("  mildest violating draw (severity per family):")
        for j, name in enumerate(sample["families"]):
            if sev[fail][mild, j] > 0.05:
                print(f"    {name:22s} {sev[fail][mild, j]:.3f}")

    if args.trace or args.metrics_out:
        from repro import obs
        from repro.obs import export
        if args.trace:
            tracer.save(args.trace)
            print(f"\nwrote {args.trace} ({len(tracer)} events; load in "
                  f"https://ui.perfetto.dev)")
        if args.metrics_out:
            export.write_prometheus(args.metrics_out)
            jsonl = os.path.splitext(args.metrics_out)[0] + ".jsonl"
            export.write_jsonl(jsonl, meta={"example": "chaos_campaign",
                                            "seed": args.seed})
            print(f"wrote {args.metrics_out} + {jsonl}")
        obs.set_tracer(None)
        obs.disable()


def _knob_of(family: str) -> str:
    from repro.chaos import FAULT_LIBRARY
    return FAULT_LIBRARY[family].knob


if __name__ == "__main__":
    enable_compile_cache()
    main()
