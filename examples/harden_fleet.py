"""Dependency-hardening campaign, end to end (paper §5-6).

The paper's safety pipeline before the 2x buffer could be dropped: detect
fail-close dependencies (runtime correlation + static analysis), build the
call graph *from the detections*, propagate a full blackhole through it to
see which critical services break (multi-hop, through relay chains), run
the greedy hardening planner until the fleet certifies, then keep it
certified with the regression gate.  Prints the hardened-edge count next
to the paper's 4,000+ figure.

  PYTHONPATH=src python examples/harden_fleet.py
  # with host-phase tracing + a metrics snapshot:
  PYTHONPATH=src python examples/harden_fleet.py --trace --metrics-out
"""

import argparse
import os
import time
from contextlib import nullcontext

import numpy as np

from repro.core.dependency import runtime_analysis
from repro.core.drills import remediate
from repro.core.scenarios import scenario_grid, summarize_sweep, \
    sweep_with_dependency_ensemble
from repro.core.service import synthesize_fleet, unsafe_edges
from repro.core.static_analysis import static_analysis
from repro.graph import (CallGraph, blackhole_ensemble, certify,
                         plan_hardening, regression_gate)
from repro.launch.compile_cache import enable_compile_cache

SCALE = 0.15          # detection runs on the object fleet (IR + traces)
SEED = 7


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", nargs="?", const="harden_trace.json",
                    default=None, metavar="PATH",
                    help="write a Chrome trace of the pipeline's host "
                         "phases (open in https://ui.perfetto.dev)")
    ap.add_argument("--metrics-out", nargs="?", const="harden_metrics.prom",
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

    # ---- detect ---------------------------------------------------------
    fleet = synthesize_fleet(scale=SCALE, seed=SEED, unsafe_fraction=0.10,
                             unsafe_chain_fraction=0.04)
    truth = set(unsafe_edges(fleet))
    print(f"fleet: {len(fleet)} services, {len(truth)} planted fail-close "
          f"edges (incl. critical->critical relay chains)")

    with phase("runtime-detection"):
        ra = runtime_analysis(fleet, n_records=1_500_000, seed=SEED)
    with phase("static-analysis"):
        sa = static_analysis(fleet, seed=SEED)
    detected = (ra["found"] | sa["found"])
    recall = len(detected & truth) / max(1, len(truth))
    print(f"detection: runtime={len(ra['found'])} static={len(sa['found'])} "
          f"combined_recall={recall:.2f} "
          f"(paper Table 6: 3041 runtime + 1114 static)")

    # ---- build graph from the detections + propagate --------------------
    graph = CallGraph.from_detections(fleet, detected & truth)
    cert0 = certify(graph)
    print(f"\nblackhole certification (multi-hop): "
          f"{cert0.n_broken_critical}/{cert0.n_critical} critical services "
          f"break, {int(cert0.multi_hop.sum())} only through relay chains "
          f"({cert0.rounds} propagation rounds)")

    # ---- plan hardening -------------------------------------------------
    t0 = time.time()
    with phase("plan-hardening"):
        plan = plan_hardening(graph, batch=12)
    print(f"\nhardening planner: {plan.n_hardened} edges converted "
          f"fail-open over {plan.rounds} rounds ({time.time() - t0:.1f}s) "
          f"-> certified={plan.certified}")
    print(f"  paper: 4,000+ dependencies hardened fleet-wide; this fleet "
          f"is scale={SCALE}, i.e. ~{int(plan.n_hardened / SCALE):,} "
          f"full-scale-equivalent conversions")
    print("  trajectory (hardened -> broken criticals): "
          + " ".join(f"{t['n_hardened']}->{t['n_broken_critical']}"
                     for t in plan.trajectory))

    # ---- re-certify against the ground truth ----------------------------
    remediate(fleet, set(plan.hardened_edge_names))
    cert1 = certify(CallGraph.from_specs(fleet))
    print(f"\nre-certification on the remediated fleet: "
          f"broken criticals {cert0.n_broken_critical} -> "
          f"{cert1.n_broken_critical} (ok={cert1.ok})")

    # ---- gate future regressions ----------------------------------------
    hardened = plan.graph
    crit = hardened.names[int(np.flatnonzero(hardened.critical)[0])]
    pre = hardened.names[int(np.flatnonzero(hardened.preemptible)[0])]
    gate = regression_gate(hardened, hardened.with_edge(crit, pre,
                                                        fail_open=False))
    print(f"regression gate on a planted {crit} -> {pre} fail-close edge: "
          f"ok={gate.ok} violations={gate.violations}")

    # ---- scenario ensemble with the dependency layer closed in ----------
    from repro.core.fleet_state import synthesize_fleet_state
    fs = synthesize_fleet_state(scale=1.0, seed=SEED,
                                unsafe_chain_fraction=0.05)
    g_paper = CallGraph.from_fleet_state(fs)
    t0 = time.time()
    with phase("certify-paper-scale"):
        cert_paper = certify(g_paper)
        ens = blackhole_ensemble(g_paper, n_scenarios=256, seed=SEED)
    dt = time.time() - t0
    print(f"\npaper scale: {g_paper.n} SEs / {g_paper.n_edges} edges — "
          f"full certification + 256-scenario blackhole ensemble in "
          f"{dt:.2f}s ({cert_paper.n_broken_critical} broken criticals "
          f"un-hardened; ensemble ok-rate "
          f"{float(np.mean(ens['ok'])):.2f})")
    res = sweep_with_dependency_ensemble(
        fs, scenario_grid(evict_fraction=(1.0, 0.75, 0.5, 0.25)), seed=SEED)
    s = summarize_sweep(res)
    print(f"scenario sweep with dependency verdicts: "
          f"{s['n_dep_ok']}/{s['n_scenarios']} scenarios dependency-clean, "
          f"worst broken-critical fraction {s['worst_dep_broken_frac']:.3f}")

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
            export.write_jsonl(jsonl, meta={"example": "harden_fleet",
                                            "scale": SCALE})
            print(f"wrote {args.metrics_out} + {jsonl}")
        obs.set_tracer(None)
        obs.disable()


if __name__ == "__main__":
    enable_compile_cache()
    main()
