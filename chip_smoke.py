"""Smoke run of the paper-scale UFA failover pipeline on a TPU.

  python chip_smoke.py               # one chip: every phase below
  python chip_smoke.py --four-chips  # four chips: the sharded sweep only

Phases, through the public entry points, on the paper-scale fleet
(``synthesize_fleet(scale=1.0, seed=7)``: ~22k service environments,
~120k call edges):

  detect  ``runtime_analysis``: ~48M sampled RPCs folded into the per-edge
          evidence through the ingest kernel; the same stream is folded
          again through the XLA twin ``ref_ingest_hist`` and the counts
          must be equal.
  graph   ``certify`` + ``blackhole_ensemble(n_scenarios=256)`` +
          ``plan_hardening`` through the propagation kernel, against the
          same calls on the XLA scatter-max path (``REPRO_UFA_KERNELS=0``):
          broken sets, counts and round numbers equal, the same hardened
          edge sequence.
  sweep   ``Orchestrator.sweep_engine`` on a 4,096-scenario temporal +
          dependency grid (reducer kernel + in-pipeline propagation
          kernel), against the ``reducer="scan"`` engine on the XLA path;
          once on the fleet as built and once after the planner's
          hardening.
  drill   the live failover drill at the ``--smoke`` spec of
          ``examples/live_failover_drill.py``, with its SLA asserts.

``--four-chips`` runs a 65,536-scenario temporal + dependency sweep
sharded over four devices (``devices=4``) beside the same grid on one.

Every comparison runs on the chip, on the same inputs (the trace sampler
draws ``rbg`` bits, which may differ between backends).  Booleans and
integers must be equal; floats, whose reductions the kernels reorder,
must agree to float32 precision (``FLOAT_RTOL``).  The timings printed
are smoke timings: cold includes compilation; they are not benchmark
results.

Exits non-zero, without the result line, when JAX finds no TPU, when the
UFA kernels are switched off (``REPRO_UFA_KERNELS=0``) or would run in
interpret mode, when a kernel program lacks its ``tpu_custom_call``, or
when a phase raises or mismatches.  On success the last line is

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 7
FLOAT_RTOL = 1e-5            # float32-tight: a reordered f32 sum


class Mismatch(AssertionError):
    pass


def require(ok: bool, what: str):
    if not ok:
        raise Mismatch(what)


@contextlib.contextmanager
def xla_twins():
    """Route the UFA hot paths to their XLA twins (the dispatch reads
    ``REPRO_UFA_KERNELS`` per call)."""
    prev = os.environ.get("REPRO_UFA_KERNELS")
    os.environ["REPRO_UFA_KERNELS"] = "0"
    try:
        yield
    finally:
        if prev is None:
            del os.environ["REPRO_UFA_KERNELS"]
        else:
            os.environ["REPRO_UFA_KERNELS"] = prev


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0


def mosaic_program(jitted, *args, **kw):
    """Compile ``jitted`` for these arguments; the program must hold the
    Pallas kernel as a Mosaic ``tpu_custom_call``."""
    text = jitted.lower(*args, **kw).compile().as_text()
    require("tpu_custom_call" in text,
            f"{getattr(jitted, '__name__', jitted)}: compiled program has "
            f"no tpu_custom_call")


def compare(name: str, got: dict, want: dict, float_keys=None) -> str:
    """Booleans and integers equal; floats equal in their non-finite
    entries and within ``FLOAT_RTOL`` elsewhere.  Returns a digest line."""
    require(set(got) == set(want), f"{name}: keys differ")
    worst, n_float = 0.0, 0
    for k in sorted(want):
        a, b = np.asarray(got[k]), np.asarray(want[k])
        require(a.shape == b.shape and a.dtype == b.dtype,
                f"{name}[{k}]: {a.shape}/{a.dtype} vs {b.shape}/{b.dtype}")
        if a.dtype.kind != "f" or (float_keys is not None
                                   and k not in float_keys):
            require(np.array_equal(a, b), f"{name}[{k}] differs")
            continue
        n_float += 1
        fin = np.isfinite(b)
        require(np.array_equal(fin, np.isfinite(a))
                and np.array_equal(a[~fin], b[~fin]),
                f"{name}[{k}]: non-finite entries differ")
        if fin.any():
            rel = np.abs(a[fin] - b[fin]) / np.maximum(np.abs(b[fin]),
                                                       np.float32(1e-30))
            worst = max(worst, float(rel.max()))
    require(worst <= FLOAT_RTOL,
            f"{name}: float max rel diff {worst:.3g} > {FLOAT_RTOL}")
    return (f"{len(want) - n_float} exact keys equal, {n_float} float keys "
            f"max rel diff {worst:.3g}")


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def report(phase: str, **fields):
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[smoke] {phase}: {body}", flush=True)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_detect(fs, check_program, n_records=None):
    from repro.core import dependency as dep
    from repro.kernels.ufa.ingest import ingest_hist, ref_ingest_hist

    res, cold = timed(dep.runtime_analysis, fs, n_records=n_records, seed=0)
    det = res["detector"]
    edges = dep.trace_edges(fs, seed=0)
    # the same stream (same seed, same backend), folded through the twin
    want = np.zeros((edges.n, 4), np.int64)
    t0 = time.perf_counter()
    for chunk in dep._iter_trace_chunks(
            edges, res["n_records"], 0, dep.AMBIENT_CALLEE_FAILURE,
            dep.AMBIENT_CALLER_ERROR, dep.PROPAGATION_PROB):
        want += np.asarray(ref_ingest_hist(*chunk, edges.n), np.int64)
    twin = time.perf_counter() - t0
    got = {"calls": det.calls, "callee_failures": det.callee_failures,
           "errors_given_failure": det.errors_given_failure,
           "errors_given_ok": det.errors_given_ok}
    ref = {"calls": want.sum(axis=1), "callee_failures": want[:, 2]
           + want[:, 3], "errors_given_failure": want[:, 3],
           "errors_given_ok": want[:, 1]}
    digest = compare("detect", got, ref)
    require(det.n_records == res["n_records"], "detect: records lost")
    check_program(ingest_hist, *chunk, edges.n)
    _, warm = timed(dep.runtime_analysis, fs, n_records=n_records, seed=0)
    report("detect", edges=edges.n, records=res["n_records"],
           found=len(res["found"]), precision=f"{res['precision']:.3f}",
           recall=f"{res['recall']:.3f}", cold_s=f"{cold:.2f}",
           warm_s=f"{warm:.2f}", twin_s=f"{twin:.2f}",
           peak_bytes=peak_bytes(), check=digest)


def phase_graph(fs, check_program):
    from repro.graph import (CallGraph, blackhole_ensemble, certify,
                             plan_hardening)
    from repro.graph.propagation import edge_consts
    from repro.kernels.ufa.propagation import fixed_point_ell

    graph = CallGraph.from_fleet_state(fs)

    def run():
        cert = certify(graph)
        ens = blackhole_ensemble(graph, n_scenarios=256, seed=SEED)
        plan = plan_hardening(graph)
        return {"cert_broken": cert.broken, "cert_rounds": cert.rounds,
                "cert_n_broken_critical": cert.n_broken_critical,
                **{f"ens_{k}": v for k, v in ens.items()},
                "plan_edges": np.asarray(plan.hardened_edges, np.int64),
                "plan_trajectory": np.asarray(
                    [sorted(r.items()) for r in plan.trajectory]),
                "plan_certified": plan.certified}

    got, cold = timed(run)
    with xla_twins():
        want, twin = timed(run)
    digest = compare("graph", got, want, float_keys=())
    consts = edge_consts(graph)
    require("ell_dst" in consts, "graph: no ELL adjacency on the kernel path")
    dark = np.zeros((256, graph.n), bool)
    check_program(fixed_point_ell, dark, consts["ell_dst"],
                  consts["ell_closed"])
    _, warm = timed(run)
    report("graph", services=graph.n, edges=len(graph.src),
           ell_k=int(consts["ell_dst"].shape[1]),
           rounds=int(got["cert_rounds"]),
           hardened=len(got["plan_edges"]),
           certified=bool(got["plan_certified"]), cold_s=f"{cold:.2f}",
           warm_s=f"{warm:.2f}", twin_s=f"{twin:.2f}",
           peak_bytes=peak_bytes(), check=digest)
    return graph.input_edge_indices(got["plan_edges"])


def _orchestrated(fs):
    from repro.core.capacity import RegionCapacity
    from repro.core.omg import Orchestrator
    from repro.graph import CallGraph
    fs.apply_ufa_target_classes()
    orch = Orchestrator(fs, RegionCapacity.for_fleet("smoke", fs),
                        scale=1.0)
    return orch, CallGraph.from_fleet_state(fs)


def phase_sweep(fs, check_program, hardened_edges, n_scenarios=4096):
    """The grid on the fleet as built (fail-close chains break criticals
    in every scenario) and again after the planner's hardening (the
    verdicts split)."""
    from repro.core.scenarios import scenario_grid
    from repro.core.sweep_engine import tile_grid
    from repro.core.timeline_sim import N_TIERS, RESTORE_THRESH
    from repro.kernels.ufa.reduce import timeline_reduce

    grid = tile_grid(scenario_grid(), n_scenarios)
    for stage in ("as-built", "hardened"):
        if stage == "hardened":
            fs.edges.fail_open[hardened_edges] = True
        orch, graph = _orchestrated(fs)
        eng = orch.sweep_engine(graph=graph, seed=SEED)
        require(eng.reducer == "pallas", "sweep: reducer kernel not selected")
        require("ell_dst" in eng.dep,
                "sweep: propagation kernel not selected")
        got, cold = timed(eng.run, grid)
        _, warm = timed(eng.run, grid)
        with xla_twins():
            ref_eng = orch.sweep_engine(graph=graph, seed=SEED,
                                        reducer="scan")
            want, twin = timed(ref_eng.run, grid)
        digest = compare(f"sweep {stage}", got, want)
        report(f"sweep {stage}", scenarios=n_scenarios, steps=len(eng.ts),
               t_sla_ok=int(got["t_sla_ok"].sum()),
               broken_critical=int((got["dep_n_broken_critical"] > 0).sum()),
               cold_s=f"{cold:.2f}", warm_s=f"{warm:.2f}",
               twin_s=f"{twin:.2f}", peak_bytes=peak_bytes(), check=digest)
    a = np.zeros((n_scenarios, len(eng.ts)), np.float32)
    check_program(timeline_reduce, a, a, a,
                  np.ones((*a.shape, N_TIERS), np.float32),
                  eng.ts.astype(np.float32), thresh=RESTORE_THRESH)


def phase_drill():
    sys.path.insert(0, os.path.join(ROOT, "examples"))
    import live_failover_drill
    _, cold = timed(live_failover_drill.main, smoke=True)
    report("drill", spec="--smoke", cold_s=f"{cold:.2f}",
           peak_bytes=peak_bytes(), check="SLA asserts passed")


def phase_four_chips(fs, n_scenarios=65536, n_devices=4):
    import jax
    from repro.core.scenarios import scenario_grid
    from repro.core.sweep_engine import tile_grid

    require(len(jax.devices()) >= n_devices,
            f"four-chips: {len(jax.devices())} devices")
    orch, graph = _orchestrated(fs)
    grid = tile_grid(scenario_grid(), n_scenarios)
    sharded = orch.sweep_engine(graph=graph, seed=SEED, devices=n_devices)
    single = orch.sweep_engine(graph=graph, seed=SEED, devices=1)
    require(sharded._shard_for((1, sharded.chunk)),
            "four-chips: the sweep would not shard")
    got, cold = timed(sharded.run, grid)
    _, warm = timed(sharded.run, grid)
    want, one = timed(single.run, grid)
    _, one_warm = timed(single.run, grid)
    digest = compare("four-chips", got, want)
    report("four-chips", scenarios=n_scenarios, devices=n_devices,
           t_sla_ok=int(got["t_sla_ok"].sum()), sharded_cold_s=f"{cold:.2f}",
           sharded_warm_s=f"{warm:.2f}", single_cold_s=f"{one:.2f}",
           single_warm_s=f"{one_warm:.2f}", check=digest)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def preflight() -> list:
    import jax
    from repro.kernels.backend import default_interpret, use_ufa_kernels
    problems = []
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        problems.append(f"JAX found no TPU (first device: {dev.platform})")
    if not use_ufa_kernels():
        problems.append("the UFA kernels are switched off "
                        f"(REPRO_UFA_KERNELS="
                        f"{os.environ.get('REPRO_UFA_KERNELS')!r})")
    if default_interpret():
        problems.append("Pallas kernels would run in interpret mode")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 65,536-scenario sweep sharded over "
                         "four devices, beside the same grid on one")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()

    import jax
    problems = preflight()
    if problems:
        for p in problems:
            print(f"[smoke] refused: {p}", file=sys.stderr)
        return 2

    from repro.core.service import synthesize_fleet
    dev = jax.devices()[0]
    report("start", platform=dev.platform, kind=repr(dev.device_kind),
           devices=len(jax.devices()), jax=jax.__version__,
           compile_cache=cache)
    try:
        fs, dt = timed(synthesize_fleet, scale=1.0, seed=SEED,
                       as_arrays=True)
        report("fleet", services=fs.n, edges=len(fs.edges.src),
               seconds=f"{dt:.2f}")
        if args.four_chips:
            phase_four_chips(fs)
            count = 4
        else:
            phase_detect(fs, mosaic_program)
            hardened = phase_graph(fs, mosaic_program)
            phase_sweep(fs, mosaic_program, hardened)
            phase_drill()
            count = len(jax.devices())
    except Exception:
        traceback.print_exc()
        print("[smoke] FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
