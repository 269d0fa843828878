"""Fused sweep engine: composed-path bit-exact equivalence, bucket-padded
jit-cache reuse, sharded-vs-single-device equality, wrapper delegation,
and the float32 dtype pins (regression for silent float64/weak-type
promotion in the sweep/timeline paths)."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.capacity import RegionCapacity
from repro.core.omg import Orchestrator
from repro.core.scenarios import (FleetAggregates, analytic_consts,
                                  scenario_grid, stage_seed,
                                  sweep_scenarios,
                                  sweep_with_dependency_ensemble,
                                  _sweep_jit)
from repro.core import sweep_engine
from repro.core.service import synthesize_fleet
from repro.core.sweep_engine import (CHUNK, MIN_BUCKET, SweepEngine,
                                     bucket_shape, compiled_variants,
                                     fused_sweep, tile_grid)
from repro.core.timeline_sim import (config_for_fleet, default_ts,
                                     sweep_timeline)
from repro.graph import CallGraph

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TS = default_ts(7200.0, 240)

# every key the fused jit emits must be float32 / bool / int32 — float64
# (or a weak-type promotion that only shows up under x64) is a regression
_ALLOWED = (np.float32, np.bool_, np.int32)


@pytest.fixture(scope="module")
def fleet():
    fs = synthesize_fleet(scale=0.05, seed=7, as_arrays=True)
    fs.apply_ufa_target_classes()
    return fs


@pytest.fixture(scope="module")
def parts(fleet):
    agg = FleetAggregates.from_fleet_state(fleet)
    cfg = config_for_fleet(fleet)
    graph = CallGraph.from_fleet_state(fleet)
    return agg, cfg, graph


def _composed(agg, cfg, grid, dep_frac, ts):
    """The PR-4 composition: analytic jit + timeline jit (with the trace
    stack materialized), separate calls, host round-trips between them."""
    params = {k: jnp.asarray(v, jnp.float32) for k, v in grid.items()}
    params["dep_broken_frac"] = jnp.asarray(dep_frac, jnp.float32)
    out = {k: np.asarray(v)
           for k, v in _sweep_jit(analytic_consts(agg), params).items()}
    tres = sweep_timeline(cfg, grid=grid, ts=ts, dep_broken_frac=dep_frac,
                          return_traces=True)
    for k, v in tres.items():
        if k != "t" and not k.startswith("trace_"):
            out[f"t_{k}"] = v
    return out


def test_fused_matches_composed_bit_exact_256(parts):
    """Tentpole acceptance: one fused jitted pipeline == the composed
    three-stage path, exactly, on every verdict key at 256 scenarios."""
    agg, cfg, _ = parts
    grid = scenario_grid()
    eng = SweepEngine(agg, cfg, ts=TS)
    fused = eng.run(grid)
    want = _composed(agg, cfg, grid, np.zeros(256), TS)
    assert set(want) <= set(fused)
    for k, v in want.items():
        got = fused[k]
        assert got.dtype == v.dtype, k
        assert np.array_equal(got, v, equal_nan=True), k


def test_fused_dependency_stage_matches_composed(parts):
    """With the propagation stage fused in-program, every verdict still
    matches the composed path fed the same (device-computed) per-scenario
    broken-critical fractions."""
    agg, cfg, graph = parts
    grid = scenario_grid(evict_fraction=(1.0, 0.75, 0.5, 0.25))
    eng = SweepEngine(agg, cfg, graph=graph, seed=0, ts=TS)
    fused = eng.run(grid)
    frac, counts, n_dark = eng.dep_fractions(
        np.asarray(grid["evict_fraction"]))
    want = _composed(agg, cfg, grid, frac, TS)
    for k, v in want.items():
        assert np.array_equal(fused[k], v, equal_nan=True), k
    assert np.array_equal(fused["dep_n_broken_critical"], counts)
    assert np.array_equal(fused["dep_n_dark"], n_dark)
    # the dependency verdicts agree with the legacy host-side ensemble
    from repro.graph import blackhole_ensemble
    ens = blackhole_ensemble(graph, seed=0,
                             fractions=np.asarray(grid["evict_fraction"]))
    assert np.array_equal(fused["dep_n_broken_critical"],
                          ens["n_broken_critical"])
    assert np.array_equal(fused["dep_n_dark"], ens["n_dark"])


def test_wrappers_delegate_to_fused_engine(parts, fleet):
    """The existing APIs are thin wrappers now: ``sweep_scenarios(...,
    timeline=cfg)`` and ``sweep_with_dependency_ensemble(...,
    temporal=True)`` return exactly what the engine returns."""
    agg, cfg, graph = parts
    grid = scenario_grid(evict_fraction=(1.0, 0.5))
    via_api = sweep_scenarios(agg, grid, timeline=cfg, ts=TS)
    direct = SweepEngine(agg, cfg, ts=TS).run(grid)
    assert set(via_api) == set(direct)
    for k in direct:
        assert np.array_equal(via_api[k], direct[k], equal_nan=True), k

    # the wrapper derives an independent stream for its engine stage from
    # the campaign seed (the seed-reuse fix) — delegation is still exact
    # against an engine built with the same derived seed
    via_dep = sweep_with_dependency_ensemble(fleet, grid=grid, seed=3,
                                             temporal=True, ts=TS)
    direct_dep = SweepEngine(agg, cfg, graph=graph,
                             seed=stage_seed(3, "sweep-engine"),
                             ts=TS).run(grid)
    for k in direct_dep:
        assert np.array_equal(via_dep[k], direct_dep[k],
                              equal_nan=True), k


def test_orchestrator_sweep_engine_wrapper(fleet):
    region = RegionCapacity.for_fleet("r", fleet)
    orch = Orchestrator(fleet, region)
    eng = orch.sweep_engine()
    res = eng.run(scenario_grid(), temporal=True)
    assert len(res["t_sla_ok"]) == 256
    # operating point: same config the standalone extraction produces
    cfg = config_for_fleet(fleet, region=region)
    want = SweepEngine(FleetAggregates.from_fleet_state(fleet),
                       cfg).run(scenario_grid())
    assert np.array_equal(res["t_rl_done_s"], want["t_rl_done_s"])


def test_bucket_shape_padding():
    assert bucket_shape(1) == (1, MIN_BUCKET)
    assert bucket_shape(256) == (1, 256)
    assert bucket_shape(257) == (1, 512)
    assert bucket_shape(CHUNK) == (1, CHUNK)
    assert bucket_shape(CHUNK + 1) == (2, CHUNK)
    assert bucket_shape(10 * CHUNK) == (16, CHUNK)
    assert bucket_shape(100_000) == (32, CHUNK)
    # every width divides cleanly over up to 8 virtual devices
    for n in (1, 100, 256, 5000, 100_000):
        _, width = bucket_shape(n)
        assert width % 8 == 0


def test_no_recompile_within_padding_bucket(parts):
    """Grid sizes that pad to the same (n_chunks, width) bucket must hit
    the same compiled pipeline (keyed jit cache on static shapes only)."""
    agg, cfg, _ = parts
    eng = SweepEngine(agg, cfg, ts=TS)
    base = scenario_grid()
    eng.run(tile_grid(base, 300))              # bucket (1, 512)
    n0 = compiled_variants()
    eng.run(tile_grid(base, 511))              # same bucket
    eng.run(tile_grid(base, 400))              # same bucket
    assert compiled_variants() == n0
    eng.run(tile_grid(base, 513))              # next bucket -> one compile
    assert compiled_variants() == n0 + 1
    # padded scenarios do not leak into results
    r_400 = eng.run(tile_grid(base, 400))
    assert len(r_400["sla_ok"]) == 400
    r_511 = eng.run(tile_grid(base, 511))
    assert np.array_equal(r_400["sla_ok"], r_511["sla_ok"][:400])


def test_fused_sweep_convenience(fleet):
    res = fused_sweep(fleet, scenario_grid(evict_fraction=(1.0, 0.5)),
                      seed=0, ts=TS)
    assert "t_sla_ok" in res and "dep_n_dark" in res
    assert len(res["sla_ok"]) == 512


def test_output_dtypes_pinned(parts):
    """Every fused-pipeline output is float32 / bool / int32 — the grid
    axes pass through untouched, but no verdict may silently promote."""
    agg, cfg, graph = parts
    grid = scenario_grid(evict_fraction=(1.0, 0.5))
    eng = SweepEngine(agg, cfg, graph=graph, ts=TS)
    res = eng.run(grid)
    for k, v in res.items():
        if k in grid:
            continue                           # host passthrough
        assert v.dtype in _ALLOWED, (k, v.dtype)
    tres = sweep_timeline(cfg, grid=grid, ts=TS)
    for k, v in tres.items():
        assert v.dtype in _ALLOWED, (k, v.dtype)


# run() options per case; "trim" sweeps 600 rows in 256-wide chunks, so
# the padded tail of the last chunk is cut off
_FETCH_CASES = {
    "dependency": {},
    "explicit_dep_frac": {"dep_broken_frac": np.linspace(0.0, 0.3, 512)},
    "temporal_off": {"temporal": False},
    "soft_tau": {"soft_tau": 0.5},
    "trim": {},
}


@pytest.mark.parametrize("case", list(_FETCH_CASES))
def test_packed_fetch_matches_program_leaves(parts, monkeypatch, case):
    """``run`` fetches the result packed per dtype; it must hand back what
    ``np.asarray`` of each leaf of the program's own output gives (the
    same pipeline jitted afresh with the packing left out): the same
    keys, dtypes and shapes, bit-identical values."""
    agg, cfg, graph = parts
    kw = _FETCH_CASES[case]
    grid = scenario_grid(evict_fraction=(1.0, 0.5))
    if case == "trim":
        grid = tile_grid(grid, 600)
    eng = SweepEngine(agg, cfg, graph=graph, ts=default_ts(7200.0, 48),
                      chunk=256)
    got = eng.run(grid, **kw)

    tau = (jnp.asarray(kw["soft_tau"], jnp.float32) if "soft_tau" in kw
           else None)
    fn, args, fkw = eng._pipeline(grid, kw.get("dep_broken_frac"),
                                  kw.get("temporal", True), tau)
    monkeypatch.setattr(sweep_engine, "_pack", lambda out: out)

    def unpacked(*a, **k):          # a new function: a trace of its own
        return fn.__wrapped__(*a, **k)
    leaves = jax.jit(unpacked, static_argnames=tuple(fkw))(*args, **fkw)
    n = len(next(iter(grid.values())))
    want = {k: np.asarray(v).reshape(-1, *v.shape[2:])[:n]
            for k, v in leaves.items()}
    assert set(got) == set(want) | set(grid)
    if kw.get("temporal", True):
        assert got["t_time_to_restore_s"].shape == (n, 7)
    for k, v in want.items():
        assert (got[k].dtype, got[k].shape) == (v.dtype, v.shape), k
        assert np.array_equal(got[k], v, equal_nan=True), k


def _run(code, n_devices=1, x64=False):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    if x64:
        env["JAX_ENABLE_X64"] = "1"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_equals_single_device():
    """Under 8 virtual host devices the scenario axis is sharded across
    the mesh; verdicts must match the single-device run bit-for-bit."""
    code = textwrap.dedent("""
        import numpy as np, jax
        assert len(jax.devices()) == 8, jax.devices()
        from repro.core.scenarios import FleetAggregates, scenario_grid
        from repro.core.service import synthesize_fleet
        from repro.core.sweep_engine import SweepEngine, tile_grid
        from repro.core.timeline_sim import config_for_fleet, default_ts
        from repro.graph import CallGraph
        fs = synthesize_fleet(scale=0.02, seed=1, as_arrays=True)
        fs.apply_ufa_target_classes()
        agg = FleetAggregates.from_fleet_state(fs)
        cfg = config_for_fleet(fs)
        graph = CallGraph.from_fleet_state(fs)
        ts = default_ts(7200.0, 120)
        grid = tile_grid(scenario_grid(evict_fraction=(1.0, 0.5)), 1024)
        sharded = SweepEngine(agg, cfg, graph=graph, ts=ts, devices=8)
        single = SweepEngine(agg, cfg, graph=graph, ts=ts, devices=1)
        assert sharded.mesh is not None and single.mesh is None
        # explicit devices force sharding even on a single-chunk grid;
        # the default engine only shards multi-chunk grids (the thin
        # wrappers must not slow small default grids on multi-dev hosts)
        assert sharded._shard_for((1, 1024)) is True
        default = SweepEngine(agg, cfg, graph=graph, ts=ts)
        assert default._shard_for((1, 1024)) is False
        assert default._shard_for((2, 4096)) is True
        a, b = sharded.run(grid), single.run(grid)
        assert set(a) == set(b)
        for k in a:
            assert (a[k].dtype, a[k].shape) == (b[k].dtype, b[k].shape), k
            assert np.array_equal(a[k], b[k], equal_nan=True), k
        print("OK", len(a["sla_ok"]))
    """)
    out = _run(code, n_devices=8)
    assert "OK 1024" in out


def test_no_float64_under_x64():
    """The dtype-drift regression: with JAX_ENABLE_X64=1 every fused /
    timeline verdict (and the scan carry behind them) must still come out
    float32 / bool / int32 — a Python-scalar or numpy-scalar config value
    leaking into the kernels would promote to float64 here."""
    code = textwrap.dedent("""
        import numpy as np
        from repro.core.scenarios import FleetAggregates, scenario_grid
        from repro.core.service import synthesize_fleet
        from repro.core.sweep_engine import SweepEngine
        from repro.core.timeline_sim import (config_for_fleet, default_ts,
                                             simulate_timeline,
                                             sweep_timeline)
        from repro.graph import CallGraph
        fs = synthesize_fleet(scale=0.02, seed=1, as_arrays=True)
        fs.apply_ufa_target_classes()
        cfg = config_for_fleet(fs)
        ts = default_ts(7200.0, 60)
        grid = scenario_grid(evict_fraction=(1.0, 0.5))
        allowed = (np.float32, np.bool_, np.int32)
        eng = SweepEngine(FleetAggregates.from_fleet_state(fs), cfg,
                          graph=CallGraph.from_fleet_state(fs), ts=ts)
        res = eng.run(grid)
        for k, v in res.items():
            if k in grid:
                continue
            assert v.dtype in allowed, (k, v.dtype)
        for k, v in sweep_timeline(cfg, grid=grid, ts=ts).items():
            assert v.dtype in allowed, (k, v.dtype)
        sim = simulate_timeline(cfg, ts=ts)
        for k, v in sim.items():
            if k != "t":
                assert v.dtype in allowed, (k, v.dtype)
        print("OK")
    """)
    out = _run(code, x64=True)
    assert "OK" in out
