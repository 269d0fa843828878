"""Compile the UFA kernels and the fused sweep for a described TPU v5e.

No chip is attached: the TPU compiler compiles for a ``v5e:2x2`` topology
that is only described, and refuses what the chip would refuse (block
shapes off the tiling, more fast memory than a kernel may use, ops Mosaic
cannot lower).  Shapes are the paper-scale ones of ``chip_smoke.py``:
~22k services, ~120k call edges, 4M-record telemetry chunks, 4,096
scenarios x 240 steps.  Nothing runs, so nothing here checks results.

The topology, and everything built from it, lives in module-scoped
fixtures: only one process at a time may load the TPU library, so it is
loaded by the test that needs it and never while a module is imported.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.scenarios import scenario_grid
from repro.core.sweep_engine import tile_grid
from repro.core.timeline_sim import N_TIERS, RESTORE_THRESH
from repro.kernels.ufa.ingest import ingest_hist
from repro.kernels.ufa.propagation import fixed_point_ell
from repro.kernels.ufa.reduce import timeline_reduce

N_SERVICES, N_EDGES, ELL_K = 21_979, 120_827, 16
# the trace-shaped fleet's split layout: base width 8, 9,550 aux rows
SPLIT_K, SPLIT_AUX = 8, 9_550
CHUNK_RECORDS = 4_000_000
N_SCENARIOS, N_STEPS = 4096, 240


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled_kernels(topo):
    """Trace the kernels of the sweep pipeline for the chip: this process
    sees a CPU backend, on which they would default to interpret mode."""
    from repro.kernels.ufa import ingest, propagation, reduce
    with pytest.MonkeyPatch.context() as mp:
        for mod in (ingest, propagation, reduce):
            mp.setattr(mod, "default_interpret", lambda: False)
        jax.clear_caches()
        yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def sweep_pipeline():
    """The paper-scale fused sweep (dependency stage + reducer kernel) as
    ``SweepEngine.run`` would call it for one 4,096-wide chunk, built on
    the host with the kernel dispatch forced on."""
    from repro.core.capacity import RegionCapacity
    from repro.core.omg import Orchestrator
    from repro.core.service import synthesize_fleet
    from repro.graph import CallGraph
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_UFA_KERNELS", "1")
        fs = synthesize_fleet(scale=1.0, seed=7, as_arrays=True)
        fs.apply_ufa_target_classes()
        orch = Orchestrator(fs, RegionCapacity.for_fleet("c", fs), scale=1.0)
        eng = orch.sweep_engine(graph=CallGraph.from_fleet_state(fs))
        assert eng.reducer == "pallas" and "ell_dst" in eng.dep
        fn, args, kw = eng._pipeline(tile_grid(scenario_grid(),
                                               N_SCENARIOS))
    assert fn.__name__ == "_run_chunks_dep" and kw["mesh"] is None
    assert args[2]["traffic_mult"].shape == (1, N_SCENARIOS)
    return fn, args, kw


def _shapes(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        np.shape(a), jnp.asarray(a).dtype, sharding=sharding), tree)


def _kernel_call(kernel):
    if kernel == "ingest":
        return (lambda e, f, r: ingest_hist(e, f, r, N_EDGES,
                                            interpret=False),
                [((CHUNK_RECORDS,), jnp.int32), ((CHUNK_RECORDS,), bool),
                 ((CHUNK_RECORDS,), bool)])
    if kernel == "propagation":
        return (lambda d, e, c: fixed_point_ell(d, e, c, interpret=False),
                [((256, N_SERVICES), bool), ((N_SERVICES, ELL_K), jnp.int32),
                 ((N_SERVICES, ELL_K), bool)])
    if kernel == "propagation-split":
        rows = N_SERVICES + SPLIT_AUX
        return (lambda d, e, c, t: fixed_point_ell(d, e, c, t,
                                                   interpret=False),
                [((256, N_SERVICES), bool), ((rows, SPLIT_K), jnp.int32),
                 ((rows, SPLIT_K), bool), ((rows,), jnp.int32)])
    f32 = jnp.float32
    return (lambda a, u, c, fr, ts: timeline_reduce(
        a, u, c, fr, ts, thresh=RESTORE_THRESH, interpret=False),
        [((N_SCENARIOS, N_STEPS), f32)] * 3
        + [((N_SCENARIOS, N_STEPS, N_TIERS), f32), ((N_STEPS,), f32)])


@pytest.mark.parametrize("kernel", ["ingest", "propagation",
                                    "propagation-split", "reduce"])
def test_kernel_compiles_for_v5e(one_chip, kernel):
    fn, shapes = _kernel_call(kernel)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_sweep_chunk_compiles_for_v5e(one_chip, compiled_kernels,
                                           sweep_pipeline):
    fn, args, kw = sweep_pipeline
    compiled = fn.lower(*_shapes(args, one_chip), **kw).compile()
    text = compiled.as_text()
    # the propagation kernel and the reducer kernel
    assert text.count("tpu_custom_call") >= 2
    # each stage's named scope survives into the compiled instructions'
    # metadata, and each kernel is found by its name
    for name in ("ufa_dependency/", "ufa_analytic/", "ufa_timeline/",
                 "%propagation_round.", "%timeline_reduce."):
        assert name in text, name


def test_sharded_sweep_chunk_compiles_for_four_v5e(topo, compiled_kernels,
                                                  sweep_pipeline):
    fn, args, kw = sweep_pipeline
    mesh = jax.make_mesh((4,), ("scenarios",), devices=topo.devices[:4])
    rep, wide = NamedSharding(mesh, P()), NamedSharding(mesh,
                                                        P(None, "scenarios"))
    sharded = (*_shapes(args[:2], rep), *(_shapes(a, wide) for a in args[2:5]),
               *_shapes(args[5:], rep))
    compiled = fn.lower(*sharded, **dict(kw, mesh=mesh)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # every device runs its own quarter: no collective in the program
    assert "all-gather" not in text and "all-reduce" not in text
