"""Sharded fused sweep engine: full-peak scenario ensembles at 100k+.

The paper certifies UFA against rare full-peak failovers by exploring the
scenario space; PR 4's composition ran the analytic model
(``scenarios._sweep_jit``), the timeline scan (``timeline_sim._sweep_jit``)
and the dependency propagation (``graph.blackhole_ensemble``) as *separate*
jitted calls with host round-trips between them, and materialized the full
(S, T, series) trace stack even when only verdicts were wanted — which is
why ensembles capped out around 256 scenarios.  This module fuses the
three stages into ONE jitted, device-parallel pipeline:

  * per scenario, ``scenarios.scenario_outcome`` (closed-form verdicts),
    ``timeline_sim.timeline_verdicts`` (the ``lax.scan`` timeline kernel,
    summary-only — no trace materialization) and the dependency-propagation
    penalty are composed inside one ``vmap``;
  * the blackhole propagation runs on device inside the same program:
    unique ``evict_fraction`` dark sets (shared uniform draws, as in
    ``blackhole_ensemble``) go through the ``lax.while_loop`` fixed point
    once, and each scenario *gathers* its broken-critical fraction — the
    (S, n) dark matrix and the per-scenario verdicts never touch the host
    between stages;
  * the scenario axis is bucket-padded and reshaped to ``(n_chunks,
    chunk)`` mega-batches driven by ``lax.map`` — chunk widths and chunk
    counts are padded to powers of two, so grids from 256 to 100k+
    scenarios reuse a handful of compiled shapes (no recompile per size
    within a padding bucket; see ``bucket_shape`` / ``compiled_variants``);
  * the scenario width is split across devices (a ``NamedSharding``
    over a 1-D "scenarios" mesh, and ``shard_map`` so that each device
    runs the whole pipeline on its own slice);
  * the program packs its result columns, per dtype, into one
    ``(rows, n_chunks, width)`` buffer each (``_Packed``), so the host
    fetches two or three arrays in one batched ``jax.device_get`` rather
    than one blocking copy per column, and unpacks them into views.  No
    packed output has the shape of a scenario buffer, so none of those is
    donated: there is nothing it could be aliased to.

Config (fleet aggregates, timeline constants, graph edges) is precomputed
once into device-resident arrays and passed as *traced* arguments, so the
jit cache is keyed on static shapes only — re-running with a different
fleet or scenario values never recompiles.

Equivalence contract (pinned by ``tests/test_sweep_engine.py``): the fused
pipeline matches the composed ``sweep_scenarios`` + ``sweep_timeline`` +
propagation path exactly (bit-for-bit) on every verdict key, sharded or
not.
"""

from __future__ import annotations

import math
import time
from functools import partial
from typing import Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.scenarios import (FleetAggregates, analytic_consts,
                                  scenario_grid, scenario_outcome,
                                  stage_seed)
from repro.core.timeline_sim import (PARAM_KEYS, TimelineConfig,
                                     default_scenario, default_ts,
                                     timeline_verdicts,
                                     timeline_verdicts_batch,
                                     validate_grid)
from repro.dist.smap import shard_map
from repro.kernels import backend as _kbackend

# mega-batch width for lax.map chunking: big enough to amortize scan-step
# overhead, small enough that a chunk's per-step working set stays in
# cache (measured fastest on CPU among {256..64k} widths)
CHUNK = 4096
# smallest padded width — tiny interactive grids don't pay for a full
# 4096-wide chunk (and every bucket stays divisible by 8 devices)
MIN_BUCKET = 256


def _pow2_ceil(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def bucket_shape(n: int, chunk: int = CHUNK) -> tuple[int, int]:
    """Padded ``(n_chunks, width)`` for an ``n``-scenario grid: width is a
    power of two in [MIN_BUCKET, chunk], the chunk count a power of two —
    so every grid size in a bucket compiles (and caches) the same shapes.
    """
    if n <= chunk:
        return 1, max(MIN_BUCKET, _pow2_ceil(n))
    return _pow2_ceil(-(-n // chunk)), chunk


def _fused_verdicts(consts: Dict, p: Dict, ts, temporal: bool,
                    tau=None) -> Dict:
    """ONE scenario, all stages: the analytic closed-form verdicts plus
    (``temporal``) the ``t_``-prefixed timeline-scan verdicts — the same
    kernels the standalone sweeps vmap, composed in one trace.  ``tau``
    (a traced f32 scalar, or None) threads the opt-in soft relaxation
    into both kernels — sigmoid verdict indicators for the capacity
    optimizer; None traces the historical bit-exact ops."""
    with jax.named_scope("ufa_analytic"):
        out = dict(scenario_outcome(consts["a"], p, tau))
    if temporal:
        with jax.named_scope("ufa_timeline"):
            tsum = timeline_verdicts(consts["t"], p, ts, tau)
        out.update({f"t_{k}": v for k, v in tsum.items()})
    return out


def _fused_verdicts_block(consts: Dict, p: Dict, ts, temporal: bool,
                          reducer: str, tau=None) -> Dict:
    """One WIDTH-wide scenario block.  ``reducer="scan"`` vmaps the
    per-scenario fused trace (the historical, bit-exact default path);
    ``reducer="pallas"`` keeps the analytic stage identical but runs the
    timeline carry through the segmented Pallas verdict-reduction kernel
    (``timeline_verdicts_batch``) — exact on every verdict except the
    float32-tight availability integral.  Soft mode (``tau``) always
    takes the scan path: the Pallas reducer is hard-only."""
    if reducer == "pallas" and temporal and tau is None:
        with jax.named_scope("ufa_analytic"):
            out = dict(jax.vmap(
                lambda q: dict(scenario_outcome(consts["a"], q)))(p))
        with jax.named_scope("ufa_timeline"):
            tsum = timeline_verdicts_batch(consts["t"], p, ts)
        out.update({f"t_{k}": v for k, v in tsum.items()})
        return out
    return jax.vmap(
        lambda q: _fused_verdicts(consts, q, ts, temporal, tau))(p)


_WIDE = P(None, "scenarios")          # (n_chunks, width): split the width
_PACKED = P(None, None, "scenarios")  # (rows, n_chunks, width): split too


@jax.tree_util.register_pytree_node_class
class _Packed:
    """The pipeline's result columns, one buffer per dtype shaped
    ``(rows, n_chunks, width)``: the scenario axis is minor, so each
    column is one contiguous host row.  ``layout`` is the pytree's static
    aux data, ``(key, buffer, first row, trailing shape)`` per column:
    the host unpacks any compiled variant without tracing it again."""

    def __init__(self, buffers, layout):
        self.buffers, self.layout = tuple(buffers), layout

    def tree_flatten(self):
        return self.buffers, self.layout

    @classmethod
    def tree_unflatten(cls, layout, buffers):
        return cls(buffers, layout)


def _pack(out: Dict) -> _Packed:
    """Stack ``lax.map``'s ``(n_chunks, width, *trailing)`` outputs by
    dtype into ``_Packed`` buffers, one row per trailing index (a minor
    axis of 7 would pad to 128 lanes on the TPU).  Leaves are grouped by
    their traced dtype, so no dtype is promoted."""
    groups: Dict = {}
    for k, v in out.items():
        rows = jnp.moveaxis(v, tuple(range(2, v.ndim)),
                            tuple(range(v.ndim - 2)))
        groups.setdefault(v.dtype, []).append(
            (k, rows.reshape(-1, *v.shape[:2]), v.shape[2:]))
    buffers, layout = [], []
    for i, cols in enumerate(groups.values()):
        row = 0
        for k, rows, trailing in cols:
            layout.append((k, i, row, trailing))
            row += rows.shape[0]
        buffers.append(jnp.concatenate([rows for _, rows, _ in cols]))
    return _Packed(buffers, tuple(layout))


def _unpack(packed: _Packed, n: int) -> Dict[str, np.ndarray]:
    """Fetch the packed buffers in one ``jax.device_get`` (every copy is
    issued before the host waits on any) and cut them into the first
    ``n`` scenarios of each column: views, ``(n, *trailing)``."""
    host = jax.device_get(packed.buffers)
    out = {}
    for k, i, row, trailing in packed.layout:
        size = math.prod(trailing)
        cols = host[i][row:row + size].reshape(size, -1)[:, :n]
        out[k] = cols.T.reshape(n, *trailing)
    return out


def _per_device(fn, mesh, in_specs):
    """Run ``fn`` once per device of the 1-D scenario ``mesh`` on that
    device's slice of the scenario width (``shard_map``; ``in_specs``
    marks the ``(n_chunks, width)`` arguments ``_WIDE``, the rest ``P()``;
    each device packs its own slice of the ``_Packed`` result).  Every
    verdict is per scenario, so the body needs no collectives, and each
    device runs the Pallas reducer on its own slice.  ``mesh=None`` runs
    ``fn`` as it is."""
    if mesh is None:
        return fn
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=_PACKED)


@partial(jax.jit, static_argnames=("temporal", "reducer", "mesh"))
def _run_chunks(consts, pchunks, ts, tau=None, *, temporal,
                reducer="scan", mesh=None):
    """Fused pipeline, explicit ``dep_broken_frac``: lax.map over
    ``(n_chunks, width)`` scenario mega-batches of the fused scenario
    block function.  ``tau=None`` vs a traced scalar hit different jit
    cache entries (different pytree structures), so the hard path's
    compiled program is untouched by soft runs.  Returns ``_Packed``."""
    def body(consts, pchunks, ts, tau):
        return _pack(lax.map(lambda p: _fused_verdicts_block(
            consts, p, ts, temporal, reducer, tau), pchunks))
    return _per_device(body, mesh, (P(), _WIDE, P(), P()))(
        consts, pchunks, ts, tau)


@partial(jax.jit, static_argnames=("temporal", "reducer", "mesh"))
def _run_chunks_dep(consts, dep, pchunks, invchunks, storm_invchunks,
                    dark_u, ts, tau=None, *, temporal, reducer="scan",
                    mesh=None):
    """Fused pipeline with the dependency stage in-program: propagate the
    (U, n) unique dark sets to their fixed point (backend-dispatched —
    the Pallas ELL kernel when ``dep`` carries the ELL adjacency), then
    every scenario gathers its broken-critical fraction/counts by
    unique-fraction index — no host materialization between propagation
    and the availability model.  ``dark_u`` carries the blackhole uniques
    AND the cascade-storm uniques (``combined_dark_uniques``): one
    while_loop settles both stages, and each scenario gathers its storm
    verdict (``storm_broken_frac``) by its second index.  Sharded, every
    device settles the (few) unique dark sets itself and gathers for its
    own scenarios.  Returns ``_Packed``."""
    from repro.graph.propagation import broken_critical_fractions

    def body(consts, dep, pchunks, invchunks, storm_invchunks, dark_u, ts,
             tau):
        with jax.named_scope("ufa_dependency"):
            counts, frac, n_dark = broken_critical_fractions(dark_u, dep)

        def one(args):
            p, inv, sinv = args
            with jax.named_scope("ufa_dependency"):
                p = dict(p, dep_broken_frac=frac[inv],
                         storm_broken_frac=frac[sinv])
                dep_out = {"dep_n_broken_critical": counts[inv],
                           "dep_n_dark": n_dark[inv]}
            out = _fused_verdicts_block(consts, p, ts, temporal, reducer,
                                        tau)
            out.update(dep_out)
            return out
        return _pack(lax.map(one, (pchunks, invchunks, storm_invchunks)))
    specs = (P(), P(), _WIDE, _WIDE, _WIDE, P(), P(), P())
    return _per_device(body, mesh, specs)(consts, dep, pchunks, invchunks,
                                          storm_invchunks, dark_u, ts, tau)


def compiled_variants() -> int:
    """Number of compiled pipeline programs (jit cache entries across both
    entry points) — the scale bench asserts this does not grow across
    grid sizes within a padding bucket."""
    return int(_run_chunks._cache_size() + _run_chunks_dep._cache_size())


class SweepEngine:
    """One fleet's fused sweep pipeline: config uploaded once, then
    ``run`` executes arbitrary scenario grids end to end in one jitted,
    sharded program.

    Parameters
      agg       class-level fleet aggregates (the analytic model's input)
      timeline  ``TimelineConfig`` (from ``Orchestrator.timeline_config()``
                or ``config_for_fleet``)
      graph     optional ``CallGraph`` — enables the in-pipeline
                dependency stage (per-scenario blackholes keyed on
                ``evict_fraction``, shared draws under ``seed``)
      ts        time grid for the timeline scan (default 2h / 240 steps)
      chunk     mega-batch width (power of two; default ``CHUNK``)
      devices   devices to shard the scenario axis over (a sequence, or
                an int meaning the first k of ``jax.devices()``).
                Explicitly-passed devices always shard; the default (all
                local devices) shards only multi-chunk grids, where the
                partition overhead amortizes — small interactive grids
                run single-device either way
      reducer   timeline-carry backend: "scan" (sequential ``lax.scan``,
                bit-exact vs the composed sweeps) or "pallas" (the
                segmented verdict-reduction kernel; float32-tight on the
                availability integral, exact elsewhere).  Default: per
                backend via ``kernels.backend.use_ufa_kernels()`` —
                "pallas" on accelerators / ``REPRO_UFA_KERNELS=1``,
                "scan" on plain CPU
      analytic_extra  optional kwargs dict forwarded to
                ``analytic_consts`` (``ao_buffer`` / ``spawn_mult``) —
                the capacity optimizer's hook for verifying an optimized
                design through the real hard pipeline
    """

    def __init__(self, agg: FleetAggregates, timeline: TimelineConfig, *,
                 graph=None, seed: int = 0,
                 ts: Optional[np.ndarray] = None,
                 chunk: int = CHUNK,
                 devices: Optional[object] = None,
                 reducer: Optional[str] = None,
                 analytic_extra: Optional[Dict] = None):
        if reducer is None:
            reducer = "pallas" if _kbackend.use_ufa_kernels() else "scan"
        assert reducer in ("scan", "pallas"), reducer
        self.reducer = reducer
        self.consts = {"a": analytic_consts(agg, **(analytic_extra or {})),
                       "t": timeline.as_consts()}
        self._preheat = timeline.preheat_s
        self.ts = np.asarray(default_ts() if ts is None else ts, np.float64)
        self._ts_dev = jnp.asarray(self.ts, jnp.float32)
        self.chunk = int(chunk)
        self.graph = graph
        self.seed = seed
        if graph is not None:
            from repro.graph.propagation import dep_consts
            self.dep = dep_consts(graph)
            # the cascade-storm stage draws its dark sets from a stream
            # independent of the blackhole draws, derived from the one
            # engine seed (campaign reproducibility without stream reuse)
            self.storm_seed = stage_seed(seed, "storm")
        # explicit devices force sharding; by default shard only when the
        # grid spills past one chunk — partition overhead loses on small
        # grids (see the README scaling table), and the thin wrappers
        # (sweep_scenarios / sweep_with_dependency_ensemble) must not
        # silently slow the 256-scenario default down on multi-device
        # hosts
        self._devices_explicit = devices is not None
        if devices is None:
            devices = jax.devices()
        elif isinstance(devices, int):
            devices = jax.devices()[:devices]
        self.devices = list(devices)
        self.mesh = (jax.make_mesh((len(self.devices),), ("scenarios",),
                                   devices=self.devices)
                     if len(self.devices) > 1 else None)

    # ------------------------------------------------------------------
    def _params(self, grid: Dict[str, np.ndarray], n: int, shape) -> Dict:
        """Bucket-pad + chunk the scenario axes to float32 ``shape``
        arrays (missing axes filled with the operating-point defaults)."""
        defaults = default_scenario(burst_delay_s=self._preheat)
        out = {}
        for k in PARAM_KEYS:
            if k in ("dep_broken_frac", "storm_broken_frac"):
                continue                    # computed stages, not axes
            col = (np.asarray(grid[k], np.float32) if k in grid
                   else np.full(n, defaults[k], np.float32))
            out[k] = self._chunked(col, shape)
        return out

    def _chunked(self, col: np.ndarray, shape) -> np.ndarray:
        """(n,) -> (n_chunks, width), padding with the last scenario."""
        pad = shape[0] * shape[1] - len(col)
        if pad:
            col = np.concatenate([col, np.repeat(col[-1:], pad, axis=0)])
        return col.reshape(shape)

    def _shard_for(self, shape) -> bool:
        """Shard this run?  Explicit ``devices`` always shard; otherwise
        only multi-chunk grids (> one CHUNK) amortize the partition
        overhead."""
        if self.mesh is None or shape[1] % len(self.devices):
            return False
        return self._devices_explicit or shape[0] > 1

    def _put(self, tree, shard: bool):
        """Shard the chunk axis over the scenario mesh (replicated when
        sharding is off for this run)."""
        if not shard:
            return tree
        return jax.device_put(tree, NamedSharding(self.mesh, _WIDE))

    def _pipeline(self, grid: Dict[str, np.ndarray],
                  dep_broken_frac: Optional[np.ndarray] = None,
                  temporal: bool = True, tau=None):
        """The jitted pipeline ``run`` calls for ``grid``, with its
        arguments: ``(fn, args, kwargs)``."""
        n = validate_grid(grid)
        shape = bucket_shape(n, self.chunk)
        params = self._params(grid, n, shape)
        shard = self._shard_for(shape)
        kw = dict(temporal=temporal,
                  reducer=self.reducer if tau is None else "scan",
                  mesh=self.mesh if shard else None)
        if self.graph is not None and dep_broken_frac is None:
            from repro.graph.propagation import combined_dark_uniques
            fractions = (np.asarray(grid["evict_fraction"])
                         if "evict_fraction" in grid else np.ones(n))
            storm_fr = (np.asarray(grid["storm_refrac"])
                        if "storm_refrac" in grid else None)
            dark_u, inv, storm_inv = combined_dark_uniques(
                self.graph, fractions, storm_fr,
                seed=self.seed, storm_seed=self.storm_seed)
            return _run_chunks_dep, (
                self.consts, self.dep, self._put(params, shard),
                self._put(self._chunked(inv, shape), shard),
                self._put(self._chunked(storm_inv, shape), shard),
                jnp.asarray(dark_u), self._ts_dev, tau), kw
        frac = (np.zeros(n, np.float32) if dep_broken_frac is None
                else np.asarray(dep_broken_frac, np.float32))
        params["dep_broken_frac"] = self._chunked(frac, shape)
        sfrac = (np.asarray(grid["storm_broken_frac"], np.float32)
                 if "storm_broken_frac" in grid
                 else np.zeros(n, np.float32))
        params["storm_broken_frac"] = self._chunked(sfrac, shape)
        return _run_chunks, (self.consts, self._put(params, shard),
                             self._ts_dev, tau), kw

    # ------------------------------------------------------------------
    def dep_fractions(self, fractions: np.ndarray
                      ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Per-scenario dependency verdicts as host arrays — the
        *composed*-path helper the equivalence tests pit against the
        in-pipeline stage: (broken_critical_frac f32, n_broken_critical
        i32, n_dark i32), computed by the same device kernel."""
        from repro.graph.propagation import (broken_critical_fractions,
                                             shared_blackhole_draws)
        dark_u, inv = shared_blackhole_draws(self.graph, fractions,
                                             seed=self.seed)
        counts, frac, n_dark = broken_critical_fractions(
            jnp.asarray(dark_u), self.dep)
        return (np.asarray(frac)[inv], np.asarray(counts)[inv],
                np.asarray(n_dark)[inv])

    def storm_fractions(self, refracs: np.ndarray) -> np.ndarray:
        """Per-scenario STORM-stage broken-critical fractions as a host
        array — the composed-path mirror of the in-pipeline cascade-storm
        stage (same derived ``storm_seed`` stream, same device kernel),
        for equivalence tests and host-side what-ifs."""
        from repro.graph.propagation import (broken_critical_fractions,
                                             shared_blackhole_draws)
        dark_u, inv = shared_blackhole_draws(self.graph,
                                             np.asarray(refracs, np.float64),
                                             seed=self.storm_seed)
        _, frac, _ = broken_critical_fractions(jnp.asarray(dark_u),
                                               self.dep)
        return np.asarray(frac)[inv]

    # ------------------------------------------------------------------
    def run(self, grid: Optional[Dict[str, np.ndarray]] = None,
            dep_broken_frac: Optional[np.ndarray] = None,
            temporal: bool = True,
            soft_tau: Optional[float] = None) -> Dict[str, np.ndarray]:
        """Evaluate every scenario in ``grid`` through the fused pipeline;
        returns the analytic verdicts, the ``t_``-prefixed temporal
        verdicts (unless ``temporal=False``), the grid axes, and — when
        the engine has a graph and no explicit ``dep_broken_frac`` — the
        ``dep_n_broken_critical`` / ``dep_n_dark`` propagation verdicts.

        The grid is validated up front (``timeline_sim.validate_grid``):
        unknown axes raise instead of silently sweeping nothing (a
        misspelled key used to fall back to the operating-point default
        for every scenario), and empty/zero-length grids raise instead of
        crashing deep inside the chunker.

        ``soft_tau`` (opt-in): evaluate the SOFT-relaxed pipeline at that
        temperature — verdict keys come back as sigmoid indicators in
        [0, 1] (float, not bool).  Forces the scan reducer (the Pallas
        verdict reduction is hard-only); ``None`` runs the historical
        bit-exact program."""
        # one enabled() branch per run() call — free off (and the result
        # below is host-materialized, so the interior timing is honest)
        meter = obs.enabled()
        if meter:
            t0 = time.perf_counter()
            variants0 = compiled_variants()
        with obs.span("ufa.sweep.run") as run_span:
            with obs.span("ufa.sweep.prepare"):
                grid = scenario_grid() if grid is None else grid
                n = validate_grid(grid)
                tau = (None if soft_tau is None
                       else jnp.asarray(soft_tau, jnp.float32))
                shape = bucket_shape(n, self.chunk)
                fn, args, kw = self._pipeline(grid, dep_broken_frac,
                                              temporal, tau)
            run_span.set(scenarios=n, padded=shape[0] * shape[1],
                         chunks=shape[0], sharded=int(kw["mesh"] is not None))
            with obs.span("ufa.sweep.dispatch"):
                out = fn(*args, **kw)
            with obs.span("ufa.sweep.fetch", columns=len(out.layout),
                          transfers=len(out.buffers)):
                result = _unpack(out, n)
                result.update({k: np.asarray(v) for k, v in grid.items()})
        if meter:
            dt = time.perf_counter() - t0
            variants = compiled_variants()
            obs.inc("ufa_sweep_runs_total")
            obs.inc("ufa_sweep_scenarios_total", n)
            if dt > 0:
                obs.set_gauge("ufa_sweep_scenarios_per_s", n / dt)
            obs.observe("ufa_sweep_run_seconds", dt)
            padded = shape[0] * shape[1]
            obs.set_gauge("ufa_sweep_padding_waste_ratio",
                          (padded - n) / padded)
            obs.set_gauge("ufa_sweep_compiled_variants", variants)
            if variants > variants0:
                obs.inc("ufa_sweep_compile_misses_total",
                        variants - variants0)
        return result


def fused_sweep(fs, grid: Optional[Dict[str, np.ndarray]] = None, *,
                with_graph: bool = True, seed: int = 0, region=None,
                ts: Optional[np.ndarray] = None, temporal: bool = True,
                chunk: int = CHUNK,
                devices: Optional[object] = None
                ) -> Dict[str, np.ndarray]:
    """Convenience one-shot: build the engine for a fleet (``FleetState``
    or dict of ``ServiceSpec``) and run a grid through the full fused
    pipeline (dependency stage included when the fleet has edges and
    ``with_graph``)."""
    from repro.core.timeline_sim import config_for_fleet
    agg = (FleetAggregates.from_fleet_state(fs) if hasattr(fs, "fclass")
           else FleetAggregates.from_fleet(fs))
    graph = None
    if with_graph and hasattr(fs, "fclass"):
        from repro.graph import CallGraph
        graph = CallGraph.from_fleet_state(fs)
    timeline = config_for_fleet(fs, region=region)
    eng = SweepEngine(agg, timeline, graph=graph, seed=seed, ts=ts,
                      chunk=chunk, devices=devices)
    return eng.run(grid, temporal=temporal)


def tile_grid(grid: Dict[str, np.ndarray], n: int) -> Dict[str, np.ndarray]:
    """Tile a scenario grid out to ``n`` rows (cycling the base grid) —
    the scale benches use this to sweep {256 .. 100k+} scenario counts
    with the paper's axes."""
    return {k: np.resize(np.asarray(v), n) for k, v in grid.items()}
