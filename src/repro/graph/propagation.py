"""Multi-hop failure propagation over a ``CallGraph`` (JAX fixed point).

The safety question behind the paper's 2x -> 1.3x efficiency claim: when a
preemption/blackhole set S goes dark, which services *break*?  Breakage is
the least fixed point of

    broken = S  ∪  { caller | ∃ fail-close edge caller->callee,
                              callee ∈ broken }

— fail-open edges absorb the failure (graceful degradation), fail-close
edges relay it, cycles are handled by monotonicity.  The kernel runs one
``jax.lax.while_loop`` of scatter-max rounds over the whole edge list for a
*batch* of scenarios at once ((S, n) boolean frontier, (E,) fail-close edge
mask as a ``jnp`` array), so a 256-scenario blackhole ensemble over the
~22k-SE paper fleet is a handful of vectorized sweeps, not 256 graph
traversals.  A scalar BFS reference lives in ``tests/test_graph.py`` and
pins the kernel exactly.

Two interchangeable propagation backends sit behind ``fixed_point``:

  * the XLA scatter-max loop (``_fixed_point``, the historical path and
    the CPU default), and
  * the blocked ELL gather/reduce Pallas kernel
    (``repro.kernels.ufa.propagation``), selected when the edge consts
    carry the ELL adjacency — which ``edge_consts``/``dep_consts`` attach
    when ``repro.kernels.backend.use_ufa_kernels()`` says so
    (accelerator backends, or ``REPRO_UFA_KERNELS=1``).

Both produce bit-identical ``broken`` matrices and round counts; every
entry point (``certify``, ``blast_radius``, ``propagate_many``, the
fused sweep engine's in-pipeline stage, the planner's frontier batches)
routes through the dispatcher.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.graph.callgraph import CallGraph
from repro.kernels import backend as _backend
from repro.kernels.ufa import propagation as _pallas_prop

# blast_radius pads source batches to multiples of _BUCKET (capped at
# _CHUNK rows per propagation) so jit compiles a handful of shapes, not one
# per call — and small source sets don't pay for a full 512-row batch
_CHUNK = 512
_BUCKET = 128


@jax.jit
def _fixed_point(dark: jnp.ndarray, src: jnp.ndarray, dst: jnp.ndarray,
                 closed: jnp.ndarray):
    """Batched least fixed point: dark (S, n) bool -> (broken, rounds).

    Each round scatters ``broken[dst] & closed`` into the callers
    (segment-max over the edge list) and ORs it in; terminates when a full
    round changes nothing.  Round count is bounded by the longest fail-close
    chain (<= n), the loop exits as soon as the frontier stalls.
    """
    n = dark.shape[1]

    def cond(state):
        _, changed, i = state
        return changed & (i < n + 1)

    def body(state):
        broken, _, i = state
        hit = broken[:, dst] & closed[None, :]
        new = broken | jnp.zeros_like(broken).at[:, src].max(hit)
        return new, (new != broken).any(), i + 1

    broken, _, rounds = jax.lax.while_loop(
        cond, body, (dark, jnp.bool_(True), jnp.int32(0)))
    return broken, rounds


def _one_hot(sources: jnp.ndarray, n: int) -> jnp.ndarray:
    """(B,) service indices -> the (B, n) dark batch, one service a row."""
    return sources[:, None] == jnp.arange(n, dtype=sources.dtype)[None, :]


@jax.jit
def _radius_kernel(sources: jnp.ndarray, consts: Dict[str, jnp.ndarray],
                   crit: jnp.ndarray):
    """Batched blast-radius counts: darken each of the (B,) ``sources``
    alone, propagate the (B, n) batch to its fixed point
    (backend-dispatched) and reduce to per-row broken-critical counts *on
    device*, so only (B,) ints cross the host boundary each way (the
    (B, n) dark and broken matrices never do)."""
    broken, _ = fixed_point(_one_hot(sources, crit.shape[0]), consts)
    return (broken & crit[None, :]).sum(axis=1).astype(jnp.int32)


@jax.jit
def _weighted_radius_kernel(sources: jnp.ndarray,
                            consts: Dict[str, jnp.ndarray],
                            weights: jnp.ndarray):
    """Weighted blast radius: same fixed point, but each broken service
    contributes its (f32) weight instead of 1 — the capacity optimizer's
    availability-sensitivity weights turn the planner's edge ranking into
    a blast-*impact* ranking (weights are expected to already encode
    criticality, e.g. zero on non-critical services)."""
    broken, _ = fixed_point(_one_hot(sources, weights.shape[0]), consts)
    return (broken * weights[None, :]).sum(axis=1).astype(jnp.float32)


def fixed_point(dark: jnp.ndarray, consts: Dict[str, jnp.ndarray]):
    """Backend-dispatched batched fixed point: the ELL Pallas kernel when
    ``consts`` carries the ELL adjacency (see ``edge_consts``), the XLA
    scatter-max loop otherwise.  Bit-identical results either way
    (booleans and round counts are exact).  Traceable — the fused sweep
    engine calls it inside its jitted pipeline (the dict-key check is a
    trace-time static)."""
    if "ell_dst" in consts and consts["ell_dst"].shape[1] > 0:
        return _pallas_prop.fixed_point_ell(dark, consts["ell_dst"],
                                            consts["ell_closed"],
                                            consts.get("ell_tag"))
    return _fixed_point(dark, consts["src"], consts["dst"],
                        consts["closed"])


def _ell_topology(graph: CallGraph):
    """Cached node-topology half of the ELL build: ``(ell_dst, row, slot,
    tag)`` of ``ell_split_from_csr``.  They depend only on
    src/dst/indptr, not on the fail-close mask, so they
    survive ``harden``-style mask churn; the mask half is a cheap scatter
    recomputed per ``edge_consts`` call.  The base width follows the
    degree distribution (``ell_width``): the largest degree, rounded up,
    on a graph of like degrees."""
    cache = getattr(graph, "_ell_topology", None)
    if cache is None:
        width = _pallas_prop.ell_width(np.diff(graph.indptr))
        ell_dst, _, row, slot, tag = _pallas_prop.ell_split_from_csr(
            graph.n, graph.indptr, graph.dst, ~graph.fail_open, width)
        cache = (ell_dst, row, slot, tag)
        object.__setattr__(graph, "_ell_topology", cache)
    return cache


def layout_counts(graph: CallGraph) -> Dict[str, int]:
    """What one propagation round walks on the path ``edge_consts``
    takes: ``edges``; ``slots``, the slot loads of a round (every edge on
    the XLA edge-list path; on the ELL path every slot of every row, pad
    slots and a split row's load of its aux rows included);
    ``split_rows``, the caller rows spilled into aux rows."""
    out = {"edges": graph.n_edges, "slots": graph.n_edges, "split_rows": 0}
    if _backend.use_ufa_kernels():
        ell_dst, _, _, tag = _ell_topology(graph)
        split = int(np.count_nonzero(tag[:graph.n] >= 0))
        out.update(slots=ell_dst.size + split, split_rows=split)
    return out


def edge_consts(graph: CallGraph) -> Dict[str, jnp.ndarray]:
    """Device-resident propagation constants: int32 edge endpoints plus
    the fail-close mask, and — when the Pallas path is on
    (``backend.use_ufa_kernels()``) — the ELL adjacency the kernel
    consumes (``ell_dst``/``ell_closed`` (n, K), plus ``ell_slot`` (E,)
    so ``harden_consts`` can flip individual edges in place).  A split
    layout adds its aux rows to ``ell_dst``/``ell_closed``, their tags
    (``ell_tag``) and each edge's row (``ell_row`` (E,))."""
    out = {"src": jnp.asarray(graph.src, jnp.int32),
           "dst": jnp.asarray(graph.dst, jnp.int32),
           "closed": jnp.asarray(~graph.fail_open)}
    if _backend.use_ufa_kernels():
        ell_dst, row, slot, tag = _ell_topology(graph)
        if ell_dst.shape[1] > 0:
            closed = ~graph.fail_open
            ell_closed = np.zeros(ell_dst.shape, bool)
            ell_closed[row, slot] = closed
            out["ell_dst"] = jnp.asarray(ell_dst)
            out["ell_closed"] = jnp.asarray(ell_closed)
            out["ell_slot"] = jnp.asarray(slot)
            if ell_dst.shape[0] > graph.n:
                out["ell_row"] = jnp.asarray(row)
                out["ell_tag"] = jnp.asarray(tag)
    return out


@jax.jit
def _harden_masks(closed: jnp.ndarray, ell: Optional[tuple],
                  pick: jnp.ndarray):
    """``closed`` and, with ``ell = (ell_closed, rows, slots)``, its ELL
    mirror with edges ``pick`` set fail-open (repeats are harmless)."""
    closed = closed.at[pick].set(False)
    if ell is None:
        return closed, None
    ell_closed, rows, slots = ell
    return closed, ell_closed.at[rows[pick], slots[pick]].set(False)


def harden_consts(consts: Dict[str, jnp.ndarray],
                  pick: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Convert edges ``pick`` (CSR indices) to fail-open in the device
    consts — both the edge-list mask and, when present, its ELL mirror,
    at each edge's own row (an aux row where its caller's row is split) —
    in one jitted update that re-uploads nothing else (the planner's
    per-round update, whose ``pick`` is padded to ``batch`` with repeats
    so that it compiles once)."""
    ell = None
    if "ell_closed" in consts:
        ell = (consts["ell_closed"], consts.get("ell_row", consts["src"]),
               consts["ell_slot"])
    closed, ell_closed = _harden_masks(consts["closed"], ell, pick)
    out = dict(consts, closed=closed)
    if ell is not None:
        out["ell_closed"] = ell_closed
    return out


def radius_width(k: int) -> int:
    """Rows of the bucket-padded batches that sweep ``k`` sources: ``k``
    rounded up to a multiple of _BUCKET (a full _CHUNK is whole
    buckets)."""
    return _BUCKET * -(-k // _BUCKET)


def radius_counts(sources: np.ndarray, consts: Dict[str, jnp.ndarray],
                  crit_d, weights=None) -> np.ndarray:
    """Blast-radius counts for ``sources`` against device-resident edge
    consts (``edge_consts``) — the reusable closure the hardening planner
    calls once per greedy round (the device arrays are uploaded once, not
    per call).  Sources are swept in bucket-padded batches
    (``radius_width``, at most _CHUNK rows each) through the jitted
    kernel, which builds each batch's one-hot dark rows itself: a batch
    crosses as (width,) int32 indices and comes back as (width,) counts.
    Returns counts aligned with ``sources``.

    ``weights`` (optional, device-resident (n,) f32): rank by *weighted*
    blast radius — the sum of per-service weights over the broken set —
    instead of the unweighted broken-critical count.  ``None`` keeps the
    historical integer counts bit-identical."""
    sources = np.asarray(sources, np.int32)
    out = np.zeros(len(sources), np.int32 if weights is None else np.float32)
    for lo in range(0, len(sources), _CHUNK):
        chunk = sources[lo:lo + _CHUNK]
        pad = np.full(radius_width(len(chunk)), chunk[-1], np.int32)
        pad[:len(chunk)] = chunk
        if weights is None:
            counts = _radius_kernel(pad, consts, crit_d)
        else:
            counts = _weighted_radius_kernel(pad, consts, weights)
        out[lo:lo + len(chunk)] = np.asarray(counts)[:len(chunk)]
    return out


def dep_consts(graph: CallGraph) -> Dict[str, jnp.ndarray]:
    """Device-resident propagation constants for the fused sweep engine:
    ``edge_consts`` plus the critical mask and the (f32) critical count.
    Upload once per graph; every fused pipeline call reuses them (keyed
    jit cache on shapes only)."""
    out = edge_consts(graph)
    out["crit"] = jnp.asarray(graph.critical)
    out["n_crit"] = jnp.asarray(max(1, int(graph.critical.sum())),
                                jnp.float32)
    return out


def shared_blackhole_draws(graph: CallGraph, fractions: np.ndarray,
                           seed: int = 0
                           ) -> tuple[np.ndarray, np.ndarray]:
    """Host-side stage precompute for the fused engine: the
    ``blackhole_ensemble`` shared-draw semantics (one uniform per service,
    scenario s darkens preemptibles with ``u < fractions[s]``) compressed
    to *unique* fractions — equal fractions share one dark set, so a 100k
    scenario grid with a handful of ``evict_fraction`` values propagates
    a handful of dark sets, not 100k.  Returns ``(dark_unique (U, n)
    bool, inverse (S,) int32)`` with ``dark_unique[inverse]`` the full
    per-scenario dark matrix (never materialized)."""
    rng = np.random.default_rng(seed)
    fractions = np.asarray(fractions, np.float64)
    u = rng.random(graph.n)                  # same stream as the ensemble
    uniq, inverse = np.unique(fractions, return_inverse=True)
    dark = (u[None, :] < uniq[:, None]) & graph.preemptible[None, :]
    return dark, inverse.astype(np.int32)


def combined_dark_uniques(graph: CallGraph, evict_fractions: np.ndarray,
                          storm_fractions: Optional[np.ndarray],
                          seed: int, storm_seed: int
                          ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Unique dark sets for BOTH dependency stages of the fused engine in
    one propagation batch: the per-scenario blackhole uniques (stream
    ``seed``) plus — when a cascade storm is active anywhere in the grid
    — the storm's re-darkening uniques under an INDEPENDENT uniform
    stream (``storm_seed``; see ``core.scenarios.stage_seed``), appended
    row-wise so one ``fixed_point`` while_loop settles every dark set.
    Per-row fixed points are independent and monotone, so concatenating
    rows never changes any row's verdict.

    Returns ``(dark_u (U, n) bool, inv (S,) int32, storm_inv (S,)
    int32)`` — scenario ``s`` gathers its blackhole verdict at row
    ``inv[s]`` and its storm verdict at row ``storm_inv[s]``.  With no
    storm (``storm_fractions`` None or all zero) a single all-false row
    is appended and every ``storm_inv`` points at it, so the pipeline
    keeps one static structure either way."""
    dark_u, inv = shared_blackhole_draws(graph, evict_fractions, seed=seed)
    storm_fractions = (None if storm_fractions is None
                       else np.asarray(storm_fractions, np.float64))
    if storm_fractions is None or not (storm_fractions > 0.0).any():
        dark_u = np.concatenate(
            [dark_u, np.zeros((1, graph.n), bool)])
        storm_inv = np.full(len(inv), len(dark_u) - 1, np.int32)
        return dark_u, inv, storm_inv
    sdark, sinv = shared_blackhole_draws(graph, storm_fractions,
                                         seed=storm_seed)
    storm_inv = (sinv + len(dark_u)).astype(np.int32)
    return np.concatenate([dark_u, sdark]), inv, storm_inv


def broken_critical_fractions(dark_u: jnp.ndarray, dep: Dict
                              ) -> tuple[jnp.ndarray, jnp.ndarray,
                                         jnp.ndarray]:
    """Traceable blackhole verdicts for a (U, n) dark batch against
    ``dep_consts`` arrays: per-row broken-critical counts (int32), the
    f32 broken-critical fraction that feeds the availability penalty, and
    the dark-set sizes (int32).  Runs the same ``_fixed_point`` kernel as
    ``propagate_many`` but stays on device — the fused sweep engine calls
    it *inside* its jitted pipeline."""
    broken, _ = fixed_point(dark_u, dep)
    counts = (broken & dep["crit"][None, :]).sum(axis=1).astype(jnp.int32)
    frac = counts.astype(jnp.float32) / dep["n_crit"]
    n_dark = dark_u.sum(axis=1).astype(jnp.int32)
    return counts, frac, n_dark


def propagate_many(graph: CallGraph, dark: np.ndarray
                   ) -> tuple[np.ndarray, int]:
    """dark (S, n) bool -> (broken (S, n) bool, rounds)."""
    dark = np.asarray(dark, bool)
    assert dark.ndim == 2 and dark.shape[1] == graph.n, dark.shape
    broken, rounds = fixed_point(jnp.asarray(dark), edge_consts(graph))
    return np.asarray(broken), int(rounds)


def propagate(graph: CallGraph, dark: np.ndarray) -> np.ndarray:
    """dark (n,) bool -> broken (n,) bool (single-scenario convenience)."""
    broken, _ = propagate_many(graph, np.asarray(dark, bool)[None, :])
    return broken[0]


# ---------------------------------------------------------------------------
# certification, blast radius, ensembles
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Certification:
    ok: bool                      # no critical service breaks
    broken: np.ndarray            # (n,) bool — dark set included
    broken_critical: np.ndarray   # (n,) bool
    n_broken_critical: int
    n_critical: int
    n_dark: int
    rounds: int                   # propagation rounds to the fixed point

    @property
    def multi_hop(self) -> np.ndarray:
        """Criticals that broke but have no direct fail-close cause — they
        can only have been reached through a relay chain."""
        return self.broken_critical & ~self._direct

    _direct: np.ndarray = dataclasses.field(default=None, repr=False)


def certify(graph: CallGraph, dark: Optional[np.ndarray] = None
            ) -> Certification:
    """Full-fleet multi-hop blackhole certification: default dark set is
    every preemptible service (the failover worst case)."""
    if dark is None:
        dark = graph.preemptible
    dark = np.asarray(dark, bool)
    with obs.span("ufa.graph.certify", services=graph.n,
                  **layout_counts(graph)) as sp:
        broken_b, rounds = propagate_many(graph, dark[None, :])
        sp.set(rounds=rounds)
        broken = broken_b[0]
        bc = broken & graph.critical & ~dark
        # direct causes: criticals with a fail-close edge into the dark set
        direct_edge = ~graph.fail_open & np.asarray(dark, bool)[graph.dst]
        direct = np.zeros(graph.n, bool)
        direct[graph.src[direct_edge]] = True
    return Certification(
        ok=not bc.any(), broken=broken, broken_critical=bc,
        n_broken_critical=int(bc.sum()),
        n_critical=int(graph.critical.sum()),
        n_dark=int(np.count_nonzero(dark)), rounds=rounds,
        _direct=direct & graph.critical)


def blast_radius(graph: CallGraph,
                 sources: Optional[Sequence[int]] = None) -> np.ndarray:
    """Exact per-service blast radius: entry j = number of critical
    services that break when service j (alone) goes dark, j itself
    included if critical.

    Default sources are the services that can actually go dark and feed an
    unsafe edge — preemptible callees of fail-close edges — which is the
    set the hardening planner ranks.  Pass explicit sources for arbitrary
    what-if sweeps.  Sources are swept in padded chunks through the batched
    kernel (one (chunk, n) propagation per chunk).
    """
    if sources is None:
        unsafe_dst = graph.dst[~graph.fail_open]
        sources = np.unique(unsafe_dst[graph.preemptible[unsafe_dst]])
    sources = np.asarray(sources, np.int64)
    out = np.zeros(graph.n, np.int32)
    if len(sources) == 0:
        return out
    out[sources] = radius_counts(sources, edge_consts(graph),
                                 jnp.asarray(graph.critical))
    return out


def blackhole_ensemble(graph: CallGraph, n_scenarios: int = 256,
                       seed: int = 0,
                       fractions: Optional[np.ndarray] = None,
                       kind: str = "random") -> Dict[str, np.ndarray]:
    """Certify a whole ensemble of preemption scenarios in one batched
    pass (chaos-engineering style: hundreds of distinct blackhole sets,
    per-scenario verdicts).

    kind="random": scenario s darkens each preemptible service i.i.d. with
    probability fractions[s]; the uniform draws are shared across
    scenarios, so sorting the fractions makes the dark sets *nested* — the
    broken counts are then provably monotone in the fraction, which the
    property tests exploit.
    kind="grid": fractions swept over a linspace, same shared draws.
    """
    rng = np.random.default_rng(seed)
    if fractions is None:
        fractions = (np.linspace(0.0, 1.0, n_scenarios)
                     if kind == "grid"
                     else rng.uniform(0.05, 1.0, n_scenarios))
    fractions = np.asarray(fractions, np.float64)
    with obs.span("ufa.graph.ensemble", scenarios=len(fractions),
                  **layout_counts(graph)) as sp:
        u = rng.random(graph.n)
        dark = (u[None, :] < fractions[:, None]) & graph.preemptible[None, :]
        broken, rounds = propagate_many(graph, dark)
        sp.set(rounds=rounds)
        bc = broken & graph.critical[None, :]
        return {
            "dark_fraction": fractions,
            "n_dark": dark.sum(axis=1),
            "n_broken": broken.sum(axis=1),
            "n_broken_critical": bc.sum(axis=1),
            "broken_critical_frac": bc.sum(axis=1)
            / max(1, int(graph.critical.sum())),
            "ok": ~bc.any(axis=1),
            "rounds": np.int32(rounds),
        }
