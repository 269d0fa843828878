"""Runtime fail-close dependency detection (paper §6, first layer).

Monitors RPC traffic and correlates caller errors with callee failures: if a
caller endpoint consistently returns errors when a callee endpoint fails,
the (caller -> callee) edge is classified fail-close.  Here the "live
traffic" is generated from the synthesized fleet's call graph — the planted
``fail_open=False`` edges are the ground truth the detector must find.

The paper's runtime layer sees 62 *trillion* RPCs a week, so this module is
array-native end to end — no per-RPC Python objects anywhere on the hot
path:

  * edges are integer IDs (``TraceEdges``) with Table-2 / cold-path
    sampling weights held as arrays;
  * trace generation is one vectorized draw per chunk (a jitted JAX kernel:
    alias-method categorical sampling over the edge distribution — the O(1)
    form of inverse-CDF sampling — plus Bernoulli failure/error draws),
    returning ``(edge_id, callee_failed, caller_errored)`` arrays instead
    of dataclass objects;
  * edge statistics are one fused scatter-add histogram per chunk — a
    2-bit outcome code per record, one ``(n_edges, 4)`` histogram giving
    all four per-edge count columns in a single pass, folded into int64
    accumulators so evidence streams through ``ingest_batch`` chunk by
    chunk without ever materializing the full record stream.  On CPU the
    histogram is a host ``np.bincount`` (measured ~7x faster than XLA's
    CPU scatter for the same segment-sum); on accelerator backends (or
    ``REPRO_UFA_KERNELS=1``) it is the device-resident Pallas kernel in
    ``repro.kernels.ufa.ingest`` — same dispatch rule as
    ``kernels.backend.default_interpret``;
  * ``detect()`` is a jitted threshold kernel over the count arrays.

The scalar reference implementation (one ``RPCRecord`` per RPC, a Python
dict per edge) lives in ``tests/scalar_reference.py`` and pins this
engine's statistics; the record-based API here (``RPCRecord``,
``generate_traces``, ``RuntimeFailCloseDetector.ingest``) is a thin compat
layer over the arrays.

Throughput on one CPU core: >20M records/s sampled + ingested, which is
what makes ``runtime_analysis`` at paper scale (~22k services, ~120k
edges, ~48M sampled RPCs at the default ~400 observations/edge) a
seconds-scale operation instead of an hours-scale one.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Dict, Iterable, List, Optional, Set, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.fleet_state import FleetState
from repro.core.service import ServiceSpec
from repro.kernels.backend import use_ufa_kernels as _use_ufa_kernels

# chunk size for the streaming sample->ingest loop: big enough to amortize
# kernel dispatch, small enough to keep transient arrays off the heap
_CHUNK_RECORDS = 4_000_000

# default trace mix (the scalar reference uses the same constants)
AMBIENT_CALLEE_FAILURE = 0.025
AMBIENT_CALLER_ERROR = 0.003
PROPAGATION_PROB = 0.92          # P(caller errors | callee failed, fail-close)
COLD_PATH_FRACTION = 0.18
COLD_TRAFFIC_FACTOR = 0.01       # cold paths carry ~100x less traffic


@dataclasses.dataclass(frozen=True)
class RPCRecord:
    caller: str
    callee: str
    callee_failed: bool
    caller_errored: bool


# ---------------------------------------------------------------------------
# edge universe
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TraceEdges:
    """Integer-ID edge universe the telemetry engine samples and
    aggregates over: edge ``i`` is ``edge_names[i]`` with sampling weight
    ``weight[i]`` (Table-2 cell volume split across the cell's edges, cold
    paths x0.01)."""
    edge_names: List[Tuple[str, str]]
    weight: np.ndarray            # float64 — relative RPC volume
    unsafe: np.ndarray            # bool — planted fail-close (ground truth)
    cold: np.ndarray              # bool — under-observed unsafe paths
    caller_tier: np.ndarray       # int8
    callee_tier: np.ndarray       # int8

    # lazily-built sampling state (alias tables + device arrays)
    _tables: Optional[tuple] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def n(self) -> int:
        return len(self.edge_names)

    def unsafe_keys(self) -> Set[Tuple[str, str]]:
        return {self.edge_names[i] for i in np.flatnonzero(self.unsafe)}

    def cold_keys(self) -> Set[Tuple[str, str]]:
        return {self.edge_names[i] for i in np.flatnonzero(self.cold)}

    def sampling_tables(self):
        """(prob, alias, unsafe) device arrays for the sampling kernel."""
        if self._tables is None:
            p = self.weight / self.weight.sum()
            prob, alias = _alias_table(p)
            self._tables = (jnp.asarray(prob), jnp.asarray(alias),
                            jnp.asarray(self.unsafe))
        return self._tables


def _alias_table(p: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Vose alias tables for O(1) categorical sampling: draw bucket i
    uniformly, accept i with probability prob[i], else take alias[i]."""
    n = len(p)
    scaled = (np.asarray(p, np.float64) * n).tolist()
    prob = np.ones(n, np.float32)
    alias = np.arange(n, dtype=np.int32)
    small = [i for i in range(n) if scaled[i] < 1.0]
    large = [i for i in range(n) if scaled[i] >= 1.0]
    while small and large:
        s, l = small.pop(), large.pop()
        prob[s] = scaled[s]
        alias[s] = l
        scaled[l] -= 1.0 - scaled[s]
        (small if scaled[l] < 1.0 else large).append(l)
    # numerical leftovers keep probability 1 of themselves
    return prob, alias


def trace_edges(fleet: Union[Dict[str, ServiceSpec], FleetState],
                seed: int = 0,
                cold_path_fraction: float = COLD_PATH_FRACTION
                ) -> Optional[TraceEdges]:
    """Builds the edge universe from either fleet representation.  The
    weight rule matches the scalar reference exactly: an edge in Table-2
    cell (caller_tier, callee_tier) carries cell_volume / n_edges_in_cell;
    ``cold_path_fraction`` of unsafe edges carry ~100x less traffic — the
    defects runtime analysis tends to miss and static analysis catches.
    Returns None for an edge-free fleet."""
    from repro.core.service import _TABLE2
    from repro.core.tiers import Tier

    if isinstance(fleet, FleetState):
        assert fleet.edges is not None, "FleetState synthesized without edges"
        e = fleet.edges
        if e.n == 0:
            return None
        names = fleet.names
        edge_names = [(names[s], names[d])
                      for s, d in zip(e.src.tolist(), e.dst.tolist())]
        caller_tier = fleet.tier[e.src]
        callee_tier = fleet.tier[e.dst]
        unsafe = ~np.asarray(e.fail_open, bool)
        if e.weight is not None:
            weight = np.asarray(e.weight, np.float64)
        else:
            weight = None
    else:
        edge_names = []
        caller_tier_l: List[int] = []
        callee_tier_l: List[int] = []
        unsafe_l: List[bool] = []
        for s in fleet.values():
            ct = int(s.tier)
            for d in s.deps:
                edge_names.append((s.name, d))
                caller_tier_l.append(ct)
                callee_tier_l.append(int(fleet[d].tier))
                unsafe_l.append(not s.fail_open.get(d, True))
        if not edge_names:
            return None
        caller_tier = np.asarray(caller_tier_l, np.int8)
        callee_tier = np.asarray(callee_tier_l, np.int8)
        unsafe = np.asarray(unsafe_l, bool)
        weight = None

    if weight is None:
        tiers = list(Tier)
        vol = np.asarray([[_TABLE2[t][c] for c in range(len(tiers))]
                          for t in tiers], np.float64)
        cell = caller_tier.astype(np.int64) * len(tiers) + callee_tier
        counts = np.bincount(cell, minlength=len(tiers) ** 2)
        weight = vol.ravel()[cell] / np.maximum(counts[cell], 1)

    rng = np.random.default_rng(seed)
    cold = unsafe & (rng.random(len(unsafe)) < cold_path_fraction)
    weight = np.where(cold, weight * COLD_TRAFFIC_FACTOR, weight)
    return TraceEdges(edge_names=edge_names, weight=weight, unsafe=unsafe,
                      cold=cold, caller_tier=caller_tier,
                      callee_tier=callee_tier)


# ---------------------------------------------------------------------------
# vectorized trace sampling
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("n",))
def _sample_kernel(key, n: int, prob, alias, unsafe,
                   t_fail, t_prop, p_err):
    """One vectorized draw of ``n`` RPCs: alias-method edge choice + the
    Bernoulli failure/error draws, from 4 u32 lanes per record.  The
    16-bit Bernoulli thresholds quantize the failure/propagation rates to
    1/65536 (<0.03% relative) — far below the sampling noise of any
    realistic stream."""
    r = jax.random.bits(key, (4, n), jnp.uint32)
    n_edges = prob.shape[0]
    u0 = (r[0] >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
    i = jnp.minimum((u0 * n_edges).astype(jnp.int32), n_edges - 1)
    v = (r[1] >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
    eid = jnp.where(v < prob[i], i, alias[i])
    failed = (r[2] & jnp.uint32(0xFFFF)).astype(jnp.int32) < t_fail
    prop = (r[2] >> 16).astype(jnp.int32) < t_prop
    amb = (r[3] >> 8).astype(jnp.float32) * (1.0 / (1 << 24)) < p_err
    errored = (unsafe[eid] & failed & prop) | amb
    return eid, failed, errored


def _trace_key(seed: int):
    # rbg bit generation is ~4x faster than threefry on CPU for wide draws
    return jax.random.key(seed, impl="rbg")


def _iter_trace_chunks(edges: TraceEdges, n_records: int, seed: int,
                       ambient_callee_failure: float,
                       ambient_caller_error: float,
                       propagation_prob: float,
                       chunk_records: int = _CHUNK_RECORDS):
    """Yields device ``(edge_id, callee_failed, caller_errored)`` chunks.
    The single source of the sampling stream: ``sample_traces`` and
    ``runtime_analysis`` both draw from here, so a seed always names the
    same stream regardless of which API consumes it."""
    prob, alias, unsafe = edges.sampling_tables()
    t_fail = int(ambient_callee_failure * 65536)
    t_prop = int(propagation_prob * 65536)
    n_chunks = max(1, -(-n_records // chunk_records))
    keys = jax.random.split(_trace_key(seed), n_chunks)
    done = 0
    for k in range(n_chunks):
        n = min(chunk_records, n_records - done)
        if n <= 0:
            break
        done += n
        with obs.span("ufa.detect.sample", records=n):
            chunk = _sample_kernel(keys[k], n, prob, alias, unsafe,
                                   t_fail, t_prop, ambient_caller_error)
        yield chunk


def sample_traces(edges: TraceEdges, n_records: int, seed: int = 0,
                  ambient_callee_failure: float = AMBIENT_CALLEE_FAILURE,
                  ambient_caller_error: float = AMBIENT_CALLER_ERROR,
                  propagation_prob: float = PROPAGATION_PROB,
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Samples ``n_records`` RPCs over the edge universe in one vectorized
    draw (chunked above ``_CHUNK_RECORDS``); returns the
    ``(edge_id, callee_failed, caller_errored)`` arrays.  A fail-close
    edge propagates the callee's failure to the caller (minus flakiness);
    fail-open edges don't."""
    chunks = [tuple(np.asarray(a) for a in c)
              for c in _iter_trace_chunks(edges, n_records, seed,
                                          ambient_callee_failure,
                                          ambient_caller_error,
                                          propagation_prob)]
    if len(chunks) == 1:
        return chunks[0]
    return tuple(np.concatenate([c[i] for c in chunks]) for i in range(3))


def generate_traces(fleet: Dict[str, ServiceSpec], n_records: int = 200_000,
                    seed: int = 0,
                    ambient_callee_failure: float = AMBIENT_CALLEE_FAILURE,
                    ambient_caller_error: float = AMBIENT_CALLER_ERROR,
                    cold_path_fraction: float = COLD_PATH_FRACTION
                    ) -> Tuple[List[RPCRecord], Set[Tuple[str, str]]]:
    """Record-object compat layer over ``sample_traces`` (the seed API).
    Materializing one ``RPCRecord`` per RPC is exactly what the array
    engine exists to avoid — use ``sample_traces`` + ``ingest_batch`` for
    anything bigger than a spot check."""
    edges = trace_edges(fleet, seed=seed,
                        cold_path_fraction=cold_path_fraction)
    if edges is None:
        return [], set()
    eid, failed, errored = sample_traces(
        edges, n_records, seed=seed,
        ambient_callee_failure=ambient_callee_failure,
        ambient_caller_error=ambient_caller_error)
    names = edges.edge_names
    records = [RPCRecord(*names[e], f, er)
               for e, f, er in zip(eid.tolist(), failed.tolist(),
                                   errored.tolist())]
    return records, edges.cold_keys()


# ---------------------------------------------------------------------------
# streaming detector
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EdgeStats:
    calls: int = 0
    callee_failures: int = 0
    errors_given_failure: int = 0
    errors_given_ok: int = 0


@jax.jit
def _detect_kernel(calls, failures, err_fail, err_ok,
                   min_failures, threshold, lift):
    """Thresholding over the per-edge count arrays: enough failure
    evidence, error probability under failure above the propagation
    threshold, and a lift over the ambient error rate."""
    p_fail = err_fail / jnp.maximum(failures, 1.0)
    ok_calls = jnp.maximum(calls - failures, 1.0)
    p_ok = err_ok / ok_calls
    return ((failures >= min_failures)
            & (p_fail >= threshold)
            & (p_fail >= lift * jnp.maximum(p_ok, 1e-4)))


class RuntimeFailCloseDetector:
    """Streaming correlation of caller errors with callee failures.

    Evidence lives in four per-edge int64 count arrays; ``ingest_batch``
    scatter-adds one ``(edge_id, callee_failed, caller_errored)`` chunk
    into them, so arbitrarily long streams accumulate without ever being
    materialized.  Bind the detector to a ``TraceEdges`` universe for the
    array-native path; the record-based ``ingest`` interns (caller,
    callee) pairs on the fly and routes through the same accumulators.
    """

    def __init__(self, min_failures: int = 5,
                 propagation_threshold: float = 0.5,
                 lift_threshold: float = 5.0,
                 edges: Optional[TraceEdges] = None):
        self.min_failures = min_failures
        self.propagation_threshold = propagation_threshold
        self.lift_threshold = lift_threshold
        self.edges = edges
        if edges is not None:
            self._names: List[Tuple[str, str]] = edges.edge_names
            self._ids: Optional[Dict[Tuple[str, str], int]] = None
            n = edges.n
        else:
            self._names = []
            self._ids = {}
            n = 0
        self.calls = np.zeros(n, np.int64)
        self.callee_failures = np.zeros(n, np.int64)
        self.errors_given_failure = np.zeros(n, np.int64)
        self.errors_given_ok = np.zeros(n, np.int64)

    # ------------------------------------------------------------------
    @property
    def n_edges(self) -> int:
        return len(self.calls)

    @property
    def n_records(self) -> int:
        return int(self.calls.sum())

    def _grow(self, n: int):
        pad = n - len(self.calls)
        if pad > 0:
            for attr in ("calls", "callee_failures", "errors_given_failure",
                         "errors_given_ok"):
                setattr(self, attr,
                        np.concatenate([getattr(self, attr),
                                        np.zeros(pad, np.int64)]))

    def _edge_id(self, caller: str, callee: str) -> int:
        if self._ids is None:
            # bound mode: lazy reverse index over the universe (duplicate
            # (caller, callee) pairs map to their first edge id)
            ids: Dict[Tuple[str, str], int] = {}
            for i, key in enumerate(self._names):
                ids.setdefault(key, i)
            self._ids = ids
        i = self._ids.get((caller, callee))
        if i is None:
            if self.edges is not None:
                raise KeyError(f"unknown edge {(caller, callee)} for a "
                               "detector bound to a TraceEdges universe")
            i = len(self._names)
            self._ids[(caller, callee)] = i
            self._names.append((caller, callee))
        return i

    # ------------------------------------------------------------------
    def ingest_batch(self, edge_id: np.ndarray, callee_failed: np.ndarray,
                     caller_errored: np.ndarray):
        """Scatter-add one chunk of the stream into the per-edge counts
        (the segment-sum reduction of the array engine), fused to a
        single pass: each record gets the 2-bit outcome code
        ``2 * callee_failed + caller_errored`` and one histogram of
        ``edge_id * 4 + code`` yields all four detector columns at once
        (vs the historical four masks + four ``bincount`` sweeps).

        Backend dispatch (``repro.kernels.backend.use_ufa_kernels``): on
        accelerators the chunk stays device-resident through the Pallas
        scatter-add histogram kernel and only the (n_edges, 4) int32
        block crosses to the host; on CPU the fused ``np.bincount`` is
        the measured-faster fallback.  Both fold into the same int64
        accumulators."""
        n = self.n_edges
        # one enabled() branch per multi-million-record chunk — free off
        meter = obs.enabled()
        t0 = time.perf_counter() if meter else 0.0
        with obs.span("ufa.detect.ingest", records=len(edge_id)):
            if n and _use_ufa_kernels():
                backend = "pallas"
                from repro.kernels.ufa.ingest import ingest_hist
                counts = np.asarray(
                    ingest_hist(jnp.asarray(edge_id),
                                jnp.asarray(callee_failed),
                                jnp.asarray(caller_errored), n), np.int64)
            else:
                backend = "numpy"
                eid = np.asarray(edge_id)
                code = ((np.asarray(callee_failed, np.uint8) << 1)
                        | np.asarray(caller_errored, np.uint8))
                key_t = np.int64 if 4 * n >= (1 << 31) else np.int32
                counts = np.bincount(eid.astype(key_t) * 4 + code,
                                     minlength=4 * n).reshape(-1, 4)
            self.calls += counts.sum(axis=1)
            self.callee_failures += counts[:, 2] + counts[:, 3]
            self.errors_given_failure += counts[:, 3]
            self.errors_given_ok += counts[:, 1]
        # int64 headroom guard: far before wraparound could corrupt the
        # evidence (2^62 calls on one edge is ~70k years of the paper's
        # 62T RPCs/week), fail loudly instead
        assert int(self.calls.max(initial=0)) < (1 << 62), \
            "per-edge call count approaching int64 overflow"
        if meter:
            dt = time.perf_counter() - t0
            n_rec = len(np.asarray(edge_id))
            obs.inc("ufa_ingest_records_total", n_rec, backend=backend)
            obs.inc("ufa_ingest_batches_total", backend=backend)
            if dt > 0:
                obs.set_gauge("ufa_ingest_records_per_s", n_rec / dt)

    def ingest(self, records: Iterable[RPCRecord]):
        """Record-object compat: intern edges, then batch-ingest."""
        recs = list(records)
        if not recs:
            return
        eid = np.asarray([self._edge_id(r.caller, r.callee) for r in recs],
                         np.int64)
        self._grow(len(self._names))
        self.ingest_batch(eid,
                          np.asarray([r.callee_failed for r in recs]),
                          np.asarray([r.caller_errored for r in recs]))

    # ------------------------------------------------------------------
    @property
    def stats(self) -> Dict[Tuple[str, str], EdgeStats]:
        """Per-edge stats view (compat; materialized on demand)."""
        out: Dict[Tuple[str, str], EdgeStats] = {}
        for i in np.flatnonzero(self.calls > 0):
            out[self._names[i]] = EdgeStats(
                calls=int(self.calls[i]),
                callee_failures=int(self.callee_failures[i]),
                errors_given_failure=int(self.errors_given_failure[i]),
                errors_given_ok=int(self.errors_given_ok[i]))
        return out

    def detect_mask(self) -> np.ndarray:
        """Jitted threshold kernel over the count arrays -> edge mask."""
        if self.n_edges == 0:
            return np.zeros(0, bool)
        mask = _detect_kernel(
            jnp.asarray(self.calls.astype(np.float32)),
            jnp.asarray(self.callee_failures.astype(np.float32)),
            jnp.asarray(self.errors_given_failure.astype(np.float32)),
            jnp.asarray(self.errors_given_ok.astype(np.float32)),
            self.min_failures, self.propagation_threshold,
            self.lift_threshold)
        mask = np.asarray(mask)
        if obs.enabled():
            obs.inc("ufa_detect_runs_total")
            obs.set_gauge("ufa_detect_edges_flagged",
                          int(np.count_nonzero(mask)))
        return mask

    def detect(self) -> Set[Tuple[str, str]]:
        mask = self.detect_mask()
        found: Set[Tuple[str, str]] = set()
        for i in np.flatnonzero(mask):
            found.add(self._names[i])
        return found


# ---------------------------------------------------------------------------
# end-to-end runtime analysis
# ---------------------------------------------------------------------------


def runtime_analysis(fleet: Union[Dict[str, ServiceSpec], FleetState],
                     n_records: Optional[int] = None,
                     seed: int = 0,
                     chunk_records: int = _CHUNK_RECORDS
                     ) -> Dict[str, object]:
    """n_records defaults to ~400 observations per edge — the paper's
    runtime layer sees trillions of RPCs/day, so evidence per hot edge is
    plentiful while cold paths (~100x less traffic) stay under-observed.

    The stream is sampled and ingested in chunks (sample kernel on device,
    scatter-add reduction on host, overlapped), so paper scale (~48M
    records over ~120k edges) runs in a few seconds without ever holding
    the stream in memory.  Accepts either fleet representation; with a
    ``FleetState`` the detection graph is built straight from the edge
    mask (no per-edge Python objects anywhere).
    """
    with obs.span("ufa.detect.run"):
        return _runtime_analysis(fleet, n_records, seed, chunk_records)


def _runtime_analysis(fleet, n_records, seed, chunk_records):
    from repro.graph import CallGraph

    with obs.span("ufa.detect.tables") as tables:
        edges = trace_edges(fleet, seed=seed)
        if edges is not None:
            det = RuntimeFailCloseDetector(edges=edges)
            edges.sampling_tables()
            tables.set(edges=edges.n)
    is_arrays = isinstance(fleet, FleetState)
    if edges is None:
        # edge-free fleet: same contract, empty evidence and a 0-unsafe
        # detection graph (when a graph can be built at all)
        if is_arrays:
            graph = (CallGraph.from_fleet_state(fleet)
                     if fleet.edges is not None else None)
        else:
            graph = CallGraph.from_detections(fleet, set())
        return {"found": set(), "graph": graph, "truth": set(),
                "cold_paths": set(), "true_positives": 0,
                "false_positives": 0, "missed": 0, "missed_cold": 0,
                "precision": 0.0, "recall": 0.0, "n_records": 0,
                "detector": RuntimeFailCloseDetector()}
    if n_records is None:
        n_records = 400 * max(1, edges.n)

    pending = None            # overlap device sampling with host scatter-add
    for chunk in _iter_trace_chunks(edges, n_records, seed,
                                    AMBIENT_CALLEE_FAILURE,
                                    AMBIENT_CALLER_ERROR, PROPAGATION_PROB,
                                    chunk_records):
        if pending is not None:
            det.ingest_batch(*pending)
        pending = chunk
    if pending is not None:
        det.ingest_batch(*pending)

    with obs.span("ufa.detect.mask"):
        mask = det.detect_mask()
    with obs.span("ufa.detect.verdicts"):
        found = {edges.edge_names[i] for i in np.flatnonzero(mask)}
        truth = edges.unsafe_keys()
        cold = edges.cold_keys()
        tp = found & truth
        # the detections ARE the graph: certification/planning downstream
        # run on what this layer found, not on the planted ground truth
        if is_arrays:
            graph = CallGraph.from_detection_mask(fleet, mask)
        else:
            graph = CallGraph.from_detections(fleet, found)
    return {
        "found": found,
        "graph": graph,
        "truth": truth,
        "cold_paths": cold,
        "true_positives": len(tp),
        "false_positives": len(found - truth),
        "missed": len(truth - found),
        "missed_cold": len((truth - found) & cold),
        "precision": len(tp) / max(1, len(found)),
        "recall": len(tp) / max(1, len(truth)),
        "n_records": n_records,
        "detector": det,
    }
