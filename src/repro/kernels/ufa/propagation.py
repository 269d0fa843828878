"""Blocked ELL frontier-propagation Pallas kernel.

The XLA fixed point in ``graph/propagation.py`` runs one scatter-max over
the whole edge list per round — a data-dependent scatter XLA serializes on
CPU and lowers poorly on TPU.  This kernel flips the data layout: the CSR
adjacency is padded host-side to ELL form (every caller row gets exactly
``K`` callee slots, ``K`` = max out-degree rounded up; the paper-scale
graph measures max degree 13, so K=16 wastes little), and one round
becomes a blocked *gather*:

    hit[s, u] = any_k  broken[s, ell_dst[u, k]] & ell_closed[u, k]
    new[s, u] = broken[s, u] | hit[s, u]

The frontier is held transposed, as int32 ``(n_pad, s_pad)``: services
on sublanes, scenarios on lanes, so one callee's frontier over a
scenario block is one lane-dense row.  The grid tiles (scenario block,
caller-row block); the ``(n_pad, block_s)`` frontier slab of a scenario
block stays resident (one buffer) while the caller rows walk past it, and
each caller ORs in the rows of its fail-close callees, one dynamic row
load per ELL slot, with the slot indices and fail-close flags in SMEM —
no scatter and no lane gather (Mosaic lowers neither), and the whole
blackhole ensemble batch shares each adjacency block read.  A
``lax.while_loop`` with the same round counter/bound as the XLA path
drives the kernel to the fixed point, so ``rounds`` and the ``broken``
matrix are bit-identical to the reference (booleans: exact).

A graph whose out-degrees are not all alike (a gateway that calls
hundreds of services beside thousands that call a handful) would make
``K`` the hub's degree for every row.  ``ell_width`` then takes the base
width that walks the fewest slots, and ``ell_split_from_csr`` spills each
wider row's further callees into *aux* rows of ``K`` slots after the
``n`` caller rows.  The round kernel walks the aux rows first, each
OR-ing its hits onto those of its owner's previous aux row, so the last
aux row of an owner carries the OR over all its spilled slots; the
owner row then ORs that row in, in the same round.  Propagation still
advances one hop a round, so ``rounds`` is unchanged.

``ref_fixed_point`` is the XLA reference (the scatter-max formulation,
kept here so kernel tests do not depend on the graph layer); dispatch
between the two lives in ``graph.propagation.fixed_point``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import default_interpret


# ---------------------------------------------------------------------------
# host-side ELL precompute
# ---------------------------------------------------------------------------


def ell_width(deg: np.ndarray, pad_to: int = 8, keep: int = 32) -> int:
    """Base slot width for caller out-degrees ``deg``: the largest degree
    rounded up to ``pad_to`` while that is at most ``keep`` (no row is
    split), else the multiple of ``pad_to`` whose split layout walks the
    fewest slots a round (``ell_slots``)."""
    deg = np.asarray(deg, np.int64)
    kmax = -(-int(deg.max(initial=0)) // pad_to) * pad_to
    if kmax <= keep:
        return kmax
    widths = range(pad_to, kmax + 1, pad_to)
    return min(widths, key=lambda k: (ell_slots(deg, k), k))


def _aux_rows(deg: np.ndarray, width: int) -> np.ndarray:
    """Aux rows each caller spills into: the slots past its first
    ``width``, ``width`` to a row."""
    return -(-np.maximum(deg - width, 0) // width)


def ell_slots(deg: np.ndarray, width: int) -> int:
    """Slot loads of one round over the layout of ``ell_split_from_csr``:
    ``width`` per caller and aux row, and one per split row for the load
    of its last aux row."""
    aux = _aux_rows(np.asarray(deg, np.int64), width)
    return int((len(deg) + aux.sum()) * width + np.count_nonzero(aux))


def ell_split_from_csr(n: int, indptr: np.ndarray, dst: np.ndarray,
                       closed: np.ndarray, width: int):
    """CSR -> ELL of base width ``width``: ``(ell_dst (n + a, K) int32,
    ell_closed (n + a, K) bool, row (E,) int32, slot (E,) int32, tag
    (n + a,) int32)``.  Row ``u < n`` holds caller ``u``'s first ``K``
    callees; a caller with more spills the rest, ``K`` to a row, into
    consecutive aux rows ``n + j``.  ``tag`` is, for a caller row, the aux
    index ``j`` of its last aux row (-1 where it has none) and, for aux row
    ``j``, 1 where it continues its owner's previous aux row (0 for each
    owner's first).  Edge ``e`` lands at ``(row[e], slot[e])``, so a
    fail-close mask update for it is a scatter at that address.  With
    every degree at most ``K`` there are no aux rows and ``row`` is the
    caller."""
    indptr = np.asarray(indptr, np.int64)
    dst = np.asarray(dst, np.int64)
    closed = np.asarray(closed, bool)
    deg = np.diff(indptr)
    K = int(width)
    if K == 0:                                  # an edge-free graph
        return (np.zeros((n, 0), np.int32), np.zeros((n, 0), bool),
                np.zeros(0, np.int32), np.zeros(0, np.int32),
                np.full(n, -1, np.int32))
    aux = _aux_rows(deg, K)
    a = int(aux.sum())
    first_aux = np.cumsum(aux) - aux            # owner's first aux index
    owner = np.repeat(np.arange(n, dtype=np.int64), deg)
    pos = np.arange(len(dst), dtype=np.int64) - np.repeat(indptr[:-1], deg)
    spilled = pos >= K
    spill_pos = np.where(spilled, pos - K, 0)
    row = np.where(spilled, n + first_aux[owner] + spill_pos // K, owner)
    slot = np.where(spilled, spill_pos % K, pos)
    ell_dst = np.zeros((n + a, K), np.int32)
    ell_closed = np.zeros((n + a, K), bool)
    ell_dst[row, slot] = dst
    ell_closed[row, slot] = closed
    tag = np.ones(n + a, np.int32)
    tag[:n] = np.where(aux > 0, first_aux + aux - 1, -1)
    tag[n + first_aux[aux > 0]] = 0
    return (ell_dst, ell_closed, row.astype(np.int32),
            slot.astype(np.int32), tag)


def ell_from_csr(n: int, indptr: np.ndarray, dst: np.ndarray,
                 closed: np.ndarray, pad_to: int = 8
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """CSR -> ELL: ``(ell_dst (n, K) int32, ell_closed (n, K) bool,
    slot (E,) int32)`` with ``K`` the max out-degree rounded up to
    ``pad_to`` (0 for an edge-free graph), so no row is split.  ``slot[e]``
    is edge ``e``'s column in its caller's ELL row, so a fail-close mask
    update for edge ``e`` lands at ``ell_closed[src[e], slot[e]]`` (the
    planner's greedy loop flips edges in place).  Pad slots carry
    ``closed=False`` and never contribute a hit."""
    deg = np.diff(np.asarray(indptr, np.int64))
    K = -(-int(deg.max(initial=0)) // pad_to) * pad_to
    ell_dst, ell_closed, _, slot, _ = ell_split_from_csr(n, indptr, dst,
                                                         closed, K)
    return ell_dst, ell_closed, slot


# ---------------------------------------------------------------------------
# the kernel: one propagation round
# ---------------------------------------------------------------------------


def _or_callees(acc, i, k_slots, dst_ref, closed_ref, b_all_ref):
    """OR into ``acc`` the frontier rows of row ``i``'s fail-close slots,
    one dynamic row load per slot, the slot indices and flags in SMEM."""
    for k in range(k_slots):
        slot = i * k_slots + k
        acc = acc | (b_all_ref[pl.ds(dst_ref[slot], 1), :]
                     & closed_ref[slot])
    return acc


def _round_kernel(dst_ref, closed_ref, b_all_ref, b_cur_ref, o_ref):
    """One round for one (caller-row block, scenario block) tile, in the
    transposed layout (services on sublanes, scenarios on lanes): each
    caller row ORs in the frontier rows of its fail-close callees."""
    block_r = o_ref.shape[0]
    k_slots = dst_ref.shape[0] // block_r

    def caller(i, carry):
        acc = _or_callees(b_cur_ref[pl.ds(i, 1), :], i, k_slots, dst_ref,
                          closed_ref, b_all_ref)
        o_ref[pl.ds(i, 1), :] = acc
        return carry

    jax.lax.fori_loop(0, block_r, caller, 0)


def _split_round_kernel(dst_ref, closed_ref, tag_ref, b_all_ref, b_cur_ref,
                        o_ref, aux_ref, *, aux_blocks):
    """One round over a split layout, rows in grid order: the aux row
    blocks first, then the caller row blocks (``_round_kernel`` over them).
    Aux row ``j`` writes to the VMEM scratch ``aux_ref`` its hits OR-ed
    onto row ``j - 1``'s where its tag says it continues that row's owner;
    a caller whose tag names its last aux row ORs that row in.  A row's
    tag sits in the first of its ``K`` entries of ``tag_ref``, which is
    laid out as the slot arrays are (SMEM blocks keep their tiling)."""
    block_r = o_ref.shape[0]
    k_slots = dst_ref.shape[0] // block_r
    r = pl.program_id(1)

    @pl.when(r < aux_blocks)
    def _aux():
        def aux_row(i, carry):
            j = r * block_r + i
            acc = (aux_ref[pl.ds(jnp.maximum(j - 1, 0), 1), :]
                   * tag_ref[i * k_slots])
            aux_ref[pl.ds(j, 1), :] = _or_callees(
                acc, i, k_slots, dst_ref, closed_ref, b_all_ref)
            return carry

        jax.lax.fori_loop(0, block_r, aux_row, 0)

    @pl.when(r >= aux_blocks)
    def _callers():
        def caller(i, carry):
            acc = _or_callees(b_cur_ref[pl.ds(i, 1), :], i, k_slots,
                              dst_ref, closed_ref, b_all_ref)
            o_ref[pl.ds(i, 1), :] = acc
            link = tag_ref[i * k_slots]

            @pl.when(link >= 0)
            def _link():
                o_ref[pl.ds(i, 1), :] = acc | aux_ref[pl.ds(link, 1), :]

            return carry

        jax.lax.fori_loop(0, block_r, caller, 0)


def _vmem_limit(n_pad: int, block_s: int) -> int:
    """Scoped VMEM for one round: the resident frontier slab (single
    buffer; with the aux scratch of a split layout) plus the
    double-buffered row tiles, with headroom."""
    slab = n_pad * max(block_s, 128) * 4
    return int(min(slab + (16 << 20), 100 << 20))


@functools.partial(jax.jit,
                   static_argnames=("block_s", "block_r", "interpret"))
def fixed_point_ell(dark: jnp.ndarray, ell_dst: jnp.ndarray,
                    ell_closed: jnp.ndarray,
                    ell_tag: Optional[jnp.ndarray] = None, *,
                    block_s: int = 128, block_r: int = 256,
                    interpret: Optional[bool] = None):
    """Batched least fixed point over the ELL adjacency:
    ``dark (S, n) bool -> (broken (S, n) bool, rounds int32)`` with the
    exact round-counting semantics of the XLA reference (a final
    no-change sweep is counted, bound ``n + 1``).  ``ell_tag`` (from
    ``ell_split_from_csr``) marks a split layout, whose aux rows follow
    the ``n`` caller rows of ``ell_dst``/``ell_closed``."""
    interpret = default_interpret() if interpret is None else interpret
    S, n = dark.shape
    K = ell_dst.shape[1]
    if S == 0 or n == 0 or K == 0:
        # nothing can propagate: the reference still runs one (no-change)
        # round before the loop exits
        return dark, jnp.int32(1)

    block_s = min(block_s, S)
    block_r = min(block_r, -(-n // 8) * 8)
    s_pad = -(-S // block_s) * block_s
    n_pad = -(-n // block_r) * block_r
    a = ell_dst.shape[0] - n
    a_pad = -(-a // block_r) * block_r
    # int32 frontier, transposed: a callee's frontier is one lane-dense row
    dark_t = jnp.pad(dark.T.astype(jnp.int32), ((0, n_pad - n),
                                                (0, s_pad - S)))

    slots = pl.BlockSpec((block_r * K,), lambda s, r: (r,),
                         memory_space=pltpu.SMEM)
    slab = pl.BlockSpec((n_pad, block_s), lambda s, r: (0, s),
                        pipeline_mode=pl.Buffered(1))
    if ell_tag is None:
        dst_p = jnp.pad(ell_dst.astype(jnp.int32),
                        ((0, n_pad - n), (0, 0))).reshape(-1)
        closed_p = jnp.pad(ell_closed.astype(jnp.int32),
                           ((0, n_pad - n), (0, 0))).reshape(-1)
        tile = pl.BlockSpec((block_r, block_s), lambda s, r: (r, s))
        one_round = pl.pallas_call(
            _round_kernel,
            grid=(s_pad // block_s, n_pad // block_r),
            in_specs=[slots, slots, slab, tile],
            out_specs=tile,
            out_shape=jax.ShapeDtypeStruct((n_pad, s_pad), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_vmem_limit(n_pad, block_s)),
            interpret=interpret,
            name="propagation_round",
        )
        operands = (dst_p, closed_p)
    else:
        def grid_rows(x, caller_fill=0):
            """Rows in grid order: the aux rows, then the caller rows,
            each padded to whole blocks, flattened."""
            pad = [(0, 0)] * (x.ndim - 1)
            return jnp.concatenate([
                jnp.pad(x[n:], [(0, a_pad - a)] + pad),
                jnp.pad(x[:n], [(0, n_pad - n)] + pad,
                        constant_values=caller_fill)]).reshape(-1)

        # the aux steps touch no caller tile: they keep the first one,
        # which the first caller step then writes whole
        aux_blocks = a_pad // block_r
        tile = pl.BlockSpec((block_r, block_s),
                            lambda s, r: (jnp.maximum(r - aux_blocks, 0), s))
        one_round = pl.pallas_call(
            functools.partial(_split_round_kernel, aux_blocks=aux_blocks),
            grid=(s_pad // block_s, aux_blocks + n_pad // block_r),
            in_specs=[slots, slots, slots, slab, tile],
            out_specs=tile,
            out_shape=jax.ShapeDtypeStruct((n_pad, s_pad), jnp.int32),
            scratch_shapes=[pltpu.VMEM((max(a_pad, block_r), block_s),
                                       jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                # aux rows before the callers that read them, in order
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(n_pad + a_pad, block_s)),
            interpret=interpret,
            name="propagation_round",
        )
        operands = (grid_rows(ell_dst.astype(jnp.int32)),
                    grid_rows(ell_closed.astype(jnp.int32)),
                    grid_rows(jnp.pad(ell_tag.astype(jnp.int32)[:, None],
                                      ((0, 0), (0, K - 1))),
                              caller_fill=-1))

    def cond(state):
        _, changed, i = state
        return changed & (i < n + 1)

    def body(state):
        broken, _, i = state
        new = one_round(*operands, broken, broken)
        return new, (new != broken).any(), i + 1

    broken, _, rounds = jax.lax.while_loop(
        cond, body, (dark_t, jnp.bool_(True), jnp.int32(0)))
    return broken[:n, :S].T != 0, rounds


# ---------------------------------------------------------------------------
# XLA reference (the scatter-max formulation)
# ---------------------------------------------------------------------------


@jax.jit
def ref_fixed_point(dark: jnp.ndarray, src: jnp.ndarray, dst: jnp.ndarray,
                    closed: jnp.ndarray):
    """Edge-list scatter-max fixed point — op-for-op the original
    ``graph.propagation._fixed_point`` (which remains the production CPU
    path; this copy pins the kernel without a layer dependency)."""
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
