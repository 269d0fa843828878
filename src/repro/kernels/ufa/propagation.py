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


def ell_from_csr(n: int, indptr: np.ndarray, dst: np.ndarray,
                 closed: np.ndarray, pad_to: int = 8
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """CSR -> ELL: ``(ell_dst (n, K) int32, ell_closed (n, K) bool,
    slot (E,) int32)`` with ``K`` the max out-degree rounded up to
    ``pad_to`` (0 for an edge-free graph).  ``slot[e]`` is edge ``e``'s
    column in its caller's ELL row, so a fail-close mask update for edge
    ``e`` lands at ``ell_closed[src[e], slot[e]]`` (the planner's greedy
    loop flips edges in place).  Pad slots carry ``closed=False`` and
    never contribute a hit."""
    indptr = np.asarray(indptr, np.int64)
    dst = np.asarray(dst, np.int64)
    closed = np.asarray(closed, bool)
    deg = np.diff(indptr)
    kmax = int(deg.max(initial=0))
    if kmax == 0:
        return (np.zeros((n, 0), np.int32), np.zeros((n, 0), bool),
                np.zeros(len(dst), np.int32))
    K = -(-kmax // pad_to) * pad_to
    slot = np.arange(len(dst), dtype=np.int64) - np.repeat(indptr[:-1], deg)
    row = np.repeat(np.arange(n, dtype=np.int64), deg)
    ell_dst = np.zeros((n, K), np.int32)
    ell_closed = np.zeros((n, K), bool)
    ell_dst[row, slot] = dst
    ell_closed[row, slot] = closed
    return ell_dst, ell_closed, slot.astype(np.int32)


# ---------------------------------------------------------------------------
# the kernel: one propagation round
# ---------------------------------------------------------------------------


def _round_kernel(dst_ref, closed_ref, b_all_ref, b_cur_ref, o_ref):
    """One round for one (caller-row block, scenario block) tile, in the
    transposed layout (services on sublanes, scenarios on lanes): each
    caller row ORs in the frontier rows of its fail-close callees, read
    one dynamic row at a time with the ELL slot indices in SMEM."""
    block_r = o_ref.shape[0]
    k_slots = dst_ref.shape[0] // block_r

    def caller(i, carry):
        acc = b_cur_ref[pl.ds(i, 1), :]
        for k in range(k_slots):
            slot = i * k_slots + k
            acc = acc | (b_all_ref[pl.ds(dst_ref[slot], 1), :]
                         & closed_ref[slot])
        o_ref[pl.ds(i, 1), :] = acc
        return carry

    jax.lax.fori_loop(0, block_r, caller, 0)


def _vmem_limit(n_pad: int, block_s: int) -> int:
    """Scoped VMEM for one round: the resident frontier slab (single
    buffer) plus the double-buffered row tiles, with headroom."""
    slab = n_pad * max(block_s, 128) * 4
    return int(min(slab + (16 << 20), 100 << 20))


@functools.partial(jax.jit,
                   static_argnames=("block_s", "block_r", "interpret"))
def fixed_point_ell(dark: jnp.ndarray, ell_dst: jnp.ndarray,
                    ell_closed: jnp.ndarray, *, block_s: int = 128,
                    block_r: int = 256,
                    interpret: Optional[bool] = None):
    """Batched least fixed point over the ELL adjacency:
    ``dark (S, n) bool -> (broken (S, n) bool, rounds int32)`` with the
    exact round-counting semantics of the XLA reference (a final
    no-change sweep is counted, bound ``n + 1``)."""
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
    # int32 frontier, transposed: a callee's frontier is one lane-dense row
    dark_t = jnp.pad(dark.T.astype(jnp.int32), ((0, n_pad - n),
                                                (0, s_pad - S)))
    dst_p = jnp.pad(ell_dst.astype(jnp.int32),
                    ((0, n_pad - n), (0, 0))).reshape(-1)
    closed_p = jnp.pad(ell_closed.astype(jnp.int32),
                       ((0, n_pad - n), (0, 0))).reshape(-1)

    slots = pl.BlockSpec((block_r * K,), lambda s, r: (r,),
                         memory_space=pltpu.SMEM)
    one_round = pl.pallas_call(
        _round_kernel,
        grid=(s_pad // block_s, n_pad // block_r),
        in_specs=[
            slots, slots,
            pl.BlockSpec((n_pad, block_s), lambda s, r: (0, s),
                         pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((block_r, block_s), lambda s, r: (r, s)),
        ],
        out_specs=pl.BlockSpec((block_r, block_s), lambda s, r: (r, s)),
        out_shape=jax.ShapeDtypeStruct((n_pad, s_pad), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(n_pad, block_s)),
        interpret=interpret,
        name="propagation_round",
    )

    def cond(state):
        _, changed, i = state
        return changed & (i < n + 1)

    def body(state):
        broken, _, i = state
        new = one_round(dst_p, closed_p, broken, broken)
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
