"""Histogram ingest Pallas kernel (telemetry hot path).

``core/dependency.py`` folds ``(edge_id, callee_failed, caller_errored)``
chunks into four per-edge count arrays.  On CPU that is a host
``np.bincount`` (measured 7x faster than XLA's CPU scatter in PR 3) — but
it forces a device->host round trip per 4M-record chunk and can never
ride an accelerator.  This kernel keeps the whole reduction
device-resident: records are encoded with the 2-bit outcome code

    code = 2 * callee_failed + caller_errored

and one pass accumulates the ``(n_edges, 4)`` histogram — column 0 =
clean call, 1 = error without failure, 2 = failure absorbed, 3 = failure
propagated — from which all four detector columns derive (``calls`` =
row sum, ``callee_failures`` = col2+col3, ``errors_given_failure`` =
col3, ``errors_given_ok`` = col1).

Mosaic has no vector scatter, so the kernel counts one record at a
time: the flat bin ``4 * edge_id + code`` names a row of ``LANES`` bins
and a lane, and each record is a dynamic row load, a one-hot lane add
and a row store.  Record keys stream through SMEM in ``block_n`` blocks
(the inner grid axis); the histogram is lane-dense (~2 MB for the
paper-scale ~120k edges, not the 61 MB of an ``(n_edges, 4)`` block
padded to 128 lanes) and tiled over edges (the outer grid axis,
``block_e`` edges per tile), with each tile resident across the record
blocks (``pl.when`` zero-init on the first).  Counts are int32 per chunk
(a 4M-record chunk cannot overflow); the caller folds chunks into its
int64 accumulators host-side.  Keys outside a tile add zero, and padding
records carry key ``-1``, outside every tile.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import default_interpret

N_CODES = 4                      # 2-bit outcome code
LANES = 128                      # histogram bins per vector row
UNROLL = 8                       # records per loop iteration


def _hist_kernel(key_ref, o_ref):
    """Count one SMEM block of record keys into one edge tile of the
    lane-dense histogram (``LANES`` bins per row), one record at a time:
    a dynamic row load, a one-hot lane add, a row store.  Keys outside
    the tile (other tiles' records, and the ``-1`` padding) add zero."""
    t, r = pl.program_id(0), pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    tile_bins = o_ref.shape[0] * LANES
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def records(i, carry):
        for j in range(UNROLL):
            local = key_ref[i * UNROLL + j] - t * tile_bins
            hit = (local >= 0) & (local < tile_bins)
            local = jnp.where(hit, local, 0)
            o_ref[pl.ds(local // LANES, 1), :] += (
                (lane == local % LANES) & hit).astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, key_ref.shape[0] // UNROLL, records, 0)


@functools.partial(jax.jit,
                   static_argnames=("n_edges", "block_n", "block_e",
                                    "interpret"))
def ingest_hist(edge_id: jnp.ndarray, callee_failed: jnp.ndarray,
                caller_errored: jnp.ndarray, n_edges: int, *,
                block_n: int = 16_384, block_e: int = 131_072,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """One chunk -> ``(n_edges, 4)`` int32 outcome-code histogram.
    ``block_n`` records per grid step; ``block_e`` edges per histogram
    tile (the paper-scale ~120k edges fit one tile)."""
    interpret = default_interpret() if interpret is None else interpret
    n = edge_id.shape[0]
    if n == 0 or n_edges == 0:
        return jnp.zeros((n_edges, N_CODES), jnp.int32)
    key = (edge_id.astype(jnp.int32) * N_CODES
           + callee_failed.astype(jnp.int32) * 2
           + caller_errored.astype(jnp.int32))

    block_n = -(-min(block_n, n) // UNROLL) * UNROLL
    n_pad = -(-n // block_n) * block_n
    # histogram rows of LANES bins, tiles of whole 8-row groups
    rows = -(-n_edges * N_CODES // LANES)
    tile_rows = min(-(-block_e * N_CODES // (8 * LANES)) * 8,
                    -(-rows // 8) * 8)
    n_tiles = -(-rows // tile_rows)
    # pad records carry key -1: outside every tile, never counted
    key = jnp.pad(key, (0, n_pad - n), constant_values=-1)

    counts = pl.pallas_call(
        _hist_kernel,
        grid=(n_tiles, n_pad // block_n),
        in_specs=[pl.BlockSpec((block_n,), lambda t, r: (r,),
                               memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((tile_rows, LANES), lambda t, r: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile_rows, LANES),
                                       jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ingest_hist",
    )(key)
    return counts.reshape(-1)[:n_edges * N_CODES].reshape(n_edges, N_CODES)


@functools.partial(jax.jit, static_argnames=("n_edges",))
def ref_ingest_hist(edge_id: jnp.ndarray, callee_failed: jnp.ndarray,
                    caller_errored: jnp.ndarray, n_edges: int) -> jnp.ndarray:
    """XLA reference: the same fused single-pass histogram as one flat
    scatter-add (and the same math as the host ``np.bincount`` fallback
    in ``core.dependency.ingest_batch``)."""
    eid = edge_id.astype(jnp.int32)
    code = (callee_failed.astype(jnp.int32) * 2
            + caller_errored.astype(jnp.int32))
    flat = jnp.zeros((n_edges * N_CODES,), jnp.int32).at[
        eid * N_CODES + code].add(1, mode="drop")
    return flat.reshape(n_edges, N_CODES)
