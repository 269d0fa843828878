"""Segmented timeline verdict-reduction Pallas kernel.

``core/timeline_sim.timeline_verdicts`` folds the per-step series into
its summary carry with a sequential ``lax.scan`` — T dependent steps per
scenario, even though every accumulator is associative: the availability
integral is a dot with the step widths, the floor/peaks are min/max, and
the per-tier restore time is a first crossing.
This kernel reduces a whole scenario block at once:

    avail_int  = sum_t availability * dt        (dt[0] = 0, scan parity)
    avail_min  = min(1, min_t availability)
    util_peak  = max(0, max_t util_model)
    cloud_peak = max(0, max_t cloud_used)
    below      = tier_frac < thresh             (S, R, T)
    first      = min_t { t : below[t] }                  (T if never)
    restore_t  = min_t { ts[t] : t > first & ~below[t] } (inf if never)
    below_seen = first < T

The first crossing needs no cumulative-OR: ``seen[t] & ~below[t]`` of
the scan is ``t > first & ~below[t]``, an iota comparison.  Tiers sit on
sublanes and steps on lanes (an ``(S, T, R)`` block would pad R to 128
lanes), and every output is a keepdims reduction over the step lanes.

Min/max/first-crossing outputs are *exact* vs the scan (selections, not
sums); ``avail_int`` is a reordered float32 sum, so parity is
float32-tight rather than bitwise — which is why the sweep engine
dispatches this path per backend (``reducer="pallas"``) instead of
making it the CPU default (the default scan path stays bit-identical to
the composed sweeps, as pinned by ``tests/test_sweep_engine.py``).

``ref_timeline_reduce`` is the XLA reference (same math, plain ``jnp``).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import default_interpret


def _reduce_kernel(a_ref, u_ref, cl_ref, fr_ref, dt_ref, ts_ref,
                   int_ref, min_ref, upk_ref, cpk_ref, restore_ref,
                   seen_ref, *, thresh: float):
    a = a_ref[...]                                     # (block_s, T)
    int_ref[...] = jnp.sum(a * dt_ref[...], axis=1, keepdims=True)
    min_ref[...] = jnp.minimum(jnp.min(a, axis=1, keepdims=True), 1.0)
    upk_ref[...] = jnp.maximum(
        jnp.max(u_ref[...], axis=1, keepdims=True), 0.0)
    cpk_ref[...] = jnp.maximum(
        jnp.max(cl_ref[...], axis=1, keepdims=True), 0.0)
    below = fr_ref[...] < thresh                       # (block_s, R, T)
    n_t = below.shape[2]
    # first-crossing without a cumulative-OR: ``first`` is the first step
    # below threshold (T if never), and a tier is restored at the least
    # later step that is not below
    step = jax.lax.broadcasted_iota(jnp.int32, below.shape, 2).astype(
        jnp.float32)
    first = jnp.min(jnp.where(below, step, float(n_t)), axis=2,
                    keepdims=True)
    crossed = (step > first) & jnp.logical_not(below)
    restore_ref[...] = jnp.min(
        jnp.where(crossed, ts_ref[...][None], jnp.inf), axis=2,
        keepdims=True)
    seen_ref[...] = (first < n_t).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("thresh", "block_s", "interpret"))
def timeline_reduce(avail: jnp.ndarray, util: jnp.ndarray,
                    cloud: jnp.ndarray, tier_frac: jnp.ndarray,
                    ts: jnp.ndarray, *, thresh: float,
                    block_s: int = 128,
                    interpret: Optional[bool] = None
                    ) -> Dict[str, jnp.ndarray]:
    """avail/util/cloud (S, T) f32, tier_frac (S, T, R) f32, ts (T,) f32
    -> the scan-carry equivalents (all f32 / bool, shapes (S,) / (S, R)).
    """
    interpret = default_interpret() if interpret is None else interpret
    S, T = avail.shape
    R = tier_frac.shape[2]
    dt = jnp.maximum(jnp.diff(ts, prepend=ts[:1]), 0.0)
    dt2 = dt.astype(jnp.float32).reshape(1, T)
    ts2 = ts.astype(jnp.float32).reshape(1, T)

    block_s = min(block_s, S)
    s_pad = -(-S // block_s) * block_s
    pad = ((0, s_pad - S), (0, 0))
    # tiers on sublanes, steps on lanes: an (S, T, R) block would pad R up
    # to 128 lanes
    frac = jnp.pad(jnp.swapaxes(tier_frac, 1, 2), (*pad, (0, 0)),
                   constant_values=1.0)
    row = pl.BlockSpec((block_s, 1), lambda s: (s, 0))
    tier = pl.BlockSpec((block_s, R, 1), lambda s: (s, 0, 0))
    f32 = jax.ShapeDtypeStruct((s_pad, 1), jnp.float32)
    *stats, restore, seen = pl.pallas_call(
        functools.partial(_reduce_kernel, thresh=thresh),
        grid=(s_pad // block_s,),
        in_specs=[
            pl.BlockSpec((block_s, T), lambda s: (s, 0)),
            pl.BlockSpec((block_s, T), lambda s: (s, 0)),
            pl.BlockSpec((block_s, T), lambda s: (s, 0)),
            pl.BlockSpec((block_s, R, T), lambda s: (s, 0, 0)),
            pl.BlockSpec((1, T), lambda s: (0, 0)),
            pl.BlockSpec((1, T), lambda s: (0, 0)),
        ],
        out_specs=[row, row, row, row, tier, tier],
        out_shape=[f32, f32, f32, f32,
                   jax.ShapeDtypeStruct((s_pad, R, 1), jnp.float32),
                   jax.ShapeDtypeStruct((s_pad, R, 1), jnp.int32)],
        interpret=interpret,
        name="timeline_reduce",
    )(jnp.pad(avail, pad), jnp.pad(util, pad), jnp.pad(cloud, pad),
      frac, dt2, ts2)
    out = dict(zip(("avail_int", "avail_min", "util_peak", "cloud_peak"),
                   (v[:S, 0] for v in stats)))
    out["restore_t"] = restore[:S, :, 0]
    out["below_seen"] = seen[:S, :, 0] != 0
    return out


@functools.partial(jax.jit, static_argnames=("thresh",))
def ref_timeline_reduce(avail: jnp.ndarray, util: jnp.ndarray,
                        cloud: jnp.ndarray, tier_frac: jnp.ndarray,
                        ts: jnp.ndarray, *, thresh: float
                        ) -> Dict[str, jnp.ndarray]:
    """XLA reference: identical math, no blocking."""
    dt = jnp.maximum(jnp.diff(ts, prepend=ts[:1]), 0.0).astype(jnp.float32)
    below = tier_frac < thresh
    seen = jax.lax.associative_scan(jnp.logical_or, below, axis=1)
    crossed = seen & jnp.logical_not(below)
    return {
        "avail_int": jnp.sum(avail * dt[None, :], axis=1),
        "avail_min": jnp.minimum(jnp.min(avail, axis=1), 1.0),
        "util_peak": jnp.maximum(jnp.max(util, axis=1), 0.0),
        "cloud_peak": jnp.maximum(jnp.max(cloud, axis=1), 0.0),
        "restore_t": jnp.min(
            jnp.where(crossed, ts.astype(jnp.float32)[None, :, None],
                      jnp.inf), axis=1),
        "below_seen": seen[:, -1, :],
    }
