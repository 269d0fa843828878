"""Sharding tables for params, batches and decode state.

All entry points are *heuristic but safe*: a dimension is only pinned to a
mesh axis when it divides the axis-size product, otherwise it stays
replicated, so every table is valid on any mesh (jit/device_put reshard as
needed — these are placement hints, not correctness requirements).
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist.ctx import auto_axes


def batch_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes a global batch dimension spreads over."""
    return tuple(a for a in mesh.axis_names if a != "model")


def replicated(mesh) -> NamedSharding:
    return NamedSharding(auto_axes(mesh), P())


def cache_seq_len(seq_len: int, pad: int = 256) -> int:
    """Decode-cache length for a prompt of ``seq_len``: room for generated
    tokens, padded to a multiple of 256 so the sequence axis stays
    divisible by any production model-axis size."""
    return ((seq_len + pad + 255) // 256) * 256


def _batch_spec(mesh, dim: int):
    axes = batch_axes(mesh)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    if n > 1 and dim % n == 0:
        return axes if len(axes) > 1 else axes[0]
    return None


def _model_spec(mesh, dim: int):
    if "model" in mesh.axis_names and mesh.shape["model"] > 1 \
            and dim % mesh.shape["model"] == 0:
        return "model"
    return None


def _leaf_spec(path, leaf, mesh, fsdp: bool):
    """Tensor-parallel spec for one parameter leaf.

    2D+ weights shard their widest "width" dim over "model"; with fsdp the
    opposite end additionally shards over the data axes.  Stacked-layer
    leading dims, norm scales and biases stay replicated."""
    keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
    shape = leaf.shape
    stacked = "layers" in keys
    first = 1 if stacked else 0           # skip the (n_layers, ...) dim
    ndim = len(shape)
    if ndim - first < 2:                  # scales, biases, mix weights
        return P()
    spec = [None] * ndim
    name = keys[-1] if keys else ""
    if name in ("w_gate", "w_up", "w_down") and ndim - first == 3:
        # routed experts (E, d, f): expert-parallel over "model",
        # FSDP over the widest remaining dim
        spec[first] = _model_spec(mesh, shape[first])
        if fsdp:
            tail = first + 2 if name != "w_down" else first + 1
            spec[tail] = _batch_spec(mesh, shape[tail])
        return P(*spec)
    # generic 2D matmul weight: "model" on the last dim when divisible,
    # else the first non-stacked dim; fsdp on the other end
    if _model_spec(mesh, shape[-1]) is not None:
        spec[-1] = "model"
        if fsdp:
            spec[first] = _batch_spec(mesh, shape[first])
    elif _model_spec(mesh, shape[first]) is not None:
        spec[first] = "model"
        if fsdp:
            spec[-1] = _batch_spec(mesh, shape[-1])
    return P(*spec)


def param_shardings(cfg, mesh, fsdp: bool = True):
    """NamedSharding pytree matching ``init_params(cfg, key)``."""
    mesh = auto_axes(mesh)
    from repro.models import init_params
    abstract = jax.eval_shape(lambda k: init_params(cfg, k),
                              jax.random.PRNGKey(0))
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, _leaf_spec(path, leaf, mesh,
                                                          fsdp)),
        abstract)


def train_batch_shardings(cfg, mesh):
    """Shardings for {"inputs", "labels"} train batches (batch-dim DP)."""
    mesh = auto_axes(mesh)
    def shard(ndim_tail):
        return NamedSharding(mesh, P(batch_axes(mesh) or None,
                                     *([None] * ndim_tail)))
    inputs = shard(2 if not cfg.embed_inputs else 1)
    return {"inputs": inputs, "labels": shard(1)}


def prefill_shardings(cfg, mesh):
    return {"inputs": train_batch_shardings(cfg, mesh)["inputs"]}


def decode_token_shardings(cfg, mesh, batch: int):
    mesh = auto_axes(mesh)
    spec = _batch_spec(mesh, batch)
    if cfg.embed_inputs:
        return NamedSharding(mesh, P(spec))
    return NamedSharding(mesh, P(spec, None))


def decode_state_shardings(cfg, mesh, batch: int):
    """DecodeState shardings: KV caches shard their sequence dim over
    "model" (split-KV decode), batch dims over the data axes."""
    from repro.models.model import DecodeState, init_decode_state
    mesh = auto_axes(mesh)
    abstract = jax.eval_shape(
        lambda: init_decode_state(cfg, batch, 8, jnp.bfloat16))

    b = _batch_spec(mesh, batch)

    def cache_spec(leaf, seq_dim: int):
        spec = [None] * len(leaf.shape)
        if len(spec) >= 2:
            spec[1] = b
        return spec

    def shard(name, leaf):
        spec = cache_spec(leaf, 2)
        if name in ("k_cache", "v_cache", "k_scale", "v_scale") \
                and len(leaf.shape) > 2:
            # actual runtime seq length is the caller's max_seq, not the
            # abstract one — pin only the axis name; divisibility is
            # enforced by the split-KV fast-path gate at trace time
            if "model" in mesh.axis_names and mesh.shape["model"] > 1 \
                    and leaf.shape[2] > 0:
                spec[2] = "model"
        return NamedSharding(mesh, P(*spec))

    return DecodeState(
        k_cache=shard("k_cache", abstract.k_cache),
        v_cache=shard("v_cache", abstract.v_cache),
        k_scale=shard("k_scale", abstract.k_scale),
        v_scale=shard("v_scale", abstract.v_scale),
        conv_state=NamedSharding(mesh, P(None, b)),
        ssm_state=NamedSharding(mesh, P(None, b)),
        length=replicated(mesh),
    )
