"""Logical-axis sharding context.

Model code annotates activations with *logical* axis names
(``hint(x, "batch", None, "embed")``); a ``sharding_rules`` context binds
those names to mesh axes.  Outside a context — or when a dimension does
not divide the mapped mesh-axis product — the annotation is a no-op, so
the same model code runs unchanged on one device.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

Rule = Union[None, str, Tuple[str, ...]]

_TLS = threading.local()


def default_rules(mesh) -> Dict[str, Rule]:
    """Logical-axis -> mesh-axis table.  Batch-like axes map onto every
    non-model mesh axis (so multi-pod meshes data-parallelize over
    pod x data); everything width-like maps onto "model"."""
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    model = "model" if "model" in mesh.axis_names else None
    return {
        "batch": data_axes or None,
        "seq": model,
        "kv_seq": model,
        "heads": model,
        "ff": model,
        "vocab": model,
        "expert": model,
        "embed": None,
    }


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``Auto``.  The annotations and sharding
    tables of this package are placement hints that the compiler
    propagates; ``jax.make_mesh`` builds ``Explicit`` axes, under which
    every op that mixes differently sharded operands must be told its
    output sharding."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


class _Ctx:
    __slots__ = ("mesh", "rules")

    def __init__(self, mesh, rules):
        self.mesh = mesh
        self.rules = rules


@contextmanager
def sharding_rules(mesh, rules: Optional[Dict[str, Rule]] = None):
    """Activate a logical-axis sharding context (tracing-time state)."""
    merged = default_rules(mesh)
    if rules:
        merged.update(rules)
    prev = getattr(_TLS, "ctx", None)
    _TLS.ctx = _Ctx(auto_axes(mesh), merged)
    try:
        yield _TLS.ctx
    finally:
        _TLS.ctx = prev


def _current() -> Optional[_Ctx]:
    return getattr(_TLS, "ctx", None)


def current_mesh():
    ctx = _current()
    return ctx.mesh if ctx is not None else None


def get_rule(name: str) -> Rule:
    ctx = _current()
    if ctx is None:
        return None
    return ctx.rules.get(name)


def _axes_of(rule: Rule) -> Tuple[str, ...]:
    if rule is None:
        return ()
    return (rule,) if isinstance(rule, str) else tuple(rule)


def axis_size(name: str) -> int:
    """Product of mesh-axis sizes the logical axis maps to (1 outside a
    context)."""
    ctx = _current()
    if ctx is None:
        return 1
    n = 1
    for a in _axes_of(ctx.rules.get(name)):
        n *= ctx.mesh.shape[a]
    return n


def hint(x, *logical_axes):
    """Annotate ``x`` with a sharding constraint derived from logical axis
    names (one per dimension, ``None`` = replicated).  Identity when no
    context is active, inside ``shard_map``, on 1-sized mappings, and on
    non-divisible dims."""
    ctx = _current()
    if ctx is None:
        return x
    mesh = ctx.mesh
    if jax.sharding.get_abstract_mesh().manual_axes:
        # inside a shard_map body the caller already owns the layout
        return x
    spec = []
    pinned = False
    for dim, name in zip(x.shape, logical_axes):
        axes = _axes_of(ctx.rules.get(name)) if name else ()
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        if n <= 1 or dim % n != 0:
            spec.append(None)
        else:
            spec.append(axes[0] if len(axes) == 1 else axes)
            pinned = True
    if not pinned:
        return x
    spec += [None] * (x.ndim - len(spec))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))
