"""shard_map wrapper.

Collective-heavy code (MoE expert parallel, split-KV decode, pipeline)
goes through here.  The replication check is off: our bodies mix
psum/pmax merges whose replication typing the checker rejects.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
