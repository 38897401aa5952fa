"""The one `shard_map` spelling shared by every manual-SPMD module."""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, **kwargs):
    """`jax.shard_map` with replication checking off unless asked for."""
    kwargs.setdefault("check_vma", False)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
