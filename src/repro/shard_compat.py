"""``shard_map`` with the repo's default: one import site for every
sharded path (the round engine, the MoE expert-parallel path and the
sharded kernels).

``check`` defaults to False: the sharded kernels invoke ``pallas_call``
inside the mapped body, and pallas has no replication rule — the check
would reject an otherwise-correct program.
"""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the replication check off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
