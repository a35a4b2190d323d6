"""The three mesh helpers every sharded module shares.

Thin spellings of the jax APIs the code targets (top-level
``jax.shard_map``, ``jax.set_mesh``, ``jax.make_mesh`` with explicit
axis types), kept in one place so call sites agree on the defaults.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with the replication check off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def use_mesh(mesh: jax.sharding.Mesh):
    """Context manager making ``mesh`` ambient (``jax.set_mesh``)."""
    return jax.set_mesh(mesh)


def make_mesh(shape: Sequence[int], axis_names: Tuple[str, ...],
              ) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types (jax defaults to Explicit,
    which the shard_map code here does not want)."""
    return jax.make_mesh(
        tuple(shape), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))
