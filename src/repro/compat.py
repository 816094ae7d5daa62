"""The two JAX entry points every lowering shares.

Meshes are always fully "auto" (GSPMD-managed) and the shard_map
replication checker is always off (the master/worker lowering is
deliberately rank-divergent); these wrappers pin both choices in one
place.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(AxisType.Auto,) * len(names))


def shard_map(f, *, mesh, in_specs, out_specs) -> Any:
    """``jax.shard_map`` without the replication check."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
