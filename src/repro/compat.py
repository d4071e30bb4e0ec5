"""Small wrappers over the jax APIs the repo uses in several places.

They target the installed jax (0.9): meshes carry explicit axis types
and ``shard_map`` lives at the top level with its ``check_vma`` switch.
"""
from __future__ import annotations

import jax

AxisType = jax.sharding.AxisType


def auto_axis_types(n: int):
    """``(AxisType.Auto,) * n`` — the repo's only axis-type usage."""
    return (AxisType.Auto,) * n


def shard_map(fn, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
