"""Small wrappers over the jax APIs the repo uses in several places.

They target the installed jax (0.9): meshes carry explicit axis types,
64-bit mode is switched by the scoped ``jax.enable_x64`` context and
``shard_map`` lives at the top level with its ``check_vma`` switch.
"""
from __future__ import annotations

import contextlib

import jax

AxisType = jax.sharding.AxisType


def auto_axis_types(n: int):
    """``(AxisType.Auto,) * n`` — the repo's only axis-type usage."""
    return (AxisType.Auto,) * n


def x64_context(enable: bool):
    """Thread-local 64-bit mode, as a context manager that can also no-op.

    The streaming sweep widens its flat design-point indices to int64 only
    when the grid actually crosses 2**31 points; everything else in the
    repo stays in the default 32-bit world, so the switch must be scoped
    (``jax.enable_x64(True)``), never the global x64 flag.
    """
    return jax.enable_x64(True) if enable else contextlib.nullcontext()


def shard_map(fn, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-manual-axes check off."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
