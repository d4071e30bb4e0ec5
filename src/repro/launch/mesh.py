"""Production meshes.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state.  The dry-run entrypoint
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` in its
``main()`` before the first device use; everything else (smoke tests,
benches) sees the real device count.

Topology mapping (TPU v5e): the single-pod mesh is one 16x16 pod —
(data=16, model=16); 'model' rides the fastest ICI dimension (TP traffic is
per-layer), 'data' the other (gradient reduce-scatter amortizes over the
step).  The multi-pod mesh adds pod=2 over DCN: the only cross-pod
collective is the once-per-step gradient all-reduce (optionally int8-
compressed, distributed/compression.py).
"""
from __future__ import annotations

import math
from typing import Optional

import jax

from ..compat import auto_axis_types


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()[:need]
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices but only "
            f"{len(jax.devices())} visible — run under dryrun.py, which "
            f"sets XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=auto_axis_types(len(axes)))


def make_batch_mesh(num_devices: Optional[int] = None):
    """1-D ``("batch",)`` mesh for sharding design-space sweeps.

    The sweep batch axis is embarrassingly parallel, so the mesh is a flat
    strip over every visible device (or the first ``num_devices`` of
    them — the sweep scaling bench uses subsets).  On CPU hosts, validate
    multi-device behavior by setting
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before the
    first jax import (tests/test_shard_sweep.py style).
    """
    devices = jax.devices()
    n = len(devices) if num_devices is None else int(num_devices)
    if n < 1 or n > len(devices):
        raise RuntimeError(
            f"batch mesh wants {n} devices but {len(devices)} are visible "
            f"— force more with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=<n>")
    return jax.make_mesh((n,), ("batch",), devices=devices[:n],
                         axis_types=auto_axis_types(1))


def make_host_mesh(data: Optional[int] = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    if data is None:
        data = max(n // model, 1)
    need = data * model
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=jax.devices()[:need],
                         axis_types=auto_axis_types(2))
