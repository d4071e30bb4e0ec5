"""Pallas TPU kernel: per-category energy accumulation for design sweeps.

The batched energy engine produces a dense ``[B, U]`` matrix of per-unit
energies (B design points x U hardware units).  The paper's reports (Eq. 1,
Fig. 9) need the per-category totals SEN / COMP-A / MEM-A / ADC / COMP-D /
MEM-D / MIPI / UTSV — a segment-sum over units, expressed here as a tiny
matmul against a ``[U, C]`` category one-hot so the reduction rides the MXU.
Same row-strip blocking idiom as ``stencil_conv``: the unit axis is small
(U, C << 128) and stays un-blocked; only the design-point axis is tiled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .runtime import resolve_interpret


def _reduce_kernel(e_ref, w_ref, o_ref):
    e = e_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    # HIGHEST: one bf16 pass (the TPU default) would round the energies
    o_ref[...] = jnp.dot(e, w, precision=jax.lax.Precision.HIGHEST
                         ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_points", "interpret"))
def category_reduce(unit_energy: jax.Array, weights: jax.Array,
                    block_points: int = 2048,
                    interpret: bool = None) -> jax.Array:
    """``[B, U] @ [U, C] -> [B, C]`` segment-sum over hardware units.

    ``weights`` is typically a category one-hot, but any unit-weighting
    works (e.g. an off-sensor mask column for on-sensor totals).
    """
    interpret = resolve_interpret(interpret)
    b, u = unit_energy.shape
    u2, c = weights.shape
    assert u == u2, (unit_energy.shape, weights.shape)
    block_points = max(min(block_points, b), 1)
    pad = (-b) % block_points
    if pad:
        unit_energy = jnp.pad(unit_energy, ((0, pad), (0, 0)))
    grid = ((b + pad) // block_points,)
    out = pl.pallas_call(
        _reduce_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_points, u), lambda i: (i, 0)),
            pl.BlockSpec((u, c), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_points, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b + pad, c), unit_energy.dtype),
        interpret=interpret,
    )(unit_energy, weights)
    return out[:b]
