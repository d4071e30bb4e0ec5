"""Pallas TPU kernel: on-device cartesian-grid decoding for mega-sweeps.

The PR-2 streaming driver re-materialized every chunk on the host:
``np.unravel_index`` over ``chunk_size`` flat indices, eight axis gathers,
tail padding and a full host->device transfer of the point batch — pure
overhead that grows with sweep size and serializes against dispatch.  This
kernel moves the whole decode on device: the driver ships ONE scalar
(``start``) per chunk and the kernel expands it into the ``(n_axes,
chunk)`` axis-value matrix plus per-point variant ids.

Decode of a flat stream index ``g`` (variant-major, C-order within a
variant, exactly :class:`repro.core.sweep.ChunkedGrid` semantics):

* ``variant = g // n_var``, ``local = g % n_var`` — the per-variant block;
* per axis ``a``: ``idx_a = (local // stride_a) % size_a`` with the grid
  shape/strides baked statically (they define the executable; the axis
  VALUES stay traced inputs so re-gridding never recompiles);
* value lookup from the tiny ``(n_axes, V * Lmax)`` axis-value table as a
  one-hot matmul — the same MXU-friendly gather idiom as
  ``category_reduce`` (one-hot rows sum exactly one f32 table entry, so
  decoded values are bit-identical to the host gather).

Indices are int32.  The staged streaming engine passes one variant's
table and offsets inside that variant, so they stay under 2**31 however
large the whole space is.  Out-of-range tail indices are clamped to
``total - 1``; callers mask them via their own ``flat < hi`` validity
predicate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .runtime import resolve_interpret


def decode_axis_values(off, table, *, shape, strides, n_var, n_variants,
                       lmax, gather):
    """Decode clamped flat indices into per-axis value vectors in-kernel.

    ``off`` is a ``(1, block)`` integer array of flat stream indices
    (already clamped to ``total - 1``); ``table`` the ``(n_axes,
    n_variants * lmax)`` axis-value bank loaded from a kernel ref.
    Returns ``(vals, vid32)``: a list of ``(block,)`` f32 axis-value
    vectors in :class:`~repro.core.sweep.ChunkedGrid` axis order and the
    ``(1, block)`` int32 variant ids.  The staged engine's decode: its
    chunks may span variants, and it returns per-point variant ids.  The
    fused megakernel's chunks are variant-uniform, so it decodes with
    selects over its chunk's own table
    (``repro.kernels.fused_sweep.decode_block``) instead; the
    fused == staged parity tests in ``tests/test_fused_sweep.py`` and
    both decodes' bit-for-bit tests against the host ``ChunkedGrid``
    (``tests/test_grid_decode.py``, ``tests/test_fused_sweep.py``) keep
    the two from drifting.
    """
    vid = off // n_var
    local = off - vid * n_var
    vid32 = vid.astype(jnp.int32)
    # table entry of each point, as the sublane index of a (V * Lmax,
    # block) one-hot: the lookup is then a lane-dense (1, V * Lmax) x
    # (V * Lmax, block) matmul with no relayout of the index vector
    entry = jax.lax.broadcasted_iota(jnp.int32, (n_variants * lmax, 1), 0)
    vals = []
    for a in range(len(shape)):
        idx_a = ((local // strides[a]) % shape[a]).astype(jnp.int32)
        ci = vid32 * lmax + idx_a
        if gather:
            # interpreter path: a direct (block,) gather beats building
            # block x (V * Lmax) one-hots element by element
            vals.append(jnp.take(table[a, :], ci[0]))
        else:
            # compiled TPU path: table lookup as a one-hot matmul so the
            # gather rides the MXU; HIGHEST precision keeps every f32
            # table entry exact (one bf16 pass would round it)
            onehot = (ci == entry).astype(jnp.float32)
            vals.append(jnp.dot(table[a:a + 1, :], onehot,
                                precision=jax.lax.Precision.HIGHEST)[0])
    return vals, vid32


def _decode_kernel(start_ref, table_ref, vals_ref, vid_ref, *, shape,
                   strides, n_var, total, block, n_variants, lmax, gather):
    i = pl.program_id(0)
    off = (start_ref[0, 0] + i * block
           + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1))
    off = jnp.minimum(off, total - 1)          # clamp tail; caller masks
    vals, vid32 = decode_axis_values(
        off, table_ref[...], shape=shape, strides=strides, n_var=n_var,
        n_variants=n_variants, lmax=lmax, gather=gather)
    for a in range(len(shape)):
        vals_ref[a, :] = vals[a]
    vid_ref[0, :] = vid32[0]


def grid_strides(shape) -> tuple:
    """C-order strides of a grid shape (last axis fastest)."""
    strides = [1] * len(shape)
    for a in range(len(shape) - 2, -1, -1):
        strides[a] = strides[a + 1] * shape[a + 1]
    return tuple(strides)


@functools.partial(jax.jit, static_argnames=(
    "shape", "n_var", "total", "chunk", "block_points", "interpret"))
def grid_decode(tables: jax.Array, start, *, shape, n_var: int, total: int,
                chunk: int, block_points: int = 4096,
                interpret: bool = None):
    """Decode flat stream indices ``[start, start + chunk)`` on device.

    ``tables`` is the ``(V, n_axes, Lmax)`` f32 axis-value bank (axis
    ``a`` of variant ``v`` holds its first ``shape[a]`` entries; padding
    is never indexed).  ``shape`` is the per-variant grid shape shared by
    all variants, ``n_var = prod(shape)`` the per-variant block size and
    ``total = V * n_var`` the stream length.  Returns ``(vals, vid)``:
    the ``(n_axes, chunk)`` f32 axis values and ``(chunk,)`` int32
    variant ids.
    """
    n_variants, n_axes, lmax = tables.shape
    assert n_axes == len(shape), (tables.shape, shape)
    assert total <= n_variants * n_var, (total, n_variants, n_var)
    bp = max(min(block_points, chunk), 1)
    nb = -(-chunk // bp)
    interpret = resolve_interpret(interpret)
    table2 = jnp.transpose(tables, (1, 0, 2)).reshape(
        n_axes, n_variants * lmax).astype(jnp.float32)
    start2 = jnp.asarray(start, jnp.int32).reshape(1, 1)
    vals, vid = pl.pallas_call(
        functools.partial(
            _decode_kernel, shape=tuple(shape), strides=grid_strides(shape),
            n_var=n_var, total=total, block=bp, n_variants=n_variants,
            lmax=lmax, gather=interpret),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((n_axes, n_variants * lmax), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((n_axes, bp), lambda i: (0, i)),
            pl.BlockSpec((1, bp), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_axes, nb * bp), jnp.float32),
            jax.ShapeDtypeStruct((1, nb * bp), jnp.int32),
        ],
        interpret=interpret,
    )(start2, table2)
    return vals[:, :chunk], vid[0, :chunk]
