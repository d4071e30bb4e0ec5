"""Pallas kernel: fused decode -> evaluate -> reduce sweep megakernel.

The PR-3 streaming step was three staged device passes per chunk —
``grid_decode`` (flat indices -> ``(n_axes, B)`` point matrix),
``evaluate_bank`` (points -> ``B x n_out`` output table), ``block_stats``
(+ a full-chunk ``top_k``) — with every intermediate round-tripping
through HBM.  At mega-sweep scale the model is a few hundred FLOPs per
point, so the sweep is bandwidth-bound: the staged path writes and
re-reads ~100 B of HBM per design point that the reduction immediately
collapses to O(k) scalars.

This kernel fuses the whole per-chunk pipeline into ONE pass per block.
A block of points is one dense ``(8, block / 8)`` tile (:data:`ROWS`
rows, :func:`block_positions`), and every per-point value in the kernel
has that shape, so each fills whole ``(8, 128)`` vregs:

1. **decode** — the block's offsets inside its variant expand into
   axis-value tiles in VMEM (:func:`decode_block`): static div/mod
   against the grid strides gives each axis's index, and a chain of
   selects over that axis's static length picks its value out of the
   chunk variant's ``(n_axes, lmax)`` table, which rides in SMEM.
   Chunks are variant-uniform, so the caller slices that table; the
   kernel never derives a variant.  A select copies a table entry, so decoded values
   are bit-identical to the host gather.  The staged engine's
   ``grid_decode`` keeps its own one-hot lookup (its chunks may span
   variants); the fused == XLA twin == staged parity tests in
   ``tests/test_fused_sweep.py`` and the decode tests there against the
   host grid keep the two from drifting;
2. **evaluate** — the banked Eq. 1-17 physics runs on the decoded block
   through the coefficient-form compute function
   (``repro.core.batch.build_coeff_compute``): one tile per slot, each
   coefficient a scalar read from the chunk's fused ``(W,)`` row in SMEM
   and broadcast across the tile, a traced slot index resolved by
   selects, so the kernel body holds no matmul;
3. **reduce** — the block folds to its masked metric sum / feasible
   count and its k smallest candidates (iterative min-extract, branchless
   — ``lax.top_k`` has no Mosaic lowering) before anything is written.

Only the ``(G, k)`` candidate lists and ``(G, 2)`` stat partials ever
leave the kernel — the decoded point matrix and the per-point output
table never touch HBM.  Winning rows re-gather their full output schema
in a tiny O(k) second pass at sweep finalization.

Masking follows the streaming driver's contract: a point is valid iff
``low <= off < limit`` AND it lies inside this call's ``chunk`` span
(blocks are padded up to :func:`kernel_block`; the spillover positions
would otherwise double-count the next shard's points).  ``start``,
``low`` and ``limit`` are int32 offsets inside the chunk's variant: the
driver cuts a sweep into per-variant segments on the host, so a space
may pass 2**31 points while every value the kernel holds stays int32.

Mosaic's rules shape the layout: ``start`` / ``low`` / ``limit`` arrive
as one SMEM vector, the chunk's axis table and its coefficient row as two
more (their scalars broadcast into the tiles), each block writes whole
``(1, n)`` rows of ``(G, 1, n)`` outputs (the block's trailing dims then
equal the array's), and the kernel holds no 64-bit value.
``tests/test_tpu_compile.py`` compiles it for a TPU v5e.
Compiled and interpreted kernels run the same decode, so the CPU tests
exercise the code the chip runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .grid_decode import grid_strides
from .runtime import resolve_interpret

#: the megakernel's name in the compiled program (the Mosaic custom
#: call's kernel name), so a profile or HLO text finds it by name
KERNEL_NAME = "camj_megakernel"


#: sublane rows of a block's tile: a block of ``block`` points is laid
#: out as one dense ``(ROWS, block // ROWS)`` array, so a per-point value
#: fills whole vregs instead of one sublane row of each
ROWS = 8


def kernel_block(block_points: int, chunk: int) -> int:
    """The kernel's block size for a ``chunk``-point call: at most
    ``block_points`` (and the chunk), rounded up to whole tile rows.
    Positions past the chunk are masked, so rounding adds no point."""
    bp = max(min(block_points, chunk), 1)
    return -(-bp // ROWS) * ROWS


def block_positions(block):
    """Each tile element's position in its block: row ``r`` and column
    ``c`` of the ``(ROWS, block // ROWS)`` tile hold ``r * (block //
    ROWS) + c``."""
    tile = (ROWS, block // ROWS)
    return (jax.lax.broadcasted_iota(jnp.int32, tile, 0) * (block // ROWS)
            + jax.lax.broadcasted_iota(jnp.int32, tile, 1))


def decode_block(bounds_ref, tab_ref, *, shape, strides, lmax, chunk,
                 block):
    """The validity mask and decoded axis values of this grid step's block.

    ``bounds_ref`` holds ``start``, ``low`` and ``limit`` (int32 offsets
    inside the chunk's variant); ``tab_ref`` the chunk variant's axis
    table flattened to ``(n_axes * lmax,)`` (axis ``a`` holds its values
    at ``a * lmax`` onwards; padding is never read).  ``block`` is a
    multiple of :data:`ROWS` (:func:`kernel_block`).  Returns the mask
    and a list of f32 values in :class:`~repro.core.sweep.ChunkedGrid`
    axis order, each a ``(ROWS, block // ROWS)`` tile laid out by
    :func:`block_positions`.  The axis index ``(off // stride) % size``
    stays in range past the variant's end (masked points) without a
    clamp.  Each value is a chain of selects over the axis's static
    length: an exact copy of a table entry.
    """
    pos = pl.program_id(0) * block + block_positions(block)
    off = bounds_ref[0] + pos                   # offset inside the variant
    valid = ((off >= bounds_ref[1]) & (off < bounds_ref[2])
             & (pos < chunk))
    # lax, not jnp: off >= 0, so truncating div/rem equal the floored
    # ones without their sign fix-ups, and the ~100 selects trace as bare
    # primitives (jnp's wrappers made them a visible share of set-up)
    vals = []
    for a, (n, stride) in enumerate(zip(shape, strides)):
        val = jax.lax.broadcast(tab_ref[a * lmax], off.shape)
        if n > 1:
            idx = jax.lax.rem(jax.lax.div(off, jnp.int32(stride)),
                              jnp.int32(n))
            for j in range(1, n):
                val = jax.lax.select(
                    jax.lax.eq(idx, jnp.int32(j)),
                    jax.lax.broadcast(tab_ref[a * lmax + j], off.shape), val)
        vals.append(val)
    return valid, vals


def _fused_kernel(bounds_ref, tab_ref, row_ref, cv_ref, cl_ref, st_ref,
                  *, compute, metric, axis_names, kk, block, **decode):
    valid, vals = decode_block(bounds_ref, tab_ref, block=block, **decode)
    out = compute(row_ref, dict(zip(axis_names, vals)),
                  keys=(metric, "feasible"))
    ok = out["feasible"] & valid
    mv = out[metric].astype(jnp.float32)

    # block-local top-k by iterative min extraction: k is tiny and static.
    # Each pass takes the block minimum and the LOWEST position holding it
    # (a min over the masked positions, which is also the tie rule of
    # lax.top_k in the XLA twin), both reduced over the whole tile to
    # (1, 1), then masks that position; the winners accumulate into
    # (1, kk) vectors by lane select, so the kernel stores whole vectors
    # only
    masked = jnp.where(ok, mv, jnp.inf)
    posi = block_positions(block)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, kk), 1)
    cand_v = jnp.full((1, kk), jnp.inf, jnp.float32)
    cand_l = jnp.zeros((1, kk), jnp.int32)
    for j in range(kk):
        m = jnp.min(masked, keepdims=True)
        am = jnp.min(jnp.where(masked == m, posi, block), keepdims=True)
        cand_v = jnp.where(slot == j, m, cand_v)
        cand_l = jnp.where(slot == j, am, cand_l)
        masked = jnp.where(posi == am, jnp.inf, masked)
    cv_ref[...] = cand_v
    cl_ref[...] = cand_l
    s = jnp.sum(jnp.where(ok, mv, 0.0), keepdims=True)
    n = jnp.sum(ok.astype(jnp.float32), keepdims=True)
    st_ref[...] = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (1, 2), 1) == 0, s, n)


def fused_sweep_block(table: jax.Array, row: jax.Array, start, low, limit,
                      *, compute, metric: str, axis_names, shape,
                      chunk: int, block_points: int = 4096,
                      kk: int = 16, interpret: bool = None):
    """Decode + evaluate + reduce offsets ``[start, start + chunk)``.

    ``table`` is the chunk variant's ``(n_axes, lmax)`` f32 axis-value
    table (axis ``a`` holds its first ``shape[a]`` entries; chunks are
    variant-uniform), ``row`` the variant's ``(1, W)`` fused coefficient
    row and ``compute`` the coefficient-form evaluator from
    :func:`repro.core.batch.build_coeff_compute`.  Blocks hold
    ``bp = kernel_block(block_points, chunk)`` points.  Returns
    ``(cand_v, cand_l, sums, counts)``: per-block ascending candidate
    metric values ``(G, kk)`` (+inf-padded), their block-LOCAL int32
    indices ``(G, kk)`` (offset = ``start + g * bp + cand_l``), and the
    masked per-block metric sums / valid counts ``(G,)``.
    """
    n_axes, lmax = table.shape
    assert n_axes == len(shape) == len(axis_names), (table.shape, shape)
    assert max(shape) <= lmax, (table.shape, shape)
    bp = kernel_block(block_points, chunk)
    nb = -(-chunk // bp)
    interpret = resolve_interpret(interpret)

    bounds = jnp.stack([jnp.asarray(v, jnp.int32)
                        for v in (start, low, limit)])
    # per-block outputs are (G, 1, n) arrays whose leading block dim is
    # squeezed: each kernel block writes one whole (1, n) row, and the
    # block's trailing two dims equal the array's, as Mosaic requires
    cv, cl, st = pl.pallas_call(
        functools.partial(
            _fused_kernel, compute=compute, metric=metric,
            axis_names=tuple(axis_names), kk=kk, shape=tuple(shape),
            strides=grid_strides(shape), lmax=lmax, chunk=chunk, block=bp),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, kk), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, kk), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, 2), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, 1, kk), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, kk), jnp.int32),
            jax.ShapeDtypeStruct((nb, 1, 2), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_NAME,
    )(bounds, table.reshape(-1), row.reshape(-1))
    return cv[:, 0], cl[:, 0], st[:, 0, 0], st[:, 0, 1]
