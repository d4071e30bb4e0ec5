"""XLA-native twin of the fused decode -> evaluate -> reduce megakernel.

``repro.kernels.fused_sweep`` expresses the fused sweep step as a Pallas
kernel: Mosaic-compiled on TPU, emulated by the Pallas interpreter
everywhere else.  Off-TPU the interpreter is pure overhead — every
``pallas_call`` grid step re-enters Python — yet the kernel body is
ordinary element-wise math + a bounded reduction, exactly the program
shape XLA already compiles well on CPU and GPU.  This module is that
body re-expressed in pure ``jnp``:

1. **decode** — the kernel's stride math on the same offsets, then
   a plain XLA gather from the chunk variant's ``(n_axes, lmax)`` table
   (the kernel's select chain is the Mosaic form of that gather, and the
   parity tests hold the two together);
2. **evaluate** — the same coefficient-form Eq. 1-17 compute function
   from ``repro.core.batch.build_coeff_compute(dims)`` on ``(B,)``
   points (the kernel runs it on ``(8, B/8)`` tiles), the chunk's fused
   ``(W,)`` coefficient row broadcasting across the block;
3. **reduce** — per block of ``block_points``, masked metric sums /
   feasible counts and the ``kk`` smallest candidates via
   ``jax.lax.top_k`` (ties break to the LOWEST flat index, matching the
   Pallas kernel's iterative min-extract and the staged oracle).

The return contract is bit-for-bit the Pallas kernel's: ``(cand_v,
cand_l, sums, counts)`` with ``(G, kk)`` ascending +inf-padded candidate
values, ``(G, kk)`` block-LOCAL int32 indices (the point's offset
inside its variant is ``start + g * block_points + cand_l``), and
``(G,)`` stats — so
``core.shard_sweep._fused_step`` folds either backend's output through
the identical merge path, and the rel-1e-6 parity chain (XLA == Pallas
== staged == monolithic) is asserted in tests/test_fused_sweep.py.

Validity masking is the shared streaming contract: a point counts iff
``low <= off < limit`` AND it lies inside this call's ``chunk`` span
(blocks pad up to ``block_points``; spillover positions would otherwise
double-count the next shard's points).  ``start``, ``low`` and ``limit``
are int32 offsets inside the chunk's variant, as the kernel's are.  Past
the variant's end the axis indices wrap around, exactly like the
kernel's.

The function is jitted (shape-static args) for the same reason
``grid_decode`` is: it also runs nested inside the already-jitted
superchunk scan, where the inner jit inlines for free, and standalone
callers get a compiled step — which also roots it for the
``repro.analysis`` hot-path purity rules (a host sync reintroduced here
is a per-block stall on the sweep's innermost loop).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .grid_decode import grid_strides


@functools.partial(jax.jit, static_argnames=(
    "compute", "metric", "axis_names", "shape", "chunk", "block_points",
    "kk"))
def fused_sweep_block_xla(table: jax.Array, row: jax.Array, start, low,
                          limit, *, compute, metric: str, axis_names,
                          shape, chunk: int, block_points: int = 4096,
                          kk: int = 16):
    """Decode + evaluate + reduce offsets ``[start, start + chunk)``.

    Same signature and return contract as
    :func:`repro.kernels.fused_sweep.fused_sweep_block`, minus the
    ``interpret=`` knob (XLA has no interpreter mode), and its blocks
    hold ``min(block_points, chunk)`` points, unrounded — ``compute``
    comes from ``build_coeff_compute(dims)``.
    """
    n_axes, lmax = table.shape
    assert n_axes == len(shape) == len(axis_names), (table.shape, shape)
    assert max(shape) <= lmax, (table.shape, shape)
    bp = max(min(block_points, chunk), 1)
    nb = -(-chunk // bp)

    pos = jnp.arange(nb * bp, dtype=jnp.int32).reshape(1, -1)
    off = jnp.asarray(start, jnp.int32) + pos
    valid = ((off >= jnp.asarray(low, jnp.int32))
             & (off < jnp.asarray(limit, jnp.int32))
             & (pos < chunk))[0]
    vals = [jnp.take(table[a], (off[0] // stride) % n)
            for a, (n, stride) in enumerate(zip(shape,
                                                grid_strides(shape)))]
    out = compute(row.reshape(-1), dict(zip(axis_names, vals)),
                  keys=(metric, "feasible"))
    ok = out["feasible"] & valid
    mv = out[metric].astype(jnp.float32)

    masked = jnp.where(ok, mv, jnp.inf).reshape(nb, bp)
    # lax.top_k is stable: equal values keep the lower index, matching
    # the Pallas argmin-extract loop (and the staged oracle's top_k)
    neg, cl = jax.lax.top_k(-masked, min(kk, bp))
    if kk > bp:                 # pad contract: (G, kk) even for tiny blocks
        pad = kk - bp
        neg = jnp.pad(neg, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        cl = jnp.pad(cl, ((0, 0), (0, pad)))
    sums = jnp.sum(jnp.where(ok, mv, 0.0).reshape(nb, bp), axis=1)
    counts = jnp.sum(ok.reshape(nb, bp).astype(jnp.float32), axis=1)
    return -neg, cl.astype(jnp.int32), sums, counts
