"""Work of the fused sweep megakernel (``repro/kernels/fused_sweep.py``):
the operations and HBM bytes one design point needs.

The operations are the physics' (Eqs. 1-17 and the power density, as
:func:`camj_ref.vector.outputs` writes them), counted for one point of
each structural variant from the benchmark's own lowering of it and
averaged over the variants (every variant holds the same number of
points).  They do not count how an implementation decodes a point or
ranks it, so a share of the roofline compares implementations on the
same work.  One add, multiply, divide, compare, select, ``ceil``,
``exp`` or ``log`` counts one; a table interpolation counts four.

A point reads no bytes from HBM: its axis values are decoded from
tables a chunk reads once, so the bytes are those tables, the variant's
coefficient row and the candidates written, per chunk call.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

from camj_ref.vector import variant_plans

INTERP = 4


def _plan_ops(plan) -> int:
    ops = 3                                  # frame time, t_a, feasible
    for i in range(len(plan.d_is_sys)):
        ops += 7 if plan.d_is_sys[i] else 0   # thr, ceil, +dims, /clock
        ops += 3 * int(np.sum(plan.d_edge_mask[i]))   # start: max(+ *)
        ops += 2                              # end, t_d max/min
        ops += INTERP + 2 + 2                 # node scale, dyn, static
    for a in range(len(plan.a_const)):
        ops += 2                              # pad, * ops
        ops += 4 * int(np.sum(np.asarray(plan.lin_arr) == a))
        ops += (6 + INTERP) * int(np.sum(np.asarray(plan.fom_arr) == a))
    # memories: node scale, access energies, tech selects, leakage
    # (two interpolations), reads, alpha, the row, the cell area
    ops += len(plan.m_reads_fixed) * (3 * INTERP + 22)
    units = plan.num_units
    ops += 3 * units                          # categories, total, on-sensor
    ops += 8                                  # area, power, density
    return ops


def ops_per_point(algorithms: Sequence[str]) -> float:
    plans = variant_plans(algorithms)
    return float(np.mean([_plan_ops(p) for _, _, p in plans]))


def bytes_per_chunk(algorithms: Sequence[str], *, lmax: int = 16,
                    n_axes: int = 10, k: int = 16,
                    block_points: int = 4096,
                    chunk: int = 1 << 18) -> float:
    """HBM bytes one chunk call reads and writes."""
    plans = variant_plans(algorithms)
    n_var = len(plans)
    tables = n_axes * n_var * lmax * 4
    row = max(p.num_units for _, _, p in plans) * 32 * 4
    blocks = chunk // block_points
    out = blocks * (k * 8 + 16)
    return float(tables + row + out)


def roofline(*, points: int, chunks: int, kernel_s_per_device: float,
             n_devices: int, algorithms: Sequence[str],
             peaks: Dict) -> Dict:
    """The least time a device needs for its share of the work, over the
    kernel's time on it; names the bound that sets the least time."""
    flops = ops_per_point(algorithms) * points / n_devices
    nbytes = bytes_per_chunk(algorithms) * chunks / n_devices
    t_flops = flops / peaks["flops_bf16"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    least = max(t_flops, t_bytes)
    return dict(share_pct=100.0 * least / kernel_s_per_device,
                bound="compute" if t_flops >= t_bytes else "memory",
                flops=flops, bytes=nbytes, least_s=least)
