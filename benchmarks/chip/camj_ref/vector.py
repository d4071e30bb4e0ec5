"""A whole cartesian design space through the CamJ equations, in plain
``jax.numpy``.

Each structural variant is lowered once (``plan.lower`` of the copied
model, built at the 65 nm reference node like the program's sweeps) and
priced with Eqs. 1-17 written as broadcasts over the axis values: an
axis's values sit on their own array dimension, so every term is
computed at the shape of the axes it depends on, and only the final
sums run at the full grid.  No gather, no kernel, no scan.  The grid is
cut into blocks along its first axis; each block is reduced on the
device to its feasible count, metric sum, minimum and top-k, and the
host folds the blocks in float64.

Flat indices follow the sweep's layout: variant-major, and within a
variant C order over :data:`AXES`.  Ties rank the lower flat index
first.  ``dtype`` selects the arithmetic (float32 for the reference,
bfloat16 for the precision control).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .constants import (DYNAMIC_ENERGY_SCALE, MIPI_CSI2_ENERGY_PER_BYTE,
                        SRAM_ACCESS_ENERGY_PER_BIT_65,
                        SRAM_HP_LEAKAGE_PER_BIT, SRAM_LEAKAGE_PER_BIT,
                        STT_LEAKAGE_PER_BIT, STT_READ_ENERGY_PER_BIT_65,
                        STT_WRITE_ENERGY_PER_BIT_65, UTSV_ENERGY_PER_BYTE,
                        table_points)
from .energy import CATEGORIES
from .fom import fom_table_points
from .plan import TECH_INDEX, lower
from .scalar import OUT_KEYS
from .usecases import ALGORITHMS

#: the swept axes, in the sweep's flat-index order (the two coefficient
#: hook axes that follow them, vdd_scale and adc_bits, stay at defaults)
AXES = ("cis_node", "soc_node", "mem_tech", "sys_rows", "sys_cols",
        "frame_rate", "active_fraction_scale", "pixel_pitch_um")
#: the node every structure is built at before the node axes re-scale it
REF_CIS_NODE = 65
#: the width of the rows a block's top-k is taken over, level by level
_TOPK_ROW = 256


def encode(grids: Dict[str, Sequence]) -> Dict[str, np.ndarray]:
    """Axis values as float64 arrays, ``mem_tech`` names as codes."""
    out = {}
    for ax in AXES:
        vals = grids[ax]
        if ax == "mem_tech":
            vals = [TECH_INDEX[v] if isinstance(v, str) else int(v)
                    for v in vals]
        out[ax] = np.asarray(vals, np.float64)
    return out


def variant_plans(algorithms: Sequence[str], soc_node: int = 22):
    """``[(algorithm, variant, plan)]`` in the sweep's variant order."""
    out = []
    for algo in algorithms:
        build, variants = ALGORITHMS[algo]
        for variant in variants:
            hw, stages, mapping, _ = build(variant, cis_node=REF_CIS_NODE,
                                           soc_node=soc_node)
            out.append((algo, variant, lower(hw, stages, mapping)))
    return out


def _lerp(x, xs, ys, dt):
    """Piecewise-linear ``ys`` over ascending ``xs`` at ``x``, held at the
    end values outside them (``numpy.interp``), in the arithmetic ``dt``."""
    x = jnp.asarray(x, dt)
    out = jnp.full(x.shape, ys[0], dt)
    for i in range(len(xs) - 1):
        t = (x - xs[i]) / (xs[i + 1] - xs[i])
        out = jnp.where(x >= xs[i], ys[i] + t * (ys[i + 1] - ys[i]), out)
    return jnp.where(x >= xs[-1], jnp.asarray(ys[-1], dt), out)


def _interp(x, table, dt):
    """A technology table at a node: geometric between its nodes."""
    nodes, vals = table_points(table)
    return jnp.exp(_lerp(x, nodes, [math.log(v) for v in vals], dt))


def _walden(rate, dt):
    """The Walden figure of merit at a sampling rate: log-log linear."""
    log_r, log_e = fom_table_points()
    return 10.0 ** _lerp(jnp.log10(rate), log_r, log_e, dt)


def outputs(plan, ax: Dict[str, jnp.ndarray], dt) -> Dict[str, jnp.ndarray]:
    """Every output of the sweep schema at the broadcast of ``ax``.

    ``ax`` maps each name of :data:`AXES` to an array of dtype ``dt``
    (``mem_tech`` as int32) whose shapes broadcast together.
    """
    f = float                    # plan constants enter as weak scalars
    cis, soc = ax["cis_node"], ax["soc_node"]
    rows, cols = ax["sys_rows"], ax["sys_cols"]
    fr, afs = ax["frame_rate"], ax["active_fraction_scale"]
    pitch, tech = ax["pixel_pitch_um"], ax["mem_tech"]
    frame_time = 1.0 / fr

    def node(role, declared):
        return cis if role == 0 else soc if role == 1 else f(declared)

    # Sec. 4.1 digital timing over the stage DAG
    D = len(plan.d_is_sys)
    durs, starts, ends = [], [], []
    for i in range(D):
        if plan.d_is_sys[i]:
            thr = rows * cols * f(plan.d_util[i])
            cycles = jnp.ceil(f(plan.d_macs[i]) / thr) + rows + cols
        else:
            cycles = f(plan.d_cycles_fixed[i])
        durs.append(cycles / f(plan.d_clock_hz[i]))
    for i in range(D):
        s_i = jnp.zeros((), dt)
        for j in range(i):
            if plan.d_edge_mask[i, j]:
                s_i = jnp.maximum(s_i, starts[j]
                                  + f(plan.d_edge_w[i, j]) * durs[j])
        starts.append(s_i)
        ends.append(s_i + durs[i])
    t_d = (functools.reduce(jnp.maximum, ends)
           - functools.reduce(jnp.minimum, starts)) if D \
        else jnp.zeros((), dt)
    t_a = (frame_time - t_d) / f(plan.n_phases)

    units: List = []
    # Eqs. 2-13: analog arrays
    for a in range(len(plan.a_const)):
        pad = t_a * f(plan.a_pad_coeff[a])
        e = jnp.asarray(f(plan.a_const[a]), dt)
        for j in np.flatnonzero(np.asarray(plan.lin_arr) == a):
            t_cell = jnp.maximum(pad * f(plan.lin_inv_div[j]), 1e-12)
            e = e + f(plan.lin_coeff[j]) * t_cell
        for j in np.flatnonzero(np.asarray(plan.fom_arr) == a):
            t_cell = jnp.maximum(pad * f(plan.fom_inv_div[j]), 1e-12)
            e = e + f(plan.fom_scale[j]) * _walden(1.0 / t_cell, dt)
        units.append(e * f(plan.a_ops[a]))
    # Eqs. 14-15: digital compute
    for i in range(D):
        s_u = _interp(node(plan.d_role[i], plan.d_declared_node[i]),
                      DYNAMIC_ENERGY_SCALE, dt)
        units.append(f(plan.d_dyn_coeff[i]) * s_u
                     + f(plan.d_static_power[i]) * durs[i])
    # Eq. 16: memories
    M = len(plan.m_reads_fixed)
    digital_area = jnp.zeros((), dt)
    for m in range(M):
        n_m = node(plan.m_role[m], plan.m_declared_node[m])
        s_m = _interp(n_m, DYNAMIC_ENERGY_SCALE, dt)
        t_m = jnp.where(tech >= 0, tech, int(plan.m_tech[m]))
        stt = t_m == 2
        bits = f(plan.m_bits_per_access[m])
        sram = (SRAM_ACCESS_ENERGY_PER_BIT_65 * bits
                * f(plan.m_size_factor[m])) * s_m
        read_e = jnp.where(stt, STT_READ_ENERGY_PER_BIT_65 * bits * s_m,
                           sram)
        write_e = jnp.where(stt, STT_WRITE_ENERGY_PER_BIT_65 * bits * s_m,
                            sram)
        if not math.isnan(plan.m_read_explicit[m]):
            read_e = jnp.asarray(f(plan.m_read_explicit[m]), dt)
        if not math.isnan(plan.m_write_explicit[m]):
            write_e = jnp.asarray(f(plan.m_write_explicit[m]), dt)
        leak_bit = jnp.where(
            stt, jnp.asarray(STT_LEAKAGE_PER_BIT, dt),
            jnp.where(t_m == 1, _interp(n_m, SRAM_HP_LEAKAGE_PER_BIT, dt),
                      _interp(n_m, SRAM_LEAKAGE_PER_BIT, dt)))
        leak = leak_bit * f(plan.m_bits_total[m])
        if not math.isnan(plan.m_leak_explicit[m]):
            leak = jnp.asarray(f(plan.m_leak_explicit[m]), dt)
        reads = (f(plan.m_reads_fixed[m])
                 + f(plan.m_reads_dnn2[m]) / jnp.maximum(rows, 1.0))
        alpha = f(plan.m_alpha[m]) * afs
        units.append(read_e * reads + write_e * f(plan.m_writes[m])
                     + leak * frame_time * alpha)
        area_node = node(plan.m_area_role[m], plan.m_declared_node[m])
        digital_area = digital_area + f(plan.m_bits_total[m]) * (
            150.0 * (area_node * 1e-6) ** 2)
    # Eq. 17: communication
    if plan.utsv_bytes:
        units.append(jnp.asarray(
            f(plan.utsv_bytes) * UTSV_ENERGY_PER_BYTE, dt))
    units.append(jnp.asarray(f(plan.mipi_bytes) * MIPI_CSI2_ENERGY_PER_BYTE,
                             dt))
    if len(units) != plan.num_units:
        raise AssertionError(f"{len(units)} unit rows for "
                             f"{plan.num_units} units of {plan.hw_name}")

    out = {}
    zero = jnp.zeros((), dt)
    for c, cat in enumerate(CATEGORIES):
        out[f"cat_{cat}_j"] = sum(
            (e for e, uc in zip(units, plan.unit_category) if uc == c), zero)
    out["total_j"] = sum(units, zero)
    out["on_sensor_j"] = sum(
        (e for e, on in zip(units, plan.unit_on_sensor) if on), zero)
    # Sec. 6.2 power density
    analog_area = f(plan.n_pixels) * (pitch * 1e-3) ** 2
    area = (jnp.maximum(analog_area, digital_area) if plan.stacked
            else analog_area + digital_area)
    out["t_d_s"] = t_d
    out["t_a_s"] = t_a
    out["feasible"] = t_a > 0.0
    out["area_mm2"] = area
    out["power_mw"] = out["on_sensor_j"] * fr * 1e3
    out["density_mw_mm2"] = out["power_mw"] / jnp.maximum(area, 1e-9)
    return out


def smallest_k(x, k: int):
    """The ``k`` smallest of a 1-D array and their positions, ascending,
    the lower position first among equals.

    Taken row by row over rows of ``_TOPK_ROW``, level after level: one
    ``top_k`` over a long vector takes the TPU compiler minutes, rows of
    a few hundred take it a second.  Candidates keep their row-major
    order between levels, so ties still go to the lower position."""
    v = -x
    pos = jnp.arange(x.shape[0], dtype=jnp.int32)
    while v.shape[0] > _TOPK_ROW:
        pad = (-v.shape[0]) % _TOPK_ROW
        if pad:
            v = jnp.concatenate([v, jnp.full((pad,), -jnp.inf, v.dtype)])
            pos = jnp.concatenate([pos, jnp.full((pad,), x.shape[0],
                                                 jnp.int32)])
        kk = min(k, _TOPK_ROW)
        v, p = jax.lax.top_k(v.reshape(-1, _TOPK_ROW), kk)
        pos = jnp.take_along_axis(pos.reshape(-1, _TOPK_ROW), p,
                                  axis=1).reshape(-1)
        v = v.reshape(-1)
    v, p = jax.lax.top_k(v, min(k, v.shape[0]))
    return -v, pos[p]


def _block_reduce(plan, dt, metric: str, k: int, shape: Tuple[int, ...],
                  cis, rest):
    """Reduce one block (one value of the first axis) on the device."""
    n_rest = len(AXES) - 1
    ax = {"cis_node": cis}
    for pos, (name, vals) in enumerate(zip(AXES[1:], rest)):
        bshape = [1] * n_rest
        bshape[pos] = vals.shape[0]
        ax[name] = vals.reshape(bshape)
    out = outputs(plan, ax, dt)
    feas = jnp.broadcast_to(out["feasible"], shape)
    val = jnp.broadcast_to(out[metric], shape).astype(jnp.float32)
    masked = jnp.where(feas, val, jnp.inf).reshape(-1)
    top_v, top_i = smallest_k(masked, k)
    amin = jnp.argmin(masked)
    return dict(top_v=top_v, top_i=top_i,
                n_feasible=jnp.sum(feas, dtype=jnp.int32),
                total=jnp.sum(jnp.where(feas, val, 0.0)),
                vmin=masked[amin], amin=amin.astype(jnp.int32))


@dataclasses.dataclass
class RefSweep:
    """What a sweep of the space returns, as the reference computes it."""
    labels: List[str]
    n_var: int
    #: ``[(slot, local_index, value)]`` ascending by value, then index
    topk: List[Tuple[int, int, float]]
    #: label -> {n, n_feasible, metric_min, metric_mean, argmin_index}
    summaries: Dict[str, Dict]


class Reference:
    """The reference of one set of algorithms, at one arithmetic."""

    def __init__(self, algorithms: Sequence[str], *, dtype=jnp.float32,
                 soc_node: int = 22):
        self.dtype = jnp.dtype(dtype)
        self.plans = variant_plans(algorithms, soc_node)
        self._jit: Dict[tuple, object] = {}

    def labels(self) -> List[str]:
        return [f"{a}/{v}" for a, v, _ in self.plans]

    def _fn(self, slot: int, metric: str, k: int, shape):
        key = (slot, metric, k, shape)
        if key not in self._jit:
            self._jit[key] = jax.jit(functools.partial(
                _block_reduce, self.plans[slot][2], self.dtype, metric, k,
                shape))
        return self._jit[key]

    def sweep(self, grids: Dict[str, Sequence], *, metric: str,
              k: int) -> RefSweep:
        """Top-k and per-variant summaries of the whole space."""
        vals = encode(grids)
        shape = tuple(len(vals[a]) for a in AXES)
        n_block = int(np.prod(shape[1:]))
        n_var = int(np.prod(shape))
        dt = self.dtype
        rest = [jnp.asarray(vals[a], jnp.int32 if a == "mem_tech" else dt)
                for a in AXES[1:]]
        cand_v: List[np.ndarray] = []
        cand_i: List[np.ndarray] = []
        summaries: Dict[str, Dict] = {}
        with jax.default_matmul_precision("highest"):
            for slot, label in enumerate(self.labels()):
                fn = self._fn(slot, metric, k, shape[1:])
                parts = [fn(jnp.asarray(c, dt), rest)
                         for c in vals["cis_node"]]
                parts = jax.device_get(parts)
                nf, total = 0, 0.0
                vmin, amin = math.inf, -1
                for b, p in enumerate(parts):
                    nf += int(p["n_feasible"])
                    total += float(p["total"])
                    if float(p["vmin"]) < vmin:
                        vmin, amin = float(p["vmin"]), \
                            b * n_block + int(p["amin"])
                    cand_v.append(np.asarray(p["top_v"], np.float64))
                    cand_i.append(slot * n_var + b * n_block
                                  + np.asarray(p["top_i"], np.int64))
                summaries[label] = dict(
                    n=n_var, n_feasible=nf, metric_min=vmin,
                    metric_mean=total / nf if nf else math.nan,
                    argmin_index=amin)
        v = np.concatenate(cand_v)
        g = np.concatenate(cand_i)
        order = np.lexsort((g, v))[:k]
        topk = [(int(g[j] // n_var), int(g[j] % n_var), float(v[j]))
                for j in order if np.isfinite(v[j])]
        return RefSweep(labels=self.labels(), n_var=n_var, topk=topk,
                        summaries=summaries)

    def points(self, slot: int, points: List[Dict[str, float]]
               ) -> List[Dict[str, float]]:
        """Every output at each of ``points`` of variant ``slot``."""
        dt = self.dtype
        n = len(points)
        key = ("points", slot, n)
        if key not in self._jit:
            plan = self.plans[slot][2]

            def at(ax):
                out = outputs(plan, ax, dt)
                return {k: jnp.broadcast_to(out[k], (n,)).astype(
                    jnp.float32) for k in OUT_KEYS}
            self._jit[key] = jax.jit(at)
        ax = {a: jnp.asarray([p[a] for p in points],
                             jnp.int32 if a == "mem_tech" else dt)
              for a in AXES}
        with jax.default_matmul_precision("highest"):
            host = jax.device_get(self._jit[key](ax))
        return [{k: float(host[k][j]) for k in OUT_KEYS} for j in range(n)]


def unravel(local: int, grids: Dict[str, Sequence]) -> Dict[str, float]:
    """The axis values at a variant-local flat index (f64, as encoded)."""
    vals = encode(grids)
    shape = tuple(len(vals[a]) for a in AXES)
    idx = np.unravel_index(int(local), shape)
    return {a: float(vals[a][i]) for a, i in zip(AXES, idx)}
