"""Batched design-point evaluator: Eqs. 1-17 over thousands of designs.

``DesignPoints`` is a struct-of-arrays pytree of swept parameters.  The
Eq. 1-17 physics exists in three parity-locked forms here, from most to
least specialized:

* ``_build_eval`` — the per-plan evaluator: closes over one
  ``EnergyPlan``'s coefficient vectors (baked constants), per-point
  arithmetic ``vmap``-ed and ``jit``-ed into a single device call, with
  the per-category accumulation riding the Pallas ``category_reduce``
  kernel;
* ``build_banked_eval`` — the banked evaluator: coefficients arrive as a
  traced ``PlanBank`` row (``plan_bank.bank_layout``), same per-point
  arithmetic ``vmap``-ed; one executable serves every variant;
* ``build_coeff_compute`` — the coefficient-form BLOCK compute: the same
  banked physics on points of any shape, one value per slot and scalar
  coefficients, with kernel-legal primitives only, callable from inside
  a Pallas kernel body — this is what the fused mega-sweep megakernel
  (``repro.kernels.fused_sweep``) evaluates so per-point intermediates
  never reach HBM.

Numerics note: evaluation runs in f32 on device (the scalar oracle is
f64 Python); per-plan parity holds to ~1e-5 relative vs the oracle, and
banked/coefficient-form parity to 1e-6 relative vs per-plan — asserted
in tests.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.category_reduce import category_reduce
from ..spans import span
from .axes import (ADC_DECLARED, AXES, AXES_SPEC, AXIS_BY_NAME,
                   TECH_DECLARED, axis_default)
from .constants import (MIPI_CSI2_ENERGY_PER_BYTE, DYNAMIC_ENERGY_SCALE,
                        SRAM_ACCESS_ENERGY_PER_BIT_65, SRAM_HP_LEAKAGE_PER_BIT,
                        SRAM_LEAKAGE_PER_BIT, STT_LEAKAGE_PER_BIT,
                        STT_READ_ENERGY_PER_BIT_65, STT_WRITE_ENERGY_PER_BIT_65,
                        UTSV_ENERGY_PER_BYTE, table_points)
from .fom import fom_table_points
from .plan import CATEGORIES, EnergyPlan, _EXTRA_CACHES

#: the banked evaluator's category matmul must not round its f32
#: operands: at default precision a TPU multiplies in one bf16 pass
_EXACT = jax.lax.Precision.HIGHEST


class DesignPoints(NamedTuple):
    """Struct-of-arrays batch of design points (all fields shape (B,)).

    Field order is the axis-registry order (``repro.core.axes.AXES``) —
    the on-device grid decoder emits axis rows positionally against it.
    """
    cis_node: jnp.ndarray            # nm, sensor-layer process node
    soc_node: jnp.ndarray            # nm, host/compute-layer process node
    mem_tech: jnp.ndarray            # int: -1 declared, 0 sram, 1 hp, 2 stt
    sys_rows: jnp.ndarray            # systolic array rows
    sys_cols: jnp.ndarray            # systolic array cols
    frame_rate: jnp.ndarray          # FPS
    active_fraction_scale: jnp.ndarray   # multiplies each memory's alpha
    pixel_pitch_um: jnp.ndarray      # analog area knob (power density)
    vdd_scale: jnp.ndarray           # supply scale: dyn x v^2, static x v
    adc_bits: jnp.ndarray            # ADC resolution override (-1 declared)

    @property
    def batch(self) -> int:
        return int(self.cis_node.shape[0])


# the axis registry and the point struct can never drift apart
assert DesignPoints._fields == AXES, (DesignPoints._fields, AXES)

#: coefficient hooks + their PlanBank reference columns, read FROM the
#: axis registry (repro.core.axes) — the Axis entry is the single
#: definition site of each knob's physics; the evaluators below only
#: apply them at the fixed term-group sites (dynamic / static / fom)
_VDD_HOOKS = AXIS_BY_NAME["vdd_scale"].coeff_hook
_ADC_HOOK = AXIS_BY_NAME["adc_bits"].coeff_hook["fom"]
_ADC_REF_COL = AXIS_BY_NAME["adc_bits"].coeff_cols[0]      # "fom_bits"


def _hooks_active(points: "DesignPoints") -> bool:
    """Whether a batch leaves the coefficient-hook defaults.

    Decided BEFORE dispatch so the per-plan evaluator can specialize: a
    default-valued batch (``vdd_scale == 1``, ``adc_bits < 0``) compiles
    the exact pre-hook graph and pays zero arithmetic for the knobs.
    Reads the point arrays back to host — sweep drivers that know their
    grids should decide ONCE via :func:`grid_hooks_active` and thread
    the flag down instead of paying this per chunk.
    """
    return bool(np.any(np.asarray(points.vdd_scale) != 1.0)
                or np.any(np.asarray(points.adc_bits) >= 0))


def grid_hooks_active(grids: Dict[str, Sequence]) -> bool:
    """Sweep-level hook decision from a (host) grids dict.

    True iff any coefficient-hook axis leaves its default anywhere in
    the grid; unswept hook axes fill their literal registry defaults
    (``vdd_scale = 1``, ``adc_bits = -1``), so absence means inactive.
    """
    v = np.asarray(grids.get("vdd_scale", 1.0), np.float64)
    a = np.asarray(grids.get("adc_bits", ADC_DECLARED), np.float64)
    return bool(np.any(v != 1.0) or np.any(a >= 0.0))


def point_defaults(plan: EnergyPlan) -> Dict[str, float]:
    """Per-axis default values: what the structure was built with.

    Derived from the axis registry (``repro.core.axes.AXES_SPEC``) —
    ``make_points`` and the sweep front doors all fill unswept axes from
    here, so a sweep over a subset of axes stays parity-exact with the
    scalar oracle on the others.
    """
    return {a.name: axis_default(a, plan) for a in AXES_SPEC}


def make_points(plan: EnergyPlan, n: Optional[int] = None,
                **axes: Sequence) -> DesignPoints:
    """Broadcast per-axis values against :func:`point_defaults`."""
    defaults = point_defaults(plan)
    unknown = set(axes) - set(defaults)
    if unknown:
        raise KeyError(f"unknown sweep axes {sorted(unknown)}; "
                       f"valid: {sorted(defaults)}")
    if n is None:
        n = max([np.size(v) for v in axes.values()] or [1])
    out = {}
    for name, dflt in defaults.items():
        v = np.asarray(axes.get(name, dflt), np.float64)
        v = np.broadcast_to(np.atleast_1d(v), (n,))
        dt = jnp.int32 if AXIS_BY_NAME[name].integer else jnp.float32
        out[name] = jnp.asarray(v.astype(np.float64), dt)
    return DesignPoints(**out)


def points_from_axis_rows(vals: Sequence) -> DesignPoints:
    """``DesignPoints`` from decoded per-axis value rows in AXES order.

    The streaming shard bodies feed the on-device decoder's ``(n_axes,
    B)`` output here; integer-coded axes (``mem_tech``) are cast per the
    axis registry, so new axes never need hand-edited construction sites.
    """
    assert len(vals) == len(AXES_SPEC), (len(vals), AXES)
    return DesignPoints(*(v.astype(jnp.int32) if spec.integer else v
                          for spec, v in zip(AXES_SPEC, vals)))


# ---------------------------------------------------------------------------
# Vectorized technology tables
# ---------------------------------------------------------------------------
def _log_interp_const(table: dict):
    nodes, vals = table_points(table)
    return (jnp.asarray(nodes, jnp.float32),
            jnp.asarray([math.log(v) for v in vals], jnp.float32))


def _interp_table(node, nodes, log_vals):
    """Geometric interpolation over process nodes (== constants._lookup_scale)."""
    return jnp.exp(jnp.interp(node, nodes, log_vals))


def _walden_fom(rate):
    log_r, log_e = fom_table_points()
    return 10.0 ** jnp.interp(jnp.log10(rate),
                              jnp.asarray(log_r, jnp.float32),
                              jnp.asarray(log_e, jnp.float32))


# ---------------------------------------------------------------------------
# Per-plan evaluator construction
# ---------------------------------------------------------------------------
def _build_eval(plan: EnergyPlan):
    f32 = lambda x: jnp.asarray(np.asarray(x), jnp.float32)  # noqa: E731
    A = len(plan.a_const)
    D = len(plan.d_is_sys)
    M = len(plan.m_reads_fixed)

    a_const, a_padc, a_ops = map(f32, (plan.a_const, plan.a_pad_coeff,
                                       plan.a_ops))
    lin_coeff, lin_inv = f32(plan.lin_coeff), f32(plan.lin_inv_div)
    fom_scale, fom_inv = f32(plan.fom_scale), f32(plan.fom_inv_div)
    fom_bits = f32(plan.fom_bits)
    lin_arr = jnp.asarray(plan.lin_arr, jnp.int32)
    fom_arr = jnp.asarray(plan.fom_arr, jnp.int32)

    dyn_nodes, dyn_logv = _log_interp_const(DYNAMIC_ENERGY_SCALE)
    leak_nodes, leak_logv = _log_interp_const(SRAM_LEAKAGE_PER_BIT)
    hp_nodes, hp_logv = _log_interp_const(SRAM_HP_LEAKAGE_PER_BIT)

    m_tech_declared = jnp.asarray(plan.m_tech, jnp.int32)
    m_role = jnp.asarray(plan.m_role, jnp.int32)
    m_area_role = jnp.asarray(plan.m_area_role, jnp.int32)
    m_node_decl = f32(plan.m_declared_node)
    d_role = jnp.asarray(plan.d_role, jnp.int32)
    d_node_decl = f32(plan.d_declared_node)

    def node_for(role, declared, cis, soc):
        return jnp.where(role == 0, cis, jnp.where(role == 1, soc, declared))

    def eval_one(pt: DesignPoints, hooks: bool):
        frame_time = 1.0 / pt.frame_rate
        # axis-registry coefficient hooks; `hooks` is STATIC — default-
        # valued batches (see _hooks_active) compile the hook-free graph
        if hooks:
            dyn_v = _VDD_HOOKS["dynamic"](pt.vdd_scale)
            stat_v = _VDD_HOOKS["static"](pt.vdd_scale)

        def hdyn(x):
            return x * dyn_v if hooks else x

        def hstat(x):
            return x * stat_v if hooks else x

        # ----- Sec. 4.1: digital timing, unrolled over the (tiny) DAG -----
        durs = []
        for i in range(D):
            if plan.d_is_sys[i]:
                thr = pt.sys_rows * pt.sys_cols * plan.d_util[i]
                cycles = (jnp.ceil(plan.d_macs[i] / thr)
                          + pt.sys_rows + pt.sys_cols)
            else:
                cycles = jnp.float32(plan.d_cycles_fixed[i])
            durs.append(cycles / plan.d_clock_hz[i])
        starts, ends = [], []
        for i in range(D):
            s_i = jnp.float32(0.0)
            for j in range(i):
                if plan.d_edge_mask[i, j]:
                    s_i = jnp.maximum(
                        s_i, starts[j] + plan.d_edge_w[i, j] * durs[j])
            starts.append(s_i)
            ends.append(s_i + durs[i])
        if D:
            t_d = (jnp.max(jnp.stack(ends))
                   - jnp.min(jnp.stack(starts)))
        else:
            t_d = jnp.float32(0.0)
        t_a = (frame_time - t_d) / plan.n_phases
        feasible = t_a > 0.0

        rows = []

        # ----- analog rows (Eqs. 2-13) ------------------------------------
        if A:
            pad = t_a * a_padc                       # per-access delay
            e_access = hdyn(a_const)
            if len(plan.lin_arr):
                t_cell = jnp.maximum(pad[lin_arr] * lin_inv, 1e-12)
                e_access = e_access + jnp.zeros(A, jnp.float32).at[
                    lin_arr].add(hstat(lin_coeff * t_cell))
            if len(plan.fom_arr):
                t_cell = jnp.maximum(pad[fom_arr] * fom_inv, 1e-12)
                fom = _walden_fom(1.0 / t_cell)
                if hooks:
                    fom = fom * _ADC_HOOK(pt.adc_bits, fom_bits)
                e_access = e_access + jnp.zeros(A, jnp.float32).at[
                    fom_arr].add(hdyn(fom_scale * fom))
            rows.append(e_access * a_ops)

        # ----- digital compute rows (Eqs. 14-15) --------------------------
        if D:
            node_u = node_for(d_role, d_node_decl, pt.cis_node, pt.soc_node)
            s_u = _interp_table(node_u, dyn_nodes, dyn_logv)
            dyn = f32(plan.d_dyn_coeff) * s_u
            # systolic dynamic energy is per-MAC (dims don't change it);
            # static power integrates over the (dims-dependent) runtime
            rows.append(hdyn(dyn)
                        + hstat(f32(plan.d_static_power)
                                * jnp.stack(durs)))

        # ----- memory rows (Eq. 16) ---------------------------------------
        if M:
            node_m = node_for(m_role, m_node_decl, pt.cis_node, pt.soc_node)
            s_m = _interp_table(node_m, dyn_nodes, dyn_logv)
            tech = jnp.where(pt.mem_tech >= 0,
                             jnp.full((M,), pt.mem_tech, jnp.int32),
                             m_tech_declared)
            is_stt = tech == 2
            bits = f32(plan.m_bits_per_access)
            sram_access = (SRAM_ACCESS_ENERGY_PER_BIT_65 * bits
                           * f32(plan.m_size_factor)) * s_m
            read_e = jnp.where(is_stt,
                               STT_READ_ENERGY_PER_BIT_65 * bits * s_m,
                               sram_access)
            write_e = jnp.where(is_stt,
                                STT_WRITE_ENERGY_PER_BIT_65 * bits * s_m,
                                sram_access)
            read_e = jnp.where(jnp.isnan(f32(plan.m_read_explicit)),
                               read_e, f32(plan.m_read_explicit))
            write_e = jnp.where(jnp.isnan(f32(plan.m_write_explicit)),
                                write_e, f32(plan.m_write_explicit))
            leak_bit = jnp.where(
                is_stt, jnp.float32(STT_LEAKAGE_PER_BIT),
                jnp.where(tech == 1,
                          _interp_table(node_m, hp_nodes, hp_logv),
                          _interp_table(node_m, leak_nodes, leak_logv)))
            leak = leak_bit * f32(plan.m_bits_total)
            leak = jnp.where(jnp.isnan(f32(plan.m_leak_explicit)),
                             leak, f32(plan.m_leak_explicit))
            reads = (f32(plan.m_reads_fixed)
                     + f32(plan.m_reads_dnn2) / jnp.maximum(pt.sys_rows, 1.0))
            alpha = f32(plan.m_alpha) * pt.active_fraction_scale
            rows.append(hdyn(read_e * reads + write_e * f32(plan.m_writes))
                        + hstat(leak * frame_time * alpha))

        # ----- communication rows (Eq. 17) --------------------------------
        comm = []
        if plan.utsv_bytes:
            comm.append(plan.utsv_bytes * UTSV_ENERGY_PER_BYTE)
        comm.append(plan.mipi_bytes * MIPI_CSI2_ENERGY_PER_BYTE)
        rows.append(jnp.asarray(comm, jnp.float32))

        unit_e = jnp.concatenate(rows) if rows else jnp.zeros((0,))

        # ----- Sec. 6.2 power density -------------------------------------
        analog_area = plan.n_pixels * (pt.pixel_pitch_um * 1e-3) ** 2
        if M:
            node_area = node_for(m_area_role, m_node_decl,
                                 pt.cis_node, pt.soc_node)
            cell_area = 150.0 * (node_area * 1e-6) ** 2
            digital_area = jnp.sum(f32(plan.m_bits_total) * cell_area)
        else:
            digital_area = jnp.float32(0.0)
        if plan.stacked:
            area = jnp.maximum(analog_area, digital_area)
        else:
            area = analog_area + digital_area

        return dict(unit_e=unit_e, t_d=t_d, t_a=t_a, feasible=feasible,
                    area_mm2=area)

    onehot = jnp.asarray(plan.category_onehot())
    on_mask = jnp.asarray(plan.unit_on_sensor)[:, None]
    ones = jnp.ones((plan.num_units, 1), jnp.float32)
    # [C category columns | total | on-sensor total] in one Pallas reduce
    weights = jnp.concatenate([onehot, ones, on_mask], axis=1)

    def eval_batch(points: DesignPoints, keep_unit_energies: bool = False,
                   hooks: bool = False):
        per = jax.vmap(lambda pt: eval_one(pt, hooks))(points)
        red = category_reduce(per["unit_e"], weights)
        n_c = len(CATEGORIES)
        out = {f"cat_{c}_j": red[:, i] for i, c in enumerate(CATEGORIES)}
        out["total_j"] = red[:, n_c]
        out["on_sensor_j"] = red[:, n_c + 1]
        out["t_d_s"] = per["t_d"]
        out["t_a_s"] = per["t_a"]
        out["feasible"] = per["feasible"]
        out["area_mm2"] = per["area_mm2"]
        out["power_mw"] = out["on_sensor_j"] * points.frame_rate * 1e3
        out["density_mw_mm2"] = out["power_mw"] / jnp.maximum(
            per["area_mm2"], 1e-9)
        # gated on a STATIC flag: in the default path the B x U matrix is
        # never an output, so XLA dead-code-eliminates the concatenated
        # per-unit rows and nothing B x U is ever transferred to host
        if keep_unit_energies:
            out["unit_e"] = per["unit_e"]
        return out

    return jax.jit(eval_batch,
                   static_argnames=("keep_unit_energies", "hooks"))


# ---------------------------------------------------------------------------
# Banked (multi-variant) evaluator: PlanBank coefficients as traced inputs
# ---------------------------------------------------------------------------
def row_getter(row, layout):
    """``name -> coefficient view`` accessor into one fused bank row.

    Shared by the vmap-ed banked evaluator (``row`` is a traced (W,)
    slice) and the fused megakernel body (``row`` is a (W,) VMEM load) —
    the single place that interprets :func:`plan_bank.bank_layout`.
    """
    def g(name):
        off, shape = layout[name]
        if not shape:
            return row[off]
        size = int(np.prod(shape))
        v = row[off:off + size]
        return v.reshape(shape) if len(shape) > 1 else v
    return g


def build_banked_eval(dims):
    """Evaluator ``(bank_arrays, variant_ids, points) -> outputs`` whose
    coefficients are ARGUMENTS, not baked constants.

    Shape-specialized on :class:`repro.core.plan_bank.BankDims` only: one
    XLA executable serves every structural variant / algorithm stacked in
    the bank, so the mega-sweep compiles once per chunk shape total.
    Returns ``(eval_bank, eval_bank_uniform)``:

    * ``eval_bank(bank, variant_ids, points)`` — fully mixed batches;
      each point gathers its variant's fused coefficient row
      (``plan_bank.bank_layout``) — O(B x W) gather traffic, the
      flexible path;
    * ``eval_bank_uniform(bank, variant_id, points)`` — one traced
      variant INDEX for the whole batch; the coefficient row is a single
      dynamic slice broadcast across points, so per-point traffic is
      zero, matching the baked-constant evaluator's speed.  The
      streaming driver aligns chunks to variant boundaries exactly so it
      can ride this path.

    The physics is the same Eqs. 1-17 arithmetic as the per-plan
    evaluator with padded slots arranged to contribute exact zeros; the
    per-category sum runs as a matvec against the row's ``(U, C+2)``
    weight slab (the per-plan path keeps the shared-weight Pallas
    ``category_reduce``).
    """
    from .plan_bank import bank_layout
    V, A, L, F, D, M = dims
    n_c = len(CATEGORIES)
    layout = bank_layout(dims)

    dyn_nodes, dyn_logv = _log_interp_const(DYNAMIC_ENERGY_SCALE)
    leak_nodes, leak_logv = _log_interp_const(SRAM_LEAKAGE_PER_BIT)
    hp_nodes, hp_logv = _log_interp_const(SRAM_HP_LEAKAGE_PER_BIT)

    def node_for(role, declared, cis, soc):
        # roles ride the fused row as exact small floats
        return jnp.where(role == 0, cis, jnp.where(role == 1, soc, declared))

    def eval_one(row, pt: DesignPoints):
        g = row_getter(row, layout)
        frame_time = 1.0 / pt.frame_rate
        # axis-registry coefficient hooks: the per-variant reference data
        # (fom_bits) rides the bank row, so these axes are traced inputs
        # end to end — zero new executables per swept value
        dyn_v = _VDD_HOOKS["dynamic"](pt.vdd_scale)
        stat_v = _VDD_HOOKS["static"](pt.vdd_scale)

        # ----- Sec. 4.1 digital timing, data-driven over padded slots -----
        if D:
            thr = pt.sys_rows * pt.sys_cols * g("d_util")
            cycles = jnp.where(g("d_is_sys") > 0.5,
                               jnp.ceil(g("d_macs") / thr)
                               + pt.sys_rows + pt.sys_cols,
                               g("d_cycles"))
            durs = cycles / g("d_clock")
            edge_w = g("d_edge_w")
            edge_m = g("d_edge_mask") > 0.5
            starts = jnp.zeros((D,), jnp.float32)
            for i in range(D):        # static unroll; masks stay traced
                s_i = jnp.max(jnp.where(edge_m[i],
                                        starts + edge_w[i] * durs, 0.0))
                starts = starts.at[i].set(s_i)
            ends = starts + durs
            dv = g("d_valid") > 0.5
            t_d = (jnp.max(jnp.where(dv, ends, -jnp.inf))
                   - jnp.min(jnp.where(dv, starts, jnp.inf)))
            t_d = jnp.where(jnp.any(dv), t_d, 0.0)
        else:
            t_d = jnp.float32(0.0)
        t_a = (frame_time - t_d) / g("n_phases")
        feasible = t_a > 0.0

        rows = []

        # ----- analog rows (Eqs. 2-13) ------------------------------------
        if A:
            pad = t_a * g("a_pad_coeff")
            e_access = g("a_const") * dyn_v
            if L:
                la = g("lin_arr").astype(jnp.int32)
                t_cell = jnp.maximum(pad[la] * g("lin_inv"), 1e-12)
                e_access = e_access + jnp.zeros((A,), jnp.float32).at[
                    la].add(g("lin_coeff") * t_cell * stat_v)
            if F:
                fa = g("fom_arr").astype(jnp.int32)
                t_cell = jnp.maximum(pad[fa] * g("fom_inv"), 1e-12)
                fom = _walden_fom(1.0 / t_cell)
                fom = fom * _ADC_HOOK(pt.adc_bits, g(_ADC_REF_COL))
                e_access = e_access + jnp.zeros((A,), jnp.float32).at[
                    fa].add(g("fom_scale") * fom * dyn_v)
            rows.append(e_access * g("a_ops"))

        # ----- digital compute rows (Eqs. 14-15) --------------------------
        if D:
            node_u = node_for(g("d_role"), g("d_node"),
                              pt.cis_node, pt.soc_node)
            s_u = _interp_table(node_u, dyn_nodes, dyn_logv)
            rows.append(g("d_dyn") * s_u * dyn_v
                        + g("d_static") * durs * stat_v)

        # ----- memory rows (Eq. 16) ---------------------------------------
        if M:
            node_m = node_for(g("m_role"), g("m_node"),
                              pt.cis_node, pt.soc_node)
            s_m = _interp_table(node_m, dyn_nodes, dyn_logv)
            tech = jnp.where(pt.mem_tech >= 0,
                             pt.mem_tech.astype(jnp.float32), g("m_tech"))
            is_stt = tech == 2
            bits = g("m_bits_pa")
            sram_access = (SRAM_ACCESS_ENERGY_PER_BIT_65 * bits
                           * g("m_size_f")) * s_m
            read_e = jnp.where(is_stt,
                               STT_READ_ENERGY_PER_BIT_65 * bits * s_m,
                               sram_access)
            write_e = jnp.where(is_stt,
                                STT_WRITE_ENERGY_PER_BIT_65 * bits * s_m,
                                sram_access)
            read_e = jnp.where(jnp.isnan(g("m_read_x")),
                               read_e, g("m_read_x"))
            write_e = jnp.where(jnp.isnan(g("m_write_x")),
                                write_e, g("m_write_x"))
            leak_bit = jnp.where(
                is_stt, jnp.float32(STT_LEAKAGE_PER_BIT),
                jnp.where(tech == 1,
                          _interp_table(node_m, hp_nodes, hp_logv),
                          _interp_table(node_m, leak_nodes, leak_logv)))
            leak = leak_bit * g("m_bits_total")
            leak = jnp.where(jnp.isnan(g("m_leak_x")),
                             leak, g("m_leak_x"))
            reads = (g("m_reads_fixed")
                     + g("m_reads_dnn2") / jnp.maximum(pt.sys_rows, 1.0))
            alpha = g("m_alpha") * pt.active_fraction_scale
            rows.append((read_e * reads + write_e * g("m_writes")) * dyn_v
                        + leak * frame_time * alpha * stat_v)

        # ----- communication rows (Eq. 17, fixed utsv+mipi slots) ---------
        rows.append(jnp.stack([
            g("utsv_bytes") * UTSV_ENERGY_PER_BYTE,
            g("mipi_bytes") * MIPI_CSI2_ENERGY_PER_BYTE]))
        unit_e = jnp.concatenate(rows)
        red = jnp.dot(unit_e, g("weights"), precision=_EXACT)

        # ----- Sec. 6.2 power density -------------------------------------
        analog_area = g("n_pixels") * (pt.pixel_pitch_um * 1e-3) ** 2
        if M:
            node_area = node_for(g("m_area_role"), g("m_node"),
                                 pt.cis_node, pt.soc_node)
            cell_area = 150.0 * (node_area * 1e-6) ** 2
            digital_area = jnp.sum(g("m_bits_total") * cell_area)
        else:
            digital_area = jnp.float32(0.0)
        area = jnp.where(g("stacked") > 0,
                         jnp.maximum(analog_area, digital_area),
                         analog_area + digital_area)

        return dict(red=red, t_d=t_d, t_a=t_a, feasible=feasible,
                    area_mm2=area)

    def _outputs(per, points):
        red = per["red"]
        out = {f"cat_{c}_j": red[:, i] for i, c in enumerate(CATEGORIES)}
        out["total_j"] = red[:, n_c]
        out["on_sensor_j"] = red[:, n_c + 1]
        out["t_d_s"] = per["t_d"]
        out["t_a_s"] = per["t_a"]
        out["feasible"] = per["feasible"]
        out["area_mm2"] = per["area_mm2"]
        out["power_mw"] = out["on_sensor_j"] * points.frame_rate * 1e3
        out["density_mw_mm2"] = out["power_mw"] / jnp.maximum(
            per["area_mm2"], 1e-9)
        # trace-time guard: the streaming path relies on OUT_KEYS being
        # exactly this schema — catch drift when a new output is added
        assert set(out) == set(OUT_KEYS), (sorted(out), OUT_KEYS)
        return out

    def eval_bank(bank, variant_ids, points: DesignPoints):
        per = jax.vmap(lambda v, pt: eval_one(bank["fused"][v], pt)
                       )(variant_ids, points)
        return _outputs(per, points)

    def eval_bank_uniform(bank, variant_id, points: DesignPoints):
        row = bank["fused"][variant_id]          # one slice, broadcast
        per = jax.vmap(lambda pt: eval_one(row, pt))(points)
        return _outputs(per, points)

    return eval_bank, eval_bank_uniform


# ---------------------------------------------------------------------------
# Coefficient-form block compute: the fused megakernel's physics
# ---------------------------------------------------------------------------
def _static_log_points(table):
    """Per-node ``(nodes, log(values))`` as static Python f32 floats."""
    nodes, vals = table_points(table)
    return ([np.float32(n) for n in nodes],
            [np.float32(math.log(v)) for v in vals])


def _piecewise_interp(x, xs, ys):
    """Branchless clamped piecewise-linear interpolation, static knots.

    Semantics of ``jnp.interp`` (endpoint clamping included) expressed as
    a static unroll of compares + the very same per-segment ``ys[i] +
    (delta / dx) * dy`` arithmetic, over Python-float knots — a Pallas
    kernel body may not capture array constants, and the unroll also
    needs no gather/searchsorted lowering on the compiled Mosaic path.
    Inside a shared segment the result is bit-identical to
    ``jnp.interp``; only an ``x`` landing exactly on the LAST knot can
    differ by one ulp (clamp vs computed endpoint).
    """
    y = jnp.full_like(x, ys[0])
    for i in range(len(xs) - 1):
        t = (x - xs[i]) / (xs[i + 1] - xs[i])
        seg = ys[i] + t * (ys[i + 1] - ys[i])
        y = jax.lax.select((x >= xs[i]) & (x < xs[i + 1]), seg, y)
    return jnp.where(x >= xs[-1], ys[-1], y)


def _make_scale_interp(table):
    """Geometric node-scaling lookup usable inside a Pallas kernel body."""
    xs, ys = _static_log_points(table)
    return lambda x: jnp.exp(_piecewise_interp(x, xs, ys))


def _make_fom_interp():
    """Walden-FoM lookup (log-log interpolation over the survey table)."""
    log_r, log_e = fom_table_points()
    xs = [np.float32(v) for v in log_r]
    ys = [np.float32(v) for v in log_e]
    return lambda rate: 10.0 ** _piecewise_interp(jnp.log10(rate), xs, ys)


def _pick_slot(slots, idx):
    """``slots[idx]`` for a traced scalar slot index (an exact small
    float): a select chain over the static slots, which copies the chosen
    value exactly."""
    out = slots[0]
    for a in range(1, len(slots)):
        out = jnp.where(idx == a, slots[a], out)
    return out


def _scatter_add_slots(slots, idx, terms):
    """``slots[a] + (the sum of the terms whose slot index is a)`` for
    every slot ``a``, the terms summed in their order: the arithmetic of
    a scatter-add into zeros, as masked sums over the static slots."""
    out = []
    for a, base in enumerate(slots):
        acc = None
        for i, term in zip(idx, terms):
            t = jnp.where(i == a, term, 0.0)
            acc = t if acc is None else acc + t
        out.append(base + acc)
    return out


def _over_slots(fn, slots):
    """``[fn(s) for s in slots]`` for an elementwise ``fn``, traced once:
    ``fn`` runs on the slots stacked along a new leading axis, so each
    slot stays a whole tile and each point's arithmetic is unchanged."""
    if not slots:
        return []
    out = fn(jnp.stack(slots))
    return [out[i] for i in range(len(slots))]


def build_coeff_compute(dims):
    """The banked Eqs. 1-17 physics as ONE point-vectorized function
    callable from inside a Pallas kernel body.

    Returns ``compute(row, pt, keys=OUT_KEYS) -> {key: array of the
    points' shape}`` for the output ``keys`` asked for; a category row
    that none of them needs is not traced.  ``row`` is a variant's fused ``(W,)`` coefficient row
    (``plan_bank.bank_layout``), anything that a static int indexes to
    an f32 scalar: an array, or the kernel's SMEM ref.  ``pt`` maps every
    :data:`repro.core.sweep.AXES` name to an f32 array of one shape, any
    shape (``mem_tech`` as its numeric code): ``(B,)`` in the XLA twin,
    a dense ``(8, B/8)`` tile in the megakernel.  Every slot (analog,
    digital, memory, unit, category row) is its own value of that shape
    in a static Python list and every coefficient a scalar, so
    cross-slot reductions are elementwise folds and a traced slot index
    picks among the slots by selects.  Unlike the vmap-ed
    :func:`build_banked_eval` path there is no batching transform and no
    gather, so the whole computation stays legal inside a kernel: the
    fused mega-sweep kernel (``repro.kernels.fused_sweep``) evaluates a
    block of decoded points without the ``(n_axes, B)`` point matrix or
    the ``B x n_out`` output table ever reaching HBM.

    Each point runs the banked evaluator's arithmetic in its order, so
    outputs match it to f32 elementwise roundoff (the node interpolation
    is a static piecewise unroll, see :func:`_piecewise_interp`).  The
    output schema is exactly :data:`OUT_KEYS`.
    """
    from .plan_bank import bank_layout
    V, A, L, F, D, M = dims
    n_c = len(CATEGORIES)
    n_w = n_c + 2
    layout = bank_layout(dims)

    dyn_scale = _make_scale_interp(DYNAMIC_ENERGY_SCALE)
    leak_scale = _make_scale_interp(SRAM_LEAKAGE_PER_BIT)
    hp_scale = _make_scale_interp(SRAM_HP_LEAKAGE_PER_BIT)
    walden = _make_fom_interp()

    def compute(row, pt, keys=OUT_KEYS):
        def g(name, i=0):
            # entry i of a coefficient slot (row-major), as a scalar
            return row[layout[name][0] + i]

        shape = pt["frame_rate"].shape
        cis, soc = pt["cis_node"], pt["soc_node"]

        def node_for(role, declared):
            return jnp.where(role == 0, cis,
                             jnp.where(role == 1, soc, declared))

        frame_time = 1.0 / pt["frame_rate"]
        # axis-registry coefficient hooks; same arithmetic order as the
        # vmap evaluators
        dyn_v = _VDD_HOOKS["dynamic"](pt["vdd_scale"])
        stat_v = _VDD_HOOKS["static"](pt["vdd_scale"])

        # ----- Sec. 4.1 digital timing over padded slots ------------------
        if D:
            sq = pt["sys_rows"] * pt["sys_cols"]
            fill = pt["sys_rows"] + pt["sys_cols"]
            durs = []
            for i in range(D):
                cycles = jnp.where(
                    g("d_is_sys", i) > 0.5,
                    jnp.ceil(g("d_macs", i) / (sq * g("d_util", i)))
                    + fill, g("d_cycles", i))
                durs.append(cycles / g("d_clock", i))
            starts = []
            for i in range(D):      # static unroll; DAG edges go backward
                s_i = jnp.zeros(shape, jnp.float32)
                for j in range(i):
                    s_i = jnp.maximum(s_i, jnp.where(
                        g("d_edge_mask", i * D + j) > 0.5,
                        starts[j] + g("d_edge_w", i * D + j) * durs[j],
                        0.0))
                starts.append(s_i)
            last = jnp.full(shape, -jnp.inf, jnp.float32)
            first = jnp.full(shape, jnp.inf, jnp.float32)
            for i in range(D):
                dv = g("d_valid", i) > 0.5
                last = jnp.maximum(last, jnp.where(
                    dv, starts[i] + durs[i], -jnp.inf))
                first = jnp.minimum(first, jnp.where(dv, starts[i],
                                                     jnp.inf))
                any_dv = dv if i == 0 else any_dv | dv
            t_d = jnp.where(any_dv, last - first, 0.0)
        else:
            t_d = jnp.zeros(shape, jnp.float32)
        t_a = (frame_time - t_d) / g("n_phases")
        feasible = t_a > 0.0

        units = []

        # ----- analog rows (Eqs. 2-13) ------------------------------------
        if A:
            pad = [t_a * g("a_pad_coeff", a) for a in range(A)]
            e_access = [g("a_const", a) * dyn_v for a in range(A)]
            if L:
                la = [g("lin_arr", i) for i in range(L)]
                e_access = _scatter_add_slots(e_access, la, [
                    g("lin_coeff", i) * jnp.maximum(
                        _pick_slot(pad, la[i]) * g("lin_inv", i), 1e-12)
                    * stat_v for i in range(L)])
            if F:
                fa = [g("fom_arr", i) for i in range(F)]
                rate = [1.0 / jnp.maximum(
                    _pick_slot(pad, fa[i]) * g("fom_inv", i), 1e-12)
                    for i in range(F)]
                terms = []
                for i, fom in enumerate(_over_slots(walden, rate)):
                    fom = fom * _ADC_HOOK(pt["adc_bits"],
                                          g(_ADC_REF_COL, i))
                    terms.append(g("fom_scale", i) * fom * dyn_v)
                e_access = _scatter_add_slots(e_access, fa, terms)
            units += [e_access[a] * g("a_ops", a) for a in range(A)]

        # ----- digital compute rows (Eqs. 14-15) --------------------------
        # every slot's node scaling in one pass over the stacked nodes
        node_m = [node_for(g("m_role", m), g("m_node", m)) for m in range(M)]
        scale = _over_slots(dyn_scale, [
            node_for(g("d_role", i), g("d_node", i)) for i in range(D)]
            + node_m)
        for i in range(D):
            units.append(g("d_dyn", i) * scale[i] * dyn_v
                         + g("d_static", i) * durs[i] * stat_v)

        # ----- memory rows (Eq. 16) ---------------------------------------
        mt = pt["mem_tech"].astype(jnp.float32)
        reads_div = jnp.maximum(pt["sys_rows"], 1.0)
        hp_m = _over_slots(hp_scale, node_m)
        leak_m = _over_slots(leak_scale, node_m)
        for m in range(M):
            s_m = scale[D + m]
            tech = jnp.where(mt >= 0, mt, g("m_tech", m))
            is_stt = tech == 2
            bits = g("m_bits_pa", m)
            sram_access = (SRAM_ACCESS_ENERGY_PER_BIT_65 * bits
                           * g("m_size_f", m)) * s_m
            read_e = jnp.where(is_stt,
                               STT_READ_ENERGY_PER_BIT_65 * bits * s_m,
                               sram_access)
            write_e = jnp.where(is_stt,
                                STT_WRITE_ENERGY_PER_BIT_65 * bits * s_m,
                                sram_access)
            read_x, write_x = g("m_read_x", m), g("m_write_x", m)
            read_e = jnp.where(jnp.isnan(read_x), read_e, read_x)
            write_e = jnp.where(jnp.isnan(write_x), write_e, write_x)
            leak_bit = jnp.where(
                is_stt, jnp.float32(STT_LEAKAGE_PER_BIT),
                jnp.where(tech == 1, hp_m[m], leak_m[m]))
            leak = leak_bit * g("m_bits_total", m)
            leak_x = g("m_leak_x", m)
            leak = jnp.where(jnp.isnan(leak_x), leak, leak_x)
            reads = g("m_reads_fixed", m) + g("m_reads_dnn2", m) / reads_div
            alpha = g("m_alpha", m) * pt["active_fraction_scale"]
            units.append((read_e * reads + write_e * g("m_writes", m))
                         * dyn_v + leak * frame_time * alpha * stat_v)

        # ----- communication rows (Eq. 17) --------------------------------
        units.append(jnp.broadcast_to(
            g("utsv_bytes") * UTSV_ENERGY_PER_BYTE, shape))
        units.append(jnp.broadcast_to(
            g("mipi_bytes") * MIPI_CSI2_ENERGY_PER_BYTE, shape))
        # category reduction: each of the C+2 rows accumulates one rank-1
        # update per unit, on the VPU in exact f32 (a TPU matmul at
        # default precision would round the energies to bf16); a row is
        # traced only when an output asks for it
        red = {}

        def category(c):
            if c not in red:
                acc = g("weights", c) * units[0]
                for u in range(1, len(units)):
                    acc = acc + g("weights", u * n_w + c) * units[u]
                red[c] = acc
            return red[c]

        # ----- Sec. 6.2 power density -------------------------------------
        analog_area = g("n_pixels") * (pt["pixel_pitch_um"] * 1e-3) ** 2
        digital_area = jnp.zeros(shape, jnp.float32)
        for m in range(M):
            node_area = node_for(g("m_area_role", m), g("m_node", m))
            cell_area = 150.0 * (node_area * 1e-6) ** 2
            term = g("m_bits_total", m) * cell_area
            digital_area = term if m == 0 else digital_area + term
        area = jnp.where(g("stacked") > 0,
                         jnp.maximum(analog_area, digital_area),
                         analog_area + digital_area)

        def power():
            return category(n_c + 1) * pt["frame_rate"] * 1e3

        make = {f"cat_{c}_j": functools.partial(category, i)
                for i, c in enumerate(CATEGORIES)}
        make.update(
            total_j=functools.partial(category, n_c),
            on_sensor_j=functools.partial(category, n_c + 1),
            t_d_s=lambda: t_d, t_a_s=lambda: t_a,
            feasible=lambda: feasible, area_mm2=lambda: area,
            power_mw=power,
            density_mw_mm2=lambda: power() / jnp.maximum(area, 1e-9))
        assert set(make) == set(OUT_KEYS), (sorted(make), OUT_KEYS)
        return {key: make[key]() for key in keys}

    return compute


#: the evaluators' output schema is fixed by construction — callers that
#: only need the key list (e.g. the streaming step builder) use this
#: instead of paying an abstract trace through jax.eval_shape
OUT_KEYS = tuple(sorted(
    [f"cat_{c}_j" for c in CATEGORIES]
    + ["total_j", "on_sensor_j", "t_d_s", "t_a_s", "feasible",
       "area_mm2", "power_mw", "density_mw_mm2"]))

_BANKED_JIT: Dict[tuple, object] = {}
_EXTRA_CACHES.append(_BANKED_JIT)       # flushed by lower_cache_clear()


def banked_eval_fn(dims):
    """Jitted mixed-variant :func:`build_banked_eval`, memoized on dims."""
    fn = _BANKED_JIT.get(tuple(dims))
    if fn is None:
        fn = _BANKED_JIT[tuple(dims)] = jax.jit(build_banked_eval(dims)[0])
    return fn


def eval_fn(plan: EnergyPlan):
    """The plan's jitted evaluator ``(points, keep_unit_energies=False)``.

    Built lazily once per plan; the ``keep_unit_energies`` flag is static,
    so each value compiles its own executable (the default one has no
    B x U leaf in its output pytree — asserted in tests/test_sweep.py).
    """
    if plan._eval_fn is None:
        plan._eval_fn = _build_eval(plan)
    return plan._eval_fn


def _compiled(plan: EnergyPlan, points: DesignPoints, keep: bool,
              hooks: Optional[bool] = None):
    """AOT-compiled executable for this (batch size, flags), with compile
    time measured separately from evaluation (satellite of ISSUE 2: the
    old path folded jit compilation into the sweep wall time).  The
    coefficient-hook flag is part of the key: default-valued batches run
    the hook-free executable.  ``hooks=None`` derives the flag from the
    point values (host readback); sweep drivers pass it explicitly."""
    if plan._exec_cache is None:
        plan._exec_cache = {}
    hooks = _hooks_active(points) if hooks is None else bool(hooks)
    key = (points.batch, keep, hooks)
    hit = plan._exec_cache.get(key)
    if hit is not None:
        return hit, 0.0
    with span("grid.compile") as sp:
        exe = eval_fn(plan).lower(points, keep_unit_energies=keep,
                                  hooks=hooks).compile()
    plan._exec_cache[key] = exe
    return exe, sp.seconds


def evaluate_batch(plan: EnergyPlan, points: DesignPoints,
                   keep_unit_energies: bool = False,
                   timings: Optional[Dict[str, float]] = None,
                   hooks: Optional[bool] = None
                   ) -> Dict[str, np.ndarray]:
    """Score a whole batch of design points in one device call.

    Returns numpy arrays keyed by output name; per-unit energies are
    computed and transferred only when requested (they are B x U and
    dominate transfer size — by default the flag is baked statically into
    the jitted evaluator so the array never exists on device either).

    ``timings``, if given, is accumulated into: ``compile_s`` (AOT
    lowering + XLA compilation, only on the first call per batch size)
    and ``eval_s`` (the actual device execution + host transfer).
    """
    exe, compile_s = _compiled(plan, points, bool(keep_unit_energies),
                               hooks)
    with span("grid.eval") as sp:
        out = exe(points)
        out = {k: np.asarray(v) for k, v in out.items()}
    if timings is not None:
        timings["compile_s"] = timings.get("compile_s", 0.0) + compile_s
        timings["eval_s"] = timings.get("eval_s", 0.0) + sp.seconds
    return out
