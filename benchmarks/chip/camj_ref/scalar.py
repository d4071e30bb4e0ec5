"""One design point through the scalar CamJ model, in float64."""
from __future__ import annotations

from typing import Dict

from .digital import SystolicArray
from .energy import CATEGORIES, estimate_energy
from .plan import TECH_INDEX
from .usecases import ALGORITHMS

#: the output schema of a sweep row, in the order of ``OUT_KEYS``
OUT_KEYS = tuple(sorted([f"cat_{c}_j" for c in CATEGORIES]
                        + ["total_j", "on_sensor_j", "t_d_s", "t_a_s",
                           "feasible", "area_mm2", "power_mw",
                           "density_mw_mm2"]))


def scalar_point(algorithm: str, variant: str, *, cis_node: float,
                 soc_node: float, mem_tech: int, sys_rows: float,
                 sys_cols: float, frame_rate: float,
                 active_fraction_scale: float,
                 pixel_pitch_um: float) -> Dict[str, float]:
    """Build the variant at the point's nodes, patch the swept knobs onto
    its hardware, and price it with ``estimate_energy``."""
    build, variants = ALGORITHMS[algorithm]
    if variant not in variants:
        raise KeyError(f"{algorithm} has no variant {variant!r}")
    hw, stages, mapping, _meta = build(variant, cis_node=int(cis_node),
                                       soc_node=int(soc_node))
    hw.frame_rate = float(frame_rate)
    hw.pixel_pitch_um = float(pixel_pitch_um)
    for binding in hw.digital.values():
        if isinstance(binding.unit, SystolicArray):
            binding.unit.rows = int(sys_rows)
            binding.unit.cols = int(sys_cols)
    names = {v: k for k, v in TECH_INDEX.items()}
    for mem in hw.memories.values():
        mem.technology = names[int(mem_tech)]
        mem.active_fraction *= active_fraction_scale
    report = estimate_energy(hw, stages, mapping, strict=False)
    cats = report.by_category()
    out = {f"cat_{c}_j": cats.get(c, 0.0) for c in CATEGORIES}
    out["total_j"] = report.total()
    out["on_sensor_j"] = report.total(include_off_sensor=False)
    out["t_d_s"] = report.delay.digital_latency
    out["t_a_s"] = report.delay.analog_stage_delay
    out["feasible"] = float(report.delay.analog_stage_delay > 0)
    out["area_mm2"] = hw.total_area_mm2()
    out["power_mw"] = report.on_sensor_power(hw.frame_rate) * 1e3
    out["density_mw_mm2"] = out["power_mw"] / max(out["area_mm2"], 1e-9)
    return out
