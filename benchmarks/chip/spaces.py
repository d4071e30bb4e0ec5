"""Design spaces drawn from ``--seed``: the sweep cells' traffic.

A configuration file lists, per axis, either a fixed value set or a
lattice (``low``, ``high``, ``step``) and a length.  Every seed draws the
same lengths, so the work of a sweep does not depend on the seed, only
the values do.  Refinements keep a space's lengths and draw their values
inside a narrower interval of each drawn axis.
"""
from __future__ import annotations

import zlib
from typing import Dict, List, Optional

import numpy as np

#: the swept axes in the sweep's flat-index order
AXES = ("cis_node", "soc_node", "mem_tech", "sys_rows", "sys_cols",
        "frame_rate", "active_fraction_scale", "pixel_pitch_um")


def rng_for(seed: int, purpose: str, *more: int) -> np.random.Generator:
    """An independent stream for one use of ``seed``."""
    words = [abs(int(seed)) & 0xFFFFFFFF, abs(int(seed)) >> 32,
             zlib.crc32(purpose.encode())] + [int(m) for m in more]
    return np.random.default_rng(np.random.SeedSequence(words))


def _lattice(spec: Dict) -> np.ndarray:
    n = int(round((spec["high"] - spec["low"]) / spec["step"])) + 1
    return np.round(spec["low"] + spec["step"] * np.arange(n), 6)


def draw_axis(rng, spec: Dict, length: int,
              lo: Optional[float] = None,
              hi: Optional[float] = None) -> List:
    """``length`` distinct values of one axis, ascending."""
    if "values" in spec:
        vals = list(spec["values"])
        if len(vals) != length:
            raise ValueError(f"axis has {len(vals)} fixed values, the "
                             f"shape asks for {length}")
        return vals
    lat = _lattice(spec)
    if lo is not None:
        lat = lat[(lat >= lo) & (lat <= hi)]
    if len(lat) < length:
        raise ValueError(f"{len(lat)} lattice values for {length} draws")
    pick = np.sort(rng.choice(len(lat), size=length, replace=False))
    return [float(v) for v in lat[pick]]


def lengths(config: Dict, shape: Optional[str] = None) -> Dict[str, int]:
    if shape is not None:
        return dict(config["shapes"][shape])
    return {ax: (len(spec["values"]) if "values" in spec
                 else int(spec["length"]))
            for ax, spec in config["axes"].items()}


def draw_grids(config: Dict, rng, shape: Optional[str] = None) -> Dict:
    """One design space's grids (user-facing values, mem_tech names)."""
    lens = lengths(config, shape)
    return {ax: draw_axis(rng, config["axes"][ax], lens[ax])
            for ax in AXES}


def refine(config: Dict, grids: Dict, rng) -> Dict:
    """A refinement of ``grids``: same lengths, each drawn axis re-drawn
    inside a random sub-interval of its current span that still holds
    enough lattice values."""
    out = {}
    for ax in AXES:
        spec = config["axes"][ax]
        vals = grids[ax]
        if "values" in spec:
            out[ax] = list(vals)
            continue
        lat = _lattice(spec)
        lat = lat[(lat >= min(vals)) & (lat <= max(vals))]
        need = len(vals)
        width = int(rng.integers(need, len(lat) + 1))
        start = int(rng.integers(0, len(lat) - width + 1))
        out[ax] = draw_axis(rng, spec, need, lat[start],
                            lat[start + width - 1])
    return out


def n_points(grids: Dict, n_variants: int) -> int:
    return n_variants * int(np.prod([len(grids[ax]) for ax in AXES]))


def warm_range(n_var: int, n_variants: int, n_devices: int,
               chunk: int = 1 << 18, superchunk: int = 16):
    """The shortest leading ``index_range`` whose sweep compiles the same
    step executable as the whole space: it covers as many chunks as one
    superchunk dispatch of the whole sweep scans (the scan length is
    part of the executable's key).  Mirrors the streaming driver's chunk
    rounding: device-divisible, clamped to one variant's span."""
    chunk = -(-chunk // n_devices) * n_devices
    chunk = min(chunk, -(-n_var // n_devices) * n_devices)
    cpv = -(-n_var // chunk)
    n_chunks = cpv * n_variants
    last = min(n_chunks, superchunk) - 1
    vi, r = divmod(last, cpv)
    return 0, vi * n_var + min((r + 1) * chunk, n_var)
