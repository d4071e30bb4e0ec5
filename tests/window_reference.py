"""A plain reference for windows of a design space's flat index space.

It shares nothing with the sweep engines but the scalar CamJ model
(``repro.core.sweep.scalar_point``, the ``estimate_energy`` walk):

* :func:`decode` turns global flat indices (int64, any size) into axis
  values with numpy: variant-major, then C order over :data:`AXES`;
* :func:`reduce_window` prices every point of a window (one or more
  flat ranges) with the scalar model and reduces it in float64 to its
  top-k (ascending by the metric, ties to the lower flat index) and,
  per variant, its point and feasible counts, metric minimum and mean,
  and the offset of the minimum inside the variant.

Grids name every swept axis explicitly, so no per-variant default enters
the decode.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

from repro.core.sweep import scalar_point
from repro.core.usecases.edgaze import EDGAZE_VARIANTS
from repro.core.usecases.rhythmic import RHYTHMIC_VARIANTS

#: the swept axes in flat-index order (vdd_scale and adc_bits, which
#: follow them, stay at their defaults)
AXES = ("cis_node", "soc_node", "mem_tech", "sys_rows", "sys_cols",
        "frame_rate", "active_fraction_scale", "pixel_pitch_um")
VARIANTS = {"edgaze": EDGAZE_VARIANTS, "rhythmic": RHYTHMIC_VARIANTS}


def variant_slots(algorithms: Sequence[str]) -> List[Tuple[str, str]]:
    """``(algorithm, variant)`` per slot, in flat order."""
    return [(a, v) for a in algorithms for v in VARIANTS[a]]


def n_var(grids: Dict[str, Sequence]) -> int:
    return int(np.prod([len(grids[a]) for a in AXES]))


def decode(flat, grids: Dict[str, Sequence]
           ) -> Tuple[np.ndarray, np.ndarray, Dict[str, List]]:
    """``(slot, local, values)`` of global flat indices ``flat``."""
    flat = np.asarray(flat, np.int64)
    slot, local = np.divmod(flat, n_var(grids))
    idx = np.unravel_index(local, tuple(len(grids[a]) for a in AXES))
    values = {a: [grids[a][i] for i in ix] for a, ix in zip(AXES, idx)}
    return slot, local, values


def reduce_window(algorithms: Sequence[str], grids: Dict[str, Sequence],
                  ranges: Sequence[Tuple[int, int]], *, metric: str,
                  k: int) -> Dict:
    """The top-k and per-variant summaries of the union of the disjoint
    flat ranges ``[lo, hi)`` in ``ranges`` (module doc).

    ``topk`` is ``[(flat, value)]``; ``summaries`` maps each slot the
    ranges touch to ``{n, n_feasible, metric_min, metric_mean,
    argmin_index}``."""
    slots = variant_slots(algorithms)
    flat = np.concatenate([np.arange(lo, hi, dtype=np.int64)
                           for lo, hi in sorted(ranges)])
    slot, local, values = decode(flat, grids)
    value = np.empty(len(flat), np.float64)
    feasible = np.empty(len(flat), bool)
    for j in range(len(flat)):
        algo, variant = slots[int(slot[j])]
        out = scalar_point(algo, variant,
                           **{a: values[a][j] for a in AXES})
        value[j], feasible[j] = out[metric], bool(out["feasible"])
    ok = np.flatnonzero(feasible)
    order = ok[np.lexsort((flat[ok], value[ok]))][:k]
    summaries = {}
    for s in np.unique(slot):
        at = slot == s
        good = at & feasible
        nf = int(good.sum())
        best = (int(local[good][np.argmin(value[good])]) if nf else -1)
        summaries[int(s)] = dict(
            n=int(at.sum()), n_feasible=nf,
            metric_min=float(value[good].min()) if nf else math.inf,
            metric_mean=float(value[good].mean()) if nf else math.nan,
            argmin_index=best)
    return dict(topk=[(int(flat[j]), float(value[j])) for j in order],
                summaries=summaries)
