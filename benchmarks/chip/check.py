"""The comparison that decides ``correct``.

A sweep's answer is its top-k rows and its per-variant summaries.  Each
answer the timed path produced is compared with the plain reference
(:mod:`camj_ref`): the whole space through the vectorised reference at
the configuration's precision, and every winner through the scalar
float64 model.  Each comparison gives one reading; a reading passes when
it is at or under its limit.  The readings:

* ``decode_bad``: winners whose axis values differ from the space's
  values at the reported index (exact);
* ``eval_gap``: widest gap of any output of any winner from the scalar
  model, over the larger of the scalar value and the median of that
  output over the winners;
* ``topk_gap``: widest relative gap between the k values ranked by the
  program and by the reference;
* ``topk_bad``: a top-k list of another length, or a rank whose winner
  differs from the reference's where the reference does not price the
  two alike within ``tie_rel`` (exact);
* ``n_bad``: variants whose point count differs (exact);
* ``min_gap`` / ``mean_gap``: widest relative gap of a variant's
  metric minimum / mean (the mean is over feasible points, so a
  feasible count that differs shows there);
* ``ref_gap``: the vectorised reference held to the scalar model: the
  widest gap, measured as ``eval_gap`` is, of any output of the
  vectorised reference from the scalar model, at design points of each
  variant drawn from the seed, at the reference's own top-k and at each
  variant's minimum.  The vectorised reference prices every point
  through the lowering (``camj_ref/plan.py``); this reading holds that
  lowering to the model that it lowers, in every check.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from camj_ref.scalar import OUT_KEYS, scalar_point
from camj_ref.vector import AXES, RefSweep, Reference, unravel
from spaces import rng_for

#: design points a run draws from its seed, over all the spaces it
#: compares whole, at which ``ref_gap`` holds the vectorised reference
#: to the scalar model (at least 32 a variant in each space)
REF_SAMPLE = 2048

def answer_of(result) -> Dict:
    """The parts of an ``ExploreResult`` that are compared, as plain
    data (the program's objects can be freed before the reference runs)."""
    return dict(
        n_points=int(result.n_points),
        topk=[dict(label=f"{r['algorithm']}/{r['variant']}",
                   index=int(r["index"]),
                   axes={a: float(r[a]) for a in AXES},
                   out={k: float(r[k]) for k in OUT_KEYS})
              for r in result.topk],
        summaries={label: {key: s[key] for key in
                           ("n", "n_feasible", "metric_min", "metric_mean")}
                   for label, s in result.summaries.items()})


def answer_of_reference(ref: Reference, sweep: RefSweep, grids) -> Dict:
    """A reference sweep dressed as the program's answer (the control)."""
    rows = []
    for slot, local, _v in sweep.topk:
        axes = unravel(local, grids)
        rows.append(dict(label=sweep.labels[slot], index=local, axes=axes,
                         out=ref.points(slot, [axes])[0]))
    return dict(n_points=sweep.n_var * len(sweep.labels), topk=rows,
                summaries={lab: {k: s[k] for k in ("n", "n_feasible",
                                                   "metric_min",
                                                   "metric_mean")}
                           for lab, s in sweep.summaries.items()})


def _rel(a: float, b: float) -> float:
    if a == b:
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-300)


def _scalar(label: str, axes: Dict[str, float]) -> Dict[str, float]:
    algo, variant = label.split("/")
    pt = dict(axes)
    pt["mem_tech"] = int(pt["mem_tech"])
    return scalar_point(algo, variant, **pt)


def widest_gap(got: List[Dict[str, float]],
               scal: List[Dict[str, float]]) -> float:
    """Widest gap of any output in ``got`` from the scalar model's
    ``scal`` at the same points, over the larger of the scalar value and
    the median of that output over the points."""
    worst = 0.0
    for key in OUT_KEYS:
        med = float(np.median([abs(s[key]) for s in scal])) if scal else 0.0
        for g, s in zip(got, scal):
            gap = abs(g[key] - s[key])
            if gap:
                worst = max(worst, gap / max(abs(s[key]), med, 1e-300))
    return worst


def compare_rows(answer: Dict, grids: Dict) -> Dict[str, float]:
    """The readings of an answer's winners alone (decode, evaluate)."""
    rd = {"decode_bad": 0, "eval_gap": 0.0}
    top = answer["topk"]
    for row in top:
        want = unravel(row["index"], grids)
        if any(row["axes"][a] != want[a] for a in AXES):
            rd["decode_bad"] += 1
    scal = [_scalar(row["label"], row["axes"]) for row in top]
    rd["eval_gap"] = widest_gap([row["out"] for row in top], scal)
    return rd


def compare_reference(ref: Reference, sweep: RefSweep, grids: Dict, rng,
                      per_variant: int) -> float:
    """``ref_gap`` of one space: the vectorised reference ``ref`` against
    the scalar model at ``per_variant`` points of each variant drawn from
    ``rng``, at ``sweep``'s top-k (``ref``'s own sweep of the space) and
    at each variant's minimum."""
    picks: Dict[int, List[int]] = {
        slot: [int(i) for i in rng.integers(0, sweep.n_var, per_variant)]
        for slot in range(len(sweep.labels))}
    for slot, local, _v in sweep.topk:
        picks[slot].append(local)
    for slot, label in enumerate(sweep.labels):
        if sweep.summaries[label]["argmin_index"] >= 0:
            picks[slot].append(sweep.summaries[label]["argmin_index"])
    width = per_variant + len(sweep.topk) + 1
    worst = 0.0
    for slot, locals_ in picks.items():
        # one width for every variant: one program of ``points`` each
        locals_ += locals_[:1] * (width - len(locals_))
        pts = [unravel(i, grids) for i in locals_]
        scal = [_scalar(sweep.labels[slot], p) for p in pts]
        worst = max(worst, widest_gap(ref.points(slot, pts), scal))
    return worst


def reference_gap(ref: Reference, cfg: Dict, spaces: List[Dict],
                  seed: int) -> float:
    """``ref_gap`` of ``ref`` over ``spaces``, each swept by ``ref``."""
    metric, k = cfg["metric"], int(cfg["k"])
    per_variant = _per_variant(len(spaces), len(ref.plans))
    return max((compare_reference(ref, ref.sweep(g, metric=metric, k=k), g,
                                  rng_for(seed, "ref_sample", i),
                                  per_variant)
                for i, g in enumerate(spaces)), default=0.0)


def _per_variant(n_spaces: int, n_variants: int) -> int:
    return max(32, REF_SAMPLE // max(n_spaces * n_variants, 1))


def compare(answer: Dict, grids: Dict, ref: Reference, sweep: RefSweep,
            *, metric: str, tie_rel: float) -> Dict[str, float]:
    """Readings of one answer against the reference of its space."""
    labels = sweep.labels
    slot_of = {lab: s for s, lab in enumerate(labels)}
    rd = dict.fromkeys(("decode_bad", "topk_bad", "n_bad"), 0)
    rd.update(eval_gap=0.0, topk_gap=0.0, min_gap=0.0, mean_gap=0.0)
    top = answer["topk"]

    # decode and evaluate: the winners against the scalar model
    rd.update(compare_rows(answer, grids))
    for row in top:
        if row["label"] not in slot_of:
            rd["decode_bad"] += 1

    # reduce: the ranking against the reference's
    if len(top) != len(sweep.topk):
        rd["topk_bad"] += 1
    priced = {(labels[slot], local): v for slot, local, v in sweep.topk}
    for j, (row, (slot, local, v)) in enumerate(zip(top, sweep.topk)):
        rd["topk_gap"] = max(rd["topk_gap"], _rel(row["out"][metric], v))
        ident = (row["label"], row["index"])
        if ident == (labels[slot], local):
            continue
        # a different winner passes only if the reference prices it alike
        if ident not in priced:
            s = slot_of.get(row["label"])
            priced[ident] = (ref.points(s, [unravel(row["index"], grids)])
                             [0][metric] if s is not None else math.inf)
        if _rel(priced[ident], v) > tie_rel:
            rd["topk_bad"] += 1

    for lab, want in sweep.summaries.items():
        got = answer["summaries"].get(lab)
        if got is None or got["n"] != want["n"]:
            rd["n_bad"] += 1
            continue
        rd["min_gap"] = max(rd["min_gap"],
                            _rel(got["metric_min"], want["metric_min"]))
        if want["n_feasible"] or got["n_feasible"]:
            rd["mean_gap"] = max(rd["mean_gap"],
                                 _rel(got["metric_mean"],
                                      want["metric_mean"]))
    return rd


def fold(readings: List[Dict[str, float]]) -> Dict[str, float]:
    """Readings of several answers: counts (ints) add, gaps take the
    widest."""
    out: Dict[str, float] = {}
    for rd in readings:
        for key, val in rd.items():
            out[key] = (out.get(key, 0) + val if isinstance(val, int)
                        else max(out.get(key, 0.0), val))
    return out


def judge(readings: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every reading at or under its limit; a reading without a limit,
    or a limit without a reading, fails."""
    if set(readings) != set(limits):
        return False
    return all(readings[k] <= limits[k] for k in readings)


def run_check(cfg: Dict, limits: Dict, whole: List, rows: List,
              extra: Dict, *, seed: int, control=None) -> Dict[str, float]:
    """The readings of a run: ``whole`` answers ``(answer, grids)``
    compared with the reference of their space, ``rows`` answers by
    their winners alone, and the driver's own counts in ``extra``.
    ``seed`` draws the points at which ``ref_gap`` is read.

    ``control`` (a :class:`Reference` at a lower precision) puts the
    control in the program's place: each ``whole`` answer is replaced by
    that reference's answer for the same space."""
    ref = Reference(cfg["algorithms"])
    metric, k = cfg["metric"], int(cfg["k"])
    per_variant = _per_variant(len(whole), len(ref.plans))
    out = [dict(extra)]
    for i, (answer, grids) in enumerate(whole):
        want = ref.sweep(grids, metric=metric, k=k)
        out.append({"ref_gap": compare_reference(
            ref, want, grids, rng_for(seed, "ref_sample", i), per_variant)})
        if control is not None:
            answer = answer_of_reference(
                control, control.sweep(grids, metric=metric, k=k), grids)
        out.append(compare(answer, grids, ref, want, metric=metric,
                           tie_rel=float(limits["topk_gap"])))
    if control is None:
        out += [compare_rows(answer, grids) for answer, grids in rows]
    return fold(out)
