"""Grid-engine design-space sweeps: parameter grids -> batched evaluation.

The exploration FRONT DOOR is :func:`repro.explore.explore` with a
declarative :class:`repro.explore.DesignSpace` (ISSUE 5); this module is
the grid ENGINE behind it — full O(N) result tables, one lowering + one
compiled device call per structural variant per chunk — plus the scalar
``estimate_energy`` reference oracle (:func:`scalar_point`).  The old
``sweep()`` entry survives as a thin ``DeprecationWarning`` shim that
delegates through ``explore``.

    from repro.explore import DesignSpace, explore
    res = explore(DesignSpace(["edgaze"],
                              {"variant": ["2d_in", "3d_in"],
                               "cis_node": [130, 90, 65, 45, 28],
                               "frame_rate": [15, 30, 60],
                               "sys_rows": [8, 16, 32]}))
    best = res.best()

Grids are walked through :class:`ChunkedGrid` — flat-index unraveling, so
the full cartesian product is never materialized on host.  ``chunk_size=``
bounds the per-call batch (host memory stays O(chunk) during evaluation;
the returned tables are still O(N)) and ``mesh=`` (a 1-D ``("batch",)``
mesh, see ``repro.launch.mesh.make_batch_mesh``) shards each batch across
devices.  For sweeps too large to return N-row tables at all (>= 1e7
points), ``explore`` picks the streaming engine
(``repro.core.shard_sweep``) — same grids, bounded result.

Axis names/order, defaults, value coding and the coefficient hooks all
come from the axis registry (``repro.core.axes``); algorithms resolve via
the pluggable registry (``repro.core.algorithms``).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..spans import span
from .algorithms import get_algorithm
from .axes import AXES, TECH_DECLARED, _tech_code
from .batch import (evaluate_batch, grid_hooks_active, make_points,
                    point_defaults)
from .digital import SystolicArray
from .energy import estimate_energy, reference_outputs
from .plan import (CATEGORIES, EnergyPlan, TECH_INDEX, _EXTRA_CACHES,
                   count_cache_hit, lower)

_REF_CIS_NODE = 65   # structures are built once here and re-scaled per point


def _algorithm(name: str):
    spec = get_algorithm(name)       # KeyError lists registered names
    return spec.builder, spec.variants


class ChunkedGrid:
    """Lazy cartesian product over named axis value lists.

    Equivalent to ``np.meshgrid(*values, indexing="ij")`` flattened in C
    order, but points are materialized per chunk from flat indices via
    ``np.unravel_index`` — host memory is O(chunk_size), never O(N).  The
    old meshgrid path allocated ``len(axes)`` float64 arrays of the full
    product size twice over and died around ~1e7 points.
    """

    def __init__(self, axes: Dict[str, Sequence]):
        self.names: List[str] = list(axes)
        self.values: List[np.ndarray] = [
            np.atleast_1d(np.asarray(v, np.float64)).reshape(-1)
            for v in axes.values()]
        self.shape: Tuple[int, ...] = tuple(len(v) for v in self.values)
        self.n_points: int = int(np.prod(self.shape)) if self.shape else 0

    def __len__(self) -> int:
        return self.n_points

    def chunk(self, start: int, stop: int) -> Dict[str, np.ndarray]:
        """Axis values for flat grid indices ``[start, stop)``."""
        idx = np.arange(start, min(stop, self.n_points))
        multi = np.unravel_index(idx, self.shape)
        return {n: v[m] for n, v, m in zip(self.names, self.values, multi)}

    def point(self, i: int) -> Dict[str, float]:
        """Axis values of one flat grid index."""
        multi = np.unravel_index(int(i), self.shape)
        return {n: float(v[m])
                for n, v, m in zip(self.names, self.values, multi)}

    def chunks(self, chunk_size: Optional[int]
               ) -> Iterator[Tuple[int, Dict[str, np.ndarray]]]:
        """Yield ``(start, axis-values)`` walking the grid in order."""
        step = self.n_points if chunk_size is None else int(chunk_size)
        step = max(step, 1)
        for start in range(0, self.n_points, step):
            yield start, self.chunk(start, start + step)


@dataclasses.dataclass
class SweepResult:
    algorithm: str
    params: Dict[str, np.ndarray]        # per-point axis values (+ variant)
    outputs: Dict[str, np.ndarray]       # per-point model outputs
    variant_meta: Dict[str, Dict]        # variant -> plan metadata
    wall_s: float = 0.0                  # total front-door wall time
    compile_s: float = 0.0               # AOT lowering + XLA compilation
    eval_s: float = 0.0                  # device execution + host transfer

    def __len__(self) -> int:
        return len(self.outputs["total_j"])

    def select(self, **filters) -> np.ndarray:
        """Boolean mask of points matching the given param values.

        Numeric axes match with ``np.isclose`` (grid values round-trip
        through f32 on device and through float arithmetic when grids are
        generated, so exact ``==`` silently returns an empty mask);
        ``variant`` and the categorical ``mem_tech`` codes stay exact.
        """
        mask = np.ones(len(self), bool)
        for k, v in filters.items():
            col = self.params[k]
            if k == "mem_tech":
                mask &= col == _tech_code(v)
            elif k == "variant" or not np.issubdtype(col.dtype, np.number):
                mask &= col == v
            else:
                mask &= np.isclose(col.astype(np.float64), float(v),
                                   rtol=1e-6, atol=1e-12)
        return mask

    def row(self, i: int) -> Dict:
        d = {k: v[i] for k, v in self.params.items()}
        d.update({k: v[i] for k, v in self.outputs.items()})
        return d

    def best(self, metric: str = "total_j", feasible_only: bool = True,
             k: int = 1) -> List[Dict]:
        """Top-k rows by ``metric`` (ascending); [] if none qualify."""
        vals = np.asarray(self.outputs[metric], np.float64).copy()
        if feasible_only:
            vals[~self.outputs["feasible"].astype(bool)] = np.inf
        idx = [int(i) for i in np.argsort(vals)[:k]
               if np.isfinite(vals[int(i)])]
        return [self.row(i) for i in idx]


def build_variant(algorithm: str, variant: str, *, cis_node: int = 65,
                  soc_node: int = 22):
    build, variants = _algorithm(algorithm)
    assert variant in variants, (algorithm, variant)
    return build(variant, cis_node=cis_node, soc_node=soc_node)


_VARIANT_CACHE: Dict[tuple, EnergyPlan] = {}
_EXTRA_CACHES.append(_VARIANT_CACHE)     # flushed by lower_cache_clear()


def lower_variant(algorithm: str, variant: str, *,
                  soc_node: int = 22) -> EnergyPlan:
    """Lower one structural variant (cached on the structural signature).

    The structure is built at the fixed reference CIS node — independent
    of the user's ``soc_node`` — and the node axes are swept numerically
    by the evaluator, so the cache hits for any grid.  The ``soc_node ==
    65`` collision with the reference node is handled inside ``lower``
    (node roles tie-break on die layer / off-sensor facts), not by
    silently rebuilding the structure at a different reference node,
    which used to shift structure-derived defaults for that one value.

    Builders are deterministic in ``(algorithm, variant, soc_node)``, so
    the plan is also memoized on that triple to keep rebuilding the
    Python structure + signing it off the per-chunk sweep hot path
    (``lower``'s own structural cache still deduplicates across callers).
    """
    key = (algorithm, variant, int(soc_node))
    plan = _VARIANT_CACHE.get(key)
    if plan is None:
        hw, stages, mapping, _meta = build_variant(
            algorithm, variant, cis_node=_REF_CIS_NODE, soc_node=soc_node)
        plan = _VARIANT_CACHE[key] = lower(hw, stages, mapping)
    else:
        count_cache_hit()
    return plan


def _normalize_grids(algorithm: str, grids: Optional[Dict[str, Sequence]]
                     ) -> Tuple[List[str], Dict[str, Sequence]]:
    """Split the variant axis off and map mem_tech names to codes."""
    grids = dict(grids or {})
    _build, all_variants = _algorithm(algorithm)
    variants = [str(v) for v in grids.pop("variant", all_variants)]
    unknown = set(grids) - set(AXES)
    if unknown:
        raise KeyError(f"unknown sweep axes {sorted(unknown)}; valid: "
                       f"['variant'] + {list(AXES)}")
    if "mem_tech" in grids:
        grids["mem_tech"] = [_tech_code(v) for v in grids["mem_tech"]]
    return variants, grids


def variant_grid(plan: EnergyPlan, grids: Dict[str, Sequence]) -> ChunkedGrid:
    """The :class:`ChunkedGrid` one variant sweeps (defaults fill gaps)."""
    defaults = point_defaults(plan)
    return ChunkedGrid({ax: grids.get(ax, [defaults[ax]]) for ax in AXES})


def axis_tables(grids: List[ChunkedGrid]) -> np.ndarray:
    """Stack per-variant axis values into a ``(V, n_axes, Lmax)`` f32 bank.

    The on-device grid decoder (``repro.kernels.grid_decode``) gathers
    axis values from this table; variants share the grid SHAPE (swept axes
    come from one ``grids`` dict) but may differ in the single-value
    defaults filling unswept axes.  The f32 cast matches ``make_points``,
    so decoded points are bit-identical to the host path.
    """
    assert grids and all(g.shape == grids[0].shape for g in grids), (
        [g.shape for g in grids])
    lmax = max(max(s, 1) for s in grids[0].shape)
    out = np.zeros((len(grids), len(grids[0].names), lmax), np.float32)
    for vi, g in enumerate(grids):
        for a, vals in enumerate(g.values):
            out[vi, a, : len(vals)] = vals.astype(np.float32)
    return out


def _variant_meta(plan: EnergyPlan) -> Dict:
    return dict(
        hw_name=plan.hw_name, notes=plan.notes,
        stall_notes=plan.stall_notes,
        categories_present=[CATEGORIES[c]
                            for c in sorted(set(plan.unit_category))],
        num_units=plan.num_units)


def sweep(algorithm: str = "edgaze",
          grids: Optional[Dict[str, Sequence]] = None, *,
          soc_node: int = 22, strict: bool = False,
          chunk_size: Optional[int] = None, mesh=None) -> SweepResult:
    """DEPRECATED: use :func:`repro.explore.explore` with a
    :class:`repro.explore.DesignSpace`.

    Thin compatibility shim: builds the equivalent one-algorithm design
    space, runs it through ``explore`` on the grid engine (``chunked``
    when ``chunk_size`` is given, ``monolithic`` otherwise) and returns
    the legacy per-algorithm :class:`SweepResult` — bit-identical to the
    pre-ISSUE-5 behavior (parity-tested in tests/test_explore.py).
    """
    warnings.warn(
        "repro.core.sweep.sweep() is deprecated; use "
        "repro.explore.explore(DesignSpace([algorithm], grids)) — the "
        "unified ExploreResult keeps the full tables via .sweep_results",
        DeprecationWarning, stacklevel=2)
    from ..explore import DesignSpace, explore
    space = DesignSpace(algorithms=(algorithm,), grids=grids,
                        soc_node=soc_node)
    res = explore(space, metric="total_j",
                  engine="chunked" if chunk_size is not None
                  else "monolithic",
                  chunk_size=chunk_size, mesh=mesh, strict=strict)
    return res.sweep_results[algorithm]


def _sweep_impl(algorithm: str = "edgaze",
                grids: Optional[Dict[str, Sequence]] = None, *,
                soc_node: int = 22, strict: bool = False,
                chunk_size: Optional[int] = None, mesh=None) -> SweepResult:
    """Grid engine: score the cartesian product of the parameter grids.

    ``grids`` maps axis names (``variant`` + :data:`AXES`) to value lists;
    missing axes default to the values each variant was built with.  One
    compiled device call per structural variant per chunk.

    ``chunk_size`` bounds the per-call batch: the grid is walked lazily
    (no full meshgrid on host) and each chunk is evaluated through one
    compiled executable, so peak evaluation memory is O(chunk_size).
    Pick a power-of-two chunk (e.g. 1 << 18) large enough to amortize
    dispatch; non-divisible tails compile a second (smaller) executable.
    ``mesh``, if given, is a 1-D ``("batch",)`` device mesh
    (``repro.launch.mesh.make_batch_mesh``) and every chunk is sharded
    across its devices, padding internally to a divisible batch.

    The result's ``compile_s``/``eval_s`` report compilation and warm
    evaluation separately — ``wall_s`` alone made first-call throughput
    look arbitrarily bad and BENCH numbers depend on call order.
    """
    with span("grid.sweep", algorithm=algorithm) as sp:
        variants, grids = _normalize_grids(algorithm, grids)
        # one sweep-level hook decision (vs a per-chunk point readback):
        # a grid at the hook defaults rides the hook-free executable
        hooks = grid_hooks_active(grids)
        if mesh is not None:
            from .shard_sweep import evaluate_batch_sharded

        params: Dict[str, List] = {k: [] for k in ("variant",) + AXES}
        outputs: Dict[str, List] = {}
        variant_meta: Dict[str, Dict] = {}
        timings = {"compile_s": 0.0, "eval_s": 0.0}

        for variant in variants:
            plan = lower_variant(algorithm, variant, soc_node=soc_node)
            if strict and plan.stall_notes:
                raise ValueError("pipeline stalls detected: "
                                 + "; ".join(plan.stall_notes))
            grid = variant_grid(plan, grids)
            for _start, flat in grid.chunks(chunk_size):
                n = len(flat[AXES[0]])
                points = make_points(plan, n, **flat)
                if mesh is not None:
                    out = evaluate_batch_sharded(plan, points, mesh=mesh,
                                                 timings=timings,
                                                 hooks=hooks)
                else:
                    out = evaluate_batch(plan, points, timings=timings,
                                         hooks=hooks)
                if strict and not bool(out["feasible"].all()):
                    bad = int((~out["feasible"].astype(bool)).sum())
                    raise ValueError(
                        f"{variant}: {bad}/{n} design points cannot meet "
                        f"the frame rate (T_D >= T_FR, Sec. 4.1)")
                params["variant"].append(np.full(n, variant, object))
                for ax in AXES:
                    params[ax].append(flat[ax])
                for k, v in out.items():
                    outputs.setdefault(k, []).append(v)
            variant_meta[variant] = _variant_meta(plan)
        params = {k: np.concatenate(v) if k != "variant"
                  else np.concatenate(v).astype(str)
                  for k, v in params.items()}
        outputs = {k: np.concatenate(v) for k, v in outputs.items()}

    return SweepResult(
        algorithm=algorithm, params=params, outputs=outputs,
        variant_meta=variant_meta, wall_s=sp.seconds,
        compile_s=timings["compile_s"], eval_s=timings["eval_s"])


# ---------------------------------------------------------------------------
# Scalar reference oracle (one design point at a time)
# ---------------------------------------------------------------------------
def scalar_point(algorithm: str, variant: str, *,
                 cis_node: float = 65, soc_node: float = 22,
                 mem_tech=None, sys_rows: Optional[float] = None,
                 sys_cols: Optional[float] = None,
                 frame_rate: Optional[float] = None,
                 active_fraction_scale: float = 1.0,
                 pixel_pitch_um: Optional[float] = None,
                 vdd_scale: float = 1.0,
                 adc_bits: float = -1.0) -> Dict[str, float]:
    """Evaluate ONE design point through the scalar ``estimate_energy``.

    Rebuilds the variant at the requested node and patches the remaining
    swept knobs onto the ``HWConfig`` — exactly what a pre-batching sweep
    loop had to do per point.  Returns the batched output schema.

    The scalar walk prices the *declared* structure, so the coefficient-
    hook axes (``vdd_scale`` / ``adc_bits``, see ``repro.core.axes``) are
    only accepted at their defaults; for non-default values the banked
    evaluators are each other's parity oracle (``engine="staged"`` vs
    ``engine="fused"`` vs the per-plan path, tests/test_explore.py).
    """
    off_default = []
    if vdd_scale != 1.0:
        off_default.append(f"vdd_scale={vdd_scale!r}")
    if adc_bits is not None and adc_bits >= 0:
        off_default.append(f"adc_bits={adc_bits!r}")
    if off_default:
        raise NotImplementedError(
            "the scalar oracle does not model the coefficient-hook "
            f"axes ({', '.join(off_default)} off default); validate "
            "those axes against explore(..., engine='staged')")
    hw, stages, mapping, _meta = build_variant(
        algorithm, variant, cis_node=int(cis_node), soc_node=int(soc_node))
    if frame_rate is not None:
        hw.frame_rate = float(frame_rate)
    if pixel_pitch_um is not None:
        hw.pixel_pitch_um = float(pixel_pitch_um)
    for binding in hw.digital.values():
        if isinstance(binding.unit, SystolicArray):
            if sys_rows is not None:
                binding.unit.rows = int(sys_rows)
            if sys_cols is not None:
                binding.unit.cols = int(sys_cols)
    tech = _tech_code(mem_tech)
    for mem in hw.memories.values():
        if tech != TECH_DECLARED:
            mem.technology = {v: k for k, v in TECH_INDEX.items()}[tech]
        mem.active_fraction *= active_fraction_scale
    report = estimate_energy(hw, stages, mapping, strict=False)
    return reference_outputs(report, hw)


def scalar_sweep(algorithm: str, result_params: Dict[str, np.ndarray],
                 indices: Sequence[int]) -> List[Dict[str, float]]:
    """Run the scalar oracle over selected points of a sweep's param table."""
    rows = []
    for i in indices:
        kwargs = {ax: float(result_params[ax][i]) for ax in AXES}
        kwargs["mem_tech"] = int(result_params["mem_tech"][i])
        rows.append(scalar_point(algorithm,
                                 str(result_params["variant"][i]), **kwargs))
    return rows
