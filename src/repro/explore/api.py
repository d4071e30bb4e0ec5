"""``explore()``: the one front door over every sweep engine.

``explore(space, k=..., metric=...)`` scores a declarative
:class:`~repro.explore.space.DesignSpace` and always returns the same
:class:`ExploreResult` shape — top-k rows, per-variant summaries,
occupancy / dispatch accounting and cache statistics — regardless of
which engine ran underneath:

* ``monolithic`` — the grid engine with full O(N) result tables (kept on
  ``ExploreResult.sweep_results``), one compiled call per variant;
* ``chunked``    — the same tables walked in O(chunk) device batches;
* ``fused``      — the device-resident streaming engine: superchunk
  ``lax.scan`` over the fused decode->evaluate->reduce Pallas megakernel,
  ONE step executable for the whole sweep, O(k + V) device state;
* ``staged``     — the staged streaming pipeline (the fused engine's
  parity oracle);
* ``auto`` (default) — picks by grid size: monolithic while full tables
  are cheap (<= 2^15 points), chunked while they still fit on host
  (<= 2^21), streaming-fused beyond (or whenever ``index_range`` asks
  for a stream slice).

Engines share the same lowering, PlanBank and executable caches, so
switching engines (or re-gridding values) never recompiles more than the
shapes demand — a space sweeping the coefficient-hook axes
(``vdd_scale`` / ``adc_bits``) or a freshly registered algorithm still
compiles exactly one streaming step executable (tests/test_explore.py).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..core.axes import AXES
from ..core.batch import OUT_KEYS
from ..core.plan import lower_cache_info
from ..core.shard_sweep import (StreamResult, _stream_impl,
                                best_by_algorithm_summaries,
                                stream_cache_info)
from ..core.sweep import SweepResult, _sweep_impl
from ..spans import span, traced
from .space import DesignSpace

#: engine names accepted by :func:`explore`
ENGINES = ("auto", "monolithic", "chunked", "staged", "fused")

#: ``auto`` thresholds: full tables up to 2^15 points, chunked tables up
#: to 2^21, the bounded streaming engine beyond
AUTO_MONOLITHIC_MAX = 1 << 15
AUTO_CHUNKED_MAX = 1 << 21
_DEFAULT_CHUNK = 1 << 18


@dataclasses.dataclass
class ExploreResult:
    """Unified result of one :func:`explore` call.

    Superset of the legacy ``SweepResult`` / ``StreamResult`` surfaces:
    ``topk`` rows (ascending by ``metric``, feasible only) carry the
    owning ``algorithm`` / ``variant``, the variant-local ``index``, the
    exact axis values and every model output; ``summaries`` maps variant
    labels to ``{n, n_feasible, metric_min, metric_mean, argmin_index,
    argmin_point}``.  Grid engines additionally keep the full per-
    algorithm tables on ``sweep_results``; streaming engines expose the
    raw ``stream_result``.  ``cache`` snapshots the lowering and
    streaming-executable cache counters after the run.
    """
    space: DesignSpace
    engine: str
    metric: str
    k: int
    n_points: int
    n_feasible: int
    n_variants: int
    n_devices: int
    chunk_size: Optional[int]
    topk: List[Dict]
    summaries: Dict[str, Dict]
    wall_s: float
    compile_s: float
    eval_s: float
    dispatches: int
    superchunk: int
    occupancy: float
    cache: Dict[str, Dict]
    sweep_results: Optional[Dict[str, SweepResult]] = None
    stream_result: Optional[StreamResult] = None
    #: campaign report dict (shards executed / retried / quarantined,
    #: coverage) when the result came from a checkpointed campaign run
    campaign: Optional[Dict] = None
    #: resolved streaming execution backend ("pallas" / "xla"); None for
    #: the grid engines, which have no megakernel lane
    backend: Optional[str] = None
    #: per-tenant serving metrics (queue wait, dispatch share, coalesce
    #: group size, cache hit, ...) when the result came through a
    #: :class:`repro.serve.ExploreService`; None for direct calls
    serve: Optional[Dict] = None

    def __len__(self) -> int:
        return self.n_points

    @property
    def points_per_sec(self) -> float:
        """Warm throughput (compilation excluded)."""
        return self.n_points / max(self.eval_s, 1e-12)

    def best(self, k: Optional[int] = None) -> List[Dict]:
        """Top-k rows by the metric (ascending), feasible only."""
        return self.topk[:k]

    def best_by_algorithm(self) -> Dict[str, Dict]:
        """Per-algorithm best variant by the metric.

        ``{algorithm: {"variant", "summary", "n_feasible"}}`` — every
        algorithm of the space gets a record even when it misses the
        global top-k; ``summary["argmin_point"]`` is None when nothing
        was feasible.
        """
        return best_by_algorithm_summaries(self.summaries,
                                           self.space.algorithms[0])


def _resolve_engine(engine: str, space: DesignSpace, chunk_size,
                    index_range) -> str:
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; valid: "
                         f"{list(ENGINES)}")
    if engine == "auto":
        if index_range is not None or space.n_points > AUTO_CHUNKED_MAX:
            return "fused"
        if space.n_points <= AUTO_MONOLITHIC_MAX and chunk_size is None:
            return "monolithic"
        return "chunked"
    if engine == "monolithic" and chunk_size is not None:
        return "chunked"
    return engine


def _cache_snapshot() -> Dict[str, Dict]:
    return {"lower": lower_cache_info(), "stream": stream_cache_info()}


def _grid_explore(space: DesignSpace, engine: str, *, k, metric,
                  chunk_size, mesh, strict) -> ExploreResult:
    """Grid engines: per-algorithm full tables -> unified result."""
    with span("grid.explore") as sp:
        chunk = ((chunk_size or _DEFAULT_CHUNK) if engine == "chunked"
                 else None)
        sweep_results: Dict[str, SweepResult] = {}
        for algo in space.algorithms:
            sweep_results[algo] = _sweep_impl(
                algo, space.grids, soc_node=space.soc_node, strict=strict,
                chunk_size=chunk, mesh=mesh)

        n_var = space.n_var
        # the concatenated per-algorithm tables ARE the variant-major
        # flat index space: algorithms in space order, variants in slot
        # order, n_var C-order rows per variant — same layout the codec
        # decodes
        metric_all = np.concatenate(
            [np.asarray(sweep_results[a].outputs[metric], np.float64)
             for a in space.algorithms])
        feas_all = np.concatenate(
            [sweep_results[a].outputs["feasible"].astype(bool)
             for a in space.algorithms])
        assert len(metric_all) == space.n_points, (len(metric_all),
                                                   space.n_points)

        # ----- per-variant summaries (label convention == streaming) ------
        # argmin points come from the result tables, not the codec:
        # decode() would re-touch the lowering cache and skew its hit
        # accounting
        summaries: Dict[str, Dict] = {}
        slot = 0
        for algo in space.algorithms:
            res = sweep_results[algo]
            for v in range(len(res) // n_var):
                sl = slice(v * n_var, (v + 1) * n_var)
                vals = np.asarray(res.outputs[metric], np.float64)[sl]
                feas = res.outputs["feasible"].astype(bool)[sl]
                nf = int(feas.sum())
                if nf:
                    amin = int(np.argmin(np.where(feas, vals, np.inf)))
                    point = {ax: float(res.params[ax][v * n_var + amin])
                             for ax in AXES}
                else:
                    amin, point = -1, None
                summaries[space.label(slot)] = dict(
                    n=n_var, n_feasible=nf,
                    metric_min=float(vals[feas].min()) if nf
                    else float("inf"),
                    metric_mean=float(vals[feas].mean()) if nf
                    else float("nan"),
                    argmin_index=amin, argmin_point=point)
                slot += 1

        # ----- global top-k rows (full output schema from the tables) -----
        masked = np.where(feas_all, metric_all, np.inf)
        order = np.argsort(masked, kind="stable")[:k]
        algo_rows = np.cumsum([0] + [len(sweep_results[a])
                                     for a in space.algorithms])
        rows: List[Dict] = []
        for gi in order:
            if not np.isfinite(masked[gi]):
                break
            ai = int(np.searchsorted(algo_rows, gi, side="right") - 1)
            algo = space.algorithms[ai]
            res = sweep_results[algo]
            r = res.row(int(gi - algo_rows[ai]))
            row = dict(variant=str(r.pop("variant")), algorithm=algo,
                       index=int(gi) % n_var)
            row.update({ax: float(r[ax]) for ax in AXES})
            row.update({key: float(r[key]) for key in OUT_KEYS})
            rows.append(row)

    chunks_per_variant = (1 if chunk is None
                          else -(-n_var // max(int(chunk), 1)))
    return ExploreResult(
        space=space, engine=engine, metric=metric, k=k,
        n_points=space.n_points, n_feasible=int(feas_all.sum()),
        n_variants=space.n_variants,
        n_devices=int(mesh.devices.size) if mesh is not None else 1,
        chunk_size=chunk, topk=rows, summaries=summaries,
        wall_s=sp.seconds,
        compile_s=sum(r.compile_s for r in sweep_results.values()),
        eval_s=sum(r.eval_s for r in sweep_results.values()),
        dispatches=space.n_variants * chunks_per_variant, superchunk=1,
        occupancy=1.0, cache=_cache_snapshot(),
        sweep_results=sweep_results)


def _stream_to_explore(space: DesignSpace, st: StreamResult, *,
                       campaign: Optional[Dict] = None) -> ExploreResult:
    """Wrap a (possibly merged) :class:`StreamResult` as the unified
    :class:`ExploreResult` surface."""
    return ExploreResult(
        space=space, engine=st.engine, metric=st.metric, k=st.k,
        n_points=st.n_points, n_feasible=st.n_feasible,
        n_variants=st.n_variants, n_devices=st.n_devices,
        chunk_size=st.chunk_size, topk=st.topk, summaries=st.summaries,
        wall_s=st.wall_s, compile_s=st.compile_s, eval_s=st.eval_s,
        dispatches=st.dispatches, superchunk=st.superchunk,
        occupancy=st.occupancy, cache=_cache_snapshot(),
        stream_result=st, campaign=campaign, backend=st.backend)


def _validate_request(k, chunk_size) -> None:
    """Boundary validation shared by :func:`explore` and the serve
    front end (``repro.serve.ExploreService.submit``)."""
    if isinstance(k, bool) or not isinstance(k, (int, np.integer)):
        raise ValueError(f"k must be an integer >= 1 (the top-k row "
                         f"budget), got {k!r} of type {type(k).__name__}")
    if k < 1:
        raise ValueError(f"k must be >= 1 (at least one top-k row "
                         f"to keep), got {k}")
    if chunk_size is not None:
        if isinstance(chunk_size, bool) \
                or not isinstance(chunk_size, (int, np.integer)):
            raise ValueError(
                f"chunk_size must be an integer >= 1 (points per "
                f"dispatch) or None for the engine default, got "
                f"{chunk_size!r} of type {type(chunk_size).__name__}")
        if chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1 (points per "
                             f"dispatch), got {chunk_size}")


@traced("explore")
def explore(space: DesignSpace, *, k: int = 16, metric: str = "total_j",
            engine: str = "auto", chunk_size: Optional[int] = None,
            mesh=None, strict: bool = False, block_points: int = 4096,
            progress: Optional[Callable[[int, int], None]] = None,
            index_range: Optional[Tuple[int, int]] = None,
            pipeline_depth: int = 4, superchunk: Optional[int] = None,
            backend: str = "auto", checkpoint_dir: Optional[str] = None,
            campaign=None, workers: Optional[int] = None,
            service=None) -> ExploreResult:
    """Score a :class:`DesignSpace`; one entry point for every engine.

    ``k`` bounds the top-k winner list, ``metric`` is any model output
    key (``total_j``, ``on_sensor_j``, ``density_mw_mm2``, ...), and
    ``engine`` picks the execution strategy (see the module docstring;
    ``"auto"`` sizes it from ``space.n_points``).  ``chunk_size`` bounds
    per-dispatch batches for the chunked/streaming engines; ``mesh``
    shards batches across a 1-D ``("batch",)`` device mesh.  ``strict``
    (grid engines) raises on pipeline stalls / infeasible points like the
    scalar oracle.  ``index_range`` / ``progress`` / ``superchunk`` /
    ``pipeline_depth`` / ``block_points`` tune the streaming engines
    (``index_range`` is the multi-host partitioning hook; its bounds are
    host integers and may pass 2**31, as may the space, so long as each
    variant spans fewer than 2**31 points).

    ``backend`` selects the fused megakernel implementation: ``"pallas"``
    (``pallas_call`` — Mosaic-compiled on TPU, interpreted elsewhere),
    ``"xla"`` (the pure-``jnp`` twin XLA compiles natively on any
    platform), or ``"auto"`` (default: Pallas on TPU, XLA elsewhere; the
    ``REPRO_SWEEP_BACKEND`` environment variable overrides the auto
    policy, mirroring ``REPRO_KERNEL_INTERPRET``).  The resolved lane is
    reported on ``result.backend`` and recorded in campaign manifests —
    a campaign refuses to resume under a different backend.

    ``checkpoint_dir`` makes the call a durable CAMPAIGN: the sweep is
    sharded, each shard checkpointed with retry/split/quarantine fault
    handling, and a killed run resumes from the same directory
    dispatching only what's missing (see :mod:`repro.campaign`).
    ``campaign`` optionally passes a
    :class:`~repro.campaign.CampaignOptions`; the campaign report lands
    on ``result.campaign``.  ``workers`` (campaigns only) runs shards on
    that many persistent worker processes with overlapped checkpoint
    I/O — default 1 (serial, bit-identical to an unsharded sweep;
    ``REPRO_CAMPAIGN_WORKERS`` overrides the default).

    ``service`` routes the request through a running
    :class:`repro.serve.ExploreService` instead of dispatching inline:
    the call blocks like a direct ``explore()`` but the service may
    coalesce it with concurrent compatible tenants onto one shared step
    executable and serve repeats from its result cache
    (``result.serve`` carries the per-tenant serving metrics).
    """
    if not isinstance(space, DesignSpace):
        raise TypeError(f"explore() takes a DesignSpace, got "
                        f"{type(space).__name__}; wrap your algorithms + "
                        f"grids in DesignSpace(...)")
    if metric not in OUT_KEYS:
        raise KeyError(f"unknown metric {metric!r}; valid: "
                       f"{sorted(OUT_KEYS)}")
    _validate_request(k, chunk_size)
    if service is not None:
        for name, val, default in (("checkpoint_dir", checkpoint_dir,
                                    None),
                                   ("campaign", campaign, None),
                                   ("workers", workers, None),
                                   ("index_range", index_range, None),
                                   ("progress", progress, None),
                                   ("mesh", mesh, None),
                                   ("strict", strict, False)):
            if val != default:
                raise ValueError(f"{name}= is incompatible with "
                                 f"service= (the service owns dispatch "
                                 f"planning; submit plain requests)")
        return service.explore(space, k=k, metric=metric, engine=engine,
                               chunk_size=chunk_size,
                               block_points=block_points,
                               superchunk=superchunk, backend=backend)
    if checkpoint_dir is not None or campaign is not None \
            or workers is not None:
        if checkpoint_dir is None:
            name = "campaign=" if campaign is not None else "workers="
            raise ValueError(f"{name} options require checkpoint_dir= "
                             f"(the campaign's durable state directory)")
        for name, val in (("strict", strict or None),
                          ("index_range", index_range),
                          ("progress", progress)):
            if val is not None:
                raise ValueError(f"{name}= is incompatible with "
                                 f"checkpoint_dir= (the campaign plans "
                                 f"its own shard index ranges)")
        from ..campaign import run_campaign
        return run_campaign(space, checkpoint_dir, k=k, metric=metric,
                            engine=engine, chunk_size=chunk_size,
                            superchunk=superchunk,
                            block_points=block_points, mesh=mesh,
                            backend=backend, workers=workers,
                            options=campaign)
    engine = _resolve_engine(engine, space, chunk_size, index_range)

    if engine in ("monolithic", "chunked"):
        for name, val, default in (("index_range", index_range, None),
                                   ("progress", progress, None),
                                   ("superchunk", superchunk, None),
                                   ("block_points", block_points, 4096),
                                   ("pipeline_depth", pipeline_depth, 4),
                                   ("backend", backend, "auto")):
            if val != default:
                raise ValueError(f"{name}= requires a streaming engine "
                                 f"('fused' or 'staged'), not {engine!r}")
        return _grid_explore(space, engine, k=k, metric=metric,
                             chunk_size=chunk_size, mesh=mesh,
                             strict=strict)

    if strict:
        raise ValueError("strict=True requires a grid engine "
                         "('monolithic' or 'chunked'); the streaming "
                         "engines mask infeasible points instead")
    st = _stream_impl(
        list(space.algorithms), space.grids, soc_node=space.soc_node,
        chunk_size=chunk_size or _DEFAULT_CHUNK, metric=metric, k=k,
        mesh=mesh, block_points=block_points, progress=progress,
        index_range=index_range, pipeline_depth=pipeline_depth,
        engine=engine, superchunk=superchunk, backend=backend)
    return _stream_to_explore(space, st)
