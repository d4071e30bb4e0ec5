"""The campaign runner: durable, fault-tolerant mega-sweep execution.

``run_campaign(space, checkpoint_dir)`` turns one ``explore()`` call
into a campaign that survives process death:

1. **Plan** — on first run, a :class:`CampaignManifest` records the
   resolved design-space + plan-bank signatures, provenance (git SHA,
   jax/device fingerprint) and a deterministic split of the flat index
   space into ``index_range`` shards.  On a later run against the same
   directory, the manifest is verified against the provided space and
   only the not-yet-completed ranges are dispatched.
2. **Execute** — shards run ``explore(space, index_range=(lo, hi),
   engine='fused')`` with a FIXED ``superchunk`` through a pluggable
   executor (:mod:`repro.campaign.executor`): ``workers=1`` (default)
   dispatches in-process against one shared ``_StreamPrep`` — exactly
   the pre-parallel path, bit-identical — while ``workers=N`` feeds the
   shard queue to N persistent worker processes, each with its own JAX
   runtime and ONE step executable, folding results in arrival order.
   Completed shards checkpoint through a bounded background writer
   (atomic tmp + fsync + rename, checksummed) so serialization never
   sits between two dispatches; the writer is flushed-and-barriered
   before the merge and ``report.json``.  Failures are classified
   (:func:`classify_failure`): transient -> bounded retry with
   exponential backoff; OOM -> split the shard in half and retry the
   halves; deterministic -> quarantine and continue; a dead WORKER is a
   transient failure of its in-flight shard, never a campaign abort.
3. **Merge** — checkpointed + freshly-computed shard results fold
   through :func:`merge_stream_results` into one result bit-compatible
   (rel 1e-6) with the unsharded sweep, and a ``report.json`` records
   what ran, retried, split and quarantined, plus the parallel/overlap
   accounting (``workers``, ``dispatch_wait_s``, ``io_overlap_frac``).

``resume(manifest_path)`` rebuilds the space from the manifest payload
and re-enters the same machinery — it dispatches ONLY the missing
ranges.  Both entry points refuse (``CampaignMismatchError``) when the
space or bank layout no longer matches the manifest.
"""
from __future__ import annotations

import dataclasses
import os
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Set, Tuple

from ..ckpt import atomic_write_json
from ..core.shard_sweep import (_DEFAULT_SUPERCHUNK, StreamResult,
                                _prepare_stream, check_variant_span)
from ..kernels.runtime import explicit_backend, on_tpu, resolve_backend
from ..spans import span, traced
from .executor import (CheckpointWriter, ProcessShardExecutor,
                       SerialShardExecutor, ShardTask, _dispatch,
                       resolve_workers)
from .faults import FaultSchedule, KillWorker, classify_failure
from .manifest import (REPORT_NAME, CampaignIntegrityError,
                       CampaignManifest, CampaignMismatchError,
                       completed_shards, missing_ranges, read_shard,
                       shard_path)
from .merge import merge_stream_results, merged_coverage

_DEFAULT_CHUNK = 1 << 18

__all__ = ["CampaignOptions", "run_campaign", "resume", "_dispatch"]


@dataclasses.dataclass
class CampaignOptions:
    """Fault-handling + parallelism knobs for :func:`run_campaign`.

    ``shard_points`` sets the planned shard width (default: four chunks,
    so a shard is a handful of dispatches); ``max_retries`` bounds
    attempts per shard for transient failures, backed off exponentially
    from ``backoff_s``; ``timeout_s`` aborts a shard dispatch that runs
    too long (classified transient); OOM splits recurse down to
    ``min_shard_points`` before quarantining.  ``workers`` sets the
    shard-executor width (None: the ``REPRO_CAMPAIGN_WORKERS``
    environment variable, else 1 = serial in-process execution);
    ``workers > 1`` runs shards on persistent worker processes.
    ``faults`` injects a deterministic :class:`FaultSchedule` at shard
    boundaries (tests / drills); ``sleep`` is injectable so backoff is
    testable without wall-clock waits.
    """
    shard_points: Optional[int] = None
    max_retries: int = 3
    backoff_s: float = 0.5
    timeout_s: Optional[float] = None
    min_shard_points: int = 1
    workers: Optional[int] = None
    faults: Optional[FaultSchedule] = None
    sleep: Callable[[float], None] = time.sleep


def _quarantine(directory: str, lo: int, hi: int, *, kind: str,
                error: str, attempts: int) -> Dict:
    entry = {"lo": int(lo), "hi": int(hi), "kind": kind,
             "error": error, "attempts": int(attempts)}
    atomic_write_json(shard_path(directory, lo, hi, quarantined=True),
                      entry)
    return entry


def run_campaign(space, checkpoint_dir: str, *, k: int = 16,
                 metric: str = "total_j", engine: str = "fused",
                 chunk_size: Optional[int] = None,
                 superchunk: Optional[int] = None,
                 block_points: int = 4096, mesh=None,
                 backend: str = "auto",
                 workers: Optional[int] = None,
                 options: Optional[CampaignOptions] = None,
                 on_corrupt: str = "refuse"):
    """Run (or resume) a durable sharded sweep campaign.

    Returns the same :class:`~repro.explore.api.ExploreResult` an
    unsharded ``explore()`` call would, with the campaign report on
    ``result.campaign``.  Idempotent against ``checkpoint_dir``: a
    directory holding a finished campaign verifies + merges without
    dispatching anything; a partial one dispatches only the missing
    index ranges.  Sweep parameters (``k``/``metric``/``engine``/...)
    are recorded in the manifest on first run and REUSED on resume —
    changing them mid-campaign would make shards unmergeable.  The
    resolved execution ``backend`` ("pallas"/"xla") is likewise
    recorded: a resume under an explicitly different backend (argument
    or ``REPRO_SWEEP_BACKEND``) raises :class:`CampaignMismatchError`
    instead of silently merging shards computed by different
    executables; ``backend="auto"`` on resume reuses the recorded lane.

    ``workers`` widens shard execution across that many persistent
    worker processes (argument > ``options.workers`` >
    ``REPRO_CAMPAIGN_WORKERS`` env > 1).  The worker count is an
    EXECUTION property, not a campaign property: it is not recorded in
    the manifest, and a serial campaign may be resumed parallel (or
    vice versa) — the merge algebra is partition- and order-independent.

    ``on_corrupt``: ``'refuse'`` (default) raises
    :class:`CampaignIntegrityError` on a checksum-failing shard file;
    ``'redispatch'`` discards it and re-runs that range.
    """
    if on_corrupt not in ("refuse", "redispatch"):
        raise ValueError(f"on_corrupt must be 'refuse' or 'redispatch', "
                         f"got {on_corrupt!r}")
    opts = options or CampaignOptions()
    if workers is not None and opts.workers is not None \
            and int(workers) != int(opts.workers):
        raise ValueError(
            f"conflicting worker counts: workers={workers} vs "
            f"CampaignOptions.workers={opts.workers} — set one")
    n_workers = resolve_workers(
        workers if workers is not None else opts.workers)
    if n_workers > 1 and on_tpu():
        raise RuntimeError(
            f"workers={n_workers} would spawn {n_workers} processes that "
            f"each need the TPU, but a chip belongs to one process at a "
            f"time (this one); run the campaign with workers=1 — one "
            f"process drives every chip through the mesh")
    resumed = os.path.exists(os.path.join(checkpoint_dir, "manifest.json"))
    with span("campaign.run", resumed=resumed) as run_sp:
        return _run(space, checkpoint_dir, run_sp, resumed, k=k,
                    metric=metric, engine=engine, chunk_size=chunk_size,
                    superchunk=superchunk, block_points=block_points,
                    mesh=mesh, backend=backend, n_workers=n_workers,
                    opts=opts, on_corrupt=on_corrupt)


def _run(space, checkpoint_dir, run_sp, resumed, *, k, metric, engine,
         chunk_size, superchunk, block_points, mesh, backend, n_workers,
         opts, on_corrupt):
    """The body of :func:`run_campaign`, inside its ``campaign.run``
    span; the report's ``wall_s`` is that span so far."""
    from ..explore.api import _stream_to_explore
    # ----- plan: create or verify the manifest ----------------------------
    with span("campaign.plan"):
        if resumed:
            manifest = CampaignManifest.load(checkpoint_dir)
            manifest.verify_space(space)
            manifest.verify_bank(space)
            sweep = manifest.sweep
            # cross-backend resume refusal: shards checkpointed by one
            # megakernel lane must not merge with shards computed by the
            # other (parity is rel 1e-6, but campaign merges are asserted
            # bit-compatible).  An EXPLICIT request (argument or env) that
            # contradicts the manifest refuses; "auto" reuses the record.
            recorded = sweep.get("backend") or "pallas"
            requested = explicit_backend(backend)
            if sweep["engine"] == "fused" \
                    and requested not in (None, recorded):
                raise CampaignMismatchError(
                    f"campaign at {checkpoint_dir!r} was recorded with "
                    f"backend={recorded!r} but this resume requests "
                    f"backend={requested!r}; resuming would mix executables "
                    f"across shards — resume with backend='auto'/"
                    f"{recorded!r}, or start a fresh checkpoint_dir")
            sweep = dict(sweep, backend=recorded)
        else:
            if engine == "auto":
                engine = "fused"
            if engine not in ("fused", "staged"):
                raise ValueError(f"campaigns need a streaming engine ('fused' "
                                 f"or 'staged'), got {engine!r}")
            if engine == "staged":
                if explicit_backend(backend) == "xla":
                    raise ValueError(
                        "backend='xla' requires engine='fused'; the staged "
                        "parity oracle always runs the Pallas pipeline")
                resolved_backend = "pallas"
            else:
                resolved_backend = resolve_backend(backend)
            chunk = int(chunk_size or _DEFAULT_CHUNK)
            # refuse a space the device cannot index before planning
            # shards (the shards' sweeps would each refuse it)
            check_variant_span(space.n_var)
            sweep = {"k": int(k), "metric": metric, "engine": engine,
                     "chunk_size": chunk,
                     # FIXED scan length: the default would shrink with the
                     # shard's chunk count and each distinct s_len is a new
                     # executable — pinning it keeps the whole campaign
                     # (including OOM half-shards) on ONE step executable
                     "superchunk": int(superchunk or _DEFAULT_SUPERCHUNK),
                     "block_points": int(block_points),
                     # resolved lane, not "auto": the manifest records what
                     # actually ran so resume can refuse a cross-backend mix
                     "backend": resolved_backend}
            shard_points = int(opts.shard_points or 4 * chunk)
            manifest = CampaignManifest.create(space, sweep=sweep,
                                               shard_points=shard_points)
            manifest.save(checkpoint_dir)

    # ----- load completed shards (verified), derive the work queue --------
    results: List[StreamResult] = []
    loaded: List[Tuple[int, int]] = []
    with span("campaign.load"):
        for (lo, hi), path in sorted(
                completed_shards(checkpoint_dir).items()):
            try:
                payload = read_shard(path)
            except CampaignIntegrityError:
                if on_corrupt == "refuse":
                    raise
                os.remove(path)        # redispatch: range back to queue
                continue
            results.append(StreamResult.from_payload(payload["result"]))
            loaded.append((lo, hi))
    pending = deque(ShardTask(lo, hi) for lo, hi in
                    missing_ranges(manifest.shards, loaded))

    # ----- execute --------------------------------------------------------
    if n_workers > 1 and pending:
        # parallel lane: the parent schedules, workers prepare + dispatch
        # (one lowering/bank/table build PER WORKER, then one step
        # executable each for the rest of the campaign)
        executor = ProcessShardExecutor(
            directory=checkpoint_dir, space_sig=manifest.space_sig,
            sweep=sweep, workers=min(n_workers, len(pending)),
            n_devices=(int(mesh.devices.size) if mesh is not None
                       else None),
            timeout_s=opts.timeout_s)
    else:
        # serial lane: one lowering/bank/table build for the WHOLE
        # campaign — every shard (and every OOM half-shard) dispatches
        # against this shared prep, so per-shard fixed cost drops to
        # executable-cache lookup + O(k) finalization
        prep = None
        if pending:
            with span("campaign.prep"):
                prep = _prepare_stream(list(space.algorithms), space.grids,
                                       soc_node=space.soc_node)
        executor = SerialShardExecutor(space, sweep, mesh, prep,
                                       opts.timeout_s)
    writer = CheckpointWriter(checkpoint_dir)
    executed: List[Dict] = []
    quarantined: List[Dict] = []
    n_retries = n_splits = n_completed = 0
    dispatch_wait_s = 0.0
    done_ranges: Set[Tuple[int, int]] = set()
    graceful = True

    def fail(task: ShardTask, kind: str, error: str) -> None:
        nonlocal n_retries, n_splits
        if kind == "oom" and task.hi - task.lo >= max(
                2, 2 * max(int(opts.min_shard_points), 1)):
            mid = task.lo + (task.hi - task.lo) // 2
            n_splits += 1
            pending.appendleft(ShardTask(mid, task.hi, 1,
                                         task.splits + 1))
            pending.appendleft(ShardTask(task.lo, mid, 1,
                                         task.splits + 1))
        elif kind == "transient" and task.attempt < int(opts.max_retries):
            n_retries += 1
            opts.sleep(float(opts.backoff_s) * 2 ** (task.attempt - 1))
            pending.appendleft(dataclasses.replace(
                task, attempt=task.attempt + 1))
        else:
            quarantined.append(_quarantine(
                checkpoint_dir, task.lo, task.hi, kind=kind, error=error,
                attempts=task.attempt))

    try:
        while pending or executor.n_inflight:
            while pending and executor.idle():
                task = pending.popleft()
                die = False
                if opts.faults is not None:
                    try:
                        opts.faults.check(task.lo, task.hi, task.attempt,
                                          n_completed=n_completed)
                    except BaseException as exc:  # noqa: BLE001
                        kind = classify_failure(exc)
                        if isinstance(exc, KillWorker) \
                                and executor.can_kill_worker:
                            # submit with the die flag: the TARGET worker
                            # SIGKILLs itself with this shard in flight,
                            # exercising the real death/respawn path
                            die = True
                        else:
                            executed.append({
                                "lo": task.lo, "hi": task.hi,
                                "attempt": task.attempt,
                                "status": "fault", "kind": kind,
                                "error": str(exc)})
                            if kind == "kill":
                                raise   # simulated SIGKILL: no cleanup
                            fail(task, kind, str(exc))
                            continue
                executor.submit(task, die=die)
            if executor.n_inflight == 0:
                continue                # every submission faulted
            with span("campaign.wait") as wait_sp:
                out = executor.wait_any()
            dispatch_wait_s += wait_sp.seconds
            task = out.task
            if out.ok:
                entry = {"lo": task.lo, "hi": task.hi,
                         "attempt": task.attempt, "status": "ok"}
                if out.worker is not None:
                    entry["worker"] = out.worker
                if (task.lo, task.hi) in done_ranges:
                    # duplicate redelivery (a retried shard whose first
                    # completion was salvaged from a dying worker):
                    # merging is dedup-safe, but don't double-checkpoint
                    entry["duplicate"] = True
                    executed.append(entry)
                    continue
                done_ranges.add((task.lo, task.hi))
                writer.submit(task.lo, task.hi, out.payload,
                              attempts=task.attempt, splits=task.splits,
                              parent=out.span)
                results.append(out.result)
                executed.append(entry)
                n_completed += 1
            else:
                entry = {"lo": task.lo, "hi": task.hi,
                         "attempt": task.attempt, "status": "fault",
                         "kind": out.kind, "error": out.error}
                if out.worker is not None:
                    entry["worker"] = out.worker
                executed.append(entry)
                if out.kind == "kill":
                    raise out.exc       # simulated SIGKILL: no cleanup
                fail(task, out.kind, out.error)
    except BaseException as exc:  # noqa: BLE001 - re-raised below
        if classify_failure(exc) == "kill":
            # abrupt teardown: workers are killed, not drained — but the
            # writer still publishes shards that COMPLETED before the
            # kill point, so the drill's on-disk state is deterministic
            graceful = False
        raise
    finally:
        executor.close(graceful=graceful)
        writer.close()                  # flush-and-barrier (never raises)
    writer.raise_if_failed()

    # ----- merge + report -------------------------------------------------
    if not results:
        raise RuntimeError(
            f"campaign produced no completed shards — all "
            f"{len(quarantined)} dispatched ranges quarantined; see "
            f"{os.path.join(checkpoint_dir, 'quarantine')} for errors")
    with span("campaign.merge"):
        merged = merge_stream_results(results, k=int(sweep["k"]))
        coverage = merged_coverage(results)
        missing = missing_ranges(manifest.shards, coverage)
    report = {
        "schema": 1, "resumed": resumed,
        "n_planned": len(manifest.shards),
        "n_loaded": len(loaded), "n_executed": len(executed),
        "n_completed": len(results), "n_retries": n_retries,
        "n_splits": n_splits, "executed": executed,
        "quarantined": quarantined,
        "coverage": [[lo, hi] for lo, hi in coverage],
        "missing": [[lo, hi] for lo, hi in missing],
        "partial": bool(missing), "wall_s": run_sp.seconds,
        "workers": n_workers,
        "dispatch_wait_s": round(dispatch_wait_s, 6),
        "io_s": round(writer.io_s, 6),
        "io_overlap_frac": round(writer.io_overlap_frac, 6),
        "worker_startup_s": round(getattr(executor, "startup_s", 0.0), 6),
        "worker_step_compiles": sorted(
            getattr(executor, "worker_step_compiles", {}).values()),
    }
    with span("campaign.report"):
        atomic_write_json(os.path.join(checkpoint_dir, REPORT_NAME), report)
    return _stream_to_explore(space, merged, campaign=report)


@traced("resume")
def resume(manifest_path: str, *, space=None, mesh=None,
           backend: str = "auto", workers: Optional[int] = None,
           options: Optional[CampaignOptions] = None,
           on_corrupt: str = "refuse"):
    """Resume a campaign from its manifest (path or directory).

    Rebuilds the :class:`DesignSpace` from the manifest payload when
    ``space`` is not given, verifies signatures, re-dispatches ONLY the
    index ranges without a verified shard checkpoint, and returns the
    merged result.  Raises :class:`CampaignMismatchError` when the
    current code resolves the space or plan-bank layout differently
    from the manifest.
    """
    directory = (manifest_path if os.path.isdir(manifest_path)
                 else os.path.dirname(os.path.abspath(manifest_path)))
    manifest = CampaignManifest.load(manifest_path)
    if space is None:
        space = manifest.rebuild_space()
    return run_campaign(space, directory, mesh=mesh, backend=backend,
                        workers=workers, options=options,
                        on_corrupt=on_corrupt)
