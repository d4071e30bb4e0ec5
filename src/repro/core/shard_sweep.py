"""Sharded, streaming mega-sweeps: one executable, O(1) dispatches.

The PR-1 engine scored one monolithic batch per variant; PR 2 added
sharding + streaming but compiled one executable per variant and
re-materialized every chunk on the host; PR 3 banked the coefficients
(``PlanBank``), moved grid decoding on device and fused step+merge into
ONE executable for the whole sweep.  That left two ceilings (measured on
the 8-forced-device bench lane): the driver still dispatched the fused
executable once per 2^18-point chunk from a Python loop (48 dispatches
per 1.26e7-point mega-sweep), and inside each chunk the staged
``grid_decode`` -> ``evaluate_bank`` -> ``block_stats`` pipeline wrote
the ``(n_axes, B)`` point matrix and the ``B x n_out`` output table to
HBM only for the reducer to collapse them to O(k) scalars.  This module
removes both:

1. **Superchunk scan** — the per-chunk loop moves INSIDE the executable:
   one dispatch runs ``superchunk`` consecutive chunks under a
   ``jax.lax.scan``, each scan step deriving its chunk's ``start`` /
   ``limit`` from the carried chunk ordinal (pure index arithmetic on
   the variant-major flat space), with the banked state donated across
   dispatches.  Dispatches per sweep drop from O(points / chunk) to
   O(points / (superchunk * chunk)).
2. **Fused megakernel** — each scan step evaluates its chunk through the
   Pallas ``fused_sweep`` kernel: decode, banked Eq. 1-17 evaluation
   (``repro.core.batch.build_coeff_compute``) and block top-k/sum/count
   fold in a single pass per block, so only O(k) candidates and ``(V,)``
   scalars ever leave the kernel.  Winning rows re-gather their full
   output schema in an O(k) pass at finalization.
3. **Banked streaming state** — unchanged contract: one ``(V,)`` summary
   state + a global running top-k, merged in-body; chunks align to a
   variant-uniform grid so each chunk broadcasts ONE bank coefficient
   row.  ``chunk_size`` additionally clamps to the per-variant span so
   small-variant sweeps stop dispatching masked tail work (see
   ``StreamResult.occupancy``).

The PR-3 staged path is kept verbatim as the parity oracle
(``engine="staged"``): same grids, same state schema plus the per-chunk
``topk_out`` maintenance, per-chunk Python dispatch.  Tests pin
``engine="fused"`` == ``engine="staged"`` == the monolithic ``sweep()``
oracle at rel 1e-6.

Flat stream indices are variant-major (``variant = g // n_var``).  A
global index exists only on the host, as a Python int: the driver cuts
``[lo, hi)`` into per-variant segments (``split_index_range``), and the
device sees each point as its variant slot plus an int32 offset inside
the variant, so a space may pass 2**31 points as long as each variant
stays under it.  ``index_range=`` streams a sub-range of the flat index
space (the multi-host partitioning and campaign sharding hook).

    from repro.explore import DesignSpace, explore
    res = explore(DesignSpace(["edgaze", "rhythmic"], grids),
                  engine="fused", chunk_size=1 << 18)
    res.topk[0]                        # best design point (full row)
    res.summaries["edgaze/3d_in"]      # per-variant min / mean / argmin
    res.dispatches, res.occupancy      # O(1) dispatch + masked-work audit
    stream_cache_info()                # {"step_compiles": 1, ...}

(the old ``sweep_stream`` entry survives as a ``DeprecationWarning`` shim
delegating through ``explore``)

The compiled-executable cache is LRU-capped (``set_stream_cache_limit``,
default 16 / ``REPRO_STREAM_CACHE_LIMIT``) so long-lived processes that
sweep many distinct grid shapes don't grow it unboundedly; evictions are
surfaced in :func:`stream_cache_info`.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import threading
import warnings
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..compat import shard_map
from ..kernels.fused_sweep import fused_sweep_block, kernel_block
from ..kernels.fused_sweep_xla import fused_sweep_block_xla
from ..kernels.grid_decode import grid_decode
from ..kernels.runtime import (resolve_backend, resolve_interpret,
                               sweep_kernel_mode)
from ..kernels.stream_reduce import block_stats
from ..launch.mesh import make_batch_mesh
from ..spans import count, counters, reset_counters, span
from .batch import (DesignPoints, OUT_KEYS, _hooks_active,
                    build_banked_eval, build_coeff_compute, eval_fn,
                    make_points, points_from_axis_rows)
from .plan import EnergyPlan, _EXTRA_CACHES
from .plan_bank import PlanBank, build_plan_bank, evaluate_bank
from .sweep import (AXES, _normalize_grids, axis_tables, lower_variant,
                    variant_grid)

_BATCH_SPEC = P("batch")
_POINT_SPECS = DesignPoints(*([_BATCH_SPEC] * len(DesignPoints._fields)))

#: default number of chunks folded into one superchunk dispatch (the
#: ``jax.lax.scan`` length); bounded so tiny sweeps don't trace dead scan
#: slots and compile time stays flat
_DEFAULT_SUPERCHUNK = 16


#: variant-local offsets ride int32 on the device
_INDEX_LIMIT = 2 ** 31


def check_variant_span(n_var: int) -> None:
    """Refuse, before anything is traced, a space whose variant-local
    offsets do not fit int32.

    The device holds each point as its variant slot and an int32 offset
    inside the variant, so one variant must span fewer than 2**31
    points; the whole space may be any multiple of that.  A chunk that
    runs past the variant's end may wrap its offsets past 2**31 to
    negative values: the masks (``off >= low``, ``flat >= 0``) drop
    them with the other points past the end.
    """
    if n_var >= _INDEX_LIMIT:
        raise NotImplementedError(
            f"one variant of this space spans {n_var} points; the sweep "
            f"holds a point as its variant and an int32 offset inside "
            f"it, so a variant must span fewer than 2**31 points: sweep "
            f"fewer values on an axis, or split the variant's grid into "
            f"spaces of their own")


def split_index_range(lo: int, hi: int, n_var: int
                      ) -> List[Tuple[int, int, int]]:
    """Cut the flat range ``[lo, hi)`` into per-variant segments.

    Returns ``(slot, local_lo, local_hi)`` for each variant the range
    touches, in flat order; host Python ints throughout, so ``lo`` and
    ``hi`` may pass 2**31 while every local bound stays under ``n_var``.
    """
    out = []
    for vi in range(lo // n_var, -(-hi // n_var)) if hi > lo else ():
        base = vi * n_var
        out.append((vi, max(lo, base) - base, min(hi, base + n_var) - base))
    return out


# the on-device decoder emits axis rows in ChunkedGrid order == AXES order;
# DesignPoints consumes them positionally
assert tuple(AXES) == DesignPoints._fields, (AXES, DesignPoints._fields)


def _mesh_key(mesh) -> tuple:
    return (tuple(mesh.axis_names), tuple(d.id for d in mesh.devices.flat))


def _sharded_fn(plan: EnergyPlan, mesh, keep: bool, hooks: bool):
    """The shard_map-wrapped evaluator (untraced) + its output keys."""
    fn = eval_fn(plan)

    def body(pts: DesignPoints):
        return fn(pts, keep_unit_energies=keep, hooks=hooks)

    probe = jax.eval_shape(body, make_points(plan, mesh.devices.size))
    out_specs = {k: _BATCH_SPEC for k in probe}
    return shard_map(body, mesh=mesh, in_specs=(_POINT_SPECS,),
                     out_specs=out_specs), sorted(probe)


def _sharded_exec(plan: EnergyPlan, mesh, batch: int, keep: bool,
                  hooks: bool):
    """AOT-compiled sharded evaluator for one padded batch size.

    Compilation is timed separately and cached on the plan, so sweeps
    report warm throughput and recompile only on new (mesh, batch, flags)
    combinations.  ``batch`` must be divisible by the mesh size.
    """
    if plan._exec_cache is None:
        plan._exec_cache = {}
    key = ("shard", _mesh_key(mesh), batch, keep, hooks)
    hit = plan._exec_cache.get(key)
    if hit is not None:
        return hit, 0.0
    fn, _keys = _sharded_fn(plan, mesh, keep, hooks)
    with span("grid.compile") as sp:
        exe = jax.jit(fn).lower(make_points(plan, batch)).compile()
    plan._exec_cache[key] = exe
    return exe, sp.seconds


def pad_points(points: DesignPoints, multiple: int
               ) -> Tuple[DesignPoints, int]:
    """Pad the batch axis up to a multiple by repeating the last point.

    Returns ``(padded_points, original_batch)``; callers either slice
    outputs back to the original batch or mask the tail as invalid.
    """
    b = points.batch
    pad = (-b) % max(multiple, 1)
    if pad == 0:
        return points, b
    padded = DesignPoints(*(jnp.concatenate([x, jnp.repeat(x[-1:], pad, 0)])
                            for x in points))
    return padded, b


def evaluate_batch_sharded(plan: EnergyPlan, points: DesignPoints, *,
                           mesh=None, keep_unit_energies: bool = False,
                           timings: Optional[Dict[str, float]] = None,
                           hooks: Optional[bool] = None
                           ) -> Dict[str, np.ndarray]:
    """``evaluate_batch`` with the batch axis sharded across a mesh.

    Drop-in equal to the single-device path (exact same executable per
    shard, so parity holds to f32 roundoff); pads internally to a
    device-divisible batch and slices the padding back off.  ``timings``
    accumulates ``compile_s``/``eval_s`` like ``evaluate_batch``.
    """
    if mesh is None:
        mesh = make_batch_mesh()
    padded, b = pad_points(points, mesh.devices.size)
    hooks = _hooks_active(points) if hooks is None else bool(hooks)
    exe, compile_s = _sharded_exec(plan, mesh, padded.batch,
                                   bool(keep_unit_energies), hooks)
    with span("grid.eval") as sp:
        out = exe(padded)
        out = {k: np.asarray(v)[:b] for k, v in out.items()}
    if timings is not None:
        timings["compile_s"] = timings.get("compile_s", 0.0) + compile_s
        timings["eval_s"] = timings.get("eval_s", 0.0) + sp.seconds
    return out


# ---------------------------------------------------------------------------
# Banked streaming: PlanBank evaluation + on-device grid decoding
# ---------------------------------------------------------------------------
#: compiled step executables keyed on SHAPES only — mesh, chunk, reduction
#: params, bank dims, grid shape, scan length and index dtype.
#: Coefficients and axis values are traced inputs, so re-gridding,
#: re-lowering or swapping algorithms with the same padded dims all hit.
#: LRU-ordered: long-lived processes sweeping many distinct grid shapes
#: evict the stalest executable instead of growing without bound.
_STREAM_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
#: its counters, kept with the process counters of ``repro.spans``
_STREAM_COUNTERS = ("step_compiles", "hits", "evictions")
#: guards the executable cache: concurrent explore() calls (thread-pool
#: tenants, the serve facade) must never double-compile one key, so the
#: whole get-or-compile
#: section of the *_exec factories runs under this lock — the second
#: thread to request a cold key blocks behind the first's compile and
#: then takes the hit path.  Reentrant: a compile that re-enters a
#: cache helper on the same thread must not self-deadlock.
_STREAM_LOCK = threading.RLock()


def _coerce_cache_limit(value, source: str) -> int:
    """Validate a cache-limit setting: an integer >= 1, rejected loudly.

    ``source`` names where the value came from so the error points the
    user at the right knob (the env var or the setter argument).
    """
    if isinstance(value, bool) or not isinstance(value, (int, str)):
        raise TypeError(f"{source} must be an integer >= 1, got "
                        f"{type(value).__name__} {value!r}")
    try:
        limit = int(value)
    except ValueError:
        raise ValueError(f"{source} must be an integer >= 1, got "
                         f"{value!r}") from None
    if limit < 1:
        raise ValueError(f"{source} must be >= 1 (a zero/negative limit "
                         f"would disable executable caching entirely), "
                         f"got {limit}")
    return limit


_STREAM_CACHE_LIMIT = _coerce_cache_limit(
    os.environ.get("REPRO_STREAM_CACHE_LIMIT", "16"),
    "REPRO_STREAM_CACHE_LIMIT")
_EXTRA_CACHES.append(_STREAM_CACHE)     # flushed by lower_cache_clear()


def stream_cache_info() -> Dict[str, int]:
    """Executable-cache counters for the one-executable invariant tests
    (plus LRU ``size`` / ``limit`` / ``evictions`` accounting)."""
    totals = counters()
    with _STREAM_LOCK:
        return dict({key: int(totals.get(f"stream.{key}", 0))
                     for key in _STREAM_COUNTERS},
                    size=len(_STREAM_CACHE), limit=_STREAM_CACHE_LIMIT)


def stream_cache_clear() -> None:
    with _STREAM_LOCK:
        _STREAM_CACHE.clear()
        reset_counters("stream.")


def set_stream_cache_limit(limit: int) -> int:
    """Set the LRU capacity of the step-executable cache; returns the
    previous limit.  Shrinking evicts stalest entries immediately."""
    global _STREAM_CACHE_LIMIT
    limit = _coerce_cache_limit(limit, "set_stream_cache_limit()")
    with _STREAM_LOCK:
        old, _STREAM_CACHE_LIMIT = _STREAM_CACHE_LIMIT, limit
        while len(_STREAM_CACHE) > _STREAM_CACHE_LIMIT:
            _STREAM_CACHE.popitem(last=False)
            count("stream.evictions")
    return old


def _cache_get(key):
    with _STREAM_LOCK:
        hit = _STREAM_CACHE.get(key)
        if hit is not None:
            _STREAM_CACHE.move_to_end(key)
            count("stream.hits")
        return hit


def _cache_put(key, entry) -> None:
    with _STREAM_LOCK:
        _STREAM_CACHE[key] = entry
        _STREAM_CACHE.move_to_end(key)
        while len(_STREAM_CACHE) > _STREAM_CACHE_LIMIT:
            _STREAM_CACHE.popitem(last=False)
            count("stream.evictions")


def _validate_index_range(index_range, total: int) -> Tuple[int, int]:
    """Resolve ``index_range`` against the flat index space ``[0, total)``.

    ``None`` means the whole space.  Bounds must be integers with
    ``0 <= lo <= hi <= total``; reversed and out-of-bounds ranges are
    rejected with the valid span in the message (campaign shards and
    multi-host partitions both feed through here, so a bad split must
    fail loudly instead of silently sweeping the wrong points).  An
    empty range (``lo == hi``) is valid and yields a well-formed empty
    result.
    """
    if index_range is None:
        return 0, int(total)
    try:
        lo_raw, hi_raw = index_range
    except (TypeError, ValueError):
        raise ValueError(f"index_range must be a (lo, hi) pair, got "
                         f"{index_range!r}") from None
    try:
        lo, hi = int(lo_raw), int(hi_raw)
    except (TypeError, ValueError):
        raise ValueError(f"index_range bounds must be integers, got "
                         f"({lo_raw!r}, {hi_raw!r})") from None
    if lo > hi:
        raise ValueError(f"index_range ({lo}, {hi}) is reversed "
                         f"(lo > hi); valid flat indices span "
                         f"[0, {total}) with lo <= hi")
    if lo < 0 or hi > total:
        raise ValueError(f"index_range ({lo}, {hi}) outside the flat "
                         f"index space; valid flat indices span "
                         f"[0, {total}) with 0 <= lo <= hi <= {total}")
    return lo, hi


def _init_banked_state(k: int, n_out: int, n_variants: int,
                       with_out: bool = True) -> Dict[str, jnp.ndarray]:
    """The device reduction state: the running top-k as (value, variant
    slot, int32 offset inside the variant) and per variant its feasible
    count, metric sum, minimum and the offset of that minimum."""
    state = dict(
        topk_v=jnp.full((k,), jnp.inf, jnp.float32),
        topk_s=jnp.full((k,), -1, jnp.int32),
        topk_i=jnp.full((k,), -1, jnp.int32),
        n_feasible=jnp.zeros((n_variants,), jnp.int32),
        metric_sum=jnp.zeros((n_variants,), jnp.float32),
        metric_min=jnp.full((n_variants,), jnp.inf, jnp.float32),
        argmin=jnp.full((n_variants,), -1, jnp.int32),
    )
    if with_out:
        # the staged oracle path maintains winners' full output rows on
        # device; the fused path re-gathers them at finalization instead
        state["topk_out"] = jnp.zeros((k, n_out), jnp.float32)
    return state


def _merge_candidates(c: Dict[str, jnp.ndarray], v,
                      state: Dict[str, jnp.ndarray], k: int,
                      with_out: bool) -> Dict[str, jnp.ndarray]:
    """Fold one chunk's O(k) partials into the running banked state.

    ``v`` is the chunk's (traced) variant slot and the candidates'
    indices are int32 offsets inside it.  All update ops are neutral for
    an all-masked chunk (counts 0, mins +inf, candidates +inf), which is
    what makes dead scan slots in the superchunk path semantically free.
    Chunks arrive in flat order, so a tie keeps the state's entry: the
    lower flat index, as ``lax.top_k`` keeps the lower position.
    """
    s = jnp.argmin(c["mins"])                 # first-min shard wins
    c_min = c["mins"][s]
    c_arg = c["amin_i"][s]
    merged_v = jnp.concatenate([state["topk_v"], c["cand_v"]])
    neg2, sel = jax.lax.top_k(-merged_v, k)
    old_min = state["metric_min"][v]
    out = dict(
        topk_v=-neg2,
        topk_s=jnp.concatenate([state["topk_s"], jnp.full(
            c["cand_i"].shape, v, jnp.int32)])[sel],
        topk_i=jnp.concatenate([state["topk_i"], c["cand_i"]])[sel],
        n_feasible=state["n_feasible"].at[v].add(
            jnp.sum(c["counts"]).astype(jnp.int32)),
        metric_sum=state["metric_sum"].at[v].add(jnp.sum(c["sums"])),
        metric_min=state["metric_min"].at[v].min(c_min),
        argmin=state["argmin"].at[v].set(
            jnp.where(c_min < old_min, c_arg, state["argmin"][v])),
    )
    if with_out:
        out["topk_out"] = jnp.concatenate([state["topk_out"],
                                           c["cand_out"]])[sel]
    return out


def _banked_step(bank: PlanBank, mesh, metric: str, k: int, chunk: int,
                 block_points: int, shape: Tuple[int, ...], n_var: int):
    """Build the (untraced) STAGED banked chunk step + its output keys.

    This is the PR-3 parity oracle: per chunk, the shard body runs the
    three staged device passes — ``grid_decode`` kernel, banked
    ``evaluate_bank`` evaluator, ``block_stats`` kernel + full-chunk
    ``top_k`` — and the merge maintains winners' output rows on device.
    The driver cuts the range into per-variant segments, so the whole
    chunk shares one variant ``v`` (a traced slot, so the executable
    serves every variant): its coefficient row is a broadcast dynamic
    slice of the bank, its axis table a slice of the table bank, and
    ``start`` / ``limit`` are int32 offsets inside it.  ``limit`` masks
    both the variant's end and the sweep's ``index_range`` end.
    """
    ndev = int(mesh.devices.size)
    assert chunk % ndev == 0, (chunk, ndev)
    shard = chunk // ndev
    bp = min(block_points, shard)
    kk = min(k, shard)          # a shard only holds `shard` candidates
    _, fn_uniform = build_banked_eval(bank.dims)
    out_keys = list(OUT_KEYS)      # fixed schema; no eval_shape probe
    if metric not in out_keys:
        raise KeyError(f"unknown stream metric {metric!r}; valid: "
                       f"{out_keys}")

    def shard_body(v, start, limit, tables, bank_arrays):
        s0 = start + jax.lax.axis_index("batch").astype(jnp.int32) * shard
        # one decode block per shard: the kernel is gather-bound, so
        # grid iterations only add interpreter dispatch overhead
        table = jax.lax.dynamic_index_in_dim(tables, v, 0, keepdims=True)
        vals, _vid = grid_decode(table, s0, shape=shape, n_var=n_var,
                                 total=n_var, chunk=shard,
                                 block_points=shard)
        flat = s0 + jnp.arange(shard, dtype=jnp.int32)
        valid = (flat >= 0) & (flat < limit)    # wrapped past 2**31: < 0
        points = points_from_axis_rows(vals)
        out = fn_uniform(bank_arrays, v, points)
        ok = out["feasible"] & valid
        metric_v = out[metric].astype(jnp.float32)

        # per-shard summary partials: Pallas segment-min/sum, folded to
        # scalars in-body so only O(k) values cross the mesh
        mins, amins, sums, counts = block_stats(metric_v, ok,
                                                block_points=bp)
        g = jnp.argmin(mins)
        amin_i = s0 + g.astype(jnp.int32) * bp + amins[g]

        # per-shard global top-k candidates (ascending; invalids +inf)
        neg, pos = jax.lax.top_k(jnp.where(ok, -metric_v, -jnp.inf), kk)
        return dict(
            cand_v=-neg,
            cand_i=flat[pos],
            cand_out=jnp.stack([out[key][pos].astype(jnp.float32)
                                for key in out_keys], axis=1),
            mins=mins[g][None], amin_i=amin_i[None],
            sums=jnp.sum(sums)[None],
            counts=jnp.sum(counts)[None])

    partial_keys = ("cand_v", "cand_i", "cand_out", "mins",
                    "amin_i", "sums", "counts")
    in_specs = (P(), P(), P(), P(),
                jax.tree.map(lambda _: P(), bank.arrays))
    sharded = shard_map(shard_body, mesh=mesh, in_specs=in_specs,
                        out_specs={key: _BATCH_SPEC
                                   for key in partial_keys})

    def chunk_step(v, start, limit, tables, bank_arrays, state):
        c = sharded(v, start, limit, tables, bank_arrays)
        return _merge_candidates(c, v, state, k, True), c["counts"]

    return chunk_step, out_keys


def _banked_exec(bank: PlanBank, mesh, metric: str, k: int, chunk: int,
                 block_points: int, shape: Tuple[int, ...], n_var: int,
                 lmax: int, tables):
    """The cached STAGED fused chunk AOT executable for this sweep SHAPE."""
    key = ("banked", _mesh_key(mesh), chunk, metric, k, block_points,
           tuple(bank.dims), tuple(shape), n_var, lmax)
    with _STREAM_LOCK:
        hit = _cache_get(key)
        if hit is not None:
            return hit
        with span("step.lower"):
            chunk_step, out_keys = _banked_step(bank, mesh, metric, k,
                                                chunk, block_points, shape,
                                                n_var)
            zero = jnp.asarray(0, jnp.int32)
            state0 = _init_banked_state(k, len(out_keys),
                                        bank.dims.n_variants)
            lowered = jax.jit(chunk_step, donate_argnums=(5,)).lower(
                zero, zero, zero, tables, bank.arrays, state0)
        with span("step.compile"):
            exe = lowered.compile(compiler_options=_compiler_opts())
        count("stream.step_compiles")
        # warm the dispatch path on a no-op chunk: limit=0 makes every
        # point invalid, so counts are 0, every candidate metric is +inf
        # and the state is semantically untouched
        with span("step.warm"):
            state0, counts = exe(zero, zero, zero, tables, bank.arrays,
                                 state0)
            jax.block_until_ready(counts)
        entry = (exe, out_keys)
        _cache_put(key, entry)
        return entry


def _compiler_opts():
    # on CPU the expensive LLVM passes buy nothing measurable for this
    # program but cost ~15% of the XLA wall time (benchmarked on the
    # 8-device forced-host lane); TPU/GPU keep their defaults
    return ({"xla_llvm_disable_expensive_passes": True}
            if jax.default_backend() == "cpu" else None)


# ---------------------------------------------------------------------------
# Fused engine: superchunk scan over megakernel chunk steps
# ---------------------------------------------------------------------------
def _fused_step(bank: PlanBank, mesh, metric: str, k: int, chunk: int,
                block_points: int, shape: Tuple[int, ...], lmax: int,
                s_len: int, cpv: int, backend: str = "pallas"):
    """Build the (untraced) superchunk scan step + its output key list.

    One call evaluates ``s_len`` consecutive chunk ordinals: ordinal
    ``c`` is chunk ``c % cpv`` of variant slot ``c // cpv`` (``cpv``
    chunks cover a variant's span), which starts at int32 offset ``(c %
    cpv) * chunk`` inside the variant.  The host cut the sweep's range
    into per-variant segments: ``lows`` / ``limits`` hold each
    variant's segment as int32 offsets (``[0, 0)`` where the range
    misses it).  Scan step ``c`` runs its chunk through the fused
    megakernel shard body with its variant's coefficient row, axis table
    and segment bounds, and folds the O(k) partials into the
    scan-carried banked state, so a superchunk crosses variant
    boundaries without a dead slot.  Ordinals at or past ``c_hi`` are
    skipped by a scalar ``lax.cond`` (the carry passes through untouched
    — bit-identical to merging an all-masked chunk), so a mostly-dead
    superchunk costs only its live slots and the trailing superchunk
    needs no special-casing.  Only the metric rides the kernel; winners'
    full output rows are re-gathered by the driver at finalization.

    ``backend`` (already resolved: "pallas" or "xla") picks the fused
    megakernel implementation — ``pallas_call`` (Mosaic on TPU, Pallas
    interpreter elsewhere) or the pure-``jnp`` twin XLA compiles
    natively; both share the exact block reduction contract, so the
    merge path is backend-independent.
    """
    ndev = int(mesh.devices.size)
    assert chunk % ndev == 0, (chunk, ndev)
    shard = chunk // ndev
    compute = build_coeff_compute(bank.dims)
    if backend == "xla":
        # XLA fuses across block boundaries itself; bp only bounds the
        # top_k reduction width
        bp = max(min(block_points, shard), 1)
        block_fn = fused_sweep_block_xla
    else:
        interpret = resolve_interpret(None)
        # one kernel block per shard on the interpreter (grid steps only
        # add emulation overhead there); compiled Mosaic tiles by
        # block_points; either rounds up to whole tile rows
        bp = kernel_block(shard if interpret else block_points, shard)
        block_fn = functools.partial(fused_sweep_block, interpret=interpret)
    kk = min(k, shard)
    out_keys = list(OUT_KEYS)
    if metric not in out_keys:
        raise KeyError(f"unknown stream metric {metric!r}; valid: "
                       f"{out_keys}")

    def shard_body(start, low, limit, table, row):
        s0 = start + jax.lax.axis_index("batch").astype(jnp.int32) * shard
        cv, cl, sums, counts = block_fn(
            table, row, s0, low, limit, compute=compute,
            metric=metric, axis_names=tuple(AXES), shape=tuple(shape),
            chunk=shard, block_points=bp, kk=kk)
        # fold the (G, kk) block candidates to this shard's top-kk
        with jax.named_scope("topk_merge"):
            neg, pos = jax.lax.top_k(-cv.reshape(-1), kk)
            cand_i = s0 + (pos // kk) * bp + cl.reshape(-1)[pos]
            g = jnp.argmin(cv[:, 0])
            amin_i = s0 + g.astype(jnp.int32) * bp + cl[g, 0]
            return dict(
                cand_v=-neg, cand_i=cand_i,
                mins=cv[g, 0][None], amin_i=amin_i[None],
                sums=jnp.sum(sums)[None], counts=jnp.sum(counts)[None])

    partial_keys = ("cand_v", "cand_i", "mins", "amin_i", "sums",
                    "counts")
    sharded = shard_map(shard_body, mesh=mesh,
                        in_specs=(P(), P(), P(), P(), P()),
                        out_specs={key: _BATCH_SPEC
                                   for key in partial_keys})

    def superchunk(c0, lows, limits, c_hi, table2, bank_arrays, state):
        def live(c, st):
            v = c // cpv
            # the chunk's variant picks its coefficient row, its axis
            # table and its segment of the range
            row = jax.lax.dynamic_index_in_dim(
                bank_arrays["fused"], v, 0, keepdims=True)     # (1, W)
            table = jax.lax.dynamic_slice_in_dim(
                table2, v * lmax, lmax, axis=1)         # (n_axes, Lmax)
            parts = sharded((c - v * cpv) * chunk, lows[v], limits[v],
                            table, row)
            with jax.named_scope("state_fold"):
                return (_merge_candidates(parts, v, st, k, False),
                        parts["counts"])

        def dead(c, st):
            # a dead slot's kernel output is all-masked (+inf candidates,
            # zero sums/counts) and _merge_candidates is exactly identity
            # on it, so returning the carry untouched is bit-identical —
            # the cond makes the scan's fixed s_len cost proportional to
            # LIVE chunks (campaign shards and index_range tails run the
            # same pinned executable at a fraction of its scan length)
            return st, jnp.zeros((ndev,), jnp.float32)

        def body(st, c):
            return jax.lax.cond(c < c_hi, live, dead, c, st)

        cs = c0 + jnp.arange(s_len, dtype=jnp.int32)
        return jax.lax.scan(body, state, cs)

    return superchunk, out_keys


def _fused_table2(tables):
    """Pre-transpose the axis-value tables into the megakernel's
    ``(n_axes, n_variants * lmax)`` f32 bank layout.

    Done once per sweep on the host side: the layout is
    dispatch-invariant, so recomputing it inside the jitted superchunk
    would re-run the transpose/reshape/cast on every dispatch.
    """
    return jnp.transpose(tables, (1, 0, 2)).reshape(
        tables.shape[1], -1).astype(jnp.float32)


def _fused_exec(bank: PlanBank, mesh, metric: str, k: int, chunk: int,
                block_points: int, shape: Tuple[int, ...], lmax: int,
                table2, s_len: int, cpv: int, backend: str = "pallas"):
    """The cached superchunk AOT executable for this sweep SHAPE.

    ``backend`` joins the cache key: the Pallas and XLA lanes are
    distinct executables (one each — the per-backend one-executable
    invariant is asserted in tests/test_fused_sweep.py).
    """
    key = ("fused", backend, _mesh_key(mesh), chunk, metric, k,
           block_points, tuple(bank.dims), tuple(shape), lmax, s_len, cpv)
    with _STREAM_LOCK:
        hit = _cache_get(key)
        if hit is not None:
            return hit
        with span("step.lower"):
            superchunk, out_keys = _fused_step(
                bank, mesh, metric, k, chunk, block_points, shape, lmax,
                s_len, cpv, backend=backend)
            zero = jnp.asarray(0, jnp.int32)
            bounds = jnp.zeros((bank.dims.n_variants,), jnp.int32)
            state0 = _init_banked_state(k, len(out_keys),
                                        bank.dims.n_variants,
                                        with_out=False)
            lowered = jax.jit(superchunk, donate_argnums=(6,)).lower(
                zero, bounds, bounds, zero, table2, bank.arrays, state0)
        with span("step.compile"):
            exe = lowered.compile(compiler_options=_compiler_opts())
        count("stream.step_compiles")
        # warm the dispatch path on an all-dead superchunk: c_hi=0 turns
        # every scan slot into a no-op, leaving the state untouched
        with span("step.warm"):
            state0, counts = exe(zero, bounds, bounds, zero, table2,
                                 bank.arrays, state0)
            jax.block_until_ready(counts)
        entry = (exe, out_keys)
        _cache_put(key, entry)
        return entry


@dataclasses.dataclass
class _StreamPrep:
    """Lowered, device-resident sweep inputs shared across dispatches.

    Everything here is a pure function of ``(algorithms, grids,
    soc_node)`` — all-f32 arrays and host metadata, no index — so one
    prep serves every ``index_range`` shard of a campaign: the campaign
    runner builds it ONCE and threads it through
    ``_stream_impl(_prepared=...)``, hoisting the per-shard variant
    re-lowering, bank rebuild and table transpose out of the
    shard loop (they dominated campaign fixed overhead).  Read-only
    after construction (thread-safe to share).
    """
    algos: List[str]
    labels: List[str]
    valgos: List[str]
    vnames: List[str]
    plans: List[EnergyPlan]
    vgrids: List
    n_var: int
    n_variants: int
    total: int
    tables: jnp.ndarray          # (V, n_axes, Lmax) f32 axis-value bank
    bank: PlanBank
    lmax: int
    table2: jnp.ndarray          # (n_axes, V * Lmax) megakernel layout


def _prepare_stream(algorithm: Union[str, Sequence[str]] = "edgaze",
                    grids: Optional[Dict[str, Sequence]] = None, *,
                    soc_node: int = 22) -> _StreamPrep:
    """Resolve + lower a sweep's variant set once (see _StreamPrep)."""
    algos = [algorithm] if isinstance(algorithm, str) else list(algorithm)
    labels: List[str] = []
    valgos: List[str] = []
    vnames: List[str] = []
    plans: List[EnergyPlan] = []
    vgrids: List = []
    for algo in algos:
        variants, ngrids = _normalize_grids(algo, grids)
        for variant in variants:
            plans.append(lower_variant(algo, variant, soc_node=soc_node))
            labels.append(variant if len(algos) == 1
                          else f"{algo}/{variant}")
            valgos.append(algo)
            vnames.append(variant)
            vgrids.append(variant_grid(plans[-1], ngrids))
    if not all(g.shape == vgrids[0].shape for g in vgrids):
        raise ValueError(f"variant grids disagree on shape: "
                         f"{[g.shape for g in vgrids]}")
    n_var = len(vgrids[0])
    n_variants = len(plans)
    tables = jnp.asarray(axis_tables(vgrids))
    return _StreamPrep(
        algos=algos, labels=labels, valgos=valgos, vnames=vnames,
        plans=plans, vgrids=vgrids, n_var=n_var, n_variants=n_variants,
        total=n_variants * n_var, tables=tables,
        bank=build_plan_bank(plans), lmax=int(tables.shape[2]),
        table2=_fused_table2(tables))


def best_by_algorithm_summaries(summaries: Dict[str, Dict],
                                default_algo: str) -> Dict[str, Dict]:
    """Per-algorithm best variant from a summaries table.

    Shared by :class:`StreamResult` and ``repro.explore.ExploreResult``
    (same ``variant`` / ``algo/variant`` label convention) so the
    grouping and tie handling cannot drift between the two surfaces.
    """
    groups: Dict[str, Dict[str, Dict]] = {}
    for label, summ in summaries.items():
        algo, _, variant = label.rpartition("/")
        groups.setdefault(algo or default_algo, {})[variant] = summ
    out: Dict[str, Dict] = {}
    for algo, subs in groups.items():
        variant, summ = min(subs.items(),
                            key=lambda kv: kv[1]["metric_min"])
        out[algo] = dict(variant=variant, summary=summ,
                         n_feasible=sum(v["n_feasible"]
                                        for v in subs.values()))
    return out


@dataclasses.dataclass
class StreamResult:
    """Bounded result of a streaming mega-sweep.

    ``topk`` rows are ascending by the stream metric and carry the exact
    grid axis values (f64, reconstructed from the flat index) plus every
    model output (f32) and the owning ``algorithm`` / ``variant``.
    ``summaries`` maps variant label (``variant`` or ``algo/variant`` for
    multi-algorithm sweeps) to ``{n, n_feasible, metric_min, metric_mean,
    argmin_index, argmin_point}`` where the mean is over feasible points
    only.  ``dispatches`` counts step-executable invocations;
    ``occupancy`` is valid points / dispatched points (masked variant
    tails and dead superchunk slots are the difference).  ``wall_s`` is
    the sweep's ``sweep`` span, ``compile_s`` its ``sweep.prep`` and
    ``sweep.step`` spans and ``eval_s`` its ``sweep.dispatch`` span
    (``repro.spans``).
    """
    algorithm: str
    metric: str
    k: int
    n_points: int
    n_feasible: int
    n_devices: int
    chunk_size: int
    topk: List[Dict]
    summaries: Dict[str, Dict]
    wall_s: float = 0.0
    compile_s: float = 0.0
    eval_s: float = 0.0
    n_variants: int = 0
    index_lo: int = 0
    index_hi: int = 0
    engine: str = "fused"
    dispatches: int = 0
    superchunk: int = 1
    occupancy: float = 1.0
    n_var: int = 0          # points per variant (flat = slot*n_var + local)
    #: resolved execution backend ("pallas" or "xla") and its kernel mode
    #: tag ("interpret" / "compiled" / "xla") — bench + campaign columns
    backend: str = "pallas"
    kernel_mode: str = ""

    def to_payload(self) -> Dict:
        """JSON-serializable form (the campaign shard-checkpoint body).

        Pure-Python scalars/lists only; ``from_payload`` round-trips it
        bit-exactly (floats survive via repr round-trip).  Built by
        shallow field iteration, not ``dataclasses.asdict`` — every field
        is already a JSON-safe scalar or a dict/list of them, and the
        asdict deep-copy recursion was a measurable per-shard cost in
        campaign checkpointing; the comprehensions below copy the two
        container fields so the payload never aliases ``self``."""
        out = {f.name: getattr(self, f.name)
               for f in dataclasses.fields(self)}
        out["topk"] = [dict(r) for r in self.topk]
        out["summaries"] = {
            label: dict(sm, argmin_point=(dict(sm["argmin_point"])
                                          if sm["argmin_point"] is not None
                                          else None))
            for label, sm in self.summaries.items()}
        return out

    @classmethod
    def from_payload(cls, payload: Dict) -> "StreamResult":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in payload.items() if k in fields})

    @property
    def points_per_sec(self) -> float:
        """Warm streaming throughput (compilation excluded)."""
        return self.n_points / max(self.eval_s, 1e-12)

    def best(self, k: Optional[int] = None) -> List[Dict]:
        """Top-k rows by the stream metric (ascending), feasible only."""
        return self.topk[:k]

    def best_by_algorithm(self) -> Dict[str, Dict]:
        """Per-algorithm best variant by the stream metric.

        Returns ``{algorithm: {"variant", "summary", "n_feasible"}}``:
        ``summary`` is the winning variant's summary entry (its
        ``metric_min``/``argmin_point`` describe the best design;
        ``argmin_point`` is None when nothing was feasible) and
        ``n_feasible`` sums over all the algorithm's variants.  Unlike
        ``topk``, every algorithm is guaranteed a record.
        """
        return best_by_algorithm_summaries(self.summaries, self.algorithm)


def sweep_stream(algorithm: Union[str, Sequence[str]] = "edgaze",
                 grids: Optional[Dict[str, Sequence]] = None, *,
                 soc_node: int = 22, chunk_size: int = 1 << 18,
                 metric: str = "total_j", k: int = 16, mesh=None,
                 block_points: int = 4096,
                 progress: Optional[Callable[[int, int], None]] = None,
                 index_range: Optional[Tuple[int, int]] = None,
                 pipeline_depth: int = 4, engine: str = "fused",
                 superchunk: Optional[int] = None,
                 backend: str = "auto") -> StreamResult:
    """DEPRECATED: use :func:`repro.explore.explore` with a
    :class:`repro.explore.DesignSpace`.

    Thin compatibility shim: builds the equivalent design space, runs it
    through ``explore`` on the requested streaming engine and returns the
    legacy :class:`StreamResult` (the same object ``ExploreResult``
    wraps) — identical machinery, executables and caches.
    """
    warnings.warn(
        "repro.core.shard_sweep.sweep_stream() is deprecated; use "
        "repro.explore.explore(DesignSpace(algorithms, grids), "
        "engine='fused') — the unified ExploreResult exposes the "
        "streaming stats directly",
        DeprecationWarning, stacklevel=2)
    if engine not in ("fused", "staged"):
        raise ValueError(f"unknown engine {engine!r}; "
                         f"valid: ['fused', 'staged']")
    from ..explore import DesignSpace, explore
    algos = [algorithm] if isinstance(algorithm, str) else list(algorithm)
    space = DesignSpace(algorithms=algos, grids=grids, soc_node=soc_node)
    res = explore(space, k=k, metric=metric, engine=engine,
                  chunk_size=chunk_size, mesh=mesh,
                  block_points=block_points, progress=progress,
                  index_range=index_range, pipeline_depth=pipeline_depth,
                  superchunk=superchunk, backend=backend)
    return res.stream_result


def _stream_impl(algorithm: Union[str, Sequence[str]] = "edgaze",
                 grids: Optional[Dict[str, Sequence]] = None, *,
                 soc_node: int = 22, chunk_size: int = 1 << 18,
                 metric: str = "total_j", k: int = 16, mesh=None,
                 block_points: int = 4096,
                 progress: Optional[Callable[[int, int], None]] = None,
                 index_range: Optional[Tuple[int, int]] = None,
                 pipeline_depth: int = 4, engine: str = "fused",
                 superchunk: Optional[int] = None,
                 backend: str = "auto",
                 on_partial: Optional[
                     Callable[[int, int, Callable[[], "StreamResult"]],
                              None]] = None,
                 _prepared: Optional[_StreamPrep] = None) -> StreamResult:
    """Stream a cartesian sweep of any size through ONE executable.

    Same ``grids`` contract as ``sweep()`` (``variant`` + numeric axes;
    missing axes default per variant), but ``algorithm`` may also be a
    list (e.g. ``["edgaze", "rhythmic"]``) — every variant of every
    algorithm is stacked into one :class:`~repro.core.plan_bank.PlanBank`
    and interleaved in a single variant-major flat index space.  Host
    memory is O(1) per dispatch; device state is O(k + V).

    ``engine="fused"`` (default) runs the device-resident path: each
    dispatch executes ``superchunk`` consecutive chunks under an
    in-executable ``lax.scan`` (default auto, capped at
    ``_DEFAULT_SUPERCHUNK``), and each chunk decodes, evaluates and
    reduces in a single Pallas megakernel pass — the decoded point
    matrix and per-point outputs never reach HBM, and winners re-gather
    their full output rows in an O(k) pass at the end.
    ``engine="staged"`` is the PR-3 parity oracle: one Python dispatch
    per chunk through the staged decode/evaluate/reduce pipeline.

    ``chunk_size`` is rounded to a device-divisible size and clamped to
    the per-variant span (small-variant sweeps stop dispatching masked
    tail work — see ``StreamResult.occupancy``); every chunk runs at
    exactly that shape, so the whole sweep compiles ONE step executable
    total (asserted via :func:`stream_cache_info` in tests); re-runs
    with the same shapes hit the LRU executable cache even across
    re-gridding.  The whole space may pass 2**31 points; one variant
    may not (``check_variant_span``).  ``index_range=(lo, hi)`` streams
    only that slice of the flat index space (multi-host partitioning
    hook), cut on the host into per-variant segments;
    ``progress(done, span)`` fires after every dispatch.

    ``on_partial(done, span, snapshot)`` is the partial-result hook (the
    serve layer's streaming-top-k seam): it fires alongside ``progress``
    after every dispatch, and calling the zero-arg ``snapshot()``
    materializes the reduction state SO FAR as a :class:`StreamResult`
    (same finalization as the final result — top-k rows, summaries,
    accounting).  A snapshot drains the in-flight pipeline (device sync
    + O(k) winner re-gather), so callers throttle how often they take
    one; the snapshot closure is only valid until the NEXT dispatch
    (the state buffer is donated), so call it synchronously inside the
    hook or not at all.

    ``backend`` selects the fused megakernel implementation: "pallas"
    (``pallas_call``: Mosaic on TPU, interpreter elsewhere), "xla" (the
    pure-``jnp`` twin XLA compiles natively on any platform) or "auto"
    (Pallas on TPU, XLA elsewhere; ``REPRO_SWEEP_BACKEND`` overrides).
    The staged oracle always runs the Pallas pipeline.  ``_prepared``
    is the campaign runner's hoist hook: a :class:`_StreamPrep` built
    once for the SAME ``(algorithm, grids, soc_node)`` skips per-call
    re-lowering (callers are responsible for that match).
    """
    with span("sweep", engine=engine) as sweep_sp:
        res = _stream_run(
            sweep_sp, algorithm, grids, soc_node=soc_node,
            chunk_size=chunk_size, metric=metric, k=k, mesh=mesh,
            block_points=block_points, progress=progress,
            index_range=index_range, pipeline_depth=pipeline_depth,
            engine=engine, superchunk=superchunk, backend=backend,
            on_partial=on_partial, _prepared=_prepared)
    res.wall_s = sweep_sp.seconds
    return res


def _stream_run(sweep_sp, algorithm, grids, *, soc_node, chunk_size,
                metric, k, mesh, block_points, progress, index_range,
                pipeline_depth, engine, superchunk, backend, on_partial,
                _prepared) -> StreamResult:
    """The body of :func:`_stream_impl`, inside its ``sweep`` span.

    ``compile_s`` is the ``sweep.prep`` and ``sweep.step`` spans,
    ``eval_s`` the ``sweep.dispatch`` span (the dispatch loop, its
    pacing waits and the final drain) and ``wall_s`` the ``sweep`` span.
    """
    if engine not in ("fused", "staged"):
        raise ValueError(f"unknown engine {engine!r}; "
                         f"valid: ['fused', 'staged']")
    if engine == "staged":
        if backend not in (None, "auto", "pallas"):
            raise ValueError(
                f"backend={backend!r} requires engine='fused'; the "
                f"staged parity oracle always runs the Pallas pipeline")
        backend = "pallas"
    else:
        backend = resolve_backend(backend)
    if mesh is None:
        mesh = make_batch_mesh()
    ndev = int(mesh.devices.size)

    with span("sweep.prep") as prep_sp:
        prep = (_prepared if _prepared is not None
                else _prepare_stream(algorithm, grids, soc_node=soc_node))
        algos = prep.algos
        labels, valgos, vnames = prep.labels, prep.valgos, prep.vnames
        plans, vgrids = prep.plans, prep.vgrids
        n_var = prep.n_var
        n_variants = prep.n_variants
        total = prep.total
        # device-divisible chunk, clamped to the per-variant span: chunks
        # are variant-uniform, so any chunk budget beyond one span is
        # masked tail work dispatched on every chunk of a small-variant
        # sweep
        chunk = -(-max(int(chunk_size), 1) // ndev) * ndev
        chunk = min(chunk, -(-n_var // ndev) * ndev)
        lo, hi = _validate_index_range(index_range, total)
        check_variant_span(n_var)
        with span("sweep.split"):
            # [(slot, local lo, local hi)]: every bound the device sees
            # is an int32 offset inside one variant
            segments = split_index_range(lo, hi, n_var)
            if engine == "fused":
                # chunk ordinals: cpv chunks cover a variant's span, and
                # ordinal c is chunk c % cpv of variant c // cpv;
                # [c_lo, c_hi) are the ordinals that meet the range
                cpv = -(-n_var // chunk)
                c_lo = c_hi = 0
                if segments:
                    (v0, vlo, _), (v1, _, vhi) = segments[0], segments[-1]
                    c_lo = v0 * cpv + vlo // chunk
                    c_hi = v1 * cpv + -(-vhi // chunk)
                n_chunks = c_hi - c_lo
                s_len = (max(1, int(superchunk)) if superchunk
                         else min(max(n_chunks, 1), _DEFAULT_SUPERCHUNK))
                if n_variants * cpv + s_len > _INDEX_LIMIT:
                    raise ValueError(
                        f"{n_variants * cpv} chunk ordinals of {chunk} "
                        f"points do not fit int32; raise chunk_size")
                slots = s_len * -(-n_chunks // s_len)
            else:
                s_len = 1
                n_chunks = slots = sum(-(-(vhi - vlo) // chunk)
                                       for _vi, vlo, vhi in segments)
            count("sweep.segments", len(segments))
            count("sweep.slots", slots)
            count("sweep.dead_slots", slots - n_chunks)

    dispatches = 0
    dispatched_points = 0
    compile_s = 0.0

    def _finalize(state, out_keys, n_dispatches, n_dispatched, eval_s,
                  covered) -> StreamResult:
        """Materialize the device reduction state as a StreamResult.

        Runs once at the end of the sweep and, through the
        ``on_partial`` snapshot closure, for every partial-result
        request mid-stream (``covered`` is the points reduced so far;
        per-variant ``n`` in summaries always describes the full
        ``[lo, hi)`` span the state is converging to).  All host work
        is O(k) / O(variants).
        """
        with span("sweep.finalize"):
            with span("finalize.fetch"):
                host = jax.device_get(state)
            with span("finalize.regather"):
                n_win = 0
                while (n_win < len(host["topk_v"])
                       and np.isfinite(host["topk_v"][n_win])):
                    n_win += 1             # fewer than k feasible points
                # winners as (variant slot, offset inside it)
                win = [(int(host["topk_s"][j]), int(host["topk_i"][j]))
                       for j in range(n_win)]
                if engine == "fused" and n_win:
                    # tiny second pass over winners only: the megakernel
                    # never wrote the per-point output table, so the k
                    # winning rows re-gather their full output schema
                    # through the banked evaluator here (padded to k so
                    # every sweep shares one tiny executable)
                    pts_axes = {ax: [] for ax in AXES}
                    for vi, local in win + [win[-1]] * (k - n_win):
                        point = vgrids[vi].point(local)
                        for ax in AXES:
                            pts_axes[ax].append(point[ax])
                    vids = [vi for vi, _ in win] + [win[-1][0]] * (k - n_win)
                    out = evaluate_bank(prep.bank, np.asarray(vids, np.int32),
                                        make_points(plans[0], k, **pts_axes))
                    host["topk_out"] = np.stack(
                        [np.asarray(out[key], np.float32)[:n_win]
                         for key in out_keys], axis=1)
            with span("finalize.assemble"):
                # per-variant valid counts are the segments' lengths —
                # never computed on device
                n_seen = [0] * n_variants
                for vi, vlo, vhi in segments:
                    n_seen[vi] = vhi - vlo
                summaries: Dict[str, Dict] = {}
                n_feasible = 0
                for vi, label in enumerate(labels):
                    nf = int(host["n_feasible"][vi])
                    n_feasible += nf
                    amin = int(host["argmin"][vi])
                    summaries[label] = dict(
                        n=n_seen[vi], n_feasible=nf,
                        metric_min=float(host["metric_min"][vi]),
                        metric_mean=(float(host["metric_sum"][vi]) / nf
                                     if nf else float("nan")),
                        argmin_index=amin,
                        argmin_point=(vgrids[vi].point(amin)
                                      if amin >= 0 else None))

                rows: List[Dict] = []
                for j, (vi, local) in enumerate(win):
                    row = dict(variant=vnames[vi], algorithm=valgos[vi],
                               index=local, **vgrids[vi].point(local))
                    row.update({key: float(host["topk_out"][j][c])
                                for c, key in enumerate(out_keys)})
                    rows.append(row)

                return StreamResult(
                    algorithm="+".join(algos), metric=metric, k=k,
                    n_points=covered, n_feasible=n_feasible,
                    n_devices=ndev, chunk_size=chunk, topk=rows,
                    summaries=summaries, wall_s=sweep_sp.seconds,
                    compile_s=compile_s, eval_s=eval_s,
                    n_variants=n_variants, index_lo=lo, index_hi=hi,
                    engine=engine, dispatches=n_dispatches,
                    superchunk=s_len,
                    occupancy=(covered / n_dispatched if n_dispatched
                               else 1.0),
                    n_var=n_var, backend=backend,
                    kernel_mode=sweep_kernel_mode(backend))

    tables, bank, lmax = prep.tables, prep.bank, prep.lmax
    table2 = prep.table2
    with span("sweep.step") as step_sp:
        if engine == "fused":
            exe, out_keys = _fused_exec(
                bank, mesh, metric, k, chunk, block_points,
                vgrids[0].shape, lmax, table2, s_len, cpv, backend=backend)
        else:
            exe, out_keys = _banked_exec(
                bank, mesh, metric, k, chunk, block_points,
                vgrids[0].shape, n_var, lmax, tables)
        state = _init_banked_state(k, len(out_keys), n_variants,
                                   with_out=engine != "fused")
    compile_s = prep_sp.seconds + step_sp.seconds

    with span("sweep.dispatch") as disp_sp:
        dev = lambda x: jnp.asarray(x, jnp.int32)   # noqa: E731
        inflight: List = []
        if engine == "fused":
            # each variant's segment as int32 offsets, [0, 0) where the
            # range misses the variant
            bounds = np.zeros((2, n_variants), np.int32)
            for vi, vlo, vhi in segments:
                bounds[:, vi] = vlo, vhi
            chi_dev, lows, limits = dev(c_hi), dev(bounds[0]), dev(bounds[1])
            for d0 in range(c_lo, c_hi, s_len):
                state, counts = exe(dev(d0), lows, limits, chi_dev, table2,
                                    bank.arrays, state)
                dispatches += 1
                dispatched_points += s_len * chunk
                # pace on the counts partial so upcoming dispatches
                # overlap device execution without running unboundedly
                # ahead; the state itself is donated to the next
                # superchunk and cannot be blocked on
                inflight.append(counts)
                if len(inflight) > pipeline_depth:
                    with span("sweep.pace"):
                        jax.block_until_ready(inflight.pop(0))
                if progress is not None or on_partial is not None:
                    vi_l, r_l = divmod(min(d0 + s_len, c_hi) - 1, cpv)
                    end = min(vi_l * n_var + min((r_l + 1) * chunk, n_var),
                              hi)
                    done_pts = max(end - lo, 0)
                    if progress is not None:
                        progress(done_pts, hi - lo)
                    if on_partial is not None:
                        # bind loop state by value: the closure is only
                        # valid until the next dispatch donates `state`
                        on_partial(done_pts, hi - lo,
                                   lambda st=state, nd=dispatches,
                                   dpts=dispatched_points,
                                   cov=done_pts: _finalize(
                                       st, out_keys, nd, dpts,
                                       disp_sp.seconds, cov))
        else:
            done = 0
            # one variant per segment, so each chunk is variant-uniform
            # (the evaluator broadcasts one coefficient row); `limit`
            # masks both the variant end and the index_range end
            for vi, vlo, vhi in segments:
                v_dev, limit_dev = dev(vi), dev(vhi)
                for start in range(vlo, vhi, chunk):
                    state, counts = exe(v_dev, dev(start), limit_dev,
                                        tables, bank.arrays, state)
                    dispatches += 1
                    dispatched_points += chunk
                    inflight.append(counts)
                    if len(inflight) > pipeline_depth:
                        with span("sweep.pace"):
                            jax.block_until_ready(inflight.pop(0))
                    done += min(start + chunk, vhi) - start
                    if progress is not None:
                        progress(done, hi - lo)
                    if on_partial is not None:
                        on_partial(done, hi - lo,
                                   lambda st=state, nd=dispatches,
                                   dpts=dispatched_points,
                                   cov=done: _finalize(
                                       st, out_keys, nd, dpts,
                                       disp_sp.seconds, cov))
        with span("sweep.pace"):
            jax.block_until_ready(state["n_feasible"])
    count("sweep.dispatches", dispatches)
    # host-side finalization (all O(k) / O(variants)) — shared with the
    # on_partial snapshot path above
    return _finalize(state, out_keys, dispatches, dispatched_points,
                     disp_sp.seconds, hi - lo)
