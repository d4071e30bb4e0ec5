"""Request coalescing: compatible tenants ride ONE step executable.

The PlanBank streaming engine compiles its step executable on SHAPES
only — bank dims, grid shape, chunk geometry, scan length, reduction
params, backend — while coefficients and axis values are traced inputs.
Two requests whose shapes agree therefore share an executable no matter
how different their design-point VALUES are.  This module exploits that:

* :func:`prepare_request` resolves a request exactly the way
  ``_stream_impl`` would (same chunk rounding/clamping, same superchunk
  default, one hoisted ``_StreamPrep``) into a :class:`PreparedRequest`;
* :func:`compat_key` projects out precisely the quantities that enter
  the ``_fused_exec`` cache key — equal compat keys GUARANTEE one shared
  executable (the one-executable invariant, per group, asserted in
  tests/test_serve.py);
* :func:`run_group` round-robins superchunk-aligned ``index_range``
  segments across a group's members — N tenants interleaved through one
  warm executable, each folding its own segments back together with the
  campaign merge algebra (associative, parity-exact) and streaming
  best-so-far snapshots as its segments land;
* :func:`run_solo` is the fallback for a group of one: a single
  full-range dispatch, streaming partials through the ``on_partial``
  hook instead.  Incompatible requests always land here — coalescing is
  an optimization, never an error.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, List, Optional, Tuple

from ..campaign.merge import merge_stream_results
from ..core.shard_sweep import (_DEFAULT_SUPERCHUNK, _StreamPrep,
                                _mesh_key, _prepare_stream, _stream_impl,
                                StreamResult)
from ..explore.api import _DEFAULT_CHUNK
from .errors import RequestTimeout
from .stream import PartialEmitter

__all__ = ["GroupMember", "PreparedRequest", "compat_key",
           "plan_segments", "prepare_request", "run_group", "run_solo"]


@dataclasses.dataclass
class PreparedRequest:
    """One request resolved to dispatch geometry (see module doc)."""
    space: object                #: the DesignSpace
    k: int
    metric: str
    backend: str                 #: RESOLVED lane ("pallas" / "xla")
    block_points: int
    chunk: int                   #: device-divisible, span-clamped
    s_len: int                   #: scan length (chunks per dispatch)
    cpv: int                     #: chunk ordinals per variant
    prep: _StreamPrep            #: hoisted lowering/bank/tables

    @property
    def total(self) -> int:
        return self.prep.total


def prepare_request(space, *, k: int, metric: str, backend: str,
                    chunk_size: Optional[int], block_points: int,
                    superchunk: Optional[int], mesh) -> PreparedRequest:
    """Resolve a request the way ``_stream_impl`` would.

    The chunk rounding/clamping and superchunk default MIRROR the
    streaming driver exactly, so a solo ``explore()`` of the same space
    with the same arguments resolves to the same executable key — serve
    traffic and library calls share warm executables both ways.
    ``backend`` must already be resolved ("pallas"/"xla").
    """
    ndev = int(mesh.devices.size)
    prep = _prepare_stream(list(space.algorithms), space.grids,
                           soc_node=space.soc_node)
    chunk = -(-max(int(chunk_size or _DEFAULT_CHUNK), 1) // ndev) * ndev
    chunk = min(chunk, -(-prep.n_var // ndev) * ndev)
    cpv = -(-prep.n_var // chunk)
    n_ord = cpv * prep.n_variants
    s_len = (max(1, int(superchunk)) if superchunk
             else min(max(n_ord, 1), _DEFAULT_SUPERCHUNK))
    return PreparedRequest(
        space=space, k=int(k), metric=metric, backend=backend,
        block_points=int(block_points), chunk=chunk, s_len=s_len,
        cpv=cpv, prep=prep)


def compat_key(pr: PreparedRequest, mesh) -> tuple:
    """Dispatch-compatibility key: the shape-only projection of the
    ``_fused_exec`` executable cache key.  Equal keys => the group
    shares ONE compiled step executable."""
    return ("serve", pr.backend, _mesh_key(mesh), pr.chunk, pr.metric,
            pr.k, pr.block_points, tuple(pr.prep.bank.dims),
            tuple(pr.prep.vgrids[0].shape), pr.prep.n_var,
            pr.prep.lmax, pr.s_len, pr.cpv)


def _ordinal_span(o0: int, o1: int, *, cpv: int, n_var: int,
                  chunk: int) -> Tuple[int, int]:
    """Flat index range covered by chunk ordinals ``[o0, o1)`` (the
    ordinal order is contiguous in the variant-major flat space)."""
    vi, r = divmod(o0, cpv)
    lo = vi * n_var + r * chunk
    vi, r = divmod(o1 - 1, cpv)
    hi = vi * n_var + min((r + 1) * chunk, n_var)
    return lo, hi


def plan_segments(pr: PreparedRequest) -> List[Tuple[int, int]]:
    """Superchunk-aligned ``index_range`` segments covering the space.

    Each segment spans exactly one superchunk's worth of chunk ordinals,
    so every segment is ONE invocation of the shared step executable —
    the round-robin scheduler's unit of fairness.
    """
    n_ord = pr.cpv * pr.prep.n_variants
    return [_ordinal_span(o0, min(o0 + pr.s_len, n_ord), cpv=pr.cpv,
                          n_var=pr.prep.n_var, chunk=pr.chunk)
            for o0 in range(0, n_ord, pr.s_len)]


@dataclasses.dataclass
class GroupMember:
    """A request's slot in a dispatch group (inputs + outcome)."""
    pr: PreparedRequest
    emitter: Optional[PartialEmitter] = None
    #: absolute ``time.perf_counter()`` deadline, or None
    deadline: Optional[float] = None
    # ----- outcome --------------------------------------------------------
    result: Optional[StreamResult] = None
    error: Optional[BaseException] = None
    segments: int = 0
    dispatches: int = 0

    def _expired(self) -> bool:
        return (self.deadline is not None
                and time.perf_counter() > self.deadline)


def _dispatch_segment(member: GroupMember, lo: int, hi: int,
                      mesh) -> StreamResult:
    pr = member.pr
    st = _stream_impl(
        list(pr.space.algorithms), pr.space.grids,
        soc_node=pr.space.soc_node, chunk_size=pr.chunk,
        metric=pr.metric, k=pr.k, mesh=mesh,
        block_points=pr.block_points, index_range=(lo, hi),
        engine="fused", superchunk=pr.s_len, backend=pr.backend,
        _prepared=pr.prep)
    member.segments += 1
    member.dispatches += st.dispatches
    return st


def run_group(members: List[GroupMember], *, mesh) -> None:
    """Round-robin a compatible group through the shared executable.

    Each turn dispatches ONE superchunk segment for the next member with
    work remaining — tenants in a group make proportional progress
    instead of queueing behind each other.  A member whose deadline
    expires between segments fails with :class:`RequestTimeout` (its
    remaining segments are dropped; the others keep going); any other
    per-member failure is likewise contained.  On return every member
    carries either ``result`` (the parity-exact merge of its segments)
    or ``error``.
    """
    work = deque((m, deque(plan_segments(m.pr)), []) for m in members)
    while work:
        member, segments, partials = work.popleft()
        if member._expired():
            member.error = RequestTimeout(
                f"deadline expired after {member.segments} of "
                f"{member.segments + len(segments)} segments")
            continue
        lo, hi = segments.popleft()
        try:
            partials.append(_dispatch_segment(member, lo, hi, mesh))
        except Exception as exc:  # noqa: BLE001 - contained per member
            member.error = exc
            continue
        if segments:
            if member.emitter is not None and member.emitter.want():
                merged = merge_stream_results(partials, k=member.pr.k)
                member.emitter.emit_stream_result(
                    merged, merged.n_points, member.pr.total)
            work.append((member, segments, partials))
        else:
            try:
                member.result = merge_stream_results(partials,
                                                     k=member.pr.k)
            except Exception as exc:  # noqa: BLE001
                member.error = exc


def run_solo(member: GroupMember, *, mesh) -> None:
    """Dispatch one member standalone (full range, one ``_stream_impl``
    call), streaming partials through the driver's ``on_partial``
    hook."""
    if member._expired():
        member.error = RequestTimeout("deadline expired before dispatch")
        return
    pr = member.pr
    emitter = member.emitter

    def hook(done: int, span: int,
             snapshot: Callable[[], StreamResult]) -> None:
        # last-dispatch snapshots are redundant with the final result
        if emitter is not None and done < span and emitter.want():
            emitter.emit_stream_result(snapshot(), done, span)

    try:
        st = _stream_impl(
            list(pr.space.algorithms), pr.space.grids,
            soc_node=pr.space.soc_node, chunk_size=pr.chunk,
            metric=pr.metric, k=pr.k, mesh=mesh,
            block_points=pr.block_points, engine="fused",
            superchunk=pr.s_len, backend=pr.backend,
            on_partial=hook if emitter is not None else None,
            _prepared=pr.prep)
    except Exception as exc:  # noqa: BLE001 - contained per member
        member.error = exc
        return
    member.segments += 1
    member.dispatches += st.dispatches
    member.result = st
