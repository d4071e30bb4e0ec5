"""In-program spans and counters: the timing system of the sweep and
campaign paths.

    from repro import spans

    with spans.span("sweep.prep", lo=0) as sp:
        ...
    sp.seconds                      # the span's duration
    spans.count("sweep.dispatches", 234)
    spans.recent()                  # the kept calls, oldest first

A span opened while no span is open on its thread is a *root*: one
public call (``explore``, ``resume``, a service segment).  Every span
opened under it shares the root's id, and each records its name, start
and end (``time.perf_counter_ns()``), parent, thread and attributes.
A span also opens a ``jax.profiler.TraceAnnotation`` of the same name
and attributes, so a profile taken with ``jax.profiler.trace`` holds
the program's spans on its host plane, on the profiler's clock: a
record lines up with its annotation up to one constant offset.

``count(name, n)`` adds to a counter of the innermost open span, of its
root and of the process totals (:func:`counters`).  JAX's compile events are
counted the same way, and also on the innermost open span, which is how
a recompile is named: ``compile.trace_s`` (jaxpr tracing),
``compile.lower_s`` (lowering to MLIR, Pallas to Mosaic included),
``compile.backend_s`` (the XLA / Mosaic backend compile) and
``compile.n`` (backend compiles).

Records live in memory only, and memory is bounded: the last
:data:`MAX_ROOTS` roots are kept, each with at most
:data:`MAX_SPANS_PER_ROOT` span records; a span past the cap is still
timed (its caller reads ``seconds``) and counted in the root's
``dropped``.  The recorder is always on.  With the profiler off a span
costs two clock reads, a no-op annotation and an append.

Spans nest per thread.  Work handed to another thread takes its parent
from the caller: ``span(name, parent=handle)`` on the other thread, or
``carry(fn)``, which runs ``fn`` under the caller's innermost span.
"""
from __future__ import annotations

import collections
import functools
import itertools
import threading
import time
from typing import Callable, Dict, List, Optional

import jax

#: roots kept, oldest dropped first
MAX_ROOTS = 256
#: span records kept per root (a 1e9-point sweep records about 250, a
#: campaign call about 30 a 2**26-point shard: 3.3e9 points, 50 shards,
#: about 1,500)
MAX_SPANS_PER_ROOT = 4096

#: JAX compile events and the counters they add to
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower_s",
    "/jax/core/compile/backend_compile_duration": "compile.backend_s",
}
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"


class _Local(threading.local):
    """Each thread's stack of open spans, innermost last."""

    def __init__(self):
        self.stack: List[Span] = []


_ids = itertools.count(1)
_lock = threading.Lock()
_local = _Local()
_roots: "collections.deque[_Root]" = collections.deque(maxlen=MAX_ROOTS)
_totals: Dict[str, float] = {}


class _Root:
    """The records of one root's spans, as columns of plain values, and
    the root's counters.  Columns keep the records from adding objects
    for the garbage collector (only non-empty attributes do): spans kept
    as objects added a few hundred a sweep, enough to move a full
    collection into a sweep's finalize."""
    __slots__ = ("ids", "names", "parents", "starts", "ends", "threads",
                 "attrs", "span_counters", "counters", "dropped")

    def __init__(self):
        self.ids: List[int] = []
        self.names: List[str] = []
        self.parents: List[Optional[int]] = []
        self.starts: List[int] = []
        self.ends: List[Optional[int]] = []
        self.threads: List[int] = []
        #: a span's attributes, or None for none
        self.attrs: List[Optional[Dict]] = []
        #: span index -> the counters charged to that span
        self.span_counters: Dict[int, Dict[str, float]] = {}
        self.counters: Dict[str, float] = {}
        self.dropped = 0

    def records(self) -> List[Dict]:
        return [dict(id=self.ids[i], name=self.names[i],
                     parent=self.parents[i], start_ns=self.starts[i],
                     end_ns=self.ends[i], thread=self.threads[i],
                     attrs=dict(self.attrs[i] or {}),
                     counters=dict(self.span_counters.get(i, {})))
                for i in range(len(self.ids))]


class Span:
    """One timed interval; ``seconds`` reads its duration (the time so
    far while it is open)."""
    __slots__ = ("name", "attrs", "id", "root_id", "start_ns", "end_ns",
                 "_parent", "_rec", "_index", "_annotation")

    def __init__(self, name: str, parent: Optional["Span"],
                 attrs: Dict):
        self.name = name
        self.attrs = attrs
        self.id = next(_ids)
        self.root_id = self.id
        self.start_ns = 0
        self.end_ns: Optional[int] = None
        self._parent = parent
        self._rec: Optional[_Root] = None
        self._index = -1
        self._annotation = None

    @property
    def seconds(self) -> float:
        end = self.end_ns if self.end_ns is not None \
            else time.perf_counter_ns()
        return (end - self.start_ns) * 1e-9

    def _start(self) -> "Span":
        parent = self._parent
        if parent is None:
            stack = _local.stack
            parent = stack[-1] if stack else None
        self._parent = None
        with _lock:
            if parent is None:
                rec = _Root()
                _roots.append(rec)
            else:
                rec = parent._rec
                self.root_id = parent.root_id
            self._rec = rec
            if len(rec.ids) < MAX_SPANS_PER_ROOT:
                self._index = len(rec.ids)
                rec.ids.append(self.id)
                rec.names.append(self.name)
                rec.parents.append(parent.id if parent is not None
                                   else None)
                rec.starts.append(0)
                rec.ends.append(None)
                rec.threads.append(threading.get_ident())
                rec.attrs.append(self.attrs or None)
            else:
                rec.dropped += 1
        self.start_ns = time.perf_counter_ns()
        if self._index >= 0:
            rec.starts[self._index] = self.start_ns
        return self

    def __enter__(self) -> "Span":
        self._annotation = jax.profiler.TraceAnnotation(self.name,
                                                        **self.attrs)
        self._annotation.__enter__()
        self._start()
        _local.stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        self.close()
        stack = _local.stack
        if stack and stack[-1] is self:
            stack.pop()
        self._annotation.__exit__(*exc)
        self._annotation = None

    def close(self) -> None:
        """End the span (``__exit__`` does; call it on a :func:`start`
        span)."""
        if self.end_ns is None:
            self.end_ns = time.perf_counter_ns()
            if self._index >= 0:
                self._rec.ends[self._index] = self.end_ns


def span(name: str, *, parent: Optional[Span] = None, **attrs) -> Span:
    """A context manager that times one interval of the program.

    ``parent`` is given only where the span runs on another thread than
    the span it belongs under (a writer thread, a pool); otherwise the
    innermost open span of this thread is the parent, and with none open
    the span is a root."""
    return Span(name, parent, attrs)


def start(name: str, **attrs) -> Span:
    """Open a span that outlives the call that opens it (a worker pool's
    start-up); it is not a parent of later spans.  End it with
    ``close()``."""
    return Span(name, None, attrs)._start()


def current() -> Optional[Span]:
    """The innermost open span of this thread, or None."""
    stack = _local.stack
    return stack[-1] if stack else None


def carry(fn: Callable) -> Callable:
    """``fn`` bound to the caller's innermost span: run on another thread,
    the spans it opens fall under that span's root."""
    parent = current()

    @functools.wraps(fn)
    def run(*args, **kwargs):
        stack = _local.stack
        if parent is not None:
            stack.append(parent)
        try:
            return fn(*args, **kwargs)
        finally:
            if parent is not None and stack and stack[-1] is parent:
                stack.pop()
    return run


def traced(name: str) -> Callable:
    """Decorator: the call runs inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return run
    return wrap


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name`` of the innermost open span, of
    its root, and of the process totals."""
    sp = current()
    with _lock:
        _totals[name] = _totals.get(name, 0) + n
        if sp is not None:
            targets = [sp._rec.counters]
            if sp._index >= 0:
                targets.append(sp._rec.span_counters.setdefault(
                    sp._index, {}))
            for c in targets:
                c[name] = c.get(name, 0) + n


def counters() -> Dict[str, float]:
    """The process totals of every counter."""
    with _lock:
        return dict(_totals)


def reset_counters(prefix: str) -> None:
    """Zero the process totals whose names start with ``prefix``."""
    with _lock:
        for key in [k for k in _totals if k.startswith(prefix)]:
            del _totals[key]


def recent() -> List[Dict]:
    """The kept roots, oldest first: each the root's own record plus its
    ``spans`` (in start order, the root first), ``counters`` and the
    number of span records ``dropped``."""
    out = []
    with _lock:
        for rec in _roots:
            records = rec.records()
            out.append(dict(records[0], spans=records,
                            counters=dict(rec.counters),
                            dropped=rec.dropped))
    return out


def _on_compile_event(event: str, duration_secs: float, **_kw) -> None:
    key = _COMPILE_EVENTS.get(event)
    if key is None:
        return
    sp = current()
    n = 1 if event == _BACKEND_EVENT else 0
    with _lock:
        targets = [_totals]
        if sp is not None:
            targets.append(sp._rec.counters)
            if sp._index >= 0:
                targets.append(sp._rec.span_counters.setdefault(
                    sp._index, {}))
        for c in targets:
            c[key] = c.get(key, 0.0) + duration_secs
            if n:
                c["compile.n"] = c.get("compile.n", 0) + n


jax.monitoring.register_event_duration_secs_listener(_on_compile_event)
