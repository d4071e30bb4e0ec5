"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Reads the trace with nothing but ``jax.profiler.ProfileData``:

* the window: the host span ``bench.window`` that the harness opens
  around the measured window (the whole trace when it is absent);
* per device plane (``/device:TPU:<n>``), the operations on its
  ``XLA Ops`` line, clipped to the window: busy time (the union of
  their intervals), self time per operation (an operation's time less
  that of the operations nested in it: a ``while`` holds its body's),
  the time of the megakernel and of collectives.  An operation is named
  by its HLO instruction and opcode (``%branch_1_fun.1 (custom-call)``);
  the megakernel is the custom call inside a ``jit_superchunk`` module
  (the sweep step's only one, until the kernel carries a name of its
  own), collectives are named by their opcode;
* idle gaps: the intervals of the window in which the first device runs
  nothing, each attributed to the innermost host span open at its
  middle (what the host was doing), summed by span name.  Gaps shorter
  than ``SHORT_GAP_NS`` (the device between two operations of one
  program) are summed as ``between ops`` without a host look-up.

Device times are averaged over the device planes, except ``kernel_s``
and ``collective_s`` which are summed over them.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
#: the megakernel: a custom call (Mosaic kernel) inside the step module
KERNEL_OPCODE = "custom-call"
KERNEL_MODULE = re.compile(r"^jit_superchunk\b")
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"all-to-all|collective-permute|send|recv)")
_OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")
#: gaps under this are summed as "between ops"
SHORT_GAP_NS = 10_000.0
#: host lines that are thread pools, not program threads
_POOL_LINE = re.compile(r"^tf_|ThreadPool|Eigen", re.I)


def _inside(spans: List[Tuple[float, float]], s: float, e: float) -> bool:
    """Whether ``[s, e)`` lies inside one of the sorted ``spans``."""
    import bisect
    j = bisect.bisect_right(spans, (s, float("inf"))) - 1
    return j >= 0 and spans[j][0] <= s and e <= spans[j][1]


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_name(text: str) -> Tuple[str, str]:
    """``(instruction, opcode)`` of an op event's HLO text."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, ""
    m = _OPCODE.search(" " + rest)
    return head, (m.group(1) if m else "")


def _self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Self time per name of nested ``(start, end, name)`` intervals."""
    out: Dict[str, float] = {}
    stack: List[List] = []                   # [end, name, child time]

    def close(item):
        end, name, child, start = item
        out[name] = out.get(name, 0.0) + (end - start) - child
        if stack:
            stack[-1][2] += end - start
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        stack.append([e, name, 0.0, s])
    while stack:
        close(stack.pop())
    return out


def reduce_profile(pd) -> Dict:
    """The numbers of one trace (see the module docstring); times in s."""
    host_spans: List[Tuple[float, float, str]] = []
    window: Optional[Tuple[float, float]] = None
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if _POOL_LINE.search(line.name or ""):
                    continue
                for ev in line.events:
                    s = ev.start_ns
                    e = s + ev.duration_ns
                    if ev.name == WINDOW_SPAN:
                        window = (s, e)
                    host_spans.append((s, e, ev.name))
    per_dev = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        modules = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in (lines[MODULES_LINE].events
                       if MODULES_LINE in lines else ())
            if KERNEL_MODULE.search(ev.name))
        iv: List[Tuple[float, float]] = []
        named: List[Tuple[float, float, str]] = []
        kernel_iv: List[Tuple[float, float]] = []
        coll_iv: List[Tuple[float, float]] = []
        for line_name in (OPS_LINE, ASYNC_LINE):
            if line_name not in lines:
                continue
            for ev in lines[line_name].events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if window is not None:
                    s, e = max(s, window[0]), min(e, window[1])
                if e <= s:
                    continue
                inst, opcode = op_name(ev.name)
                if COLLECTIVE.search(opcode):
                    coll_iv.append((s, e))
                if line_name != OPS_LINE:
                    continue
                iv.append((s, e))
                named.append((s, e, f"{inst} ({opcode})" if opcode
                              else inst))
                if opcode == KERNEL_OPCODE and _inside(modules, s, e):
                    kernel_iv.append((s, e))
        per_dev.append(dict(
            ops=_self_times(named), busy=_union(iv),
            kernel_ns=sum(e - s for s, e in _union(kernel_iv)),
            collective_ns=sum(e - s for s, e in _union(coll_iv))))
    if window is None:
        edges = [t for d in per_dev for iv in d["busy"] for t in iv]
        edges += [t for s, e, _ in host_spans for t in (s, e)]
        window = (min(edges), max(edges)) if edges else (0.0, 0.0)
    w0, w1 = window
    n_dev = max(len(per_dev), 1)
    busy_ns = sum(sum(e - s for s, e in d["busy"]) for d in per_dev) / n_dev
    ops: Dict[str, float] = {}
    for d in per_dev:
        for name, ns in d["ops"].items():
            ops[name] = ops.get(name, 0.0) + ns / n_dev
    gaps: Dict[str, float] = {}
    if per_dev:
        inner = [sp for sp in host_spans if sp[2] != WINDOW_SPAN]
        h_s = np.array([sp[0] for sp in inner], float)
        h_e = np.array([sp[1] for sp in inner], float)
        t = w0
        for s, e in per_dev[0]["busy"] + [(w1, w1)]:
            if s - t >= SHORT_GAP_NS:
                mid = (t + s) / 2
                hit = np.flatnonzero((h_s <= mid) & (h_e > mid))
                name = (inner[hit[np.argmax(h_s[hit])]][2] if hit.size
                        else "no host span")
                gaps[name] = gaps.get(name, 0.0) + (s - t)
            elif s > t:
                gaps["between ops"] = gaps.get("between ops", 0.0) + (s - t)
            t = max(t, e)
    return dict(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_ns * 1e-9,
        n_devices=len(per_dev),
        kernel_s=sum(d["kernel_ns"] for d in per_dev) * 1e-9,
        collective_s=sum(d["collective_ns"] for d in per_dev) * 1e-9,
        device_ops=[[n, v * 1e-9] for n, v in
                    sorted(ops.items(), key=lambda kv: -kv[1])],
        idle_gaps=[[n, v * 1e-9] for n, v in
                   sorted(gaps.items(), key=lambda kv: -kv[1])])


def reduce_file(path: str) -> Dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def reduce_dir(directory: str) -> Dict:
    return reduce_file(find_xplane(directory))
