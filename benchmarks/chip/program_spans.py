"""The program's own spans (``repro.spans``), as the readers of per-layer
metrics see them.

A reader calls ``roots(run)`` and gets ``(setup, window)``: lists of the
recorder's roots (``repro.spans.recent()`` in this process), oldest
first.  The window's roots are picked by count, which is exact because
nothing calls the program after the window:

* sweep.study: the last ``len(record["sweeps"])`` ``explore`` roots;
* campaign.study-kill: the last ``len(record["cycles"])`` ``explore``
  roots and as many ``resume`` roots.

The set-up roots are every root that started before the first window
root.  ``roots`` returns None where the program has no recorder (a tree
before it), where the recorder holds fewer roots than the record
reports, or where a window root dropped span records.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple


def recent() -> Optional[List[Dict]]:
    try:
        from repro import spans
    except ImportError:
        return None
    return spans.recent()


def roots(run) -> Optional[Tuple[List[Dict], List[Dict]]]:
    """``(setup, window)`` roots of this run, or None (module doc)."""
    kept = recent()
    rec = run["record"]
    if kept is None:
        return None
    if "sweeps" in rec:
        want = {"explore": len(rec["sweeps"])}
    elif "cycles" in rec:
        want = {"explore": len(rec["cycles"]),
                "resume": len(rec["cycles"])}
    else:
        return None
    window: List[Dict] = []
    for name, n in want.items():
        named = [r for r in kept if r["name"] == name]
        if n < 1 or len(named) < n:
            return None
        window += named[-n:]
    if any(r["dropped"] or r["end_ns"] is None for r in window):
        return None
    window.sort(key=lambda r: r["start_ns"])
    first = window[0]["start_ns"]
    return [r for r in kept if r["start_ns"] < first], window


def seconds(sp: Dict) -> float:
    return (sp["end_ns"] - sp["start_ns"]) * 1e-9


def named(root: Dict, name: str) -> List[Dict]:
    """The spans of ``root`` called ``name``."""
    return [s for s in root["spans"] if s["name"] == name]


def children(root: Dict, sp: Dict) -> List[Dict]:
    return [s for s in root["spans"] if s["parent"] == sp["id"]]


def self_seconds(root: Dict, sp: Dict) -> float:
    """``sp``'s duration less that of its child spans."""
    return seconds(sp) - sum(seconds(c) for c in children(root, sp))


def under(root: Dict, anc: Dict, name: str) -> List[Dict]:
    """The spans called ``name`` that ``anc`` encloses, at any depth."""
    by_id = {s["id"]: s for s in root["spans"]}
    out = []
    for s in named(root, name):
        p = s["parent"]
        while p is not None and p != anc["id"]:
            p = by_id[p]["parent"] if p in by_id else None
        if p == anc["id"]:
            out.append(s)
    return out


def total(roots_: List[Dict], name: str) -> float:
    """Seconds of every span called ``name`` in ``roots_``."""
    return sum(seconds(s) for r in roots_ for s in named(r, name))


def print_window_compiles(tag: str, window: List[Dict]) -> None:
    """One line for each span of the window that was charged a compile:
    which call recompiled, and where."""
    for r in window:
        for s in r["spans"]:
            c = s["counters"]
            if c.get("compile.n") or c.get("compile.lower_s"):
                print(f"{tag}: compile in window: root {r['name']} "
                      f"{r['id']} span {s['name']} backend "
                      f"{c.get('compile.backend_s', 0.0)} s lower "
                      f"{c.get('compile.lower_s', 0.0)} s trace "
                      f"{c.get('compile.trace_s', 0.0)} s n "
                      f"{c.get('compile.n', 0)}", flush=True)
