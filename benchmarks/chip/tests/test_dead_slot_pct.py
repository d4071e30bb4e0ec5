"""The ``dead_slot_pct.campaign`` reader on small span sets, on the CPU.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q \
        benchmarks/chip/tests/test_dead_slot_pct.py

A span set is what ``repro.spans.recent()`` returns: roots with their
span records, each with its counters.  The reader sums the ``sweep.split``
counters inside the window's ``campaign.shard`` spans, and reads nothing
where the program records no ``sweep.split`` (a tree before it) or the
run is not a campaign.
"""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "src"), HERE]

import bench  # noqa: E402
import program_spans  # noqa: E402


def _span(i, name, parent, start, counters=None):
    return dict(id=i, name=name, parent=parent, start_ns=start,
                end_ns=start + 10, thread=1, attrs={},
                counters=dict(counters or {}))


def _root(i, name, start, shards, split=True):
    """A call with one ``campaign.shard`` per ``(slots, dead)`` pair."""
    spans = [_span(i, name, None, start)]
    for j, (slots, dead) in enumerate(shards):
        base = i + 10 * (j + 1)
        spans += [_span(base, "campaign.shard", i, start + j),
                  _span(base + 1, "sweep", base, start + j),
                  _span(base + 2, "sweep.prep", base + 1, start + j)]
        if split:
            spans.append(_span(base + 3, "sweep.split", base + 2,
                               start + j, {"sweep.segments": 2,
                                           "sweep.slots": slots,
                                           "sweep.dead_slots": dead}))
    return dict(spans[0], spans=spans, counters={}, dropped=0)


def _span_set(split=True):
    """A set-up call (its own split is not a shard's) and one cycle: a
    killed run of 2 shards and a resume of 3."""
    setup = _root(1000, "explore", 0, [])
    setup["spans"].append(_span(1001, "sweep.split", 1000, 1,
                                {"sweep.slots": 16,
                                 "sweep.dead_slots": 15}))
    return [setup,
            _root(2000, "explore", 100, [(32, 0), (32, 15)], split),
            _root(3000, "resume", 200, [(48, 1), (16, 0), (32, 8)],
                  split)]


@pytest.fixture
def reader():
    return bench.load_module("metrics", "dead_slot_pct.campaign")


def test_reads_dead_over_all_slots_of_window_shards(reader, monkeypatch):
    monkeypatch.setattr(program_spans, "recent", lambda: _span_set())
    got = reader.read({"record": {"cycles": [{}]}})
    assert got == pytest.approx(100.0 * 24 / 160, rel=1e-12)


@pytest.mark.parametrize("case", ["no_split_span", "not_a_campaign",
                                  "no_recorder"])
def test_reads_nothing_without_split_counters(reader, monkeypatch, case):
    roots = _span_set(split=case != "no_split_span")
    monkeypatch.setattr(program_spans, "recent",
                        lambda: None if case == "no_recorder" else roots)
    record = ({"sweeps": [{}, {}]} if case == "not_a_campaign"
              else {"cycles": [{}]})
    assert reader.read({"record": record}) is None


def test_is_a_campaign_cell_metric():
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    for cell in ("campaign.study-kill", "campaign.wide"):
        names = {m["name"] for m in bench.cell_metrics(spec, cell, True)}
        assert "dead_slot_pct.campaign" in names
    names = {m["name"] for m in bench.cell_metrics(spec, "sweep.study",
                                                   True)}
    assert "dead_slot_pct.campaign" not in names
