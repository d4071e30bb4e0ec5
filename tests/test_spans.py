"""The in-program span and counter recorder (``repro.spans``).

* a fused ``explore()`` records the span tree of the sweep path under
  one root, and its result fields are those spans;
* a recompile is charged to the span that caused it;
* a killed and resumed campaign records its checkpoint writes on the
  writer thread, under their shards' roots;
* a profile holds the spans on its host plane, on the records' clock;
* the recorder stays bounded and its counters survive threads;
* the chip benchmark's span readers, on a synthetic recorder and on the
  live one.
"""
import glob
import importlib
import os
import sys
import threading

import pytest

from repro import spans
from repro.campaign import (CampaignOptions, FaultSchedule, KillCampaign,
                            resume)
from repro.core.shard_sweep import stream_cache_clear, stream_cache_info
from repro.explore import DesignSpace, explore
from repro.launch.mesh import make_batch_mesh

CHIP = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "chip")

GRIDS = {"variant": ["2d_in", "3d_in"],
         "frame_rate": [15.0, 30.0, 60.0],
         "sys_rows": [8.0, 32.0, 64.0]}
#: a sweep of several dispatches, so the pacing window fills
CHUNK, K, DEPTH = 3, 4, 2

#: the span tree of one fused sweep: span -> parent
FUSED_TREE = {
    "sweep": "explore",
    "sweep.prep": "sweep", "sweep.step": "sweep",
    "sweep.split": "sweep.prep",
    "sweep.dispatch": "sweep", "sweep.finalize": "sweep",
    "step.lower": "sweep.step", "step.compile": "sweep.step",
    "step.warm": "sweep.step", "sweep.pace": "sweep.dispatch",
    "finalize.fetch": "sweep.finalize",
    "finalize.regather": "sweep.finalize",
    "finalize.assemble": "sweep.finalize",
}


@pytest.fixture(scope="module")
def mesh():
    return make_batch_mesh(1)


@pytest.fixture(scope="module")
def space():
    return DesignSpace(["edgaze"], GRIDS)


def _sweep(space, mesh, **kw):
    kw.setdefault("engine", "fused")
    return explore(space, k=K, chunk_size=CHUNK, superchunk=1, mesh=mesh,
                   pipeline_depth=DEPTH, **kw)


def _last(name):
    return [r for r in spans.recent() if r["name"] == name][-1]


def _named(root, name):
    return [s for s in root["spans"] if s["name"] == name]


def _one(root, name):
    found = _named(root, name)
    assert len(found) == 1, (name, [s["name"] for s in root["spans"]])
    return found[0]


def _sec(sp):
    return (sp["end_ns"] - sp["start_ns"]) * 1e-9


def test_fused_explore_span_tree(space, mesh):
    stream_cache_clear()
    res = _sweep(space, mesh)
    root = _last("explore")
    assert root["parent"] is None and root["spans"][0]["id"] == root["id"]
    by_id = {s["id"]: s for s in root["spans"]}
    names = {s["name"] for s in root["spans"]}
    assert names == set(FUSED_TREE) | {"explore"}
    for s in root["spans"][1:]:
        assert by_id[s["parent"]]["name"] == FUSED_TREE[s["name"]]
        assert s["start_ns"] >= by_id[s["parent"]]["start_ns"]
        assert s["end_ns"] <= by_id[s["parent"]]["end_ns"]
    # a pacing wait per dispatch past the pipeline depth, and the drain
    assert res.dispatches > DEPTH
    assert len(_named(root, "sweep.pace")) == res.dispatches - DEPTH + 1
    assert root["counters"]["sweep.dispatches"] == res.dispatches
    assert root["counters"]["stream.step_compiles"] == 1
    assert stream_cache_info()["step_compiles"] == 1


@pytest.mark.parametrize("engine", ["fused", "staged"])
def test_stream_fields_are_spans(space, mesh, engine):
    res = _sweep(space, mesh, engine=engine)
    root = _last("explore")
    prep, step = _one(root, "sweep.prep"), _one(root, "sweep.step")
    assert res.compile_s == pytest.approx(_sec(prep) + _sec(step),
                                          abs=1e-9)
    assert res.eval_s == pytest.approx(_sec(_one(root, "sweep.dispatch")),
                                       abs=1e-9)
    assert res.wall_s == pytest.approx(_sec(_one(root, "sweep")),
                                       abs=1e-9)
    assert res.wall_s == res.stream_result.wall_s
    assert res.compile_s + res.eval_s < res.wall_s < _sec(root)


@pytest.mark.parametrize("engine", ["monolithic", "chunked"])
def test_grid_fields_are_spans(space, mesh, engine):
    res = explore(space, k=K, engine=engine, mesh=mesh,
                  chunk_size=8 if engine == "chunked" else None)
    root = _last("explore")
    sweep_res = res.sweep_results["edgaze"]
    assert sweep_res.wall_s == pytest.approx(
        _sec(_one(root, "grid.sweep")), abs=1e-9)
    assert sweep_res.eval_s == pytest.approx(
        sum(_sec(s) for s in _named(root, "grid.eval")), abs=1e-9)
    assert res.wall_s == pytest.approx(_sec(_one(root, "grid.explore")),
                                       abs=1e-9)
    assert res.eval_s == sweep_res.eval_s
    # a cached executable reports no compile
    again = explore(space, k=K, engine=engine, mesh=mesh,
                    chunk_size=8 if engine == "chunked" else None)
    assert again.compile_s == 0.0
    assert not _named(_last("explore"), "grid.compile")


def test_recompile_charged_to_step_compile(space, mesh):
    _sweep(space, mesh)                       # the shape, cached
    wider = DesignSpace(["edgaze"], dict(GRIDS, sys_rows=[8.0, 16.0]))
    _sweep(space, mesh)
    first = _last("explore")
    assert not _named(first, "step.lower")
    assert first["counters"]["stream.hits"] == 1
    _sweep(wider, mesh)                       # a new grid shape compiles
    second = _last("explore")
    assert second["id"] != first["id"]
    comp = _one(second, "step.compile")
    assert comp["counters"]["compile.backend_s"] > 0
    assert comp["counters"]["compile.n"] >= 1
    assert _one(second, "step.lower")["counters"]["compile.lower_s"] > 0
    assert second["counters"]["compile.backend_s"] >= \
        comp["counters"]["compile.backend_s"]
    assert "compile.backend_s" not in _one(first, "sweep.step")["counters"]


def _killed_and_resumed(space, mesh, directory):
    opts = CampaignOptions(shard_points=7, sleep=lambda _s: None,
                           faults=FaultSchedule(kill_after=2))
    with pytest.raises(KillCampaign):
        explore(space, k=K, chunk_size=CHUNK, checkpoint_dir=directory,
                campaign=opts, mesh=mesh, workers=1)
    killed = _last("explore")
    res = resume(directory, mesh=mesh, workers=1)
    return killed, _last("resume"), res


@pytest.mark.parametrize("engine", ["fused", "staged"])
def test_split_span_counts_segments_and_slots(space, mesh, engine):
    """``sweep.split`` times the host's cut of the range into per-variant
    segments, under ``sweep.prep``, and carries its counters: segments,
    scan slots dispatched and the dead ones among them."""
    # 9 points a variant in 3 chunks; [4, 14) meets variant 0 at chunk
    # ordinals 1-2 and variant 1 at 3-4
    kw = dict(superchunk=3) if engine == "fused" else {}
    res = explore(space, k=K, chunk_size=CHUNK, mesh=mesh, engine=engine,
                  index_range=(4, 14), **kw)
    root = _last("explore")
    split = _one(root, "sweep.split")
    assert _one(root, "sweep.prep")["id"] == split["parent"]
    if engine == "fused":
        # 4 live chunks in 2 dispatches of 3 slots
        want = {"sweep.segments": 2, "sweep.slots": 6,
                "sweep.dead_slots": 2}
        assert res.dispatches * res.superchunk == 6
    else:
        # the staged engine dispatches each variant's chunks from its
        # segment's start: 2 + 2, none dead
        want = {"sweep.segments": 2, "sweep.slots": 4,
                "sweep.dead_slots": 0}
        assert res.dispatches == 4
    assert split["counters"] == want
    for key, n in want.items():
        assert root["counters"][key] == n


def test_campaign_ckpt_writes_under_shard_roots(space, mesh, tmp_path):
    killed, resumed, res = _killed_and_resumed(space, mesh,
                                               str(tmp_path / "c"))
    rep = res.campaign
    for root, n_writes in ((killed, 2), (resumed, rep["n_executed"])):
        by_id = {s["id"]: s for s in root["spans"]}
        writes = _named(root, "ckpt.write")
        assert len(writes) == n_writes
        for w in writes:
            assert w["thread"] != root["thread"]       # the writer's
            shard = by_id[w["parent"]]
            assert shard["name"] == "campaign.shard"
            assert (shard["attrs"]["lo"], shard["attrs"]["hi"]) == \
                (w["attrs"]["lo"], w["attrs"]["hi"])
        for s in _named(root, "campaign.shard"):
            sweeps = [c for c in root["spans"]
                      if c["parent"] == s["id"] and c["name"] == "sweep"]
            assert len(sweeps) == 1
            # every shard's range is cut into its variants' segments
            split, = [c for c in _named(root, "sweep.split")
                      if by_id[c["parent"]]["parent"] == sweeps[0]["id"]]
            assert split["counters"]["sweep.segments"] >= 1
            assert split["counters"]["sweep.slots"] >= 1
    run = _one(resumed, "campaign.run")
    assert run["attrs"] == {"resumed": True}
    for name in ("campaign.plan", "campaign.load", "campaign.prep",
                 "campaign.merge", "campaign.report"):
        assert _one(resumed, name)["parent"] == run["id"]
    assert rep["io_s"] == round(sum(_sec(s) for s in
                                    _named(resumed, "ckpt.write")), 6)
    assert rep["dispatch_wait_s"] == round(
        sum(_sec(s) for s in _named(resumed, "campaign.wait")), 6)
    assert 0 < rep["wall_s"] <= _sec(run)


def test_profile_host_plane_matches_records(space, mesh, tmp_path):
    """The shared clock: each span's offset from its root's start, and
    its duration, read the same in the profile as in the records."""
    import jax
    _sweep(space, mesh)                       # warm, so the trace is short
    with jax.profiler.trace(str(tmp_path)):
        _sweep(space, mesh)
    root = _last("explore")
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path[0])
    names = {s["name"] for s in root["spans"]}
    events = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.duration_ns))
    recs = {}
    for s in root["spans"]:
        recs.setdefault(s["name"], []).append(s)
    assert set(events) == names
    ev_root = events["explore"][-1][0]
    for name, got in recs.items():
        evs = sorted(events[name])[-len(got):]
        assert len(evs) == len(got), name
        for (ev_start, ev_dur), s in zip(evs, got):
            assert abs((ev_start - ev_root)
                       - (s["start_ns"] - root["start_ns"])) < 1e6, name
            assert abs(ev_dur - (s["end_ns"] - s["start_ns"])) < 1e6, name


def test_recorder_stays_bounded():
    for i in range(spans.MAX_ROOTS + 44):
        with spans.span("bounded", i=i):
            pass
    kept = spans.recent()
    assert len(kept) == spans.MAX_ROOTS
    assert kept[-1]["attrs"] == {"i": spans.MAX_ROOTS + 43}
    assert kept[0]["attrs"] == {"i": 44}
    n = spans.MAX_SPANS_PER_ROOT + 10
    with spans.span("wide"):
        for _ in range(n):
            with spans.span("child") as last:
                pass
    root = spans.recent()[-1]
    assert len(root["spans"]) == spans.MAX_SPANS_PER_ROOT
    assert root["dropped"] == n + 1 - spans.MAX_SPANS_PER_ROOT
    assert last.end_ns is not None and last.seconds >= 0.0


def test_counters_exact_under_threads():
    n_threads, n_counts = 16, 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    before = spans.counters().get("stress.n", 0)

    def work(i):
        with spans.span("stress", i=i):
            for _ in range(n_counts):
                spans.count("stress.n")
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert spans.counters()["stress.n"] - before == n_threads * n_counts
    roots = [r for r in spans.recent() if r["name"] == "stress"]
    assert len(roots) == n_threads
    assert all(r["counters"]["stress.n"] == n_counts for r in roots)


def test_spans_follow_their_parent_across_threads():
    with spans.span("caller") as caller:
        handed = spans.current()
        out = []

        def on_writer():
            with spans.span("handed", parent=handed):
                with spans.span("nested"):
                    pass

        def carried():
            with spans.span("carried"):
                out.append(spans.current().root_id == caller.id)
        t1 = threading.Thread(target=on_writer)
        t2 = threading.Thread(target=spans.carry(carried))
        t1.start()
        t2.start()
        t1.join(timeout=10)
        t2.join(timeout=10)
        assert not t1.is_alive() and not t2.is_alive()
    root = spans.recent()[-1]
    assert root["id"] == caller.id and out == [True]
    by_name = {s["name"]: s for s in root["spans"]}
    assert by_name["handed"]["parent"] == caller.id
    assert by_name["nested"]["parent"] == by_name["handed"]["id"]
    assert by_name["carried"]["parent"] == caller.id
    assert by_name["handed"]["thread"] != root["thread"]


def test_stream_cache_info_keeps_its_keys():
    stream_cache_clear()
    info = stream_cache_info()
    assert set(info) == {"step_compiles", "hits", "evictions", "size",
                         "limit"}
    assert (info["step_compiles"], info["hits"], info["evictions"],
            info["size"]) == (0, 0, 0, 0)
    # counted outside any span, the process totals still move
    spans.count("stream.hits", 2)
    assert stream_cache_info()["hits"] == 2
    stream_cache_clear()
    assert stream_cache_info()["hits"] == 0


# ---------------------------------------------------------------------------
# the chip benchmark's readers of these spans
# ---------------------------------------------------------------------------
SPAN_READERS = ("fetch_ms.sweep", "regather_ms.sweep",
                "dispatch_host_us.sweep", "step_lower_s",
                "step_backend_s", "setup_program_s",
                "shard_finalize_ms.campaign", "campaign_fixed_ms.campaign",
                "dead_slot_pct.campaign")


@pytest.fixture(scope="module")
def bench():
    """``bench.py``, which loads readers by path, with its directory on
    ``sys.path`` as when it runs."""
    sys.path.insert(0, CHIP)
    return importlib.import_module("bench")


class _Roots:
    """Synthetic recorder roots, in ms from 0."""

    def __init__(self):
        self.roots = []
        self._id = 0

    def _new(self, name, start, end, parent):
        self._id += 1
        return dict(id=self._id, name=name, parent=parent,
                    start_ns=int(start * 1e6), end_ns=int(end * 1e6),
                    thread=1, attrs={}, counters={})

    def root(self, name, start, end, counters=None):
        r = self._new(name, start, end, None)
        r.update(spans=[dict(r)], counters=dict(counters or {}),
                 dropped=0)
        self.roots.append(r)
        return r

    def add(self, root, name, start, end, parent=None, counters=None):
        s = self._new(name, start, end, (parent or root)["id"])
        s["counters"] = dict(counters or {})
        root["spans"].append(s)
        return s


def _sweep_recorder():
    """A set-up call and two window sweeps."""
    rec = _Roots()
    setup = rec.root("explore", 0, 9000)
    step = rec.add(setup, "sweep.step", 100, 7000)
    rec.add(setup, "step.lower", 100, 5100, step)
    rec.add(setup, "step.compile", 5100, 6100, step)
    for t in (10000, 20000):
        r = rec.root("explore", t, t + 5000, {"sweep.dispatches": 4})
        sw = rec.add(r, "sweep", t, t + 5000)
        d = rec.add(r, "sweep.dispatch", t + 10, t + 4910, sw)
        for j in range(3):                    # 4890 ms of pacing
            rec.add(r, "sweep.pace", t + 20 + 1630 * j,
                    t + 20 + 1630 * (j + 1), d)
        f = rec.add(r, "sweep.finalize", t + 4910, t + 4925, sw)
        rec.add(r, "finalize.fetch", t + 4910, t + 4917, f)
        rec.add(r, "finalize.regather", t + 4917, t + 4920, f)
    return rec.roots, {"sweeps": [{}, {}]}


def _campaign_recorder():
    """A set-up call and one kill-and-resume cycle: 2 shards before the
    kill, 3 after, each of 32 scan slots with 0, 12, 4, 0 and 8 dead."""
    rec = _Roots()
    setup = rec.root("explore", 0, 8000)
    rec.add(setup, "step.lower", 10, 4010)
    rec.add(setup, "step.compile", 4010, 5510)
    rec.add(setup, "sweep.split", 5510, 5511,
            counters={"sweep.slots": 16, "sweep.dead_slots": 15})
    dead = iter((0, 12, 4, 0, 8))
    for name, start, end, shards in (("explore", 10000, 10100, 2),
                                     ("resume", 10200, 10400, 3)):
        r = rec.root(name, start, end)
        for j in range(shards):
            s0 = start + 10 + 30 * j
            sh = rec.add(r, "campaign.shard", s0, s0 + 30)
            sw = rec.add(r, "sweep", s0, s0 + 30, sh)
            prep = rec.add(r, "sweep.prep", s0, s0 + 2, sw)
            rec.add(r, "sweep.split", s0 + 1, s0 + 2, prep,
                    counters={"sweep.segments": 1, "sweep.slots": 32,
                              "sweep.dead_slots": next(dead)})
            rec.add(r, "sweep.finalize", s0 + 20, s0 + 30, sw)
    return rec.roots, {"cycles": [{}]}


#: reader -> (recorder, expected value)
SYNTHETIC = {
    "fetch_ms.sweep": (_sweep_recorder, 7.0),
    "regather_ms.sweep": (_sweep_recorder, 3.0),
    # (4900 - 3 * 1630) ms of host time over 4 dispatches, in us
    "dispatch_host_us.sweep": (_sweep_recorder, 2500.0),
    "step_lower_s": (_campaign_recorder, 4.0),
    "step_backend_s": (_sweep_recorder, 1.0),
    "setup_program_s": (_sweep_recorder, 9.0),
    "shard_finalize_ms.campaign": (_campaign_recorder, 10.0),
    # (100 - 60) + (200 - 90) ms outside the shards, one cycle
    "campaign_fixed_ms.campaign": (_campaign_recorder, 150.0),
    # 24 of the window shards' 160 slots dead (the set-up's split is not
    # a shard's)
    "dead_slot_pct.campaign": (_campaign_recorder, 15.0),
}


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_reader_on_synthetic_recorder(bench, monkeypatch, name):
    make, want = SYNTHETIC[name]
    roots, record = make()
    monkeypatch.setattr(spans, "recent", lambda: roots)
    got = bench.load_module("metrics", name).read({"record": record})
    assert got == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_reader_reads_nothing_without_the_roots(bench, monkeypatch,
                                                     name):
    """Fewer roots than the record reports, or a program without the
    recorder, read as nothing, not as a number."""
    make, _want = SYNTHETIC[name]
    roots, record = make()
    monkeypatch.setattr(spans, "recent", lambda: roots[:1])
    reader = bench.load_module("metrics", name)
    assert reader.read({"record": record}) is None
    monkeypatch.setattr(sys.modules["program_spans"], "recent",
                        lambda: None)
    assert reader.read({"record": record}) is None


def test_span_readers_are_cell_metrics(bench):
    import json
    with open(os.path.join(CHIP, "..", "..", "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    for cell in ("sweep.study", "campaign.study-kill"):
        traced = {m["name"] for m in bench.cell_metrics(spec, cell, True)}
        assert {"step_lower_s", "step_backend_s",
                "setup_program_s"} <= traced
    assert {m["name"] for m in spec["per_layer"]} >= set(SPAN_READERS)


def test_span_readers_on_live_sweeps(bench, monkeypatch, space, mesh):
    """The readers on this process's recorder, after a set-up call and
    two window sweeps, hold to the fields they split."""
    marker = spans.recent()[-1]["id"] if spans.recent() else 0
    stream_cache_clear()
    warm = _sweep(space, mesh)
    sweeps = [_sweep(space, mesh) for _ in range(2)]
    live = spans.recent
    monkeypatch.setattr(spans, "recent",
                        lambda: [r for r in live() if r["id"] > marker])
    run = {"record": {"sweeps": [{} for _ in sweeps]}}
    got = {name: bench.load_module("metrics", name).read(run)
           for name in SPAN_READERS[:6]}
    assert all(v is not None and v > 0 for v in got.values()), got
    finalize_ms = 1e3 * sum(s.wall_s - s.compile_s - s.eval_s
                            for s in sweeps) / len(sweeps)
    assert got["fetch_ms.sweep"] + got["regather_ms.sweep"] <= finalize_ms
    assert got["step_lower_s"] + got["step_backend_s"] <= warm.compile_s
    assert got["setup_program_s"] >= warm.wall_s


def test_span_readers_on_live_campaign(bench, monkeypatch, space, mesh,
                                       tmp_path):
    marker = spans.recent()[-1]["id"] if spans.recent() else 0
    _killed_and_resumed(space, mesh, str(tmp_path / "c"))
    live = spans.recent
    monkeypatch.setattr(spans, "recent",
                        lambda: [r for r in live() if r["id"] > marker])
    run = {"record": {"cycles": [{}]}}
    for name in SPAN_READERS[6:]:
        got = bench.load_module("metrics", name).read(run)
        assert got is not None and got > 0, name
