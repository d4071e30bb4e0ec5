"""Design spaces past 2**31 and 2**32 points, swept on int32 offsets.

The space here has 8 variants of 599,040,000 points (4,792,320,000 in
all): every variant fits int32, the space does not.  The sweep holds a
point on the device as its variant and an int32 offset inside it, and
only the host holds a global index, as a Python int.  Windows of a few
thousand points are compared with the plain reference beside this file
(``window_reference.py``: numpy decode, the scalar CamJ model, a float64
reduction): the winners' flat indices exactly, their values, each
variant's minimum and mean at rel 1e-6, counts and each variant's
argmin exactly.

* ``explore()`` on windows that straddle 2**31, 2**32 and a variant
  boundary past 2**31, on the fused (Pallas), staged and XLA engines;
* a campaign on the same space, killed and resumed, whose missing
  ranges straddle shard boundaries past 2**31 (3 * 2**30 and 2**32);
* the host's cut of a range into per-variant segments, and the shard
  plan of the camj-wide deployment.
"""
import math

import numpy as np
import pytest

from repro.campaign import (CampaignOptions, FaultSchedule, KillCampaign,
                            plan_shards, resume, run_campaign)
from repro.campaign.manifest import write_shard
from repro.core.shard_sweep import StreamResult, split_index_range
from repro.explore import DesignSpace, explore
from repro.launch.mesh import make_batch_mesh

from window_reference import reduce_window, variant_slots

REL = 1e-6
ALGOS = ["edgaze", "rhythmic"]
METRIC, K, CHUNK = "density_mw_mm2", 16, 1024
#: (engine, backend) of each lane
LANES = {"fused": ("fused", "pallas"), "staged": ("staged", "auto"),
         "xla": ("fused", "xla")}


def _lattice(rng, lo, hi, step, n):
    lat = np.round(lo + step * np.arange(int(round((hi - lo) / step)) + 1),
                   6)
    return [float(v) for v in np.sort(rng.choice(lat, n, replace=False))]


def _grids(seed):
    """Every swept axis named, values drawn from ``seed`` on the chip
    benchmark's lattices: 13 * 3 * 3 * 20 * 20 * 40 * 16 * 20 points a
    variant."""
    rng = np.random.default_rng(seed)
    return {"cis_node": [130.0, 110.0, 90.0, 80.0, 65.0, 55.0, 45.0, 40.0,
                         32.0, 28.0, 22.0, 16.0, 14.0],
            "soc_node": [14.0, 22.0, 28.0],
            "mem_tech": ["sram", "sram_hp", "stt"],
            "sys_rows": _lattice(rng, 4, 128, 1, 20),
            "sys_cols": _lattice(rng, 4, 128, 1, 20),
            "frame_rate": _lattice(rng, 15, 240, 1, 40),
            "active_fraction_scale": _lattice(rng, 0.1, 1.0, 0.01, 16),
            "pixel_pitch_um": _lattice(rng, 2.0, 6.0, 0.05, 20)}


GRIDS = _grids(2 ** 31 + 17)
N_VAR = 599_040_000
#: the flat index each window of 3,000 points straddles
WINDOWS = {"2^31": 2 ** 31, "2^32": 2 ** 32, "variant_4": 4 * N_VAR}
#: the campaign's shard width and the ranges its resume leaves missing:
#: each straddles a shard boundary past 2**31
SHARD = 2 ** 30
CAMPAIGN_GAPS = [(3 * SHARD - 1200, 3 * SHARD + 1800),
                 (4 * SHARD - 1500, 4 * SHARD + 1500)]


@pytest.fixture(scope="module")
def space():
    sp = DesignSpace(ALGOS, GRIDS)
    assert sp.n_var == N_VAR < 2 ** 31
    assert sp.n_points == 8 * N_VAR > 2 ** 32
    return sp


@pytest.fixture(scope="module")
def mesh():
    return make_batch_mesh(1)


_REFS = {}


def _reference(ranges):
    key = tuple(ranges)
    if key not in _REFS:
        _REFS[key] = reduce_window(ALGOS, GRIDS, ranges, metric=METRIC,
                                   k=K)
    return _REFS[key]


def _assert_matches(res, ref, space):
    """``res`` (an ExploreResult) against the reference's reduction."""
    slots = variant_slots(ALGOS)
    got = [(slots.index((r["algorithm"], r["variant"])) * N_VAR
            + r["index"], r[METRIC]) for r in res.topk]
    assert [g for g, _ in got] == [g for g, _ in ref["topk"]]
    np.testing.assert_allclose([v for _, v in got],
                               [v for _, v in ref["topk"]], rtol=REL)
    for slot in range(space.n_variants):
        have = res.summaries[space.label(slot)]
        want = ref["summaries"].get(slot)
        if want is None:                  # a variant the window missed
            assert have["n_feasible"] == 0 and have["argmin_index"] == -1
            continue
        for key in ("n_feasible", "argmin_index"):
            assert have[key] == want[key], (slot, key)
        for key in ("metric_min", "metric_mean"):
            assert have[key] == pytest.approx(want[key], rel=REL), \
                (slot, key)


@pytest.mark.parametrize("window", sorted(WINDOWS))
@pytest.mark.parametrize("lane", sorted(LANES))
def test_explore_window_matches_reference(space, mesh, lane, window):
    lo, hi = WINDOWS[window] - 1500, WINDOWS[window] + 1500
    engine, backend = LANES[lane]
    res = explore(space, k=K, metric=METRIC, engine=engine,
                  backend=backend, chunk_size=CHUNK, index_range=(lo, hi),
                  mesh=mesh)
    st = res.stream_result
    assert (st.index_lo, st.index_hi, res.n_points) == (lo, hi, hi - lo)
    ref = _reference([(lo, hi)])
    _assert_matches(res, ref, space)
    for slot, want in ref["summaries"].items():
        assert res.summaries[space.label(slot)]["n"] == want["n"]


def _fill(directory, space, engine, backend, gaps):
    """Checkpoint everything outside ``gaps`` as shards with no feasible
    point (the state of a campaign that had swept all but those ranges,
    with nothing feasible there), so a resume dispatches the gaps."""
    labels = [space.label(s) for s in range(space.n_variants)]
    edges = [0] + [b for gap in sorted(gaps) for b in gap] + \
        [space.n_points]
    for lo, hi in zip(edges[::2], edges[1::2]):
        counts = {vi: vhi - vlo
                  for vi, vlo, vhi in split_index_range(lo, hi, N_VAR)}
        summaries = {label: dict(n=counts.get(s, 0), n_feasible=0,
                                 metric_min=math.inf,
                                 metric_mean=math.nan, argmin_index=-1,
                                 argmin_point=None)
                     for s, label in enumerate(labels)}
        result = StreamResult(
            algorithm="+".join(ALGOS), metric=METRIC, k=K,
            n_points=hi - lo, n_feasible=0, n_devices=1, chunk_size=CHUNK,
            topk=[], summaries=summaries, n_variants=space.n_variants,
            index_lo=lo, index_hi=hi, engine=engine, n_var=N_VAR,
            backend="pallas" if backend == "auto" else backend)
        write_shard(directory, lo, hi, result.to_payload())


@pytest.mark.parametrize("lane", sorted(LANES))
def test_campaign_resume_past_int32_matches_reference(space, mesh, lane,
                                                      tmp_path):
    """A killed and resumed campaign of the whole space dispatches only
    its missing ranges, cut at shard boundaries past 2**31, and merges
    them with the checkpointed shards into the reference's answer."""
    engine, backend = LANES[lane]
    d = str(tmp_path / "wide")
    kw = dict(k=K, metric=METRIC, engine=engine, backend=backend,
              chunk_size=CHUNK, mesh=mesh)

    def opts(kill_after):
        return CampaignOptions(shard_points=SHARD,
                               faults=FaultSchedule(kill_after=kill_after))
    with pytest.raises(KillCampaign):           # plans, sweeps nothing
        run_campaign(space, d, options=opts(0), **kw)
    _fill(d, space, engine, backend, CAMPAIGN_GAPS)
    with pytest.raises(KillCampaign):           # two gap shards, killed
        run_campaign(space, d, options=opts(2), **kw)
    res = resume(d, mesh=mesh)
    rep = res.campaign
    # the missing ranges, cut at the shard boundary each straddles: the
    # killed run swept the first two, the resume the last two
    pieces = [piece for lo, hi in CAMPAIGN_GAPS
              for piece in ((lo, hi // SHARD * SHARD),
                            (hi // SHARD * SHARD, hi))]
    assert all(2 ** 31 < b for _lo, b in pieces[::2])
    assert rep["n_planned"] == len(plan_shards(space.n_points, SHARD))
    assert [(e["lo"], e["hi"]) for e in rep["executed"]] == pieces[2:]
    assert rep["n_loaded"] == 3 + 2 and not rep["missing"]
    assert res.n_points == space.n_points
    _assert_matches(res, _reference(CAMPAIGN_GAPS), space)
    for slot in range(space.n_variants):
        assert res.summaries[space.label(slot)]["n"] == N_VAR


def test_split_index_range_past_int32():
    """The host's cut: per-variant segments of Python ints, every local
    bound inside its variant, whatever the global bounds."""
    lo, hi = 2 ** 32 - 5, 2 ** 32 + 2 * N_VAR
    segs = split_index_range(lo, hi, N_VAR)
    assert [s for s, _, _ in segs] == [7, 8, 9]
    assert sum(b - a for _, a, b in segs) == hi - lo
    for vi, vlo, vhi in segs:
        assert 0 <= vlo < vhi <= N_VAR < 2 ** 31
        assert all(isinstance(x, int) for x in (vi, vlo, vhi))
    assert segs[0][1] == lo - 7 * N_VAR and segs[-1][2] == hi - 9 * N_VAR
    assert split_index_range(lo, lo, N_VAR) == []
    assert split_index_range(N_VAR, 2 * N_VAR, N_VAR) == [(1, 0, N_VAR)]


def test_camj_wide_shard_plan():
    """The camj-wide deployment: 3,312,451,584 points as 50 shards of
    2**26; 2**31 falls on a shard boundary, so shards 32-49 lie wholly
    past it, and the variant boundaries past 2**31 fall inside shards."""
    n_var, total = 414_056_448, 3_312_451_584
    assert 8 * n_var == total and n_var < 2 ** 31 < total
    shards = plan_shards(total, 2 ** 26)
    assert len(shards) == 50 and shards[-1][1] == total
    assert shards[32][0] == 2 ** 31 and shards[31][1] == 2 ** 31
    for b in (6 * n_var, 7 * n_var):
        assert b > 2 ** 31
        assert any(lo < b < hi for lo, hi in shards)


def test_chunk_ordinals_past_int32_are_refused(space, mesh):
    """The scan's chunk ordinals ride int32 too: a chunk so small that
    the space holds 2**31 of them is refused before anything is traced,
    instead of wrapping to the wrong chunks."""
    with pytest.raises(ValueError, match="chunk ordinals"):
        explore(space, k=K, metric=METRIC, engine="fused", chunk_size=2,
                index_range=(2 ** 32, 2 ** 32 + 8), mesh=mesh)
