"""Sharded, streaming mega-sweep engine (repro.core.shard_sweep).

In-process tests cover the pieces that don't need a multi-device runtime:
the lazy ChunkedGrid walker, chunked-vs-monolithic sweep equality
(including non-divisible chunk sizes), the Pallas block-stats kernel, and
single-device streaming vs ``SweepResult.best()``.

The multi-device half runs in a subprocess (test_multidevice.py style —
the device-count XLA flag must precede jax init) on an 8-device forced
host platform: sharded-vs-unsharded parity at a non-divisible batch,
chunked+sharded sweep equality, and streaming top-k / summaries against
the monolithic oracle.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# ChunkedGrid: lazy walker == the old meshgrid semantics
# ---------------------------------------------------------------------------
def test_chunked_grid_matches_meshgrid_order():
    from repro.core.sweep import ChunkedGrid
    axes = {"a": [1.0, 2.0, 3.0], "b": [10.0, 20.0], "c": [5.0]}
    grid = ChunkedGrid(axes)
    assert len(grid) == 6
    mesh = np.meshgrid(*axes.values(), indexing="ij")
    flat = {name: m.reshape(-1) for name, m in zip(axes, mesh)}
    whole = grid.chunk(0, len(grid))
    for name in axes:
        np.testing.assert_array_equal(whole[name], flat[name])
    # chunked walk re-assembles to the same arrays, any chunk size
    for cs in (1, 2, 4, 5, 6, 100):
        parts = [c for _s, c in grid.chunks(cs)]
        for name in axes:
            np.testing.assert_array_equal(
                np.concatenate([p[name] for p in parts]), flat[name])
    # single-point lookup agrees with the flattened order
    for i in range(len(grid)):
        assert grid.point(i) == {n: float(flat[n][i]) for n in axes}


def test_chunked_sweep_equals_monolithic_nondivisible():
    from repro.core.sweep import sweep
    grids = {"variant": ["2d_in"], "cis_node": [130.0, 65.0, 28.0],
             "frame_rate": [15.0, 30.0], "sys_rows": [8.0, 16.0]}
    mono = sweep("rhythmic", grids)
    assert len(mono) == 12
    for cs in (5, 12, 64):        # non-divisible, exact, oversized
        chunked = sweep("rhythmic", grids, chunk_size=cs)
        for key in mono.outputs:
            np.testing.assert_array_equal(chunked.outputs[key],
                                          mono.outputs[key], err_msg=key)
        for key in mono.params:
            np.testing.assert_array_equal(chunked.params[key],
                                          mono.params[key], err_msg=key)


# ---------------------------------------------------------------------------
# Pallas block-stats kernel (the streaming reducer's wide leg)
# ---------------------------------------------------------------------------
def test_block_stats_matches_numpy_masked():
    import jax.numpy as jnp
    from repro.kernels import block_stats
    rng = np.random.default_rng(0)
    b, bp = 1000, 128                      # forces padding (1000 % 128 != 0)
    vals = rng.normal(size=b).astype(np.float32)
    mask = rng.uniform(size=b) > 0.3
    mins, amins, sums, counts = map(np.asarray, block_stats(
        jnp.asarray(vals), jnp.asarray(mask), block_points=bp))
    g = int(np.ceil(b / bp))
    assert mins.shape == (g,)
    for i in range(g):
        sl = slice(i * bp, min((i + 1) * bp, b))
        v, m = vals[sl], mask[sl]
        if m.any():
            masked = np.where(m, v, np.inf)
            assert mins[i] == masked.min()
            assert amins[i] == masked.argmin()
            np.testing.assert_allclose(sums[i], v[m].sum(), rtol=1e-5)
            assert counts[i] == m.sum()
        else:
            assert np.isinf(mins[i]) and counts[i] == 0


def test_masked_stats_global_fold():
    import jax.numpy as jnp
    from repro.kernels import masked_stats
    rng = np.random.default_rng(1)
    vals = rng.normal(size=777).astype(np.float32)
    mask = rng.uniform(size=777) > 0.5
    st = {k: np.asarray(v) for k, v in masked_stats(
        jnp.asarray(vals), jnp.asarray(mask), block_points=64).items()}
    masked = np.where(mask, vals, np.inf)
    assert st["min"] == masked.min()
    assert st["argmin"] == masked.argmin()
    np.testing.assert_allclose(st["sum"], vals[mask].sum(), rtol=1e-5)
    assert st["count"] == mask.sum()


# ---------------------------------------------------------------------------
# Streaming engine, single device (mesh of 1): top-k vs best(), summaries
# ---------------------------------------------------------------------------
def test_stream_topk_and_summaries_match_monolithic():
    from repro.core.shard_sweep import sweep_stream
    from repro.core.sweep import sweep
    grids = {"variant": ["2d_in", "3d_in"],
             "cis_node": [130.0, 65.0],
             "frame_rate": [15.0, 30.0, 60.0],
             "sys_rows": [8.0, 16.0, 32.0],
             "active_fraction_scale": [0.25, 1.0]}
    res = sweep("edgaze", grids)
    st = sweep_stream("edgaze", grids, chunk_size=16, k=5)
    assert st.n_points == len(res)
    best = res.best("total_j", k=5)
    # metric values agree rank-for-rank (ties may permute equal rows)
    np.testing.assert_allclose([r["total_j"] for r in st.topk],
                               [r["total_j"] for r in best], rtol=1e-6)
    # every reported row reproduces its metric through the full table
    for row in st.topk:
        mask = res.select(variant=row["variant"],
                          cis_node=row["cis_node"],
                          frame_rate=row["frame_rate"],
                          sys_rows=row["sys_rows"],
                          active_fraction_scale=row[
                              "active_fraction_scale"])
        assert mask.any()
        np.testing.assert_allclose(res.outputs["total_j"][mask][0],
                                   row["total_j"], rtol=1e-6)
    for variant in ("2d_in", "3d_in"):
        mask = res.params["variant"] == variant
        feas = res.outputs["feasible"][mask].astype(bool)
        vals = res.outputs["total_j"][mask][feas]
        s = st.summaries[variant]
        assert s["n"] == int(mask.sum())
        assert s["n_feasible"] == int(feas.sum())
        np.testing.assert_allclose(s["metric_min"], vals.min(), rtol=1e-6)
        np.testing.assert_allclose(s["metric_mean"], vals.mean(),
                                   rtol=1e-5)
        assert s["argmin_point"] is not None


def test_stream_topk_accumulates_across_chunks_smaller_than_k():
    """chunk_size < k must still return the full top-k: the running state
    keeps k entries, not min(k, chunk) (regression)."""
    from repro.core.shard_sweep import sweep_stream
    from repro.core.sweep import sweep
    grids = {"variant": ["3d_in"], "cis_node": [130.0, 90.0, 65.0],
             "frame_rate": [15.0, 30.0, 60.0],
             "active_fraction_scale": [0.25, 0.5, 1.0]}
    res = sweep("edgaze", grids)
    st = sweep_stream("edgaze", grids, chunk_size=4, k=8)
    best = res.best("total_j", k=8)
    assert len(st.topk) == len(best) == 8
    np.testing.assert_allclose([r["total_j"] for r in st.topk],
                               [r["total_j"] for r in best], rtol=1e-6)


def test_stream_infeasible_points_masked_out():
    from repro.core.shard_sweep import sweep_stream
    st = sweep_stream("edgaze", {"variant": ["2d_in"],
                                 "frame_rate": [1e5]}, chunk_size=8, k=3)
    assert st.n_feasible == 0
    assert st.topk == []                   # nothing feasible -> no winners
    assert st.summaries["2d_in"]["argmin_point"] is None


# ---------------------------------------------------------------------------
# ISSUE 3: one-executable mega-sweeps (PlanBank + on-device decode)
# ---------------------------------------------------------------------------
def test_one_fused_executable_across_variants_and_reruns():
    """A 3-variant stream compiles exactly ONE chunk executable, and
    re-runs — even over different grid VALUES of the same shape — hit the
    executable cache (the bank and axis tables are traced inputs)."""
    from repro.core.shard_sweep import (stream_cache_clear,
                                        stream_cache_info, sweep_stream)
    grids = {"variant": ["2d_in", "3d_in", "2d_off"],
             "cis_node": [130.0, 65.0, 28.0],
             "frame_rate": [15.0, 30.0],
             "sys_rows": [8.0, 16.0]}
    stream_cache_clear()
    st = sweep_stream("edgaze", grids, chunk_size=8, k=3)
    info = stream_cache_info()
    assert st.n_variants == 3
    assert info["step_compiles"] == 1 and info["size"] == 1, info
    st2 = sweep_stream("edgaze", grids, chunk_size=8, k=3)
    regridded = dict(grids, cis_node=[110.0, 55.0, 22.0])
    sweep_stream("edgaze", regridded, chunk_size=8, k=3)
    info = stream_cache_info()
    assert info["step_compiles"] == 1 and info["hits"] == 2, info
    # donated state buffers stay sound across cached re-runs
    np.testing.assert_array_equal([r["total_j"] for r in st2.topk],
                                  [r["total_j"] for r in st.topk])


def test_stream_multi_algorithm_single_call():
    """One sweep_stream call banks variants of BOTH algorithms; results
    match the per-algorithm monolithic oracles."""
    from repro.core.shard_sweep import sweep_stream
    from repro.core.sweep import sweep
    grids = {"variant": ["2d_in", "3d_in"],
             "cis_node": [130.0, 65.0],
             "frame_rate": [15.0, 30.0, 60.0],
             "sys_rows": [8.0, 16.0]}
    st = sweep_stream(["edgaze", "rhythmic"], grids, chunk_size=8, k=6)
    monos = {a: sweep(a, grids) for a in ("edgaze", "rhythmic")}
    assert st.algorithm == "edgaze+rhythmic"
    assert st.n_variants == 4
    assert st.n_points == sum(len(m) for m in monos.values())
    assert st.n_feasible == sum(
        int(m.outputs["feasible"].astype(bool).sum())
        for m in monos.values())
    # global top-k equals the best rows of the union table
    union = np.sort(np.concatenate(
        [np.where(m.outputs["feasible"].astype(bool),
                  m.outputs["total_j"], np.inf) for m in monos.values()]))
    np.testing.assert_allclose([r["total_j"] for r in st.topk],
                               union[:6], rtol=1e-6)
    # summaries are keyed algo/variant and match per-variant tables
    for algo, mono in monos.items():
        for variant in ("2d_in", "3d_in"):
            mask = mono.params["variant"] == variant
            feas = mono.outputs["feasible"][mask].astype(bool)
            s = st.summaries[f"{algo}/{variant}"]
            assert s["n"] == int(mask.sum())
            np.testing.assert_allclose(
                s["metric_min"],
                mono.outputs["total_j"][mask][feas].min(), rtol=1e-6)
    # every top row carries its owning algorithm
    assert {r["algorithm"] for r in st.topk} <= {"edgaze", "rhythmic"}


def test_stream_index_range_partitions_compose():
    """index_range slices of the flat stream compose to the full sweep —
    the multi-host partitioning contract."""
    from repro.core.shard_sweep import sweep_stream
    grids = {"variant": ["2d_in", "3d_in"],
             "cis_node": [130.0, 65.0, 28.0],
             "frame_rate": [15.0, 30.0],
             "active_fraction_scale": [0.25, 1.0]}
    full = sweep_stream("edgaze", grids, chunk_size=8, k=4)
    total = full.n_points
    cut = total // 3 + 1                   # splits inside a variant run
    lo_part = sweep_stream("edgaze", grids, chunk_size=8, k=4,
                           index_range=(0, cut))
    hi_part = sweep_stream("edgaze", grids, chunk_size=8, k=4,
                           index_range=(cut, total))
    assert lo_part.n_points == cut and hi_part.n_points == total - cut
    assert (lo_part.n_feasible + hi_part.n_feasible) == full.n_feasible
    for variant in ("2d_in", "3d_in"):
        assert (lo_part.summaries[variant]["n"]
                + hi_part.summaries[variant]["n"]) \
            == full.summaries[variant]["n"]
    merged = sorted([r["total_j"] for r in lo_part.topk]
                    + [r["total_j"] for r in hi_part.topk])[:4]
    np.testing.assert_allclose(merged,
                               [r["total_j"] for r in full.topk], rtol=0)


def _batch_oracle(variant, grids, local):
    """The per-plan batched evaluator at one variant-local index."""
    from repro.core.batch import evaluate_batch, make_points
    from repro.core.sweep import _normalize_grids, lower_variant, \
        variant_grid
    plan = lower_variant("edgaze", variant)
    _variants, ngrids = _normalize_grids("edgaze", dict(grids))
    point = variant_grid(plan, ngrids).point(local)
    return evaluate_batch(plan, make_points(
        plan, 1, **{ax: [val] for ax, val in point.items()}))


@pytest.mark.slow
@pytest.mark.parametrize("engine", ["fused", "staged"])
def test_stream_past_int32_ceiling(engine):
    """A space of more than 2**32 points, each variant under 2**31,
    streams on int32 (variant, offset) indices (ISSUE 3 regression): a
    window whose global indices pass 2**32 is cut into its variant's
    segment, and its winner matches the per-plan batched oracle — on
    the megakernel scan engine and the staged oracle alike."""
    from repro.core.shard_sweep import sweep_stream
    variants = ["2d_in", "2d_off", "3d_in", "3d_in_stt", "2d_in_mixed"]
    grids = {"variant": variants,
             "cis_node": list(np.linspace(28.0, 130.0, 1500)),
             "frame_rate": list(np.linspace(15.0, 120.0, 1500)),
             "active_fraction_scale": list(np.linspace(0.1, 1.0, 400))}
    n_var = 1500 * 1500 * 400
    total = len(variants) * n_var
    assert n_var < 2 ** 31 and total >= 2 ** 32
    st = sweep_stream("edgaze", grids, chunk_size=64, k=4,
                      index_range=(total - 150, total), engine=engine)
    assert st.n_points == 150
    assert st.summaries["2d_in_mixed"]["n"] == 150
    assert sum(s["n"] for s in st.summaries.values()) == 150
    row = st.topk[0]
    assert row["variant"] == "2d_in_mixed"
    assert (len(variants) - 1) * n_var + row["index"] >= 2 ** 32
    ref = _batch_oracle("2d_in_mixed", grids, row["index"])
    np.testing.assert_allclose(ref["total_j"][0], row["total_j"],
                               rtol=1e-6)


@pytest.mark.parametrize("campaign", [False, True])
def test_wide_grid_on_compiled_pallas_raises(monkeypatch, tmp_path,
                                             campaign):
    """One variant of 2**31 points or more cannot be held as an int32
    offset: explore() (or a campaign) refuses it before compiling, on
    the compiled Pallas lane as on every other, instead of falling back
    to another lane."""
    from repro.core.shard_sweep import stream_cache_info
    from repro.explore import DesignSpace, explore
    from repro.kernels import runtime
    monkeypatch.delenv("REPRO_SWEEP_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    monkeypatch.setattr(runtime, "_BACKEND_IS_TPU", True)
    grids = {"variant": ["3d_in"],
             "cis_node": list(np.linspace(28.0, 130.0, 1500)),
             "frame_rate": list(np.linspace(15.0, 120.0, 1500)),
             "active_fraction_scale": list(np.linspace(0.1, 1.0, 1000))}
    before = stream_cache_info()["step_compiles"]
    kw = dict(checkpoint_dir=str(tmp_path / "c")) if campaign else {}
    with pytest.raises(NotImplementedError,
                       match=r"spans 2250000000 points.*fewer than 2\*\*31"):
        explore(DesignSpace("edgaze", grids), engine="fused", k=4, **kw)
    assert stream_cache_info()["step_compiles"] == before


@pytest.mark.slow
@pytest.mark.parametrize("engine", ["fused", "staged"])
def test_stream_variant_just_under_int32(engine):
    """One variant of 2**31 - 2 points, the largest kind the int32
    offsets hold: a window at its end streams without widening, and the
    chunk that runs past 2**31 wraps its offsets negative, which the
    masks drop (a 24-point chunk does not divide 2**31, so on both
    engines the window's chunk passes it), instead of letting them sneak
    past as valid points."""
    from repro.core.shard_sweep import sweep_stream
    grids = {"variant": ["3d_in"],
             "cis_node": list(np.linspace(28.0, 130.0, 1057)),
             "sys_rows": list(np.linspace(4.0, 128.0, 18)),
             "frame_rate": list(np.linspace(15.0, 120.0, 341)),
             "active_fraction_scale": list(np.linspace(0.1, 1.0, 331))}
    total = 1057 * 18 * 341 * 331
    assert total == 2 ** 31 - 2            # one variant, just under
    st = sweep_stream("edgaze", grids, chunk_size=24, k=3,
                      index_range=(total - 6, total), engine=engine)
    assert st.n_points == 6
    assert st.summaries["3d_in"]["n"] == 6
    assert st.n_feasible <= 6              # wrapped garbage would exceed
    row = st.topk[0]
    assert total - 6 <= row["index"] < total
    ref = _batch_oracle("3d_in", grids, row["index"])
    np.testing.assert_allclose(ref["total_j"][0], row["total_j"],
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# Multi-device: 8 forced host devices in a subprocess
# ---------------------------------------------------------------------------
SCRIPT = r"""
import os
# overwrite (not append): the parent environment may carry a forced
# device count already
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
from repro.core.batch import evaluate_batch, make_points
from repro.core.shard_sweep import (evaluate_batch_sharded,
                                    stream_cache_info, sweep_stream)
from repro.core.sweep import lower_variant, sweep
from repro.launch.mesh import make_batch_mesh

assert len(jax.devices()) == 8
mesh = make_batch_mesh()

# 1. sharded vs unsharded parity, non-divisible batch (pad + slice)
plan = lower_variant("edgaze", "3d_in")
pts = make_points(plan, 1001, cis_node=np.linspace(28, 130, 1001),
                  frame_rate=np.linspace(15, 120, 1001))
ref = evaluate_batch(plan, pts)
sh = evaluate_batch_sharded(plan, pts, mesh=mesh)
for key in ref:
    assert sh[key].shape == ref[key].shape, key
    np.testing.assert_allclose(sh[key], ref[key], rtol=1e-6, atol=0,
                               err_msg=key)

# 2. chunked + sharded sweep == monolithic single-device sweep
grids = {"variant": ["2d_in", "3d_in"], "cis_node": [130.0, 65.0],
         "frame_rate": [15.0, 30.0, 60.0], "sys_rows": [8.0, 16.0, 32.0],
         "mem_tech": ["sram_hp", "stt"]}
mono = sweep("edgaze", grids)
shard = sweep("edgaze", grids, chunk_size=13, mesh=mesh)
assert len(mono) == len(shard)
for key in mono.outputs:
    np.testing.assert_allclose(shard.outputs[key], mono.outputs[key],
                               rtol=1e-6, atol=0, err_msg=key)

# 3. streaming top-k on the 8-device mesh vs best(); the banked path
#    must compile exactly ONE fused chunk executable for both variants
st = sweep_stream("edgaze", grids, chunk_size=32, k=5, mesh=mesh)
assert st.n_devices == 8
assert st.n_points == len(mono)
assert stream_cache_info()["step_compiles"] == 1, stream_cache_info()
best = mono.best("total_j", k=5)
np.testing.assert_allclose([r["total_j"] for r in st.topk],
                           [r["total_j"] for r in best], rtol=1e-6)
feas = mono.outputs["feasible"].astype(bool)
assert st.n_feasible == int(feas.sum())

# 4. multi-algorithm banked stream under the 8-device mesh: one more
#    executable (different bank dims), parity vs per-algorithm oracles
both = sweep_stream(["edgaze", "rhythmic"], grids, chunk_size=32, k=5,
                    mesh=mesh)
assert stream_cache_info()["step_compiles"] == 2, stream_cache_info()
mono_r = sweep("rhythmic", grids)
union = np.sort(np.concatenate(
    [np.where(m.outputs["feasible"].astype(bool),
              m.outputs["total_j"], np.inf) for m in (mono, mono_r)]))
np.testing.assert_allclose([r["total_j"] for r in both.topk],
                           union[:5], rtol=1e-6)

# 5. superchunk scan vs PR-3 staged loop driver on the 8-device mesh:
#    same results, strictly fewer executable dispatches
stg = sweep_stream("edgaze", grids, chunk_size=32, k=5, mesh=mesh,
                   engine="staged")
np.testing.assert_allclose([r["total_j"] for r in st.topk],
                           [r["total_j"] for r in stg.topk], rtol=1e-6)
assert st.n_feasible == stg.n_feasible
assert st.dispatches < stg.dispatches, (st.dispatches, stg.dispatches)
print("SHARD_SWEEP_OK")
"""


@pytest.mark.slow
def test_sharded_streaming_matches_single_device():
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARD_SWEEP_OK" in proc.stdout, proc.stdout
