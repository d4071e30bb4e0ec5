"""Fused megakernel + superchunk scan engine (ISSUE 4).

Parity chain: the fused decode->evaluate->reduce megakernel and the
in-executable superchunk scan driver (``engine="fused"``, the default)
must match the PR-3 staged pipeline (``engine="staged"``, the parity
oracle) — and through it the monolithic ``sweep()`` / per-plan oracles —
at rel 1e-6 on top-k values, summaries and feasible counts, including
``index_range`` tail slices and hypothesis-driven grid shapes.  The
superchunk sweep must keep the one-executable invariant, and the LRU cap
on the step-executable cache must evict (and count) instead of growing
unboundedly.
"""
import numpy as np
import pytest

_REL = 1e-6


def _assert_stream_equal(a, b, *, rtol=_REL):
    """Topk/summaries/feasible-count equality between two StreamResults."""
    assert a.n_points == b.n_points
    assert a.n_feasible == b.n_feasible
    np.testing.assert_allclose([r["total_j"] for r in a.topk],
                               [r["total_j"] for r in b.topk], rtol=rtol)
    assert [(r["algorithm"], r["variant"]) for r in a.topk] \
        == [(r["algorithm"], r["variant"]) for r in b.topk]
    assert sorted(a.summaries) == sorted(b.summaries)
    for label, sa in a.summaries.items():
        sb = b.summaries[label]
        assert sa["n"] == sb["n"] and sa["n_feasible"] == sb["n_feasible"]
        for key in ("metric_min", "metric_mean"):
            if np.isnan(sa[key]) or np.isnan(sb[key]):
                assert np.isnan(sa[key]) and np.isnan(sb[key]), (label, key)
            else:
                np.testing.assert_allclose(sa[key], sb[key], rtol=rtol,
                                           err_msg=f"{label}.{key}")
        assert sa["argmin_index"] == sb["argmin_index"], label


def _engines_case(grids, *, algorithm="edgaze", chunk_size=16, k=5,
                  index_range=None, superchunk=None):
    from repro.core.shard_sweep import sweep_stream
    fused = sweep_stream(algorithm, grids, chunk_size=chunk_size, k=k,
                         index_range=index_range, superchunk=superchunk)
    staged = sweep_stream(algorithm, grids, chunk_size=chunk_size, k=k,
                          index_range=index_range, engine="staged")
    assert fused.engine == "fused" and staged.engine == "staged"
    _assert_stream_equal(fused, staged)
    return fused, staged


# ---------------------------------------------------------------------------
# megakernel == staged pipeline (fixed + hypothesis-driven shapes)
# ---------------------------------------------------------------------------
def test_fused_matches_staged_property():
    """Hypothesis sweep over grid shapes, chunk sizes, k and range cuts
    (skips without hypothesis, mirroring the grid_decode tests)."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    strategy = st.tuples(
        st.integers(min_value=1, max_value=3),            # cis nodes
        st.integers(min_value=1, max_value=3),            # frame rates
        st.integers(min_value=1, max_value=2),            # sys rows
        st.integers(min_value=1, max_value=2),            # variants
        st.integers(min_value=1, max_value=19),           # chunk size
        st.integers(min_value=1, max_value=6),            # k
        st.integers(min_value=0, max_value=100),          # lo seed
        st.integers(min_value=0, max_value=100),          # hi seed
    )
    cis = [130.0, 65.0, 28.0]
    fps = [15.0, 30.0, 60.0]
    rows = [8.0, 32.0]
    variants = ["2d_in", "3d_in"]

    @hyp.settings(max_examples=10, deadline=None)
    @hyp.given(strategy)
    def run(params):
        nc, nf, nr, nv, chunk, k, lo_s, hi_s = params
        grids = {"variant": variants[:nv], "cis_node": cis[:nc],
                 "frame_rate": fps[:nf], "sys_rows": rows[:nr]}
        total = nv * nc * nf * nr
        lo = lo_s % total
        hi = lo + 1 + (hi_s % (total - lo))
        _engines_case(grids, chunk_size=chunk, k=k, index_range=(lo, hi))

    run()


# ---------------------------------------------------------------------------
# superchunk scan driver == per-chunk loop driver
# ---------------------------------------------------------------------------
def test_superchunk_lengths_agree():
    """Any scan length gives identical results to per-chunk dispatch
    (superchunk=1): the in-executable loop is pure index arithmetic."""
    from repro.core.shard_sweep import sweep_stream
    grids = {"variant": ["2d_in", "3d_in"],
             "cis_node": [130.0, 65.0, 28.0],
             "frame_rate": [15.0, 30.0],
             "sys_rows": [8.0, 16.0]}
    ref = sweep_stream("edgaze", grids, chunk_size=8, k=4, superchunk=1)
    assert ref.superchunk == 1
    for s in (2, 3, 16):
        res = sweep_stream("edgaze", grids, chunk_size=8, k=4,
                           superchunk=s)
        assert res.superchunk == s
        assert res.dispatches == -(-ref.dispatches // s)
        _assert_stream_equal(res, ref)


def test_superchunk_single_executable_and_dispatch_drop():
    """The scan sweep compiles exactly ONE step executable and dispatches
    it ceil(n_chunks / superchunk) times."""
    from repro.core.shard_sweep import (stream_cache_clear,
                                        stream_cache_info, sweep_stream)
    from repro.launch.mesh import make_batch_mesh
    # chunk/dispatch arithmetic is device-count dependent; pin 1 device
    # so the expectations hold under the forced-8-device CI lane too
    mesh = make_batch_mesh(1)
    grids = {"variant": ["2d_in", "3d_in", "2d_off"],
             "cis_node": [130.0, 65.0, 28.0],
             "frame_rate": [15.0, 30.0],
             "sys_rows": [8.0, 16.0]}
    stream_cache_clear()
    res = sweep_stream("edgaze", grids, chunk_size=4, k=3, mesh=mesh)
    info = stream_cache_info()
    assert info["step_compiles"] == 1 and info["size"] == 1, info
    # 3 variants x 12 points at chunk 4 = 9 chunks, folded into one scan
    assert res.dispatches == 1 and res.superchunk == 9
    res2 = sweep_stream("edgaze", grids, chunk_size=4, k=3, mesh=mesh)
    info = stream_cache_info()
    assert info["step_compiles"] == 1 and info["hits"] == 1, info
    _assert_stream_equal(res2, res)


# ---------------------------------------------------------------------------
# occupancy accounting + small-variant chunk clamp
# ---------------------------------------------------------------------------
def test_occupancy_clamps_small_variant_chunks():
    """A chunk_size far beyond the per-variant span must not dispatch
    span-sized masked tails on every chunk: the driver clamps the chunk
    to the span and reports the (near-)full occupancy."""
    from repro.core.shard_sweep import sweep_stream
    from repro.launch.mesh import make_batch_mesh
    grids = {"variant": ["2d_in", "3d_in"],
             "cis_node": [130.0, 65.0, 28.0],
             "frame_rate": [15.0, 30.0]}          # span = 6 per variant
    res = sweep_stream("edgaze", grids, chunk_size=1 << 18, k=3,
                       mesh=make_batch_mesh(1))   # device-count pinned
    assert res.chunk_size == 6                    # clamped to the span
    assert res.occupancy == 1.0
    assert res.n_points == 12


def test_occupancy_reports_masked_tail_work():
    from repro.core.shard_sweep import sweep_stream
    from repro.launch.mesh import make_batch_mesh
    grids = {"variant": ["2d_in"],
             "cis_node": [130.0, 65.0, 28.0],
             "frame_rate": [15.0, 30.0, 60.0]}    # span = 9
    for engine in ("fused", "staged"):
        res = sweep_stream("edgaze", grids, chunk_size=4, k=3,
                           engine=engine, mesh=make_batch_mesh(1))
        # 3 chunks of 4 dispatched for 9 valid points
        assert res.occupancy == pytest.approx(9 / 12), engine


# ---------------------------------------------------------------------------
# LRU cap on the step-executable cache
# ---------------------------------------------------------------------------
def test_stream_cache_lru_eviction():
    from repro.core.shard_sweep import (set_stream_cache_limit,
                                        stream_cache_clear,
                                        stream_cache_info, sweep_stream)
    base = {"variant": ["2d_in"], "cis_node": [130.0, 65.0],
            "frame_rate": [15.0, 30.0]}
    old = set_stream_cache_limit(2)
    try:
        stream_cache_clear()
        # three distinct SHAPES (distinct k) -> three executables
        for k in (1, 2, 3):
            sweep_stream("edgaze", base, chunk_size=4, k=k)
        info = stream_cache_info()
        assert info["step_compiles"] == 3, info
        assert info["size"] == 2 and info["limit"] == 2, info
        assert info["evictions"] == 1, info
        # k=3 is the freshest entry -> still cached
        sweep_stream("edgaze", base, chunk_size=4, k=3)
        assert stream_cache_info()["hits"] == 1
        # k=1 was evicted -> recompiles (and evicts k=2, the new stalest)
        sweep_stream("edgaze", base, chunk_size=4, k=1)
        info = stream_cache_info()
        assert info["step_compiles"] == 4 and info["evictions"] == 2, info
    finally:
        set_stream_cache_limit(old)
        stream_cache_clear()


def test_set_stream_cache_limit_shrinks_immediately():
    from repro.core.shard_sweep import (set_stream_cache_limit,
                                        stream_cache_clear,
                                        stream_cache_info, sweep_stream)
    base = {"variant": ["2d_in"], "cis_node": [130.0, 65.0],
            "frame_rate": [15.0, 30.0]}
    old = set_stream_cache_limit(8)
    try:
        stream_cache_clear()
        for k in (1, 2, 3):
            sweep_stream("edgaze", base, chunk_size=4, k=k)
        assert stream_cache_info()["size"] == 3
        set_stream_cache_limit(1)
        info = stream_cache_info()
        assert info["size"] == 1 and info["evictions"] == 2, info
    finally:
        set_stream_cache_limit(old)
        stream_cache_clear()


# ---------------------------------------------------------------------------
# execution backends: XLA lane == Pallas lane == staged oracle (ISSUE 8)
# ---------------------------------------------------------------------------
def _backend_case(grids, *, algorithm="edgaze", chunk_size=16, k=5,
                  index_range=None, superchunk=None):
    """Run the same sweep through both fused backends + the staged
    oracle and assert full topk/summary parity."""
    from repro.core.shard_sweep import sweep_stream
    xla = sweep_stream(algorithm, grids, chunk_size=chunk_size, k=k,
                       index_range=index_range, superchunk=superchunk,
                       backend="xla")
    pal = sweep_stream(algorithm, grids, chunk_size=chunk_size, k=k,
                       index_range=index_range, superchunk=superchunk,
                       backend="pallas")
    staged = sweep_stream(algorithm, grids, chunk_size=chunk_size, k=k,
                          index_range=index_range, engine="staged")
    assert xla.backend == "xla" and xla.kernel_mode == "xla"
    assert pal.backend == "pallas"
    assert pal.kernel_mode in ("interpret", "compiled")
    assert staged.backend == "pallas"
    _assert_stream_equal(xla, pal)
    _assert_stream_equal(xla, staged)
    return xla, pal, staged


_PARITY_CASES = {
    # multi-variant, tail chunks, a chunk that divides no variant span
    "fixed": dict(grids={"variant": ["2d_in", "3d_in"],
                         "cis_node": [130.0, 65.0, 28.0],
                         "frame_rate": [15.0, 30.0],
                         "sys_rows": [8.0, 16.0, 32.0],
                         "active_fraction_scale": [0.25, 1.0]},
                  chunk_size=13, k=7),
    "multi_algorithm": dict(grids={"variant": ["2d_in", "3d_in"],
                                   "cis_node": [130.0, 65.0],
                                   "frame_rate": [15.0, 60.0],
                                   "sys_rows": [8.0, 32.0],
                                   "mem_tech": ["sram_hp", "stt"]},
                            algorithm=["edgaze", "rhythmic"],
                            chunk_size=8, k=6),
    # the kernel's block is one shard on the interpreter: a 100-point
    # chunk is shorter than a block and no whole number of 8-point tile
    # rows (rounded to 104, 4 positions masked), and its (8, 13) tile's
    # rows are no whole number of 128-lane vregs; 135-point variants, so
    # each second chunk is partly past its variant's end
    "chunk_100": dict(grids={"variant": ["2d_in", "3d_in"],
                             "cis_node": [130.0, 65.0, 28.0],
                             "frame_rate": [15.0, 30.0, 60.0, 90.0, 120.0],
                             "sys_rows": [8.0, 16.0, 32.0],
                             "active_fraction_scale": [0.25, 0.5, 1.0]},
                      chunk_size=100, k=9),
}
#: index_range cuts landing inside chunks and inside variants: the fused
#: path masks a chunk's low side (ordinals are span-aligned, the staged
#: driver starts chunks exactly at the cut)
_CUT_GRIDS = {"variant": ["2d_in", "3d_in"],
              "cis_node": [130.0, 65.0, 28.0],
              "frame_rate": [15.0, 30.0],
              "active_fraction_scale": [0.25, 1.0]}
_CUT_TOTAL = 2 * 3 * 2 * 2
_PARITY_CASES.update(
    (f"index_range_{lo}_{hi}",
     dict(grids=_CUT_GRIDS, chunk_size=8, k=4, index_range=(lo, hi)))
    for lo, hi in ((0, _CUT_TOTAL), (5, _CUT_TOTAL - 3),
                   (_CUT_TOTAL // 2 - 1, _CUT_TOTAL // 2 + 3)))


@pytest.mark.parametrize("case", sorted(_PARITY_CASES))
def test_fused_parity(case):
    """Both fused backends (the XLA twin and the Pallas megakernel) ==
    the staged oracle on fixed cases; the fused driver folds many chunks
    into one scan dispatch, and neither drops nor double-counts a point
    under non-divisible chunking or a cut range."""
    kw = _PARITY_CASES[case]
    xla, pal, staged = _backend_case(**kw)
    lo, hi = kw.get("index_range") or (0, None)
    n = staged.n_points if hi is None else hi - lo
    assert xla.n_points == pal.n_points == n
    if case == "fixed":
        assert n == 2 * 3 * 2 * 3 * 2
    # both lanes ride the same scan driver: dispatch counts agree
    assert xla.dispatches == pal.dispatches
    if kw.get("index_range") is None:
        assert xla.dispatches < staged.dispatches


def test_backend_parity_property():
    """Hypothesis sweep over grid shapes / chunk / k / range cuts with
    the XLA lane judged against the Pallas lane and the staged oracle."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    strategy = st.tuples(
        st.integers(min_value=1, max_value=3),            # cis nodes
        st.integers(min_value=1, max_value=3),            # frame rates
        st.integers(min_value=1, max_value=2),            # variants
        st.integers(min_value=1, max_value=19),           # chunk size
        st.integers(min_value=1, max_value=6),            # k
        st.integers(min_value=0, max_value=100),          # lo seed
        st.integers(min_value=0, max_value=100),          # hi seed
    )
    cis = [130.0, 65.0, 28.0]
    fps = [15.0, 30.0, 60.0]
    variants = ["2d_in", "3d_in"]

    @hyp.settings(max_examples=8, deadline=None)
    @hyp.given(strategy)
    def run(params):
        nc, nf, nv, chunk, k, lo_s, hi_s = params
        grids = {"variant": variants[:nv], "cis_node": cis[:nc],
                 "frame_rate": fps[:nf]}
        total = nv * nc * nf
        lo = lo_s % total
        hi = lo + 1 + (hi_s % (total - lo))
        _backend_case(grids, chunk_size=chunk, k=k, index_range=(lo, hi))

    run()


@pytest.mark.slow
def test_backend_xla_variant_just_under_int32():
    """The XLA lane on one variant of 2**31 - 2 points, the largest the
    int32 offsets hold: a tail slice must match the Pallas lane
    bit-for-bit, its chunk's offsets past 2**31 (wrapped negative: 24
    does not divide 2**31) masked."""
    from repro.core.shard_sweep import sweep_stream
    grids = {"variant": ["3d_in"],
             "cis_node": list(np.linspace(28.0, 130.0, 1057)),
             "sys_rows": list(np.linspace(4.0, 128.0, 18)),
             "frame_rate": list(np.linspace(15.0, 120.0, 341)),
             "active_fraction_scale": list(np.linspace(0.1, 1.0, 331))}
    total = 1057 * 18 * 341 * 331
    assert total == 2 ** 31 - 2            # one variant, just under
    xla = sweep_stream("edgaze", grids, chunk_size=24, k=3,
                       index_range=(total - 6, total), backend="xla")
    pal = sweep_stream("edgaze", grids, chunk_size=24, k=3,
                       index_range=(total - 6, total), backend="pallas")
    assert xla.n_points == pal.n_points == 6
    assert total - 6 <= xla.topk[0]["index"] < total
    _assert_stream_equal(xla, pal)


def test_backend_single_executable_each():
    """Each backend keeps the one-executable invariant, and repeat
    sweeps hit the cached entry instead of recompiling."""
    from repro.core.shard_sweep import (stream_cache_clear,
                                        stream_cache_info, sweep_stream)
    from repro.launch.mesh import make_batch_mesh
    mesh = make_batch_mesh(1)
    grids = {"variant": ["2d_in", "3d_in", "2d_off"],
             "cis_node": [130.0, 65.0, 28.0],
             "frame_rate": [15.0, 30.0],
             "sys_rows": [8.0, 16.0]}
    for backend in ("xla", "pallas"):
        stream_cache_clear()
        res = sweep_stream("edgaze", grids, chunk_size=4, k=3, mesh=mesh,
                           backend=backend)
        info = stream_cache_info()
        assert info["step_compiles"] == 1 and info["size"] == 1, \
            (backend, info)
        assert res.dispatches == 1 and res.superchunk == 9, backend
        res2 = sweep_stream("edgaze", grids, chunk_size=4, k=3, mesh=mesh,
                            backend=backend)
        assert stream_cache_info()["hits"] == 1, backend
        _assert_stream_equal(res2, res)


def test_backend_distinct_cache_keys():
    """The backend is part of the executable-cache key: the same sweep
    on both backends compiles TWO executables, and re-running either
    hits its own entry."""
    from repro.core.shard_sweep import (stream_cache_clear,
                                        stream_cache_info, sweep_stream)
    grids = {"variant": ["2d_in"], "cis_node": [130.0, 65.0],
             "frame_rate": [15.0, 30.0]}
    stream_cache_clear()
    sweep_stream("edgaze", grids, chunk_size=4, k=3, backend="xla")
    sweep_stream("edgaze", grids, chunk_size=4, k=3, backend="pallas")
    info = stream_cache_info()
    assert info["step_compiles"] == 2 and info["size"] == 2, info
    sweep_stream("edgaze", grids, chunk_size=4, k=3, backend="xla")
    sweep_stream("edgaze", grids, chunk_size=4, k=3, backend="pallas")
    info = stream_cache_info()
    assert info["step_compiles"] == 2 and info["hits"] == 2, info


def test_backend_staged_rejects_explicit_xla():
    from repro.core.shard_sweep import sweep_stream
    grids = {"variant": ["2d_in"], "cis_node": [130.0, 65.0]}
    with pytest.raises(ValueError, match="staged"):
        sweep_stream("edgaze", grids, chunk_size=4, k=2, engine="staged",
                     backend="xla")
    # "auto" defers -> staged quietly runs its (pallas) pipeline
    res = sweep_stream("edgaze", grids, chunk_size=4, k=2,
                       engine="staged", backend="auto")
    assert res.backend == "pallas"


# ---------------------------------------------------------------------------
# coefficient-form compute == banked vmap evaluator (direct, no driver)
# ---------------------------------------------------------------------------
def _coeff_compute_case(shape):
    """The compute on points of ``shape`` against the banked evaluator,
    and bit for bit against itself on the same points as a vector."""
    import jax.numpy as jnp
    from repro.core.batch import build_coeff_compute, make_points
    from repro.core.plan_bank import build_plan_bank, evaluate_bank
    from repro.core.sweep import lower_variant
    plans = [lower_variant("edgaze", v)
             for v in ("2d_in", "3d_in", "2d_in_mixed")]
    bank = build_plan_bank(plans)
    rng = np.random.default_rng(5)
    n = int(np.prod(shape))
    pts = make_points(
        plans[0], n,
        cis_node=rng.choice([130.0, 65.0, 28.0], n),
        soc_node=rng.choice([14.0, 22.0], n),
        mem_tech=rng.choice([-1, 0, 1, 2], n),
        sys_rows=rng.choice([4.0, 16.0, 64.0], n),
        sys_cols=rng.choice([8.0, 32.0], n),
        frame_rate=rng.choice([15.0, 60.0, 240.0], n),
        active_fraction_scale=rng.choice([0.25, 1.0], n),
        pixel_pitch_um=rng.choice([2.0, 5.0], n))
    compute = build_coeff_compute(bank.dims)
    for vi in range(len(plans)):
        ref = evaluate_bank(bank, np.full(n, vi, np.int32), pts)
        row = bank.arrays["fused"][vi]
        got = compute(row, {ax: jnp.asarray(getattr(pts, ax),
                                            jnp.float32).reshape(shape)
                            for ax in pts._fields})
        flat = compute(row, {ax: jnp.asarray(getattr(pts, ax), jnp.float32)
                             for ax in pts._fields})
        assert sorted(got) == sorted(ref)
        for key in ref:
            assert got[key].shape == shape, (key, got[key].shape)
            np.testing.assert_array_equal(
                np.asarray(got[key]).reshape(-1), np.asarray(flat[key]),
                err_msg=(vi, key))
            np.testing.assert_allclose(np.asarray(got[key]).reshape(-1),
                                       ref[key], rtol=_REL, atol=0,
                                       err_msg=(vi, key))


def test_coeff_compute_matches_banked_eval():
    """The kernel-body physics matches the staged vmap evaluator on a
    random mixed batch for every output key (the XLA twin's ``(B,)``
    points)."""
    _coeff_compute_case((96,))


def test_coeff_compute_on_tiles_matches_banked_eval():
    """The same physics on the megakernel's dense ``(8, B/8)`` tiles
    meets the same parity, and gives each point the bits it gets as a
    ``(B,)`` vector."""
    _coeff_compute_case((8, 12))


def test_megakernel_body_holds_no_matmul():
    """The compiled kernel's body (traced, not compiled) holds no
    ``dot_general``: every slot lookup and the category reduction run on
    the VPU, so no one-hot MXU matmul comes back."""
    import jax
    import jax.numpy as jnp
    from repro.core.batch import build_coeff_compute
    from repro.core.shard_sweep import _prepare_stream
    from repro.core.sweep import AXES
    from repro.kernels.fused_sweep import fused_sweep_block
    prep = _prepare_stream(["edgaze", "rhythmic"], _DECODE_GRIDS)
    compute = build_coeff_compute(prep.bank.dims)
    table = prep.table2[:, :prep.lmax]
    row = prep.bank.arrays["fused"][:1]
    jaxpr = jax.make_jaxpr(lambda t, r: fused_sweep_block(
        t, r, 0, 0, 30, compute=compute, metric="density_mw_mm2",
        axis_names=tuple(AXES), shape=tuple(prep.vgrids[0].shape),
        chunk=1 << 14, block_points=4096, kk=16, interpret=False))(
        table, row)

    def prims(jx):
        for eqn in jx.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from prims(sub)

    calls = [e for e in prims(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    body = [e.primitive.name for e in prims(calls[0].params["jaxpr"])]
    assert "select_n" in body and "reduce_min" in body
    assert "dot_general" not in body


# ---------------------------------------------------------------------------
# the megakernel's decode == the host grid, bit for bit
# ---------------------------------------------------------------------------
#: 30 points a variant (3 x 5 x 2), Ed-Gaze + Rhythmic: 8 variants
_DECODE_GRIDS = {"cis_node": [130.0, 65.0, 28.0],
                 "frame_rate": [15.0, 30.0, 60.0, 90.0, 120.0],
                 "sys_rows": [8.0, 32.0]}
_DECODE_CASES = {
    # axes shorter than the table's lmax: each select chain stops at its
    # axis's length and never reads the padding
    "lmax_padding": dict(grids=_DECODE_GRIDS, chunk=30, ndev=1, block=16),
    # swept length-1 axes beside the default vdd_scale / adc_bits
    "length_1_axes": dict(grids={"cis_node": [65.0], "mem_tech": ["stt"],
                                 "frame_rate": [15.0, 30.0, 60.0]},
                          chunk=3, ndev=1, block=2),
    # the last chunk's blocks run past the end of the flat space
    "last_chunk_tail": dict(grids=_DECODE_GRIDS, chunk=16, ndev=1, block=6),
    # a chunk that does not divide the variant's span: each variant's
    # last chunk runs past its end
    "chunk_past_variant": dict(grids=_DECODE_GRIDS, chunk=8, ndev=1,
                               block=8),
    # four shards a chunk (shard offsets s0), shards wholly past their
    # variant's end, and a cut index range
    "four_shards": dict(grids=_DECODE_GRIDS, chunk=12, ndev=4, block=2,
                        index_range=(7, 8 * 30 - 11)),
}


@pytest.mark.parametrize("case", sorted(_DECODE_CASES))
def test_megakernel_decode_matches_host_grid(case):
    """The megakernel's decode (``decode_block``: the chunk variant's
    table in SMEM, selects over each axis's values) gives the host
    ``ChunkedGrid``'s values bit for bit at every valid position of
    every chunk and shard the superchunk step walks, for every variant
    of Ed-Gaze and Rhythmic, and marks each point of the range valid
    exactly once.  Each block is a ``(8, block / 8)`` tile, its block
    size rounded up to whole rows (the positions past the shard are
    invalid), read back in position order."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from repro.core.shard_sweep import _prepare_stream, split_index_range
    from repro.core.sweep import AXES
    from repro.kernels.fused_sweep import ROWS, decode_block, kernel_block
    from repro.kernels.grid_decode import grid_strides
    kw = _DECODE_CASES[case]
    chunk, ndev = kw["chunk"], kw["ndev"]
    prep = _prepare_stream(["edgaze", "rhythmic"], kw["grids"])
    assert prep.n_variants == 8
    assert list(prep.vgrids[0].names) == list(AXES)
    shape, n_var, lmax = tuple(prep.vgrids[0].shape), prep.n_var, prep.lmax
    total, n_axes, shard = prep.total, len(shape), chunk // ndev
    block = kernel_block(kw["block"], shard)
    nb, tile = -(-shard // block), (ROWS, block // ROWS)

    def kernel(bounds_ref, tab_ref, vals_ref, valid_ref):
        valid, vals = decode_block(
            bounds_ref, tab_ref, shape=shape, strides=grid_strides(shape),
            lmax=lmax, chunk=shard, block=block)
        for a in range(n_axes):
            assert vals[a].shape == tile
            vals_ref[a] = vals[a]
        valid_ref[...] = valid.astype(jnp.int32)

    @jax.jit
    def decode(bounds, table):
        vals, valid = pl.pallas_call(
            kernel, grid=(nb,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 2,
            out_specs=[
                pl.BlockSpec((None, n_axes) + tile, lambda i: (i, 0, 0, 0)),
                pl.BlockSpec((None,) + tile, lambda i: (i, 0, 0))],
            out_shape=[
                jax.ShapeDtypeStruct((nb, n_axes) + tile, jnp.float32),
                jax.ShapeDtypeStruct((nb,) + tile, jnp.int32)],
            interpret=True)(bounds, table.reshape(-1))
        # tile element (r, c) of block g is position g * block + r *
        # (block / ROWS) + c: row-major order is position order
        return (vals.transpose(1, 0, 2, 3).reshape(n_axes, nb * block),
                valid.reshape(nb * block))

    lo, hi = kw.get("index_range", (0, total))
    cpv = -(-n_var // chunk)
    seen = np.zeros(total, np.int64)
    # every chunk ordinal and shard of every variant segment, with the
    # variant-local bounds the superchunk step derives for them
    for vi, vlo, vhi in split_index_range(lo, hi, n_var):
        base = vi * n_var
        table = prep.table2[:, vi * lmax:(vi + 1) * lmax]
        for c in range(cpv):
            for six in range(ndev):
                s0 = c * chunk + six * shard
                vals, valid = decode(
                    jnp.asarray([s0, vlo, vhi], jnp.int32), table)
                vals = np.asarray(vals)[:, :shard]
                valid = np.asarray(valid).astype(bool)
                assert not valid[shard:].any(), (vi, c, six)
                valid = valid[:shard]
                off = s0 + np.arange(shard)
                want = (off >= vlo) & (off < vhi)
                np.testing.assert_array_equal(valid, want,
                                              err_msg=(vi, c, six))
                if not want.any():
                    continue
                seen[base + off[want]] += 1
                host = prep.vgrids[vi].chunk(int(off[want][0]),
                                             int(off[want][-1]) + 1)
                for a, name in enumerate(AXES):
                    np.testing.assert_array_equal(
                        vals[a, want], host[name].astype(np.float32),
                        err_msg=f"{name} in variant {vi} chunk {c}, "
                                f"shard {six}")
    assert (seen[lo:hi] == 1).all()
    assert not seen[:lo].any() and not seen[hi:].any()


FOUR_SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, jax
from repro.core.shard_sweep import sweep_stream
from repro.launch.mesh import make_batch_mesh
assert len(jax.devices()) == 4
grids = {"cis_node": [130.0, 65.0, 28.0],
         "frame_rate": [15.0, 30.0, 60.0, 90.0, 120.0],
         "sys_rows": [8.0, 32.0]}
kw = dict(chunk_size=12, k=8, index_range=(7, 8 * 30 - 11))
out = []
for backend, n in (("pallas", 4), ("xla", 1)):
    res = sweep_stream(["edgaze", "rhythmic"], grids, backend=backend,
                       mesh=make_batch_mesh(n), **kw)
    assert (res.n_devices, res.chunk_size) == (n, 12), (n, res.chunk_size)
    out.append(res.to_payload())
print(json.dumps(out))
"""


def test_megakernel_four_shards_match_one_device():
    """The Pallas megakernel on four devices, where a chunk's last shards
    lie wholly past their variant's end (12-point chunks, 30-point
    variants), gives the XLA twin's one-device result."""
    import json
    import os
    import subprocess
    import sys
    from repro.core.shard_sweep import StreamResult
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", FOUR_SHARD_SCRIPT],
                          env=env, capture_output=True, text=True,
                          timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    pal, xla = (StreamResult.from_payload(p) for p in
                json.loads(proc.stdout.strip().splitlines()[-1]))
    assert pal.n_points == 8 * 30 - 18
    _assert_stream_equal(pal, xla)
