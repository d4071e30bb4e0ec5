"""On-device grid decoding + PlanBank banked evaluation (ISSUE 3).

The decoder property test drives ``repro.kernels.grid_decode`` against the
``ChunkedGrid`` host oracle bit-exactly (hypothesis: random shapes with
single-value axes, random variant counts, starts landing on non-divisible
tails and past-the-end clamp regions).  The PlanBank tests pin the banked
evaluator — coefficients as traced inputs — to the per-plan baked-constant
evaluator at 1e-6 relative, per variant and with mixed variant ids.
"""
import numpy as np
import pytest


def _decode_case(lengths, n_variants, start_seed, count, value_seed):
    import jax.numpy as jnp
    from repro.core.sweep import ChunkedGrid, axis_tables
    from repro.kernels.grid_decode import grid_decode

    rng = np.random.default_rng(value_seed)
    grids = [ChunkedGrid({f"a{i}": rng.normal(size=n)
                          for i, n in enumerate(lengths)})
             for _ in range(n_variants)]
    n_var = len(grids[0])
    total = n_variants * n_var
    start = start_seed % total
    tables = jnp.asarray(axis_tables(grids))

    vals, vid = grid_decode(tables, start, shape=grids[0].shape,
                            n_var=n_var, total=total, chunk=count,
                            block_points=3)       # force blocks + tails
    vals, vid = np.asarray(vals), np.asarray(vid)
    assert vals.shape == (len(lengths), count) and vid.shape == (count,)

    flat = np.minimum(np.arange(start, start + count), total - 1)
    exp_vid = flat // n_var
    np.testing.assert_array_equal(vid, exp_vid)
    for j, g in enumerate(flat):
        v, local = divmod(int(g), n_var)
        oracle = grids[v].chunk(local, local + 1)
        for a, name in enumerate(grids[v].names):
            # bit-exact vs the host path's f64 -> f32 cast
            assert vals[a, j] == np.float32(oracle[name][0]), (
                a, j, vals[a, j], oracle[name][0])


def test_grid_decode_matches_chunked_grid_oracle_fixed_cases():
    """Deterministic decode coverage: single-value axes, tails, clamps."""
    _decode_case([3, 1, 2], 2, 4, 13, 0)       # tail past total, 1-axes
    _decode_case([1, 1], 3, 1, 7, 1)           # all-singleton grid
    _decode_case([4, 3, 2, 2], 1, 17, 31, 2)   # non-divisible blocks


def test_decode_one_hot_lookup_is_bit_exact():
    """The compiled path's one-hot matmul lookup (``gather=False``)
    decodes the same f32 values as the direct gather, bit for bit: the
    table has values that one bf16 pass would round."""
    import jax.numpy as jnp
    from repro.kernels.grid_decode import decode_axis_values, grid_strides
    shape, n_variants = (5, 3, 4), 3
    lmax = max(shape)
    rng = np.random.default_rng(11)
    table = jnp.asarray(rng.normal(size=(len(shape), n_variants * lmax))
                        * 1e-9 + 1.0, jnp.float32)
    n_var = int(np.prod(shape))
    off = jnp.arange(n_variants * n_var, dtype=jnp.int32).reshape(1, -1)
    kw = dict(shape=shape, strides=grid_strides(shape), n_var=n_var,
              n_variants=n_variants, lmax=lmax)
    ref, vid = decode_axis_values(off, table, gather=True, **kw)
    got, vid2 = decode_axis_values(off, table, gather=False, **kw)
    np.testing.assert_array_equal(np.asarray(vid), np.asarray(vid2))
    for a in range(len(shape)):
        np.testing.assert_array_equal(np.asarray(got[a]),
                                      np.asarray(ref[a]))


def test_grid_decode_property_vs_host_oracle():
    """Hypothesis sweep of the same oracle (skips without hypothesis)."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    axis_len = st.integers(min_value=1, max_value=4)
    strategy = st.tuples(
        st.lists(axis_len, min_size=2, max_size=5),       # axis lengths
        st.integers(min_value=1, max_value=3),            # n variants
        st.integers(min_value=0, max_value=200),          # start seed
        st.integers(min_value=1, max_value=37),           # count
        st.integers(min_value=0, max_value=2 ** 31 - 1),  # value seed
    )

    @hyp.settings(max_examples=15, deadline=None)
    @hyp.given(strategy)
    def run(params):
        _decode_case(*params)

    run()


def test_grid_strides_match_numpy():
    from repro.kernels.grid_decode import grid_strides
    for shape in [(3,), (2, 5), (4, 1, 3), (1, 1), (2, 3, 4, 5)]:
        idx = np.arange(int(np.prod(shape)))
        multi = np.unravel_index(idx, shape)
        strides = grid_strides(shape)
        for a in range(len(shape)):
            np.testing.assert_array_equal((idx // strides[a]) % shape[a],
                                          multi[a])


def test_block_stats_banked_matches_numpy():
    import jax.numpy as jnp
    from repro.kernels import block_stats_banked
    rng = np.random.default_rng(3)
    b, bp, n_variants = 1000, 128, 3       # forces a padded tail block
    vals = rng.normal(size=b).astype(np.float32)
    mask = rng.uniform(size=b) > 0.3
    vid = rng.integers(0, n_variants, size=b).astype(np.int32)
    mins, amins, sums, counts = map(np.asarray, block_stats_banked(
        jnp.asarray(vals), jnp.asarray(mask), jnp.asarray(vid),
        n_variants, block_points=bp))
    g = int(np.ceil(b / bp))
    assert mins.shape == amins.shape == sums.shape == counts.shape \
        == (g, n_variants)
    for i in range(g):
        sl = slice(i * bp, min((i + 1) * bp, b))
        for w in range(n_variants):
            m = mask[sl] & (vid[sl] == w)
            if m.any():
                masked = np.where(m, vals[sl], np.inf)
                assert mins[i, w] == masked.min()
                assert amins[i, w] == masked.argmin()
                np.testing.assert_allclose(sums[i, w], vals[sl][m].sum(),
                                           rtol=1e-5)
                assert counts[i, w] == m.sum()
            else:
                assert np.isinf(mins[i, w]) and counts[i, w] == 0


# ---------------------------------------------------------------------------
# PlanBank: banked evaluation == per-plan evaluation
# ---------------------------------------------------------------------------
_VARIANTS = ("2d_in", "3d_in", "2d_in_mixed")   # differing unit counts


def _bank_and_points(n=64):
    import jax.numpy as jnp
    from repro.core.batch import make_points
    from repro.core.plan_bank import build_plan_bank
    from repro.core.sweep import lower_variant
    plans = [lower_variant("edgaze", v) for v in _VARIANTS]
    bank = build_plan_bank(plans)
    rng = np.random.default_rng(7)
    pts = make_points(
        plans[0], n,
        cis_node=rng.choice([130.0, 65.0, 28.0], n),
        soc_node=rng.choice([14.0, 22.0], n),
        mem_tech=rng.choice([-1, 0, 1, 2], n),
        sys_rows=rng.choice([4.0, 16.0, 64.0], n),
        frame_rate=rng.choice([15.0, 60.0, 240.0], n),
        active_fraction_scale=rng.choice([0.25, 1.0], n),
        pixel_pitch_um=rng.choice([2.0, 5.0], n))
    return bank, pts, jnp


def test_plan_bank_parity_per_variant():
    from repro.core.batch import evaluate_batch
    from repro.core.plan_bank import evaluate_bank
    bank, pts, jnp = _bank_and_points()
    for vi, plan in enumerate(bank.plans):
        ref = evaluate_batch(plan, pts)
        out = evaluate_bank(bank, np.full(pts.batch, vi, np.int32), pts)
        assert sorted(out) == sorted(ref)
        for key in ref:
            np.testing.assert_allclose(out[key], ref[key], rtol=1e-6,
                                       atol=0, err_msg=(_VARIANTS[vi], key))


def test_plan_bank_parity_mixed_variant_ids():
    from repro.core.batch import evaluate_batch
    from repro.core.plan_bank import evaluate_bank
    bank, pts, jnp = _bank_and_points()
    rng = np.random.default_rng(11)
    vid = rng.integers(0, len(bank.plans), pts.batch).astype(np.int32)
    out = evaluate_bank(bank, vid, pts)
    refs = [evaluate_batch(plan, pts) for plan in bank.plans]
    for key in refs[0]:
        expected = np.choose(vid, [np.asarray(r[key]) for r in refs])
        np.testing.assert_allclose(out[key], expected, rtol=1e-6, atol=0,
                                   err_msg=key)


def test_bank_layout_covers_every_slot():
    from repro.core.plan_bank import bank_layout
    bank, _pts, _ = _bank_and_points(n=1)
    layout = bank_layout(bank.dims)
    width = layout.pop("__width__")[0]
    assert bank.arrays["fused"].shape == (len(bank.plans), width)
    seen = np.zeros(width, bool)
    for name, (off, shape) in layout.items():
        size = int(np.prod(shape)) if shape else 1
        assert not seen[off:off + size].any(), f"{name} overlaps"
        seen[off:off + size] = True
    assert seen.all(), "fused row has unused gaps"
