"""ISSUE 7: the `repro.analysis` static invariant checker.

Three pillars:

* the repo itself is clean — `python -m repro.analysis` over the default
  scope (core/, kernels/, explore/) reports nothing new, which is what
  lets CI fail on ANY new finding;
* mutation detection — deliberately re-introducing the failure modes the
  rules exist for (a dropped vdd_scale hook in one evaluator, a
  `.item()` host sync inside the superchunk scan body, an unhashable
  static_argnums argument, a dimensionally wrong energy term) produces
  the named rule violation;
* the framework contract — noqa suppression, content-addressed baseline
  fingerprints that survive unrelated edits, and CLI exit codes.
"""
import json
import shutil
import textwrap

import pytest

from repro.analysis import (DEFAULT_PATHS, analyze_paths, load_baseline,
                            partition_findings, rule_names, save_baseline)
from repro.analysis.__main__ import main as cli_main

SRC = __file__.rsplit("/tests/", 1)[0] + "/src/repro"


def _write(tmp_path, name, body):
    p = tmp_path / name
    p.write_text(textwrap.dedent(body))
    return str(p)


def _rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# the repo is clean
# ---------------------------------------------------------------------------
def test_repo_default_scope_is_clean():
    findings = analyze_paths()
    baseline = load_baseline()
    new, _old = partition_findings(findings, baseline)
    assert new == [], "\n".join(f.render() for f in new)


def test_default_paths_cover_the_hot_packages():
    tails = {p.rsplit("/", 1)[-1] for p in DEFAULT_PATHS}
    assert tails == {"core", "kernels", "explore", "serve"}


def test_all_rule_families_registered():
    names = set(rule_names())
    assert {"hot-host-sync", "hot-tracer-branch", "hot-kernel-array",
            "hot-nonstatic-pallas-shape", "hot-invariant-transform",
            "jit-unhashable-static", "jit-mutable-global",
            "jit-donated-reuse",
            "axis-hook-coverage", "axis-col-coverage",
            "unit-dim", "dispatch-loop-sync"} <= names


# ---------------------------------------------------------------------------
# mutation: one evaluator drops the vdd_scale hook -> axis-hook-coverage
# ---------------------------------------------------------------------------
def test_mutated_vdd_hook_fails_coverage(tmp_path):
    shutil.copy(f"{SRC}/core/axes.py", tmp_path / "axes.py")
    batch = (tmp_path / "batch.py")
    src = open(f"{SRC}/core/batch.py").read()
    # the dict-style hook application is unique to build_coeff_compute
    needle = '_VDD_HOOKS["dynamic"](pt["vdd_scale"])'
    assert needle in src
    batch.write_text(src.replace(needle, '(pt["vdd_scale"] * 0.0 + 1.0)'))

    findings = analyze_paths([str(batch)], rules=["axis-hook-coverage"])
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "axis-hook-coverage"
    assert "build_coeff_compute" in f.message
    assert "'dynamic'" in f.message and "vdd_scale" in f.message

    # the untouched copy passes the same rule
    batch.write_text(src)
    assert analyze_paths([str(batch)], rules=["axis-hook-coverage"]) == []


def test_mutated_adc_col_fails_coverage(tmp_path):
    shutil.copy(f"{SRC}/core/axes.py", tmp_path / "axes.py")
    batch = (tmp_path / "batch.py")
    src = open(f"{SRC}/core/batch.py").read()
    # sever the banked evaluator's read of the fom_bits coefficient column
    needle = "_ADC_HOOK(pt.adc_bits, g(_ADC_REF_COL))"
    assert needle in src
    batch.write_text(src.replace(
        needle, "_ADC_HOOK(pt.adc_bits, pt.adc_bits * 0.0 + 10.0)"))
    findings = analyze_paths([str(batch)], rules=["axis-col-coverage"])
    assert [f.rule for f in findings] == ["axis-col-coverage"]
    assert "fom_bits" in findings[0].message
    assert "build_banked_eval" in findings[0].message


# ---------------------------------------------------------------------------
# mutation: .item() inside the superchunk scan body -> hot-host-sync
# ---------------------------------------------------------------------------
def test_mutated_scan_body_item_is_flagged(tmp_path):
    sweep = tmp_path / "shard_sweep.py"
    src = open(f"{SRC}/core/shard_sweep.py").read()
    needle = "v = c // cpv"
    assert needle in src
    sweep.write_text(src.replace(needle, "v = c.item() // cpv"))

    findings = analyze_paths([str(sweep)], rules=["hot-host-sync"])
    assert [f.rule for f in findings] == ["hot-host-sync"]
    assert ".item()" in findings[0].message
    assert "c.item()" in findings[0].snippet

    # the shipped file is clean under the same rule
    assert analyze_paths([f"{SRC}/core/shard_sweep.py"],
                         rules=["hot-host-sync"]) == []


# ---------------------------------------------------------------------------
# mutation: .item() inside the XLA fused lane's reduction -> hot-host-sync
# ---------------------------------------------------------------------------
def test_mutated_xla_lane_item_is_flagged(tmp_path):
    """fused_sweep_block_xla is jit-decorated, so the taint engine roots
    it: a host sync smuggled into its reduction body must fire on the
    compiled sweep lane exactly as it does on the Pallas scan driver."""
    mod = tmp_path / "fused_sweep_xla.py"
    src = open(f"{SRC}/kernels/fused_sweep_xla.py").read()
    needle = "counts = jnp.sum(ok.reshape(nb, bp)"
    assert needle in src
    mod.write_text(src.replace(
        needle, "counts = jnp.sum(ok.reshape(nb, bp).item() * ok.reshape(nb, bp)"))

    findings = analyze_paths([str(mod)], rules=["hot-host-sync"])
    assert [f.rule for f in findings] == ["hot-host-sync"]
    assert ".item()" in findings[0].message

    # the shipped XLA lane is clean under the same rule
    assert analyze_paths([f"{SRC}/kernels/fused_sweep_xla.py"],
                         rules=["hot-host-sync"]) == []


# ---------------------------------------------------------------------------
# mutation: re-introduce the PR-7 dogfood finding -> hot-invariant-transform
# ---------------------------------------------------------------------------
def test_relayout_inside_scan_driver_is_flagged(tmp_path):
    sweep = tmp_path / "shard_sweep.py"
    src = open(f"{SRC}/core/shard_sweep.py").read()
    needle = ("def superchunk(c0, lows, limits, c_hi, table2, bank_arrays, "
              "state):")
    assert needle in src
    sweep.write_text(src.replace(
        needle,
        "def superchunk(c0, lows, limits, c_hi, tables, bank_arrays, "
        "state):\n"
        "        table2 = jnp.transpose(tables, (1, 0, 2)).reshape(\n"
        "            tables.shape[1], -1).astype(jnp.float32)"))
    findings = analyze_paths([str(sweep)],
                             rules=["hot-invariant-transform"])
    assert [f.rule for f in findings] == ["hot-invariant-transform"]
    assert "superchunk" in findings[0].message
    assert "hoist" in findings[0].message


# ---------------------------------------------------------------------------
# mutation: unhashable static_argnums argument -> jit-unhashable-static
# ---------------------------------------------------------------------------
def test_unhashable_static_argument_is_flagged(tmp_path):
    mod = _write(tmp_path, "mod.py", """\
        import jax

        def f(shape, y):
            return y.reshape(shape)

        g = jax.jit(f, static_argnums=(0,))

        def run(y):
            return g([4, 2], y)
        """)
    findings = analyze_paths([mod], rules=["jit-unhashable-static"])
    assert [f.rule for f in findings] == ["jit-unhashable-static"]
    assert "static" in findings[0].message

    # hashable tuple at the same position is fine
    clean = _write(tmp_path, "clean.py", """\
        import jax

        def f(shape, y):
            return y.reshape(shape)

        g = jax.jit(f, static_argnums=(0,))

        def run(y):
            return g((4, 2), y)
        """)
    assert analyze_paths([clean], rules=["jit-unhashable-static"]) == []


def test_unhashable_static_argname_direct_invocation(tmp_path):
    mod = _write(tmp_path, "mod.py", """\
        import jax

        def f(x, *, opts):
            return x

        def run(x):
            return jax.jit(f, static_argnames=("opts",))(x, opts={"a": 1})
        """)
    findings = analyze_paths([mod], rules=["jit-unhashable-static"])
    assert [f.rule for f in findings] == ["jit-unhashable-static"]
    assert "opts" in findings[0].message


# ---------------------------------------------------------------------------
# mutation: dimensionally wrong energy term -> unit-dim
# ---------------------------------------------------------------------------
def test_mutated_energy_term_dimension_is_flagged(tmp_path):
    plan = tmp_path / "plan.py"
    src = open(f"{SRC}/core/plan.py").read()
    needle = "sink_const.append(cell.energy_per_conversion * apo)"
    assert needle in src
    plan.write_text(src.replace(
        needle,
        "sink_const.append(cell.energy_per_conversion * cell.vdda * apo)"))
    findings = analyze_paths([str(plan)], rules=["unit-dim"])
    assert [f.rule for f in findings] == ["unit-dim"]
    assert "sink_const" in findings[0].message
    assert "J" in findings[0].message

    plan.write_text(src)
    assert analyze_paths([str(plan)], rules=["unit-dim"]) == []


# ---------------------------------------------------------------------------
# remaining hot-path rules on focused snippets
# ---------------------------------------------------------------------------
def test_tracer_branch_in_jitted_function(tmp_path):
    mod = _write(tmp_path, "mod.py", """\
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
        """)
    findings = analyze_paths([mod], rules=["hot-tracer-branch"])
    assert [f.rule for f in findings] == ["hot-tracer-branch"]


def test_static_shape_reads_are_not_tainted(tmp_path):
    mod = _write(tmp_path, "mod.py", """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            if x.ndim > 1:
                x = x.reshape(-1)
            for _ in range(x.shape[0] // 4):
                x = x * 2.0
            return float(x.size) * jnp.sum(x)
        """)
    assert analyze_paths([mod], rules=["hot-tracer-branch",
                                       "hot-host-sync"]) == []


def test_kernel_array_construction_is_flagged(tmp_path):
    mod = _write(tmp_path, "mod.py", """\
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def kern(x_ref, o_ref):
            bias = jnp.array([1.0, 2.0])
            o_ref[...] = x_ref[...] + bias[0]

        def run(x):
            return pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)
        """)
    findings = analyze_paths([mod], rules=["hot-kernel-array"])
    assert [f.rule for f in findings] == ["hot-kernel-array"]


def test_nonstatic_pallas_grid_is_flagged(tmp_path):
    mod = _write(tmp_path, "mod.py", """\
        import jax
        from jax.experimental import pallas as pl

        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        @jax.jit
        def run(x, n):
            return pl.pallas_call(
                kern, grid=(n,),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)
        """)
    findings = analyze_paths([mod], rules=["hot-nonstatic-pallas-shape"])
    assert [f.rule for f in findings] == ["hot-nonstatic-pallas-shape"]
    assert "grid" in findings[0].message

    # shape-derived grids are static even though x is traced
    clean = _write(tmp_path, "clean.py", """\
        import jax
        from jax.experimental import pallas as pl

        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        @jax.jit
        def run(x):
            return pl.pallas_call(
                kern, grid=(x.shape[0] // 8,),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)
        """)
    assert analyze_paths([clean],
                         rules=["hot-nonstatic-pallas-shape"]) == []


def test_mutable_global_capture_is_flagged(tmp_path):
    mod = _write(tmp_path, "mod.py", """\
        import jax

        SCALES = {"gain": 2.0}

        @jax.jit
        def f(x):
            return x * SCALES["gain"]
        """)
    findings = analyze_paths([mod], rules=["jit-mutable-global"])
    assert [f.rule for f in findings] == ["jit-mutable-global"]
    assert "SCALES" in findings[0].message


def test_donated_buffer_reuse_is_flagged(tmp_path):
    mod = _write(tmp_path, "mod.py", """\
        import jax

        def step(state, delta):
            return state + delta

        exe = jax.jit(step, donate_argnums=(0,))

        def drive(state, delta):
            out = exe(state, delta)
            return out + state
        """)
    findings = analyze_paths([mod], rules=["jit-donated-reuse"])
    assert [f.rule for f in findings] == ["jit-donated-reuse"]
    assert "state" in findings[0].message

    # rebinding the donated name from the result is the sanctioned shape
    clean = _write(tmp_path, "clean.py", """\
        import jax

        def step(state, delta):
            return state + delta

        exe = jax.jit(step, donate_argnums=(0,))

        def drive(state, delta):
            for _ in range(3):
                state = exe(state, delta)
            return state
        """)
    assert analyze_paths([clean], rules=["jit-donated-reuse"]) == []


def test_donated_reuse_across_loop_iterations(tmp_path):
    mod = _write(tmp_path, "mod.py", """\
        import jax

        def step(state, delta):
            return state + delta

        exe = jax.jit(step, donate_argnums=(0,))

        def drive(state, delta):
            out = None
            for _ in range(3):
                out = exe(state, delta)
            return out
        """)
    findings = analyze_paths([mod], rules=["jit-donated-reuse"])
    assert [f.rule for f in findings] == ["jit-donated-reuse"]


# ---------------------------------------------------------------------------
# framework: noqa, baseline fingerprints, CLI
# ---------------------------------------------------------------------------
def test_noqa_suppresses_named_rule(tmp_path):
    mod = _write(tmp_path, "mod.py", """\
        import jax

        @jax.jit
        def f(x):
            return float(x)  # repro: noqa[hot-host-sync]
        """)
    assert analyze_paths([mod], rules=["hot-host-sync"]) == []


def test_noqa_bare_and_wrong_rule(tmp_path):
    bare = _write(tmp_path, "bare.py", """\
        import jax

        @jax.jit
        def f(x):
            return float(x)  # repro: noqa
        """)
    assert analyze_paths([bare], rules=["hot-host-sync"]) == []

    wrong = _write(tmp_path, "wrong.py", """\
        import jax

        @jax.jit
        def f(x):
            return float(x)  # repro: noqa[unit-dim]
        """)
    findings = analyze_paths([wrong], rules=["hot-host-sync"])
    assert [f.rule for f in findings] == ["hot-host-sync"]


def test_unknown_rule_selection_raises():
    with pytest.raises(ValueError, match="no-such-rule"):
        analyze_paths([], rules=["no-such-rule"])


def test_fingerprints_survive_unrelated_edits(tmp_path):
    body = """\
        import jax

        @jax.jit
        def f(x):
            return float(x)
        """
    mod = _write(tmp_path, "mod.py", body)
    (before,) = analyze_paths([mod], rules=["hot-host-sync"])
    mod = _write(tmp_path, "mod.py", "# a new leading comment\n"
                 + textwrap.dedent(body))
    (after,) = analyze_paths([mod], rules=["hot-host-sync"])
    assert before.line != after.line
    assert before.fingerprint == after.fingerprint


def test_baseline_roundtrip(tmp_path):
    mod = _write(tmp_path, "mod.py", """\
        import jax

        @jax.jit
        def f(x):
            return float(x)
        """)
    findings = analyze_paths([mod], rules=["hot-host-sync"])
    assert len(findings) == 1
    bl_path = str(tmp_path / "baseline.json")
    save_baseline(findings, bl_path)
    baseline = load_baseline(bl_path)
    new, old = partition_findings(findings, baseline)
    assert new == [] and len(old) == 1


def test_cli_exit_codes_and_report(tmp_path, capsys):
    mod = _write(tmp_path, "mod.py", """\
        import jax

        @jax.jit
        def f(x):
            return float(x)
        """)
    bl = str(tmp_path / "bl.json")
    report = str(tmp_path / "report.json")

    # new finding -> non-zero, rendered with rule name
    rc = cli_main([mod, "--baseline", bl, "--fail-on-new",
                   "--report", report])
    assert rc == 1
    out = capsys.readouterr().out
    assert "hot-host-sync" in out and "1 new" in out
    data = json.load(open(report))
    assert data["counts"]["new"] == 1
    assert data["findings"][0]["rule"] == "hot-host-sync"

    # accept into the baseline -> clean run exits 0
    assert cli_main([mod, "--baseline", bl, "--write-baseline"]) == 0
    assert cli_main([mod, "--baseline", bl, "--fail-on-new"]) == 0

    # clean file -> 0 without any baseline
    clean = _write(tmp_path, "clean.py", "x = 1\n")
    assert cli_main([clean, "--baseline",
                     str(tmp_path / "none.json")]) == 0


def test_cli_list_rules(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for name in ("hot-host-sync", "jit-donated-reuse", "unit-dim"):
        assert name in out


def test_parse_error_is_reported(tmp_path):
    bad = _write(tmp_path, "bad.py", "def f(:\n")
    findings = analyze_paths([bad])
    assert [f.rule for f in findings] == ["parse-error"]


# ---------------------------------------------------------------------------
# dispatch-loop-sync: unconditional drains inside a dispatch loop
# ---------------------------------------------------------------------------
DISPATCH_LOOP = """
    import jax

    def sweep(bank, state, chunks, pipeline_depth):
        exe, keys = _fused_exec(bank)
        inflight = []
        for d0 in chunks:
            state, counts = exe(d0, state)
            inflight.append(counts)
            {pacing}
        jax.block_until_ready(state)          # post-loop barrier: fine
        return jax.device_get(state)          # outside the loop: fine
"""


def _dispatch_case(tmp_path, pacing):
    path = _write(tmp_path, "drv.py", DISPATCH_LOOP.format(pacing=pacing))
    return analyze_paths([path], rules=["dispatch-loop-sync"])


def test_unconditional_loop_sync_is_flagged(tmp_path):
    findings = _dispatch_case(
        tmp_path, "jax.block_until_ready(inflight.pop(0))")
    assert [f.rule for f in findings] == ["dispatch-loop-sync"]
    assert "EVERY iteration" in findings[0].message
    # device_get in the loop body is the same serialization
    findings = _dispatch_case(tmp_path, "host = jax.device_get(counts)")
    assert [f.rule for f in findings] == ["dispatch-loop-sync"]


def test_depth_guarded_pacing_passes(tmp_path):
    findings = _dispatch_case(
        tmp_path,
        "if len(inflight) > pipeline_depth:\n"
        "                jax.block_until_ready(inflight.pop(0))")
    assert findings == []


def test_loop_without_executable_dispatch_passes(tmp_path):
    # draining a results list is not a dispatch loop
    path = _write(tmp_path, "drain.py", """
        import jax

        def drain(results):
            for r in results:
                jax.block_until_ready(r)
    """)
    assert analyze_paths([path], rules=["dispatch-loop-sync"]) == []


def test_shipped_drivers_pass_dispatch_loop_sync():
    findings = analyze_paths([f"{SRC}/core/shard_sweep.py"],
                             rules=["dispatch-loop-sync"])
    assert findings == [], "\n".join(f.render() for f in findings)
