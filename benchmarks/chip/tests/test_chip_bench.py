"""Tests of the chip benchmark's harness, on the CPU at tiny sizes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

They check the trace reduction on a recorded trace, the peak table, the
work counts, that every cell, traffic mix, driver and metric resolves by
name (and that new files are found without an edit), each driver's run
through the harness and its result line, the refusal off a TPU, the
control failing the check, and the check catching a sweep whose timed
path is broken underneath.
"""
from __future__ import annotations

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "src"), HERE]

import bench  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    BENCH = json.load(_fh)
#: every cell file: those of BENCHMARK.json and those kept for a later
#: benchmark change (serve.zipf-refine)
CELLS = sorted(f[:-len(".json")] for f in os.listdir(os.path.join(HERE,
                                                                 "cells")))
ONE_CHIP = [c for c in CELLS if bench.load_cell(c)["chips"] == 1]

TINY_AXES = {
    "cis_node": {"values": [130, 65, 28]},
    "soc_node": {"values": [22]},
    "mem_tech": {"values": ["sram", "stt"]},
    "sys_rows": {"low": 4, "high": 128, "step": 1, "length": 3},
    "sys_cols": {"low": 4, "high": 128, "step": 1, "length": 2},
    "frame_rate": {"low": 15, "high": 240, "step": 1, "length": 4},
    "active_fraction_scale": {"low": 0.1, "high": 1.0, "step": 0.01,
                              "length": 2},
    "pixel_pitch_um": {"low": 2.0, "high": 6.0, "step": 0.05, "length": 2},
}


def tiny_cell(name: str, chips: int = 1):
    """A cell as the harness resolves it, with its space cut to a few
    thousand points and its campaign to ten shards."""
    cell = bench.load_cell(name)
    cell["name"] = name
    cfg = cell["config_data"]
    if "shapes" in cfg:
        small = dict(cis_node=3, soc_node=1, mem_tech=2, sys_rows=2,
                     sys_cols=2, frame_rate=2, active_fraction_scale=2,
                     pixel_pitch_um=2)
        cfg["shapes"] = {"quick": small,
                         "deep": dict(small, sys_rows=3, frame_rate=4)}
        cfg["axes"] = {k: {kk: vv for kk, vv in v.items() if kk != "length"}
                       for k, v in TINY_AXES.items()}
        cfg["base_spaces"] = 3
        cell["traffic_data"]["rate_per_s"] = 5.0
    else:
        cfg["axes"] = copy.deepcopy(TINY_AXES)
        cfg["campaign"] = {"shard_points": 500}
    cell["chips"] = chips
    return cell


def run_tiny(name: str, trace: int = 0, seed: int = 2 ** 31 + 7,
             chips: int = 1):
    args = bench.parse_args(["--workload", name, "--seed", str(seed),
                             "--seconds", "1", "--trace", str(trace)])
    return bench.run_cell(args, require_tpu=False,
                          cell=tiny_cell(name, chips))


# ----- trace reduction ------------------------------------------------------
#: one TPU with a step module, a while loop holding a fusion and the
#: kernel, and an all-reduce; one host thread; times in ns
SMALL_TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 5 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 20000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 21000000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = f32[8]{0:T(128)} fusion(f32[8]{0:T(128)} %p)" } }
  event_metadata { key: 2 value { id: 2
    name: "%branch_1_fun.1 = (f32[64,1,16]{2,1,0:T(1,128)}) custom-call(s32[3]{0} %a)" } }
  event_metadata { key: 3 value { id: 3
    name: "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %b)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_superchunk(1234)" } }
  event_metadata { key: 5 value { id: 5
    name: "%while.4 = (s32[]{:T(128)}, f32[8]{0}) while((s32[]{:T(128)}, f32[8]{0}) %t)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 40000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 15000000 duration_ps: 25000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "explore" } }
  event_metadata { key: 3 value { id: 3 name: "resume" } }
}
"""


def test_trace_reduction_on_a_small_trace(tmp_path):
    from jax.profiler import ProfileData
    trace_mod = bench.load_module(".", "trace")
    raw = ProfileData.text_proto_to_serialized_xspace(SMALL_TRACE)
    path = tmp_path / "plugins" / "profile" / "run" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(raw)
    red = trace_mod.reduce_dir(str(tmp_path))
    assert red["window_s"] == pytest.approx(40e-6)
    assert red["busy_s"] == pytest.approx(6e-6)      # while 5 + all-reduce 1
    assert red["kernel_s"] == pytest.approx(2e-6)
    assert red["collective_s"] == pytest.approx(1e-6)
    assert red["n_devices"] == 1
    # self times: the while holds 1 us of its own beside its body
    assert dict(red["device_ops"]) == pytest.approx(
        {"%fusion.1 (fusion)": 2e-6, "%branch_1_fun.1 (custom-call)": 2e-6,
         "%all-reduce.3 (all-reduce)": 1e-6, "%while.4 (while)": 1e-6})
    # 15 us with no span open; 19 us in resume()
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"no host span": 15e-6, "resume": 19e-6})
    assert red["idle_gaps"][0][0] == "resume"


def test_op_names():
    trace_mod = bench.load_module(".", "trace")
    assert trace_mod.op_name(
        "%sort.1 = (f32[32]{0:T(128)S(1)}, s32[32]{0}) sort(f32[32]{0} "
        "%x, s32[32]{0} %iota.1), dimensions={0}") == ("%sort.1", "sort")
    assert trace_mod.op_name("jit_eval_bank(77)") == ("jit_eval_bank(77)",
                                                       "")


#: recorded on one TPU v5 lite around one 12,579,840-point sweep of the
#: quick shape (``explore`` inside ``bench.window``)
RECORDED = os.path.join(HERE, "tests", "data", "sweep.xplane.pb.gz")


def test_trace_reduction_on_a_chip_trace(tmp_path):
    import gzip
    trace_mod = bench.load_module(".", "trace")
    path = tmp_path / "sweep.xplane.pb"
    with gzip.open(RECORDED) as fh:
        path.write_bytes(fh.read())
    red = trace_mod.reduce_file(str(path))
    assert red["n_devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    assert 0 < red["kernel_s"] <= red["busy_s"]
    assert red["collective_s"] == 0
    # the megakernel is nearly all of the device's time
    assert red["device_ops"][0][0].endswith("(custom-call)")
    assert red["kernel_s"] > 0.9 * red["busy_s"]
    assert red["window_s"] == pytest.approx(0.086234267)
    assert red["busy_s"] == pytest.approx(0.056557927)
    assert sum(v for _, v in red["idle_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)


# ----- peaks and work --------------------------------------------------------
def test_peaks_by_device_kind():
    import peaks
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["flops_bf16"] == 1.97e14
    assert v5e["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99")


def test_work_counts():
    work = bench.load_module("work", "megakernel")
    ops = work.ops_per_point(["edgaze", "rhythmic"])
    assert 100 < ops < 1000
    assert work.ops_per_point(["rhythmic"]) < work.ops_per_point(["edgaze"])
    nbytes = work.bytes_per_chunk(["edgaze", "rhythmic"])
    assert nbytes > 10 * 8 * 16 * 4            # the axis tables at least
    peak = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e9}
    roof = work.roofline(points=10 ** 9, chunks=3744,
                         kernel_s_per_device=1.0, n_devices=1,
                         algorithms=["edgaze", "rhythmic"], peaks=peak)
    assert roof["bound"] == "compute"
    assert roof["share_pct"] == pytest.approx(100 * ops * 1e9 / 1e12)
    four = work.roofline(points=10 ** 9, chunks=3744,
                         kernel_s_per_device=0.25, n_devices=4,
                         algorithms=["edgaze", "rhythmic"], peaks=peak)
    assert four["share_pct"] == pytest.approx(roof["share_pct"])


# ----- resolution by name ----------------------------------------------------
@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves(name):
    cell = bench.load_cell(name)
    entry = next((w for w in BENCH["workloads"] if w["name"] == name), None)
    if entry is not None:
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            entry["config"], entry["traffic"], entry["chips"])
        cfg = next(c for c in BENCH["configs"]
                   if c["name"] == cell["config"])
        assert cfg["file"] == (f"benchmarks/chip/configs/"
                               f"{cell['config']}.json")
    assert cell["config_data"]["name"] == cell["config"]
    driver = bench.load_module("drivers", cell["traffic_data"]["driver"])
    for fn in ("setup", "window", "release", "answers"):
        assert callable(getattr(driver, fn))
    assert "topk_gap" in cell["limits"]
    for trace in (0, 1):
        names = [m["name"] for m in bench.cell_metrics(BENCH, name, trace)]
        assert names
        assert ("setup_s" in names) == (not trace)


@pytest.mark.parametrize("name", sorted(
    {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    | {f[:-len(".py")] for f in os.listdir(os.path.join(HERE, "metrics"))
       if f.endswith(".py")}))
def test_every_metric_resolves(name):
    assert callable(bench.load_module("metrics", name).read)


def test_new_files_are_found_without_an_edit(tmp_path, monkeypatch):
    """A later PR adds a configuration, a traffic mix, a cell, a driver
    and a metric as files; the harness finds each by its name."""
    here = tmp_path / "chip"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns(
        ".jax_cache", ".work", "__pycache__"))
    shutil.copy(here / "configs" / "camj-study.json",
                here / "configs" / "camj-new.json")
    cfg = json.loads((here / "configs" / "camj-new.json").read_text())
    cfg["name"] = "camj-new"
    (here / "configs" / "camj-new.json").write_text(json.dumps(cfg))
    (here / "traffic" / "new-mix.json").write_text(json.dumps(
        {"driver": "new_driver", "check": {"sweeps": 1}}))
    (here / "cells" / "sweep.new.json").write_text(json.dumps(
        {"config": "camj-new", "traffic": "new-mix", "chips": 1,
         "limits": {"topk_gap": 1e-5}}))
    (here / "drivers" / "new_driver.py").write_text(
        "def setup(ctx):\n    return {}\n")
    (here / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 1.0\n")
    monkeypatch.setattr(bench, "HERE", str(here))
    cell = bench.load_cell("sweep.new")
    assert cell["config_data"]["name"] == "camj-new"
    assert cell["traffic_data"]["driver"] == "new_driver"
    assert bench.load_module("drivers", "new_driver").setup(None) == {}
    assert bench.load_module("metrics", "new_metric").read({}) == 1.0
    new = dict(BENCH, per_layer=BENCH["per_layer"] + [
        {"name": "new_metric", "unit": "count", "better": "lower",
         "source": "program_counter", "layer": "service",
         "moves": "sweep_points_per_s", "workloads": ["sweep.new"]}])
    assert [m["name"] for m in bench.cell_metrics(new, "sweep.new", True)
            ] == ["step_compile_s", "new_metric"]


# ----- whole runs on the CPU -------------------------------------------------
def _check_result(out, name, trace):
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "check"
    assert out["correct"] is True, out["check"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    want = {m["name"]: m["unit"] for m in
            bench.cell_metrics(BENCH, name, bool(trace))}
    assert set(out["metrics"]) <= set(want)
    for key, m in out["metrics"].items():
        assert m["unit"] == want[key] and isinstance(m["value"], float)
    dev = out["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    for item in out["check"].values():
        assert item["value"] <= item["limit"]
    json.loads(json.dumps(out, allow_nan=False))
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        assert "step_compile_s" in out["metrics"]
    else:
        assert set(out["metrics"]) == set(want)
        assert "breakdown" not in out


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", ONE_CHIP)
def test_driver_runs_through_the_harness(name, trace, capsys):
    out = run_tiny(name, trace)
    _check_result(out, name, trace)
    printed = capsys.readouterr()
    assert "compiles_in_window=0" in printed.out
    assert printed.err.rstrip().splitlines()[-1] == "check: correct True"


def test_entry_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "bench.py"), "--workload",
         "sweep.study", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 3
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_entry_fails_without_the_program(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's
    files has no system to measure: no result, a non-zero exit."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".jax_cache", ".work",
                                                  "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload",
         "sweep.study", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


# ----- the control and planted faults ----------------------------------------
@pytest.mark.parametrize("name", ["sweep.study", "serve.zipf-refine"])
def test_control_fails_the_check(name):
    """The reference in bfloat16, in the program's place, reads past the
    limits; the program on the same spaces reads within them."""
    import check
    import control
    import jax.numpy as jnp
    from camj_ref.vector import Reference
    cell = tiny_cell(name)
    cfg = cell["config_data"]
    got = control.readings_for_seed(
        cell, 11, 1.0,
        control_ref=Reference(cfg["algorithms"], dtype=jnp.bfloat16))
    assert check.judge(got["program"], cell["limits"]), got["program"]
    assert not check.judge(got["control"], cell["limits"])
    assert got["control"]["topk_gap"] > 10 * cell["limits"]["topk_gap"]


def _lowering_fault(plans):
    """The first variant's MIPI bytes counted 2% high where the
    structure is lowered (``plan.lower``)."""
    import dataclasses
    algo, variant, plan = plans[0]
    plans[0] = (algo, variant, dataclasses.replace(
        plan, mipi_bytes=plan.mipi_bytes * 1.02))


@pytest.mark.parametrize("fault", ["none", "lowering", "bfloat16"])
def test_ref_gap_holds_the_reference_to_the_scalar_model(fault):
    """``ref_gap`` reads the vectorised reference against the scalar
    model: within its limit as it stands, past it with a fault in the
    lowering that the program's sweep could share, and past it in
    bfloat16."""
    import check
    import jax.numpy as jnp
    import spaces
    from camj_ref.vector import Reference
    cell = tiny_cell("sweep.study")
    cfg = cell["config_data"]
    ref = Reference(cfg["algorithms"],
                    dtype=jnp.bfloat16 if fault == "bfloat16"
                    else jnp.float32)
    if fault == "lowering":
        _lowering_fault(ref.plans)
    grids = [spaces.draw_grids(cfg, spaces.rng_for(5, "sweep", i))
             for i in range(2)]
    gap = check.reference_gap(ref, cfg, grids, seed=2 ** 33 + 1)
    limit = cell["limits"]["ref_gap"]
    if fault == "none":
        assert gap <= limit
    else:
        assert gap > 10 * limit


def test_a_lowering_fault_fails_the_run(monkeypatch):
    """A run whose reference lowers a structure wrongly reads ``correct``
    false, though the program's answers are sound."""
    import camj_ref.vector as vector
    real = vector.variant_plans

    def faulty(*a, **kw):
        plans = real(*a, **kw)
        _lowering_fault(plans)
        return plans
    monkeypatch.setattr(vector, "variant_plans", faulty)
    out = run_tiny("sweep.study")
    assert out["correct"] is False
    assert out["check"]["ref_gap"]["value"] > out["check"]["ref_gap"]["limit"]


def _serve_schedule(seed: int, rate: float = 6.0, seconds: float = 20.0):
    import spaces
    cell = tiny_cell("serve.zipf-refine")
    cfg = cell["config_data"]
    driver = bench.load_module("drivers", "open_loop_serve")
    ctx = bench.Context(workload="serve.zipf-refine", seed=seed,
                        seconds=seconds, trace=False, chips=1, cell=cell,
                        config=cfg, traffic=cell["traffic_data"], mesh=None,
                        work_dir="")
    bases = {(b, shape): {"grids": spaces.draw_grids(
        cfg, spaces.rng_for(1, "base", b, s), shape)}
        for b in range(cfg["base_spaces"])
        for s, shape in enumerate(("quick", "deep"))}
    return driver.schedule(ctx, bases, rate, seconds)


def test_serve_schedule_is_poisson_with_the_same_work_every_seed():
    """Every seed sends the same gaps, kinds and bases, each in an order
    of its own; the gaps are those of a Poisson process of the rate."""
    import numpy as np
    a, b = _serve_schedule(2 ** 31 + 5), _serve_schedule(2 ** 32 + 9)
    assert len(a) == len(b) == 120

    def work(reqs):
        gaps = np.diff([0.0] + [r["due"] for r in reqs])
        kinds = sorted((r["shape"], r["repeat"]) for r in reqs)
        return np.sort(gaps), kinds, sorted(r["base"] for r in reqs)
    (ga, ka, ba), (gb, kb, bb) = work(a), work(b)
    assert np.allclose(ga, gb) and ka == kb and ba == bb
    assert [r["due"] for r in a] != [r["due"] for r in b]
    assert [r["shape"] for r in a] != [r["shape"] for r in b]
    assert np.mean(ga) == pytest.approx(1 / 6.0, rel=0.05)
    assert np.median(ga) == pytest.approx(np.log(2) / 6.0, rel=0.05)
    assert sum(r["shape"] == "deep" for r in a) == 24
    assert sum(r["repeat"] for r in a) == 60


def test_knee_rule_is_monotone():
    import knee
    assert knee.knee_of({4.0: False, 5.0: False, 6.0: True,
                         7.0: False}) == 5.0
    assert knee.knee_of({4.0: True, 5.0: False}) == 0.0
    assert knee.knee_of({4.0: False, 5.0: False}) == 5.0
    steady = {"latency_s": [0.01, 0.3, 0.2, 0.01, 0.3, 0.25],
              "requests": [{"repeat": r} for r in
                           (True, False, False, True, False, False)]}
    assert not knee.backlog_grew(steady)
    growing = dict(steady, latency_s=[0.01, 0.3, 0.2, 0.01, 2.0, 3.0])
    assert knee.backlog_grew(growing)


def _stuck_step(monkeypatch):
    """A superchunk step that returns its state unchanged."""
    import jax.numpy as jnp
    from repro.core import shard_sweep
    real = shard_sweep._fused_exec

    def fused_exec(*a, **kw):
        exe, keys = real(*a, **kw)

        def stuck(d0, lo, hi, c_hi, table2, bank, state):
            return state, jnp.zeros((1,), jnp.float32)
        return stuck, keys
    monkeypatch.setattr(shard_sweep, "_fused_exec", fused_exec)


def _half_batch(monkeypatch):
    """Every dispatch scores the first half of its range only; the
    summaries' means are then taken over the rest."""
    from repro.core import shard_sweep
    real = shard_sweep._fused_exec

    def fused_exec(*a, **kw):
        exe, keys = real(*a, **kw)

        def half(d0, lo, hi, c_hi, table2, bank, state):
            return exe(d0, lo, lo + (hi - lo) // 2, c_hi, table2, bank,
                       state)
        return half, keys
    monkeypatch.setattr(shard_sweep, "_fused_exec", fused_exec)


def _altered_answer(monkeypatch):
    """The best row's metric altered where the result is produced."""
    from repro.explore import api
    from repro.serve import service
    real = api._stream_to_explore

    def wrap(space, st, **kw):
        if st.topk:
            st.topk[0] = dict(st.topk[0])
            st.topk[0][st.metric] *= 1.001
        return real(space, st, **kw)
    monkeypatch.setattr(api, "_stream_to_explore", wrap)
    monkeypatch.setattr(service, "_stream_to_explore", wrap)


FAULTS = {"state_unchanged": _stuck_step, "half_batch": _half_batch,
          "altered_answer": _altered_answer}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", ONE_CHIP)
def test_a_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    from repro.core.shard_sweep import stream_cache_clear
    stream_cache_clear()
    FAULTS[fault](monkeypatch)
    try:
        out = run_tiny(name)
    finally:
        stream_cache_clear()
    assert out["correct"] is False


FOUR_DEVICES = r"""
import json, sys
sys.path[:0] = sys.argv[1:3]
import test_chip_bench as t
if sys.argv[3] == "drop_exchange":
    from repro.core import shard_sweep
    real = shard_sweep._merge_candidates

    def first_chip_only(c, v, state, k, with_out):
        kk = c["cand_v"].shape[0] // c["mins"].shape[0]
        c = {key: (val[:kk] if key.startswith("cand") else val[:1])
             for key, val in c.items()}
        return real(c, v, state, k, with_out)
    shard_sweep._merge_candidates = first_chip_only
out = t.run_tiny("sweep.study", chips=4)
print("RESULT " + json.dumps(out))
"""


@pytest.mark.parametrize("fault", ["none", "drop_exchange"])
def test_four_chip_exchange(fault):
    """On four (host) devices the sweep is correct, and it is not once
    the exchange between the chips is left out of the merge."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES, os.path.join(ROOT, "src"),
         os.path.dirname(os.path.abspath(__file__)), fault],
        env=env, capture_output=True, text=True, timeout=900, cwd=HERE)
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")]
    assert proc.returncode == 0 and line, proc.stderr[-3000:]
    out = json.loads(line[-1][len("RESULT "):])
    assert out["device"]["count"] == 4
    assert out["correct"] is (fault == "none"), out["check"]
