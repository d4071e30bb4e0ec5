"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per the repo contract, where
``derived`` is the headline quantity the table/figure reports (MAPE, energy
ratios, densities, ...).  The roofline/dry-run tables live in
benchmarks/results/dryrun.json (built by ``python -m repro.launch.dryrun``)
and are summarized by ``roofline_table`` below when present.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, List

from compile_cache import CACHE_DIR, setup_compile_cache

RESULTS = os.path.join(os.path.dirname(__file__), "results")


def _cache_entries() -> int:
    try:
        return len(os.listdir(CACHE_DIR))
    except OSError:
        return 0


def _timed(fn: Callable) -> tuple:
    t0 = time.perf_counter()
    out = fn()
    dt = (time.perf_counter() - t0) * 1e6
    return out, dt


def fig7_validation() -> List[str]:
    """Fig. 7 / Tbl. 2: nine-chip validation (MAPE + Pearson)."""
    from repro.core.chips import validate_all
    r, us = _timed(lambda: validate_all())
    rows = [f"fig7_validation,{us:.0f},mape={r['mape']*100:.1f}%"
            f" pearson={r['pearson']:.5f}"]
    for row in r["rows"]:
        rows.append(f"fig7_{row['chip']},{us/9:.0f},"
                    f"est={row['estimated_pj']:.1f}pJ"
                    f" rep={row['reported_pj']:.1f}pJ"
                    f" err={row['error']*100:.1f}%")
    return rows


def fig9a_rhythmic() -> List[str]:
    """Fig. 9a: Rhythmic Pixel Regions in/off/3D energy."""
    from repro.core.usecases import run_study
    rows_, us = _timed(lambda: run_study("rhythmic"))
    out = []
    for r in rows_:
        bd = " ".join(f"{k}={v:.1f}" for k, v in
                      sorted(r["breakdown_uj"].items()))
        out.append(f"fig9a_{r['cis_node']}nm_{r['variant']},{us:.0f},"
                   f"total={r['total_uj']:.1f}uJ {bd}")
    return out


def fig9b_edgaze() -> List[str]:
    """Fig. 9b + Fig. 11: Ed-Gaze variants incl. mixed-signal."""
    from repro.core.usecases import run_study
    rows_, us = _timed(lambda: run_study("edgaze"))
    out = []
    for r in rows_:
        out.append(f"fig9b_{r['cis_node']}nm_{r['variant']},{us:.0f},"
                   f"total={r['total_uj']:.1f}uJ")
    return out


def tbl3_power_density() -> List[str]:
    """Tbl. 3: power density across variants."""
    from repro.core.usecases import run_study
    out = []
    for algo in ("rhythmic", "edgaze"):
        rows_, us = _timed(lambda a=algo: run_study(a))
        for r in rows_:
            out.append(f"tbl3_{algo}_{r['cis_node']}nm_{r['variant']},"
                       f"{us:.0f},density={r['density_mw_mm2']:.3f}mW/mm2")
    return out


def fig12_stage_breakdown() -> List[str]:
    """Fig. 12/13: Ed-Gaze memory/compute split, digital vs mixed."""
    from repro.core.usecases import run_study
    from repro.core.usecases.study import find_row
    rows_, us = _timed(lambda: run_study("edgaze", cis_nodes=(65,)))
    dig = find_row(rows_, "2d_in", 65)
    mix = find_row(rows_, "2d_in_mixed", 65)
    out = []
    for name, r in (("digital", dig), ("mixed", mix)):
        out.append(f"fig12_{name},{us:.0f},"
                   f"total={r['total_uj']:.1f}uJ"
                   f" mem_d={r['breakdown_uj'].get('MEM-D', 0):.1f}uJ"
                   f" comp_a={r['breakdown_uj'].get('COMP-A', 0):.2f}uJ"
                   f" comp_d={r['breakdown_uj'].get('COMP-D', 0):.2f}uJ")
    return out


def kernel_microbench() -> List[str]:
    """Pallas kernels: walltime in whichever mode the backend selects
    (compiled Mosaic on TPU, interpreter elsewhere — reported per row)."""
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels import kernel_mode, ops
    rng = np.random.default_rng(0)
    img = jnp.asarray(rng.normal(size=(256, 256)).astype(np.float32))
    ker = jnp.asarray(rng.normal(size=(3, 3)).astype(np.float32))
    mode = kernel_mode()
    out = []
    for name, fn in (
            ("binning", lambda: ops.binning(img).block_until_ready()),
            ("stencil_conv", lambda: ops.stencil_conv(img, ker)
             .block_until_ready()),
            ("frame_event", lambda: ops.frame_event(img, img)
             .block_until_ready())):
        fn()  # compile
        t0 = time.perf_counter()
        for _ in range(3):
            fn()
        us = (time.perf_counter() - t0) / 3 * 1e6
        out.append(f"kernel_{name},{us:.0f},mode={mode}")
    return out


def design_sweep(n_scalar_sample: int = 64,
                 emit_json: bool = True) -> List[str]:
    """Batched design-space engine vs the scalar estimate_energy loop.

    Scores >=10k Ed-Gaze + Rhythmic design points (node x frame rate x
    systolic dims x memory tech x gating x pitch) through ``sweep()`` and
    compares wall-clock against looping the scalar oracle over the same
    points.  The scalar side is timed on an even subsample and projected
    (the full loop at ~0.2 ms/point would dominate the harness); the
    batched side is measured directly, cold (lowering + jit) and hot.
    """
    from repro.core.sweep import _sweep_impl, scalar_sweep
    from repro.kernels import kernel_mode

    grids = {"cis_node": [130, 110, 90, 65, 45, 32, 28],
             "frame_rate": [15.0, 30.0, 60.0, 120.0],
             "sys_rows": [4.0, 8.0, 16.0, 32.0],
             "sys_cols": [8.0, 16.0, 32.0],
             "mem_tech": ["sram_hp", "stt"],
             "active_fraction_scale": [0.25, 1.0],
             "pixel_pitch_um": [3.0, 5.0]}

    def run_all():
        # this bench isolates the grid ENGINE (explore()'s host-side
        # result assembly — top-k/summaries over full tables — would
        # otherwise ride the timed region; the explore() front door is
        # exercised end-to-end by the example smoke + test suite)
        return [_sweep_impl(algo, grids)
                for algo in ("edgaze", "rhythmic")]

    t0 = time.perf_counter()
    results = run_all()
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    results = run_all()
    hot_s = time.perf_counter() - t0
    # warm-aware split: compile_s is AOT lowering+compilation (first call
    # only), eval_s the warm device time — the numbers BENCH records no
    # longer depend on call order (satellite of ISSUE 2)
    compile_s = sum(r.compile_s for r in results)
    eval_s = sum(r.eval_s for r in results)
    n_points = sum(len(r) for r in results)
    assert compile_s == 0.0, "second pass must reuse compiled executables"
    assert n_points >= 10_000, n_points

    # scalar oracle: even subsample over both algorithms, projected
    t0 = time.perf_counter()
    n_sampled = 0
    import numpy as np
    for res in results:
        idx = np.linspace(0, len(res) - 1,
                          n_scalar_sample // len(results)).astype(int)
        scalar_sweep(res.algorithm, res.params, idx)
        n_sampled += len(idx)
    scalar_us_pp = (time.perf_counter() - t0) / n_sampled * 1e6
    scalar_total_s = scalar_us_pp * n_points / 1e6

    speedup_hot = scalar_total_s / hot_s
    speedup_cold = scalar_total_s / cold_s
    rec = dict(n_points=n_points,
               batched_hot_s=round(hot_s, 4),
               batched_cold_s=round(cold_s, 4),
               batched_eval_s=round(eval_s, 4),
               batched_us_per_point=round(hot_s / n_points * 1e6, 3),
               eval_us_per_point=round(eval_s / n_points * 1e6, 3),
               scalar_us_per_point=round(scalar_us_pp, 1),
               scalar_sampled_points=n_sampled,
               scalar_projected_s=round(scalar_total_s, 2),
               speedup_hot=round(speedup_hot, 1),
               speedup_cold=round(speedup_cold, 1),
               meets_20x=bool(speedup_hot >= 20.0),
               kernel_mode=kernel_mode())
    if emit_json:
        _update_bench_json(rec)
        import jax
        _append_history("design_sweep", rec,
                        devices=jax.local_device_count())
    return [f"design_sweep,{hot_s*1e6:.0f},points={n_points}"
            f" speedup={speedup_hot:.0f}x (cold {speedup_cold:.1f}x)"
            f" scalar={scalar_us_pp:.0f}us/pt"
            f" batched={hot_s/n_points*1e6:.2f}us/pt"
            f" eval={eval_s/n_points*1e6:.2f}us/pt"
            f" mode={rec['kernel_mode']}"]


def _update_bench_json(rec: dict) -> None:
    """Merge ``rec`` into BENCH_sweep.json (design_sweep + mega_sweep
    write disjoint keys into the same trajectory file)."""
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, "BENCH_sweep.json")
    merged = {}
    if os.path.exists(path):
        with open(path) as f:
            merged = json.load(f)
    merged.update(rec)
    with open(path, "w") as f:
        json.dump(merged, f, indent=1)


#: append-only perf trajectory: BENCH_sweep.json only keeps the LATEST
#: numbers, so until ISSUE 4 the "trajectory" was a single point.  Every
#: bench run appends one schema-versioned row here; the CI throughput
#: guard (benchmarks/check_regression.py) reads the tail as its baseline.
HISTORY = os.path.join(RESULTS, "BENCH_history.jsonl")
HISTORY_SCHEMA = 1


def _git_sha():
    try:
        import subprocess
        out = subprocess.run(["git", "rev-parse", "--short=12", "HEAD"],
                             capture_output=True, text=True, timeout=10,
                             cwd=os.path.dirname(__file__))
        return out.stdout.strip() or None
    except Exception:  # noqa: BLE001 - history rows degrade gracefully
        return None


def _append_history(bench: str, rec: dict, devices) -> None:
    """Append one run record to the BENCH_history.jsonl trajectory."""
    os.makedirs(RESULTS, exist_ok=True)
    row = {"schema": HISTORY_SCHEMA, "ts": round(time.time(), 2),
           "git_sha": _git_sha(), "bench": bench, "devices": devices,
           "cpus": os.cpu_count()}
    row.update(rec)
    with open(HISTORY, "a") as f:
        f.write(json.dumps(row) + "\n")


def read_history(bench: str = None) -> List[dict]:
    """All (optionally bench-filtered) history rows, oldest first.

    The history file is append-only and crash-prone by nature (a killed
    bench run leaves a truncated last line), so corrupt, truncated or
    non-object lines are skipped WITH A WARNING instead of poisoning or
    crashing the regression guard; an empty/absent file is simply no
    history."""
    import sys
    rows = []
    if not os.path.exists(HISTORY):
        return rows
    with open(HISTORY) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError:
                print(f"warning: {HISTORY}:{lineno}: skipping "
                      f"malformed history line (truncated or corrupt "
                      f"JSON)", file=sys.stderr)
                continue
            if not isinstance(row, dict):
                print(f"warning: {HISTORY}:{lineno}: skipping "
                      f"non-object history row "
                      f"({type(row).__name__})", file=sys.stderr)
                continue
            if bench is None or row.get("bench") == bench:
                rows.append(row)
    return rows


# grid for the mega_sweep bench: ~1.57e6 points per structural variant,
# ~1.26e7 across the 5 Ed-Gaze + 3 Rhythmic variants
MEGA_GRIDS = {
    "cis_node": [130., 110., 90., 80., 65., 55., 45., 40., 32., 28., 22.,
                 16., 14.],
    "soc_node": [14., 22., 28.],
    "frame_rate": [15., 24., 30., 45., 60., 90., 120., 240.],
    "sys_rows": [4., 8., 16., 32., 48., 64., 96., 128.],
    "sys_cols": [4., 8., 16., 32., 64., 128.],
    "mem_tech": ["sram", "sram_hp", "stt"],
    "active_fraction_scale": [0.1, 0.25, 0.5, 0.75, 1.0],
    "pixel_pitch_um": [2., 2.5, 3., 3.5, 4., 5., 6.],
}

_MEGA_CHILD = r"""
import json, os, sys
n_dev = int(sys.argv[1])
# the forced-host-device lanes measure HOST-CPU device scaling by design,
# so pin the cpu platform (they run only where no accelerator is in
# use); keep any other operator XLA flags, replacing only a stale
# forced count
os.environ["JAX_PLATFORMS"] = "cpu"
flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
         if not f.startswith("--xla_force_host_platform_device_count")]
os.environ["XLA_FLAGS"] = " ".join(
    flags + [f"--xla_force_host_platform_device_count={n_dev}"])
sys.path.insert(0, sys.argv[2])
import jax
from run import _mega_lane, setup_compile_cache
assert len(jax.devices()) == n_dev, (n_dev, jax.devices())
setup_compile_cache()
lane = _mega_lane(json.loads(os.environ["MEGA_GRIDS_JSON"]), n_dev)
print("MEGA_JSON:" + json.dumps(lane))
"""


def _mega_lane(grids: dict, n_dev: int) -> dict:
    """One mega-sweep lane on the first ``n_dev`` devices, in-process.

    ONE banked call: every Ed-Gaze + Rhythmic variant rides one fused
    step+merge executable (PlanBank + on-device grid decode).
    """
    import jax
    from repro.core.batch import evaluate_batch, make_points
    from repro.core.shard_sweep import stream_cache_info
    from repro.core.sweep import lower_variant
    from repro.explore import DesignSpace, explore
    from repro.launch.mesh import make_batch_mesh
    before = stream_cache_info()["step_compiles"]
    s = explore(DesignSpace(["edgaze", "rhythmic"], grids),
                engine="fused", chunk_size=1 << 18, k=3,
                mesh=make_batch_mesh(n_dev))
    best = {}
    for r in s.topk:                       # full rows, global top-k order
        best.setdefault(r["algorithm"], r)
    for algo, rec in s.best_by_algorithm().items():
        # an algorithm may miss the global top-k entirely
        sm = rec["summary"]
        if algo in best or sm["argmin_point"] is None:
            continue
        # re-score the argmin point through the per-plan evaluator so the
        # fallback row carries the same full output schema as top-k rows
        plan = lower_variant(algo, rec["variant"])
        out = evaluate_batch(plan, make_points(
            plan, 1,
            **{ax: [val] for ax, val in sm["argmin_point"].items()}))
        best[algo] = dict(variant=rec["variant"], algorithm=algo,
                          index=sm["argmin_index"], **sm["argmin_point"],
                          **{key: float(val[0]) for key, val in out.items()})
    dev = jax.devices()[0]
    return {"n_devices": n_dev, "platform": dev.platform,
            "device_kind": dev.device_kind, "n_points": s.n_points,
            "n_feasible": s.n_feasible, "n_variants": s.n_variants,
            "eval_s": s.eval_s, "compile_s": s.compile_s,
            "points_per_sec": s.points_per_sec,
            "step_compiles": stream_cache_info()["step_compiles"] - before,
            "engine": s.engine, "dispatches": s.dispatches,
            "superchunk": s.superchunk,
            "occupancy": round(s.occupancy, 6), "backend": s.backend,
            "kernel_mode": s.stream_result.kernel_mode,
            "topk": list(best.values())}


#: tcmalloc locations probed by the tuned host-CPU lane (Debian/Ubuntu
#: multiarch + generic prefixes); first hit wins, none -> graceful skip
_TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/aarch64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/libtcmalloc.so.4",
    "/usr/local/lib/libtcmalloc.so.4",
)


def _find_tcmalloc() -> str:
    for path in _TCMALLOC_PATHS:
        if os.path.exists(path):
            return path
    return ""


def _tuned_host_env(env: dict) -> bool:
    """Apply the tuned host-CPU recipe to a child-process environment.

    The HomebrewNLP CPU recipe (SNIPPETS.md): preload tcmalloc so XLA's
    allocator churn stops serializing on glibc malloc's arena locks,
    silence the large-alloc reports it would spam at sweep-sized
    buffers, pin the default dtype to 32-bit so forced-device lanes
    measure parallelism rather than f64 bandwidth, and mute TF logging.
    Returns True when the full recipe (incl. tcmalloc) applied; without
    libtcmalloc on the host the dtype/logging knobs still apply but the
    lane reports untuned so history rows stay comparable.
    """
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "4")
    env.setdefault("JAX_DEFAULT_DTYPE_BITS", "32")
    tcmalloc = _find_tcmalloc()
    if not tcmalloc:
        return False
    env["LD_PRELOAD"] = " ".join(
        p for p in (tcmalloc, env.get("LD_PRELOAD", "")) if p)
    env.setdefault("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD", "60000000000")
    return True


def mega_sweep(emit_json: bool = True) -> List[str]:
    """Streaming mega-sweep: >=1e7 Ed-Gaze + Rhythmic points, sharded.

    On a TPU every lane runs in this process, because a chip belongs to
    one process at a time: one lane on 1 chip and, where more are
    visible, one on all of them.  Elsewhere the grid runs in two CPU
    subprocesses, once on 1 device and once on 8 forced host devices
    (the device-count XLA flag must precede jax init); those host lanes
    are skipped on a TPU, with the reason printed.  Records warm
    points/sec, the device-scaling ratio, the one-executable compile
    split (``mega_step_compiles`` must stay 1) and the persistent
    compilation-cache traffic.  Scale down with MEGA_SWEEP_GRIDS_JSON
    for smoke runs.

    Every history row is backend-tagged (``backend`` / ``kernel_mode``
    from the lanes' resolved sweep backend — ``REPRO_SWEEP_BACKEND``
    propagates to the host lanes) and names its platform and device
    kind.  When the resolved host lane is XLA an extra 1-device
    Pallas-lane child runs for the cross-backend speedup column
    (``mega_xla_speedup_1dev``).  ``BENCH_TUNED_HOST=1`` applies the
    tuned host-CPU recipe (tcmalloc LD_PRELOAD + pinned 32-bit dtype;
    see ``_tuned_host_env``) to the host lanes, recorded as
    ``tuned_host``.
    """
    import subprocess
    import sys
    import jax
    from repro.kernels import on_tpu
    grids = json.loads(os.environ.get("MEGA_SWEEP_GRIDS_JSON",
                                      json.dumps(MEGA_GRIDS)))
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "..", "src")
    env = dict(
        os.environ,
        PYTHONPATH=os.pathsep.join([src, os.environ.get("PYTHONPATH", "")]),
        MEGA_GRIDS_JSON=json.dumps(grids))
    tuned = False
    if os.environ.get("BENCH_TUNED_HOST", "") not in ("", "0"):
        tuned = not on_tpu() and _tuned_host_env(env)
        if not tuned:
            print("mega_sweep: BENCH_TUNED_HOST set but the host lanes do "
                  "not run here (TPU) or no libtcmalloc was found; lanes "
                  "run untuned", flush=True)

    def _lane(n_dev, extra_env=None):
        lane_env = dict(env, **(extra_env or {}))
        proc = subprocess.run([sys.executable, "-c", _MEGA_CHILD,
                               str(n_dev), here], env=lane_env,
                              capture_output=True, text=True, timeout=3600)
        assert proc.returncode == 0, proc.stderr[-3000:]
        line = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("MEGA_JSON:")][-1]
        return json.loads(line[len("MEGA_JSON:"):])

    lanes = {}
    pallas_ref = None
    cache = {"dir": CACHE_DIR, "entries_before": _cache_entries()}
    if on_tpu():
        print("mega_sweep: the forced 8-host-device lanes are skipped: "
              "they pin JAX_PLATFORMS=cpu in child processes, and on a "
              "TPU the chip stays with this process", flush=True)
        n_all = len(jax.devices())
        for n_dev in sorted({1, n_all}):
            lanes[n_dev] = _mega_lane(grids, n_dev)
    else:
        for n_dev in (1, 8):
            lanes[n_dev] = _lane(n_dev)
        # cross-backend reference: when the resolved lane is XLA, time
        # the Pallas lane once (1 device) so the history quantifies the
        # compiled backend's win on THIS host/grid
        if lanes[1]["backend"] == "xla":
            pallas_ref = _lane(1, {"REPRO_SWEEP_BACKEND": "pallas"})
    cache["entries_after"] = _cache_entries()
    cache["new_entries"] = cache["entries_after"] - cache["entries_before"]
    # 0 new entries on a re-run == every XLA compile was a cache hit
    cache["hit"] = bool(cache["entries_before"]
                        and cache["new_entries"] == 0)
    top = max(lanes)
    lane = lanes[top]
    rec = {"backend": lane["backend"], "kernel_mode": lane["kernel_mode"],
           "platform": lane["platform"], "device_kind": lane["device_kind"],
           "tuned_host": tuned,
           "mega_n_points": lane["n_points"],
           "mega_n_feasible": lane["n_feasible"],
           "mega_n_variants": lane["n_variants"]}
    for n_dev, ln in lanes.items():
        rec[f"mega_points_per_sec_{n_dev}dev"] = round(ln["points_per_sec"])
        rec[f"mega_eval_s_{n_dev}dev"] = round(ln["eval_s"], 2)
        rec[f"mega_compile_s_{n_dev}dev"] = round(ln["compile_s"], 2)
        rec[f"mega_dispatches_{n_dev}dev"] = ln["dispatches"]
    rec.update({"mega_step_compiles": lane["step_compiles"],
                "mega_engine": lane["engine"],
                f"mega_superchunk_{top}dev": lane["superchunk"],
                f"mega_occupancy_{top}dev": lane["occupancy"]})
    scaling = None
    if top > 1:
        scaling = lane["points_per_sec"] / lanes[1]["points_per_sec"]
        rec[f"mega_device_scaling_{top}v1"] = round(scaling, 2)
    rec.update({"mega_compile_cache": cache, "mega_best": lane["topk"]})
    if pallas_ref is not None:
        xla_speedup = (lanes[1]["points_per_sec"]
                       / pallas_ref["points_per_sec"])
        rec["mega_pallas_points_per_sec_1dev"] = round(
            pallas_ref["points_per_sec"])
        rec["mega_pallas_kernel_mode"] = pallas_ref["kernel_mode"]
        rec["mega_xla_speedup_1dev"] = round(xla_speedup, 2)
    if emit_json:
        _update_bench_json(rec)
        _append_history("mega_sweep",
                        {k: v for k, v in rec.items()
                         if k not in ("mega_best", "mega_compile_cache")},
                        devices=sorted(lanes))
    xla_col = (f" xla_speedup={rec['mega_xla_speedup_1dev']:.2f}x"
               if pallas_ref is not None else "")
    scaling_col = f" scaling={scaling:.2f}x" if scaling is not None else ""
    return [f"mega_sweep,{lane['eval_s']*1e6:.0f},points={lane['n_points']}"
            f" device={lane['platform']}:{lane['device_kind']}"
            f" backend={rec['backend']}"
            f" mode={rec['kernel_mode']}"
            f" tuned_host={tuned}"
            + "".join(f" pps_{n}dev={ln['points_per_sec']:,.0f}"
                      for n, ln in lanes.items())
            + f"{scaling_col}{xla_col}"
            f" compile_{top}dev={lane['compile_s']:.2f}s"
            f" executables={lane['step_compiles']}"
            f" dispatches={lane['dispatches']}"
            f" occupancy={lane['occupancy']:.3f}"
            f" cache_hit={cache['hit']}"]


# grid for the campaign_sweep bench: big enough for ~6 shards but small
# enough that the fault-tolerance drill (straight + campaign + kill +
# resume = ~2.5 sweeps) stays a minutes-not-hours lane; scale with
# CAMPAIGN_SWEEP_GRIDS_JSON
_CAMPAIGN_GRIDS = {
    "cis_node": [130., 90., 65., 45., 28.],
    "frame_rate": [15., 30., 60., 90., 120., 240.],
    "sys_rows": [4., 8., 16., 32., 64., 128.],
    "sys_cols": [8., 16., 32., 64.],
    "active_fraction_scale": [0.1, 0.25, 0.5, 1.0],
    "pixel_pitch_um": [2., 3., 4., 5., 6.],
}


def campaign_sweep(emit_json: bool = True) -> List[str]:
    """Fault-tolerant campaign overhead + kill/resume drill.

    Runs the same fused sweep three ways — straight ``explore()``, a
    checkpointed campaign, and a campaign killed mid-run (injected
    transient fault + simulated SIGKILL) then resumed — asserting
    bit-identical top-k across all three and recording the campaign's
    manifest/checkpoint overhead into BENCH_history.jsonl.  The campaign
    directory (manifest + shard checkpoints + report) is left under
    ``benchmarks/results/campaign_demo`` for CI artifact upload.
    """
    import shutil
    from repro.campaign import (CampaignOptions, FaultSchedule,
                                KillCampaign, TransientFault, resume,
                                run_campaign)
    from repro.core.shard_sweep import stream_cache_clear, stream_cache_info
    from repro.explore import DesignSpace, explore

    grids = json.loads(os.environ.get("CAMPAIGN_SWEEP_GRIDS_JSON",
                                      json.dumps(_CAMPAIGN_GRIDS)))
    space = DesignSpace(["edgaze"], grids)
    chunk = int(os.environ.get("CAMPAIGN_SWEEP_CHUNK", 1 << 12))
    # default shard = 4 chunks (the runner's own default ratio): big
    # enough that per-shard fixed cost is measured against real compute,
    # small enough the lane still plans several shards for the drill
    shard_points = int(os.environ.get("CAMPAIGN_SWEEP_SHARD_POINTS",
                                      1 << 14))
    # env-shrunk smoke lanes (CI fast job: 64-point shards) are fixed-
    # cost-dominated by construction — only the default lane's overhead
    # ratio is a meaningful guard
    default_lane = ("CAMPAIGN_SWEEP_GRIDS_JSON" not in os.environ
                    and "CAMPAIGN_SWEEP_CHUNK" not in os.environ
                    and "CAMPAIGN_SWEEP_SHARD_POINTS" not in os.environ)
    camp_dir = os.path.join(RESULTS, "campaign_demo")
    shutil.rmtree(camp_dir, ignore_errors=True)

    # superchunk pinned to the campaign runner's fixed scan length so
    # straight, campaign, drill and resume all ride ONE step executable
    # (asserted below) and the overhead comparison is warm-vs-warm
    stream_cache_clear()
    explore(space, engine="fused", chunk_size=chunk, k=8,
            superchunk=16)                                  # warm compile
    t0 = time.perf_counter()
    straight = explore(space, engine="fused", chunk_size=chunk, k=8,
                       superchunk=16)
    straight_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    camp = run_campaign(space, camp_dir, k=8, engine="fused",
                        chunk_size=chunk,
                        options=CampaignOptions(shard_points=shard_points))
    campaign_s = time.perf_counter() - t0
    n_shards = camp.campaign["n_planned"]

    # kill/resume drill: one transient fault (retried), then SIGKILL
    # after half the shards; resume must re-dispatch ONLY the rest
    drill_dir = os.path.join(RESULTS, "campaign_drill")
    shutil.rmtree(drill_dir, ignore_errors=True)
    faults = FaultSchedule({(0, 1): TransientFault("injected flake")},
                           kill_after=max(1, n_shards // 2))
    killed = False
    try:
        run_campaign(space, drill_dir, k=8, engine="fused",
                     chunk_size=chunk,
                     options=CampaignOptions(shard_points=shard_points,
                                             faults=faults,
                                             sleep=lambda s: None))
    except KillCampaign:
        killed = True
    t0 = time.perf_counter()
    resumed = resume(drill_dir)
    resume_s = time.perf_counter() - t0
    shutil.rmtree(drill_dir, ignore_errors=True)

    def _key(res):
        return [(round(r["total_j"], 15), r["variant"], r["index"])
                for r in res.topk]
    parity = (_key(straight) == _key(camp) == _key(resumed)
              and not camp.campaign["partial"]
              and not resumed.campaign["partial"])
    assert parity, "campaign/resume top-k diverged from straight explore"
    assert killed, "kill drill never fired"
    assert stream_cache_info()["step_compiles"] == 1, \
        "campaign lanes must share one step executable"
    overhead = campaign_s / straight_s - 1.0 if straight_s else 0.0
    # fixed-overhead budget: with the per-shard prep hoisted, the warm
    # executable shared, dead superchunk slots cond-skipped and shard
    # checkpoints single-encoded, manifest+checkpoint bookkeeping must
    # not triple the sweep (the pre-hoist demo lane sat at ~4.2x)
    if default_lane:
        assert overhead < 2.0, (
            f"campaign overhead {overhead:.2f}x exceeds the 2.0 bound")
    rec = {"backend": straight.backend,
           "kernel_mode": straight.stream_result.kernel_mode,
           "campaign_n_points": camp.n_points,
           "campaign_n_shards": n_shards,
           "campaign_straight_s": round(straight_s, 4),
           "campaign_wall_s": round(campaign_s, 4),
           "campaign_overhead_frac": round(overhead, 4),
           "campaign_points_per_sec": round(camp.n_points
                                            / max(campaign_s, 1e-12)),
           "campaign_resume_executed": resumed.campaign["n_executed"],
           "campaign_resume_loaded": resumed.campaign["n_loaded"],
           "campaign_resume_s": round(resume_s, 4),
           "campaign_step_compiles": stream_cache_info()["step_compiles"],
           "campaign_parity": parity,
           # parallel-executor accounting (workers=1 here: the serial
           # lane, but the columns keep history rows comparable across
           # worker counts and record how much checkpoint I/O the
           # background writer hid behind dispatch)
           "workers": camp.campaign["workers"],
           "io_overlap_frac": camp.campaign["io_overlap_frac"],
           "dispatch_wait_s": camp.campaign["dispatch_wait_s"]}
    if emit_json:
        _update_bench_json(rec)
        import jax
        _append_history("campaign_sweep", rec,
                        devices=jax.local_device_count())
    return [f"campaign_sweep,{campaign_s*1e6:.0f},"
            f"points={camp.n_points} shards={n_shards}"
            f" backend={rec['backend']}"
            f" overhead={overhead:+.1%}"
            f" resume_loaded={rec['campaign_resume_loaded']}"
            f" resume_executed={rec['campaign_resume_executed']}"
            f" executables={rec['campaign_step_compiles']}"
            f" workers={rec['workers']}"
            f" io_overlap={rec['io_overlap_frac']:.2f}"
            f" parity={parity}"]


# grid for the campaign_parallel bench: ~14.7M points over many small
# shards, so steady-state shard execution dominates the parent's
# scheduling/checkpoint machinery while the lane still finishes in a
# couple of minutes; shrink with CAMPAIGN_PARALLEL_GRIDS_JSON
_PARALLEL_GRIDS = {
    "cis_node": [180., 130., 90., 65., 45., 28.],
    "frame_rate": [float(v) for v in range(10, 250, 10)],
    "sys_rows": [float(v) for v in range(8, 136, 8)],
    "sys_cols": [float(v) for v in range(8, 136, 8)],
    "active_fraction_scale": [i / 16.0 for i in range(1, 9)],
    "pixel_pitch_um": [1.0 + 0.5 * i for i in range(10)],
}


def campaign_parallel(emit_json: bool = True) -> List[str]:
    """Multi-worker campaign executor: workers=2 vs workers=1.

    Runs the same sharded campaign serial and with two persistent worker
    processes, asserting bit-identical top-k, ONE step executable per
    worker, and — on the default lane on multi-core hosts — a
    steady-state speedup floor.  Steady-state excludes the pool spin-up
    (``worker_startup_s``: fresh interpreter + JAX runtime + compile per
    worker), a per-campaign constant that amortizes over real campaign
    lengths but dominates a minutes-long CI lane.  The workers=2
    campaign directory is left under ``benchmarks/results/
    campaign_parallel`` for CI artifact upload.
    """
    import shutil
    from repro.campaign import CampaignOptions, run_campaign
    from repro.core.shard_sweep import stream_cache_clear
    from repro.explore import DesignSpace, explore
    from repro.kernels import on_tpu

    if on_tpu():
        reason = ("workers=2 spawns a process per worker, and a TPU "
                  "belongs to one process")
        print(f"campaign_parallel: skipped: {reason}", flush=True)
        return [f"campaign_parallel,0,skipped ({reason})"]
    grids = json.loads(os.environ.get("CAMPAIGN_PARALLEL_GRIDS_JSON",
                                      json.dumps(_PARALLEL_GRIDS)))
    space = DesignSpace(["edgaze"], grids)
    chunk = int(os.environ.get("CAMPAIGN_PARALLEL_CHUNK", 1 << 12))
    shard_points = int(os.environ.get("CAMPAIGN_PARALLEL_SHARD_POINTS",
                                      1 << 19))
    default_lane = ("CAMPAIGN_PARALLEL_GRIDS_JSON" not in os.environ
                    and "CAMPAIGN_PARALLEL_CHUNK" not in os.environ
                    and "CAMPAIGN_PARALLEL_SHARD_POINTS" not in os.environ)
    serial_dir = os.path.join(RESULTS, "campaign_parallel_serial")
    par_dir = os.path.join(RESULTS, "campaign_parallel")
    shutil.rmtree(serial_dir, ignore_errors=True)
    shutil.rmtree(par_dir, ignore_errors=True)

    stream_cache_clear()
    explore(space, engine="fused", chunk_size=chunk, k=8,
            superchunk=16)                                  # warm compile
    t0 = time.perf_counter()
    serial = run_campaign(
        space, serial_dir, k=8, engine="fused", chunk_size=chunk,
        workers=1, options=CampaignOptions(shard_points=shard_points))
    serial_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    par = run_campaign(
        space, par_dir, k=8, engine="fused", chunk_size=chunk,
        workers=2, options=CampaignOptions(shard_points=shard_points))
    parallel_s = time.perf_counter() - t0
    shutil.rmtree(serial_dir, ignore_errors=True)  # parallel dir stays

    def _key(res):
        return [(round(r["total_j"], 15), r["variant"], r["index"])
                for r in res.topk]
    parity = (_key(serial) == _key(par)
              and not serial.campaign["partial"]
              and not par.campaign["partial"])
    assert parity, "workers=2 campaign top-k diverged from workers=1"
    compiles = par.campaign["worker_step_compiles"]
    assert compiles and set(compiles) == {1}, (
        f"every worker must ride ONE step executable, got {compiles}")
    startup_s = par.campaign["worker_startup_s"]
    speedup_wall = serial_s / max(parallel_s, 1e-9)
    speedup_steady = serial_s / max(parallel_s - startup_s, 1e-9)
    min_speedup = float(os.environ.get("CAMPAIGN_PARALLEL_MIN_SPEEDUP",
                                       "1.5"))
    if default_lane and (os.cpu_count() or 1) >= 2:
        assert speedup_steady >= min_speedup, (
            f"workers=2 steady-state speedup {speedup_steady:.2f}x "
            f"(wall {speedup_wall:.2f}x, startup {startup_s:.1f}s) is "
            f"under the {min_speedup}x floor")
    rec = {"backend": serial.backend,
           "kernel_mode": serial.stream_result.kernel_mode,
           "workers": par.campaign["workers"],
           "io_overlap_frac": par.campaign["io_overlap_frac"],
           "dispatch_wait_s": par.campaign["dispatch_wait_s"],
           "parallel_n_points": par.n_points,
           "parallel_n_shards": par.campaign["n_planned"],
           "parallel_serial_s": round(serial_s, 4),
           "parallel_wall_s": round(parallel_s, 4),
           "parallel_worker_startup_s": round(startup_s, 4),
           "parallel_speedup_wall": round(speedup_wall, 4),
           "parallel_speedup_steady": round(speedup_steady, 4),
           "parallel_points_per_sec": round(par.n_points
                                            / max(parallel_s, 1e-12)),
           "parallel_parity": parity}
    if emit_json:
        _update_bench_json(rec)
        import jax
        _append_history("campaign_parallel", rec,
                        devices=jax.local_device_count())
    return [f"campaign_parallel,{parallel_s*1e6:.0f},"
            f"points={par.n_points} shards={rec['parallel_n_shards']}"
            f" workers={rec['workers']}"
            f" speedup={speedup_wall:.2f}x steady={speedup_steady:.2f}x"
            f" startup={startup_s:.1f}s"
            f" io_overlap={rec['io_overlap_frac']:.2f}"
            f" executables={compiles}"
            f" parity={parity}"]


# grids for the serve bench: each client sweeps a distinct-but-shape-
# compatible space (different vdd_scale values, same axis lengths), so
# the concurrent wave coalesces into shared dispatch groups on ONE step
# executable; the second, identical wave must be served entirely from
# the result cache.  Shrink with SERVE_BENCH_GRIDS_JSON for smoke runs.
_SERVE_GRIDS = {
    "cis_node": [180., 130., 90., 65., 45., 28.],
    "frame_rate": [float(v) for v in range(10, 250, 10)],
    "sys_rows": [float(v) for v in range(8, 136, 8)],
    "pixel_pitch_um": [1.0 + 0.5 * i for i in range(10)],
}


def serve_bench(emit_json: bool = True) -> List[str]:
    """Exploration service: concurrent tenants vs sequential solo calls.

    Baseline: N sequential solo ``explore()`` calls over N distinct
    same-shape spaces.  Serve side: the same N requests submitted
    concurrently (wave 1 — coalesced dispatch), then repeated (wave 2 —
    result-cache replay).  Asserts the one-executable invariant across
    solo + serve, rel-1e-6 top-k parity per tenant, a fully-cached
    second wave with zero new dispatches, and — on the default lane —
    an aggregate requests/s floor over the sequential baseline
    (``SERVE_BENCH_MIN_SPEEDUP``, default 1.2: the window latency and
    scheduler overhead must cost less than the cache wins back).
    """
    import threading
    from repro.core.shard_sweep import (stream_cache_clear,
                                        stream_cache_info)
    from repro.explore import DesignSpace, explore
    from repro.serve import ExploreService

    clients = int(os.environ.get("SERVE_BENCH_CLIENTS", "8"))
    grids = json.loads(os.environ.get("SERVE_BENCH_GRIDS_JSON",
                                      json.dumps(_SERVE_GRIDS)))
    chunk = int(os.environ.get("SERVE_BENCH_CHUNK", 1 << 12))
    default_lane = ("SERVE_BENCH_GRIDS_JSON" not in os.environ
                    and "SERVE_BENCH_CHUNK" not in os.environ)

    def mkspace(i):
        return DesignSpace(["edgaze"],
                           dict(grids,
                                vdd_scale=[0.80 + 0.002 * i, 1.0]))

    spaces = [mkspace(i) for i in range(clients)]
    stream_cache_clear()
    explore(spaces[0], k=8, engine="fused",
            chunk_size=chunk)                           # warm compile
    t0 = time.perf_counter()
    solos = [explore(s, k=8, engine="fused", chunk_size=chunk)
             for s in spaces]
    solo_s = time.perf_counter() - t0
    assert stream_cache_info()["step_compiles"] == 1

    svc = ExploreService(coalesce_window_s=0.05)

    def wave():
        out = {}

        def client(i):
            out[i] = svc.explore(spaces[i], k=8, engine="fused",
                                 chunk_size=chunk)
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return out, time.perf_counter() - t0

    wave1, wave1_s = wave()
    wave2, wave2_s = wave()
    metrics = svc.metrics()
    svc.close()

    assert stream_cache_info()["step_compiles"] == 1, (
        "serving must ride the ONE solo-warmed step executable")

    def _key(res):
        return [(round(r["total_j"], 12), r["variant"], r["index"])
                for r in res.topk]
    parity = all(_key(wave1[i]) == _key(solos[i])
                 and _key(wave2[i]) == _key(solos[i])
                 for i in range(clients))
    assert parity, "served top-k diverged from solo explore()"
    assert all(r.serve["cache_hit"] and r.serve["dispatches"] == 0
               for r in wave2.values()), (
        "wave 2 must be served entirely from the result cache")

    hit_rate = metrics["cache"]["hits"] / max(metrics["submitted"], 1)
    serve_s = wave1_s + wave2_s
    serve_rps = 2 * clients / max(serve_s, 1e-9)
    solo_rps = clients / max(solo_s, 1e-9)
    speedup = serve_rps / solo_rps
    min_speedup = float(os.environ.get("SERVE_BENCH_MIN_SPEEDUP", "1.2"))
    if default_lane:
        assert speedup >= min_speedup, (
            f"aggregate serve throughput {serve_rps:.2f} req/s is only "
            f"{speedup:.2f}x the sequential baseline {solo_rps:.2f} "
            f"req/s (floor {min_speedup}x)")

    rec = {"backend": solos[0].backend,
           "kernel_mode": solos[0].stream_result.kernel_mode,
           "clients": clients,
           "coalesced_groups": metrics["coalesced_groups"],
           "cache_hit_rate": round(hit_rate, 4),
           "serve_n_points": spaces[0].n_points,
           "serve_max_group": metrics["max_group"],
           "serve_solo_s": round(solo_s, 4),
           "serve_wall_s": round(serve_s, 4),
           "serve_requests_per_sec": round(serve_rps, 4),
           "solo_requests_per_sec": round(solo_rps, 4),
           "serve_speedup": round(speedup, 4),
           "serve_step_compiles":
               stream_cache_info()["step_compiles"],
           "serve_parity": parity}
    if emit_json:
        _update_bench_json(rec)
        import jax
        _append_history("serve_bench", rec,
                        devices=jax.local_device_count())
    return [f"serve_bench,{serve_s*1e6:.0f},"
            f"clients={clients} points={rec['serve_n_points']}"
            f" speedup={speedup:.2f}x"
            f" rps={serve_rps:.2f} solo_rps={solo_rps:.2f}"
            f" groups={rec['coalesced_groups']}"
            f" max_group={rec['serve_max_group']}"
            f" hit_rate={hit_rate:.2f}"
            f" executables={rec['serve_step_compiles']}"
            f" parity={parity}"]


def roofline_table() -> List[str]:
    """§Roofline summary from the dry-run results (if present)."""
    path = os.path.join(RESULTS, "dryrun.json")
    if not os.path.exists(path):
        return ["roofline_table,0,missing (run python -m repro.launch.dryrun)"]
    with open(path) as f:
        results = json.load(f)
    out = []
    for key, rec in sorted(results.items()):
        if rec.get("status") != "ok" or "roofline" not in rec:
            continue
        r = rec["roofline"]
        out.append(
            f"roofline_{rec['arch']}_{rec['shape']},0,"
            f"dom={r['dominant']} frac={r['roofline_fraction']:.4f}"
            f" tc={r['t_compute_s']:.3e} tm={r['t_memory_s']:.3e}"
            f" tcoll={r['t_collective_s']:.3e}"
            f" useful={r['useful_compute_ratio']:.2f}")
    return out or ["roofline_table,0,no completed cells yet"]


BENCHES = [fig7_validation, fig9a_rhythmic, fig9b_edgaze, tbl3_power_density,
           fig12_stage_breakdown, kernel_microbench, design_sweep,
           mega_sweep, campaign_sweep, campaign_parallel, serve_bench,
           roofline_table]


_EPILOG = """\
environment knobs:
  REPRO_SWEEP_BACKEND    force the fused-sweep backend for the sweep
                         lanes: "xla" (pure-jnp megakernel, XLA-compiled
                         on any platform), "pallas" (pallas_call lane),
                         or "auto"/unset (Pallas on TPU, XLA elsewhere).
                         Propagates to the mega_sweep subprocess lanes.
  BENCH_TUNED_HOST=1     apply the tuned host-CPU recipe to the
                         mega_sweep lanes (HomebrewNLP CPU setup):
                           LD_PRELOAD=libtcmalloc.so.4   (arena-lock-free
                                                          allocator)
                           TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD=6e10
                           JAX_DEFAULT_DTYPE_BITS=32     (pin f32)
                           TF_CPP_MIN_LOG_LEVEL=4
                         Skips gracefully (tuned_host=false in history
                         rows) when libtcmalloc is not installed.  The
                         device-count flag the lanes already force is the
                         other half of the recipe:
                           XLA_FLAGS=--xla_force_host_platform_device_count=N
  MEGA_SWEEP_GRIDS_JSON / CAMPAIGN_SWEEP_GRIDS_JSON
                         shrink the sweep grids for smoke runs.
  REPRO_CAMPAIGN_WORKERS default worker-process count for campaign
                         runs (run_campaign(workers=)/explore(workers=)
                         and CampaignOptions.workers win over the env).
  CAMPAIGN_PARALLEL_GRIDS_JSON / CAMPAIGN_PARALLEL_CHUNK /
  CAMPAIGN_PARALLEL_SHARD_POINTS
                         shrink the campaign_parallel lane for smoke
                         runs; any of them set marks the lane
                         non-default, which skips the speedup assert.
  CAMPAIGN_PARALLEL_MIN_SPEEDUP
                         steady-state workers=2 speedup floor (default
                         1.5), asserted only on the default lane on
                         hosts with >= 2 cores.
  SERVE_BENCH_CLIENTS    concurrent tenants in the serve_bench lane
                         (default 8; the CI serve job raises it for the
                         load test).
  SERVE_BENCH_GRIDS_JSON / SERVE_BENCH_CHUNK
                         shrink the serve_bench per-client space for
                         smoke runs; either set marks the lane
                         non-default, which skips the speedup assert.
  SERVE_BENCH_MIN_SPEEDUP
                         aggregate served-requests/s floor over the
                         sequential solo baseline (default 1.2),
                         asserted only on the default lane.
  JAX_COMPILATION_CACHE_DIR
                         persistent XLA compile cache location (default
                         benchmarks/.jax_cache).
"""


def main(argv: List[str] = None) -> None:
    """Run all benches, or only those named on the command line
    (``python benchmarks/run.py mega_sweep design_sweep``).

    A bench that raises prints an ``ERROR`` row and the others still
    run, but the process then exits 1."""
    import argparse
    parser = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        epilog=_EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    by_name = {b.__name__: b for b in BENCHES}
    parser.add_argument(
        "benches", nargs="*", metavar="BENCH",
        help=f"benches to run (default: all): {', '.join(sorted(by_name))}")
    names = parser.parse_args(argv).benches
    unknown = [n for n in names if n not in by_name]
    if unknown:
        parser.error(f"unknown benches {unknown}; valid: {sorted(by_name)}")
    setup_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for bench in ([by_name[n] for n in names] or BENCHES):
        try:
            for row in bench():
                print(row)
        except Exception as e:  # noqa: BLE001
            failed.append(bench.__name__)
            print(f"{bench.__name__},0,ERROR {type(e).__name__}: {e}")
    if failed:
        raise SystemExit(f"benches raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
