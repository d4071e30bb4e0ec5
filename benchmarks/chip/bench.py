"""Chip benchmark of the CamJ sweep engine: one cell, one run.

    python benchmarks/chip/bench.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs in one process on the chip JAX finds, and refuses to run (exit 3,
no result) unless the first device is a TPU and the cell's chips are
there.  Everything about a cell is data found by name beside this file:

* ``cells/<cell>.json``: its configuration, traffic mix, chips and the
  limits of its correctness readings;
* ``configs/<config>.json``: the design space and its deployment;
* ``traffic/<traffic>.json``: the mix's parameters and the driver that
  generates it;
* ``drivers/<driver>.py``: ``setup``, ``window``, ``release`` and
  ``answers`` (what the check compares) of one kind of traffic;
* ``metrics/<metric>.py``: ``read(run)`` of one metric, ``None`` when
  the run holds nothing to read;
* the metrics a cell reports, from ``BENCHMARK.json`` at the checkout's
  root: its end-to-end metrics with ``--trace 0``, its per-layer
  metrics with ``--trace 1``.

Set-up (``setup_s``) runs from the start of the process to the end of
the driver's warm-up, which compiles every shape the window uses; the
window then measures for ``--seconds``.  XLA compiles and lowerings
inside the window are counted and printed.  After the window the device's peak memory is
read, the program's state freed, and the driver compares what the timed
path produced with the plain reference (``check.py``).  Each reading is
printed beside its limit on standard error, last; the last line of
standard output is the result object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "src"), HERE]

#: where JAX keeps compiled programs when JAX_COMPILATION_CACHE_DIR is
#: unset: a fixed path in the checkout, so that later runs hit
CACHE_DIR = os.path.join(HERE, ".jax_cache")
#: traces and campaign checkpoints of a run, removed when it ends
WORK_DIR = os.path.join(HERE, ".work")


def load_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` beside this file, imported by path."""
    path = os.path.normpath(os.path.join(HERE, kind, f"{name}.py"))
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} entry {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + f"{kind}_{name}".strip("._").replace(
            ".", "_").replace("-", "_").replace("/", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> Dict:
    """A cell with its configuration and traffic mix resolved."""
    cell = load_json(HERE, "cells", f"{name}.json")
    cell["config_data"] = load_json(HERE, "configs",
                                    f"{cell['config']}.json")
    cell["traffic_data"] = load_json(HERE, "traffic",
                                     f"{cell['traffic']}.json")
    return cell


def cell_metrics(bench: Dict, cell: str, trace: bool) -> List[Dict]:
    """The metric entries of ``BENCHMARK.json`` this cell reports."""
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


@dataclasses.dataclass
class Context:
    """What a driver is given."""
    workload: str
    seed: int
    seconds: float
    trace: bool
    chips: int
    cell: Dict
    config: Dict
    traffic: Dict
    mesh: object
    work_dir: str

    def log(self, msg: str) -> None:
        print(msg, flush=True)


class CompileCounter:
    """Counts XLA backend compiles, and lowerings to MLIR (a jit cache
    miss, also where the persistent cache then serves the compile),
    through JAX's monitoring events."""
    EVENT = "/jax/core/compile/backend_compile_duration"
    LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        self.n_lower = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.n += 1
            self.seconds += secs
        elif event == self.LOWER_EVENT:
            self.n_lower += 1


def setup_jax(chips: int):
    """Point the compile cache at its fixed directory and check the chip.

    Returns ``(jax, devices)``; exits 3 with no result off a TPU or with
    fewer chips than the cell asks for."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: JAX found no TPU (first device platform is "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        sys.exit(3)
    if len(devs) < chips:
        print(f"bench: the cell asks for {chips} chips, JAX found "
              f"{len(devs)}; nothing was run", file=sys.stderr)
        sys.exit(3)
    for var in ("REPRO_SWEEP_BACKEND", "REPRO_KERNEL_INTERPRET"):
        if os.environ.get(var, "").strip().lower() not in ("", "auto"):
            print(f"bench: {var}={os.environ[var]!r} would move the sweep "
                  f"off the compiled Pallas lane; unset it",
                  file=sys.stderr)
            sys.exit(3)
    return jax, devs


def run_cell(args, *, require_tpu: bool = True, cell: Optional[Dict] = None,
             bench: Optional[Dict] = None) -> Dict:
    """One run of one cell; returns the result object (and prints the
    log lines).  Tests drive the harness on the CPU with
    ``require_tpu=False`` and a resolved ``cell`` of their own."""
    cell = cell if cell is not None else load_cell(args.workload)
    config = cell["config_data"]
    bench = bench if bench is not None else load_json(ROOT, "BENCHMARK.json")
    chips = int(cell["chips"])
    if require_tpu:
        jax, devs = setup_jax(chips)
    else:
        import jax
        devs = jax.devices()
    from repro.launch.mesh import make_batch_mesh
    import check as check_mod
    driver = load_module("drivers", cell["traffic_data"]["driver"])
    specs = cell_metrics(bench, args.workload, bool(args.trace))
    readers = {m["name"]: load_module("metrics", m["name"]) for m in specs}

    work_dir = os.path.join(WORK_DIR, args.workload)
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir, exist_ok=True)
    counter = CompileCounter()
    mesh = make_batch_mesh(chips)
    ctx = Context(workload=args.workload, seed=int(args.seed),
                  seconds=float(args.seconds), trace=bool(args.trace),
                  chips=chips, cell=cell, config=config,
                  traffic=cell["traffic_data"], mesh=mesh,
                  work_dir=work_dir)
    try:
        state = driver.setup(ctx)
        setup_s = time.perf_counter() - T_START
        setup_compiles = (counter.n, counter.seconds, counter.n_lower)
        trace_dir = os.path.join(work_dir, "trace")
        if ctx.trace:
            jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            record = driver.window(ctx, state)
        window_s = time.perf_counter() - t0
        if ctx.trace:
            jax.profiler.stop_trace()
        n_compiles = counter.n - setup_compiles[0]
        n_lowerings = counter.n_lower - setup_compiles[2]
        used = list(mesh.devices.flat)
        peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
                 for d in used if d.memory_stats()]
        driver.release(state)
        del state
        gc.collect()
        reduced = None
        if ctx.trace:
            reduced = load_module(".", "trace").reduce_dir(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
        t_check = time.perf_counter()
        readings = check_mod.run_check(
            config, cell["limits"], *driver.answers(ctx, record),
            seed=ctx.seed)
        check_s = time.perf_counter() - t_check
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)

    limits = {k: float(v) for k, v in cell["limits"].items()}
    correct = check_mod.judge(readings, limits)
    run = dict(record=record, trace=reduced, setup_s=setup_s, chips=chips,
               device_kind=devs[0].device_kind, config=config)
    metrics = {}
    for spec in specs:
        value = readers[spec["name"]].read(run)
        if value is None:
            if not ctx.trace:
                raise RuntimeError(f"end-to-end metric {spec['name']} "
                                   f"read nothing")
            continue
        metrics[spec["name"]] = {"value": float(value),
                                 "unit": spec["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": chips,
              "memory_peak_bytes": int(max(peaks) if peaks else 0)}
    out = {"correct": bool(correct), "attempted": int(record["attempted"]),
           "failed": int(record["failed"]), "metrics": metrics,
           "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                            "idle_gaps": reduced["idle_gaps"][:10]}
    print(f"bench: {args.workload} seed={args.seed} setup_s={setup_s} "
          f"(compiles {setup_compiles[0]}, {setup_compiles[1]} s) "
          f"window_s={window_s} compiles_in_window={n_compiles} "
          f"lowerings_in_window={n_lowerings} "
          f"check_s={check_s}", flush=True)
    if reduced is not None:
        print(f"bench: trace busy_s={reduced['busy_s']} "
              f"window_s={reduced['window_s']} "
              f"kernel_s={reduced['kernel_s']} "
              f"collective_s={reduced['collective_s']} "
              f"n_devices={reduced['n_devices']}", flush=True)
    out["check"] = {k: {"value": _finite(readings.get(k)),
                        "limit": limits.get(k)}
                    for k in sorted(set(readings) | set(limits))}
    for key, item in out["check"].items():
        print(f"check: {key} {item['value']} limit {item['limit']}",
              file=sys.stderr, flush=True)
    print(f"check: correct {correct}", file=sys.stderr, flush=True)
    return out


def _finite(x):
    """A reading as a JSON number: a non-finite gap reads as 1e300."""
    if x is None:
        return None
    return float(x) if abs(float(x)) < 1e300 else 1e300


def parse_args(argv: Optional[List[str]] = None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv: Optional[List[str]] = None) -> None:
    out = run_cell(parse_args(argv))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
