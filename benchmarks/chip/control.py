"""Readings of the program and of its control, seed after seed, on the chip.

    python benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds 11,12,13,...

For each seed, in one process: the cell's driver sets up, runs a short
window of ``--seconds`` at the cell's own load, and hands over the
answers its check compares.  The program's readings are those answers
against the reference (``check.run_check``); the control's are the
reference computed in bfloat16, the precision below the configuration's
float32, put in the program's place for the same spaces.  ``ref_gap``,
which holds the vectorised reference to the scalar model, reads the
bfloat16 reference in the float32 one's place (side ``reference``).  The
largest program reading over the seeds is a limit's lower reading, the
smallest control or reference reading its upper one (``PERF.md`` gives
both with each limit).

Prints one JSON line per seed and side, and a summary; the benchmark's
own runs never run the control.  Refuses to run off a TPU, as the
benchmark does; ``--cpu`` lifts that for tests.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench  # noqa: E402


def readings_for_seed(cell: Dict, seed: int, seconds: float, *,
                      control_ref) -> Dict[str, Dict[str, float]]:
    """``{"program": readings, "control": readings}`` of one seed."""
    import check
    from repro.launch.mesh import make_batch_mesh
    cfg = cell["config_data"]
    driver = bench.load_module("drivers", cell["traffic_data"]["driver"])
    work_dir = os.path.join(bench.WORK_DIR, f"control-{cell['name']}")
    os.makedirs(work_dir, exist_ok=True)
    ctx = bench.Context(workload=cell["name"], seed=seed, seconds=seconds,
                        trace=False, chips=int(cell["chips"]), cell=cell,
                        config=cfg, traffic=cell["traffic_data"],
                        mesh=make_batch_mesh(int(cell["chips"])),
                        work_dir=work_dir)
    state = driver.setup(ctx)
    record = driver.window(ctx, state)
    driver.release(state)
    whole, rows, extra = driver.answers(ctx, record)
    limits = cell["limits"]
    return {"program": check.run_check(cfg, limits, whole, rows, extra,
                                       seed=seed),
            "control": check.run_check(cfg, limits, whole, [], extra,
                                       seed=seed, control=control_ref),
            "reference": {"ref_gap": check.reference_gap(
                control_ref, cfg, [g for _a, g in whole], seed)}}


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    cell["name"] = args.workload
    if not args.cpu:
        bench.setup_jax(int(cell["chips"]))
    import jax.numpy as jnp
    from camj_ref.vector import Reference
    control_ref = Reference(cell["config_data"]["algorithms"],
                            dtype=jnp.bfloat16)
    lower: Dict[str, float] = {}
    upper: Dict[str, float] = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        got = readings_for_seed(cell, seed, args.seconds,
                                control_ref=control_ref)
        for side, rd in got.items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "side": side, "readings": rd}), flush=True)
        for key, val in got["program"].items():
            lower[key] = max(lower.get(key, 0.0), val)
        for key, val in {**got["control"], **got["reference"]}.items():
            upper[key] = min(upper.get(key, float("inf")), val)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper}), flush=True)


if __name__ == "__main__":
    main()
