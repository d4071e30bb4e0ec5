"""Sweep the offered rate of an open-loop serve cell to find its knee.

    python benchmarks/chip/knee.py --workload serve.zipf-refine \
        --seed 5 --seconds 40 --rates 4,5,6,7,8 --seeds 11,12,13

One process: the cell's driver sets up once (service, bases, warm
cache) from ``--seed``, then sends one window of the cell's mix at each
rate in ascending order, once for each traffic seed of ``--seeds``,
and stops after the first rate at which the backlog grew.
Per window it prints the latency percentiles and whether the backlog
grew: the median latency of the misses (requests the cache does not
answer) due in the window's last third exceeds that of its first third
by more than half and by more than 0.1 s.  The rule is monotone: the
knee is the highest rate below the first rate at which any seed's
backlog grew.  The cell's ``rate_per_s`` is set at about four fifths of
it, by hand, once.  Refuses to run off a TPU, as the benchmark does.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench  # noqa: E402


def backlog_grew(rec) -> bool:
    """The misses due late in the window wait longer than the early ones
    (see the module docstring)."""
    miss = [lat for lat, r in zip(rec["latency_s"], rec["requests"])
            if not r["repeat"]]
    third = max(len(miss) // 3, 1)
    early, late = np.median(miss[:third]), np.median(miss[-third:])
    return bool(late > 1.5 * early and late > early + 0.1)


def knee_of(grew_at) -> float:
    """The highest rate below the first rate at which the backlog grew
    (``grew_at`` maps each rate to whether it grew on any seed); the
    highest rate swept when it grew at none, and 0 when at the first."""
    knee = 0.0
    for rate in sorted(grew_at):
        if grew_at[rate]:
            break
        knee = rate
    return knee


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="serve.zipf-refine")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    bench.setup_jax(int(cell["chips"]))
    from repro.launch.mesh import make_batch_mesh
    driver = bench.load_module("drivers", cell["traffic_data"]["driver"])
    ctx = bench.Context(workload=args.workload, seed=args.seed,
                        seconds=args.seconds, trace=False,
                        chips=int(cell["chips"]), cell=cell,
                        config=cell["config_data"],
                        traffic=cell["traffic_data"],
                        mesh=make_batch_mesh(int(cell["chips"])),
                        work_dir=bench.WORK_DIR)
    state = driver.setup(ctx)
    grew_at = {}
    for rate in sorted(float(r) for r in args.rates.split(",")):
        for seed in (int(s) for s in args.seeds.split(",")):
            reqs = driver.schedule(dataclasses.replace(ctx, seed=seed),
                                   state["bases"], rate, args.seconds,
                                   tag=f"knee{rate}")
            rec = driver.run_schedule(ctx, state["svc"], reqs)
            grew = backlog_grew(rec)
            grew_at[rate] = grew_at.get(rate, False) or grew
            lat = np.asarray(rec["latency_s"])
            print(json.dumps({
                "rate_per_s": rate, "seed": seed, "requests": len(lat),
                "failed": rec["failed"],
                "p50_s": float(np.percentile(lat, 50)),
                "p90_s": float(np.percentile(lat, 90)),
                "first_p90_s": float(np.percentile(rec["first_s"], 90)),
                "backlog_grew": grew,
                "max_late_s": rec["max_late_s"]}), flush=True)
        if grew_at[rate]:
            break
    driver.release(state)
    print(json.dumps({"knee_per_s": knee_of(grew_at),
                      "grew_at": grew_at}), flush=True)


if __name__ == "__main__":
    main()
