"""``campaign_kill``: the configuration's space as a durable campaign
that is killed and resumed, cycle after cycle.

Each cycle draws the space's values anew from the seed, starts
``explore(checkpoint_dir=...)`` in a fresh directory with a
``KillCampaign`` scheduled after a seeded number of completed shards
(``repro.campaign.faults``), and then ``resume()``s it to the end.  The
window starts cycles until ``--seconds`` have passed and ends when the
last resume returns.  The check compares ``check.cycles`` merged results
drawn from the seed whole against the reference and the winners of the
others against the scalar model, and counts every resume that did other
than run exactly the shards the kill left (``resume_bad``).
"""
from __future__ import annotations

import math
import os
import shutil
import time

import jax

from check import answer_of
from spaces import draw_grids, n_points, rng_for, warm_range
from repro.campaign import CampaignOptions, resume
from repro.campaign.faults import FaultSchedule, KillCampaign
from repro.explore import DesignSpace, explore

#: the scan length the campaign runner pins for every shard
SUPERCHUNK = 16


def setup(ctx):
    cfg = ctx.config
    grids = draw_grids(cfg, rng_for(ctx.seed, "campaign", 0))
    space = DesignSpace(list(cfg["algorithms"]), grids)
    with jax.profiler.TraceAnnotation("explore"):
        warm = explore(space, k=int(cfg["k"]), metric=cfg["metric"],
                       engine="fused", mesh=ctx.mesh,
                       superchunk=SUPERCHUNK, index_range=warm_range(
                           space.n_points // space.n_variants,
                           space.n_variants, ctx.chips))
    ctx.log(f"campaign_kill: warm-up compile_s={warm.compile_s}")
    return {"step_compile_s": warm.compile_s}


def window(ctx, state):
    cfg = ctx.config
    shard_points = int(cfg["campaign"]["shard_points"])
    t0 = time.perf_counter()
    cycles = []
    i = 1
    while time.perf_counter() - t0 < ctx.seconds:
        rng = rng_for(ctx.seed, "campaign", i)
        grids = draw_grids(cfg, rng)
        space = DesignSpace(list(cfg["algorithms"]), grids)
        n_shards = math.ceil(n_points(grids, space.n_variants)
                             / shard_points)
        kill_after = int(rng.integers(1, n_shards))
        directory = os.path.join(ctx.work_dir, f"cycle{i}")
        opts = CampaignOptions(shard_points=shard_points,
                               faults=FaultSchedule(kill_after=kill_after))
        killed = False
        with jax.profiler.TraceAnnotation("run_campaign"):
            try:
                explore(space, k=int(cfg["k"]), metric=cfg["metric"],
                        checkpoint_dir=directory, campaign=opts,
                        mesh=ctx.mesh, workers=1)
            except KillCampaign:
                killed = True
        t_resume = time.perf_counter()
        with jax.profiler.TraceAnnotation("resume"):
            res = resume(directory, mesh=ctx.mesh, workers=1)
        t_end = time.perf_counter()
        rep = res.campaign
        shutil.rmtree(directory, ignore_errors=True)
        cycles.append(dict(
            grids=grids, answer=answer_of(res), n_points=res.n_points,
            killed=killed, kill_after=kill_after,
            resume_s=t_end - t_resume, io_s=rep["io_s"],
            n_planned=rep["n_planned"], n_loaded=rep["n_loaded"],
            n_completed=rep["n_completed"], n_executed=rep["n_executed"],
            quarantined=len(rep["quarantined"]),
            missing=len(rep["missing"]), t_end=t_end - t0))
        ctx.log(f"cycle {i}: {res.n_points} points, killed after "
                f"{kill_after}/{rep['n_planned']} shards, resume ran "
                f"{rep['n_executed']} in {t_end - t_resume} s, "
                f"io_s={rep['io_s']}")
        i += 1
    return dict(attempted=len(cycles), failed=0, cycles=cycles,
                points=sum(c["n_points"] for c in cycles),
                span_s=cycles[-1]["t_end"], chips=ctx.chips,
                step_compile_s=state["step_compile_s"])


def release(state) -> None:
    state.clear()


def resume_faults(c) -> int:
    """Ways in which a cycle's kill and resume went other than planned."""
    return sum((not c["killed"],
                c["n_loaded"] != c["kill_after"],
                c["n_executed"] != c["n_planned"] - c["kill_after"],
                c["n_completed"] != c["n_planned"],
                c["quarantined"] != 0, c["missing"] != 0))


def answers(ctx, rec):
    """``(whole, rows, extra)``: the merged results compared whole, those
    whose winners alone are compared, and ``resume_bad``."""
    cycles = rec["cycles"]
    n = min(int(ctx.traffic["check"]["cycles"]), len(cycles))
    whole = set(rng_for(ctx.seed, "check").choice(len(cycles), n,
                                                  replace=False).tolist())
    pairs = [(c["answer"], c["grids"]) for c in cycles]
    return ([p for j, p in enumerate(pairs) if j in whole],
            [p for j, p in enumerate(pairs) if j not in whole],
            {"resume_bad": sum(resume_faults(c) for c in cycles)})
