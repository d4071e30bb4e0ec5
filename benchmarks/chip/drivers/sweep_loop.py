"""``sweep_loop``: whole ``explore()`` sweeps of the configuration's
space, back to back, each with its axis values drawn anew from the seed.

Set-up warms the step executable and the winners' re-gather on the first
superchunk of the first space.  The window starts sweeps until
``--seconds`` have passed and ends when the last one returns.  The check
compares ``check.sweeps`` sweeps drawn from the seed whole against the
reference, and the winners of every other sweep against the scalar
model.
"""
from __future__ import annotations

import time

import jax

from check import answer_of
from spaces import draw_grids, rng_for, warm_range
from repro.explore import DesignSpace, explore


def space_of(ctx, purpose: str, i: int):
    grids = draw_grids(ctx.config, rng_for(ctx.seed, purpose, i))
    return grids, DesignSpace(list(ctx.config["algorithms"]), grids)


def sweep(ctx, space, **kw):
    cfg = ctx.config
    with jax.profiler.TraceAnnotation("explore"):
        return explore(space, k=int(cfg["k"]), metric=cfg["metric"],
                       engine="fused", mesh=ctx.mesh, **kw)


def setup(ctx):
    _grids, space = space_of(ctx, "sweep", 0)
    warm = sweep(ctx, space, index_range=warm_range(
        space.n_points // space.n_variants, space.n_variants, ctx.chips))
    ctx.log(f"sweep_loop: warm-up compile_s={warm.compile_s} "
            f"backend={warm.backend}/{warm.stream_result.kernel_mode}")
    return {"step_compile_s": warm.compile_s}


def window(ctx, state):
    t0 = time.perf_counter()
    sweeps = []
    i = 1
    while time.perf_counter() - t0 < ctx.seconds:
        grids, space = space_of(ctx, "sweep", i)
        res = sweep(ctx, space)
        t_end = time.perf_counter() - t0
        sweeps.append(dict(
            grids=grids, answer=answer_of(res), n_points=res.n_points,
            wall_s=res.wall_s, compile_s=res.compile_s, eval_s=res.eval_s,
            dispatches=res.dispatches, t_end=t_end))
        ctx.log(f"sweep {i}: {res.n_points} points wall_s={res.wall_s} "
                f"compile_s={res.compile_s} eval_s={res.eval_s} "
                f"dispatches={res.dispatches}")
        i += 1
    return dict(attempted=len(sweeps), failed=0, sweeps=sweeps,
                points=sum(s["n_points"] for s in sweeps),
                span_s=sweeps[-1]["t_end"], chips=ctx.chips,
                step_compile_s=state["step_compile_s"])


def release(state) -> None:
    state.clear()


def answers(ctx, rec):
    """``(whole, rows, extra)``: the sweeps compared whole, those whose
    winners alone are compared, and no counts of the driver's own."""
    sweeps = rec["sweeps"]
    n = min(int(ctx.traffic["check"]["sweeps"]), len(sweeps))
    whole = set(rng_for(ctx.seed, "check").choice(len(sweeps), n,
                                                   replace=False).tolist())
    pairs = [(s["answer"], s["grids"]) for s in sweeps]
    return ([p for j, p in enumerate(pairs) if j in whole],
            [p for j, p in enumerate(pairs) if j not in whole], {})
