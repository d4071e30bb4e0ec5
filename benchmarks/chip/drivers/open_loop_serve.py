"""``open_loop_serve``: exploration requests to an ``ExploreService``,
sent on a schedule whether or not earlier ones have finished.

The configuration names base design spaces, each in a quick and a deep
shape; set-up draws them from the seed, starts the service, and serves
every base once (``stream=True``), which compiles one step executable
per shape and fills the result cache.  The cell's traffic then fixes,
for ``--seconds`` at ``rate_per_s``, ``N = rate * seconds`` requests:

* ``deep_share`` of the requests ask for the deep shape, and
  ``repeat_share`` repeat a base exactly (the cache answers them); the
  rest refine their base: same lengths, values drawn inside narrower
  intervals (a miss, served by the step executable of its shape);
* arrival gaps are the ``N`` quantiles of the exponential distribution
  of mean ``1 / rate``: the gaps of a Poisson process of that rate;
* bases are ranked by Zipf(``zipf_s``) popularity.

The counts of each kind, the gaps and the counts of each base are fixed
by ``N`` and the shares; the seed draws their order, freely and each on
its own, so deep misses may arrive together as they do in Poisson
traffic, while every seed offers the same work.

Each request is timed from its due time to its final result and to its
first partial update.  A request that fails or is refused counts as
missing.  The check compares ``check.requests`` finished requests drawn
from the seed, with the longest deep miss among them, whole against the
reference; it counts requests that never finished (``lost``) and those
whose final partial update differs from their result (``final_bad``).
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List

import jax
import numpy as np

from check import answer_of
from spaces import draw_grids, refine, rng_for
from repro.explore import DesignSpace
from repro.serve import ExploreService

SHAPES = ("quick", "deep")
#: how long after the last due time requests are waited for
GRACE_S = 60.0


def _quantile_counts(p: np.ndarray, n: int) -> np.ndarray:
    """``n`` split by the shares ``p`` (largest remainders)."""
    raw = p / p.sum() * n
    counts = np.floor(raw).astype(int)
    for j in np.argsort(raw - counts)[::-1][: n - counts.sum()]:
        counts[j] += 1
    return counts


def schedule(ctx, bases: Dict, rate: float, seconds: float,
             tag: str = "window") -> List[Dict]:
    """The requests of one window (see the module docstring)."""
    traffic = ctx.traffic
    n = max(int(round(rate * seconds)), 1)
    rng = rng_for(ctx.seed, tag)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    due = np.cumsum(rng.permutation(gaps))
    p_deep = float(traffic["deep_share"])
    p_rep = float(traffic["repeat_share"])
    kinds = [(d, r) for d in (True, False) for r in (True, False)]
    shares = np.array([(p_deep if d else 1 - p_deep)
                       * (p_rep if r else 1 - p_rep) for d, r in kinds])
    kind_seq = [kinds[j] for j in rng.permutation(
        np.repeat(np.arange(len(kinds)), _quantile_counts(shares, n)))]
    n_base = int(ctx.config["base_spaces"])
    zipf = 1.0 / np.arange(1, n_base + 1) ** float(traffic["zipf_s"])
    ranks = rng.permutation(np.repeat(np.arange(n_base),
                                      _quantile_counts(zipf, n)))
    reqs = []
    for j in range(n):
        deep, repeat = kind_seq[j]
        shape = SHAPES[int(deep)]
        base = bases[(int(ranks[j]), shape)]
        grids = (base["grids"] if repeat else
                 refine(ctx.config, base["grids"],
                        rng_for(ctx.seed, tag + ".refine", j)))
        reqs.append(dict(due=float(due[j]), shape=shape,
                         base=int(ranks[j]), repeat=bool(repeat),
                         grids=grids,
                         space=DesignSpace(list(ctx.config["algorithms"]),
                                           grids)))
    return reqs


def _serve_one(svc, ctx, space, rec, t0, stream=True) -> None:
    """Submit one request and follow its updates (on its own thread
    once submitted); fills ``rec``."""
    cfg = ctx.config
    try:
        with jax.profiler.TraceAnnotation("submit"):
            handle = svc.submit(space, k=int(cfg["k"]),
                                metric=cfg["metric"], stream=stream)
    except Exception as exc:  # noqa: BLE001 - a refused request is missing
        rec["error"] = f"{type(exc).__name__}: {exc}"
        return
    rec["t_submit"] = time.perf_counter() - t0

    def follow():
        final = None
        try:
            for upd in handle.partials():
                now = time.perf_counter() - t0
                rec.setdefault("t_first", now)
                if upd.final:
                    final = upd
                    rec["t_done"] = now
            with jax.profiler.TraceAnnotation("result"):
                res = handle.result(timeout=0)
        except Exception as exc:  # noqa: BLE001 - counted as missing
            rec["error"] = f"{type(exc).__name__}: {exc}"
            rec.pop("t_done", None)
            return
        rec["answer"] = answer_of(res)
        rec["serve"] = dict(res.serve or {})
        rec["final_same"] = (final is not None and [
            (r["algorithm"], r["variant"], r["index"], r[cfg["metric"]])
            for r in final.topk] == [
            (r["algorithm"], r["variant"], r["index"], r[cfg["metric"]])
            for r in res.topk])

    th = threading.Thread(target=follow, daemon=True)
    th.start()
    rec["_thread"] = th


def run_schedule(ctx, svc, reqs: List[Dict]) -> Dict:
    """Send ``reqs`` open-loop; wait for every answer (or the grace)."""
    t0 = time.perf_counter()
    records = []
    for r in reqs:
        delay = t0 + r["due"] - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        rec = dict(due=r["due"], shape=r["shape"], base=r["base"],
                   repeat=r["repeat"], grids=r["grids"])
        records.append(rec)
        _serve_one(svc, ctx, r["space"], rec, t0)
    close = reqs[-1]["due"] + GRACE_S
    for rec in records:
        th = rec.pop("_thread", None)
        if th is not None:
            th.join(max(close - (time.perf_counter() - t0), 0.0))
            if th.is_alive():
                rec["error"] = "no result within the grace period"
    lat = [(rec["t_done"] - rec["due"]) if "t_done" in rec
           else close - rec["due"] for rec in records]
    first = [(rec["t_first"] - rec["due"]) if "t_first" in rec
             and "t_done" in rec else close - rec["due"]
             for rec in records]
    late = [rec["t_submit"] - rec["due"] for rec in records
            if "t_submit" in rec]
    failed = sum(1 for rec in records
                 if "error" in rec or "t_done" not in rec)
    return dict(attempted=len(records), failed=failed, requests=records,
                latency_s=lat, first_s=first,
                max_late_s=max(late) if late else 0.0,
                span_s=time.perf_counter() - t0)


def setup(ctx):
    cfg = ctx.config
    svc = ExploreService(mesh=ctx.mesh, **cfg["service"])
    bases = {}
    t_warm = []
    for b in range(int(cfg["base_spaces"])):
        for s, shape in enumerate(SHAPES):
            grids = draw_grids(cfg, rng_for(ctx.seed, "base", b, s), shape)
            space = DesignSpace(list(cfg["algorithms"]), grids)
            t = time.perf_counter()
            res = svc.submit(space, k=int(cfg["k"]), metric=cfg["metric"],
                             stream=True)
            for _ in res.partials():
                pass
            out = res.result()
            t_warm.append((shape, time.perf_counter() - t,
                           out.compile_s))
            bases[(b, shape)] = dict(grids=grids, space=space)
    ctx.log(f"open_loop_serve: warmed {len(bases)} bases: {t_warm}")
    first = {}
    for shape, secs, comp in t_warm:
        first.setdefault(shape, comp)
    rate = float(ctx.traffic["rate_per_s"])
    reqs = schedule(ctx, bases, rate, ctx.seconds)
    return dict(svc=svc, bases=bases, reqs=reqs,
                step_compile_s=sum(first.values()))


def window(ctx, state):
    rec = run_schedule(ctx, state["svc"], state["reqs"])
    rec["chips"] = ctx.chips
    rec["step_compile_s"] = state["step_compile_s"]
    served = [r for r in rec["requests"] if "serve" in r]
    ctx.log(f"open_loop_serve: {rec['attempted']} requests at "
            f"{ctx.traffic['rate_per_s']}/s, failed "
            f"{rec['failed']}, cache hits "
            f"{sum(r['serve'].get('cache_hit', False) for r in served)}, "
            f"generator max lateness {rec['max_late_s']} s, "
            f"p50 {float(np.percentile(rec['latency_s'], 50))} s")
    return rec


def release(state) -> None:
    state["svc"].close()
    state.clear()


def answers(ctx, rec):
    """``(whole, rows, extra)``: the sampled requests compared whole, and
    the counts ``lost`` and ``final_bad``."""
    reqs = rec["requests"]
    done = [j for j, r in enumerate(reqs) if "answer" in r]
    extra = dict(lost=sum(1 for r in reqs if "answer" not in r),
                 final_bad=sum(1 for r in reqs if "answer" in r
                               and not r["final_same"]))
    pick = []
    deep_miss = [j for j in done if reqs[j]["shape"] == "deep"
                 and not reqs[j]["serve"].get("cache_hit")]
    if deep_miss:
        pick.append(max(deep_miss, key=lambda j: rec["latency_s"][j]))
    rest = [j for j in done if j not in pick]
    n = min(int(ctx.traffic["check"]["requests"]) - len(pick), len(rest))
    if n > 0:
        pick += rng_for(ctx.seed, "check").choice(rest, n,
                                                  replace=False).tolist()
    return [(reqs[j]["answer"], reqs[j]["grids"]) for j in pick], [], extra
