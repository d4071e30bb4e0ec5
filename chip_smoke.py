"""Chip smoke test: the design-space sweep through its user entry points.

    python chip_smoke.py             # one TPU chip: phases (a)-(d)
    python chip_smoke.py --chips 4   # the mega sweep on 4 chips vs 1

Runs in ONE process, because a TPU chip belongs to one process at a time.
Refuses to start (non-zero exit, no result line) unless JAX's first
device is a TPU, and refuses ``REPRO_SWEEP_BACKEND`` /
``REPRO_KERNEL_INTERPRET`` overrides: on the chip the sweep must resolve
to the Mosaic-compiled Pallas megakernel.

Phases, on a one-chip ``make_batch_mesh(1)`` (run in the order a, d, b, c,
so that the campaign reuses the sweep's compiled step):

(a) sweep: ``explore()`` over the ~1.26e7-point mega grid (all 10 axes,
    Ed-Gaze + Rhythmic); one step executable, lane ``pallas/compiled``,
    top-k equal to the XLA twin's on the same chip at rel 1e-6, every
    winner equal to the scalar oracle at rel 5e-4, and the device decode
    equal to the host grid bit for bit;
(b) monolithic: a <= 2**15-point ``explore()`` (per-plan evaluator and
    the ``category_reduce`` kernel) against the scalar oracle;
(c) service: 4 concurrent same-shape requests to an ``ExploreService``,
    all answered, none failed, one step executable between them;
(d) campaign: a checkpointed campaign of >= 4 shards, one shard file
    deleted, ``resume()``: full coverage, nothing quarantined, top-k
    equal to (a).

Every line but the last is a log line.  The last line of stdout is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
#: phase (d)'s checkpoint directory, inside the checkout (git-ignored)
WORKDIR = os.path.join(_HERE, "benchmarks", "results", "chip_smoke")
sys.path[:0] = [os.path.join(_HERE, "src"), os.path.join(_HERE, "benchmarks")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from compile_cache import setup_compile_cache  # noqa: E402
from run import MEGA_GRIDS  # noqa: E402
from repro.campaign import CampaignOptions, resume  # noqa: E402
from repro.campaign.manifest import completed_shards  # noqa: E402
from repro.core.shard_sweep import (_prepare_stream,  # noqa: E402
                                    stream_cache_clear, stream_cache_info)
from repro.core.sweep import AXES, scalar_point  # noqa: E402
from repro.explore import DesignSpace, explore  # noqa: E402
from repro.kernels.grid_decode import grid_decode  # noqa: E402
from repro.launch.mesh import make_batch_mesh  # noqa: E402
from repro.serve import ExploreService  # noqa: E402

#: the lane the sweep must resolve to on a TPU
TPU_LANE = ("pallas", "compiled")
ALGORITHMS = ("edgaze", "rhythmic")
K = 8
#: the compiled megakernel's block: ``explore()``'s default
BLOCK_POINTS = 4096
#: f32 device math against the f64 scalar oracle
ORACLE_REL = 5e-4
#: the same sweep on another lane or mesh
PARITY_REL = 1e-6

#: phase (b): 24 points per variant, 192 in all — the monolithic engine
SMALL_GRIDS = {"cis_node": [130.0, 65.0, 28.0],
               "frame_rate": [30.0, 120.0],
               "sys_rows": [8.0, 32.0],
               "mem_tech": ["sram", "stt"]}


def log(msg: str) -> None:
    print(msg, flush=True)


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def _device_label() -> str:
    devs = jax.devices()
    return f"{devs[0].platform}:{devs[0].device_kind} x{len(devs)}"


def _oracle_check(res, what: str) -> float:
    """Every top-k row against the scalar oracle; returns the worst rel
    error over the numeric outputs."""
    _check(len(res.topk) > 0, f"{what}: no feasible winner")
    worst = 0.0
    for row in res.topk:
        kw = {ax: row[ax] for ax in AXES}
        kw["mem_tech"] = int(row["mem_tech"])
        ref = scalar_point(row["algorithm"], row["variant"], **kw)
        _check(bool(ref["feasible"]),
               f"{what}: winner {row['index']} infeasible in the oracle")
        for key, want in ref.items():
            if key == "feasible":
                continue
            rel = abs(row[key] - want) / max(abs(want), 1e-30)
            if abs(row[key] - want) > 1e-12:
                worst = max(worst, rel)
            _check(rel <= ORACLE_REL or abs(row[key] - want) <= 1e-12,
                   f"{what}: {row['algorithm']}/{row['variant']} "
                   f"#{row['index']} {key}={row[key]!r} vs oracle "
                   f"{want!r} (rel {rel:.3g})")
    return worst


def _topk_rel(a, b) -> float:
    va = np.array([r["total_j"] for r in a.topk])
    vb = np.array([r["total_j"] for r in b.topk])
    _check(va.shape == vb.shape, f"top-k sizes {va.shape} vs {vb.shape}")
    return float(np.max(np.abs(va - vb) / np.abs(vb))) if va.size else 0.0


def _same_winners(a, b, rel: float) -> None:
    """Rank-by-rank equal top-k values at ``rel``; the winners' identity
    must agree at every rank whose value no other rank matches within
    ``rel`` (a near-tie may order either way)."""
    worst = _topk_rel(a, b)
    _check(worst <= rel, f"top-k values differ: max rel {worst:.3g}")
    vals = np.array([r["total_j"] for r in b.topk])
    for j, (ra, rb) in enumerate(zip(a.topk, b.topk)):
        tied = np.sum(np.abs(vals - vals[j]) <= rel * abs(vals[j])) > 1
        ida = (ra["algorithm"], ra["variant"], ra["index"])
        idb = (rb["algorithm"], rb["variant"], rb["index"])
        _check(tied or ida == idb, f"rank {j}: winner {ida} vs {idb}")


def _same_summaries(a, b, mean_rel: float) -> float:
    """Equal per-variant counts and minima; means at ``mean_rel`` (f32
    sums folded in another order).  Returns the worst mean rel diff."""
    _check(sorted(a.summaries) == sorted(b.summaries), "variant labels")
    worst = 0.0
    for label, sa in a.summaries.items():
        sb = b.summaries[label]
        for key in ("n", "n_feasible"):
            _check(sa[key] == sb[key], f"{label}.{key}: {sa[key]} vs "
                                       f"{sb[key]}")
        _check(abs(sa["metric_min"] - sb["metric_min"])
               <= PARITY_REL * abs(sb["metric_min"]),
               f"{label}.metric_min {sa['metric_min']} vs "
               f"{sb['metric_min']}")
        if sb["n_feasible"]:
            rel = (abs(sa["metric_mean"] - sb["metric_mean"])
                   / abs(sb["metric_mean"]))
            worst = max(worst, rel)
            _check(rel <= mean_rel, f"{label}.metric_mean rel {rel:.3g}")
    return worst


def _decode_check(grids, n: int = 1 << 16) -> int:
    """The staged engine's device decode (``grid_decode``, its one-hot
    ``decode_axis_values``) against the host ``ChunkedGrid``, bit for
    bit, on windows at the start, across a variant boundary and at the
    end of the flat index space.  Returns the number of points
    checked."""
    prep = _prepare_stream(list(ALGORITHMS), grids)
    n = min(n, prep.total // 2)
    shape = prep.vgrids[0].shape
    checked = 0
    for start in (0, max(prep.n_var - n // 2, 0), prep.total - n):
        vals, vid = grid_decode(prep.tables, start, shape=shape,
                                n_var=prep.n_var, total=prep.total,
                                chunk=n)
        vals, vid = np.asarray(vals), np.asarray(vid)
        flat = np.arange(start, start + n)
        want_vid = flat // prep.n_var
        _check(np.array_equal(vid, want_vid), f"variant ids at {start}")
        for v in np.unique(want_vid):
            sel = want_vid == v
            local = flat[sel] - v * prep.n_var
            host = prep.vgrids[v].chunk(int(local[0]), int(local[-1]) + 1)
            for a, name in enumerate(prep.vgrids[v].names):
                _check(np.array_equal(vals[a, sel],
                                      host[name].astype(np.float32)),
                       f"decoded {name} differs from the host grid in "
                       f"the window at {start}")
        checked += n
    return checked


def phase_sweep(mesh, grids, *, lane=TPU_LANE, reference="xla"):
    """(a) the mega sweep: one executable, the expected lane, parity with
    the ``reference`` backend and with the scalar oracle."""
    space = DesignSpace(list(ALGORITHMS), grids)
    stream_cache_clear()
    res = explore(space, k=K, engine="fused", mesh=mesh,
                  block_points=BLOCK_POINTS)
    st = res.stream_result
    compiles = stream_cache_info()["step_compiles"]
    _check(compiles == 1, f"sweep compiled {compiles} step executables")
    _check((res.backend, st.kernel_mode) == lane,
           f"sweep ran {res.backend}/{st.kernel_mode}, expected "
           f"{'/'.join(lane)}")
    block = min(BLOCK_POINTS, st.chunk_size // st.n_devices)
    log(f"phase a: sweep {res.n_points} points on {_device_label()} "
        f"[{res.backend}/{st.kernel_mode}, block_points={block}, "
        f"chunk={st.chunk_size}, superchunk={st.superchunk}]: "
        f"compile_s={res.compile_s} eval_s={res.eval_s} "
        f"points_per_s={st.points_per_sec} dispatches={res.dispatches} "
        f"step_executables={compiles}")
    ref = explore(space, k=K, engine="fused", mesh=mesh, backend=reference)
    _same_winners(res, ref, PARITY_REL)
    mean_rel = _same_summaries(res, ref, 1e-5)
    worst = _oracle_check(res, "phase a")
    decoded = _decode_check(grids)
    log(f"phase a: {decoded} decoded points == host ChunkedGrid bit for "
        f"bit")
    log(f"phase a: top-k == {reference} lane (max rel "
        f"{_topk_rel(res, ref)}, summary mean rel {mean_rel}); "
        f"{len(res.topk)} winners == scalar oracle (max rel {worst}); "
        f"{reference} lane compile_s={ref.compile_s} "
        f"eval_s={ref.eval_s}")
    return res


def phase_monolithic(mesh, grids=SMALL_GRIDS):
    """(b) a small sweep on the monolithic engine (per-plan evaluator +
    ``category_reduce``) against the scalar oracle."""
    space = DesignSpace(list(ALGORITHMS), grids)
    _check(space.n_points <= 2 ** 15, f"{space.n_points} points")
    res = explore(space, k=K, mesh=mesh)
    _check(res.engine == "monolithic", f"engine {res.engine}")
    worst = _oracle_check(res, "phase b")
    log(f"phase b: monolithic {res.n_points} points, {len(res.topk)} "
        f"winners == scalar oracle (max rel {worst})")
    return res


def service_spaces(grids, n: int = 4):
    """``n`` distinct spaces of one shape: frame rates shifted per
    tenant."""
    return [DesignSpace(list(ALGORITHMS),
                        dict(grids, frame_rate=[f + i for f in
                                                grids["frame_rate"]]))
            for i in range(n)]


def phase_service(mesh, spaces, *, chunk_size=None):
    """(c) concurrent same-shape requests coalesce onto one executable;
    every one answered, none failed."""
    stream_cache_clear()
    with ExploreService(mesh=mesh, coalesce_window_s=0.2) as svc:
        handles = [svc.submit(s, k=K, engine="fused",
                              chunk_size=chunk_size) for s in spaces]
        results = [h.result(timeout=1200) for h in handles]
        metrics = svc.metrics()
    compiles = stream_cache_info()["step_compiles"]
    for space, res in zip(spaces, results):
        _check(res.n_points == space.n_points and len(res.topk) > 0,
               f"service result {res.n_points} points, "
               f"{len(res.topk)} winners")
    _check(metrics["failed"] == 0, f"service failures: {metrics}")
    _check(metrics["completed"] == len(spaces), f"completed: {metrics}")
    _check(compiles == 1, f"service compiled {compiles} executables")
    log(f"phase c: service answered {len(results)}/{len(spaces)} "
        f"requests, failed={metrics['failed']}, "
        f"max_group={metrics['max_group']}, step_executables={compiles}")
    return results


def phase_campaign(mesh, grids, reference, workdir, *, shard_points=None,
                   chunk_size=None):
    """(d) checkpointed campaign, one shard checkpoint lost, resume:
    full coverage, nothing quarantined, top-k equal to ``reference``."""
    space = DesignSpace(list(ALGORITHMS), grids)
    ckpt = os.path.join(workdir, "campaign")
    shutil.rmtree(ckpt, ignore_errors=True)
    opts = CampaignOptions(shard_points=shard_points)
    first = explore(space, k=K, checkpoint_dir=ckpt, campaign=opts,
                    mesh=mesh, chunk_size=chunk_size, workers=1)
    shards = completed_shards(ckpt)
    _check(len(shards) >= 4, f"campaign planned {len(shards)} shards")
    lost = sorted(shards)[len(shards) // 2]
    os.remove(shards[lost])
    res = resume(ckpt, mesh=mesh, workers=1)
    for rep in (first.campaign, res.campaign):
        _check(not rep["quarantined"], f"quarantined: {rep['quarantined']}")
        _check(not rep["partial"] and not rep["missing"],
               f"coverage gaps: {rep['missing']}")
    _check(res.campaign["n_executed"] == 1,
           f"resume ran {res.campaign['n_executed']} shards, expected 1")
    _same_winners(res, reference, PARITY_REL)
    log(f"phase d: campaign {len(shards)} shards, lost {list(lost)} and "
        f"resumed 1: coverage full, quarantined 0, top-k == phase a "
        f"(max rel {_topk_rel(res, reference)})")
    return res


def phase_four_chips(grids):
    """The mega sweep sharded over 4 chips against the same sweep on 1:
    identical top-k, equal summaries."""
    space = DesignSpace(list(ALGORITHMS), grids)
    runs = {}
    for n in (4, 1):
        runs[n] = explore(space, k=K, engine="fused",
                          mesh=make_batch_mesh(n))
        st = runs[n].stream_result
        _check((runs[n].backend, st.kernel_mode) == TPU_LANE,
               f"{n} chips ran {runs[n].backend}/{st.kernel_mode}")
        log(f"chips={n}: {runs[n].n_points} points on "
            f"{_device_label()} [{runs[n].backend}/{st.kernel_mode}]: "
            f"compile_s={runs[n].compile_s} eval_s={runs[n].eval_s} "
            f"points_per_s={st.points_per_sec}")
    a, b = runs[4], runs[1]
    ids = [(r["algorithm"], r["variant"], r["index"], r["total_j"])
           for r in a.topk]
    _check(ids == [(r["algorithm"], r["variant"], r["index"], r["total_j"])
                   for r in b.topk], "top-k differs between 4 chips and 1")
    mean_rel = _same_summaries(a, b, PARITY_REL)
    log(f"chips=4 vs 1: top-k identical ({len(ids)} rows, bit-equal "
        f"values); summaries equal (mean rel {mean_rel})")


def _refuse_unless_tpu() -> None:
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (first device "
                         f"platform is {platform!r}); nothing was run")
    for var in ("REPRO_SWEEP_BACKEND", "REPRO_KERNEL_INTERPRET"):
        if os.environ.get(var, "").strip().lower() not in ("", "auto"):
            raise SystemExit(f"chip_smoke: {var}={os.environ[var]!r} "
                             f"would move the sweep off the compiled "
                             f"Pallas lane; unset it")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mega sweep on 4 chips vs 1")
    args = ap.parse_args(argv)
    _refuse_unless_tpu()
    setup_compile_cache()
    log(f"chip_smoke: {_device_label()}, jax {jax.__version__}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(MEGA_GRIDS)
    else:
        mesh = make_batch_mesh(1)
        swept = phase_sweep(mesh, MEGA_GRIDS)
        # the campaign reuses the sweep's step executable: run it next
        try:
            phase_campaign(mesh, MEGA_GRIDS, swept, WORKDIR)
        finally:
            shutil.rmtree(WORKDIR, ignore_errors=True)
        phase_monolithic(mesh)
        small = dict(MEGA_GRIDS, cis_node=MEGA_GRIDS["cis_node"][:4])
        phase_service(mesh, service_spaces(small))
    log(f"chip_smoke: all phases passed in "
        f"{time.perf_counter() - t0:.1f} s")
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
