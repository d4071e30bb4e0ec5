"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The production meshes need 512 placeholder host devices.  ``main()``
forces that count through ``XLA_FLAGS`` before its first device use (jax
reads the flag when its backends first initialize); importing this
module changes nothing.  The backend-probe reset keeps an earlier
memoized platform answer from leaking past the forced device count.

Per cell this harness produces:
  * feasibility proof: full-depth scanned step compiles on the mesh;
  * memory proof: compiled.memory_analysis() per-device bytes;
  * cost extraction (single-pod): python-unrolled reduced-depth compiles at
    L=2 and L=4 (identical widths and shardings) give exact per-layer FLOPs/
    bytes/collective-bytes by linear diff — lax.scan bodies are counted
    once by XLA cost analysis, so the scanned module CANNOT be used for
    costs (measured; see DESIGN.md §6).  Hybrid archs add a third compile
    (L=2, shared-attn every block) to separate the shared-attention cost.
  * roofline terms + CamJ-for-TPU energy breakdown.

Results append to benchmarks/results/dryrun.json; reruns skip completed
cells unless --force.
"""
import argparse
import dataclasses
import json
import os
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config
from ..distributed import (cache_shardings, input_shardings, param_shardings,
                           use_mesh)
from ..energy import (collective_bytes, model_flops, roofline_terms,
                      tpu_energy_report)
from ..energy.roofline import V5E
from ..kernels.runtime import reset_backend_cache
from ..models import model as M
from ..models.config import ModelConfig
from .mesh import make_production_mesh
from .shapes import SHAPES, ShapeSpec, cell_skip_reason

from jax.sharding import NamedSharding, PartitionSpec as P

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results")


# ---------------------------------------------------------------------------
# Abstract inputs (ShapeDtypeStruct — no allocation, per the assignment)
# ---------------------------------------------------------------------------
def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """Abstract model inputs for one cell.  [vlm]/[audio] frontends are
    stubs: precomputed patch/frame embeddings feed the backbone."""
    B, S = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)
    if shape.kind == "decode":
        tok = (jax.ShapeDtypeStruct((B, 1, cfg.d_model), dt)
               if cfg.family == "vlm"
               else jax.ShapeDtypeStruct((B, 1), jnp.int32))
        return {"tokens": tok}
    batch: Dict[str, Any] = {}
    if cfg.family == "vlm":
        batch["embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
        batch["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    else:
        batch["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if cfg.family == "encdec":
        batch["audio_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder_seq, cfg.d_model), dt)
    return batch


def _batch_shardings(mesh, cfg: ModelConfig, batch: Dict[str, Any],
                     global_batch: int, profile: str = "tp"):
    from ..distributed.sharding import batch_spec
    tok_spec = batch_spec(mesh, global_batch, extra_dims=1, profile=profile)
    out = {}
    for k, v in batch.items():
        if k in ("embeds", "audio_embeds") or (k == "tokens" and v.ndim == 3):
            if profile == "fsdp":
                out[k] = NamedSharding(mesh, P(*tok_spec, None))
            else:
                out[k] = input_shardings(mesh, global_batch)["embeds"]
        else:
            spec = list(tok_spec)[:v.ndim]
            spec += [None] * (v.ndim - len(spec))
            out[k] = NamedSharding(mesh, P(*spec))
    return out


# ---------------------------------------------------------------------------
# Step builders (abstract args + shardings)
# ---------------------------------------------------------------------------
def build_cell(cfg: ModelConfig, shape: ShapeSpec, mesh, unroll: bool,
               vocab_chunk: int = 0, profile: str = "tp"):
    """Returns (jitted_fn, abstract_args)."""
    params = M.abstract_params(cfg)
    psh = param_shardings(params, mesh, profile=profile)
    batch = input_specs(cfg, shape)
    bsh = _batch_shardings(mesh, cfg, batch, shape.global_batch,
                           profile=profile)

    if shape.kind == "train":
        opt = {"m": jax.tree.map(
                   lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32),
                   params),
               "v": jax.tree.map(
                   lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32),
                   params),
               "count": jax.ShapeDtypeStruct((), jnp.int32)}
        osh = {"m": psh, "v": psh,
               "count": NamedSharding(mesh, P())}
        step = jax.ShapeDtypeStruct((), jnp.int32)

        def fn(p, o, b, s):
            from ..optim import adamw_update
            from ..train.steps import cross_entropy_loss
            with use_mesh(mesh, profile=profile):
                def loss(params):
                    logits = M.forward(params, b, cfg, remat=True,
                                       unroll=unroll)
                    labels = b.get("labels")
                    if labels is None:
                        labels = jnp.roll(b["tokens"], -1, axis=1)
                    return cross_entropy_loss(logits, labels, vocab_chunk)
                lval, grads = jax.value_and_grad(loss)(p)
                newp, newo, om = adamw_update(grads, o, p, 3e-4)
                return newp, newo, {"loss": lval, **om}

        jfn = jax.jit(fn, in_shardings=(psh, osh, bsh,
                                        NamedSharding(mesh, P())),
                      donate_argnums=(0, 1))
        return jfn, (params, opt, batch, step)

    cache = M.abstract_cache(cfg, shape.global_batch, shape.seq_len)
    csh = cache_shardings(mesh, cache, shape.global_batch)

    if shape.kind == "prefill":
        def fn(p, b, c):
            with use_mesh(mesh, profile=profile):
                return M.prefill(p, b, c, cfg, unroll=unroll)
        jfn = jax.jit(fn, in_shardings=(psh, bsh, csh), donate_argnums=(2,))
        return jfn, (params, batch, cache)

    # decode
    tok = batch["tokens"]
    tsh = bsh["tokens"]

    def fn(p, t, c):
        with use_mesh(mesh, profile=profile):
            return M.decode_step(p, t, c, cfg, unroll=unroll)
    jfn = jax.jit(fn, in_shardings=(psh, tsh, csh), donate_argnums=(2,))
    return jfn, (params, tok, cache)


def _reduced_cfg(cfg: ModelConfig, layers: int,
                 shared_every: Optional[int] = None) -> ModelConfig:
    upd: Dict[str, Any] = {"n_layers": layers}
    if cfg.n_encoder_layers:
        upd["n_encoder_layers"] = layers
    if shared_every is not None:
        upd["shared_attn_every"] = shared_every
    return dataclasses.replace(cfg, **upd)


def _compile(cfg, shape, mesh, unroll, vocab_chunk=0, profile="tp"):
    fn, args = build_cell(cfg, shape, mesh, unroll, vocab_chunk, profile)
    t0 = time.time()
    lowered = fn.lower(*args)
    compiled = lowered.compile()
    dt = time.time() - t0
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    coll_w, coll_ops = collective_bytes(compiled.as_text())
    # HBM-traffic proxy: every assigned buffer is written once and read once
    # (2x args+outputs+temps).  The CPU backend's raw 'bytes accessed' counts
    # unfused operand bytes (10-30x pessimistic vs a fusing TPU backend);
    # the buffer-assignment footprint is fusion-aware, so 2x footprint is
    # the documented traffic model (EXPERIMENTS.md §Roofline).  Raw HLO
    # bytes are kept as 'bytes_hlo_dev' for reference.
    traffic = 2.0 * (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes)
    return {
        "compile_s": dt,
        "flops_dev": float(ca.get("flops", 0.0)),
        "bytes_dev": float(traffic),
        "bytes_hlo_dev": float(ca.get("bytes accessed", 0.0)),
        "coll_bytes_dev": coll_w,
        "coll_ops": coll_ops,
        "arg_gb_dev": ma.argument_size_in_bytes / 1e9,
        "temp_gb_dev": ma.temp_size_in_bytes / 1e9,
        "out_gb_dev": ma.output_size_in_bytes / 1e9,
        "peak_gb_dev": (ma.argument_size_in_bytes
                        + ma.temp_size_in_bytes) / 1e9,
    }


def run_cell(arch: str, shape: ShapeSpec, multi_pod: bool = False,
             with_costs: bool = True, vocab_chunk: int = 0,
             profile: str = "tp", remat_policy: str = "full",
             decode_no_repeat: bool = False) -> Dict:
    cfg = get_config(arch)
    if remat_policy != "full" or decode_no_repeat:
        cfg = dataclasses.replace(cfg, remat_policy=remat_policy,
                                  decode_no_repeat=decode_no_repeat)
    skip = cell_skip_reason(cfg, shape)
    rec: Dict[str, Any] = {"arch": arch, "shape": shape.name,
                           "mesh": "2x16x16" if multi_pod else "16x16",
                           "kind": shape.kind}
    if skip:
        rec.update(status="skipped", reason=skip)
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    try:
        # ---- feasibility + memory: full depth, scanned --------------------
        full = _compile(cfg, shape, mesh, unroll=False,
                        vocab_chunk=vocab_chunk, profile=profile)
        rec.update(status="ok", chips=chips, **{f"scan_{k}": v
                                                for k, v in full.items()})
        rec["fits_hbm"] = full["peak_gb_dev"] <= V5E.hbm_bytes / 1e9

        if with_costs and not multi_pod:
            # ---- exact costs: unrolled L-diff ------------------------------
            c2 = _compile(_reduced_cfg(cfg, 2), shape, mesh, unroll=True,
                          vocab_chunk=vocab_chunk, profile=profile)
            c4 = _compile(_reduced_cfg(cfg, 4), shape, mesh, unroll=True,
                          vocab_chunk=vocab_chunk, profile=profile)
            per_layer = {k: (c4[k] - c2[k]) / 2.0
                         for k in ("flops_dev", "bytes_dev", "bytes_hlo_dev",
                                   "coll_bytes_dev")}
            base = {k: c2[k] - 2.0 * per_layer[k] for k in per_layer}
            L = cfg.n_layers
            shared_cost = {k: 0.0 for k in per_layer}
            n_shared = 0
            if cfg.family == "hybrid":
                ce = _compile(_reduced_cfg(cfg, 2, shared_every=1), shape,
                              mesh, unroll=True, vocab_chunk=vocab_chunk,
                              profile=profile)
                shared_cost = {k: max(ce[k] - c2[k], 0.0) for k in per_layer}
                n_shared = (L + cfg.shared_attn_every - 1) \
                    // cfg.shared_attn_every
                base = {k: base[k] - shared_cost[k] for k in per_layer}
            total = {k: base[k] + L * per_layer[k]
                     + n_shared * shared_cost[k] for k in per_layer}
            mf = model_flops(cfg, shape.kind, shape.global_batch,
                             shape.seq_len)
            terms = roofline_terms(total["flops_dev"], total["bytes_dev"],
                                   total["coll_bytes_dev"], chips, mf)
            rec["roofline"] = terms.as_dict()
            rec["roofline"]["bytes_hlo_global"] = \
                total["bytes_hlo_dev"] * chips
            rec["energy"] = tpu_energy_report(
                total["flops_dev"], total["bytes_dev"],
                total["coll_bytes_dev"], chips)
            rec["per_layer"] = per_layer
            rec["cost_base"] = base
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    return rec


# ---------------------------------------------------------------------------
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all' (aliases accepted)")
    ap.add_argument("--shape", default="all", choices=["all"] + list(SHAPES))
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--no-costs", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--vocab-chunk", type=int, default=0)
    ap.add_argument("--profile", default="tp", choices=["tp", "fsdp"])
    ap.add_argument("--remat", default="full", choices=["full", "dots"])
    ap.add_argument("--no-repeat", action="store_true",
                    help="grouped-einsum GQA decode")
    ap.add_argument("--tag", default="",
                    help="suffix for the result key (hillclimb variants)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    reset_backend_cache()   # platform set changed: drop any memoized probe

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES.values()) if args.shape == "all" \
        else [SHAPES[args.shape]]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(RESULTS_DIR, exist_ok=True)
    out_path = args.out or os.path.join(RESULTS_DIR, "dryrun.json")
    results: Dict[str, Dict] = {}
    if os.path.exists(out_path) and not args.force:
        with open(out_path) as f:
            results = json.load(f)

    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                key = f"{arch}|{shape.name}|{'multi' if multi else 'single'}"
                if args.tag:
                    key += f"|{args.tag}"
                if key in results and results[key].get("status") in \
                        ("ok", "skipped") and not args.force:
                    print(f"[cached] {key}")
                    continue
                t0 = time.time()
                rec = run_cell(arch, shape, multi_pod=multi,
                               with_costs=not args.no_costs,
                               vocab_chunk=args.vocab_chunk,
                               profile=args.profile,
                               remat_policy=args.remat,
                               decode_no_repeat=args.no_repeat)
                if args.tag:
                    rec["tag"] = args.tag
                    rec["levers"] = dict(profile=args.profile,
                                         remat=args.remat,
                                         no_repeat=args.no_repeat,
                                         vocab_chunk=args.vocab_chunk)
                results[key] = rec
                with open(out_path, "w") as f:
                    json.dump(results, f, indent=1)
                status = rec.get("status")
                extra = ""
                if status == "ok" and "roofline" in rec:
                    r = rec["roofline"]
                    extra = (f" dom={r['dominant']}"
                             f" frac={r['roofline_fraction']:.3f}"
                             f" mem={rec['scan_peak_gb_dev']:.2f}GB")
                elif status == "error":
                    extra = " " + rec.get("error", "")[:120]
                print(f"[{status}] {key} ({time.time()-t0:.0f}s){extra}",
                      flush=True)

    n_ok = sum(1 for r in results.values() if r.get("status") == "ok")
    n_skip = sum(1 for r in results.values()
                 if r.get("status") == "skipped")
    n_err = sum(1 for r in results.values() if r.get("status") == "error")
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_err} errors -> {out_path}")


if __name__ == "__main__":
    main()
