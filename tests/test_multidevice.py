"""Multi-device execution tests (not just compile): run sharded train and
decode steps on an 8-host-device mesh in a subprocess (the device-count
XLA flag must precede jax init), and check numerical equality with the
single-device result — the strongest runnability evidence available on CPU.
"""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
import repro.models.model as M
from repro.compat import auto_axis_types
from repro.configs import get_config, reduced
from repro.distributed import param_shardings, use_mesh, cache_shardings
from repro.distributed.sharding import batch_spec
from repro.optim import adamw_init
from repro.train import build_train_step

assert len(jax.devices()) == 8
for arch in ("qwen3_4b", "granite_moe_1b_a400m", "falcon_mamba_7b"):
    cfg = dataclasses.replace(reduced(get_config(arch)), d_head=0)
    cfg = reduced(get_config(arch), d_model=64)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 64),
                                          0, cfg.vocab)}
    # single-device reference
    ref_step = jax.jit(build_train_step(cfg, warmup_steps=1, total_steps=10))
    _, _, ref_metrics = ref_step(params, opt, batch, 1)
    ref_loss = float(ref_metrics["loss"])

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=auto_axis_types(2))
    psh = param_shardings(params, mesh)
    osh = {"m": psh, "v": psh, "count": NamedSharding(mesh, P())}
    bsh = {"tokens": NamedSharding(mesh, batch_spec(mesh, 8))}
    base = build_train_step(cfg, warmup_steps=1, total_steps=10)

    def step(p, o, b, s):
        with use_mesh(mesh):
            return base(p, o, b, s)
    jstep = jax.jit(step, in_shardings=(psh, osh, bsh,
                                        NamedSharding(mesh, P())))
    p_sh = jax.device_put(params, psh)
    o_sh = jax.device_put(opt, osh)
    b_sh = {"tokens": jax.device_put(batch["tokens"], bsh["tokens"])}
    _, _, m2 = jstep(p_sh, o_sh, b_sh, 1)
    sharded_loss = float(m2["loss"])
    err = abs(sharded_loss - ref_loss) / max(abs(ref_loss), 1e-6)
    print(f"{arch}: ref={ref_loss:.6f} sharded={sharded_loss:.6f} "
          f"rel={err:.2e}")
    assert err < 2e-2, (arch, ref_loss, sharded_loss)

    # decode path on the mesh
    cache = M.init_cache(cfg, 8, max_seq=80)
    csh = cache_shardings(mesh, cache, 8)
    with use_mesh(mesh):
        pre = jax.jit(lambda p, b, c: M.prefill(p, b, c, cfg),
                      in_shardings=(psh, bsh, csh))
        lg, cache2 = pre(p_sh, b_sh, jax.device_put(cache, csh))
    assert np.isfinite(np.asarray(lg, np.float32)).all(), arch
print("MULTIDEVICE_OK")
"""


@pytest.mark.slow
def test_sharded_execution_matches_single_device():
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MULTIDEVICE_OK" in proc.stdout, proc.stdout


BACKEND_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.pop("REPRO_SWEEP_BACKEND", None)
import jax
from repro.kernels import runtime
assert len(jax.devices()) == 8
# a stale platform probe (memoized before this process forced its cpu
# device set — the bug reset_backend_cache exists for) must not leak
# into backend resolution after a reset
runtime.on_tpu()
runtime._BACKEND_IS_TPU = True           # simulate the stale memo
assert runtime.resolve_backend(None) == "pallas"
runtime.reset_backend_cache()
assert runtime.on_tpu() is False
assert runtime.resolve_backend(None) == "xla"
from repro.core.shard_sweep import sweep_stream
grids = {"variant": ["2d_in", "3d_in"],
         "cis_node": [130.0, 65.0, 28.0],
         "frame_rate": [15.0, 30.0]}
res = sweep_stream("edgaze", grids, chunk_size=4, k=3)
assert res.backend == "xla" and res.kernel_mode == "xla", (
    res.backend, res.kernel_mode)
print("BACKEND_RESET_OK")
"""


@pytest.mark.slow
def test_backend_cache_reset_on_forced_device_mesh():
    """reset_backend_cache() re-probes the platform inside a subprocess
    whose device set was forced after a (simulated) earlier probe; the
    resolved auto backend then drives an actual 8-device sweep."""
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", BACKEND_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "BACKEND_RESET_OK" in proc.stdout, proc.stdout
