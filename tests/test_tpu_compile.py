"""Compile rehearsals for one TPU v5e, without the chip.

The sweep's Mosaic kernels and the superchunk step compile here for a
described ``v5e:2x2`` topology at the mega grid's real widths (10 axes,
8 variants, ~1.26e7 points, ``block_points=4096``); the megakernel and
the steps also at the camj-study space's (five 16-value axes, so the
decode's select chains and the SMEM axis table are as long as the chip
benchmark's).  A compile that the
TPU compiler refuses fails here at no chip time; a compile that passes
is not a chip run and says nothing about results or speed.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and each test worker imports every
test file.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.compat import auto_axis_types
from repro.kernels.fused_sweep import KERNEL_NAME

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
from run import MEGA_GRIDS  # noqa: E402

BLOCK = 4096
CHUNK = 1 << 18
K = 16

#: the widths of the chip benchmark's camj-study space
#: (``benchmarks/chip/configs/camj-study.json``): every value of the
#: three categorical axes and 16 values on each other swept axis
STUDY_GRIDS = {
    "cis_node": [130.0, 110.0, 90.0, 80.0, 65.0, 55.0, 45.0, 40.0, 32.0,
                 28.0, 22.0, 16.0, 14.0],
    "soc_node": [14.0, 22.0, 28.0],
    "mem_tech": ["sram", "sram_hp", "stt"],
    "sys_rows": list(np.linspace(4.0, 128.0, 16)),
    "sys_cols": list(np.linspace(4.0, 128.0, 16)),
    "frame_rate": list(np.linspace(15.0, 240.0, 16)),
    "active_fraction_scale": list(np.linspace(0.1, 1.0, 16)),
    "pixel_pitch_um": list(np.linspace(2.0, 6.0, 16)),
}
#: the widths of the camj-wide space (``configs/camj-wide.json``):
#: camj-study's with 24 values on sys_rows, sys_cols and frame_rate,
#: 414,056,448 points a variant and 3,312,451,584 in all, past 2**31
WIDE_GRIDS = dict(STUDY_GRIDS,
                  sys_rows=list(np.linspace(4.0, 128.0, 24)),
                  sys_cols=list(np.linspace(4.0, 128.0, 24)),
                  frame_rate=list(np.linspace(15.0, 240.0, 24)))
#: grids and metric of each width the rehearsals compile at
WIDTHS = {"mega": (MEGA_GRIDS, "total_j"),
          "camj-study": (STUDY_GRIDS, "density_mw_mm2")}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def preps():
    from repro.core.shard_sweep import _prepare_stream
    return {name: _prepare_stream(["edgaze", "rhythmic"], grids)
            for name, (grids, _metric) in WIDTHS.items()}


@pytest.fixture
def prep(preps):
    return preps["mega"]


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def steer_tpu(monkeypatch):
    """The step factory asks the runtime for its platform; the described
    chip is not what ``jax.default_backend()`` sees, so steer it."""
    from repro.kernels import runtime
    monkeypatch.delenv("REPRO_SWEEP_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    monkeypatch.setattr(runtime, "_BACKEND_IS_TPU", True)


def _kernel(prep, metric="total_j"):
    from repro.core.batch import build_coeff_compute
    from repro.core.sweep import AXES
    from repro.kernels.fused_sweep import fused_sweep_block
    compute = build_coeff_compute(prep.bank.dims)

    def f(table, row, start, low, limit):
        return fused_sweep_block(
            table, row, start, low, limit, compute=compute,
            metric=metric, axis_names=tuple(AXES),
            shape=tuple(prep.vgrids[0].shape), chunk=CHUNK,
            block_points=BLOCK, kk=K, interpret=False)
    return f


def _kernel_args(prep, sharding):
    width = prep.bank.arrays["fused"].shape[1]
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)
    return (jax.ShapeDtypeStruct((prep.table2.shape[0], prep.lmax),
                                 jnp.float32, sharding=sharding),
            jax.ShapeDtypeStruct((1, width), jnp.float32,
                                 sharding=sharding),
            scalar, scalar, scalar)


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_megakernel_compiles_for_v5e(preps, width, one_chip,
                                     no_persistent_cache):
    prep = preps[width]
    compiled = jax.jit(_kernel(prep, WIDTHS[width][1])).lower(
        *_kernel_args(prep, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _lower_step(prep, mesh, metric):
    """The superchunk scan step of the sweep as ``explore()`` builds it
    on a TPU, lowered for ``mesh``."""
    from repro.core.shard_sweep import (_DEFAULT_SUPERCHUNK, _fused_step,
                                        _init_banked_state)
    superchunk, out_keys = _fused_step(
        prep.bank, mesh, metric, K, CHUNK, BLOCK, prep.vgrids[0].shape,
        prep.lmax, _DEFAULT_SUPERCHUNK, -(-prep.n_var // CHUNK),
        backend="pallas")
    rep = NamedSharding(mesh, P())

    def spec(x):
        return jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype,
                                    sharding=rep)
    state0 = _init_banked_state(K, len(out_keys), prep.n_variants,
                                with_out=False)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    bounds = jax.ShapeDtypeStruct((prep.n_variants,), jnp.int32,
                                  sharding=rep)
    return jax.jit(superchunk, donate_argnums=(6,)).lower(
        scalar, bounds, bounds, scalar, spec(prep.table2),
        jax.tree.map(spec, prep.bank.arrays), jax.tree.map(spec, state0))


def test_wide_space_step_holds_no_64bit_value(topo, steer_tpu,
                                              no_persistent_cache):
    """A camj-wide space (3.3e9 points, each variant under 2**31) runs
    on the same int32 superchunk step as any other: it compiles for the
    chip with no 64-bit integer anywhere in the program, while a space
    with ONE variant of 2**31 points or more is refused before tracing
    (ROADMAP B1's limit)."""
    from repro.core.shard_sweep import _prepare_stream, stream_cache_info
    from repro.explore import DesignSpace, explore
    prep = _prepare_stream(["edgaze", "rhythmic"], WIDE_GRIDS)
    assert prep.n_var == 414_056_448 and prep.n_variants == 8
    assert prep.total == 3_312_451_584 > 2 ** 31
    mesh = Mesh(np.array(topo.devices[:1]), ("batch",),
                axis_types=auto_axis_types(1))
    lowered = _lower_step(prep, mesh, "density_mw_mm2")
    # no 64-bit integer value: none in the lowered module's tensors, none
    # in the compiled program's shapes
    assert not re.search(r"tensor<(?:[0-9?]+x)*u?i64>", lowered.as_text())
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo and KERNEL_NAME in hlo
    assert not re.search(r"\b[su]64\[", hlo)

    before = stream_cache_info()["step_compiles"]
    one_variant = dict(WIDE_GRIDS, variant=["3d_in"],
                       active_fraction_scale=list(np.linspace(0.1, 1.0,
                                                              83)))
    space = DesignSpace("edgaze", one_variant)
    assert space.n_var >= 2 ** 31
    with pytest.raises(NotImplementedError, match=r"fewer than 2\*\*31"):
        explore(space, engine="fused", k=K)
    assert stream_cache_info()["step_compiles"] == before


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("n_chips", [1, 4])
def test_superchunk_step_compiles_for_v5e(preps, width, topo, n_chips,
                                          steer_tpu, no_persistent_cache):
    """The whole superchunk scan step of the sweep, on a mesh of the
    described chips, as ``explore()`` builds it on a TPU."""
    mesh = Mesh(np.array(topo.devices[:n_chips]), ("batch",),
                axis_types=auto_axis_types(1))
    hlo = _lower_step(preps[width], mesh, WIDTHS[width][1]).compile(
    ).as_text()
    assert "tpu_custom_call" in hlo
    # the profile reduction can find the megakernel by its name
    assert KERNEL_NAME in hlo


def test_grid_decode_compiles_for_v5e(prep, one_chip, no_persistent_cache):
    """The staged engine's standalone decode kernel (its one-hot
    ``decode_axis_values``), which the chip smoke checks bit for bit
    against the host grid."""
    from repro.kernels.grid_decode import grid_decode
    compiled = jax.jit(lambda t, s: grid_decode(
        t, s, shape=tuple(prep.vgrids[0].shape), n_var=prep.n_var,
        total=prep.total, chunk=1 << 16, block_points=BLOCK,
        interpret=False)).lower(
        jax.ShapeDtypeStruct(prep.tables.shape, jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_category_reduce_compiles_for_v5e(one_chip, no_persistent_cache):
    """The monolithic engine's per-category reduction kernel."""
    from repro.kernels.category_reduce import category_reduce
    compiled = jax.jit(
        lambda e, w: category_reduce(e, w, interpret=False)).lower(
        jax.ShapeDtypeStruct((1 << 15, 11), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((11, 10), jnp.float32,
                             sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
