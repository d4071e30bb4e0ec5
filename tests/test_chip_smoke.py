"""``chip_smoke.py`` on the CPU: its phases at a tiny grid, and its refusal.

The phases take a mesh and a grid, so here they run end to end through
the XLA twin (the CPU's lane), with the interpreted Pallas kernel as the
cross-lane reference.  Run as a script without a TPU, the smoke must exit
non-zero before any phase and print no result line.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

#: every mega-grid axis, 1-2 values each: 32 points per variant
TINY = {"cis_node": [130.0, 65.0], "soc_node": [22.0],
        "frame_rate": [30.0, 120.0], "sys_rows": [8.0, 32.0],
        "sys_cols": [16.0], "mem_tech": ["sram", "stt"],
        "active_fraction_scale": [0.5, 1.0], "pixel_pitch_um": [3.0]}


@pytest.fixture(scope="module")
def mesh():
    from repro.launch.mesh import make_batch_mesh
    return make_batch_mesh(1)


def test_smoke_phases_on_cpu_through_the_xla_twin(mesh, tmp_path,
                                                  monkeypatch):
    monkeypatch.delenv("REPRO_SWEEP_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_KERNEL_INTERPRET", raising=False)
    swept = chip_smoke.phase_sweep(mesh, TINY, lane=("xla", "xla"),
                                   reference="pallas")
    assert swept.n_points == 8 * 32
    chip_smoke.phase_campaign(mesh, TINY, swept, str(tmp_path),
                              shard_points=48, chunk_size=8)
    chip_smoke.phase_monolithic(mesh)
    results = chip_smoke.phase_service(
        mesh, chip_smoke.service_spaces(TINY), chunk_size=8)
    assert len(results) == 4


def test_smoke_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT,
                                                        "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "phase" not in proc.stdout and '"ok"' not in proc.stdout
