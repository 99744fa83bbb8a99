"""`chip_smoke.py` off the chip: it refuses to pass without a TPU, and
its phases' logic holds at a tiny size on the CPU (Pallas in interpret
mode, float32 as on the chip), so a refactor that breaks the smoke
fails here before it costs a chip call."""

import os
import subprocess
import sys

import pytest

import chip_smoke
from kube_batch_tpu import faults
from kube_batch_tpu.testing import x64_enabled

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_smoke_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setattr(chip_smoke, "FULL", (1000, 100))
    monkeypatch.setattr(chip_smoke, "WAVE_PODS", 400)
    monkeypatch.setattr(chip_smoke, "REFERENCE", (500, 80))
    monkeypatch.setenv("KBT_PALLAS", "interpret")
    monkeypatch.setenv("KBT_MESH_PALLAS", "interpret")
    faults.solver_ladder.reset()  # the smoke's own process starts fresh
    with x64_enabled(False):
        yield


def test_smoke_main_path_on_cpu(tiny, capsys):
    chip_smoke.main_path()
    rows = capsys.readouterr().out
    assert '"tier": "pallas"' in rows and '"tier": "xla"' in rows
    assert '"check": "pallas_vs_xla_twin", "binds": 1000, "identical": true' in rows
    assert '"check": "device_vs_serial", "binds": 500, "identical": true' in rows


def test_smoke_mesh_path_on_cpu(tiny, monkeypatch, capsys):
    import jax

    # conftest's virtual CPU mesh: `mesh: auto` takes every device
    monkeypatch.setattr(chip_smoke, "MESH_CHIPS", len(jax.devices()))
    monkeypatch.setattr(chip_smoke, "MESH_BLOCK", "interpret")
    chip_smoke.mesh_path()
    rows = capsys.readouterr().out
    assert '"tier": "mesh_pallas"' in rows
    assert '"check": "mesh_vs_single_chip", "binds": 1000, "identical": true' in rows


def test_compile_cache_dir_rule(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX's own, and the code sets
    nothing; unset: the fixed <checkout>/.jax_cache/."""
    import jax

    from kube_batch_tpu.ops import enable_compilation_cache

    monkeypatch.delenv("KBT_JAX_CACHE", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compilation_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(_REPO, ".jax_cache")
        assert enable_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
