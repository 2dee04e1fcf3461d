"""Process set-up of the launch drivers and ``chip_smoke.py`` (CPU)."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.launch import runtime
from repro.launch.mesh import make_solver_mesh

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


def test_cache_follows_env_and_sets_nothing(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert runtime.setup_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_fallback_is_one_fixed_path(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    paths = []
    for sub in ("a", "b"):
        (tmp_path / sub).mkdir()
        monkeypatch.chdir(tmp_path / sub)
        paths.append(runtime.setup_compilation_cache())
    assert paths[0] == paths[1] == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == paths[0]


def test_host_devices_forced_only_on_cpu(monkeypatch):
    monkeypatch.setattr(os, "execv", lambda *a: pytest.fail("re-exec'd"))
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    runtime.force_host_devices(4, "repro.launch.solve")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    runtime.force_host_devices(4, "repro.launch.solve")  # already forced


def test_solver_precision_on_cpu():
    assert runtime.solver_dtype() == np.float64
    assert runtime.default_tol(np.float64) == 1e-8
    assert runtime.default_tol(np.float32) == 1e-4


def test_solver_mesh_from_real_devices():
    mesh = make_solver_mesh()
    assert mesh.axis_names == ("node", "proc")
    assert mesh.size == len(jax.devices())
    with pytest.raises(ValueError, match="available"):
        make_solver_mesh(len(jax.devices()) + 1)


def _run_smoke(script: Path, cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(script)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def _prints_no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "ok" in json.loads(line):
                return False
        except (ValueError, TypeError):
            pass
    return True


def test_chip_smoke_refuses_cpu():
    proc = _run_smoke(REPO / "chip_smoke.py", REPO)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert _prints_no_result(proc.stdout)


def test_chip_smoke_needs_the_repo(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run_smoke(tmp_path / "chip_smoke.py", tmp_path)
    assert proc.returncode != 0
    assert "repro package" in proc.stderr
    assert _prints_no_result(proc.stdout)
