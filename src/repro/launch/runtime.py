"""Process set-up shared by the launch drivers and ``chip_smoke.py``.

* :func:`setup_compilation_cache` — JAX's persistent compilation cache:
  ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads the
  variable itself; nothing is overridden), else one fixed directory inside
  the checkout, so every process of this checkout finds what an earlier one
  compiled (the path is part of the cache key: a per-run name never hits).
* :func:`force_host_devices` — virtual devices for a multi-device run on
  the CPU backend (``JAX_PLATFORMS=cpu``).  On an accelerator the mesh is
  built from the real devices, and one process holds them all.
* :func:`solver_dtype` — the solver's precision on the current backend.

Nothing here runs at import.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

import numpy as np

#: the checkout this package lives in (``src/repro/launch/runtime.py``)
REPO_ROOT = Path(__file__).resolve().parents[3]
#: compilation-cache directory used when the environment names none
CACHE_DIR = REPO_ROOT / ".jax_cache"

_FORCE_FLAG = "--xla_force_host_platform_device_count"


def setup_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def force_host_devices(n: int, module: str) -> None:
    """Re-exec ``python -m module`` with ``n`` virtual CPU devices.

    Only under ``JAX_PLATFORMS=cpu`` and only when the flag is not already
    set (the re-exec'd process sees it and returns).  Must run before JAX
    initializes a backend.
    """
    if not n or os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if _FORCE_FLAG in flags:
        return
    os.environ["XLA_FLAGS"] = f"{flags} {_FORCE_FLAG}={n}".strip()
    os.execv(sys.executable, [sys.executable, "-m", module] + sys.argv[1:])


def solver_dtype():
    """float64 on the CPU backend (turns ``jax_enable_x64`` on, for the
    f64 reference runs); float32 on an accelerator, whose Pallas kernels
    have no f64 path (x64 stays off there)."""
    import jax

    if jax.default_backend() == "cpu":
        jax.config.update("jax_enable_x64", True)
        return np.float64
    return np.float32


def default_tol(dtype) -> float:
    """Solver tolerance (recursive residual) a precision reaches on the
    launchers' operators up to the paper's full 1.3M-row DG size: 1e-8 in
    float64; 1e-4 in float32, where ECG crawls below about 1e-4 at full
    size (recursive 6.3e-5 after 3,000 iterations on a TPU v5e) and the
    float64 true residual lands within 5x of the recursive one."""
    return 1e-8 if np.dtype(dtype) == np.float64 else 1e-4
