"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device state
(jax locks the device count on first backend init — see dryrun.py lines 1-2).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Assigned production meshes: 16x16 chips per pod; 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_solver_mesh(n_devices: int | None = None, ppn: int | None = None):
    """Two-level ("node", "proc") grid for the distributed ECG solver over
    the first ``n_devices`` devices (default: all).

    ``ppn`` ranks per node defaults to ``n_devices // 2``: a 2x2 grid on a
    four-chip host, 2x4 on eight virtual CPU devices.
    """
    devices = jax.devices()
    n = len(devices) if n_devices is None else n_devices
    if not 1 <= n <= len(devices):
        raise ValueError(f"asked for {n} devices, {len(devices)} available")
    ppn = max(1, n // 2) if ppn is None else ppn
    if n % ppn:
        raise ValueError(f"{n} devices do not split into nodes of ppn={ppn}")
    return jax.make_mesh((n // ppn, ppn), ("node", "proc"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices[:n])


def make_smoke_mesh():
    """1x1 mesh for CPU smoke paths."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
