"""Drive one whole benchmark run on the CPU at a small size, with the
harness's look for a chip skipped, and print the result line; optionally
with the timed path broken underneath (``--fault``):

* ``unchanged``: the solve hands back its initial state (x = x0 = 0);
* ``half``: half of the answer's rows are left out (left at x0);
* ``exchange``: the halo exchange between devices is left out (every
  ``ppermute`` of the distributed SpMBV returns its input);
* ``answer``: one entry of the answer is altered where it is produced.

    JAX_PLATFORMS=cpu python chipbench/tests/drive.py --devices 4 --fault exchange

Run it in a process of its own: it fixes JAX's device count and keeps the
compilation cache off.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def inject(fault: str) -> None:
    import jax
    import numpy as np

    from repro.solver import ECGSolver

    if fault == "exchange":
        import repro.sparse.spmbv as spmbv

        spmbv.jax = _JaxWithout(jax)
        return
    solve = ECGSolver.solve

    def broken(self, b, x0=None):
        res = solve(self, b, x0)
        x = np.array(self.unshard(res.x))
        if fault == "unchanged":
            x[:] = 0.0
        elif fault == "half":
            x[x.shape[0] // 2:] = 0.0
        elif fault == "answer":
            x[x.shape[0] // 3] += 1.0
        else:
            raise ValueError(f"unknown fault {fault!r}")
        res.x = x if self.mesh is None else self.op.shard_vector(x)
        return res

    ECGSolver.solve = broken


class _JaxWithout:
    """``jax`` as ``repro.sparse.spmbv`` sees it, with ``lax.ppermute``
    returning its input (no exchange between devices)."""

    def __init__(self, jax):
        self._jax = jax
        self.lax = _LaxWithout(jax.lax)

    def __getattr__(self, name):
        return getattr(self._jax, name)


class _LaxWithout:
    def __init__(self, lax):
        self._lax = lax

    def ppermute(self, x, axis_name, perm):
        return x

    def __getattr__(self, name):
        return getattr(self._lax, name)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--fault", default="")
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args(argv)
    if args.devices > 1:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count={args.devices}")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    from chipbench import harness
    from chipbench.tests.small import small_bench

    if args.fault:
        inject(args.fault)
    with tempfile.TemporaryDirectory() as tmp:
        root, cell = small_bench(Path(tmp), args.devices)
        result = harness.run_cell(
            harness.Bench(root), cell, seed=2**33 + 17, seconds=0.2,
            trace=bool(args.trace), t_start=time.perf_counter(),
            out_dir=Path(tmp) / "out", require_chip=False)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
