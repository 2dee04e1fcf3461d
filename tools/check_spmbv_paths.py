"""The SpMBV kernel's two paths against each other at full size.

    PYTHONPATH=src python tools/check_spmbv_paths.py

On a TPU it builds random Block-ELL operators at the DG cell's shape
(81,920 block rows, gather reach 256) and at the audikw_1 surrogate's
(59,049 block rows, a ragged last lane group, reach 4,047), 16x16 tiles,
kmax 5, t = 8, float32, and checks that ``bsr_spmbv_pallas`` (the vreg
path: ``v_rows``, the gather in grid order, ``_vreg_kernel``) gives W bit
for bit equal to the lane path (``_gather_lanes`` and ``_product`` with
``vregs=False``) and close to ``bsr_spmbv_ref``.  It prints the wall time
per call (calls queued back to back) of each path and of the vreg path's
three parts.  On the CPU it runs the same checks at a small size in
interpret mode.

Exit code 0 when every case is bit-equal and within 1e-5 of the reference.
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bsr_spmbv import kernel as K
from repro.kernels.bsr_spmbv.ref import bsr_spmbv_ref

ON_CPU = jax.default_backend() == "cpu"
BC, KMAX, T = 16, 5, 8
#: (name, block rows, reach of the block-column ids)
CASES = ([("dg", 300, 20), ("surrogate", 200, 50)] if ON_CPU else
         [("dg", 81920, 256), ("surrogate", 59049, 4047)])


def operator(nbr: int, reach: int, seed: int):
    """Random lane-major tiles (padded block rows' tiles zero), ids within
    ``reach`` of the diagonal, and a block vector."""
    rng = np.random.default_rng(seed)
    nbg = -(-nbr // K.LANES)
    flat = rng.standard_normal((nbg * K.LANES, KMAX * BC, BC), dtype=np.float32)
    flat[nbr:] = 0
    blocks = flat.reshape(nbg, K.LANES, KMAX * BC, BC).transpose(0, 2, 3, 1)
    near = np.arange(nbr)[:, None] + rng.integers(-reach, reach + 1, (nbr, KMAX))
    idx = np.clip(near, 0, nbr - 1).astype(np.int32)
    v = rng.standard_normal((nbr * BC, T), dtype=np.float32)
    return jnp.asarray(np.ascontiguousarray(blocks)), jnp.asarray(idx), jnp.asarray(v)


def ms_per_call(f, *args, n: int = 3 if ON_CPU else 30) -> float:
    """Wall time per call of ``f`` over ``n`` calls queued back to back."""
    f = jax.jit(f)
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    jax.block_until_ready([f(*args) for _ in range(n)])
    return (time.perf_counter() - t0) / n * 1e3


def main() -> int:
    ok = True
    for name, nbr, reach in CASES:
        blocks, idx, v = operator(nbr, reach, seed=7)
        nbg = blocks.shape[0]
        kw = dict(bc=BC, nbg=nbg, interpret=ON_CPU)

        def vreg(b, i, v):
            return K.bsr_spmbv_pallas(b, i, v, interpret=ON_CPU)

        def lane(b, i, v):
            vg = K._gather_lanes(v, i, vregs=False, **kw)
            return K._product(b, vg, nbr=nbr, vregs=False, interpret=ON_CPU)

        def rows(v):
            return K._v_rows(v.T, ON_CPU)

        def gather(v, i):
            return K._gather_lanes(v, i, vregs=True, **kw)

        def product(b, vg):
            return K._product(b, vg, nbr=nbr, vregs=True, interpret=ON_CPU)

        new = jax.jit(vreg)(blocks, idx, v)
        old = jax.jit(lane)(blocks, idx, v)
        same = bool(jnp.array_equal(new, old))
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(bsr_spmbv_ref)(blocks, idx, v)
        err = float(jnp.max(jnp.abs(new - ref)) / jnp.max(jnp.abs(ref)))
        ok &= same and err < 1e-5
        vg = jax.jit(gather)(v, idx)
        print(f"{name}: nbr {nbr}, bit-equal {same}, max rel err vs ref {err:.3e}; "
              f"ms per call: vreg path {ms_per_call(vreg, blocks, idx, v):.4f}, "
              f"lane path {ms_per_call(lane, blocks, idx, v):.4f}, "
              f"v_rows {ms_per_call(rows, v):.4f}, "
              f"gather with v_rows {ms_per_call(gather, v, idx):.4f}, "
              f"vreg kernel {ms_per_call(product, blocks, vg):.4f}", flush=True)
    print("ALL BIT-EQUAL" if ok else "MISMATCH")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
