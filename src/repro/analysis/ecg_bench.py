"""Measured ECG hot-path benchmarks: kernel-vs-oracle and overlap-vs-blocking.

Shared by ``benchmarks/kernel_sweep.py`` (CSV, 8 forced host devices) and
``repro.launch.perf --ecg`` (JSON).  Two families:

* :func:`overlap_vs_blocking_sweep` — distributed SpMBV wall time over
  strategies x t x backend x {blocking, overlap}, so the comm-hiding win of
  the interior/boundary schedule is *measured*, not asserted.  On CPU hosts
  the ppermute rounds are memcpys, so overlap speedups are modest; on a real
  TPU mesh the interior compute hides actual ICI latency.
* :func:`kernel_vs_oracle` — local hot-spot formulations head to head:
  Block-ELL SpMBV (Pallas kernel on TPU, jnp oracle elsewhere) vs the
  scalar-gather CSR baseline, and the fused gram / fused tail vs their
  unfused counterparts.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

STRATEGIES = ("standard", "2step", "3step", "optimal")


def _timeit(fn, *args, repeats: int = 3) -> float:
    """Median wall microseconds per call (after one warmup/compile call).

    Delegates to the shared :func:`repro.observe.timed_median_us` timer —
    the measurement discipline is identical across every benchmark, and an
    installed ambient tracer sees each timed call as a ``timed/*`` span.
    """
    from repro.observe import get_tracer, timed_median_us

    return timed_median_us(fn, *args, repeats=repeats, label="ecg_bench",
                           tracer=get_tracer())


def overlap_vs_blocking_sweep(
    a,
    mesh,
    ts=(4, 8),
    strategies=STRATEGIES,
    backends=("jnp", "pallas"),
    repeats: int = 5,
    machine=None,
    ell_block: int = 8,
    seed: int = 0,
):
    """Distributed SpMBV timings; returns rows of dicts (name/us/derived).

    ``seed`` fixes the operand RNG and ``repeats`` the median-of-k timing so
    host-mode numbers are reproducible run-to-run.
    """
    from repro.sparse.spmbv import _make_distributed_spmbv

    rng = np.random.default_rng(seed)
    rows = []
    for strategy in strategies:
        for t in ts:
            big_v = rng.standard_normal((a.shape[0], t))
            for backend in backends:
                base_us = None
                for overlap in (False, True):
                    op = _make_distributed_spmbv(
                        a, mesh, strategy, t=t, machine=machine,
                        backend=backend, overlap=overlap, ell_block=ell_block,
                    )
                    f = jax.jit(op.matvec_fn())
                    v = op.shard_vector(big_v)
                    us = _timeit(f, v, repeats=repeats)
                    if overlap:
                        derived = f"speedup_vs_blocking={base_us / us:.2f}"
                    else:
                        base_us = us
                        derived = f"halo={op.plan.halo_size}"
                    mode = "overlap" if overlap else "blocking"
                    rows.append(dict(
                        name=f"spmbv/{strategy}_t{t}_{backend}_{mode}",
                        us=us, derived=derived,
                    ))
    return rows


def kernel_vs_oracle(ts=(2, 4, 8), repeats: int = 5, elements=(16, 16), block: int = 16,
                     seed: int = 2):
    """Local hot-spot timings on the current default backend (fixed ``seed``
    + median-of-``repeats`` for run-to-run reproducibility)."""
    from repro.sparse import dg_laplace_2d, csr_spmbv, csr_to_bsr
    from repro.kernels import bsr_spmbv, bsr_to_block_ell, fused_gram, ecg_tail

    a = dg_laplace_2d(elements, block=block, dtype=jnp.float32)
    blocks, idx = bsr_to_block_ell(csr_to_bsr(a, block, block))
    rng = np.random.default_rng(seed)
    rows = []
    for t in ts:
        v = jnp.asarray(rng.standard_normal((a.shape[0], t)), jnp.float32)
        us_csr = _timeit(jax.jit(lambda vv: csr_spmbv(a, vv)), v, repeats=repeats)
        us_ell = _timeit(jax.jit(lambda vv: bsr_spmbv(blocks, idx, vv)), v, repeats=repeats)
        rows.append(dict(name=f"kernel/csr_spmbv_t{t}", us=us_csr, derived=f"nnz={a.nnz}"))
        rows.append(dict(
            name=f"kernel/block_ell_spmbv_t{t}", us=us_ell,
            derived=f"csr/ell={us_csr / us_ell:.2f}",
        ))

        n_loc = 32768
        mats = [jnp.asarray(rng.standard_normal((n_loc, t)), jnp.float32) for _ in range(4)]
        us_fused = _timeit(jax.jit(lambda *m: fused_gram(*m)), *mats, repeats=repeats)
        us_sep = _timeit(
            jax.jit(lambda p, r, ap, apo: (p.T @ r, ap.T @ ap, apo.T @ ap)),
            *mats, repeats=repeats,
        )
        rows.append(dict(
            name=f"kernel/fused_gram_t{t}", us=us_fused,
            derived=f"unfused/fused={us_sep / us_fused:.2f}",
        ))

        x, r, p, ap, po = (
            jnp.asarray(rng.standard_normal((n_loc, t)), jnp.float32) for _ in range(5)
        )
        c, d, do = (jnp.asarray(rng.standard_normal((t, t)), jnp.float32) for _ in range(3))
        us_tail = _timeit(
            jax.jit(lambda *args: ecg_tail(*args)), x, r, p, ap, po, c, d, do,
            repeats=repeats,
        )
        us_unf = _timeit(
            jax.jit(lambda x, r, p, ap, po, c, d, do: (
                x + p @ c, r - ap @ c, ap - p @ d - po @ do
            )),
            x, r, p, ap, po, c, d, do, repeats=repeats,
        )
        rows.append(dict(
            name=f"kernel/ecg_tail_t{t}", us=us_tail,
            derived=f"unfused/fused={us_unf / us_tail:.2f}",
        ))
    return rows
