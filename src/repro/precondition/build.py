"""Build the preconditioner apply callable for a solver handle.

Both builders return ``precond(V, k) -> M⁻¹ₖ V`` (or ``None`` for
``kind="none"``): V is the (n, t) block in the handle's vector layout
(padded per-rank slots distributed), k the traced iteration index — only
the inexact kind reads it.  All kinds are columnwise-linear with a zero
fixed point for fixed k, so zero-masked columns stay zero and the adaptive
width controller composes with every preconditioner unchanged.

Collective accounting (what keeps the two-psum invariant intact):

* block-Jacobi — rank-local batched triangular solves, **zero** extra
  communication of any kind;
* Chebyshev / inexact — extra *SpMBV* applications (p2p halo exchange
  only); no psum is ever issued by a preconditioner apply.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.precondition.block_jacobi import (
    extract_blocks,
    factor_blocks,
    rank_slot_layout,
    slot_layout,
)
from repro.precondition.chebyshev import (
    distributed_power_matvec,
    make_chebyshev_apply,
    resolve_bounds,
)
from repro.precondition.config import PreconditionConfig
from repro.precondition.inexact import extract_diagonal, make_inexact_apply


def _block_apply(factors, n_rows: int, block: int):
    """Sequential block-Jacobi apply over the plain 0..n-1 row layout."""
    from repro.kernels import block_trisolve

    nb = factors.shape[0]
    n_slots = nb * block
    factors = jnp.asarray(factors)

    def apply(x, k):
        del k
        xp = jnp.pad(x, ((0, n_slots - n_rows), (0, 0)))
        y = block_trisolve(factors.astype(x.dtype), xp.reshape(nb, block, -1))
        return y.reshape(n_slots, -1)[:n_rows]

    return apply


def build_sequential_preconditioner(a, cfg: PreconditionConfig, a_apply):
    """Preconditioner for the single-device handle (``None`` when inactive).

    a_apply: the handle's (n, t) → (n, t) SpMBV — Chebyshev/inexact applies
    compose it, so they run whatever backend the operator was built with.
    """
    if not cfg.active:
        return None
    n = a.shape[0]
    if cfg.kind == "block_jacobi":
        row_of_slot, _ = slot_layout(n, cfg.block)
        factors = factor_blocks(extract_blocks(a, row_of_slot, cfg.block))
        return _block_apply(factors, n, cfg.block)
    if cfg.kind == "chebyshev":
        # λmax power iteration through the vectorized CSR SpMV (the
        # default matvec of estimate_lambda_max) — never a host row loop
        lmin, lmax = resolve_bounds(a, cfg)
        cheb = make_chebyshev_apply(a_apply, lmin, lmax, cfg.degree)
        return lambda x, k: cheb(x)
    # inexact
    diag = extract_diagonal(a)
    return make_inexact_apply(a_apply, diag, cfg.omega, cfg.sweeps)


def build_distributed_preconditioner(a, cfg: PreconditionConfig, op, mesh, a_apply):
    """Preconditioner for the distributed handle (``None`` when inactive).

    Block-Jacobi blocks are carved inside each rank's padded slot range
    (identity on padding slots, blocks never straddle ranks) and applied
    under ``shard_map`` — the solve stays free of preconditioner
    collectives.  Chebyshev/inexact compose the global distributed SpMBV.
    """
    if not cfg.active:
        return None
    if cfg.kind == "chebyshev":
        # λmax power iteration runs *distributed*: width-1 SpMBV sub-plan,
        # p2p halo exchange only — no densified operator on any host, and
        # zero all-reduces (pinned in tests/dist_worker.py)
        lmin, lmax = resolve_bounds(a, cfg, matvec=distributed_power_matvec(op))
        cheb = make_chebyshev_apply(a_apply, lmin, lmax, cfg.degree)
        return lambda x, k: cheb(x)
    if cfg.kind == "inexact":
        diag = extract_diagonal(a, row_of_slot=op.true_row_of_slot())
        return make_inexact_apply(a_apply, diag, cfg.omega, cfg.sweeps)

    # block_jacobi: per-rank factors, shard_map'd local batched solves
    import jax
    from jax import shard_map
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.kernels import block_trisolve

    block = cfg.block
    p, rmax = op.p, op.rmax
    rmax_pad = -(-rmax // block) * block
    nb_rank = rmax_pad // block
    row_of_slot = rank_slot_layout(op.true_row_of_slot(), p, block)
    factors_np = factor_blocks(extract_blocks(a, row_of_slot, block))
    # (p * nb_rank, bs, bs), sharded so each rank holds its own factors —
    # device_put happens here, at build time, never inside a trace
    factors = jax.device_put(
        jnp.asarray(factors_np),
        NamedSharding(mesh, P(("node", "proc"), None, None)),
    )

    def local_solve(l, v):  # v: (rmax, t) local block rows
        vp = jnp.pad(v, ((0, rmax_pad - rmax), (0, 0)))
        y = block_trisolve(l.astype(v.dtype), vp.reshape(nb_rank, block, -1))
        return y.reshape(rmax_pad, -1)[:rmax]

    smapped = shard_map(
        local_solve,
        mesh=mesh,
        in_specs=(P(("node", "proc"), None, None), op.vec_spec),
        out_specs=op.vec_spec,
        check_vma=False,
    )
    return lambda x, k: smapped(factors, x)
