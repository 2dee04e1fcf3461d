"""Breakdown-safe, rank-revealing Gram factorization (pivoted Cholesky).

ECG A-orthonormalizes the t search directions through ``G = ZᵀAZ = CᵀC``
every iteration.  When the columns of Z become (near-)linearly dependent —
a right-hand side that is zero on a subdomain, t larger than the number of
independent residual components, or directions that converged individually —
G is singular and the bare Cholesky propagates NaNs through the whole solve.

The fix, following the flexible/enlarged-CG literature (Moufawad 2023) and
the s-step stability analysis (Moufawad 2018), is structural: factorize G
with *diagonal pivoting* so the numerical rank is revealed, and keep the
block shape (n, t) with the dependent directions zero-masked.  Downstream
products (the packed gram reductions, the Pallas ``fused_gram``/``ecg_tail``
kernels, the two psums of §3.1) are untouched — a zero column contributes
zeros everywhere.

Everything here is jit-compatible with static shapes: t is tiny (≤ 16), so
the factorization is an O(t) ``fori_loop`` of O(t²) vector ops.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.observe import scopes


def default_rank_rtol(dtype) -> float:
    """Relative pivot threshold: diagonal entries below ``rtol · max(diag G)``
    are treated as numerically dependent directions.  Scaled well above the
    unit roundoff because G's entries already carry O(n) accumulated rounding
    from the gram product."""
    eps = float(jnp.finfo(dtype).eps)
    return eps ** (2.0 / 3.0)  # ~3.6e-11 (f64), ~2.4e-5 (f32)


def pivoted_cholesky(g: jax.Array, rtol: float | None = None):
    """Diagonally pivoted Cholesky of a PSD t x t matrix.

    Returns ``(l, perm, rank)`` with ``G[perm][:, perm] ≈ L·Lᵀ``, L lower
    triangular, and only the first ``rank`` columns of L nonzero.  Pivots are
    chosen greedily as the largest remaining diagonal entry, so once a pivot
    falls below ``rtol · max(diag G)`` all later ones do too — the dependent
    directions are exactly the trailing ``t − rank`` columns.
    """
    t = g.shape[0]
    if rtol is None:
        rtol = default_rank_rtol(g.dtype)
    idx = jnp.arange(t)
    thresh = rtol * jnp.maximum(jnp.max(jnp.diag(g)), jnp.asarray(0.0, g.dtype))

    def step(k, carry):
        a, l, perm, rank = carry
        # pivot: largest remaining diagonal entry (rows/cols >= k)
        d = jnp.where(idx >= k, jnp.diag(a), -jnp.inf)
        j = jnp.argmax(d)
        sw = idx.at[k].set(j).at[j].set(k)  # transposition k <-> j
        a = a[sw][:, sw]
        l = l[sw]
        perm = perm[sw]
        pivot = a[k, k]
        ok = pivot > thresh
        root = jnp.sqrt(jnp.where(ok, pivot, 1.0))
        col = jnp.where(idx > k, a[:, k] / root, 0.0).at[k].set(root)
        col = jnp.where(ok, col, 0.0)  # dependent direction: zero column
        l = l.at[:, k].set(col)
        a = a - jnp.outer(col, col)  # Schur complement update
        return a, l, perm, rank + ok.astype(jnp.int32)

    l0 = jnp.zeros_like(g)
    _, l, perm, rank = jax.lax.fori_loop(
        0, t, step, (g, l0, idx, jnp.int32(0))
    )
    return l, perm, rank


def rank_revealing_apply(g: jax.Array, *mats: jax.Array, rtol: float | None = None):
    """Breakdown-safe replacement for ``[M C⁻¹ for M in mats]``.

    Factorizes ``G[perm][:, perm] = L·Lᵀ`` by :func:`pivoted_cholesky` and
    returns ``(outs, rank, active)`` where ``outs[i] = mats[i][:, perm]·L⁻ᵀ``
    with the ``t − rank`` dependent columns zeroed, ``active`` is the
    (t,)-bool column mask (the first ``rank`` columns), and the outputs keep
    the full (n, t) shape.  The active columns of ``Z[:, perm]·L⁻ᵀ`` are
    A-orthonormal; column order follows the pivot order, which is immaterial
    to the solver (P and AP are permuted identically within one iteration,
    and no cross-iteration column identification is assumed anywhere).
    """
    t = g.shape[0]
    with jax.named_scope(scopes.FACTOR):
        l, perm, rank = pivoted_cholesky(g, rtol=rtol)
        active = jnp.arange(t) < rank
        # unit-ize the dead columns so the triangular solve is nonsingular;
        # their solution rows are garbage and are masked out below.
        l_solve = l + jnp.diag(jnp.where(active, 0.0, 1.0).astype(l.dtype))
        colmask = active.astype(l.dtype)[None, :]
        outs = []
        for m in mats:
            mp = m[:, perm]
            # solve Y·Lᵀ = M_p row-wise  =>  L·Yᵀ = M_pᵀ (lower-triangular solve)
            y = jax.scipy.linalg.solve_triangular(l_solve, mp.T, lower=True).T
            outs.append(y * colmask)
    return outs, rank, active
