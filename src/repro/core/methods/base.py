"""Iteration-scheme abstraction for the ECG engine.

One ECG configuration = one :class:`MethodSpec` (the *scheme*: which
collectives fire per iteration and what the loop carry holds) bound to one
:class:`MethodContext` (the *plumbing*: the SpMBV operator, the reduction
closures, the splitting, the adaptive policy).  ``repro.core.ecg.
make_ecg_runner`` builds the context once and delegates the ``init``/``step``
closures to the spec — the guarded while-loop, convergence condition, and
result finalization stay method-agnostic in the driver.

Three schemes ship (see their modules for the per-iteration maths):

* :mod:`~repro.core.methods.classic`   — the paper's §3.1 two-psum form.
* :mod:`~repro.core.methods.pipelined` — same collectives, but the packed
  Gram reduction is data-independent of the next SpMBV (AZ recurrence), so
  the compiler overlaps it with the exchange.
* :mod:`~repro.core.methods.sstep`     — s SpMBV sweeps per collective
  *pair*: 2 psums per s iterations, with the pivoted rank-revealing
  factorization as the mandatory stability safeguard.

Every spec also carries its **collective accounting**
(:meth:`MethodSpec.psums_per_block` / :meth:`~MethodSpec.iters_per_block` /
:meth:`~MethodSpec.psum_payload_floats`): the synchronization term of the
tuner's cost model (``repro.tune.method_sync_cost``) and the lowered-HLO
gates in ``tests/dist_worker.py`` both read the *same* numbers, so the model
and the compiled collective structure cannot drift apart.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.observe import scopes


def _chol_inv_apply(g: jax.Array, *mats: jax.Array, eps: float = 0.0):
    """Given G = CᵀC, return [M C⁻¹ for M in mats] via triangular solves."""
    t = g.shape[0]
    with jax.named_scope(scopes.FACTOR):
        if eps:
            g = g + eps * jnp.eye(t, dtype=g.dtype)
        c = jnp.linalg.cholesky(g, upper=True)  # G = CᵀC with C upper-triangular
        outs = []
        for m in mats:
            # solve Y C = M  =>  Cᵀ Yᵀ = Mᵀ  (lower-triangular solve)
            y = jax.scipy.linalg.solve_triangular(c.T, m.T, lower=True).T
            outs.append(y)
    return outs


def _apply_vec(a_apply: Callable, v: jax.Array, t: int) -> jax.Array:
    """Apply the SpMBV operator to a single vector as a width-1 block.

    Used once, for the initial residual (Alg 3 line 1).  A width-1 SpMV costs
    t× fewer flops and bytes than the old formulation, which embedded v in a
    zero-padded (n, t) block and multiplied all t columns.
    """
    del t  # kept in the signature for call-site clarity; width is always 1
    return a_apply(v[:, None])[:, 0]


@dataclasses.dataclass(frozen=True)
class MethodContext:
    """Everything a :class:`MethodSpec` needs to build its loop closures.

    The reduction closures (``gram1``/``gram2``/``sqnorm``) already wrap
    their collective (identity single-shard, fused shard_map psum
    distributed); ``tail`` is the local X/R/Z update.  ``a_apply_masked``
    and ``use_mask`` carry the width-compacted exchange of the segmented
    solver; ``split_fn`` is T_{r,t}.  ``rank_rtol`` overrides the pivot
    threshold of method-mandated rank-revealing factorizations (s-step);
    None defers to the policy's threshold or the dtype default.

    ``precond`` is the preconditioner apply ``M⁻¹ₖ: (V, k) -> (n, t)`` (None
    = unpreconditioned); when set, schemes orthogonalize the preconditioned
    directions W = M⁻¹AP through ``gram2p`` — the 5-operand packed reduction
    ``[PᵀR | APᵀW | AP_oldᵀW]``, still exactly one psum, so each scheme's
    declared collective structure survives preconditioning.

    ``precond_reseed`` (classic only) reseeds the direction chain from the
    preconditioned residual every that-many iterations.  The classic chain
    ``Z' = W − Pd − P_old d_old`` never re-reads the residual, so an
    iteration-*varying* M⁻¹ₖ knocks it off the Krylov rails permanently —
    the truncated-flexible failure mode of Notay (SISC 22(4), 2000); the
    periodic reseed ``Z' = M⁻¹ₖR`` is the flexible restart that re-acquires
    the lost error components, and costs zero extra collectives (the next
    iteration's Gram/rank-revealing step absorbs the unorthogonalized
    seed).  The s-step scheme reseeds from the residual every block by
    construction and never needs it; pipelined cannot reseed at all (an
    in-loop SpMBV would be needed to rebuild the AZ recurrence).

    ``groups`` (classic only) is a :class:`~repro.adaptive.GroupSpec`
    describing a *packed* multi-RHS solve: ``t`` becomes the total width
    ``n_groups · t_each``, ``init`` takes (n, n_groups) operands, and each
    group converges against its own tolerance and retires (R and Z slabs
    zeroed) independently.  ``sqnorm_cols`` is the matching per-column
    squared-norm reduction ``(n, g) -> (g,)`` — it *replaces* the scalar
    ``sqnorm`` collective in group mode (one psum of g floats instead of
    one float), so the scheme's collective count is unchanged.
    """

    t: int
    s: int
    max_iters: int
    policy: object
    use_mask: bool
    chol_eps: float
    reorth: bool
    rank_rtol: float | None
    backend: str
    a_apply: Callable
    a_apply_masked: Callable | None
    split_fn: Callable
    gram1: Callable
    gram2: Callable
    sqnorm: Callable
    tail: Callable
    precond: Callable | None = None
    gram2p: Callable | None = None
    precond_reseed: int | None = None
    groups: object | None = None
    sqnorm_cols: Callable | None = None


class MethodSpec:
    """One iteration scheme: loop closures + collective accounting.

    Implementations override :meth:`build` (returning ``(init, step)``
    closures over a :class:`MethodContext`) and the accounting methods when
    they deviate from the classic 2-psums-per-iteration shape.
    ``overlaps_gram`` declares that the packed Gram reduction is issued
    data-independently of the SpMBV exchange (the pipelining invariant the
    HLO reachability gate asserts).
    """

    name: str = "?"
    overlaps_gram: bool = False

    # ------------------------------------------------------------ closures
    def validate(self, ctx: MethodContext) -> None:
        """Raise ``ValueError`` for context options this scheme cannot run."""
        if ctx.s != 1:
            raise ValueError(
                f"method {self.name!r} has no inner-step count; s={ctx.s} "
                "only applies to method 'sstep'"
            )
        if ctx.reorth:
            raise ValueError(
                "reorth (per-block Cholesky-QR2) only applies to method 'sstep'"
            )

    def build(self, ctx: MethodContext):
        """Return ``(init, step)``: ``init(b, x0) -> carry`` and one raw,
        unguarded ``step(carry) -> carry`` of this scheme."""
        raise NotImplementedError

    # ---------------------------------------------------------- accounting
    def iters_per_block(self, s: int = 1) -> int:
        """SpMBV sweeps amortized by one ``step`` call (s for s-step)."""
        return 1

    def psums_per_block(self, s: int = 1, reorth: bool = False) -> int:
        """Allreduce-shaped collectives one ``step`` call issues (the
        convergence-norm reduction is excluded — identical across schemes)."""
        return 2

    def psum_payload_floats(self, t: int, s: int = 1, reorth: bool = False) -> int:
        """Total floats those psums reduce (t² + 3t² for the classic shape)."""
        return 4 * t * t

    def collectives_per_iteration(self, s: int = 1, reorth: bool = False) -> float:
        """Psums per *effective* iteration — the number the tuner's
        synchronization term charges and the HLO gates assert."""
        return self.psums_per_block(s, reorth) / self.iters_per_block(s)
