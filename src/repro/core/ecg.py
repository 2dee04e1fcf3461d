"""Enlarged Conjugate Gradients (paper Algorithms 1–3).

Communication-efficient Grigori–Tissot form:

  per iteration —
    AZ   = A * Z                          SpMBV             (p2p comm)
    G    = ZᵀAZ                           block inner prod  (allreduce #1, t²)
    CᵀC  = chol(G)                        local Cholesky
    P    = Z C⁻¹ ;  AP = AZ C⁻¹           local TRSMs (AP reuses AZ — no 2nd SpMBV)
    c    = PᵀR ; d = APᵀAP ; d_old = AP_oldᵀAP
                                          fused block inner prods (allreduce #2, 3t²)
    X   += P c ;  R -= AP c
    Z    = AP − P d − P_old d_old

Exactly two allreduce-shaped collectives per iteration, matching §3.1.  The
``allreduce`` argument is identity for a single-shard run and a ``psum`` for
the shard_map-distributed run, so the same iteration body serves both — and
the fusion of the second reduction (c, d, d_old packed in one buffer) is
structural, not cosmetic.

The per-iteration maths above is the *classic* scheme — one of three
pluggable iteration schemes (:mod:`repro.core.methods`): ``pipelined``
overlaps the packed Gram reduction with the SpMBV exchange via an AZ
recurrence, and ``sstep`` amortizes both psums over s SpMBV sweeps with a
rank-revealing safeguard.  This module is the method-agnostic driver.

Two layers live here:

* :func:`make_ecg_runner` — builds the pure iteration machinery once (an
  :class:`ECGRunner` with ``init``/``step``/``run``), all jit-traceable.
  This is what :class:`repro.solver.ECGSolver` compiles exactly once per
  width and reuses across right-hand sides, and what the ``t="auto"``
  probes drive step-by-step for early stopping.
* :func:`ecg_solve` — the legacy one-shot functional spelling (resolve
  config, build a runner, run it, wrap a :class:`SolveResult`).  New code
  should build a :class:`repro.solver.ECGSolver` handle instead; the
  handle amortizes setup and compilation over many solves.

Backend switch: ``backend="jnp"`` (default) runs the iteration body on plain
XLA ops; ``backend="pallas"`` routes the two per-iteration hot spots that the
paper's performance model singles out through the Pallas kernel suite —
``kernels/fused_gram`` for the packed [PᵀR | APᵀAP | AP_oldᵀAP] product (one
HBM pass over P/R/AP/AP_old instead of three GEMM passes) and
``kernels/block_update.ecg_tail`` for the X/R/Z tail (one pass over P/AP
instead of two).  On non-TPU platforms the kernel ops dispatch to their
pure-jnp oracles, so the switch is always safe to flip; the SpMBV itself is
owned by the caller via ``a_apply``.

Adaptivity (:mod:`repro.adaptive`): a ``ReductionPolicy`` replaces the bare
Cholesky with a pivoted, rank-revealing factorization so a singular Gram
matrix drops the dependent directions (zero-masked columns, static shapes)
instead of poisoning the solve with NaNs; the flexible-ECG stagnation
criterion additionally retires stagnant directions, with an optional
plateau re-enlarge/restart.  Every solve is breakdown-guarded: a non-finite
iterate freezes the state at the last finite iteration and sets
``SolveResult.breakdown``.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp

from repro.adaptive.reduce import resolve_policy
from repro.core.cg import SolveResult, _guarded_while
from repro.core.enlarging import split_residual
from repro.core.methods import MethodContext, get_method
from repro.core.methods.base import _apply_vec, _chol_inv_apply  # noqa: F401  (back-compat re-exports)
from repro.kernels.block_update.ops import ecg_tail
from repro.kernels.fused_gram.ops import fused_gram
from repro.observe.scopes import CHECK, GRAM, SPMBV, UPDATE, scoped


class _ConstArgJit:
    """``jax.jit(fn)`` with the device arrays ``fn``'s closures hold passed
    to the compiled program as arguments.

    ``jax.jit`` embeds closed-over arrays in the program as constants; the
    solve loop closes over the whole operator (Block-ELL tiles, exchange
    plans, preconditioner factors), which at deployment size is gigabytes
    of HLO literal to hash, fold and compile.  One trace per argument shape
    finds those arrays; the jitted program then takes them as inputs.
    """

    def __init__(self, fn):
        self._fn = fn
        self._entries: dict = {}

    def _entry(self, args):
        leaves, tree = jax.tree.flatten(args)
        key = (tree, tuple((tuple(x.shape), np.dtype(x.dtype)) for x in leaves))
        entry = self._entries.get(key)
        if entry is None:
            closed, out_shape = jax.make_jaxpr(self._fn, return_shape=True)(*args)
            out_tree = jax.tree.structure(out_shape)
            lift = [isinstance(c, jax.Array) for c in closed.consts]

            def run(lifted, *a):
                it = iter(lifted)
                consts = [next(it) if f else c for c, f in zip(closed.consts, lift)]
                out = jax.core.eval_jaxpr(closed.jaxpr, consts, *jax.tree.leaves(a))
                return jax.tree.unflatten(out_tree, out)

            lifted = [c for c, f in zip(closed.consts, lift) if f]
            entry = self._entries[key] = (jax.jit(run), lifted)
        return entry

    def __call__(self, *args):
        fn, lifted = self._entry(args)
        return fn(lifted, *args)

    def lower(self, *args):
        fn, lifted = self._entry(args)
        return fn.lower(lifted, *args)


def jit_solve(go):
    """Compile a solve program: operator arrays as arguments, and float32
    matmuls at full precision (the TPU default rounds them to bfloat16,
    which the Gram/Cholesky steps of ECG cannot absorb)."""

    def traced(*args):
        with jax.default_matmul_precision("highest"):
            return go(*args)

    return _ConstArgJit(traced)


@dataclasses.dataclass(frozen=True)
class ECGRunner:
    """The compiled-once iteration machinery of one ECG configuration.

    ``init(b, x0) -> carry`` builds the initial loop carry (initial residual
    SpMV, splitting, norm); ``step(carry) -> carry`` is one raw, unguarded
    iteration of Algorithm 3 (used by the ``t="auto"`` probes to drive the
    loop one iteration at a time); ``run(carry) -> carry`` is the
    breakdown-guarded ``lax.while_loop`` to convergence (or to a width-exit
    event).  All three are pure and jit-traceable — the solver handle wraps
    ``lambda b, x0: run(init(b, x0))`` in one ``jax.jit`` and reuses it for
    every right-hand side, which is what makes ``solve_many`` retrace-free.
    """

    t: int
    tol: float
    max_iters: int
    policy: object
    use_mask: bool
    init: Callable
    step: Callable
    run: Callable
    method: str = "classic"
    s: int = 1


def make_ecg_runner(
    a_apply: Callable[[jax.Array], jax.Array],
    t: int,
    *,
    tol: float = 1e-8,
    max_iters: int = 1000,
    mapping: str = "contiguous",
    allreduce: Callable[[jax.Array], jax.Array] = lambda x: x,
    split: Callable[[jax.Array, int], jax.Array] | None = None,
    chol_eps: float = 0.0,
    gram1: Callable | None = None,
    gram2: Callable | None = None,
    sqnorm: Callable | None = None,
    tail: Callable | None = None,
    backend: str = "jnp",
    policy: object = None,
    a_apply_masked: Callable | None = None,
    exit_below_width: int | None = None,
    method: str = "classic",
    s: int = 1,
    reorth: bool = False,
    rank_rtol: float | None = None,
    precond: Callable | None = None,
    gram2p: Callable | None = None,
    precond_reseed: int | None = None,
    groups: object = None,
    sqnorm_cols: Callable | None = None,
) -> ECGRunner:
    """Build the ECG iteration machinery for one fixed configuration.

    Arguments mirror :func:`ecg_solve` (which is implemented on top of this)
    except that ``t`` must already be an int and ``policy`` an already
    resolved :class:`~repro.adaptive.ReductionPolicy` (or None).  See the
    module docstring of :mod:`repro.core.ecg` for the iteration body and
    :func:`ecg_solve` for the meaning of each hook.

    ``method`` selects the iteration scheme ("classic" | "pipelined" |
    "sstep" — see :mod:`repro.core.methods`); ``s``/``reorth``/``rank_rtol``
    parameterize the s-step scheme (inner-step count, per-block
    Cholesky-QR2 second pass, safeguard pivot threshold).  This driver owns
    only the reduction-closure defaults, the convergence condition, and the
    breakdown-guarded while-loop; the per-iteration maths lives in the
    method spec.

    ``precond`` is the preconditioner apply ``(V, k) -> M⁻¹ₖ V`` (see
    :mod:`repro.precondition`); ``gram2p`` the matching 5-operand packed
    reduction ``[PᵀR | APᵀW | AP_oldᵀW]`` (defaulted here sequentially, one
    psum distributed) the preconditioned recurrence needs in place of the
    symmetric ``gram2`` payload.

    ``groups`` (a :class:`~repro.adaptive.GroupSpec`, classic only) turns
    the runner into a *packed* multi-RHS program: ``t`` is the total width
    ``n_groups · t_each``, ``init`` takes (n, n_groups) operands, each group
    converges against its own tolerance and retires independently, and the
    loop runs while any group is live.  ``sqnorm_cols`` is the per-column
    squared-norm reduction ``(n, g) -> (g,)`` that replaces the scalar
    ``sqnorm`` collective in group mode (identity-wrapped local sum by
    default; one psum of g floats distributed).
    """
    if policy is not None and chol_eps:
        raise ValueError(
            "chol_eps regularization and adaptive= are mutually exclusive: the "
            "rank-revealing factorization handles near-singular G structurally "
            "(tune ReductionPolicy.rank_rtol instead of eps-jitter)"
        )
    if backend not in ("jnp", "pallas"):
        raise ValueError(f"unknown backend {backend!r}")
    if not isinstance(s, int) or s < 1:
        raise ValueError(f"s must be an int >= 1, got {s!r}")
    spec = get_method(method)

    # The fixed-shape Pallas gram/tail kernels assume the classic (t, 3t)
    # packed layout; s-step reduces mixed widths ((st, t+2st) packed, (n, st)
    # blocks), so its default reductions always go through the
    # width-polymorphic jnp path regardless of ``backend`` — the SpMBV keeps
    # whatever backend the operator was built with.
    kernel_backend = backend if spec.name != "sstep" else "jnp"
    if gram2p is None:
        # preconditioned packed reduction: [PᵀR | APᵀW | AP_oldᵀW] — three
        # asymmetric products the fused_gram kernel cannot express (its
        # middle term is the symmetric APᵀAP), concatenated locally so the
        # payload still rides ONE psum (the tail kernel is reused unchanged;
        # the W correction is a single (n, t) add after it)
        gram2p = lambda p, r, ap, apo, w: allreduce(
            jnp.concatenate([p.T @ r, ap.T @ w, apo.T @ w], axis=1)
        )
    if gram1 is None:
        gram1 = lambda z, az: allreduce(z.T @ az)
    if gram2 is None:
        if kernel_backend == "pallas":
            gram2 = lambda p, r, ap, apo: allreduce(fused_gram(p, r, ap, apo))
        else:
            gram2 = lambda p, r, ap, apo: allreduce(
                jnp.concatenate([p.T @ r, ap.T @ ap, apo.T @ ap], axis=1)
            )
    if sqnorm is None:
        sqnorm = lambda v: allreduce(jnp.asarray([[v @ v]], v.dtype))[0, 0]
    if tail is None:
        if kernel_backend == "pallas":
            tail = ecg_tail
        else:
            tail = lambda x, r, p, ap, po, c, d, do: (
                x + p @ c, r - ap @ c, ap - p @ d - po @ do
            )
    split_fn = split if split is not None else (
        lambda r_, t_: split_residual(r_, t_, mapping)
    )
    if groups is not None:
        if spec.name != "classic":
            raise ValueError(
                f"packed group solves require method 'classic', got {spec.name!r}"
            )
        if groups.width != t:
            raise ValueError(
                f"groups describe width {groups.width} "
                f"({groups.n_groups}×{groups.t_each}) but t={t}"
            )
        if policy is None:
            raise ValueError(
                "packed group solves require a rank-revealing policy "
                "(adaptive='rankrev' at minimum): retirement zeroes Z "
                "columns, so the Gram matrix is structurally singular from "
                "the first retirement on, and the direction budget is "
                "enforced through the pivoted factorization's column mask"
            )
        if policy.restart:
            raise ValueError(
                "packed group solves cannot run a restart policy: the "
                "re-enlarge rebuilds the splitting from the summed residual, "
                "which would mix request boundaries"
            )
        if sqnorm_cols is None:
            sqnorm_cols = lambda m: jnp.sum(m * m, axis=0)
    use_mask = a_apply_masked is not None and policy is not None

    # every scheme builds its iteration from these closures, so each stage
    # is named once here (repro.observe.scopes)
    ctx = MethodContext(
        t=t, s=s, max_iters=max_iters, policy=policy, use_mask=use_mask,
        chol_eps=chol_eps, reorth=reorth, rank_rtol=rank_rtol,
        backend=backend, a_apply=scoped(SPMBV, a_apply),
        a_apply_masked=scoped(SPMBV, a_apply_masked), split_fn=split_fn,
        gram1=scoped(GRAM, gram1), gram2=scoped(GRAM, gram2),
        sqnorm=scoped(CHECK, sqnorm), tail=scoped(UPDATE, tail), precond=precond,
        gram2p=scoped(GRAM, gram2p), precond_reseed=precond_reseed,
        groups=groups, sqnorm_cols=scoped(CHECK, sqnorm_cols),
    )
    spec.validate(ctx)
    init, iterate = spec.build(ctx)

    def cond(c):
        if groups is None:
            go = (c["rn"] > tol) & (c["k"] < max_iters)
        else:
            # packed solve: run while ANY request is live — each group's own
            # tolerance already gated its retirement inside the iteration
            go = jnp.any(c["grp_live"]) & (c["k"] < max_iters)
        if exit_below_width is not None and use_mask:
            # width-reduction event: hand control back so the caller can
            # re-slice the exchange plan at the shrunken width and resume
            go = go & (jnp.sum(c["act"]) >= exit_below_width)
        return go

    def run(carry):
        return _guarded_while(cond, iterate, carry)

    return ECGRunner(
        t=t, tol=tol, max_iters=max_iters, policy=policy, use_mask=use_mask,
        init=init, step=iterate, run=run, method=spec.name, s=s,
    )


def finalize_result(
    out: dict,
    *,
    x0,
    t: int,
    tol: float,
    policy: object = None,
    selection: object = None,
) -> SolveResult:
    """Convert a final loop carry into a :class:`SolveResult` (host syncs)."""
    x = x0 + out["X"].sum(axis=1)  # line 14: x = Σᵢ (X)ᵢ
    breakdown = bool(out["bd"])
    return SolveResult(
        x=x,
        n_iters=int(out["k"]),
        res_hist=out["hist"],
        converged=bool(out["rn"] <= tol) and not breakdown,
        breakdown=breakdown,
        t=t,
        active_hist=out["ahist"] if policy is not None else None,
        restarts=int(out["restarts"]) if policy is not None else 0,
        selection=selection,
        event_hist=out.get("evhist"),
        final_carry=out,
    )


def _ecg_solve(
    a_apply: Callable[[jax.Array], jax.Array],
    b: jax.Array,
    t: int | str,
    x0: jax.Array | None = None,
    tol: float = 1e-8,
    max_iters: int = 1000,
    mapping: str = "contiguous",
    allreduce: Callable[[jax.Array], jax.Array] = lambda x: x,
    split: Callable[[jax.Array, int], jax.Array] | None = None,
    chol_eps: float = 0.0,
    gram1: Callable | None = None,
    gram2: Callable | None = None,
    sqnorm: Callable | None = None,
    tail: Callable | None = None,
    backend: str = "jnp",
    tuned: object | None = None,
    adaptive: object = None,
    matrix: object = None,
    select: object = None,
    t_candidates: tuple = (1, 2, 4, 8, 16),
    machine: object = None,
    a_apply_masked: Callable | None = None,
    exit_below_width: int | None = None,
    resume_state: dict | None = None,
    method: str = "classic",
    s: int = 1,
    reorth: bool = False,
    rank_rtol: float | None = None,
    precond: Callable | None = None,
    gram2p: Callable | None = None,
    precond_reseed: int | None = None,
) -> SolveResult:
    """One-shot functional ECG solve (the engine behind :func:`ecg_solve`).

    Internal — callers inside ``repro.*`` use this (or a runner / the
    :class:`repro.solver.ECGSolver` handle) so that only genuinely external
    code goes through the deprecated public spelling.
    """
    selection = select
    if isinstance(t, str):
        from repro.adaptive.select_t import resolve_auto_t

        t, selection, adaptive = resolve_auto_t(
            t, adaptive, a=matrix, b=b, select=select,
            candidates=t_candidates, tol=tol, machine=machine, backend=backend,
        )
    policy = resolve_policy(adaptive)
    if tuned is not None:
        backend = getattr(tuned, "backend", backend)

    runner = make_ecg_runner(
        a_apply, t, tol=tol, max_iters=max_iters, mapping=mapping,
        allreduce=allreduce, split=split, chol_eps=chol_eps, gram1=gram1,
        gram2=gram2, sqnorm=sqnorm, tail=tail, backend=backend, policy=policy,
        a_apply_masked=a_apply_masked, exit_below_width=exit_below_width,
        method=method, s=s, reorth=reorth, rank_rtol=rank_rtol,
        precond=precond, gram2p=gram2p, precond_reseed=precond_reseed,
    )
    # Run the whole program (init + guarded loop) under one jit — the same
    # compiled shape the ECGSolver handle caches, so the one-shot legacy
    # spelling and a handle solve are bit-identical by construction.
    x0 = jnp.zeros_like(b) if x0 is None else x0
    if resume_state is not None:
        # continue a width-segmented solve from the carried loop state
        out = jit_solve(runner.run)(dict(resume_state))
    else:
        out = jit_solve(lambda b_, x0_: runner.run(runner.init(b_, x0_)))(b, x0)
    return finalize_result(
        out, x0=x0, t=t, tol=tol, policy=policy, selection=selection
    )


def ecg_solve(a_apply, b, t, *args, **kwargs) -> SolveResult:
    """Solve A x = b with ECG using enlarging factor ``t``.

    .. deprecated::
        ``ecg_solve`` is the legacy one-shot spelling: it re-derives the
        whole configuration and re-traces the solve loop on every call.
        Build a :class:`repro.solver.ECGSolver` handle instead —
        ``ECGSolver.build(a, config=SolverConfig(t=4)).solve(b)`` — which
        pays setup and compilation once and solves many right-hand sides
        without retracing.

    a_apply:   SpMBV — maps (n, t) block vectors to (n, t) block vectors
               (applied column-wise to A).  For the distributed solver this is
               the node-aware halo-exchange SpMBV.
    t:         enlarging factor, or ``"auto"`` to pick one from the
               iterations-vs-cost model (needs ``matrix=`` — the CSRMatrix
               behind ``a_apply`` — or a precomputed ``select=`` TSelection;
               ``t_candidates``/``machine`` parameterize the model).
    allreduce: reduction applied to every *local* t x t (or packed t x 3t)
               gram product; identity when running single-shard.
    gram1:     (Z, AZ) -> ZᵀAZ, globally reduced     (allreduce #1, t²)
    gram2:     (P, R, AP, AP_old) -> [PᵀR | APᵀAP | AP_oldᵀAP] packed and
               globally reduced in ONE collective     (allreduce #2, 3t²)
    sqnorm:    v -> globally-reduced vᵀv.
    The defaults compute local products wrapped in ``allreduce``; the
    distributed solver substitutes fused shard_map psums so the lowered HLO
    carries exactly two collectives per iteration (paper §3.1).
    split:     optional override of T_{r,t} (e.g. distributed splitting).
    tail:      (X, R, P, AP, P_old, c, d, d_old) -> (X, R, Z) — the local
               block-vector updates; defaults per ``backend``.
    backend:   "jnp" | "pallas" — see module docstring.
    tuned:     optional :class:`repro.tune.TunedConfig` (duck-typed, so core
               stays import-cycle-free): adopts its ``backend``.
    adaptive:  None/"off" (exact historical behavior), "rankrev" (breakdown-
               safe rank-revealing factorization, drop dependent directions),
               "reduce" (+ flexible-ECG stagnation drops),
               "reduce+restart" (+ re-enlarge on plateau), or a
               :class:`repro.adaptive.ReductionPolicy`.

    Width-segmented execution (used by the width-aware distributed solver —
    see :class:`repro.solver.ECGSolver`): ``a_apply_masked`` is an
    ``(V, active_mask) -> W`` operator that may exploit the (t,) bool mask
    of live directions (e.g. compact the halo-exchange payload to the
    active columns); when given (and a policy is on) it replaces ``a_apply``
    inside the loop and the mask is carried across iterations.
    ``exit_below_width`` additionally terminates the while-loop as soon as
    the active width falls below it — the caller then re-slices its
    operator at the shrunken width and *resumes* by passing
    ``SolveResult.final_carry`` back in as ``resume_state`` (all counters,
    histories, and block vectors continue; the maths is identical to the
    monolithic loop because only the exchange payload changes).
    """
    warnings.warn(
        "ecg_solve() is the legacy one-shot spelling; build a "
        "repro.solver.ECGSolver handle (compile-once / solve-many, typed "
        "SolverConfig) instead",
        DeprecationWarning,
        stacklevel=2,
    )
    return _ecg_solve(a_apply, b, t, *args, **kwargs)


@dataclasses.dataclass(frozen=True)
class ECGOperationCounts:
    """Per-iteration flop/communication counts of Algorithm 3 (used by the
    performance model, eq. 3.3)."""

    n: int
    nnz: int
    p: int
    t: int

    @property
    def spmbv_flops(self) -> float:  # 2·t·nnz/p
        return 2 * self.t * self.nnz / self.p

    @property
    def gram_flops(self) -> float:  # ZᵀAZ: 2·(n/p)·t² … counted as n/p·t² per Alg 3
        return self.n / self.p * self.t**2

    @property
    def fused_gram_flops(self) -> float:  # c,d,d_old: 3 products
        return 3 * self.n / self.p * self.t**2

    @property
    def cholesky_flops(self) -> float:  # (1/6)t³ (+ ~(1/2)t² triangular work)
        return self.t**3 / 6 + self.t**2 / 2

    @property
    def trsm_flops(self) -> float:  # two TRSMs with n/p rhs rows: 2·(n/p)·t²
        return 2 * self.n / self.p * self.t**2

    @property
    def update_flops(self) -> float:  # X += Pc, R -= APc, Z = AP − Pd − P_old d_old
        return (2 + 2) * self.n / self.p * self.t + 4 * self.n / self.p * self.t**2

    @property
    def total_flops(self) -> float:
        """Paper eq. (3.3): γ-weighted flop count per iteration."""
        return (
            (2 + 2 * self.t) * self.nnz / self.p
            + (4 * self.t + 4 * self.t**2) * self.n / self.p
            + self.t**2 / 2
            + self.t**3 / 6
        )

    @property
    def allreduce_payload_floats(self) -> tuple[int, int]:
        """(t², 3t²) — the two fused reductions of §3.1."""
        return (self.t**2, 3 * self.t**2)
