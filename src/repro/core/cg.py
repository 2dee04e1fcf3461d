"""Classical conjugate gradients — the paper's baseline method.

Plain CG *is* enlarged CG at t=1 (the splitting is the identity, the block
recurrences collapse to the scalar ones), so the standalone while-loop this
module used to carry is gone: :func:`_cg_solve` runs the classic method of
the pluggable ECG engine at width 1 and inherits its breakdown guard.  Only
:class:`SolveResult` (the result type every solver returns) and
:func:`_guarded_while` (the breakdown-guarded loop the engine drives) live
here.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Callable

import jax
import jax.numpy as jnp

from repro.observe import scopes

#: ``SolveResult.event_hist`` bitmask values.
EV_RECOVERY = 1  # rank-revealing factorization dropped live directions
EV_RESEED = 2    # flexible restart reseeded Z from the preconditioned residual

#: event-bit -> human-readable code name (the ``iter_trace`` spelling)
EVENT_NAMES = {EV_RECOVERY: "recovery", EV_RESEED: "reseed"}


@dataclasses.dataclass
class SolveResult:
    x: jax.Array
    n_iters: int
    res_hist: jax.Array  # (max_iters + 1,), padded with NaN past convergence
    converged: bool
    # --- breakdown / adaptive metadata (defaults keep old call sites valid)
    breakdown: bool = False          # a non-finite iterate was produced; the
    #                                  state (x, residual norm) froze at the
    #                                  last finite iteration instead of NaNs
    t: int | None = None             # enlarging factor used (ECG; via t="auto")
    active_hist: jax.Array | None = None  # (max_iters + 1,) active block width
    #                                  per iteration — the reduction trace
    #                                  (adaptive ECG only, -1 past the end)
    restarts: int = 0                # re-enlarge events (adaptive ECG)
    selection: object = None         # TSelection when t was chosen by "auto"
    comm_segments: list | None = None  # [(exchange width, iterations)] per
    #                                  width segment of the re-sliced solve
    #                                  (width-aware distributed ECG only)
    event_hist: jax.Array | None = None  # (max_iters + 1,) int32 event bitmask
    #                                  per iteration: EV_RECOVERY (the
    #                                  rank-revealing factorization dropped
    #                                  live directions — an in-flight
    #                                  breakdown recovery), EV_RESEED (the
    #                                  flexible restart reseeded the chain
    #                                  from the preconditioned residual).
    #                                  -1 past the recorded end; None when no
    #                                  tracked mechanism was active.
    pack: dict | None = None         # width-packing telemetry when this
    #                                  result came out of a packed multi-RHS
    #                                  solve (repro.serve width packing):
    #                                  total width, group layout, this
    #                                  request's group index/tolerance,
    #                                  retirement iteration, total packed
    #                                  iterations — None for solo solves
    final_carry: dict | None = dataclasses.field(default=None, repr=False)
    #                                ^ loop carry at exit — the resume handle
    #                                  the segmented solver threads between
    #                                  width segments

    def __iter__(self):  # convenient unpacking (historical 4-tuple)
        return iter((self.x, self.n_iters, self.res_hist, self.converged))

    def reduction_events(self) -> list[tuple[int, int, int]]:
        """[(iteration, width_before, width_after)] from the reduction trace
        — every iteration where the active block width changed.

        Scans the *full valid* trace (every entry >= 0) rather than slicing
        at ``n_iters``: the trace is -1-padded past the last recorded
        iteration, so the valid prefix **is** the recorded history and the
        events cannot depend on ``n_iters`` bookkeeping staying in lockstep
        with the history writes — in particular a width drop recorded on
        the final iteration (including a capped ``max_iters``-th one) is
        always reported.
        """
        if self.active_hist is None:
            return []
        import numpy as np

        h = np.asarray(self.active_hist).tolist()
        return [
            (k, h[k - 1], h[k])
            for k in range(1, len(h))
            if h[k] >= 0 and h[k - 1] >= 0 and h[k] != h[k - 1]
        ]

    def _event_iters(self, bit: int) -> list[int]:
        """Iterations whose event-bitmask entry carries ``bit`` (valid
        entries only — the trace is -1-padded past the recorded end, same
        full-valid-prefix convention as :meth:`reduction_events`)."""
        if self.event_hist is None:
            return []
        import numpy as np

        h = np.asarray(self.event_hist).tolist()
        return [k for k in range(len(h)) if h[k] >= 0 and int(h[k]) & bit]

    def recovery_events(self) -> list[int]:
        """Iterations where the rank-revealing factorization dropped live
        directions — the breakdown-recovery trace.  Classic/pipelined record
        a drop of the entering active width; s-step records every block
        whose mandatory safeguard rejected candidate basis columns (the
        monomial basis losing rank is the event the safeguard exists for)."""
        return self._event_iters(EV_RECOVERY)

    def reseed_events(self) -> list[int]:
        """Iterations where the flexible restart reseeded the direction
        chain from the preconditioned residual (classic + an
        iteration-varying preconditioner, every ``reseed``-th iteration)."""
        return self._event_iters(EV_RESEED)

    @property
    def n_recoveries(self) -> int:
        return len(self.recovery_events())

    @property
    def n_reseeds(self) -> int:
        return len(self.reseed_events())

    def iter_trace(self) -> list[dict]:
        """Structured per-iteration view over the recorded histories.

        One dict per *recorded* iteration ``k`` (including iteration 0,
        the initial residual)::

            dict(k, resnorm, active, events)

        ``resnorm`` is the residual norm, ``active`` the active block
        width (None when no reduction trace was recorded), ``events`` a
        tuple of event code names (``"recovery"`` / ``"reseed"``; empty
        when none fired or no mechanism was tracked).

        The valid prefix is the leading run of finite ``res_hist``
        entries: the history is NaN-padded past convergence — and, for a
        request out of a packed multi-RHS solve, past its *retirement*
        — so the rows stop exactly where this request's recorded history
        does, not at the shared loop's last iteration.  This is the
        tracer's solve-segment source (``repro.observe``).
        """
        import numpy as np

        hist = np.asarray(self.res_hist, np.float64)
        finite = np.isfinite(hist)
        end = int(np.argmin(finite)) if not finite.all() else hist.size
        act = (
            None if self.active_hist is None
            else np.asarray(self.active_hist).tolist()
        )
        ev = (
            None if self.event_hist is None
            else np.asarray(self.event_hist).tolist()
        )
        rows = []
        for k in range(end):
            events = ()
            if ev is not None and k < len(ev) and ev[k] > 0:
                events = tuple(
                    name for bit, name in sorted(EVENT_NAMES.items())
                    if int(ev[k]) & bit
                )
            active = None
            if act is not None and k < len(act) and act[k] >= 0:
                active = int(act[k])
            rows.append(dict(
                k=k, resnorm=float(hist[k]), active=active, events=events,
            ))
        return rows


def _guarded_while(cond_extra, body_fn, init: dict):
    """``lax.while_loop`` with a breakdown guard.

    ``body_fn`` computes the next carry; if it produces a non-finite residual
    norm (singular Gram matrix, zero curvature, ...), the previous — last
    finite — carry is kept and the ``bd`` flag is raised, terminating the
    loop.  The returned state is therefore always finite, and callers report
    ``breakdown=True`` with the last finite residual instead of NaN garbage.
    """

    def cond(carry):
        with jax.named_scope(scopes.CHECK):
            return (~carry["bd"]) & cond_extra(carry)

    def body(carry):
        new = body_fn(carry)
        with jax.named_scope(scopes.CHECK):
            ok = jnp.isfinite(new["rn"])
            merged = jax.tree_util.tree_map(
                lambda old, cur: jnp.where(ok, cur, old), carry, new
            )
            merged["bd"] = carry["bd"] | ~ok
        return merged

    init = dict(init, bd=~jnp.isfinite(init["rn"]))
    return jax.lax.while_loop(cond, body, init)


def _cg_solve(
    a_apply: Callable[[jax.Array], jax.Array],
    b: jax.Array,
    x0: jax.Array | None = None,
    tol: float = 1e-8,
    max_iters: int = 1000,
) -> SolveResult:
    """Plain CG = the classic ECG method at t=1 (internal spelling).

    ``a_apply`` is the (possibly distributed) *vector* SpMV — it is adapted
    to the engine's width-1 block shape here.  The t=1 Gram matrix is the
    1×1 curvature pᵀAp, so the engine's breakdown guard subsumes the old
    zero-curvature guard.
    """
    from repro.core.ecg import _ecg_solve  # lazy: ecg imports this module

    res = _ecg_solve(
        lambda v_block: a_apply(v_block[:, 0])[:, None],
        b, 1, x0=x0, tol=tol, max_iters=max_iters,
    )
    return dataclasses.replace(res, t=None)  # plain CG has no enlarging factor


def cg_solve(
    a_apply: Callable[[jax.Array], jax.Array],
    b: jax.Array,
    x0: jax.Array | None = None,
    tol: float = 1e-8,
    max_iters: int = 1000,
) -> SolveResult:
    """Solve A x = b with CG. ``a_apply`` is the (possibly distributed) SpMV.

    .. deprecated::
        Plain CG is enlarged CG at t=1; use the engine directly — a
        :class:`repro.solver.ECGSolver` handle with ``SolverConfig(t=1)``
        (compile-once / solve-many), or this one-shot shim.
    """
    warnings.warn(
        "cg_solve() now runs the classic ECG method at t=1; build a "
        "repro.solver.ECGSolver handle with SolverConfig(t=1) instead",
        DeprecationWarning,
        stacklevel=2,
    )
    return _cg_solve(a_apply, b, x0=x0, tol=tol, max_iters=max_iters)
