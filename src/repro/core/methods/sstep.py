"""s-step enlarged CG — two psums amortized over s SpMBV sweeps.

Each ``step`` (one *block* = s effective iterations) seeds an s-deep
monomial block-Krylov basis from the current split residual and
A-orthonormalizes the whole (n, s·t) candidate block at once, in the
residual-seeded MSDO/s-step shape of Moufawad's enlarged Krylov methods
(arXiv:1804.10629):

  per block —
    V  = [R, AR, …, A^{s−1}R],  AV = A·V      s SpMBVs           (p2p only)
    [VᵀAV | PᵀAV | P₂ᵀAV]                     fused gram1        (psum #1, 3(st)²)
    V −= P a + P₂ b ; AV −= AP a + AP₂ b      project vs prev two blocks
    G' = G − aᵀa − bᵀb                        (algebraic — no extra psum)
    P', AP' = rank-revealing A-orthonorm. of (V, AV)             (local)
    c  = P'ᵀR                                 gram1              (psum #2, st·t)
    X += P'c ; R −= AP'c

Seeding from the residual (rather than recurring the previous block's AP
through A-powers) is what keeps the two-block projection sufficient: each
block update is the *exact* A-norm projection of the error onto span(P'),
so the A-norm error decreases monotonically per block no matter how much
A-orthogonality to older blocks the monomial powers leak.  The projection
coefficients ride in psum #1 for free — PᵀAV = (AP)ᵀV = PᵀAV is a local
product against the carried AP blocks, packed into the same reduction as
the Gram matrix (and G' follows algebraically from PᵀAP = diag(act),
PᵀAP₂ = 0, so the projected Gram costs no second collective).

The mixed widths ((n, st) blocks against (n, t) residuals, an (st, t)
coefficient block) do not fit the fixed-shape Pallas gram/tail kernels, so
this scheme uses only the width-polymorphic ``gram1``/``sqnorm``
reductions plus inline jnp updates — the SpMBV itself keeps whatever
backend the operator was built with.

Stability: the monomial basis is intentionally communication-free and
correspondingly ill-conditioned (its condition number grows like κ(A)^s),
so the pivoted rank-revealing Cholesky of :mod:`repro.adaptive.rankrev` is
**mandatory** here — dependent candidate columns come out zero-masked
instead of poisoning the block.  ``reorth=True`` adds a per-block
Cholesky-QR2 second pass (one extra (st)² psum) for matrices where a
single pivoted factorization leaves too much A-orthogonality on the
table.

Adaptivity: a :class:`~repro.adaptive.ReductionPolicy` drops stagnant
*seed* columns (the t-wide mask is scored from the transposed coefficient
block, so a dropped residual direction stops spawning basis vectors), and
restart re-enlarges trivially — the seed is rebuilt from the residual
every block anyway, so plateau restarts just clear the mask and the
carried projection blocks.  ``k`` counts blocks; histories have one entry
per s effective iterations.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.adaptive.rankrev import rank_revealing_apply
from repro.adaptive.reduce import plateau_update, stagnation_mask
from repro.core.cg import EV_RECOVERY
from repro.core.methods.base import MethodContext, MethodSpec, _apply_vec
from repro.observe import scopes


class SStepMethod(MethodSpec):
    """s inner steps per collective pair, rank-revealing safeguarded."""

    name = "sstep"

    def validate(self, ctx: MethodContext) -> None:
        if ctx.s < 1:
            raise ValueError(f"s must be >= 1, got {ctx.s}")
        if ctx.chol_eps:
            raise ValueError(
                "method 'sstep' always factorizes through the pivoted "
                "rank-revealing Cholesky (the monomial basis demands it); "
                "chol_eps jitter does not apply — tune rank_rtol instead"
            )

    def iters_per_block(self, s: int = 1) -> int:
        return s

    def psums_per_block(self, s: int = 1, reorth: bool = False) -> int:
        return 3 if reorth else 2

    def psum_payload_floats(self, t: int, s: int = 1, reorth: bool = False) -> int:
        st = s * t
        payload = 3 * st * st + st * t  # fused gram1+projections, then c = PᵀR
        if reorth:
            payload += st * st  # Cholesky-QR2 second gram
        return payload

    def build(self, ctx: MethodContext):
        t, s = ctx.t, ctx.s
        st = s * t
        max_iters = ctx.max_iters
        policy = ctx.policy
        use_mask = ctx.use_mask
        reorth = ctx.reorth
        a_apply = ctx.a_apply
        a_apply_masked = ctx.a_apply_masked
        split_fn = ctx.split_fn
        gram1, sqnorm = ctx.gram1, ctx.sqnorm
        precond = ctx.precond
        # safeguard threshold: explicit override > policy's > dtype default
        rr_rtol = ctx.rank_rtol
        if rr_rtol is None and policy is not None:
            rr_rtol = policy.rank_rtol

        def iterate(carry):
            big_x, big_r = carry["X"], carry["R"]
            p1, ap1 = carry["P"], carry["AP"]      # previous block
            p2, ap2 = carry["Pp"], carry["APp"]    # block before that
            k, hist = carry["k"], carry["hist"]
            act_t = carry["act"] if policy is not None else None

            # residual-seeded monomial basis: s width-t SpMBVs, p2p exchange
            # only — no collective fires inside this sweep.  Preconditioned,
            # the basis is the M⁻¹A-Krylov sequence [M⁻¹R, (M⁻¹A)M⁻¹R, …]
            # with AV tracked exactly (avs[i] = A·vs[i] by construction), so
            # the A-orthonormalization below — including the MANDATORY
            # rank-revealing safeguard — is untouched: a preconditioned
            # monomial basis conditions *better*, but the pivoted Cholesky
            # still backstops whatever dependence survives.
            seed = big_r
            if policy is not None:
                seed = seed * act_t.astype(seed.dtype)[None, :]
            vs, avs = [], []
            cur = seed if precond is None else precond(seed, k)
            for _ in range(s):
                if use_mask:
                    nxt = a_apply_masked(cur, act_t)  # A zero-col ⇒ zero-col
                else:
                    nxt = a_apply(cur)
                vs.append(cur)
                avs.append(nxt)
                cur = nxt if precond is None else precond(nxt, k)
            v = jnp.concatenate(vs, axis=1)    # (n, st)
            av = jnp.concatenate(avs, axis=1)  # = A·V

            # psum #1: Gram and both projection coefficient blocks fused in
            # one (3st, st) reduction — [VᵀAV ; PᵀAV ; P₂ᵀAV]
            big1 = gram1(jnp.concatenate([v, p1, p2], axis=1), av)
            g = big1[:st]
            a1 = big1[st:2 * st]   # = PᵀAV  (A-projection onto previous block)
            a2 = big1[2 * st:]     # = P₂ᵀAV
            v = v - p1 @ a1 - p2 @ a2
            av = av - ap1 @ a1 - ap2 @ a2
            # projected Gram, algebraically: PᵀAP = diag(act), PᵀAP₂ = 0,
            # and the dead rows of a1/a2 are already zero
            g = g - a1.T @ a1 - a2.T @ a2

            # mandatory safeguard: pivoted rank-revealing A-orthonormalization
            (p, ap), _rank, _active_st = rank_revealing_apply(g, v, av, rtol=rr_rtol)
            # telemetry: live candidate columns = s per live seed column (a
            # dead seed spawns only zero basis vectors); fewer accepted
            # pivots means the safeguard just absorbed a rank loss of the
            # monomial basis — the breakdown-recovery event this scheme's
            # mandatory factorization exists for
            live = s * (jnp.sum(act_t).astype(jnp.int32) if policy is not None
                        else jnp.int32(t))
            recovered = _rank < live
            if reorth:
                # Cholesky-QR2 second pass: one extra (st)² psum per block
                g2 = gram1(p, ap)
                (p, ap), _rank2, _act2 = rank_revealing_apply(g2, p, ap, rtol=rr_rtol)
                recovered = recovered | (_rank2 < _rank)

            c = gram1(p, big_r)  # psum #2: (st, t) coefficient block = PᵀR
            # exact A-norm error projection onto span(P): monotone per block
            # (inline here: s-step has no tail closure to carry the scope)
            with jax.named_scope(scopes.UPDATE):
                big_x = big_x + p @ c
                big_r = big_r - ap @ c

            rsum = big_r.sum(axis=1)
            rn = jnp.sqrt(sqnorm(rsum))
            hist = hist.at[k + 1].set(rn)  # k counts blocks (s iterations each)
            out = dict(
                X=big_x, R=big_r, P=p, AP=ap, Pp=p1, APp=ap1,
                k=k + 1, rn=rn, hist=hist, bd=carry["bd"],
                evhist=carry["evhist"].at[k + 1].set(
                    jnp.where(recovered, EV_RECOVERY, 0)
                ),
            )
            if policy is not None:
                # seed-level stagnation: score residual column l by its
                # coefficient column c[:, l] (rows of cᵀ), mask at width t
                act_t = stagnation_mask(c.T, carry["rn"], act_t, policy)
                n_active = jnp.sum(act_t).astype(jnp.int32)
                best_rn, since = plateau_update(
                    rn, carry["best_rn"], carry["since"], policy
                )
                restarts = carry["restarts"]
                if policy.restart:
                    # re-enlarge: the seed is rebuilt from the residual every
                    # block, so a restart just clears the mask and the carried
                    # projection blocks
                    do_rs = (since >= policy.plateau_window) & (n_active < t)
                    for key in ("P", "AP", "Pp", "APp"):
                        out[key] = jnp.where(do_rs, jnp.zeros_like(out[key]), out[key])
                    act_t = jnp.where(do_rs, jnp.ones_like(act_t), act_t)
                    n_active = jnp.where(do_rs, jnp.int32(t), n_active)
                    since = jnp.where(do_rs, 0, since)
                    best_rn = jnp.where(do_rs, rn, best_rn)
                    restarts = restarts + do_rs.astype(jnp.int32)
                out.update(
                    act=act_t, best_rn=best_rn, since=since, restarts=restarts,
                    ahist=carry["ahist"].at[k + 1].set(n_active),
                )
            return out

        def init(b, x0):
            n = b.shape[0]
            dtype = b.dtype
            r0 = b - _apply_vec(a_apply, x0, t)
            big_r0 = split_fn(r0, t)
            rn0 = jnp.sqrt(sqnorm(r0))
            hist0 = jnp.full((max_iters + 1,), jnp.nan, dtype=dtype).at[0].set(rn0)
            zeros_nst = jnp.zeros((n, st), dtype)
            carry = dict(X=jnp.zeros((n, t), dtype), R=big_r0,
                         P=zeros_nst, AP=zeros_nst, Pp=zeros_nst, APp=zeros_nst,
                         k=jnp.int32(0), rn=rn0, hist=hist0,
                         bd=~jnp.isfinite(rn0),
                         evhist=jnp.full((max_iters + 1,), -1,
                                         jnp.int32).at[0].set(0))
            if policy is not None:
                carry.update(
                    act=jnp.ones((t,), bool),
                    best_rn=rn0,
                    since=jnp.int32(0),
                    restarts=jnp.int32(0),
                    ahist=jnp.full((max_iters + 1,), -1, jnp.int32).at[0].set(t),
                )
            return carry

        return init, iterate
