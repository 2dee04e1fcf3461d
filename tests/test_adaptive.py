"""repro.adaptive: rank-revealing factorization, breakdown guards, dynamic
width reduction, and automatic t selection."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.adaptive import (
    ReductionPolicy,
    TSelection,
    default_rank_rtol,
    pivoted_cholesky,
    rank_revealing_apply,
    resolve_policy,
    select_t,
)
from repro.core import cg_solve, ecg_solve, split_rank
from repro.core.ecg import _chol_inv_apply
from repro.sparse import fd_laplace_2d, csr_spmbv, csr_spmv
from repro.sparse.csr import CSRMatrix


@pytest.fixture(scope="module")
def system():
    a = fd_laplace_2d(16)  # 256 rows
    b = np.random.default_rng(0).standard_normal(a.shape[0])
    return a, b


def deficient_rhs(n: int, t: int, m: int, seed: int = 0) -> np.ndarray:
    """RHS supported on only the first m of t contiguous subdomains, so
    split_residual produces t − m exactly-zero (dependent) columns."""
    b = np.zeros(n)
    lo = 0
    hi = (m * n) // t  # first m contiguous subdomains of subdomain_map_contiguous
    b[lo:hi] = np.random.default_rng(seed).standard_normal(hi - lo)
    return b


def as_dtype(a: CSRMatrix, b: np.ndarray, dtype):
    return (
        dataclasses.replace(a, data=a.data.astype(dtype)),
        jnp.asarray(b, dtype),
    )


class TestPivotedCholesky:
    def test_full_rank_reconstructs(self):
        rng = np.random.default_rng(1)
        f = rng.standard_normal((8, 8))
        g = jnp.asarray(f @ f.T + 8 * np.eye(8))
        l, perm, rank = pivoted_cholesky(g)
        assert int(rank) == 8
        gp = np.asarray(g)[np.asarray(perm)][:, np.asarray(perm)]
        np.testing.assert_allclose(np.asarray(l @ l.T), gp, atol=1e-10)

    @pytest.mark.parametrize("r", [1, 3, 6])
    def test_detects_numerical_rank(self, r):
        rng = np.random.default_rng(2)
        f = rng.standard_normal((8, r))
        g = jnp.asarray(f @ f.T)
        l, perm, rank = pivoted_cholesky(g)
        assert int(rank) == r
        # dependent directions are exactly the trailing zero columns
        assert np.allclose(np.asarray(l)[:, r:], 0.0)
        gp = np.asarray(g)[np.asarray(perm)][:, np.asarray(perm)]
        np.testing.assert_allclose(np.asarray(l @ l.T), gp, atol=1e-9)

    def test_f32_threshold_scales_with_dtype(self):
        assert default_rank_rtol(jnp.float32) > 100 * default_rank_rtol(jnp.float64)
        rng = np.random.default_rng(3)
        f = rng.standard_normal((6, 4)).astype(np.float32)
        g = jnp.asarray(f @ f.T)
        _, _, rank = pivoted_cholesky(g)
        assert int(rank) == 4

    def test_apply_a_orthonormalizes_active_block(self, system):
        """PᵀAP = I on the active columns, 0 on the masked ones — the
        breakdown-safe analogue of TestAOrthonormalization."""
        a, _ = system
        rng = np.random.default_rng(4)
        z_ind = rng.standard_normal((a.shape[0], 3))
        z = jnp.asarray(np.hstack([z_ind, z_ind[:, :2] @ [[1.0], [2.0]]]))  # col 3 dependent
        az = csr_spmbv(a, z)
        g = z.T @ az
        (p, ap), rank, active = rank_revealing_apply(g, z, az)
        assert int(rank) == 3
        assert np.asarray(active).sum() == 3
        ptap = np.asarray(p.T @ csr_spmbv(a, p))
        np.testing.assert_allclose(ptap[:3, :3], np.eye(3), atol=1e-8)
        assert np.allclose(ptap[3:], 0.0) and np.allclose(np.asarray(p)[:, 3:], 0.0)
        np.testing.assert_allclose(np.asarray(ap), np.asarray(csr_spmbv(a, p)), atol=1e-8)

    def test_matches_plain_cholesky_span_when_full_rank(self, system):
        a, _ = system
        rng = np.random.default_rng(5)
        z = jnp.asarray(rng.standard_normal((a.shape[0], 5)))
        az = csr_spmbv(a, z)
        g = z.T @ az
        p_ref, _ = _chol_inv_apply(g, z, az)
        (p, _), rank, _ = rank_revealing_apply(g, z, az)
        assert int(rank) == 5
        # same A-orthonormal span (columns may be permuted/rotated)
        ptap = np.asarray(p.T @ csr_spmbv(a, p))
        np.testing.assert_allclose(ptap, np.eye(5), atol=1e-8)
        # both bases span the same subspace
        q_ref, _ = np.linalg.qr(np.asarray(p_ref))
        resid = np.asarray(p) - q_ref @ (q_ref.T @ np.asarray(p))
        assert np.abs(resid).max() < 1e-8


class TestBreakdownGuard:
    @pytest.mark.parametrize("t", [4, 8])
    def test_fixed_ecg_reports_breakdown(self, system, t):
        a, _ = system
        b = deficient_rhs(a.shape[0], t, m=t // 2)
        res = ecg_solve(lambda V: csr_spmbv(a, V), jnp.asarray(b), t=t,
                        tol=1e-9, max_iters=500)
        assert res.breakdown and not res.converged
        # state froze at the last finite iterate — no NaN garbage escapes
        assert bool(jnp.isfinite(res.x).all())
        assert np.isfinite(np.asarray(res.res_hist)[res.n_iters])

    def test_cg_zero_curvature_breakdown(self):
        # singular diagonal matrix, b in the nullspace: p·Ap = 0 on step 1
        n = 4
        diag = jnp.asarray([1.0, 1.0, 1.0, 0.0])
        a = CSRMatrix(
            indptr=jnp.arange(n + 1, dtype=jnp.int32),
            indices=jnp.arange(n, dtype=jnp.int32),
            data=diag,
            shape=(n, n),
        )
        b = jnp.asarray([0.0, 0.0, 0.0, 1.0])
        res = cg_solve(lambda v: csr_spmv(a, v), b, tol=1e-10, max_iters=50)
        assert res.breakdown and not res.converged
        assert bool(jnp.isfinite(res.x).all())

    def test_healthy_solves_keep_flag_clear(self, system):
        a, b = system
        res = ecg_solve(lambda V: csr_spmbv(a, V), jnp.asarray(b), t=4,
                        tol=1e-9, max_iters=2000)
        assert res.converged and not res.breakdown
        res_cg = cg_solve(lambda v: csr_spmv(a, v), jnp.asarray(b), tol=1e-9,
                          max_iters=2000)
        assert res_cg.converged and not res_cg.breakdown


class TestAdaptiveReduction:
    @pytest.mark.parametrize("t", [2, 4, 8])
    @pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
    def test_converges_where_fixed_breaks_down(self, system, t, dtype):
        a, _ = system
        m = max(t // 2, 1)
        b = deficient_rhs(a.shape[0], t, m=m)
        a_d, b_d = as_dtype(a, b, dtype)
        tol = 1e-9 if dtype == jnp.float64 else 2e-4
        fixed = ecg_solve(lambda V: csr_spmbv(a_d, V), b_d, t=t, tol=tol, max_iters=1500)
        assert fixed.breakdown
        res = ecg_solve(lambda V: csr_spmbv(a_d, V), b_d, t=t, tol=tol,
                        max_iters=1500, adaptive="reduce")
        assert res.converged and not res.breakdown
        ad = np.asarray(a.todense(), np.float64)
        relres = np.linalg.norm(ad @ np.asarray(res.x, np.float64) - b) / np.linalg.norm(b)
        assert relres < (1e-7 if dtype == jnp.float64 else 1e-2)
        # the dependent directions were dropped on the first iteration, down
        # to exactly the rank of the initial splitting
        assert int(split_rank(jnp.asarray(b), t)) == m
        ah = np.asarray(res.active_hist)
        assert ah[0] == t and ah[1] == m
        assert res.reduction_events()[0] == (1, t, m)

    def test_duplicated_rhs_blocks(self, system):
        """An exactly-duplicated splitting (rank 1) must degrade to CG."""
        a, b = system
        dup = lambda r, t_: jnp.tile(r[:, None], (1, t_)) / t_
        fixed = ecg_solve(lambda V: csr_spmbv(a, V), jnp.asarray(b), t=4,
                          tol=1e-9, max_iters=1500, split=dup)
        assert fixed.breakdown
        res = ecg_solve(lambda V: csr_spmbv(a, V), jnp.asarray(b), t=4,
                        tol=1e-9, max_iters=1500, split=dup, adaptive="reduce")
        assert res.converged
        assert int(np.asarray(res.active_hist)[1]) == 1
        cg = cg_solve(lambda v: csr_spmv(a, v), jnp.asarray(b), tol=1e-9, max_iters=1500)
        assert abs(res.n_iters - cg.n_iters) <= 2

    def test_no_spurious_drops_on_full_rank(self, system):
        a, b = system
        plain = ecg_solve(lambda V: csr_spmbv(a, V), jnp.asarray(b), t=4,
                          tol=1e-9, max_iters=2000)
        res = ecg_solve(lambda V: csr_spmbv(a, V), jnp.asarray(b), t=4,
                        tol=1e-9, max_iters=2000, adaptive="reduce")
        assert res.converged
        assert res.n_iters <= plain.n_iters + 2
        ah = np.asarray(res.active_hist)[: res.n_iters + 1]
        assert ah[0] == 4

    def test_policy_objects_and_errors(self):
        assert resolve_policy(None) is None and resolve_policy("off") is None
        pol = resolve_policy("reduce+restart")
        assert isinstance(pol, ReductionPolicy) and pol.restart
        custom = ReductionPolicy(min_t=2, drop_tol=1e-3)
        assert resolve_policy(custom) is custom
        with pytest.raises(ValueError):
            resolve_policy("bogus")
        with pytest.raises(TypeError):
            resolve_policy(3)

    def test_chol_eps_conflicts_with_adaptive(self, system):
        a, b = system
        with pytest.raises(ValueError, match="chol_eps"):
            ecg_solve(lambda V: csr_spmbv(a, V), jnp.asarray(b), t=4,
                      chol_eps=1e-10, adaptive="reduce")

    def test_explicit_off_honored_under_auto(self, system):
        """t='auto' defaults to rankrev, but an explicit adaptive='off' must
        keep the historical bare-Cholesky body (no trace recorded)."""
        a, b = system
        res = ecg_solve(lambda V: csr_spmbv(a, V), jnp.asarray(b), t="auto",
                        matrix=a, tol=1e-8, max_iters=2000, adaptive="off")
        assert res.converged and res.active_hist is None

    def test_restart_policy_smoke(self, system):
        a, _ = system
        b = deficient_rhs(a.shape[0], 4, m=2)
        res = ecg_solve(lambda V: csr_spmbv(a, V), jnp.asarray(b), t=4,
                        tol=1e-9, max_iters=1500,
                        adaptive=ReductionPolicy(restart=True, plateau_window=10))
        assert res.converged and res.restarts >= 0


class TestSelectT:
    def test_select_t_table_and_argmin(self, system):
        a, b = system
        sel = select_t(a, b, candidates=(1, 2, 4, 8), tol=1e-8)
        assert isinstance(sel, TSelection)
        assert sel.t in (1, 2, 4, 8)
        assert set(sel.table) == {1, 2, 4, 8}
        costs = {t: row["total_cost_s"] for t, row in sel.table.items()}
        assert sel.t == min(costs, key=costs.get)
        for row in sel.table.values():
            assert row["est_iters"] >= 1 and row["iter_cost_s"] > 0
        assert "chosen" in sel.summary()

    def test_distributed_cost_shifts_choice_upward(self, system):
        """Under a communication-dominated machine model the per-iteration
        cost is latency-bound, so larger t (fewer iterations) should never
        lose to t=1 by much — the paper's central trade-off."""
        a, b = system
        seq = select_t(a, b, candidates=(1, 8), tol=1e-8, n_nodes=1, ppn=1)
        dist = select_t(a, b, candidates=(1, 8), tol=1e-8, n_nodes=2, ppn=4)
        ratio_seq = seq.table[8]["iter_cost_s"] / seq.table[1]["iter_cost_s"]
        ratio_dist = dist.table[8]["iter_cost_s"] / dist.table[1]["iter_cost_s"]
        # communication amortizes the width: relative cost of t=8 shrinks
        assert ratio_dist < ratio_seq

    def test_ecg_solve_auto(self, system):
        a, b = system
        res = ecg_solve(lambda V: csr_spmbv(a, V), jnp.asarray(b), t="auto",
                        matrix=a, tol=1e-8, max_iters=2000)
        assert res.converged
        assert res.t in (1, 2, 4, 8, 16)
        assert isinstance(res.selection, TSelection)
        # auto-t implies breakdown safety (rankrev path records the trace)
        assert res.active_hist is not None

    def test_auto_requires_matrix_or_selection(self, system):
        a, b = system
        with pytest.raises(ValueError, match="matrix="):
            ecg_solve(lambda V: csr_spmbv(a, V), jnp.asarray(b), t="auto")
        with pytest.raises(ValueError, match="auto"):
            ecg_solve(lambda V: csr_spmbv(a, V), jnp.asarray(b), t="bogus")
        sel = select_t(a, b, candidates=(2, 4), tol=1e-8)
        res = ecg_solve(lambda V: csr_spmbv(a, V), jnp.asarray(b), t="auto",
                        select=sel, tol=1e-8, max_iters=2000)
        assert res.t == sel.t and res.selection is sel

    def test_kappa_mode(self, system):
        a, b = system
        sel = select_t(a, b, candidates=(1, 4), mode="kappa")
        assert sel.t in (1, 4) and sel.mode == "kappa"
        with pytest.raises(ValueError):
            select_t(a, b, mode="bogus")
        with pytest.raises(ValueError):
            select_t(a, None, mode="probe")


class TestKernelDispatch:
    def test_gpu_fallback_warns_once_when_verbose(self, monkeypatch):
        from repro.kernels import dispatch
        from repro.kernels.fused_gram.ops import fused_gram

        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        dispatch.reset_dispatch_warnings()  # conftest resets too; explicit here
        monkeypatch.setenv("REPRO_KERNEL_VERBOSE", "1")
        m = jnp.ones((8, 2))
        with pytest.warns(RuntimeWarning, match="no Pallas GPU lowering"):
            fused_gram(m, m, m, m)
        import warnings as _w

        with _w.catch_warnings():
            _w.simplefilter("error")  # second call: warn-once means silence
            fused_gram(m, m, m, m)

    def test_gpu_fallback_silent_by_default(self, monkeypatch):
        from repro.kernels import dispatch
        from repro.kernels.bsr_spmbv.kernel import LANES
        from repro.kernels.bsr_spmbv.ops import bsr_spmbv

        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        dispatch.reset_dispatch_warnings()
        monkeypatch.delenv("REPRO_KERNEL_VERBOSE", raising=False)
        blocks = jnp.ones((1, 4, 4, LANES))  # one 4x4 tile, lane-major
        idx = jnp.zeros((1, 1), jnp.int32)
        import warnings as _w

        with _w.catch_warnings():
            _w.simplefilter("error")
            out = bsr_spmbv(blocks, idx, jnp.ones((4, 2)))
        assert out.shape == (4, 2)

    def test_tpu_unaffected_cpu_oracle(self):
        from repro.kernels.dispatch import resolve_dispatch

        use, interpret = resolve_dispatch("fused_gram", None)
        assert use is False and interpret is True  # CPU host
        use, interpret = resolve_dispatch("fused_gram", True)
        assert use is True and interpret is True  # forced interpret-mode


class TestReductionEventTrace:
    """Regression: ``reduction_events()`` must report every recorded width
    change by scanning the full valid (-1-padded) trace, independently of
    ``n_iters`` bookkeeping — in particular a drop recorded on the *final*
    iteration (capped or converged) used to fall off the sliced view."""

    def test_events_do_not_depend_on_n_iters(self, system):
        from repro.core.cg import SolveResult

        # n_iters deliberately inconsistent with the trace: the events must
        # come from the trace alone
        res = SolveResult(
            x=jnp.zeros(4), n_iters=0, res_hist=jnp.zeros(5),
            converged=False, active_hist=jnp.asarray([4, 2, 2, 1, -1]),
        )
        assert res.reduction_events() == [(1, 4, 2), (3, 2, 1)]

    def test_padding_never_generates_events(self):
        from repro.core.cg import SolveResult

        res = SolveResult(
            x=jnp.zeros(4), n_iters=3, res_hist=jnp.zeros(5),
            converged=True, active_hist=jnp.asarray([4, 4, 4, -1, -1]),
        )
        assert res.reduction_events() == []
        assert SolveResult(
            x=jnp.zeros(4), n_iters=0, res_hist=jnp.zeros(1),
            converged=False, active_hist=None,
        ).reduction_events() == []

    def test_capped_final_iteration_drop_is_reported(self, system):
        """max_iters caps the solve on exactly the iteration that drops the
        width: the event must still be visible."""
        a, _ = system
        b = deficient_rhs(a.shape[0], 4, m=2)
        res = ecg_solve(lambda V: csr_spmbv(a, V), jnp.asarray(b), t=4,
                        tol=1e-9, max_iters=1, adaptive="reduce")
        assert not res.converged
        ah = np.asarray(res.active_hist)
        assert ah[0] == 4 and ah[1] == 2
        assert res.reduction_events() == [(1, 4, 2)]

    @pytest.mark.parametrize("method,s", [("classic", 1), ("pipelined", 1),
                                          ("sstep", 2)])
    def test_first_iteration_drop_reported_for_every_scheme(
        self, system, method, s
    ):
        a, _ = system
        b = deficient_rhs(a.shape[0], 4, m=2)
        res = ecg_solve(lambda V: csr_spmbv(a, V), jnp.asarray(b), t=4,
                        tol=1e-9, max_iters=1500, adaptive="reduce",
                        method=method, s=s)
        assert res.converged
        events = res.reduction_events()
        assert events and events[0][0] == 1 and events[0][1] == 4
        assert events[0][2] <= 2

    def test_converge_and_drop_on_same_iteration(self, system):
        """Width drop recorded on the convergence iteration itself: run the
        reduced solve to convergence, then cap a fresh run at exactly that
        count — both views must agree on the events."""
        a, _ = system
        b = deficient_rhs(a.shape[0], 4, m=2)
        full = ecg_solve(lambda V: csr_spmbv(a, V), jnp.asarray(b), t=4,
                         tol=1e-9, max_iters=1500, adaptive="reduce")
        assert full.converged
        capped = ecg_solve(lambda V: csr_spmbv(a, V), jnp.asarray(b), t=4,
                           tol=1e-9, max_iters=full.n_iters,
                           adaptive="reduce")
        assert capped.reduction_events() == full.reduction_events()
