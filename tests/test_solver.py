"""repro.solver: typed config validation, handle reuse, and back-compat.

The heart of this file is the handle-reuse matrix: for every
t ∈ {2, 4, 8} × backend ∈ {jnp, pallas} × adaptive ∈ {off, reduce}, a
second ``ECGSolver.solve`` call must trigger **no retrace** (jit cache hit,
asserted via ``SolverStats.traces``) and be **bit-identical** to the
one-shot legacy ``ecg_solve`` path.  The distributed equivalents (4-RHS
``solve_many`` vs four legacy ``distributed_ecg`` calls, two-psum HLO
invariant) run in ``dist_worker.check_solver_handle``.
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.adaptive import ReductionPolicy, TSelection, select_t
from repro.core import ecg_solve
from repro.solver import (
    AdaptiveConfig,
    CommConfig,
    ECGSolver,
    KernelConfig,
    SolverConfig,
    TuneConfig,
)
from repro.sparse import dg_laplace_2d, fd_laplace_2d
from repro.sparse.csr import csr_spmbv
from repro.tune import TunedConfig, tune as run_tune


@pytest.fixture(scope="module")
def system():
    a = dg_laplace_2d((8, 6), block=4)  # 192 rows
    b = np.random.default_rng(7).standard_normal(a.shape[0])
    return a, b


# --------------------------------------------------------------- config
class TestSolverConfig:
    def test_validation_at_construction(self):
        with pytest.raises(ValueError, match="strategy"):
            CommConfig(strategy="bogus")
        with pytest.raises(ValueError, match="backend"):
            KernelConfig(backend="cuda")
        with pytest.raises(ValueError, match="tune mode"):
            TuneConfig(mode="magic")
        with pytest.raises(ValueError, match="adaptive mode"):
            AdaptiveConfig(policy="bogus")
        with pytest.raises(ValueError, match="col_split"):
            CommConfig(col_split=0)
        with pytest.raises(ValueError, match="ell_block"):
            KernelConfig(ell_block=(8, 0))
        with pytest.raises(ValueError, match="t must be"):
            SolverConfig(t=0)
        with pytest.raises(ValueError, match="t must be"):
            SolverConfig(t="automatic")
        with pytest.raises(ValueError, match="max_iters"):
            SolverConfig(max_iters=0)
        with pytest.raises(ValueError, match="probe_iters"):
            AdaptiveConfig(probe_iters=1)

    def test_coercions(self):
        cfg = SolverConfig(t=4, tune="model", adaptive="reduce",
                           kernel=KernelConfig(ell_block=8))
        assert cfg.tune == TuneConfig(mode="model")
        assert isinstance(cfg.adaptive.policy, ReductionPolicy)
        assert cfg.kernel.ell_block == (8, 8)
        # a precomputed TunedConfig slots into the tune field
        tc = TunedConfig(strategy="3step", br=4, bc=4, kmax=8, overlap=False,
                         backend="jnp", t=4, mode="model")
        cfg2 = SolverConfig(t=4, tune=tc)
        assert cfg2.tune.tuned is tc and cfg2.tune.active

    def test_replace_flat_and_nested(self):
        cfg = SolverConfig(t=4)
        c2 = cfg.replace(strategy="3step", backend="pallas", tol=1e-6,
                         policy="rankrev", tune_mode="model")
        assert c2.comm.strategy == "3step"
        assert c2.kernel.backend == "pallas"
        assert c2.tol == 1e-6
        assert isinstance(c2.adaptive.policy, ReductionPolicy)
        assert c2.tune.mode == "model"
        assert cfg.comm.strategy == "standard"  # original untouched
        with pytest.raises(ValueError, match="unknown config override"):
            cfg.replace(stratgy="3step")
        with pytest.raises(ValueError, match="cannot combine"):
            cfg.replace(comm=CommConfig(), strategy="3step")

    def test_frozen_and_comparable(self):
        assert SolverConfig(t=4) == SolverConfig(t=4)
        assert SolverConfig(t=4) != SolverConfig(t=8)
        with pytest.raises(Exception):
            SolverConfig(t=4).t = 8


# --------------------------------------------------------------- handle
class TestHandleReuse:
    @pytest.mark.parametrize("t", [2, 4, 8])
    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    @pytest.mark.parametrize("adaptive", [None, "reduce"])
    def test_second_solve_no_retrace_and_bit_identical_to_legacy(
        self, system, t, backend, adaptive
    ):
        a, b = system
        b2 = np.random.default_rng(t).standard_normal(a.shape[0])
        solver = ECGSolver.build(a, config=SolverConfig(
            t=t, tol=1e-8, max_iters=400,
            kernel=KernelConfig(backend=backend),
            adaptive=AdaptiveConfig(policy=adaptive),
        ))
        res1 = solver.solve(b)
        traces = solver.stats.traces
        res2 = solver.solve(b2)
        assert solver.stats.traces == traces, "second solve retraced"
        assert res1.converged and res2.converged

        if backend == "pallas":
            # the handle routes the SpMBV through the same Block-ELL apply
            from repro.kernels import make_block_ell_apply

            apply_ref = make_block_ell_apply(a, block=(8, 8))
        else:
            apply_ref = lambda V: csr_spmbv(a, V)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            ref = ecg_solve(
                apply_ref, jnp.asarray(b2), t=t, tol=1e-8,
                max_iters=400, backend=backend, adaptive=adaptive,
            )
        assert res2.n_iters == ref.n_iters
        assert np.array_equal(np.asarray(res2.x), np.asarray(ref.x)), (
            "handle solve is not bit-identical to the one-shot legacy path"
        )
        assert np.array_equal(
            np.asarray(res2.res_hist), np.asarray(ref.res_hist), equal_nan=True
        )

    def test_solve_many_zero_retraces(self, system):
        a, _ = system
        rng = np.random.default_rng(3)
        bs = [rng.standard_normal(a.shape[0]) for _ in range(4)]
        solver = ECGSolver.build(a, config=SolverConfig(t=4, max_iters=400))
        first = solver.solve(bs[0])
        traces = solver.stats.traces
        rest = solver.solve_many(bs[1:])
        assert solver.stats.traces == traces
        assert solver.stats.solves == 4
        assert all(r.converged for r in [first] + rest)
        # the solves are independent: each matches its own fresh handle
        fresh = ECGSolver.build(a, config=SolverConfig(t=4, max_iters=400))
        assert np.array_equal(
            np.asarray(rest[-1].x), np.asarray(fresh.solve(bs[-1]).x)
        )

    def test_with_config_reuses_or_rebuilds(self, system):
        a, b = system
        solver = ECGSolver.build(a, config=SolverConfig(t=4, max_iters=400))
        # solve-level override: same operator, fresh jit cache
        s_tol = solver.with_config(tol=1e-6)
        assert s_tol.stats.op_reused and s_tol.config.tol == 1e-6
        assert s_tol.solve(b).converged
        # policy override still reuses the operator
        s_ad = solver.with_config(policy="reduce")
        assert s_ad.stats.op_reused and s_ad.policy is not None
        assert s_ad.solve(b).converged
        # kernel override rebuilds (sequential handle: new apply closure)
        s_pl = solver.with_config(backend="pallas")
        assert not s_pl.stats.op_reused
        assert s_pl.solve(b).converged

    def test_x0_and_auto_t(self, system):
        a, b = system
        solver = ECGSolver.build(a, config=SolverConfig(t=4, max_iters=400))
        res = solver.solve(b, x0=solver.solve(b).x)
        assert res.converged and res.n_iters <= 2
        s_auto = ECGSolver.build(
            a,
            config=SolverConfig(t="auto", max_iters=400,
                                adaptive=AdaptiveConfig(t_candidates=(2, 4))),
            b=b,
        )
        assert s_auto.t in (2, 4)
        r = s_auto.solve(b)
        assert r.converged and r.selection is s_auto.selection
        assert set(r.selection.probe_iters_used) == {2, 4}

    def test_with_config_reselects_auto_t_on_adaptive_knob_change(self, system):
        a, b = system
        s = ECGSolver.build(a, config=SolverConfig(
            t="auto", max_iters=400,
            adaptive=AdaptiveConfig(t_candidates=(2, 4)),
        ), b=b)
        # changing a selection input on an auto-t handle must re-run the
        # selection, not silently reuse the stale one
        s2 = s.with_config(t_candidates=(8, 16))
        assert not s2.stats.op_reused
        assert s2.selection.candidates == (8, 16) and s2.t in (8, 16)
        # auto-t's implied rankrev guard survives the re-derivation
        assert s2.policy is not None
        # tol is a selection input too (est_iters-to-tol drives the ranking)
        s3 = s.with_config(tol=1e-4)
        assert not s3.stats.op_reused and s3.selection.tol == 1e-4
        # an unrelated solve-level knob on a fixed-t handle still reuses
        s_fixed = ECGSolver.build(a, config=SolverConfig(t=4, max_iters=400))
        assert s_fixed.with_config(tol=1e-6).stats.op_reused

    def test_explicit_off_suppresses_auto_t_rankrev(self, system):
        a, b = system
        on = ECGSolver.build(a, config=SolverConfig(
            t="auto", max_iters=400, adaptive=AdaptiveConfig(t_candidates=(2, 4)),
        ), b=b)
        assert on.policy is not None  # auto-t implies breakdown safety...
        off = ECGSolver.build(a, config=SolverConfig(
            t="auto", max_iters=400,
            adaptive=AdaptiveConfig(policy="off", t_candidates=(2, 4)),
        ), b=b)
        assert off.config.adaptive.explicit_off
        assert off.policy is None  # ...unless explicitly switched off
        res = off.solve(b)
        assert res.converged and res.active_hist is None
        # explicit_off is not sticky: overriding the policy later (on either
        # the reuse or the rebuild path) must honor the new policy
        back_on = off.with_config(policy="reduce", backend="pallas")
        assert not back_on.config.adaptive.explicit_off
        assert back_on.policy is not None
        assert back_on.solve(b).converged

    def test_new_api_emits_no_deprecation_warning(self, system):
        a, b = system
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            solver = ECGSolver.build(a, config=SolverConfig(t=4, max_iters=400))
            assert solver.solve(b).converged

    def test_legacy_spellings_warn(self, system):
        a, b = system
        with pytest.warns(DeprecationWarning, match="ECGSolver"):
            ecg_solve(lambda V: csr_spmbv(a, V), jnp.asarray(b), t=4,
                      max_iters=400)


# ---------------------------------------------------- satellite round trips
class TestConfigSerialization:
    def test_tunedconfig_json_round_trip_lossless(self, system):
        a, _ = system
        cfg = run_tune(a, t=4, n_nodes=2, ppn=4, backend="pallas")
        js = cfg.to_json()
        back = TunedConfig.from_json(js)
        assert back == cfg                     # dataclass fields
        assert back.machine == cfg.machine     # resolved MachineParams
        assert back.to_json() == js            # lossless fixed point
        # and it feeds straight back into the typed config
        solver = ECGSolver.build(a, config=SolverConfig(t=4, tune=back))
        assert solver.tuned is back

    def test_tselection_json_round_trip_lossless(self, system):
        a, b = system
        sel = select_t(a, b, candidates=(2, 4), tol=1e-8)
        js = sel.to_json()
        back = TSelection.from_json(js)
        assert back.t == sel.t and back.candidates == sel.candidates
        assert back.table == sel.table
        assert back.probe_iters_used == sel.probe_iters_used
        assert back.to_json() == js            # lossless fixed point
        # configs (TunedConfig per candidate) survive too
        assert set(back.configs) == set(sel.configs)
        assert all(back.configs[t] == sel.configs[t] for t in back.configs)
        # a selection loaded from disk skips the probes entirely
        solver = ECGSolver.build(a, config=SolverConfig(
            t="auto", adaptive=AdaptiveConfig(select=back, t_candidates=(2, 4)),
        ))
        assert solver.t == sel.t


class TestProbeEarlyStop:
    def test_early_stop_records_iters_used(self, system):
        a, b = system
        budget = 12
        sel = select_t(a, b, candidates=(2, 4), tol=1e-8, probe_iters=budget)
        assert set(sel.probe_iters_used) == {2, 4}
        assert all(3 <= u <= budget for u in sel.probe_iters_used.values())
        # on this smoothly-decaying system the fitted rate stabilizes well
        # before the budget — the early stop must actually engage
        assert any(u < budget for u in sel.probe_iters_used.values())

    def test_rtol_zero_disables_early_stop(self, system):
        a, b = system
        sel = select_t(a, b, candidates=(4,), tol=1e-8, probe_iters=6,
                       probe_rtol=0.0)
        assert sel.probe_iters_used == {4: 6}

    def test_estimates_stay_calibrated(self, system):
        a, b = system
        early = select_t(a, b, candidates=(4,), tol=1e-8, probe_iters=10)
        full = select_t(a, b, candidates=(4,), tol=1e-8, probe_iters=10,
                        probe_rtol=0.0)
        e1 = early.table[4]["est_iters"]
        e2 = full.table[4]["est_iters"]
        assert abs(e1 - e2) / max(e2, 1) <= 0.35, (e1, e2)


class TestDispatchOverheadMicrobench:
    def test_measures_positive_seconds(self):
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:1]).reshape(1, 1), ("node", "proc")
        )
        from repro.tune import measure_dispatch_overhead

        v = measure_dispatch_overhead(mesh, repeats=3, chain=(2, 8))
        assert np.isfinite(v) and 0 < v < 1.0


# ------------------------------------------------------------- solve_packed
class TestSolvePacked:
    CFG = dict(t=4, tol=1e-8, adaptive="rankrev")

    def test_groupspec_validation(self):
        from repro.adaptive import GroupSpec

        spec = GroupSpec(t_each=4, tols=(1e-4, 1e-8))
        assert spec.width == 8 and spec.n_groups == 2
        assert hash(spec) == hash(GroupSpec(t_each=4, tols=(1e-4, 1e-8)))
        with pytest.raises(ValueError, match="t_each"):
            GroupSpec(t_each=0, tols=(1e-8,))
        with pytest.raises(ValueError, match="at least one group"):
            GroupSpec(t_each=4, tols=())
        with pytest.raises(ValueError, match="tol"):
            GroupSpec(t_each=4, tols=(1e-8, -1.0))

    def test_each_request_meets_its_tolerance(self, system):
        a, _ = system
        rng = np.random.default_rng(21)
        bs = [rng.standard_normal(a.shape[0]) for _ in range(3)]
        tols = [1e-3, 1e-6, 1e-9]
        solver = ECGSolver.build(a, config=SolverConfig(**self.CFG))
        results = solver.solve_packed(bs, tols=tols)
        dense = np.asarray(a.todense())
        for res, b, tol in zip(results, bs, tols):
            assert bool(res.converged)
            assert np.linalg.norm(dense @ np.asarray(res.x) - b) <= tol * 1.01
            assert res.pack["tol"] == tol and res.t == 4
        # retirement order follows tolerance order on a shared operator
        iters = [r.n_iters for r in results]
        assert iters == sorted(iters)
        assert solver.stats.solves == 3

    def test_pack_converges_faster_than_solo_total(self, system):
        a, _ = system
        rng = np.random.default_rng(22)
        bs = [rng.standard_normal(a.shape[0]) for _ in range(4)]
        solver = ECGSolver.build(a, config=SolverConfig(**self.CFG))
        packed = solver.solve_packed(bs)
        solo = ECGSolver.build(a, config=SolverConfig(**self.CFG))
        solo_iters = [solo.solve(b).n_iters for b in bs]
        # the shared search space: the pack's total iterations beat the
        # slowest solo solve, not just the sum
        assert packed[0].pack["packed_iters"] <= max(solo_iters)

    def test_x0_at_tolerance_retires_at_zero(self, system):
        a, b = system
        solver = ECGSolver.build(a, config=SolverConfig(**self.CFG))
        x_star = solver.solve(b).x
        rng = np.random.default_rng(23)
        b2 = rng.standard_normal(a.shape[0])
        res = solver.solve_packed([b, b2], x0s=[np.asarray(x_star), None])
        assert res[0].n_iters == 0 and bool(res[0].converged)
        assert res[0].pack["retired_iter"] == 0
        assert res[1].n_iters > 0 and bool(res[1].converged)

    def test_repack_same_layout_zero_retraces(self, system):
        a, _ = system
        rng = np.random.default_rng(24)
        solver = ECGSolver.build(a, config=SolverConfig(**self.CFG))
        solver.solve_packed([rng.standard_normal(a.shape[0]) for _ in range(3)])
        traces0 = solver.stats.traces
        solver.solve_packed([rng.standard_normal(a.shape[0]) for _ in range(3)])
        assert solver.stats.traces == traces0  # same (t_each, tols) layout

    def test_rejects_unsupported_configs(self, system):
        a, b = system
        bs = [b]
        no_policy = ECGSolver.build(a, config=SolverConfig(t=4, tol=1e-8))
        with pytest.raises(ValueError, match="rank-revealing"):
            no_policy.solve_packed(bs)
        sstep = ECGSolver.build(
            a, config=SolverConfig(t=4, adaptive="rankrev",
                                   method=dict(name="sstep"))
        )
        with pytest.raises(ValueError, match="classic"):
            sstep.solve_packed(bs)
        restart = ECGSolver.build(
            a, config=SolverConfig(t=4, adaptive="reduce+restart")
        )
        with pytest.raises(ValueError, match="restart"):
            restart.solve_packed(bs)
        solver = ECGSolver.build(a, config=SolverConfig(**self.CFG))
        with pytest.raises(ValueError, match="at least one"):
            solver.solve_packed([])
        with pytest.raises(ValueError, match="guesses"):
            solver.solve_packed(bs, x0s=[None, None])


class TestTable3Surrogate:
    """The repo's window-shuffled surrogate of Table 3's audikw_1 at a small
    scale (144 block rows: a ragged last lane group), solved
    through ``ECGSolver.build``/``solve`` with every kernel in Pallas
    interpret mode, against a plain float64 solve of the same CSR."""

    def test_pallas_solve_against_float64_reference(self, monkeypatch):
        import importlib

        from repro.sparse import suite_surrogate

        for name in ("bsr_spmbv", "fused_gram", "block_update"):
            monkeypatch.setattr(importlib.import_module(f"repro.kernels.{name}.ops"),
                                "resolve_dispatch", lambda op, use: (True, True))
        a = suite_surrogate("audikw_1", scale=0.05)
        ad = np.asarray(a.todense(), np.float64)
        b = np.random.default_rng(11).standard_normal(a.shape[0])
        tol = 1e-10
        solver = ECGSolver.build(a, None, SolverConfig(
            t=8, tol=tol, max_iters=500, tune="off",
            kernel=KernelConfig(backend="pallas", ell_block=(16, 16))))
        res = solver.solve(b)
        assert bool(res.converged)
        x = np.asarray(res.x, np.float64)
        want = np.linalg.solve(ad, b)
        # the recursive residual meets tol; in float64 the true one stays
        # within 10x of it, and the error is bounded by cond(A) times that
        assert np.linalg.norm(b - ad @ x) / np.linalg.norm(b) < 10 * tol
        bound = np.linalg.cond(ad) * 10 * tol
        assert np.linalg.norm(x - want) / np.linalg.norm(want) < bound
