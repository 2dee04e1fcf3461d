"""Multi-device worker executed in a subprocess by test_distributed.py.

Must run with XLA_FLAGS=--xla_force_host_platform_device_count=8 so ordinary
tests keep a single device (see conftest note).
"""

import os
import sys

assert "--xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""), (
    "run me via test_distributed.py"
)

import warnings

# No repro-internal module may go through the deprecated back-compat shims
# (ecg_solve/distributed_ecg/make_distributed_spmbv) during these checks.
# This must be an in-process filter: PYTHONWARNINGS/-W escape the module
# field and match it in full, so they cannot express "any repro submodule".
# The worker itself (__main__) deliberately exercises the legacy spellings
# and only sees the warning.
warnings.filterwarnings("error", category=DeprecationWarning, module=r"repro\..*")

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.sparse import dg_laplace_2d, fd_laplace_2d
from repro.sparse.csr import csr_spmbv
from repro.sparse.spmbv import make_distributed_spmbv, distributed_ecg
from repro.core import ecg_solve
from repro.core.machines import BLUE_WATERS


def check_spmbv_strategies():
    mesh = jax.make_mesh((2, 4), ("node", "proc"))
    rng = np.random.default_rng(0)
    for a, label in [
        (dg_laplace_2d((8, 6), block=4), "dg"),
        (fd_laplace_2d(13), "fd-uneven"),  # 169 rows, uneven over 8
    ]:
        ad = np.asarray(a.todense(), np.float64)
        for t in (1, 3, 8):
            V = rng.standard_normal((a.shape[0], t))
            for strategy in ("standard", "2step", "3step", "optimal"):
                op = make_distributed_spmbv(a, mesh, strategy, t=t, machine=BLUE_WATERS)
                W = op.unshard(jax.jit(op.matvec_fn())(op.shard_vector(V)))
                err = np.abs(W - ad @ V).max()
                assert err < 1e-10, (label, strategy, t, err)
                rows = op.plan.comm_rows()
                if strategy != "standard":
                    assert rows["inter"] <= std_inter, (label, strategy, rows)
                else:
                    std_inter = rows["inter"]
        # backend x overlap sweep: kernel-backed and comm-hiding variants
        # must produce the same product as the blocking CSR reference
        V = rng.standard_normal((a.shape[0], 3))
        for strategy in ("standard", "2step", "3step", "optimal"):
            for backend in ("jnp", "pallas"):
                for overlap in (False, True):
                    op = make_distributed_spmbv(
                        a, mesh, strategy, t=3, machine=BLUE_WATERS,
                        backend=backend, overlap=overlap,
                    )
                    W = op.unshard(jax.jit(op.matvec_fn())(op.shard_vector(V)))
                    err = np.abs(W - ad @ V).max()
                    assert err < 1e-10, (label, strategy, backend, overlap, err)
    print("spmbv strategies OK")


def check_kernel_backend_ecg_parity():
    """Kernel-backed distributed ECG must match the jnp path: identical
    iterate count everywhere, and residual history to 1e-10 on the FD system
    (where the Block-ELL summation order coincides with CSR; the DG system's
    iteration dynamics amplify tile-order rounding, so it checks count +
    convergence only)."""
    mesh = jax.make_mesh((2, 4), ("node", "proc"))
    rng = np.random.default_rng(1)

    a = fd_laplace_2d(13)
    b = rng.standard_normal(a.shape[0])
    ref, _ = distributed_ecg(a, b, mesh, t=4, strategy="3step")
    h_ref = np.asarray(ref.res_hist)
    live = ~np.isnan(h_ref)
    for backend, overlap in (("pallas", False), ("pallas", True), ("jnp", True)):
        res, _ = distributed_ecg(a, b, mesh, t=4, strategy="3step",
                                 backend=backend, overlap=overlap)
        assert res.n_iters == ref.n_iters, (backend, overlap, res.n_iters, ref.n_iters)
        h = np.asarray(res.res_hist)
        dh = np.abs(h[live] - h_ref[live]).max()
        assert dh < 1e-10, (backend, overlap, dh)

    a = dg_laplace_2d((8, 6), block=4)
    ad = np.asarray(a.todense(), np.float64)
    b = rng.standard_normal(a.shape[0])
    ref, _ = distributed_ecg(a, b, mesh, t=4, strategy="optimal")
    res, op = distributed_ecg(a, b, mesh, t=4, strategy="optimal",
                              backend="pallas", overlap=True)
    assert res.converged and res.n_iters == ref.n_iters, (res.n_iters, ref.n_iters)
    x = op.unshard(res.x)
    relres = np.linalg.norm(ad @ x - b) / np.linalg.norm(b)
    assert relres < 1e-6, relres
    print("kernel-backend ecg parity OK")


def check_distributed_ecg_matches_sequential():
    mesh = jax.make_mesh((2, 4), ("node", "proc"))
    a = dg_laplace_2d((8, 6), block=4)
    ad = np.asarray(a.todense(), np.float64)
    rng = np.random.default_rng(1)
    b = rng.standard_normal(a.shape[0])
    res_seq = ecg_solve(lambda X: csr_spmbv(a, X), jnp.asarray(b), t=4, tol=1e-8, max_iters=500)
    for strategy in ("standard", "2step", "3step", "optimal"):
        res, op = distributed_ecg(a, b, mesh, t=4, strategy=strategy, tol=1e-8, max_iters=500)
        assert res.converged, strategy
        assert abs(res.n_iters - res_seq.n_iters) <= 2, (strategy, res.n_iters, res_seq.n_iters)
        x = op.unshard(res.x)
        relres = np.linalg.norm(ad @ x - b) / np.linalg.norm(b)
        assert relres < 1e-6, (strategy, relres)
    print("distributed ecg OK")


def check_tuned_and_col_split():
    """tune="model" end-to-end on devices, and a forced col-split plan
    through the real executor (including the width-1 initial-residual path)."""
    mesh = jax.make_mesh((2, 4), ("node", "proc"))
    rng = np.random.default_rng(3)
    a = dg_laplace_2d((8, 6), block=4)
    ad = np.asarray(a.todense(), np.float64)
    b = rng.standard_normal(a.shape[0])

    res, op = distributed_ecg(a, b, mesh, t=4, strategy="tuned", backend="pallas")
    cfg = op.tuned
    assert cfg is not None and cfg.mode == "model"
    assert cfg.strategy in ("standard", "2step", "3step", "optimal")
    assert op.ell_block == (cfg.br, cfg.bc) and op.overlap == cfg.overlap
    assert op.plan.col_split == cfg.col_split  # applied plan matches config

    # applying a precomputed TunedConfig must honor its col_split verbatim
    from repro.tune import TunedConfig

    cfg2 = TunedConfig(strategy="optimal", br=4, bc=4, kmax=8, overlap=False,
                       backend="jnp", t=4, mode="model", col_split=2)
    op2 = make_distributed_spmbv(a, mesh, t=4, tune=cfg2)
    assert op2.plan.col_split == 2, op2.plan.col_split
    V = rng.standard_normal((a.shape[0], 4))
    W = op2.unshard(jax.jit(op2.matvec_fn())(op2.shard_vector(V)))
    assert np.abs(W - ad @ V).max() < 1e-10
    x = op.unshard(res.x)
    relres = np.linalg.norm(ad @ x - b) / np.linalg.norm(b)
    assert res.converged and relres < 1e-6, (cfg.strategy, relres)

    for t, cs in ((4, 2), (8, 4)):
        V = rng.standard_normal((a.shape[0], t))
        op = make_distributed_spmbv(
            a, mesh, "optimal", t=t, machine=BLUE_WATERS, col_split=cs
        )
        assert op.plan.col_split == cs
        f = jax.jit(op.matvec_fn())
        W = op.unshard(f(op.shard_vector(V)))
        assert np.abs(W - ad @ V).max() < 1e-10, (t, cs)
        v1 = rng.standard_normal((a.shape[0], 1))
        W1 = op.unshard(f(op.shard_vector(v1)))
        assert np.abs(W1 - ad @ v1).max() < 1e-10, (t, cs, "width-1")
    print("tuned + col-split OK")


def check_adaptive_and_auto_t():
    """Adaptive ECG on the shard_map path: a rank-deficient splitting that
    breaks fixed-t must converge with adaptive="reduce", and the reduction
    trace must agree with the sequential solver (same math, same drops).
    t="auto" end-to-end records the selection on result + TunedConfig."""
    mesh = jax.make_mesh((2, 4), ("node", "proc"))
    a = fd_laplace_2d(13)
    n = a.shape[0]
    ad = np.asarray(a.todense(), np.float64)
    t, m = 4, 2
    rng = np.random.default_rng(7)
    b = np.zeros(n)
    b[: (m * n) // t] = rng.standard_normal((m * n) // t)  # t−m zero subdomains

    res_fixed, _ = distributed_ecg(a, b, mesh, t=t, strategy="3step", tol=1e-8)
    assert res_fixed.breakdown and not res_fixed.converged, "fixed t should break down"

    from repro.sparse.csr import csr_spmbv as seq_spmbv

    seq = ecg_solve(lambda X: seq_spmbv(a, X), jnp.asarray(b), t=t, tol=1e-8,
                    max_iters=300, adaptive="reduce")
    res, op = distributed_ecg(a, b, mesh, t=t, strategy="3step", tol=1e-8,
                              max_iters=300, adaptive="reduce")
    assert seq.converged and res.converged
    assert abs(res.n_iters - seq.n_iters) <= 2, (res.n_iters, seq.n_iters)
    # width-aware exchange: the reduction event re-sliced the plan, the tail
    # segment ran at the reduced width, and the wire payload shrank with it
    segs = res.comm_segments
    assert segs is not None and segs[0][0] == t and segs[-1][0] == m, segs
    assert sum(it for _, it in segs) == res.n_iters, (segs, res.n_iters)
    by_full = op.plan.wire_bytes(8)
    by_red = op.plan.at_width(m).wire_bytes(8)
    assert by_red * t == by_full * m, (by_full, by_red)  # exact t_active/t cut
    x = op.unshard(res.x)
    relres = np.linalg.norm(ad @ x - b) / np.linalg.norm(b)
    assert relres < 1e-6, relres
    # reduction traces agree: the dependent directions drop at iteration 1 on
    # both paths, and the active width histories match over the common prefix
    k = min(res.n_iters, seq.n_iters) + 1
    ah_d = np.asarray(res.active_hist)[:k]
    ah_s = np.asarray(seq.active_hist)[:k]
    assert ah_d[0] == t and ah_d[1] == m, ah_d[:2]
    assert np.array_equal(ah_d, ah_s), (ah_d, ah_s)
    h_d = np.asarray(res.res_hist)[:k]
    h_s = np.asarray(seq.res_hist)[:k]
    np.testing.assert_allclose(h_d, h_s, rtol=1e-5, atol=1e-10)

    # t="auto" on the tuned distributed path
    b_full = rng.standard_normal(n)
    res_a, op_a = distributed_ecg(a, b_full, mesh, t="auto", strategy="tuned",
                                  tol=1e-8, max_iters=300, t_candidates=(1, 2, 4))
    assert res_a.converged
    assert res_a.selection is not None and res_a.t == res_a.selection.t
    assert op_a.tuned is not None and op_a.tuned.selection is res_a.selection
    assert res_a.t in (1, 2, 4)
    print("adaptive + auto-t OK")


def check_adaptive_opcode_count():
    """The §3.1 invariant under adaptivity: one full adaptive iteration body
    (gram1 → rank-revealing factorization → packed gram2 → tail → norm)
    lowers to exactly the same all-reduce count as the fixed-width body —
    the pivoted factorization and masking run on replicated t x t data and
    add NO collectives."""
    # hand-built iteration bodies outside the solver: an Auto-axes mesh
    # leaves their t x t algebra to sharding propagation, as the solver does
    mesh = jax.make_mesh((2, 4), ("node", "proc"), axis_types=(AxisType.Auto,) * 2)
    a = dg_laplace_2d((4, 4), block=4)
    op = make_distributed_spmbv(a, mesh, "3step", t=4, machine=BLUE_WATERS)
    apply_a = op.matvec_fn()
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core.ecg import _chol_inv_apply
    from repro.adaptive import rank_revealing_apply, stagnation_mask
    from repro.adaptive.reduce import ReductionPolicy

    axes = ("node", "proc")
    vspec = op.vec_spec
    gram1 = shard_map(lambda z, az: jax.lax.psum(z.T @ az, axes), mesh=mesh,
                      in_specs=(vspec, vspec), out_specs=P(None, None), check_vma=False)
    gram2 = shard_map(
        lambda pp, rr, ap, apo: jax.lax.psum(
            jnp.concatenate([pp.T @ rr, ap.T @ ap, apo.T @ ap], axis=1), axes
        ),
        mesh=mesh, in_specs=(vspec,) * 4, out_specs=P(None, None), check_vma=False,
    )
    sqnorm = shard_map(lambda v: jax.lax.psum(jnp.vdot(v, v), axes), mesh=mesh,
                       in_specs=P(axes), out_specs=P(), check_vma=False)
    policy = ReductionPolicy()

    def body(z, r, p_old, ap_old, rn, adaptive):
        az = apply_a(z)
        g = gram1(z, az)
        if adaptive:
            (p, ap), _rank, active = rank_revealing_apply(g, z, az)
        else:
            p, ap = _chol_inv_apply(g, z, az)
        packed = gram2(p, r, ap, ap_old)
        c, d, d_old = jnp.split(packed, 3, axis=1)
        x2 = p @ c
        r2 = r - ap @ c
        z2 = ap - p @ d - p_old @ d_old
        if adaptive:
            active = stagnation_mask(c, rn, active, policy)
            z2 = z2 * active.astype(z2.dtype)[None, :]
        return x2, r2, z2, jnp.sqrt(sqnorm(r2.sum(axis=1)))

    sds = jax.ShapeDtypeStruct((op.n_padded, 4), jnp.float64)
    rn_sds = jax.ShapeDtypeStruct((), jnp.float64)
    counts = {}
    for adaptive in (False, True):
        fn = jax.jit(lambda z, r, po, apo, rn, ad=adaptive: body(z, r, po, apo, rn, ad))
        txt = fn.lower(sds, sds, sds, sds, rn_sds).compile().as_text()
        counts[adaptive] = txt.count(" all-reduce(")
    assert counts[False] == counts[True] == 3, counts  # gram1 + gram2 + norm
    print(f"adaptive opcode count OK (all-reduce x{counts[True]} per iteration, unchanged)")


def check_packed_exchange_lowering():
    """The packed-buffer executor's lowered collective structure: the SpMBV
    itself carries ZERO all-reduces at every active width (so the §3.1
    two-psum iteration invariant is preserved verbatim — check_adaptive_
    opcode_count exercises the full body against the same executor), and
    exactly one collective-permute per nonzero rotation offset of the plan
    — packing fused the gathers/scatters, not the rotations."""
    mesh = jax.make_mesh((2, 4), ("node", "proc"))
    a = dg_laplace_2d((8, 6), block=4)
    for strategy in ("standard", "2step", "3step", "optimal"):
        op = make_distributed_spmbv(a, mesh, strategy, t=8, machine=BLUE_WATERS)
        n_perm_plan = sum(1 for s in op.plan.steps if s.offset)
        for ta in (8, 2):
            plan_w = op.plan.at_width(ta)
            n_perm_w = sum(1 for s in plan_w.steps if s.offset)
            sds = jax.ShapeDtypeStruct((op.n_padded, ta), jnp.float64)
            txt = jax.jit(op.matvec_fn(t_active=None if ta == 8 else ta)) \
                .lower(sds).compile().as_text()
            n_ar = txt.count(" all-reduce(")
            n_cp = txt.count(" collective-permute(") + txt.count(
                " collective-permute-start("
            )
            assert n_ar == 0, (strategy, ta, n_ar)
            assert n_cp == n_perm_w, (strategy, ta, n_cp, n_perm_w)
        assert n_perm_plan == sum(1 for s in op.plan.at_width(2).steps if s.offset), (
            strategy, "re-slice must not change the rotation structure",
        )
    print("packed exchange lowering OK (0 all-reduce, 1 collective-permute "
          "per rotation, at full and reduced widths)")


def _permute_payload_elems(txt):
    """Total elements moved by collective-permutes in optimized HLO text —
    the p2p payload a packed solve pays per exchange sweep (sum over the
    result shapes, equal to the operand shapes, of every
    collective-permute / collective-permute-start; for the async start the
    first element of its result tuple)."""
    import re

    total = 0
    for line in txt.splitlines():
        if not re.search(r" collective-permute(?:-start)?\(", line):
            continue
        m = re.search(r"= \(?[a-z]+[0-9]*\[([\d,]+)\]", line)
        if m:
            dims = [int(d) for d in m.group(1).split(",")]
            total += int(np.prod(dims))
    return total


def check_packed_retirement():
    """Cross-request width packing on the shard_map path: three requests
    with staggered tolerances solve as ONE enlarged width-12 block solve,
    and each retirement re-slices the exchange —

    * ``comm_segments`` widths strictly decrease (12 → 8 → 4) and every
      request's true residual meets its own tolerance;
    * the packed program's all-reduce count is 4 at EVERY segment width
      (3 body + 1 init — grouping the convergence norm into per-request
      norms is one psum of g floats, not g psums, and narrowing the
      exchange adds no collective);
    * the collective-permute payload (elements moved per sweep, read off
      the lowered HLO operand shapes) strictly drops at each retirement
      width while the permute COUNT stays fixed — re-slicing compacts
      bytes, never the rotation structure;
    * retirement iterations agree with the sequential packed solve on the
      same operator to a small margin (only SpMBV summation order differs;
      after a retirement the Gram is structurally singular, so pivot-order
      decisions amplify last-bit differences — the FD system keeps that
      chaos bounded, where the DG system does not).
    """
    from repro.solver import CommConfig, ECGSolver, SolverConfig

    mesh = jax.make_mesh((2, 4), ("node", "proc"))
    a = fd_laplace_2d(13)
    ad = np.asarray(a.todense(), np.float64)
    rng = np.random.default_rng(7)
    bs = [rng.standard_normal(a.shape[0]) for _ in range(3)]
    tols = [1e-2, 1e-5, 1e-8]

    cfg = SolverConfig(
        t=4, tol=1e-8, max_iters=500, adaptive="rankrev",
        comm=CommConfig(strategy="optimal", machine=BLUE_WATERS),
    )
    solver = ECGSolver.build(a, mesh, cfg)
    results = solver.solve_packed(bs, tols=tols)

    for res, b, tol in zip(results, bs, tols):
        assert bool(res.converged), res.pack
        rnorm = np.linalg.norm(ad @ solver.unshard(res.x) - np.asarray(b))
        assert rnorm <= tol * 1.01, (tol, rnorm)
    iters = [r.n_iters for r in results]
    assert iters == sorted(iters), iters

    segs = results[0].comm_segments
    widths = [w for w, _ in segs]
    assert widths[0] == 12 and len(widths) >= 3, segs
    assert all(w1 > w2 for w1, w2 in zip(widths, widths[1:])), segs

    seq = ECGSolver.build(a, config=cfg).solve_packed(bs, tols=tols)
    for res, sres in zip(results, seq):
        assert abs(res.n_iters - sres.n_iters) <= max(5, sres.n_iters // 3), (
            "distributed retirement diverged from sequential",
            res.n_iters, sres.n_iters,
        )

    # lowered collective structure at each live width the solve visited
    payloads, counts = [], []
    for w in widths:
        txt = solver.packed_lowered_text(tols, width_seg=w)
        n_ar = txt.count(" all-reduce(")
        assert n_ar == 4, (w, f"expected 3 body + 1 init all-reduces, got {n_ar}")
        counts.append(
            txt.count(" collective-permute(")
            + txt.count(" collective-permute-start(")
        )
        payloads.append(_permute_payload_elems(txt))
    assert len(set(counts)) == 1 and counts[0] > 0, (
        "retirement re-slice must not change the rotation structure", counts,
    )
    assert all(p1 > p2 for p1, p2 in zip(payloads, payloads[1:])), (
        "collective-permute payload must drop at each retirement width",
        list(zip(widths, payloads)),
    )
    print(
        "packed retirement OK (widths "
        + " -> ".join(str(w) for w in widths)
        + f"; all-reduce x4 at every width; permute payload "
        + " -> ".join(str(p) for p in payloads)
        + f" elems over {counts[0]} permutes; iters {iters})"
    )


def check_solver_handle():
    """The ECGSolver handle on the shard_map path: ``solve_many`` over 4 RHS
    compiles the loop exactly once (zero retraces after the first solve),
    every solve is bit-identical to a one-shot legacy ``distributed_ecg``
    call, and the §3.1 two-psum-per-iteration invariant holds through the
    handle's compiled program (3 all-reduces in the while body — gram1,
    packed gram2, convergence norm — plus exactly 1 for the initial
    residual norm)."""
    import warnings

    from repro.solver import CommConfig, ECGSolver, SolverConfig

    mesh = jax.make_mesh((2, 4), ("node", "proc"))
    a = dg_laplace_2d((8, 6), block=4)
    n = a.shape[0]
    rng = np.random.default_rng(11)
    bs = [rng.standard_normal(n) for _ in range(4)]

    solver = ECGSolver.build(a, mesh, SolverConfig(
        t=4, tol=1e-8, max_iters=500, comm=CommConfig(strategy="3step"),
    ))
    first = solver.solve(bs[0])
    traces_after_first = solver.stats.traces
    rest = solver.solve_many(bs[1:])
    results = [first] + rest
    assert solver.stats.traces == traces_after_first, (
        "solve_many retraced after the first solve",
        solver.stats.traces, traces_after_first,
    )
    assert solver.stats.solves == 4 and solver.stats.builds == 1

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for b, res in zip(bs, results):
            ref, _ = distributed_ecg(a, b, mesh, t=4, strategy="3step",
                                     tol=1e-8, max_iters=500)
            assert res.converged and res.n_iters == ref.n_iters
            assert np.array_equal(np.asarray(res.x), np.asarray(ref.x)), (
                "handle solve is not bit-identical to the one-shot legacy path"
            )
            assert np.array_equal(
                np.asarray(res.res_hist), np.asarray(ref.res_hist),
                equal_nan=True,
            )

    # §3.1 invariant through the handle's compiled program: the while body
    # carries gram1 + packed gram2 + norm = 3 all-reduces (2 psums + the
    # convergence norm), and the init adds exactly one more (r0 norm)
    txt = solver.lowered_text()
    n_ar = txt.count(" all-reduce(")
    assert n_ar == 4, f"expected 3 body + 1 init all-reduces, got {n_ar}"

    # width-segmented adaptive reuse: second solve of the same deficient
    # system replays the cached per-width programs — zero new traces
    t, m = 4, 2
    b_def = np.zeros(n)
    b_def[: (m * n) // t] = rng.standard_normal((m * n) // t)
    s_ad = solver.with_config(policy="reduce")
    assert s_ad.stats.op_reused and s_ad.op is solver.op
    r1 = s_ad.solve(b_def)
    traces = s_ad.stats.traces
    r2 = s_ad.solve(b_def)
    assert s_ad.stats.traces == traces, "adaptive re-solve retraced"
    assert r1.converged and r1.comm_segments == r2.comm_segments
    assert np.array_equal(np.asarray(r1.x), np.asarray(r2.x))
    print("solver handle OK (4-RHS solve_many: 0 retraces, bit-identical to "
          "legacy; 2 psums + norm per iteration through the handle path)")


def _hlo_computations(txt):
    """Split optimized HLO text into {computation_name: [instruction lines]}."""
    comps, cur, lines = {}, None, []
    for raw in txt.splitlines():
        stripped = raw.strip()
        if cur is None:
            if (stripped.startswith("%") or stripped.startswith("ENTRY")) and stripped.endswith("{"):
                cur, lines = stripped.split()[0], []
        elif stripped.startswith("}"):
            comps[cur] = lines
            cur = None
        elif " = " in stripped:
            lines.append(stripped)
    return comps


def _hlo_instr(line):
    """Parse one HLO instruction line -> (name, opcode, operand names).

    Operands are the %names inside the balanced parens right after the
    opcode — attributes (control-predecessors, calls=, sharding) come after
    the operand list and are deliberately excluded, so the def-use graph
    carries data dependencies only.
    """
    import re

    lhs, rhs = line.split(" = ", 1)
    name = lhs.strip().removeprefix("ROOT ").strip()
    rhs = rhs.strip()
    if rhs.startswith("("):  # tuple-shaped result: skip the balanced group
        depth = 0
        for k, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        rhs = rhs[k + 1:].lstrip()
    elif " " in rhs:  # plain shape token
        rhs = rhs.split(" ", 1)[1]
    i = rhs.find("(")
    opcode = rhs[:i].strip()
    depth = 0
    for j in range(i, len(rhs)):
        depth += rhs[j] == "("
        depth -= rhs[j] == ")"
        if depth == 0:
            break
    return name, opcode, re.findall(r"%[\w.\-]+", rhs[i:j + 1])


def _has_collective_permute_ancestor(comp_lines, target_name):
    """True iff a collective-permute reaches ``target_name`` through the
    def-use graph of one computation (data edges only)."""
    instrs = {}
    for ln in comp_lines:
        name, opcode, ops = _hlo_instr(ln)
        instrs[name] = (opcode, ops)
    seen, todo = set(), [target_name]
    while todo:
        cur = todo.pop()
        if cur in seen or cur not in instrs:
            continue
        seen.add(cur)
        opcode, ops = instrs[cur]
        if cur != target_name and opcode.startswith("collective-permute"):
            return True
        todo.extend(ops)
    return False


def check_method_collective_structure():
    """The tentpole's lowered-HLO gates, per iteration scheme:

    * every scheme's fresh solve program carries exactly 4 all-reduces
      (body psums + convergence norm + initial-residual norm) — sstep's 2
      psums serve s effective iterations, so its collectives/iter really is
      2/s in the compiled program, not just in the spec's accounting;
    * collective-permutes = plan rotations x SpMBV sweeps (classic 2: init
      r0 + body; pipelined 3: init r0 + init AZ0 + body; sstep s+1);
    * the overlap claim is structural, not aspirational: pipelined's packed
      (t, 3t) Gram all-reduce has NO collective-permute ancestor in the
      while body (it depends only on carried state, so XLA is free to run
      it concurrently with the exchange), while classic's same-shaped
      all-reduce provably depends on the body's SpMBV.
    """
    from repro.core.ecg import _ecg_solve
    from repro.core.methods import get_method
    from repro.solver import CommConfig, ECGSolver, SolverConfig

    mesh = jax.make_mesh((2, 4), ("node", "proc"))
    a = dg_laplace_2d((8, 6), block=4)
    ad = np.asarray(a.todense(), np.float64)
    rng = np.random.default_rng(23)
    b = rng.standard_normal(a.shape[0])
    t, s = 4, 2
    seq = {
        m: _ecg_solve(lambda X: csr_spmbv(a, X), jnp.asarray(b), t, tol=1e-8,
                      max_iters=500, method=m, s=s if m == "sstep" else 1)
        for m in ("classic", "pipelined", "sstep")
    }
    texts = {}
    for method in ("classic", "pipelined", "sstep"):
        ms = s if method == "sstep" else 1
        solver = ECGSolver.build(a, mesh, SolverConfig(
            t=t, tol=1e-8, max_iters=500, comm=CommConfig(strategy="3step"),
            method=dict(name=method, s=ms),
        ))
        res = solver.solve(b)
        assert res.converged and res.n_iters == seq[method].n_iters, (
            method, res.n_iters, seq[method].n_iters)
        x = solver.unshard(res.x)
        relres = np.linalg.norm(ad @ x - b) / np.linalg.norm(b)
        assert relres < 1e-6, (method, relres)

        txt = solver.lowered_text()
        texts[method] = txt
        n_ar = txt.count(" all-reduce(")
        assert n_ar == 4, (method, n_ar)
        spec = get_method(method)
        assert spec.psums_per_block(ms) / spec.iters_per_block(ms) == (
            {"classic": 2, "pipelined": 2, "sstep": 2 / s}[method]
        )
        rot = sum(1 for step in solver.op.plan.steps if step.offset)
        n_cp = txt.count(" collective-permute(") + txt.count(
            " collective-permute-start(")
        spmbvs = {"classic": 2, "pipelined": 3, "sstep": s + 1}[method]
        assert n_cp == rot * spmbvs, (method, n_cp, rot, spmbvs)

    # overlap proof on the packed (t, 3t) Gram reduction — it is the only
    # all-reduce in either program with a (t, 3t) result shape
    shape = f"f64[{t},{3 * t}]"
    for method, expect_dep in (("classic", True), ("pipelined", False)):
        found = None
        for cname, lines in _hlo_computations(texts[method]).items():
            for ln in lines:
                if " all-reduce(" not in ln:
                    continue
                name, opcode, _ = _hlo_instr(ln)
                if opcode == "all-reduce" and ln.split(" = ", 1)[1].lstrip().startswith(shape):
                    found = (cname, lines, name)
        assert found is not None, (method, "packed (t,3t) all-reduce not found")
        cname, lines, name = found
        dep = _has_collective_permute_ancestor(lines, name)
        assert dep == expect_dep, (
            method, f"packed Gram all-reduce in {cname}: collective-permute "
            f"ancestor={dep}, expected {expect_dep}")
    print("method collective structure OK (4 all-reduces each; CPs = "
          "rotations x {2,3,s+1}; pipelined packed Gram independent of the "
          "body exchange, classic dependent)")


def check_method_segmented_resume():
    """Width-segmented adaptive solves per scheme on the shard_map path: a
    deficient splitting must reduce t=8 -> 2 under pipelined and sstep and
    match each scheme's own monolithic sequential run exactly (count,
    history, reduction trace)."""
    from repro.core.ecg import _ecg_solve
    from repro.solver import CommConfig, ECGSolver, SolverConfig

    mesh = jax.make_mesh((2, 4), ("node", "proc"))
    a = fd_laplace_2d(13)
    n = a.shape[0]
    ad = np.asarray(a.todense(), np.float64)
    t, m = 8, 2
    rng = np.random.default_rng(7)
    b = np.zeros(n)
    b[: (m * n) // t] = rng.standard_normal((m * n) // t)

    for method, s in (("pipelined", 1), ("sstep", 2)):
        seq = _ecg_solve(lambda X: csr_spmbv(a, X), jnp.asarray(b), t,
                         tol=1e-8, max_iters=300, adaptive="reduce",
                         method=method, s=s)
        assert seq.converged, method
        solver = ECGSolver.build(a, mesh, SolverConfig(
            t=t, tol=1e-8, max_iters=300, comm=CommConfig(strategy="3step"),
            adaptive="reduce", method=dict(name=method, s=s),
        ))
        res = solver.solve(b)
        assert res.converged and res.n_iters == seq.n_iters, (
            method, res.n_iters, seq.n_iters)
        segs = res.comm_segments
        assert segs is not None and segs[0][0] == t and segs[-1][0] == m, (
            method, segs)
        assert sum(it for _, it in segs) == res.n_iters, (method, segs)
        k = res.n_iters + 1
        np.testing.assert_allclose(
            np.asarray(res.res_hist)[:k], np.asarray(seq.res_hist)[:k],
            rtol=1e-5, atol=1e-10)
        assert np.array_equal(np.asarray(res.active_hist)[:k],
                              np.asarray(seq.active_hist)[:k]), method
        x = solver.unshard(res.x)
        relres = np.linalg.norm(ad @ x - b) / np.linalg.norm(b)
        assert relres < 1e-6, (method, relres)
    print("method segmented resume OK (t=8->2 under pipelined and sstep, "
          "matching their monolithic runs)")


def check_rank_methods_structural():
    """tune="model:structural" ranks the three schemes on the real partition
    geometry: the table decomposes exactly, sstep amortizes synchronization,
    pipelined never syncs more than classic."""
    from repro.tune import rank_methods

    a = dg_laplace_2d((8, 6), block=4)
    best, table = rank_methods(a, 4, n_nodes=2, ppn=4, s=2,
                               mode="model:structural")
    assert set(table) == {"classic", "pipelined", "sstep"}
    for row in table.values():
        assert abs(row["iter_s"] - (row["sync_s"] + row["spmbv_s"] + row["local_s"])) < 1e-18
    assert table["sstep"]["sync_s"] < table["classic"]["sync_s"]
    assert table["pipelined"]["sync_s"] <= table["classic"]["sync_s"]
    assert best == min(table, key=lambda k: table[k]["iter_s"])
    print(f"rank_methods structural OK (best={best})")


def check_two_psums_per_iteration():
    """The §3.1 discipline: the iteration body must carry exactly 2 psums
    (plus the convergence-norm reduction) — inspect the lowered HLO.  Count
    the ``all-reduce(`` opcode, not the bare substring: each instruction's
    SSA name (e.g. ``%all-reduce.1``) would otherwise double-count."""
    # hand-built iteration bodies outside the solver: an Auto-axes mesh
    # leaves their t x t algebra to sharding propagation, as the solver does
    mesh = jax.make_mesh((2, 4), ("node", "proc"), axis_types=(AxisType.Auto,) * 2)
    a = dg_laplace_2d((4, 4), block=4)
    op = make_distributed_spmbv(a, mesh, "3step", t=4, machine=BLUE_WATERS)
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.kernels import fused_gram

    def n_allreduce(txt):
        return txt.count(" all-reduce(")

    vspec = op.vec_spec
    sds = jax.ShapeDtypeStruct((op.n_padded, 4), jnp.float64)
    gram1 = shard_map(
        lambda z, az: jax.lax.psum(z.T @ az, ("node", "proc")),
        mesh=mesh, in_specs=(vspec, vspec), out_specs=P(None, None), check_vma=False,
    )
    txt = jax.jit(gram1).lower(sds, sds).compile().as_text()
    assert n_allreduce(txt) == 1, (
        f"fused gram should lower to one all-reduce, got {n_allreduce(txt)}"
    )
    # kernel-backed gram2 keeps the same collective structure: the packed
    # [PᵀR | APᵀAP | AP_oldᵀAP] product feeds exactly ONE psum
    gram2 = shard_map(
        lambda pp, rr, ap, apo: jax.lax.psum(
            fused_gram(pp, rr, ap, apo), ("node", "proc")
        ),
        mesh=mesh, in_specs=(vspec,) * 4, out_specs=P(None, None), check_vma=False,
    )
    txt2 = jax.jit(gram2).lower(sds, sds, sds, sds).compile().as_text()
    assert n_allreduce(txt2) == 1, (
        f"kernel-backed gram2 should lower to one all-reduce, got {n_allreduce(txt2)}"
    )
    print("psum fusion OK")



def check_preconditioned_solver():
    """Preconditioned ECG on the shard_map path.

    * classic + {none, block_jacobi, chebyshev}: the lowered program still
      carries exactly 4 all-reduces (2 body psums — gram1 and the packed
      preconditioned gram2 — + body norm + init norm).  The preconditioner
      applies add ZERO collectives: block-Jacobi solves rank-local blocks,
      Chebyshev only adds SpMBVs (point-to-point exchanges).
    * block_jacobi / chebyshev cut iterations vs none at the same t.
    * precondition="none" stays bit-identical to the unpreconditioned
      handle.
    * the iteration-varying "inexact" kind converges on classic (flexible
      residual reseed) and sstep (reseeds every block), and solutions hit
      the true residual tolerance.
    """
    from repro.solver import ECGSolver, MethodConfig, SolverConfig

    mesh = jax.make_mesh((2, 4), ("node", "proc"))
    a = fd_laplace_2d(14)  # 196 rows
    b = np.random.default_rng(0).standard_normal(a.shape[0])
    ad = np.asarray(a.todense())
    x_true = np.linalg.solve(ad, b)
    base_cfg = SolverConfig(t=4, tol=1e-10, max_iters=400)

    iters = {}
    for kind in ("none", "block_jacobi", "chebyshev"):
        solver = ECGSolver.build(
            a, mesh, base_cfg.replace(precondition=kind)
        )
        res = solver.solve(b)
        assert res.converged, f"classic+{kind} did not converge"
        np.testing.assert_allclose(solver.op.unshard(res.x), x_true, rtol=1e-6)
        iters[kind] = res.n_iters
        n_ar = solver.lowered_text().count(" all-reduce(")
        assert n_ar == 4, (
            f"classic+{kind}: expected 3 body + 1 init all-reduces "
            f"(preconditioning must not add collectives), got {n_ar}"
        )
        if kind == "none":
            plain = ECGSolver.build(a, mesh, base_cfg).solve(b)
            assert np.array_equal(np.asarray(res.x), np.asarray(plain.x)), (
                "precondition='none' is not bit-identical to unpreconditioned"
            )
            assert res.n_iters == plain.n_iters
    assert iters["block_jacobi"] < iters["none"], iters
    assert iters["chebyshev"] < iters["none"], iters

    for mc in (MethodConfig(name="classic"), MethodConfig(name="sstep", s=2)):
        solver = ECGSolver.build(
            a, mesh,
            base_cfg.replace(method=mc).replace(precondition="inexact"),
        )
        res = solver.solve(b)
        assert res.converged, f"{mc.name}+inexact did not converge"
        np.testing.assert_allclose(solver.op.unshard(res.x), x_true, rtol=1e-6)

    print(
        "preconditioned solver OK (4 all-reduces each; iters "
        + ", ".join(f"{k}={v}" for k, v in iters.items())
        + ")"
    )


def check_chebyshev_lambda_max_p2p():
    """The Chebyshev λmax power iteration runs through the width-1 SpMBV
    sub-plan, never a densified or host-looped operator:

    * the lowered power-step program carries ZERO all-reduces (the Rayleigh
      quotient and norms reduce host-side after unshard) and exactly the
      width-1 plan's collective-permutes — i.e. the estimate adds only p2p
      exchange, the same kind (and count) of collective as one SpMBV sweep;
    * the distributed estimate agrees with the sequential one (identical
      deterministic start vector, same iteration count — only SpMBV
      summation order differs);
    * a col_split > 1 plan re-slices to width 1 through its rebuild closure
      (the path a nodal-optimal operator takes at build time).
    """
    from repro.precondition.chebyshev import (
        distributed_power_matvec,
        estimate_lambda_max,
    )

    mesh = jax.make_mesh((2, 4), ("node", "proc"))
    a = dg_laplace_2d((8, 6), block=4)
    lam_seq = estimate_lambda_max(a)
    for strategy, col_split in (("2step", 1), ("optimal", 2)):
        op = make_distributed_spmbv(
            a, mesh, strategy, t=4, machine=BLUE_WATERS, col_split=col_split
        )
        plan1 = op.plan.at_width(1)
        n_perm = sum(1 for s in plan1.steps if s.offset)
        sds = jax.ShapeDtypeStruct((op.n_padded, 1), jnp.float64)
        txt = jax.jit(op.matvec_fn(t_active=1)).lower(sds).compile().as_text()
        n_ar = txt.count(" all-reduce(")
        n_cp = txt.count(" collective-permute(") + txt.count(
            " collective-permute-start(")
        assert n_ar == 0, (strategy, "power step must issue no all-reduce", n_ar)
        assert n_cp == n_perm, (strategy, n_cp, n_perm)
        lam_dist = estimate_lambda_max(a, matvec=distributed_power_matvec(op))
        assert abs(lam_dist - lam_seq) <= 1e-9 * abs(lam_seq), (
            strategy, lam_dist, lam_seq,
        )
    print(f"chebyshev lambda-max p2p OK (0 all-reduce, plan-exact permutes, "
          f"lmax={lam_seq:.6f} sequential == distributed)")


if __name__ == "__main__":
    assert len(jax.devices()) == 8
    check_spmbv_strategies()
    check_distributed_ecg_matches_sequential()
    check_kernel_backend_ecg_parity()
    check_tuned_and_col_split()
    check_adaptive_and_auto_t()
    check_adaptive_opcode_count()
    check_packed_exchange_lowering()
    check_packed_retirement()
    check_two_psums_per_iteration()
    check_solver_handle()
    check_preconditioned_solver()
    check_method_collective_structure()
    check_method_segmented_resume()
    check_rank_methods_structural()
    check_chebyshev_lambda_max_p2p()
    print("ALL DISTRIBUTED CHECKS PASSED")
