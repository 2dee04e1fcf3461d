"""ECG solve driver (single- or multi-device) on the ECGSolver handle API.

    PYTHONPATH=src python -m repro.launch.solve --matrix dg --t 8 \
        --strategy tuned [--devices 8] [--backend pallas] [--tune model] \
        [--adaptive reduce] [--t auto] [--method sstep --s 4]

The driver builds one :class:`repro.solver.ECGSolver` session — partition,
exchange plan, autotuning, t-selection, and Block-ELL conversion happen
once — then solves (the timed call reuses the compiled loop; a second RHS
would pay zero retraces).

--backend pallas routes the SpMBV through the Block-ELL Pallas kernel and
the gram/tail updates through the fused kernels (oracles on CPU).

--tune model (the default with --strategy tuned) hands strategy, Block-ELL
tile shape, and blocking-vs-overlap to the setup-time autotuner
(repro.tune); --tune measure calibrates with microbenchmarks on the real
mesh instead of the models; --tune off keeps the explicit --strategy /
--ell-block / --overlap flags.

--t auto picks the enlarging factor from the iterations-vs-cost model
(repro.adaptive.select_t) — it composes the tuner's per-iteration cost with
probe-calibrated convergence rates, so it requires the cost models and is
rejected together with an explicit --tune off.  --adaptive enables the
in-solve width controller (rank-revealing breakdown safety, flexible-ECG
stagnation drops, optional plateau restart); the run summary prints the
chosen t and every reduction event.
"""

from __future__ import annotations

import argparse
import time


def _parse_t(value: str) -> int | str:
    if value == "auto":
        return "auto"
    try:
        t = int(value)
    except ValueError:
        raise argparse.ArgumentTypeError(f"--t must be a positive int or 'auto', got {value!r}")
    if t < 1:
        raise argparse.ArgumentTypeError(f"--t must be >= 1, got {t}")
    return t


def _print_adaptive_summary(res) -> None:
    """Chosen t, selection table, and reduction events for the run summary."""
    if res.selection is not None:
        print(res.selection.summary())
    events = res.reduction_events()
    if events:
        for k, before, after in events:
            kind = "re-enlarged" if after > before else "reduced"
            print(f"  iter {k}: active width {kind} {before} -> {after}")
        if res.restarts:
            print(f"  restarts: {res.restarts}")
    elif res.active_hist is not None:
        print(f"  active width constant at t={res.t}")
    if res.comm_segments and len(res.comm_segments) > 1:
        trace = ", ".join(f"{it} iters @ width {w}" for w, it in res.comm_segments)
        print(f"  exchange payload re-sliced: {trace}")
    if res.breakdown:
        print("  BREAKDOWN: solver stopped at the last finite iterate")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--matrix", default="dg", choices=["dg", "fd", "random"])
    ap.add_argument("--elements", type=int, default=16)
    ap.add_argument("--block", type=int, default=16)
    ap.add_argument("--t", type=_parse_t, default=8,
                    help="enlarging factor, or 'auto' to pick it from the "
                         "iterations-vs-cost model")
    ap.add_argument("--tol", type=float, default=None,
                    help="residual tolerance (default: 1e-8 in float64, "
                         "1e-4 in float32)")
    ap.add_argument("--strategy", default="tuned",
                    choices=["sequential", "standard", "2step", "3step", "optimal", "tuned"])
    ap.add_argument("--devices", type=int, default=0,
                    help="distributed solve on this many devices (virtual "
                         "host devices under JAX_PLATFORMS=cpu; re-execs)")
    ap.add_argument("--ppn", type=int, default=None,
                    help="ranks per node of the (node, proc) mesh "
                         "(default: devices // 2)")
    ap.add_argument("--backend", default="jnp", choices=["jnp", "pallas"])
    ap.add_argument("--overlap", action="store_true",
                    help="hide halo exchange behind interior SpMBV compute")
    ap.add_argument("--ell-block", type=int, default=8, help="Block-ELL tile size")
    ap.add_argument("--tune", default=None,
                    choices=["model", "model:structural", "measure", "off"],
                    help="autotune strategy/tile/overlap (default: model when "
                         "--strategy tuned or --t auto, else off; "
                         "model:structural ranks strategies by the executor-"
                         "structural cost — plan dispatches + moved bytes — "
                         "the right model on host/TPU backends)")
    ap.add_argument("--adaptive", default=None,
                    choices=["off", "rankrev", "reduce", "reduce+restart"],
                    help="in-solve width controller: breakdown-safe rank "
                         "reveal / flexible-ECG reduction / plateau restart "
                         "(default: off, except --t auto implies rankrev; an "
                         "explicit 'off' is honored even with --t auto)")
    ap.add_argument("--method", default="classic",
                    choices=["classic", "pipelined", "sstep"],
                    help="iteration scheme: classic two-psum ECG, pipelined "
                         "(packed Gram psum overlapped with the SpMBV "
                         "exchange), or sstep (--s inner steps per psum pair)")
    ap.add_argument("--s", type=int, default=1,
                    help="s-step depth: inner iterations per collective pair "
                         "(sstep only)")
    ap.add_argument("--reorth", action="store_true",
                    help="sstep only: per-block Cholesky-QR2 second pass "
                         "(one extra psum per block) for tougher spectra")
    ap.add_argument("--precondition", default="none",
                    choices=["none", "block_jacobi", "chebyshev", "inexact"],
                    help="preconditioner: rank-local block-Jacobi, Chebyshev "
                         "polynomial, or the iteration-varying inexact kind "
                         "(flexible ECG; classic reseeds the residual, "
                         "incompatible with --method pipelined)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a trace of the run: *.json = Chrome/Perfetto "
                         "trace (open in chrome://tracing or ui.perfetto.dev), "
                         "*.jsonl = append-only event log")
    args = ap.parse_args()
    if args.method == "pipelined" and args.precondition == "inexact":
        ap.error("--precondition inexact needs the flexible residual reseed, "
                 "which --method pipelined cannot absorb into its AZ "
                 "recurrence; use --method classic or sstep, or a fixed "
                 "preconditioner")
    if args.method != "sstep":
        if args.s != 1:
            ap.error(f"--s {args.s} only applies to --method sstep")
        if args.reorth:
            ap.error("--reorth only applies to --method sstep")
    if args.t == "auto" and args.tune == "off":
        ap.error("--t auto composes the tuner's cost models and cannot run "
                 "with --tune off; use --tune model (or --tune measure — the "
                 "t ranking itself is always model-based, measured "
                 "calibration applies to the operator tuning)")
    if args.t == "auto" and args.tune == "measure":
        print("note: --t auto ranks candidates with the model-mode cost; "
              "--tune measure calibrates the distributed operator tuning only")
    if args.tune is None:
        args.tune = "model" if (args.strategy == "tuned" or args.t == "auto") else "off"

    from repro.launch.runtime import (
        default_tol, force_host_devices, setup_compilation_cache,
        solver_dtype,
    )

    force_host_devices(args.devices, "repro.launch.solve")
    import numpy as np
    import jax.numpy as jnp

    dtype = solver_dtype()
    setup_compilation_cache()
    if args.tol is None:
        args.tol = default_tol(dtype)

    tracer = None
    if args.trace:
        # install as the ambient tracer: the solver build/solve spans and
        # counters flow to the sink without threading the handle through
        from repro.observe import Tracer, open_sink, set_tracer

        tracer = Tracer(sinks=[open_sink(args.trace)])
        set_tracer(tracer)

    def _close_trace():
        if tracer is not None:
            tracer.close()
            print(f"# trace written to {args.trace}")

    from repro.sparse import dg_laplace_2d, fd_laplace_2d, random_spd, csr_spmbv
    from repro.core.cg import _cg_solve
    from repro.core.machines import TPU_V5E_POD
    from repro.core.methods import get_method
    from repro.launch.mesh import make_solver_mesh
    from repro.serve.packing import true_relres
    from repro.solver import (
        AdaptiveConfig, CommConfig, ECGSolver, KernelConfig, MethodConfig,
        SolverConfig, TuneConfig,
    )

    a = {
        "dg": lambda: dg_laplace_2d((args.elements, args.elements),
                                    block=args.block, dtype=dtype),
        "fd": lambda: fd_laplace_2d(args.elements * 4, dtype=dtype),
        "random": lambda: random_spd(1024, density=0.02, dtype=dtype),
    }[args.matrix]()
    rng = np.random.default_rng(0)
    b = rng.standard_normal(a.shape[0]).astype(dtype)
    print(f"matrix: {a.shape[0]} rows, {a.nnz} nnz, {np.dtype(dtype).name}; t={args.t}")

    sequential = args.strategy == "sequential" or not args.devices
    mesh = None if sequential else make_solver_mesh(args.devices, args.ppn)
    if sequential and args.tune == "measure":
        print("note: measured tuning needs a device mesh; using the model "
              "for the sequential run")
        args.tune = "model"
    strategy = args.strategy if args.strategy not in ("sequential", "tuned") else "standard"
    config = SolverConfig(
        t=args.t,
        tol=args.tol,
        max_iters=5000,
        comm=CommConfig(
            strategy=strategy,
            overlap=args.overlap,
            machine=(None if sequential
                     else TPU_V5E_POD.with_ppn(mesh.shape["proc"])),
        ),
        kernel=KernelConfig(backend=args.backend, ell_block=args.ell_block),
        # None = solver defaults (auto-t turns on rankrev); explicit "off" sticks
        adaptive=AdaptiveConfig(policy=args.adaptive),
        tune=TuneConfig(mode=args.tune),
        method=MethodConfig(name=args.method, s=args.s, reorth=args.reorth),
        precondition=args.precondition,
    )
    if config.precondition.active:
        print(f"preconditioner: {config.precondition.kind}")
    coll = get_method(args.method).collectives_per_iteration(args.s, args.reorth)
    mtag = args.method + (f"[s={args.s}]" if args.method == "sstep" else "")
    print(f"method: {mtag} ({coll:g} psums/iter)")

    if sequential:
        solver = ECGSolver.build(a, config=config, b=b)
        if solver.tuned is not None:
            print(f"tuned tile: {solver.tuned.ell_block} kmax={solver.tuned.kmax}")
        t0 = time.time()
        res = solver.solve(b)
        print(f"sequential ECG[{mtag}/{args.backend}] t={res.t}: iters={res.n_iters} "
              f"converged={res.converged} {time.time()-t0:.1f}s")
        _print_adaptive_summary(res)
        res_cg = _cg_solve(lambda v: csr_spmbv(a, v[:, None])[:, 0], jnp.asarray(b), tol=args.tol, max_iters=20000)
        print(f"reference CG:  iters={res_cg.n_iters}")
        _close_trace()
        return

    n_dev = mesh.size
    t0 = time.time()
    solver = ECGSolver.build(a, mesh, config, b=b)
    res = solver.solve(b)
    if solver.tuned is not None:
        cfg = solver.tuned
        strategy = cfg.strategy
        print(f"tuned[{cfg.mode}]: strategy={cfg.strategy} tile={cfg.ell_block} "
              f"kmax={cfg.kmax} overlap={cfg.overlap} col_split={cfg.col_split}")
        if "p2p" in cfg.predicted:
            print("  p2p model:",
                  {k: f"{v*1e6:.0f}us" for k, v in cfg.predicted["p2p"].items()})
    relres = true_relres(a, solver.unshard(res.x), b)
    print(
        f"distributed ECG[{mtag}/{strategy}/{args.backend}"
        f"{'/overlap' if solver.op.overlap else ''}] t={res.t} on {n_dev} devices: "
        f"iters={res.n_iters} converged={res.converged} relres={relres:.2e} "
        f"{time.time()-t0:.1f}s"
    )
    _print_adaptive_summary(res)
    _close_trace()


if __name__ == "__main__":
    main()
