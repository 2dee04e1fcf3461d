#!/usr/bin/env python3
"""Run the ECG solver's main path once on a TPU, at the paper's full size.

    python chip_smoke.py              # one chip: solve, solve_many, ECGServer
    python chip_smoke.py --chips 4    # the 2x2 ("node", "proc") path only

The operator is the paper's Example 2.1 DG Laplacian,
``dg_laplace_2d((320, 256), block=16)`` in float32: 1,310,720 rows and
about 104.5M nonzeros, stored as lane-major Block-ELL of 16x16 element tiles.
The solver is classic ECG with t = 8 and ``backend="pallas"``; right-hand
sides are seeded unit-norm Gaussians, so the absolute tolerance is also the
relative one.  Every answer is checked by its true relative residual,
recomputed in float64 numpy on the host.

One chip runs three phases:

1. ``ECGSolver.build`` then one ``solve`` to tolerance; the compiled solve
   program must contain ``tpu_custom_call`` (the Pallas kernels ran
   compiled, not as an oracle or interpreted);
2. ``solve_many`` of two more right-hand sides with no retrace;
3. an ``ECGServer`` answering three requests on the same operator, one a
   duplicate payload, with one registry build.

``--chips 4`` runs only the distributed path: the same operator on a 2x2
mesh with the ``standard`` and the node-aware ``3step`` exchange, each
compared with a one-device solve of the same right-hand side.

Any failed check ends the run with a non-zero exit; so does a machine
without a TPU.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ELEMENTS = (320, 256)  # Example 2.1 element grid
BLOCK = 16             # DG element block = Block-ELL tile
T = 8
# true relative residual, float64 on the host, against the solver's float32
# tolerance (``repro.launch.runtime.default_tol``): the float32 recurrence
# drifts from it by about 1e-4 at this size (true 1.5e-4 at recursive
# 6.3e-5 on a v5e), so the bound is 5x the tolerance
RELRES_FACTOR = 5
MAX_ITERS = 4000       # a cap: about three times what the solve needs
# distributed vs one-device iteration counts: the 2x2 solve sums its Gram
# products and tiles in another order, and in float32 that moves where the
# residual crosses the tolerance by up to about 1% of the iterations at full
# size (1,128 vs 1,136 on a v5e 2x2; identical at 81,920 rows)
ITER_SLACK_FRACTION = 0.01
SEED = 0


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX's devices are {devices[0].platform!r}; this smoke "
            "run measures the chip and has no CPU fallback"
        )
    if len(devices) < n_chips:
        raise SmokeFailure(f"--chips {n_chips} needs {n_chips} TPUs, found {len(devices)}")
    return devices


def rhs(n: int, k: int, dtype) -> list[np.ndarray]:
    rng = np.random.default_rng(SEED)
    out = []
    for _ in range(k):
        b = rng.standard_normal(n)
        out.append((b / np.linalg.norm(b)).astype(dtype))
    return out


def custom_calls(hlo: str) -> int:
    return hlo.count("tpu_custom_call")


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def tolerance() -> float:
    from repro.launch.runtime import default_tol

    return default_tol(np.float32)


def solver_config(strategy: str = "standard"):
    from repro.solver import CommConfig, KernelConfig, SolverConfig

    return SolverConfig(
        t=T, tol=tolerance(), max_iters=MAX_ITERS,
        comm=CommConfig(strategy=strategy),
        kernel=KernelConfig(backend="pallas", ell_block=(BLOCK, BLOCK)),
    )


def relres_ok(a, x, b, label: str, res) -> float:
    from repro.serve.packing import true_relres

    rr = true_relres(a, x, b)
    log(f"{label}: iters={res.n_iters} converged={bool(res.converged)} "
        f"true_relres={rr!r}")
    hist = np.asarray(res.res_hist)
    step = max(1, res.n_iters // 8)
    log(f"{label}: recursive residual " + " ".join(
        f"{k}:{hist[k]:.2e}" for k in range(0, res.n_iters + 1, step)))
    check(bool(res.converged), f"{label} did not converge")
    bound = RELRES_FACTOR * tolerance()
    check(rr <= bound, f"{label} true relres {rr} > {bound}")
    return rr


def build_operator():
    import jax.numpy as jnp

    from repro.sparse import dg_laplace_2d

    t0 = time.perf_counter()
    a = dg_laplace_2d(ELEMENTS, block=BLOCK, dtype=jnp.float32)
    log(f"operator: dg_laplace_2d{ELEMENTS} block={BLOCK} float32: "
        f"rows={a.shape[0]} nnz={a.nnz}")
    log(f"setup: matrix generation {time.perf_counter() - t0:.3f}s")
    return a


def build_solver(a, mesh=None, strategy: str = "standard"):
    from repro.solver import ECGSolver

    t0 = time.perf_counter()
    solver = ECGSolver.build(a, mesh, solver_config(strategy))
    where = "1 device" if mesh is None else f"mesh {dict(mesh.shape)}"
    log(f"setup: ECGSolver.build ({where}, {strategy}, Block-ELL conversion) "
        f"{time.perf_counter() - t0:.3f}s")
    return solver


def compile_solve(solver, label: str) -> int:
    """AOT-compile the solve program (the first solve then loads it from
    the compilation cache) and count its Pallas kernels."""
    t0 = time.perf_counter()
    hlo = solver.lowered_text()
    n_calls = custom_calls(hlo)
    log(f"compile: {label} solve program {time.perf_counter() - t0:.3f}s, "
        f"tpu_custom_call x{n_calls}")
    check(n_calls > 0, f"{label}: no tpu_custom_call in the solve program")
    return n_calls


def timed_solve(solver, b, label: str):
    t0 = time.perf_counter()
    res = solver.solve(b)
    x = solver.unshard(res.x)  # host copy: waits for the device
    log(f"solve: {label} {time.perf_counter() - t0:.3f}s")
    return res, x


def one_chip(devices) -> None:
    from repro.serve import ECGServer, ServeConfig

    a = build_operator()
    bs = rhs(a.shape[0], 6, np.float32)

    solver = build_solver(a)
    compile_solve(solver, "1-device")

    # phase 1: one solve to tolerance
    res, x = timed_solve(solver, bs[0], "phase 1")
    relres_ok(a, x, bs[0], "phase 1 rhs 0", res)

    # phase 2: solve_many of two more right-hand sides, no retrace
    traces = solver.stats.traces
    t0 = time.perf_counter()
    many = solver.solve_many(bs[1:3])
    xs = [solver.unshard(r.x) for r in many]
    log(f"solve: phase 2 solve_many x{len(many)} {time.perf_counter() - t0:.3f}s")
    for i, (r, xi) in enumerate(zip(many, xs), start=1):
        relres_ok(a, xi, bs[i], f"phase 2 rhs {i}", r)
    check(solver.stats.traces == traces,
          f"solve_many retraced ({traces} -> {solver.stats.traces})")
    log(f"phase 2: traces {traces} -> {solver.stats.traces} (no retrace)")
    del solver, res, many

    # phase 3: a server answering three requests, one a duplicate payload
    server = ECGServer(ServeConfig(solver=solver_config()))
    t0 = time.perf_counter()
    reqs = [bs[3], bs[4], bs[3]]
    tickets = [server.submit(a, b) for b in reqs]
    server.flush()
    log(f"serve: 3 requests submitted and flushed {time.perf_counter() - t0:.3f}s")
    for i, (tk, b) in enumerate(zip(tickets, reqs)):
        check(tk.done, f"request {i} not answered")
        relres_ok(a, np.asarray(server.solution(tk)), b,
                  f"phase 3 request {i}{' (dedup)' if tk.deduped else ''}",
                  tk.result)
    builds = len(server.stats()["registry"]["builds"])
    log(f"phase 3: registry builds={builds}")
    check(builds == 1, f"registry built the operator {builds} times")
    log(f"peak_bytes_in_use: {peak_bytes(devices[0])}")


def four_chips(devices) -> None:
    from repro.launch.mesh import make_solver_mesh

    a = build_operator()
    (b,) = rhs(a.shape[0], 1, np.float32)

    # every solve runs and reports before a failed check ends the run
    failures = []

    def checked(fn, *args):
        try:
            fn(*args)
        except SmokeFailure as e:
            failures.append(str(e))

    ref = build_solver(a)
    compile_solve(ref, "1-device")
    res, x = timed_solve(ref, b, "1-device reference")
    checked(relres_ok, a, x, b, "1-device reference", res)
    ref_iters = res.n_iters
    del ref, res

    mesh = make_solver_mesh(4, ppn=2)
    for strategy in ("standard", "3step"):
        solver = build_solver(a, mesh, strategy)
        compile_solve(solver, f"2x2 {strategy}")
        res, x = timed_solve(solver, b, f"2x2 {strategy}")
        checked(relres_ok, a, x, b, f"2x2 {strategy}", res)
        slack = max(5, ITER_SLACK_FRACTION * ref_iters)
        checked(check, abs(res.n_iters - ref_iters) <= slack,
                f"2x2 {strategy}: {res.n_iters} iterations vs {ref_iters} on "
                "one device")
        del solver, res
    for d in devices[:4]:
        log(f"peak_bytes_in_use[{d.id}]: {peak_bytes(d)}")
    check(not failures, "; ".join(failures))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the 2x2 distributed path")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.launch.runtime import setup_compilation_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script: {e}",
              file=sys.stderr)
        return 2
    try:
        devices = require_tpu(args.chips)
        log(f"compilation cache: {setup_compilation_cache()}")
        run = one_chip if args.chips == 1 else four_chips
        run(devices)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    import jax

    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind, "count": len(d),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
