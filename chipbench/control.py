#!/usr/bin/env python3
"""The control of a cell's check: the plain reference solver in the
program's place, one precision below the configuration's.

    python chipbench/control.py --workload dg_ex21_1chip.rhs_stream --rhs 0 1 2
    python chipbench/control.py --workload ... --rhs 0 --precision highest

The configuration states float32 with products at ``highest``; the control
takes every product at ``high`` (three bfloat16 passes), the step a later
change would be tempted to take.  Each ``--rhs i`` solves right-hand side
``i`` of the traffic's seeded draw (``harness.rhs_for``; a run's pool is
its first ``pool`` of them) with ``reference.ecg_solve`` at the cell's
tolerance and iteration cap, and is judged by the cell's limits as a run
would be.  At ``--precision highest`` the same solver is a second
witness of what sound float32 reaches.  It prints one JSON line per
right-hand side; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_readings(bench, cell_name: str, rhs, precision: str, log=print) -> list[dict]:
    from chipbench import harness, reference

    cell = bench.cell(cell_name)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    limits = bench.limits(cell_name)
    host = bench.operator(config)
    vals, idx = reference.tiles(host, config["solver"]["ell_block"][0])
    out = []
    for i in rhs:
        b = harness.rhs_for(traffic, host.n, i, config["dtype"])
        t0 = time.perf_counter()
        x, iters, converged = reference.ecg_solve(
            vals, idx, b, t=config["solver"]["t"], tol=float(traffic["tol"]),
            max_iters=config["solver"]["max_iters"], precision=precision)
        relres = reference.true_relres(host, x, b)
        row = {"cell": cell_name, "precision": precision, "rhs": i,
               "iters": iters, "converged": converged, "true_relres": relres,
               "limit": limits["true_relres_max"],
               "correct": converged and relres <= limits["true_relres_max"],
               "seconds": time.perf_counter() - t0}
        log(json.dumps(row))
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rhs", type=int, nargs="+", required=True)
    ap.add_argument("--precision", choices=("high", "highest"), default="high")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness

    control_readings(harness.Bench(ROOT), args.workload, args.rhs, args.precision)
    return 0


if __name__ == "__main__":
    sys.exit(main())
