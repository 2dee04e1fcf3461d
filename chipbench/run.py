#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout; ``harness.py`` says what a
run does.  The last line of standard output is the result object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``, each number compared
with its limit; the checks are also the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        from chipbench import harness
        import repro.solver  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"chipbench: cannot import the benchmark or the program: {e}",
              file=sys.stderr)
        return 2
    return harness.main(args, t_start=T_START, root=ROOT)


if __name__ == "__main__":
    sys.exit(main())
