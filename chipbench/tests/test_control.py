"""The control of the check (``control.py``): the plain reference solver
with every product at three bfloat16 passes.  On the chip at the cells'
size it ends far outside the limit (PERF.md); at the sizes a test holds,
float32 ECG converges either way, so here the control's arithmetic and its
drive through a cell's files are what is checked."""

import numpy as np
import pytest

from chipbench import reference
from chipbench.control import control_readings
from chipbench.harness import Bench
from chipbench.tests.small import small_bench


def rel_err(precision):
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    x = rng.standard_normal((64, 256)).astype(np.float32)
    y = rng.standard_normal((256, 8)).astype(np.float32)
    exact = x.astype(np.float64) @ y.astype(np.float64)
    got = np.asarray(reference._products(precision)("ij,jk->ik", jnp.asarray(x), jnp.asarray(y)))
    return np.abs(got - exact).max() / np.abs(exact).max()


def test_three_passes_lose_float32_digits():
    highest, high = rel_err("highest"), rel_err("high")
    assert highest < 1e-6
    # bfloat16 x 3 keeps about 16 bits: far worse than float32, far better than 1 pass
    assert 10 * highest < high < 1e-4


def test_unknown_precision_refused():
    with pytest.raises(ValueError):
        reference._products("default")


def test_control_runs_through_a_cells_files(tmp_path):
    root, cell = small_bench(tmp_path)
    rows = control_readings(Bench(root), cell, [0, 1], "high", log=lambda s: None)
    limit = Bench(root).limits(cell)["true_relres_max"]
    assert [r["rhs"] for r in rows] == [0, 1]
    for r in rows:
        assert r["limit"] == limit and r["iters"] > 0
        assert r["true_relres"] > 0
        assert r["correct"] == (r["converged"] and r["true_relres"] <= limit)


def test_witness_solves_to_tolerance(tmp_path):
    root, cell = small_bench(tmp_path)
    (row,) = control_readings(Bench(root), cell, [0], "highest", log=lambda s: None)
    tol = Bench(root).traffic("rhs_stream")["tol"]
    assert row["converged"] and row["true_relres"] < 2 * tol
