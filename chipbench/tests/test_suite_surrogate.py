"""The benchmark's copy of the Table 3 audikw_1 surrogate
(``operators/suite_surrogate.py``) against the program's generator, its
full-size counts, and the float64 reference on it."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import reference
from chipbench.operators import suite_surrogate
from chipbench.tests.test_operators import to_dense

AUDIKW = json.loads((Path(__file__).resolve().parents[1] / "configs"
                     / "audikw1_1chip.json").read_text())


def surrogate(scale, dtype=np.float32):
    """The copy at the grid and window the program's ``suite_surrogate``
    derives from ``scale``."""
    op = AUDIKW["operator"]
    grid = [max(2, int(g * scale)) for g in op["grid"]]
    window = max(16, int(op["window"] * scale))
    return suite_surrogate.generate(grid, op["block"], window, op["perm_seed"], np.dtype(dtype))


@pytest.mark.parametrize("scale,elements", [
    (0.05, 144),   # 12 x 12: 144 block rows, a ragged second lane group
    (0.066, 256),  # 16 x 16: two whole lane groups, windows of 135 and a short one
    (0.03, 49),    # 7 x 7: one window larger than the grid
    (0.1, 576),    # 24 x 24: two full windows of 204 and a short one
    (0.15, 1296),  # 36 x 36: four full windows of 307 and a short one
])
def test_surrogate_matches_program_generator(scale, elements):
    import jax.numpy as jnp

    from repro.sparse import suite_surrogate as program

    a = program("audikw_1", scale=scale, dtype=jnp.float32)
    h = surrogate(scale)
    assert h.n == a.shape[0] == elements * 16 and h.nnz == a.nnz
    np.testing.assert_array_equal(h.indptr, np.asarray(a.indptr))
    np.testing.assert_array_equal(h.indices, np.asarray(a.indices))
    assert h.data.dtype == np.asarray(a.data).dtype == np.float32
    np.testing.assert_array_equal(h.data, np.asarray(a.data))


def test_surrogate_config_matches_program():
    from repro.sparse.matrices import SUITE_MATRICES, surrogate_perm_seed

    spec, op = SUITE_MATRICES["audikw_1"], AUDIKW["operator"]
    assert op["perm_seed"] == surrogate_perm_seed("audikw_1")
    assert (tuple(op["grid"]), op["block"], op["window"]) == (spec.grid, spec.block, spec.window)


def test_surrogate_full_size_counts_from_formula():
    # 243 x 243 elements: 5 stencil entries less the boundary's missing
    # neighbours, each a dense 16x16 block; the window shuffle moves none
    nx, ny, b = 243, 243, 16
    assert (nx * ny * 5 - 2 * (nx + ny)) * b * b == AUDIKW["nnz"] == 75_333_888
    assert nx * ny * b == AUDIKW["rows"] == 944_784


def test_reference_on_surrogate_against_dense_solve():
    h = surrogate(0.05, np.float64)
    b = np.random.default_rng(5).standard_normal(h.n)
    x = np.linalg.solve(to_dense(h), b)
    assert reference.true_relres(h, x, b) < 1e-12
