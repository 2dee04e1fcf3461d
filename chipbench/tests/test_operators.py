"""The benchmark's own operator and its float64 reference."""

import numpy as np
import pytest

from chipbench import reference
from chipbench.operators import dg_laplace_2d


def dg(elements, dtype=np.float32):
    return dg_laplace_2d.generate(elements, 16, np.dtype(dtype))


@pytest.mark.parametrize("elements", [(8, 8), (5, 7)])
def test_dg_matches_program_generator(elements):
    import jax.numpy as jnp

    from repro.sparse import dg_laplace_2d

    a = dg_laplace_2d(elements, block=16, dtype=jnp.float32)
    h = dg(elements)
    assert h.n == a.shape[0] and h.nnz == a.nnz
    np.testing.assert_array_equal(h.indptr, np.asarray(a.indptr))
    np.testing.assert_array_equal(h.indices, np.asarray(a.indices))
    assert h.data.dtype == np.asarray(a.data).dtype == np.float32
    np.testing.assert_array_equal(h.data, np.asarray(a.data))


def test_dg_full_size_counts_from_formula():
    # (320, 256) elements: 5 stencil entries less the boundary's missing
    # neighbours, each a dense 16x16 block
    nx, ny, b = 320, 256, 16
    assert (nx * ny * 5 - 2 * (nx + ny)) * b * b == 104_562_688
    assert nx * ny * b == 1_310_720


def to_dense(h):
    a = np.zeros((h.n, h.n))
    rows = np.repeat(np.arange(h.n), np.diff(h.indptr))
    a[rows, h.indices] = h.data
    return a


def test_reference_matvec_matches_dense():
    h = dg((3, 4), np.float64)
    dense = to_dense(h)
    rng = np.random.default_rng(3)
    x = rng.standard_normal(h.n)
    np.testing.assert_allclose(reference.matvec(h, x), dense @ x, rtol=1e-13, atol=1e-13)
    xs = rng.standard_normal((h.n, 3))
    np.testing.assert_allclose(reference.matvec(h, xs), dense @ xs, rtol=1e-13, atol=1e-13)
    b = dense @ x
    assert reference.true_relres(h, x, b) < 1e-14
    assert reference.true_relres(h, np.zeros(h.n), b) == pytest.approx(1.0)


def test_reference_matvec_in_row_blocks(monkeypatch):
    h = dg((4, 4), np.float64)
    x = np.random.default_rng(4).standard_normal(h.n)
    whole = reference.matvec(h, x)
    monkeypatch.setattr(reference, "_ROWS_PER_BLOCK", 7)  # blocks split rows unevenly
    np.testing.assert_allclose(reference.matvec(h, x), whole, rtol=1e-14, atol=1e-14)


def test_reference_tiles_rebuild_the_matrix():
    h = dg((3, 3))
    vals, idx = reference.tiles(h, 16)
    assert vals.shape == (9, 5, 16, 16) and idx.shape == (9, 5)
    dense = np.zeros((h.n, h.n), np.float32)
    for r in range(9):
        for k in range(5):
            dense[r * 16:(r + 1) * 16, idx[r, k] * 16:(idx[r, k] + 1) * 16] += vals[r, k]
    np.testing.assert_array_equal(dense, to_dense(h).astype(np.float32))
