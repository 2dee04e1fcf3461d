"""Sparse substrate: containers, generators, partitioning."""

import numpy as np
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.sparse import (
    CSRMatrix,
    csr_spmv,
    csr_spmbv,
    csr_to_bsr,
    dg_laplace_2d,
    fd_laplace_2d,
    fd_laplace_3d,
    random_spd,
    suite_surrogate,
    partition_csr,
    SUITE_MATRICES,
)
from repro.sparse.matrices import example_2_1_graph, window_shuffle_perm


def dense(a):
    return np.asarray(a.todense(), np.float64)


class TestGenerators:
    def test_fd_laplace_2d_spd(self):
        a = fd_laplace_2d(10)
        d = dense(a)
        assert np.allclose(d, d.T)
        assert np.linalg.eigvalsh(d).min() > 0

    def test_fd_laplace_3d_spd(self):
        a = fd_laplace_3d(4)
        d = dense(a)
        assert np.allclose(d, d.T)
        assert np.linalg.eigvalsh(d).min() > 0

    def test_dg_laplace_structure(self):
        # Example 2.1 shape law: rows = elements * block, nnz/row ~= 5*block
        a = dg_laplace_2d((8, 8), block=16)
        assert a.shape[0] == 8 * 8 * 16
        assert a.nnz / a.shape[0] == pytest.approx(5 * 16, rel=0.1)
        d = dense(a)
        assert np.allclose(d, d.T, atol=1e-12)
        assert np.linalg.eigvalsh(d).min() > 0

    def test_example_2_1_full_scale_stats(self):
        # At full scale the surrogate must match the paper's published size.
        g, blk = example_2_1_graph()
        rows = g.shape[0] * blk
        nnz = g.nnz * blk * blk
        assert rows == 1_310_720
        assert abs(nnz - 104_529_920) / 104_529_920 < 0.001

    def test_random_spd(self):
        a = random_spd(40, density=0.2, seed=3)
        d = dense(a)
        assert np.allclose(d, d.T)
        assert np.linalg.eigvalsh(d).min() > 0

    @pytest.mark.parametrize("name", ["Geo_1438", "thermal2"])
    def test_suite_surrogate_stats(self, name):
        spec = SUITE_MATRICES[name]
        a = suite_surrogate(name, scale=0.1)
        # structure class preserved: nnz/row within 25% of published
        assert a.nnz / a.shape[0] == pytest.approx(spec.nnz_per_row, rel=0.30)

    def test_window_shuffle_is_permutation(self):
        p = window_shuffle_perm(1000, 64, seed=5)
        assert np.array_equal(np.sort(p), np.arange(1000))


class TestSpMV:
    def test_spmv_matches_dense(self, rng):
        a = dg_laplace_2d((5, 4), block=4)
        d = dense(a)
        v = rng.standard_normal(a.shape[0])
        assert np.allclose(np.asarray(csr_spmv(a, jnp.asarray(v))), d @ v, atol=1e-10)

    @pytest.mark.parametrize("t", [1, 2, 5, 20])
    def test_spmbv_matches_dense(self, rng, t):
        a = fd_laplace_2d(9)
        d = dense(a)
        V = rng.standard_normal((a.shape[0], t))
        W = np.asarray(csr_spmbv(a, jnp.asarray(V)))
        assert np.allclose(W, d @ V, atol=1e-10)

    def test_from_dense_roundtrip(self, rng):
        m = rng.standard_normal((7, 9)) * (rng.random((7, 9)) < 0.4)
        a = CSRMatrix.from_dense(m)
        assert np.allclose(np.asarray(a.todense()), m)


class TestBSR:
    @pytest.mark.parametrize("br,bc", [(2, 2), (4, 4), (4, 8)])
    def test_bsr_roundtrip(self, rng, br, bc):
        a = dg_laplace_2d((4, 4), block=4)
        b = csr_to_bsr(a, br, bc)
        db = np.asarray(b.todense(), np.float64)[: a.shape[0], : a.shape[1]]
        assert np.allclose(db, dense(a), atol=1e-12)

    @given(
        n=st.integers(6, 24),
        br=st.sampled_from([2, 3, 4]),
        seed=st.integers(0, 2**16),
    )
    @settings(max_examples=20, deadline=None)
    def test_bsr_roundtrip_property(self, n, br, seed):
        rng = np.random.default_rng(seed)
        m = rng.standard_normal((n, n)) * (rng.random((n, n)) < 0.3)
        a = CSRMatrix.from_dense(m)
        b = csr_to_bsr(a, br, br)
        db = np.asarray(b.todense(), np.float64)[:n, :n]
        assert np.allclose(db, m, atol=1e-12)


class TestPartition:
    @pytest.mark.parametrize("p", [2, 3, 7, 8])
    def test_partitioned_spmv_reconstructs(self, rng, p):
        a = dg_laplace_2d((6, 5), block=4)
        d = dense(a)
        pm = partition_csr(a, p)
        x = rng.standard_normal(a.shape[0])
        out = np.zeros(a.shape[0])
        for r in range(p):
            lo, hi = pm.part.local_range(r)
            xloc = np.concatenate([x[lo:hi], x[pm.halo_sources[r]]])
            ptr, idx = pm.local_indptr[r], pm.local_indices[r]
            dat = np.asarray(pm.local_data[r], np.float64)
            for i in range(hi - lo):
                out[lo + i] = dat[ptr[i] : ptr[i + 1]] @ xloc[idx[ptr[i] : ptr[i + 1]]]
        assert np.allclose(out, d @ x, atol=1e-10)

    def test_send_recv_transpose(self):
        a = fd_laplace_2d(12)
        pm = partition_csr(a, 6)
        for r in range(6):
            for q, rows in pm.comms[r].recv_rows.items():
                assert np.array_equal(pm.comms[q].send_rows[r], rows)

    def test_uneven_rows(self):
        a = fd_laplace_2d(7)  # 49 rows over 4 procs
        pm = partition_csr(a, 4)
        sizes = [pm.part.local_range(r)[1] - pm.part.local_range(r)[0] for r in range(4)]
        assert sum(sizes) == 49
        assert max(sizes) - min(sizes) <= 1

    @pytest.mark.parametrize("p", [2, 4, 6])
    def test_interior_boundary_split(self, p):
        """Interior/boundary sets partition the local rows, and interior rows
        reference no halo column (the invariant the overlap schedule needs)."""
        from repro.sparse.partition import interior_boundary_split

        a = fd_laplace_2d(11)
        pm = partition_csr(a, p)
        for r, (interior, boundary) in enumerate(interior_boundary_split(pm)):
            lo, hi = pm.part.local_range(r)
            n_local = hi - lo
            assert len(interior) + len(boundary) == n_local
            assert not set(interior) & set(boundary)
            ptr, ix = pm.local_indptr[r], pm.local_indices[r]
            for row in interior:
                assert (ix[ptr[row] : ptr[row + 1]] < n_local).all()
            for row in boundary:
                assert (ix[ptr[row] : ptr[row + 1]] >= n_local).any()


def _suite_small(name, dtype=jnp.float64):
    """Small-but-representative scale per suite family: 2D block surrogates
    shrink quadratically, the 3D scalar one cubically."""
    spec = SUITE_MATRICES[name]
    scale = 0.06 if spec.block == 1 else 0.035
    return suite_surrogate(name, scale=scale, dtype=dtype)


class TestSuiteInvariants:
    """Every Table-3 surrogate must be a genuine SPD operator at any scale:
    exactly symmetric, positive definite, and with the diagonal dominating
    each row (the structural property the Laplacian-plus-block construction
    promises).  These invariants are what the preconditioner builders
    (Cholesky block factors, Chebyshev bounds, positive diagonals) rely on."""

    @pytest.mark.parametrize("name", sorted(SUITE_MATRICES))
    def test_symmetric_spd_diag_dominant(self, name):
        a = _suite_small(name)
        d = dense(a)
        assert d.shape[0] >= 32  # scale kept it non-degenerate
        np.testing.assert_allclose(d, d.T, atol=1e-12)
        assert np.linalg.eigvalsh(d).min() > 0
        diag = np.diag(d)
        assert (diag > 0).all()
        if SUITE_MATRICES[name].block == 1:
            # scalar stencils are weakly diagonally dominant; the kron-block
            # surrogates are SPD by construction but trade dominance for the
            # published nnz/row, so only the scalar family asserts it
            off = np.abs(d).sum(axis=1) - np.abs(diag)
            assert (diag >= off * (1 - 1e-12)).all(), (
                f"{name}: diagonal dominance violated"
            )

    @pytest.mark.parametrize("name", sorted(SUITE_MATRICES))
    @pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
    def test_convergence_smoke(self, name, dtype):
        """ECG at t=4 converges on every surrogate in both dtypes."""
        from repro.solver import ECGSolver, SolverConfig

        a = _suite_small(name, dtype=dtype)
        b = np.random.default_rng(11).standard_normal(a.shape[0]).astype(
            np.float64 if dtype == jnp.float64 else np.float32
        )
        tol = 1e-9 if dtype == jnp.float64 else 5e-4
        res = ECGSolver.build(
            a, config=SolverConfig(t=4, tol=tol, max_iters=4000)
        ).solve(b)
        assert res.converged, f"{name}/{np.dtype(dtype).name} did not converge"
        relres = np.linalg.norm(
            dense(a) @ np.asarray(res.x, np.float64) - b
        ) / np.linalg.norm(b)
        assert relres < (1e-7 if dtype == jnp.float64 else 5e-2)

    def test_dtype_respected(self):
        a32 = _suite_small("thermal2", dtype=jnp.float32)
        assert a32.data.dtype == jnp.float32


class TestIllConditionedGenerators:
    def test_aniso_laplace_2d_spd_and_conditioning(self):
        from repro.sparse import aniso_laplace_2d

        eps = 0.01
        a = aniso_laplace_2d(12, eps=eps)
        d = dense(a)
        np.testing.assert_allclose(d, d.T, atol=1e-12)
        ev = np.linalg.eigvalsh(d)
        assert ev.min() > 0
        # the stencil is genuinely anisotropic: x-coupling −1, y-coupling −eps
        np.testing.assert_allclose(np.diag(d), 2 + 2 * eps)
        np.testing.assert_allclose(d[0, 12], -1.0)  # x neighbor (row-major y,x)
        np.testing.assert_allclose(d[0, 1], -eps)   # y neighbor
        # small eigenvalues cluster: many more modes below the isotropic
        # minimum, which is what slows unpreconditioned CG down
        iso_min = np.linalg.eigvalsh(dense(fd_laplace_2d(12))).min()
        assert (ev < iso_min).sum() >= 8
        with pytest.raises(ValueError, match="eps"):
            aniso_laplace_2d(8, eps=0.0)

    def test_scaled_laplace_2d_spd_and_conditioning(self):
        from repro.sparse import scaled_laplace_2d

        a = scaled_laplace_2d(12, decades=4.0, seed=0)
        d = dense(a)
        np.testing.assert_allclose(d, d.T, atol=1e-9)
        ev = np.linalg.eigvalsh(d)
        assert ev.min() > 0
        iso = dense(fd_laplace_2d(12))
        ev_iso = np.linalg.eigvalsh(iso)
        assert ev.max() / ev.min() > 100 * ev_iso.max() / ev_iso.min()
        # seeds are reproducible and distinct
        same = dense(scaled_laplace_2d(12, decades=4.0, seed=0))
        np.testing.assert_array_equal(d, same)
        other = dense(scaled_laplace_2d(12, decades=4.0, seed=1))
        assert not np.array_equal(d, other)
        with pytest.raises(ValueError, match="decades"):
            scaled_laplace_2d(8, decades=0.0)


_SURROGATE_DIGEST = r"""
import hashlib, sys
import numpy as np
from repro.sparse.matrices import suite_surrogate, surrogate_graph

gen = sys.argv[1]
a = (suite_surrogate("audikw_1", scale=0.05) if gen == "suite_surrogate"
     else surrogate_graph("audikw_1", scale=0.05)[0])
h = hashlib.sha256()
for arr in (a.indptr, a.indices, a.data):
    h.update(np.asarray(arr).tobytes())
print(h.hexdigest())
"""


class TestSurrogateSeed:
    """The window shuffle of a Table-3 surrogate is seeded from its name by
    a function that does not change between processes (Python salts the
    hash of a ``str`` per process, by ``PYTHONHASHSEED``)."""

    @pytest.mark.parametrize("gen", ["suite_surrogate", "surrogate_graph"])
    def test_same_arrays_in_every_process(self, gen):
        import os
        import subprocess
        import sys
        from pathlib import Path

        src = str(Path(__file__).resolve().parents[1] / "src")
        digests = set()
        for hash_seed in ("1", "2"):
            env = dict(os.environ, PYTHONHASHSEED=hash_seed, JAX_PLATFORMS="cpu",
                       PYTHONPATH=src)
            out = subprocess.run([sys.executable, "-c", _SURROGATE_DIGEST, gen], env=env,
                                 capture_output=True, text=True, timeout=300)
            assert out.returncode == 0, out.stderr[-2000:]
            digests.add(out.stdout.split()[-1])
        assert len(digests) == 1

    def test_seed_is_the_crc_of_the_name(self):
        from repro.sparse.matrices import surrogate_perm_seed

        assert surrogate_perm_seed("audikw_1") == 286_300_102
        assert len({surrogate_perm_seed(n) for n in SUITE_MATRICES}) == len(SUITE_MATRICES)
