"""Sparse test-matrix generators.

``dg_laplace_2d`` reproduces the *structure* of the paper's Example 2.1 (a
discontinuous-Galerkin discretization of the Laplacian on the unit square:
dense element blocks on a 5-point element stencil).  At full scale
(``elements=(320, 256), block=16``) it yields exactly 1 310 720 rows and
~104.5M nonzeros (within 0.04% of the paper's 104 529 920 — the tiny gap is
boundary-face bookkeeping of the unknown exact MFEM grid).

The SuiteSparse matrices of Table 3 cannot be downloaded in this offline
container; ``suite_surrogate`` generates *structural surrogates* matched to
published rows / nnz-per-row / density (see DESIGN.md §5).
"""

from __future__ import annotations

import dataclasses
import zlib

import numpy as np
import jax.numpy as jnp

from repro.sparse.csr import CSRMatrix


def _kron_block_csr(
    indptr: np.ndarray,
    indices: np.ndarray,
    data: np.ndarray,
    n: int,
    block: np.ndarray,
) -> CSRMatrix:
    """CSR(L) ⊗ dense SPD block  ->  CSR.  Kronecker of SPD x SPD is SPD."""
    b = block.shape[0]
    nnz = len(indices)
    # each scalar nonzero becomes a dense b x b block
    new_indptr = np.zeros(n * b + 1, dtype=np.int64)
    row_counts = np.diff(indptr)
    per_row = np.repeat(row_counts, b) * b
    new_indptr[1:] = np.cumsum(per_row)

    new_indices = np.empty(nnz * b * b, dtype=np.int32)
    new_data = np.empty(nnz * b * b, dtype=block.dtype)
    # block row layout: for each of the b sub-rows of scalar row i, all
    # (col, b) entries of row i — nonzero p = j-th of row i, sub-row r and
    # block column c land at new_indptr[i*b + r] + j*b + c
    offs = np.arange(b)
    row_of = np.repeat(np.arange(n), row_counts)
    j = np.arange(nnz) - np.asarray(indptr)[row_of]
    pos = (
        new_indptr[(row_of * b)[:, None] + offs][:, :, None]
        + (j * b)[:, None, None]
        + offs[None, None, :]
    )
    new_indices[pos] = (np.asarray(indices, np.int64)[:, None] * b + offs)[:, None, :]
    new_data[pos] = np.asarray(data)[:, None, None] * block[None, :, :]
    return CSRMatrix(
        indptr=jnp.asarray(new_indptr, jnp.int32),
        indices=jnp.asarray(new_indices),
        data=jnp.asarray(new_data),
        shape=(n * b, n * b),
    )


def _grid_laplacian_2d(nx: int, ny: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """5-point Laplacian (Dirichlet) on an nx x ny grid, scalar CSR arrays."""
    n = nx * ny
    idx = np.arange(n).reshape(nx, ny)
    rows, cols, vals = [], [], []
    for i in range(nx):
        for j in range(ny):
            r = idx[i, j]
            rows.append(r), cols.append(r), vals.append(4.0)
            for di, dj in ((-1, 0), (1, 0), (0, -1), (0, 1)):
                ii, jj = i + di, j + dj
                if 0 <= ii < nx and 0 <= jj < ny:
                    rows.append(r), cols.append(idx[ii, jj]), vals.append(-1.0)
    return _coo_to_csr(np.array(rows), np.array(cols), np.array(vals), n)


def _grid_laplacian_3d(nx: int, ny: int, nz: int):
    n = nx * ny * nz
    idx = np.arange(n).reshape(nx, ny, nz)
    rows, cols, vals = [], [], []
    for i in range(nx):
        for j in range(ny):
            for k in range(nz):
                r = idx[i, j, k]
                rows.append(r), cols.append(r), vals.append(6.0)
                for d in ((-1, 0, 0), (1, 0, 0), (0, -1, 0), (0, 1, 0), (0, 0, -1), (0, 0, 1)):
                    ii, jj, kk = i + d[0], j + d[1], k + d[2]
                    if 0 <= ii < nx and 0 <= jj < ny and 0 <= kk < nz:
                        rows.append(r), cols.append(idx[ii, jj, kk]), vals.append(-1.0)
    return _coo_to_csr(np.array(rows), np.array(cols), np.array(vals), n)


def _coo_to_csr(rows, cols, vals, n):
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(indptr[1:], rows, 1)
    indptr = np.cumsum(indptr)
    return indptr, cols.astype(np.int32), vals.astype(np.float64)


def _permute_graph(indptr, cols, vals, n, perm):
    """Symmetric permutation  A -> P A Pᵀ  of a scalar CSR graph."""
    inv = np.empty_like(perm)
    inv[perm] = np.arange(n)
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    return _coo_to_csr(inv[rows], inv[cols], vals, n)


def window_shuffle_perm(n: int, window: int, seed: int = 0) -> np.ndarray:
    """Permutation shuffling ids within windows — emulates the 'natural'
    (non-graph-partitioned) ordering of unstructured FE meshes, which scatters
    geometric neighbours across nearby index ranges.  Used for the SuiteSparse
    surrogates so comm graphs show the paper's message heterogeneity."""
    rng = np.random.default_rng(seed)
    perm = np.arange(n)
    for s in range(0, n, window):
        e = min(s + window, n)
        perm[s:e] = rng.permutation(perm[s:e])
    return perm


def _spd_block(b: int, seed: int = 7) -> np.ndarray:
    """Deterministic dense SPD b x b block with unit diagonal scale."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((b, b))
    m = q @ q.T / b + np.eye(b)
    return (m / np.linalg.norm(m, 2)).astype(np.float64) * 2.0


def fd_laplace_2d(nx: int, ny: int | None = None, dtype=jnp.float64) -> CSRMatrix:
    """5-point finite-difference Laplacian, Dirichlet BCs (SPD)."""
    ny = ny or nx
    indptr, cols, vals = _grid_laplacian_2d(nx, ny)
    return CSRMatrix(
        indptr=jnp.asarray(indptr, jnp.int32),
        indices=jnp.asarray(cols),
        data=jnp.asarray(vals, dtype),
        shape=(nx * ny, nx * ny),
    )


def fd_laplace_3d(nx: int, ny: int | None = None, nz: int | None = None, dtype=jnp.float64) -> CSRMatrix:
    ny, nz = ny or nx, nz or nx
    indptr, cols, vals = _grid_laplacian_3d(nx, ny, nz)
    n = nx * ny * nz
    return CSRMatrix(
        indptr=jnp.asarray(indptr, jnp.int32),
        indices=jnp.asarray(cols),
        data=jnp.asarray(vals, dtype),
        shape=(n, n),
    )


def dg_laplace_2d(
    elements: tuple[int, int] = (32, 32),
    block: int = 16,
    dtype=jnp.float64,
) -> CSRMatrix:
    """DG-structured Laplacian: dense ``block``-sized element blocks on the
    5-point element stencil (Example 2.1 surrogate).  SPD by construction
    (Kronecker of SPD factors)."""
    nx, ny = elements
    indptr, cols, vals = _grid_laplacian_2d(nx, ny)
    mat = _kron_block_csr(indptr, cols, vals, nx * ny, _spd_block(block))
    return CSRMatrix(mat.indptr, mat.indices, mat.data.astype(dtype), mat.shape)


def aniso_laplace_2d(
    nx: int, ny: int | None = None, eps: float = 0.01, dtype=jnp.float64
) -> CSRMatrix:
    """Anisotropic 5-point Laplacian: −u_xx − eps·u_yy (Dirichlet, SPD).

    ``eps`` ≪ 1 stretches the spectrum — the condition number grows like
    κ(isotropic)/eps, making this the standard ill-conditioned testbed where
    a preconditioner pays for itself (iterations with ``block_jacobi`` /
    ``chebyshev`` drop well below the unpreconditioned count).
    """
    if not 0 < eps <= 1:
        raise ValueError(f"eps must be in (0, 1], got {eps!r}")
    ny = ny or nx
    n = nx * ny
    idx = np.arange(n).reshape(nx, ny)
    rows, cols, vals = [], [], []
    for i in range(nx):
        for j in range(ny):
            r = idx[i, j]
            rows.append(r), cols.append(r), vals.append(2.0 + 2.0 * eps)
            for di, dj, w in ((-1, 0, 1.0), (1, 0, 1.0), (0, -1, eps), (0, 1, eps)):
                ii, jj = i + di, j + dj
                if 0 <= ii < nx and 0 <= jj < ny:
                    rows.append(r), cols.append(idx[ii, jj]), vals.append(-w)
    indptr, cols_s, vals_s = _coo_to_csr(np.array(rows), np.array(cols), np.array(vals), n)
    return CSRMatrix(
        indptr=jnp.asarray(indptr, jnp.int32),
        indices=jnp.asarray(cols_s),
        data=jnp.asarray(vals_s, dtype),
        shape=(n, n),
    )


def scaled_laplace_2d(
    nx: int,
    ny: int | None = None,
    decades: float = 4.0,
    seed: int = 0,
    dtype=jnp.float64,
) -> CSRMatrix:
    """Diagonally-scaled 5-point Laplacian: D^{1/2} L D^{1/2} with D drawn
    log-uniformly over ``decades`` orders of magnitude (SPD by congruence).

    Models wildly varying coefficients/row scales — the regime where
    (block-)Jacobi preconditioning is near-optimal, since M captures
    exactly the diagonal scaling that inflates κ.
    """
    if decades <= 0:
        raise ValueError(f"decades must be > 0, got {decades!r}")
    ny = ny or nx
    n = nx * ny
    indptr, cols, vals = _grid_laplacian_2d(nx, ny)
    rng = np.random.default_rng(seed)
    d_half = np.power(10.0, rng.uniform(-decades / 2, decades / 2, size=n))
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    vals = vals * d_half[rows] * d_half[cols]
    return CSRMatrix(
        indptr=jnp.asarray(indptr, jnp.int32),
        indices=jnp.asarray(cols),
        data=jnp.asarray(vals, dtype),
        shape=(n, n),
    )


def random_spd(n: int, density: float = 0.05, seed: int = 0, dtype=jnp.float64) -> CSRMatrix:
    """Random sparse SPD: A = B Bᵀ + n·I structure via symmetrized mask."""
    rng = np.random.default_rng(seed)
    mask = rng.random((n, n)) < density
    mask = mask | mask.T
    np.fill_diagonal(mask, True)
    vals = rng.standard_normal((n, n)) * mask
    vals = (vals + vals.T) / 2
    # diagonal dominance => SPD
    np.fill_diagonal(vals, np.abs(vals).sum(axis=1) + 1.0)
    dense = vals
    rows, cols = np.nonzero(dense)
    indptr, cols_s, vals_s = _coo_to_csr(rows, cols, dense[rows, cols], n)
    return CSRMatrix(
        indptr=jnp.asarray(indptr, jnp.int32),
        indices=jnp.asarray(cols_s),
        data=jnp.asarray(vals_s, dtype),
        shape=(n, n),
    )


@dataclasses.dataclass(frozen=True)
class SuiteSpec:
    """Published stats (paper Table 3) + surrogate generator parameters."""

    rows: int
    nnz: int
    nnz_per_row: float
    # surrogate params: block size + element grid (2D) or grid (3D stencil)
    block: int
    grid: tuple[int, ...]
    # id-shuffle window (elements) emulating the unstructured natural ordering;
    # 0 = keep the structured ordering
    window: int = 2048


# Table 3 of the paper.  Surrogate: dense `block` blocks on a 5-pt (2D) or
# 7-pt (3D, thermal2) stencil, grid sized so rows and nnz/row approximate the
# published values (rows_surrogate = block * prod(grid)).
SUITE_MATRICES: dict[str, SuiteSpec] = {
    "audikw_1": SuiteSpec(943_695, 77_651_847, 82.3, 16, (243, 243)),
    "Geo_1438": SuiteSpec(1_437_960, 60_236_322, 41.9, 8, (424, 424)),
    "bone010": SuiteSpec(986_703, 47_851_783, 48.5, 9, (331, 331)),
    "Emilia_923": SuiteSpec(923_136, 40_373_538, 43.7, 9, (320, 320)),
    "Flan_1565": SuiteSpec(1_565_794, 114_165_372, 72.9, 15, (323, 323)),
    "Hook_1498": SuiteSpec(1_498_023, 59_374_451, 39.6, 8, (433, 433)),
    "ldoor": SuiteSpec(952_203, 42_493_817, 44.6, 9, (325, 325)),
    "Serena": SuiteSpec(1_391_349, 64_131_971, 46.1, 9, (393, 393)),
    "thermal2": SuiteSpec(1_228_045, 8_580_313, 7.0, 1, (107, 107, 107)),
}

#: Example 2.1 of the paper: 1 310 720 rows, ~104.5M nnz at full scale.
EXAMPLE_2_1 = dict(elements=(320, 256), block=16)


def surrogate_perm_seed(name: str) -> int:
    """Seed of a surrogate's window shuffle: the CRC-32 of its name, the
    same in every process, where Python salts the hash of a ``str``."""
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def suite_surrogate(name: str, scale: float = 1.0, dtype=jnp.float64) -> CSRMatrix:
    """Structural surrogate of a Table-3 matrix (optionally scaled down).

    ``scale`` < 1 shrinks the grid linearly (rows shrink ~quadratically for 2D
    surrogates); structure class (block size, stencil) is preserved.
    """
    spec = SUITE_MATRICES[name]
    grid = tuple(max(2, int(g * scale)) for g in spec.grid)
    if len(grid) == 3:
        indptr, cols, vals = _grid_laplacian_3d(*grid)
        n = grid[0] * grid[1] * grid[2]
    else:
        indptr, cols, vals = _grid_laplacian_2d(*grid)
        n = grid[0] * grid[1]
    if spec.window:
        window = max(16, int(spec.window * scale))
        perm = window_shuffle_perm(n, window, seed=surrogate_perm_seed(name))
        indptr, cols, vals = _permute_graph(indptr, cols, vals, n, perm)
    if spec.block == 1:
        return CSRMatrix(
            indptr=jnp.asarray(indptr, jnp.int32),
            indices=jnp.asarray(cols),
            data=jnp.asarray(vals, dtype),
            shape=(n, n),
        )
    mat = _kron_block_csr(indptr, cols, vals, n, _spd_block(spec.block))
    return CSRMatrix(mat.indptr, mat.indices, mat.data.astype(dtype), mat.shape)


def surrogate_graph(name: str, scale: float = 1.0) -> tuple[CSRMatrix, int]:
    """Element-level graph of a Table-3 surrogate + its ``row_block`` factor.

    Communication statistics computed on this graph with
    ``build_comm_graph(..., row_block=block)`` are identical to dof-level
    statistics when partitions align to element blocks (DESIGN.md §5) — and
    ~block² cheaper to build, so full published scale is tractable.
    """
    spec = SUITE_MATRICES[name]
    grid = tuple(max(2, int(g * scale)) for g in spec.grid)
    if len(grid) == 3:
        indptr, cols, vals = _grid_laplacian_3d(*grid)
    else:
        indptr, cols, vals = _grid_laplacian_2d(*grid)
    n = int(np.prod(grid))
    if spec.window:
        window = max(16, int(spec.window * scale))
        perm = window_shuffle_perm(n, window, seed=surrogate_perm_seed(name))
        indptr, cols, vals = _permute_graph(indptr, cols, vals, n, perm)
    g = CSRMatrix(
        indptr=jnp.asarray(indptr, jnp.int32),
        indices=jnp.asarray(cols),
        data=jnp.asarray(vals),
        shape=(n, n),
    )
    return g, spec.block


def example_2_1_graph(scale: float = 1.0) -> tuple[CSRMatrix, int]:
    """Element-level graph of Example 2.1 (320x256 elements, block 16)."""
    nx, ny = EXAMPLE_2_1["elements"]
    nx, ny = max(2, int(nx * scale)), max(2, int(ny * scale))
    indptr, cols, vals = _grid_laplacian_2d(nx, ny)
    g = CSRMatrix(
        indptr=jnp.asarray(indptr, jnp.int32),
        indices=jnp.asarray(cols),
        data=jnp.asarray(vals),
        shape=(nx * ny, nx * ny),
    )
    return g, EXAMPLE_2_1["block"]
