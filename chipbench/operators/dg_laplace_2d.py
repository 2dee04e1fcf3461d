"""DG Laplacian of the paper's Example 2.1: the 5-point element stencil on
an ``elements`` grid (Dirichlet), Kronecker with one fixed dense SPD
``block`` x ``block`` element matrix.

Same matrix, bit for bit, as the program's generator at the same arguments
(checked in ``tests/test_operators.py``); written here without loops, so
the paper's full size (1,310,720 rows, 104,562,688 nonzeros) takes seconds.
"""

from __future__ import annotations

import numpy as np

from chipbench.operators import HostCSR


def spd_block(b: int, seed: int = 7) -> np.ndarray:
    """The element matrix: ``q qᵀ / b + I`` for a seeded Gaussian ``q``,
    scaled to spectral norm 2 (float64)."""
    q = np.random.default_rng(seed).standard_normal((b, b))
    m = q @ q.T / b + np.eye(b)
    return m / np.linalg.norm(m, 2) * 2.0


def grid_laplacian(nx: int, ny: int):
    """5-point Laplacian on an nx x ny grid (node i*ny + j): CSR arrays
    with columns ascending, 4 on the diagonal and -1 to each neighbour."""
    n = nx * ny
    i, j = np.divmod(np.arange(n), ny)
    # neighbour slots in ascending column order: up, left, self, right, down
    cols = np.stack([i * ny + j - ny, i * ny + j - 1, i * ny + j,
                     i * ny + j + 1, i * ny + j + ny], axis=1)
    valid = np.stack([i > 0, j > 0, np.ones(n, bool), j < ny - 1, i < nx - 1], axis=1)
    vals = np.where(np.arange(5) == 2, 4.0, -1.0)[None, :].repeat(n, axis=0)
    indptr = np.concatenate([[0], np.cumsum(valid.sum(axis=1))]).astype(np.int64)
    return indptr, cols[valid].astype(np.int64), vals[valid]


def generate(elements, block: int, dtype) -> HostCSR:
    nx, ny = elements
    lptr, lcols, lvals = grid_laplacian(nx, ny)
    n_el = nx * ny
    b = block
    counts = np.diff(lptr)
    # row (e*b + s) lists, for each neighbour slot q of element e in column
    # order, columns lcols[q]*b + 0..b-1 with values lvals[q] * B[s, :]
    lens = np.repeat(counts, b)                       # slots per scalar row
    starts = np.repeat(lptr[:-1], b)
    first = np.cumsum(lens) - lens
    q = np.arange(int(lens.sum())) - np.repeat(first, lens) + np.repeat(starts, lens)
    s = np.repeat(np.tile(np.arange(b), n_el), lens)  # sub-row of each slot
    offs = np.arange(b)
    indices = ((lcols[q] * b)[:, None] + offs).astype(np.int32).ravel()
    # lvals are 4 and -1: scaling by them is exact, so rounding the block
    # first gives the same values as rounding the float64 products
    blk = spd_block(b).astype(dtype)
    data = (lvals[q].astype(dtype)[:, None] * blk[s]).ravel()
    indptr = np.concatenate([[0], np.cumsum(lens * b)]).astype(np.int64)
    return HostCSR(indptr=indptr, indices=indices, data=data, n=n_el * b)
