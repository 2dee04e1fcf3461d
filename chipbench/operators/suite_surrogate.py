"""Structural surrogate of a SuiteSparse matrix of the paper's Table 3:
dense SPD ``block`` x ``block`` element blocks on the 5-point element
stencil of a ``grid`` of elements, with element ids shuffled within
consecutive windows of ``window`` ids (a seeded permutation of each
window, in order), which mimics the natural, non-partitioned ordering of
an unstructured FE mesh.

Same matrix, bit for bit, as the program's
``repro.sparse.matrices.suite_surrogate`` at the same grid, window and seed
(checked in ``tests/test_suite_surrogate.py``); written here without loops, so
audikw_1 at its published size (944,784 rows, 75,333,888 nonzeros) takes
seconds.
"""

from __future__ import annotations

import numpy as np

from chipbench.operators import HostCSR
from chipbench.operators.dg_laplace_2d import grid_laplacian, spd_block


def window_shuffle(n: int, window: int, seed: int) -> np.ndarray:
    """``perm``: ids ``[s, s + window)`` permuted among themselves, window
    after window, from one generator (the last window may be short)."""
    rng = np.random.default_rng(seed)
    full = n // window
    # a row-wise shuffle draws from the generator as one permutation per
    # window in turn would
    head = rng.permuted(np.arange(full * window).reshape(full, window), axis=1).ravel()
    tail = full * window + rng.permutation(n - full * window)
    return np.concatenate([head, tail])


def generate(grid, block: int, window: int, perm_seed: int, dtype) -> HostCSR:
    nx, ny = grid
    n_el = nx * ny
    lptr, lcols, lvals = grid_laplacian(nx, ny)
    # symmetric permutation P L Pᵀ: element perm[k] becomes element k
    inv = np.empty(n_el, np.int64)
    inv[window_shuffle(n_el, window, perm_seed)] = np.arange(n_el)
    rows = inv[np.repeat(np.arange(n_el), np.diff(lptr))]
    cols = inv[lcols]
    order = np.lexsort((cols, rows))
    lcols, lvals = cols[order], lvals[order]
    lptr = np.concatenate([[0], np.cumsum(np.bincount(rows, minlength=n_el))]).astype(np.int64)

    b = block
    counts = np.diff(lptr)
    # row (e*b + s) lists, for each neighbour slot q of element e in column
    # order, columns lcols[q]*b + 0..b-1 with values lvals[q] * B[s, :]
    lens = np.repeat(counts, b)                       # slots per scalar row
    starts = np.repeat(lptr[:-1], b)
    first = np.cumsum(lens) - lens
    q = np.arange(int(lens.sum())) - np.repeat(first, lens) + np.repeat(starts, lens)
    s = np.repeat(np.tile(np.arange(b), n_el), lens)  # sub-row of each slot
    indices = ((lcols[q] * b)[:, None] + np.arange(b)).astype(np.int32).ravel()
    # lvals are 4 and -1: scaling by them is exact, so rounding the block
    # first gives the same values as rounding the float64 products
    blk = spd_block(b).astype(dtype)
    data = (lvals[q].astype(dtype)[:, None] * blk[s]).ravel()
    indptr = np.concatenate([[0], np.cumsum(lens * b)]).astype(np.int64)
    return HostCSR(indptr=indptr, indices=indices, data=data, n=n_el * b)
