"""Lane-dense views of narrow ``(n, t)`` block vectors for the Pallas kernels.

An (n, t) float32 block vector with t = 8 fills 8 of the 128 lanes of every
vreg and VMEM tile, and XLA keeps such an array transposed in HBM; as a
Pallas operand it costs a 16x lane-padded relayout copy on each side of the
call.  The row-pass kernels (``fused_gram``, ``block_update``/``ecg_tail``)
see the same bytes as ``(m, fold·t')`` rows instead: ``fold`` consecutive
block-vector rows per lane-dense row, t' = t rounded up to a power of two
(or to whole lane tiles when t > 128).  Then

* a row-local map ``X·C`` is one lane-dense matmul ``X_lanes · kron(I_fold, C)``
  (:func:`block_diag`), and
* a Gram product ``XᵀY`` is ``X_lanesᵀ Y_lanes``, whose ``fold`` diagonal
  (t', t') blocks sum to the (t, t) answer (:func:`diag_sum`).
"""

from __future__ import annotations

import jax.numpy as jnp

LANES = 128


def fold_width(t: int) -> tuple[int, int]:
    """``(t', fold)``: padded width and block-vector rows per lane-dense row."""
    if t >= LANES:
        return -(-t // LANES) * LANES, 1
    tp = 1 << (t - 1).bit_length()
    return tp, LANES // tp


def step_rows(n: int, fold: int, block_rows: int) -> int:
    """Lane-dense rows per grid step: ``block_rows``, or fewer (a multiple
    of 8) when the whole array is smaller."""
    return min(block_rows, -(-n // (fold * 8)) * 8)


def to_lanes(x, tp: int, fold: int, rows: int):
    """(n, t) -> (m, fold·t'), zero-padded so ``rows`` divides m."""
    n, t = x.shape
    m = -(-n // (fold * rows)) * rows
    x = jnp.pad(x, ((0, m * fold - n), (0, tp - t)))
    # spelled from xᵀ, the layout XLA keeps a narrow array in: one compact
    # transpose instead of a relayout through a lane-padded copy
    return x.T.reshape(tp, m, fold).transpose(1, 2, 0).reshape(m, fold * tp)


def from_lanes(xl, n: int, t: int, tp: int):
    """Inverse of :func:`to_lanes`."""
    return xl.reshape(-1, tp)[:n, :t]


def block_diag(c, tp: int, fold: int):
    """(t, t) -> kron(I_fold, C padded to (t', t'))."""
    c = jnp.pad(c, ((0, tp - c.shape[0]), (0, tp - c.shape[1])))
    return jnp.kron(jnp.eye(fold, dtype=c.dtype), c)


def diag_sum(g, t: int, tp: int, fold: int):
    """(fold·t', fold·t') Gram of lane-dense rows -> the (t, t) Gram."""
    return jnp.einsum("aiaj->ij", g.reshape(fold, tp, fold, tp))[:t, :t]
