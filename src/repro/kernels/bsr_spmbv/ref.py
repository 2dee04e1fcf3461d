"""Pure-jnp oracle for the Block-ELL SpMBV kernel."""

from __future__ import annotations

import jax.numpy as jnp


def bsr_spmbv_ref(blocks: jnp.ndarray, indices: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """W = A @ V for Block-ELL A.

    blocks:  (nbg, kmax*bc, br, L) lane-major tiles:
             ``blocks[g, k*bc + c, i, l]`` is row i, column c of tile slot k
             of block row g·L + l (zero tiles where padded)
    indices: (nbr, kmax) block-column ids (0 where padded — safe: zero tiles)
    v:       (nbc * bc, t)
    returns: (nbr * br, t)
    """
    nbg, kbc, br, lanes = blocks.shape
    nbr, kmax = indices.shape
    t = v.shape[1]
    panels = blocks.transpose(0, 3, 2, 1).reshape(nbg * lanes, br, kbc)[:nbr]
    gathered = v.reshape(-1, kbc // kmax, t)[indices].reshape(nbr, kbc, t)
    out = jnp.einsum("nik,nkt->nit", panels, gathered)
    return out.reshape(nbr * br, t)
