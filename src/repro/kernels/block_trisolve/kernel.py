"""Pallas TPU kernel: batched two-triangle solve for block-Jacobi applies.

One grid step solves one diagonal block: ``L Lᵀ y = x`` by forward then
backward substitution, with the factor tile and both substitution states
VMEM-resident.  The block-Jacobi apply is the solver-loop hot path of a
preconditioned iteration (one batched solve per iteration per rank); a
LAPACK-style column algorithm would serialize scalar work on the VPU, so
the substitutions are expressed as *masked row extractions + (1, bs)×(bs, t)
contractions* — every fori_loop step is dense vector/matrix work the TPU
can vectorize, and no dynamically-indexed loads hit the tile.

Substitution (per block, row i of the forward pass):

    y[i] = (x[i] − L[i, :] · y) / L[i, i]          (y rows ≥ i still zero)

and the backward pass mirrors it against L's columns (Lᵀ rows).  The
row/column extraction uses an iota mask, so the loop body is shape-static.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(l_ref, x_ref, out_ref):
    l = l_ref[0]  # (bs, bs) lower factor
    x = x_ref[0]  # (bs, t)
    bs = l.shape[0]
    # row ids down the sublanes, column ids along the lanes (two iotas: a
    # transposed (bs, 1) mask would be a 1-lane transpose Mosaic refuses)
    row_id = jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
    col_id = jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)

    def fwd(i, y):
        row_mask = row_id == i  # (bs, 1)
        row = jnp.sum(jnp.where(row_mask, l, 0.0), axis=0, keepdims=True)  # L[i, :]
        xi = jnp.sum(jnp.where(row_mask, x, 0.0), axis=0, keepdims=True)   # x[i, :]
        lii = jnp.sum(jnp.where(col_id == i, row, 0.0))                    # L[i, i]
        yi = (xi - jnp.dot(row, y, precision=_HIGHEST,
                            preferred_element_type=y.dtype)) / lii
        return jnp.where(row_mask, yi, y)

    y = jax.lax.fori_loop(0, bs, fwd, jnp.zeros_like(x))

    def bwd(j, z):
        i = bs - 1 - j
        row_mask = row_id == i
        col = jnp.sum(jnp.where(col_id == i, l, 0.0), axis=1, keepdims=True)  # L[:, i]
        yi = jnp.sum(jnp.where(row_mask, y, 0.0), axis=0, keepdims=True)
        lii = jnp.sum(jnp.where(row_mask, col, 0.0))
        zi = (yi - jnp.sum(col * z, axis=0, keepdims=True)) / lii  # L[:, i]ᵀ z
        return jnp.where(row_mask, zi, z)

    out_ref[0] = jax.lax.fori_loop(0, bs, bwd, jnp.zeros_like(x))


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_trisolve_pallas(l, x, *, interpret: bool = False):
    """Batched ``L Lᵀ y = x`` solve; see :mod:`.ref` for the oracle.

    l: (nb, bs, bs) lower Cholesky factors, x: (nb, bs, t) → (nb, bs, t).
    """
    nb, bs, _ = l.shape
    t = x.shape[2]
    l = l.astype(x.dtype)
    return pl.pallas_call(
        _kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, bs, bs), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, bs, t), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, t), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, bs, t), x.dtype),
        interpret=interpret,
        name="block_trisolve",
    )(l, x)
