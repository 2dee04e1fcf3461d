"""Pallas TPU kernels: fused ECG block-vector updates.

X += P·c and R -= AP·c share the (t x t) coefficient block c; fusing them
halves kernel dispatches and lets each row tile of X/R be updated while
P/AP tiles are VMEM-resident.  Grid: 1-D over row tiles; c is broadcast to
every step (small, stays in VMEM).

``ecg_tail_pallas`` extends the fusion to the whole per-iteration tail of
Algorithm 3 — X += P·c, R -= AP·c, Z = AP − P·d − P_old·d_old — so each
row tile of P and AP is read from HBM exactly once and feeds three
small MXU matmuls while VMEM-resident (P feeds both the X and Z updates, AP
feeds both the R and Z updates).  The unfused formulation reads P and AP
twice each: 7 tile reads instead of 5 (a 1.4x traffic cut on the tail).

Both run on the (t, n) views of :mod:`repro.kernels.lanes`, the layout XLA
already keeps the block vectors in: on a (t, L) block the updates read
Xᵀ += cᵀPᵀ, Rᵀ −= cᵀAPᵀ and Zᵀ = APᵀ − dᵀPᵀ − d_oldᵀP_oldᵀ, each a
(t, t) @ (t, L) product.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lanes import lane_block

_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    # narrower operands are widened first: their products are exact either
    # way, and the CPU's interpreted dot takes no bf16 x bf16 -> f32
    acc = jnp.float64 if a.dtype == jnp.float64 else jnp.float32
    return jnp.dot(a.astype(acc), b.astype(acc), precision=_HIGHEST,
                   preferred_element_type=acc)


def _row_pass(kernel, name, vecs, coefs, n_out, block_rows, interpret):
    """Run ``kernel`` over (t, L) blocks of the (t, n) views of the (n, t)
    ``vecs``, with the (t, t) ``coefs`` transposed; returns ``n_out``
    (n, t) arrays."""
    n, t = vecs[0].shape
    rows = lane_block(n, t, vecs[0].dtype, block_rows)
    spec = pl.BlockSpec((t, rows), lambda i: (0, i))
    cspec = pl.BlockSpec((t, t), lambda i: (0, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, rows),),
        in_specs=[spec] * len(vecs) + [cspec] * len(coefs),
        out_specs=[spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct((t, n), x.dtype) for x in vecs[:n_out]],
        interpret=interpret,
        name=name,
    )(*(x.T for x in vecs), *(c.T for c in coefs))
    return tuple(o.T for o in outs)


def _kernel(x_ref, r_ref, p_ref, ap_ref, c_ref, xo_ref, ro_ref):
    c = c_ref[...]
    xo_ref[...] = (x_ref[...] + _mm(c, p_ref[...])).astype(xo_ref.dtype)
    ro_ref[...] = (r_ref[...] - _mm(c, ap_ref[...])).astype(ro_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def block_update_pallas(x, r, p, ap, c, *, block_rows: int | None = None,
                        interpret: bool = False):
    return _row_pass(_kernel, "block_update", (x, r, p, ap), (c,), 2, block_rows, interpret)


def _tail_kernel(x_ref, r_ref, p_ref, ap_ref, po_ref, c_ref, d_ref, do_ref,
                 xo_ref, ro_ref, zo_ref):
    p, ap = p_ref[...], ap_ref[...]
    xo_ref[...] = (x_ref[...] + _mm(c_ref[...], p)).astype(xo_ref.dtype)
    ro_ref[...] = (r_ref[...] - _mm(c_ref[...], ap)).astype(ro_ref.dtype)
    zo_ref[...] = (
        ap - _mm(d_ref[...], p) - _mm(do_ref[...], po_ref[...])
    ).astype(zo_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ecg_tail_pallas(x, r, p, ap, p_old, c, d, d_old, *, block_rows: int | None = None,
                    interpret: bool = False):
    """Fused ECG tail: (X+P·c, R−AP·c, AP−P·d−P_old·d_old) in one row pass."""
    # outputs take the dtypes of x, r and p (= ap's in the solver)
    return _row_pass(
        _tail_kernel, "ecg_tail", (x, r, p, ap, p_old), (c, d, d_old), 3, block_rows,
        interpret,
    )
