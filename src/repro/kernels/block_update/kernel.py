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

Both run on the lane-dense views of :mod:`repro.kernels.lanes`: every
(t x t) coefficient block enters as ``kron(I_fold, c)``, so each product is
one (rows, 128) @ (128, 128) matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lanes import block_diag, fold_width, from_lanes, step_rows, to_lanes

_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    acc = jnp.float64 if a.dtype == jnp.float64 else jnp.float32
    return jnp.dot(a, b, precision=_HIGHEST, preferred_element_type=acc)


def _lanes_call(kernel, name, vecs, coefs, n_out, block_rows, interpret):
    """Run ``kernel`` over lane-dense views of the (n, t) ``vecs`` with the
    (t, t) ``coefs`` as block diagonals; returns ``n_out`` (n, t) arrays."""
    n, t = vecs[0].shape
    tp, fold = fold_width(t)
    lanes = tp * fold
    rows = step_rows(n, fold, block_rows)
    ops = [to_lanes(x, tp, fold, rows) for x in vecs]
    m = ops[0].shape[0]
    spec = pl.BlockSpec((rows, lanes), lambda i: (i, 0))
    cspec = pl.BlockSpec((lanes, lanes), lambda i: (0, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(m // rows,),
        in_specs=[spec] * len(vecs) + [cspec] * len(coefs),
        out_specs=[spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct((m, lanes), x.dtype) for x in vecs[:n_out]],
        interpret=interpret,
        name=name,
    )(*ops, *(block_diag(c, tp, fold) for c in coefs))
    return tuple(from_lanes(o, n, t, tp) for o in outs)


def _kernel(x_ref, r_ref, p_ref, ap_ref, c_ref, xo_ref, ro_ref):
    c = c_ref[...]
    xo_ref[...] = (x_ref[...] + _mm(p_ref[...], c)).astype(xo_ref.dtype)
    ro_ref[...] = (r_ref[...] - _mm(ap_ref[...], c)).astype(ro_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def block_update_pallas(x, r, p, ap, c, *, block_rows: int = 512, interpret: bool = False):
    return _lanes_call(_kernel, "block_update", (x, r, p, ap), (c,), 2, block_rows, interpret)


def _tail_kernel(x_ref, r_ref, p_ref, ap_ref, po_ref, c_ref, d_ref, do_ref,
                 xo_ref, ro_ref, zo_ref):
    p, ap = p_ref[...], ap_ref[...]
    xo_ref[...] = (x_ref[...] + _mm(p, c_ref[...])).astype(xo_ref.dtype)
    ro_ref[...] = (r_ref[...] - _mm(ap, c_ref[...])).astype(ro_ref.dtype)
    zo_ref[...] = (
        ap - _mm(p, d_ref[...]) - _mm(po_ref[...], do_ref[...])
    ).astype(zo_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ecg_tail_pallas(x, r, p, ap, p_old, c, d, d_old, *, block_rows: int = 512,
                    interpret: bool = False):
    """Fused ECG tail: (X+P·c, R−AP·c, AP−P·d−P_old·d_old) in one row pass."""
    # outputs take the dtypes of x, r and p (= ap's in the solver)
    return _lanes_call(
        _tail_kernel, "ecg_tail", (x, r, p, ap, p_old), (c, d, d_old), 3, block_rows,
        interpret,
    )
