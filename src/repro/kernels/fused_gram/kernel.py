"""Pallas TPU kernel: fused ECG block inner products.

Computes the packed (t, 3t) payload  [PᵀR | APᵀAP | AP_oldᵀAP]  in a single
pass over the row dimension.  The naive implementation reads P, R, AP, AP_old
from HBM in three separate GEMM passes (AP twice); this kernel streams each
operand tile exactly once — the local-compute counterpart of the paper's
"fuse the reductions" discipline (§3.1): one HBM pass feeding one allreduce.

Memory-bound analysis (per n-row shard, bf16/f32):
    naive:  reads P, R, 2·AP, AP_old  = 5·n·t·f bytes
    fused:  reads P, R, AP, AP_old    = 4·n·t·f bytes   (1.25x traffic cut)

Grid: 1-D over row tiles of the lane-dense views (:mod:`repro.kernels.lanes`);
the (L, 3L) accumulator of lane-row Grams lives in the revisited output
block (VMEM-resident across the whole grid) and is folded to (t, 3t) after.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lanes import diag_sum, fold_width, step_rows, to_lanes

_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(p_ref, r_ref, ap_ref, apo_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = out_ref.dtype
    gram = lambda a, b: jnp.dot(a.T, b, precision=_HIGHEST, preferred_element_type=acc)
    ap = ap_ref[...]
    out_ref[...] += jnp.concatenate(
        [gram(p_ref[...], r_ref[...]), gram(ap, ap), gram(apo_ref[...], ap)], axis=1
    )


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_gram_pallas(p, r, ap, ap_old, *, block_rows: int = 512, interpret: bool = False):
    n, t = p.shape
    tp, fold = fold_width(t)
    lanes = tp * fold
    rows = step_rows(n, fold, block_rows)
    ops = [to_lanes(x, tp, fold, rows) for x in (p, r, ap, ap_old)]
    acc = jnp.float64 if p.dtype == jnp.float64 else jnp.float32
    spec = pl.BlockSpec((rows, lanes), lambda i: (i, 0))
    g = pl.pallas_call(
        _kernel,
        grid=(ops[0].shape[0] // rows,),
        in_specs=[spec, spec, spec, spec],
        out_specs=pl.BlockSpec((lanes, 3 * lanes), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((lanes, 3 * lanes), acc),
        interpret=interpret,
        name="fused_gram",
    )(*ops)
    parts = [diag_sum(g[:, k * lanes : (k + 1) * lanes], t, tp, fold) for k in range(3)]
    return jnp.concatenate(parts, axis=1).astype(p.dtype)
