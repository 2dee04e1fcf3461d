"""Pallas TPU kernel: fused ECG block inner products.

Computes the packed (t, 3t) payload  [PᵀR | APᵀAP | AP_oldᵀAP]  in a single
pass over the row dimension.  The naive implementation reads P, R, AP, AP_old
from HBM in three separate GEMM passes (AP twice); this kernel streams each
operand tile exactly once — the local-compute counterpart of the paper's
"fuse the reductions" discipline (§3.1): one HBM pass feeding one allreduce.

Memory-bound analysis (per n-row shard, f32):
    naive:  reads P, R, 2·AP, AP_old  = 5·n·t·f bytes
    fused:  reads P, R, AP, AP_old    = 4·n·t·f bytes   (1.25x traffic cut)
The operands are the (t, n) views of :mod:`repro.kernels.lanes`, the layout
XLA already keeps the block vectors in, so those 4·n·t·f bytes are all the
call moves: no relayout copy on the way in.

Grid: 1-D over (t, L) blocks; each block adds three NT products contracting
over its lanes to the (3, t, t) accumulator, the revisited output block
(VMEM-resident across the whole grid).  Lanes past n in the ragged last
block are masked before they are summed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lanes import lane_block

_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(n, p_ref, r_ref, ap_ref, apo_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = out_ref.dtype
    t, rows = p_ref.shape
    ops = [ref[...].astype(acc) for ref in (p_ref, r_ref, ap_ref, apo_ref)]
    if n % rows:
        live = i * rows + jax.lax.broadcasted_iota(jnp.int32, (t, rows), 1) < n
        ops = [jnp.where(live, x, 0) for x in ops]
    p, r, ap, apo = ops
    gram = lambda a, b: jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=_HIGHEST, preferred_element_type=acc
    )
    out_ref[0] += gram(p, r)
    out_ref[1] += gram(ap, ap)
    out_ref[2] += gram(apo, ap)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_gram_pallas(p, r, ap, ap_old, *, block_rows: int | None = None,
                      interpret: bool = False):
    n, t = p.shape
    rows = lane_block(n, t, p.dtype, block_rows)
    acc = jnp.float64 if p.dtype == jnp.float64 else jnp.float32
    spec = pl.BlockSpec((t, rows), lambda i: (0, i))
    g = pl.pallas_call(
        functools.partial(_kernel, n),
        grid=(pl.cdiv(n, rows),),
        in_specs=[spec] * 4,
        out_specs=pl.BlockSpec((3, t, t), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((3, t, t), acc),
        interpret=interpret,
        name="fused_gram",
    )(p.T, r.T, ap.T, ap_old.T)
    return jnp.concatenate(list(g), axis=1).astype(p.dtype)
