"""Pallas TPU kernels: packed halo-exchange send/recv buffers.

One exchange *phase* of an :class:`~repro.core.node_aware.ExchangePlan`
moves many (row, column-segment) slots at once.  The historical executor
issued one XLA gather and one scatter per *step*; these kernels assemble the
whole phase in two dispatches:

* ``halo_pack`` — gather: ``out[i] = src[idx[i]]``.  Scalar-prefetched slot
  indices drive the ``index_map`` of the source operand (one phase's slots
  fit SMEM easily), so each packed row streams HBM → VMEM exactly once, in
  send-buffer order — the buffer the ppermute rounds then slice is
  contiguous by construction.
* ``halo_unpack`` — scatter: ``dst[pos[i]] = buf[i]``, with ``dst`` aliased
  to the output so slots the phase does not write keep their prior contents
  (earlier phases' deliveries).  Out-of-range positions are pre-clamped by
  the plan to the trailing dump slot, so every program writes a valid block.

Rows move as (1, 1, w) blocks of an (m, 1, w) view, w = t_active/col_split:
a block's last two dimensions must be multiples of (8, 128) or span the
array, and a (1, w) block of an (m, w) array is neither.  Narrow for the
lane width, but the packed layout is what buys the win: the per-phase
dispatch count is O(1) instead of O(steps), and the ppermute payload is
exactly the active-width bytes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pack_kernel(idx_ref, src_ref, out_ref):
    del idx_ref  # consumed by the index_map (scalar prefetch)
    out_ref[...] = src_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def halo_pack_pallas(src, idx, *, interpret: bool = False):
    """src (m, w); idx (c,) int32 -> packed (c, w) = src[idx]."""
    c = idx.shape[0]
    m, w = src.shape
    return pl.pallas_call(
        _pack_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(c,),
            in_specs=[pl.BlockSpec((1, 1, w), lambda i, idx: (idx[i], 0, 0))],
            out_specs=pl.BlockSpec((1, 1, w), lambda i, idx: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((c, 1, w), src.dtype),
        interpret=interpret,
        name="halo_pack",
    )(idx, src.reshape(m, 1, w)).reshape(c, w)


def _unpack_kernel(pos_ref, dst_ref, buf_ref, out_ref):
    del pos_ref, dst_ref  # position drives the out index_map; dst aliases out
    out_ref[...] = buf_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def halo_unpack_pallas(dst, buf, pos, *, interpret: bool = False):
    """dst (m, w); buf (c, w); pos (c,) int32 -> dst.at[pos].set(buf).

    ``dst`` is donated and aliased to the output: slots not named by ``pos``
    keep their previous contents without a copy.
    """
    c = pos.shape[0]
    m, w = dst.shape
    return pl.pallas_call(
        _unpack_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(c,),
            in_specs=[
                pl.BlockSpec((1, 1, w), lambda i, pos: (pos[i], 0, 0)),
                pl.BlockSpec((1, 1, w), lambda i, pos: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, w), lambda i, pos: (pos[i], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, 1, w), dst.dtype),
        input_output_aliases={1: 0},  # dst (first post-prefetch operand) -> out
        interpret=interpret,
        name="halo_unpack",
    )(pos, dst.reshape(m, 1, w), buf.reshape(c, 1, w)).reshape(m, w)
