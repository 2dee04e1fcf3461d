"""Pallas TPU kernel: Block-ELL sparse-matrix x block-vector product.

TPU adaptation of the paper's SpMBV hot spot (DESIGN.md §2): instead of the
CPU/GPU scalar-gather CSR formulation, the matrix is stored as dense
(br x bc) tiles in Block-ELL layout (fixed ``kmax`` tiles per block row —
DG/FE matrices are naturally block-uniform).

Layout: **block rows on lanes.**  With t = 8 columns every per-block-row
product is far too thin for the MXU (a (br x kmax·bc) @ (kmax·bc x t)
matmul reloads its weights for 8 output columns), and an (n, t) operand
fills 8 of the 128 lanes.  So the operator is stored *lane-major*,
``(nbg, kmax·bc, br, LANES)`` with ``LANES`` = 128 consecutive block rows
on the lanes::

    blocks[g, k·bc + c, i, l] = A[block row g·LANES + l][i, k·bc + c]

and V is gathered by XLA into the same orientation (block-column ids need
no SMEM table, whatever the size), ``vg[g, k·bc + c, j, l] = V[idx[g·LANES
+ l, k]·bc + c, j]``.  Each output element is then a VPU multiply-add of
full vregs: for every tile column kc, row i of the tile slab (one lane row,
broadcast down the sublanes) times the (t, LANES) slab of V.  The operator
streams from HBM exactly once per call, unpadded; the kernel is bound by
that stream, not by the MXU.  Accumulation is f32 (f64 under interpret
mode, for the CPU oracle checks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# block rows per lane group of the operator layout (the TPU lane width)
from repro.kernels.lanes import LANES
from repro.observe.scopes import GATHER


def _acc_dtype(dtype):
    return jnp.float64 if dtype == jnp.float64 else jnp.float32


def _kernel(a_ref, v_ref, out_ref):
    # a_ref (1, kbc, br, L) operator slab; v_ref (1, kbc, t, L) gathered V;
    # out_ref (1, br, t, L): out[i, j, l] = sum_kc a[kc, i, l] * v[kc, j, l]
    kbc, br = a_ref.shape[1], a_ref.shape[2]
    t, lanes = v_ref.shape[2], v_ref.shape[3]
    acc = _acc_dtype(out_ref.dtype)

    def step(kc, rows):
        a = a_ref[0, kc].astype(acc)  # (br, L)
        v = v_ref[0, kc].astype(acc)  # (t, L)
        return tuple(rows[i] + a[i : i + 1, :] * v for i in range(br))

    zero = jnp.zeros((t, lanes), acc)
    rows = jax.lax.fori_loop(0, kbc, step, (zero,) * br)
    for i in range(br):
        out_ref[0, i] = rows[i].astype(out_ref.dtype)


def _gather_lanes(v, indices, bc: int, nbg: int):
    """V rows each block row's tiles need, block rows on the lanes:
    ``(nbg, kmax·bc, t, LANES)`` (padded block rows gather block column 0
    and meet zero tiles), traced under the ``ecg.gather`` scope."""
    nbr, kmax = indices.shape
    t = v.shape[1]
    with jax.named_scope(GATHER):
        idx = jnp.pad(indices, ((0, nbg * LANES - nbr), (0, 0)))
        # gather whole (bc·t)-wide rows (one per block column), then swap the
        # block-row axis onto the lanes: (bc·t, LANES) transposes.  The rows
        # are spelled from Vᵀ, the layout XLA keeps a narrow (n, t) array in;
        # a plain ``v.reshape`` relayouts V through a lane-padded copy first.
        rows = v.T.reshape(t, -1, bc).transpose(1, 2, 0).reshape(-1, bc * t)
        vg = rows[idx]  # (nbg·L, kmax, bc·t)
        vg = vg.reshape(nbg, LANES, kmax, bc * t).transpose(0, 2, 3, 1)
        return vg.reshape(nbg, kmax * bc, t, LANES)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bsr_spmbv_pallas(blocks, indices, v, *, interpret: bool = False):
    """blocks (nbg, kmax*bc, br, LANES) lane-major; indices (nbr, kmax);
    v (nbc*bc, t) -> (nbr*br, t)."""
    nbg, kbc, br, lanes = blocks.shape
    nbr, kmax = indices.shape
    t = v.shape[1]
    vg = _gather_lanes(v, indices, kbc // kmax, nbg)
    out = pl.pallas_call(
        _kernel,
        grid=(nbg,),
        in_specs=[
            pl.BlockSpec((1, kbc, br, lanes), lambda g: (g, 0, 0, 0)),
            pl.BlockSpec((1, kbc, t, lanes), lambda g: (g, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, br, t, lanes), lambda g: (g, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nbg, br, t, lanes), v.dtype),
        interpret=interpret,
        name="bsr_spmbv",
    )(blocks, vg)
    # (g, i, j, l) -> row (g·L + l)·br + i, column j
    return out.transpose(0, 3, 1, 2).reshape(nbg * lanes * br, t)[: nbr * br]
