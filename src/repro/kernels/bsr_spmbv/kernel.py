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

and each output element is a VPU multiply-add of full vregs: for every
tile column kc, row i of the tile slab (one lane row, broadcast down the
sublanes) times the (t, LANES) slab of V its block rows need.  The
operator streams from HBM exactly once per call, unpadded; the kernel is
bound by that stream, not by the MXU.  Accumulation is f32 (f64 under
interpret mode, for the CPU oracle checks).

V is gathered by XLA (block-column ids need no SMEM table, whatever the
size), under the ``ecg.gather`` scope, as whole rows of ``bc·t`` values,
one per block column: ``rows[b, c·t + j] = V[b·bc + c, j]``.  XLA keeps a
narrow (n, t) array as Vᵀ, (t, n) row-major, so every change of layout
between Vᵀ, the rows and the kernel's lanes is the real cost around the
product.  Where ``br·t == bc·t == LANES`` with t = 8 (16x16 tiles at
t = 8, every deployed cell) and 32-bit values, each one runs in VMEM:

1. ``v_rows`` (a Pallas kernel, under ``ecg.gather``) reads Vᵀ in
   (t, bc·LANES) slabs and writes the rows, 128 block columns at a time:
   a transpose of 16-lane chunks inside each vreg, two (128, 128)
   transposes and a strided reload, both lane-dense.
2. The gather reads the rows with the block-column ids in the kernel's
   grid order (``(nbg, kmax, LANES)``, padded block rows reading block
   column 0 against zero tiles) and writes ``(nbg, kmax, LANES, bc·t)``:
   a bitcast of the kernel's ``(nbg, kmax·bc, t, LANES)`` operand.
3. ``bsr_spmbv`` transposes each slot's (LANES, bc·t) slab onto the lanes
   on the XLU before its multiply-adds, and writes W as Wᵀ, (t, nbr·br),
   the layout its callers keep: the (br·t, LANES) accumulator goes back
   through the inverse of step 1's relayout.

No array with a minor dimension of t or bc reaches HBM.  Other shapes (and
16-bit values, whose vregs pack two rows to a sublane) take the lane path:
XLA relayouts the rows and the gathered slabs, and the kernel writes
``(nbg, br, t, LANES)``.  Both paths run the same multiply-adds in the
same order, so they agree bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# block rows per lane group of the operator layout (the TPU lane width)
from repro.kernels.lanes import LANES
from repro.observe.scopes import GATHER

SUBLANES = 8  # rows of a 32-bit vreg
CHUNK = LANES // SUBLANES  # lanes of one row's share of a vreg: 16
#: 128-block-column groups of Vᵀ per step of ``v_rows`` (512 KiB blocks)
ROW_GROUPS = 8


def _acc_dtype(dtype):
    return jnp.float64 if dtype == jnp.float64 else jnp.float32


def _on_vregs(br: int, bc: int, t: int, dtype) -> bool:
    """Whether the relayouts fit whole vregs: t columns on the sublanes, a
    block row's ``bc·t`` gathered (and ``br·t`` output) values one vreg
    row of 32-bit values."""
    return t == SUBLANES and br == bc == CHUNK and jnp.dtype(dtype).itemsize >= 4


def _swap_chunks(x):
    """``out[.., a, CHUNK·j + c] = x[.., j, CHUNK·a + c]`` for each
    (SUBLANES, LANES) vreg of ``x``: its 8 x 8 grid of 16-lane chunks
    transposed, one diagonal per sublane and lane rotation."""
    sub, lane = x.ndim - 2, x.ndim - 1
    diag = ((jax.lax.broadcasted_iota(jnp.int32, x.shape, lane) // CHUNK)
            - jax.lax.broadcasted_iota(jnp.int32, x.shape, sub)) % SUBLANES
    out = x
    for d in range(1, SUBLANES):
        moved = pltpu.roll(pltpu.roll(x, SUBLANES - d, sub), CHUNK * d, lane)
        out = jnp.where(diag == d, moved, out)
    return out


def _rows_kernel(x_ref, o_ref, buf_ref):
    # x_ref (t, G·bc·L) slab of Vᵀ; o_ref (G·L, bc·t) rows; buf_ref (L, L).
    # Per group of L block columns, x = Vᵀ[j, CHUNK·(8s + a) + c] for vreg
    # s, sublane j, lane CHUNK·a + c; the row is o[8s + a, t·c + j].
    t = x_ref.shape[0]
    span = LANES * CHUNK  # lanes of Vᵀ per L block columns
    for q in range(x_ref.shape[1] // span):
        x = x_ref[:, q * span:(q + 1) * span]
        p = _swap_chunks(jnp.stack([x[:, s * LANES:(s + 1) * LANES]
                                    for s in range(CHUNK)]))
        buf_ref[...] = p.reshape(LANES, LANES).T  # [CHUNK·j + c, 8s + a]
        by_c = jnp.concatenate([buf_ref[pl.ds(c, t, stride=CHUNK)]
                                for c in range(CHUNK)])  # [t·c + j, 8s + a]
        o_ref[q * LANES:(q + 1) * LANES] = by_c.T


def _v_rows(vt, interpret: bool):
    """Vᵀ (t, nbc·bc) -> the gather's rows ``(nbc, bc·t)``, one lane-dense
    read and one lane-dense write of V."""
    t, n = vt.shape
    nbc = n // CHUNK
    groups = min(ROW_GROUPS, pl.cdiv(nbc, LANES))
    return pl.pallas_call(
        _rows_kernel,
        grid=(pl.cdiv(nbc, groups * LANES),),
        in_specs=[pl.BlockSpec((t, groups * LANES * CHUNK), lambda s: (0, s))],
        out_specs=pl.BlockSpec((groups * LANES, LANES), lambda s: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((nbc, LANES), vt.dtype),
        scratch_shapes=[pltpu.VMEM((LANES, LANES), vt.dtype)],
        interpret=interpret,
        name="v_rows",
    )(vt)


@functools.partial(jax.jit, static_argnames=("bc", "nbg", "vregs", "interpret"))
def _gather_lanes(v, indices, *, bc: int, nbg: int, vregs: bool, interpret: bool = False):
    """The kernel's gathered operand ``(nbg, kmax·bc, t, LANES)``, traced
    under the ``ecg.gather`` scope (padded block rows gather block column 0
    and meet zero tiles).

    On ``vregs`` (:func:`_on_vregs`): ``v_rows`` builds the rows from Vᵀ in
    VMEM, the ids are put in grid order (XLA keeps them column-major, so
    no more than a bitcast), and the gather's ``(nbg, kmax, LANES, bc·t)``
    result is the operand by a bitcast: slot k's ``bc`` (t, LANES) slabs
    hold the rows of its LANES block rows, one row per sublane.  Otherwise
    the rows are spelled from Vᵀ and the gathered rows transposed onto the
    lanes by XLA, so slab ``k·bc + c`` holds column c of slot k,
    ``(t, LANES)``."""
    nbr, kmax = indices.shape
    t = v.shape[1]
    with jax.named_scope(GATHER):
        idx = jnp.pad(indices, ((0, nbg * LANES - nbr), (0, 0)))
        if vregs:
            rows = _v_rows(v.T, interpret)
            vg = rows[idx.reshape(nbg, LANES, kmax).transpose(0, 2, 1)]
            return vg.reshape(nbg, kmax * bc, t, LANES)
        rows = v.T.reshape(t, -1, bc).transpose(1, 2, 0).reshape(-1, bc * t)
        vg = rows[idx]  # (nbg·L, kmax, bc·t)
        vg = vg.reshape(nbg, LANES, kmax, bc * t).transpose(0, 2, 3, 1)
        return vg.reshape(nbg, kmax * bc, t, LANES)


def _madd(rows, a, v):
    """One tile column's multiply-adds, shared by both kernels:
    ``rows[i] + a[i] * v`` for each row i of the (br, L) tile slab ``a``
    (one lane row, broadcast down the sublanes of the (t, L) slab ``v``)."""
    return tuple(r + a[i : i + 1, :] * v for i, r in enumerate(rows))


def _kernel(a_ref, v_ref, out_ref):
    # a_ref (1, kbc, br, L) operator slab; v_ref (1, kbc, t, L) gathered V;
    # out_ref (1, br, t, L): out[i, j, l] = sum_kc a[kc, i, l] * v[kc, j, l]
    kbc, br = a_ref.shape[1], a_ref.shape[2]
    t, lanes = v_ref.shape[2], v_ref.shape[3]
    acc = _acc_dtype(out_ref.dtype)

    def step(kc, rows):
        return _madd(rows, a_ref[0, kc].astype(acc), v_ref[0, kc].astype(acc))

    zero = jnp.zeros((t, lanes), acc)
    rows = jax.lax.fori_loop(0, kbc, step, (zero,) * br)
    for i in range(br):
        out_ref[0, i] = rows[i].astype(out_ref.dtype)


def _vreg_kernel(a_ref, v_ref, out_ref, buf_ref):
    # a_ref (1, kbc, br, L) operator slab; v_ref (1, kbc, t, L) gathered V,
    # slot k's bc slabs the (L, bc·t) rows of its block rows; out_ref
    # (t, L·br) the group's columns of Wᵀ; buf_ref (br·t, L).  ``_kernel``'s
    # multiply-adds (``_madd``), in the same kc order.
    kbc, br, lanes = a_ref.shape[1:]
    t = v_ref.shape[2]
    bc = lanes // t
    acc = _acc_dtype(out_ref.dtype)

    def slot(k, rows):
        # (bc·t, L): row c·t + j is column j of the slot's tile column c
        vt = v_ref[0, pl.ds(k * bc, bc)].reshape(lanes, bc * t).T.astype(acc)
        for c in range(bc):
            rows = _madd(rows, a_ref[0, k * bc + c].astype(acc), vt[c * t:(c + 1) * t])
        return rows

    zero = jnp.zeros((t, lanes), acc)
    rows = jax.lax.fori_loop(0, kbc // bc, slot, (zero,) * br)
    buf_ref[...] = jnp.concatenate(rows).astype(buf_ref.dtype)  # [t·i + j, l]
    by_j = jnp.concatenate([buf_ref[pl.ds(j, br, stride=t)] for j in range(t)])
    w = _swap_chunks(by_j.T.reshape(br, SUBLANES, lanes))  # [m][j, br·a + i]
    for m in range(br):  # block row l = 8m + a: W row br·l + i
        out_ref[:, m * lanes:(m + 1) * lanes] = w[m]


@functools.partial(jax.jit, static_argnames=("nbr", "vregs", "interpret"))
def _product(blocks, vg, *, nbr: int, vregs: bool, interpret: bool = False):
    nbg, kbc, br, lanes = blocks.shape
    t = vg.shape[2]
    common = dict(
        grid=(nbg,),
        in_specs=[
            pl.BlockSpec((1, kbc, br, lanes), lambda g: (g, 0, 0, 0)),
            pl.BlockSpec((1, kbc, t, lanes), lambda g: (g, 0, 0, 0)),
        ],
        interpret=interpret,
        name="bsr_spmbv",
    )
    if vregs:
        wt = pl.pallas_call(
            _vreg_kernel,
            out_specs=pl.BlockSpec((t, lanes * br), lambda g: (0, g)),
            out_shape=jax.ShapeDtypeStruct((t, nbr * br), vg.dtype),
            scratch_shapes=[pltpu.VMEM((br * t, lanes), vg.dtype)],
            **common,
        )(blocks, vg)
        return wt.T
    out = pl.pallas_call(
        _kernel,
        out_specs=pl.BlockSpec((1, br, t, lanes), lambda g: (g, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nbg, br, t, lanes), vg.dtype),
        **common,
    )(blocks, vg)
    # (g, i, j, l) -> row (g·L + l)·br + i, column j
    return out.transpose(0, 3, 1, 2).reshape(nbg * lanes * br, t)[: nbr * br]


def bsr_spmbv_pallas(blocks, indices, v, *, interpret: bool = False):
    """blocks (nbg, kmax*bc, br, LANES) lane-major; indices (nbr, kmax);
    v (nbc*bc, t) -> (nbr*br, t).

    The gather and the product are jitted apart, so that of the two Pallas
    calls only the product's ``op_name`` names ``bsr_spmbv``."""
    nbg, kbc, br, _ = blocks.shape
    nbr, kmax = indices.shape
    bc = kbc // kmax
    vregs = _on_vregs(br, bc, v.shape[1], v.dtype)
    vg = _gather_lanes(v, indices, bc=bc, nbg=nbg, vregs=vregs, interpret=interpret)
    return _product(blocks, vg, nbr=nbr, vregs=vregs, interpret=interpret)
