"""Public op: Block-ELL SpMBV with Pallas-on-TPU / oracle-on-CPU dispatch.

Besides the kernel wrapper this module carries the host-side (numpy)
conversion machinery that puts the kernel on the solver hot path:

* :func:`csr_arrays_to_block_ell` / :func:`count_block_ell_tiles` convert raw
  CSR arrays (a rank's local [own ‖ halo] block in the distributed solver)
  into the fixed-``kmax`` Block-ELL layout the kernel consumes.  Conversion
  cost is O(nnz log nnz) (one sort + one pass over nonzeros) and is paid once
  at ``make_distributed_spmbv`` setup — the analogue of the MPI communicator
  setup phase, amortized over all solver iterations.
* :func:`make_block_ell_apply` builds a ``(n, t) -> (n, t)`` closure over a
  global CSR matrix for the sequential solver's ``backend="pallas"`` path.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.sparse.csr import BSRMatrix, CSRMatrix, csr_to_bsr
from repro.kernels.bsr_spmbv.kernel import LANES, bsr_spmbv_pallas
from repro.kernels.bsr_spmbv.ref import bsr_spmbv_ref
from repro.kernels.dispatch import resolve_dispatch


def lane_major(tiles: np.ndarray) -> np.ndarray:
    """Tiles ``(nbr, kmax, br, bc)`` -> the kernel's lane-major Block-ELL
    ``(nbg, kmax*bc, br, LANES)`` (block rows padded to whole lane groups
    with zero tiles)."""
    nbr, kmax, br, bc = tiles.shape
    nbg = max(1, -(-nbr // LANES))
    tiles = np.pad(tiles, ((0, nbg * LANES - nbr), (0, 0), (0, 0), (0, 0)))
    return (
        tiles.reshape(nbg, LANES, kmax, br, bc)
        .transpose(0, 2, 4, 3, 1)
        .reshape(nbg, kmax * bc, br, LANES)
    )


def bsr_to_block_ell(b: BSRMatrix, kmax: int | None = None):
    """BSR -> lane-major Block-ELL tiles ``(nbg, kmax*bc, br, LANES)`` +
    ``(nbr, kmax)`` block-column ids (fixed tiles per block row;
    zero-padded)."""
    nbr = b.n_block_rows
    indptr = np.asarray(b.block_indptr)
    per_row = np.diff(indptr)
    kmax = int(per_row.max()) if kmax is None else kmax
    br, bc = b.block_shape
    src_blocks = np.asarray(b.blocks)
    brow = np.repeat(np.arange(nbr), per_row)
    slot = np.arange(len(brow)) - indptr[brow]
    tiles = np.zeros((nbr, kmax, br, bc), dtype=src_blocks.dtype)
    indices = np.zeros((nbr, kmax), dtype=np.int32)
    tiles[brow, slot] = src_blocks
    indices[brow, slot] = np.asarray(b.block_indices)
    return jnp.asarray(lane_major(tiles)), jnp.asarray(indices)


def block_ell_from_csr(a: CSRMatrix, br: int, bc: int):
    return bsr_to_block_ell(csr_to_bsr(a, br, bc))


def _tile_keys(indptr, indices, n_rows: int, n_cols: int, br: int, bc: int):
    """Tile key ``block_row * nbc + block_col`` of every nonzero, and the
    sorted distinct keys.  CSR rows list their columns in order, so a
    tile's nonzeros come in runs: only the run heads are sorted."""
    nnz = int(indptr[min(n_rows, len(indptr) - 1)])
    rows = np.repeat(np.arange(n_rows, dtype=np.int64), np.diff(indptr[: n_rows + 1]))
    nbc = (n_cols + bc - 1) // bc
    key = (rows // br) * nbc + indices[:nnz] // bc
    head = np.ones(nnz, bool)
    head[1:] = key[1:] != key[:-1]
    return rows, key, head, np.unique(key[head])


def block_ell_tile_keys(indptr, indices, n_rows: int, n_cols: int, br: int, bc: int):
    """Sorted distinct tile keys ``block_row * nbc + block_col`` of a
    raw-CSR matrix's (br x bc) tiles."""
    indptr = np.asarray(indptr, dtype=np.int64)
    indices = np.asarray(indices, dtype=np.int64)
    if int(indptr[min(n_rows, len(indptr) - 1)]) == 0:
        return np.zeros(0, np.int64)
    return _tile_keys(indptr, indices, n_rows, n_cols, br, bc)[3]


def count_block_ell_tiles(indptr, indices, n_rows: int, n_cols: int, br: int, bc: int) -> int:
    """Max distinct (br x bc) tiles in any block row of a raw-CSR matrix."""
    nbc = (n_cols + bc - 1) // bc
    tiles = block_ell_tile_keys(indptr, indices, n_rows, n_cols, br, bc)
    return int(np.bincount(tiles // nbc).max()) if len(tiles) else 0


def tile_counters(keys, nbc: int, nbr: int, kmax: int) -> dict:
    """The structure of a Block-ELL layout, as the counters its conversion
    span records; ``keys`` holds the sorted tile keys of each stacked part
    (one per device), each part ``nbr`` block rows of ``kmax`` slots:

    * ``ell_kmax``: tile slots per block row;
    * ``ell_tiles``: tiles stored, over all parts;
    * ``ell_zero_slot_share``: the share of the parts' ``nbr · kmax`` slots
      that hold no tile (a zero tile the kernel streams all the same);
    * ``gather_reach``: the largest distance, in block rows, between a
      tile's block column and its block row — how far apart the rows of V
      lie that the SpMBV's gather reads for neighbouring block rows.
    """
    tiles = sum(len(k) for k in keys)
    slots = len(keys) * nbr * kmax
    reach = max((int(np.abs(k % nbc - k // nbc).max()) for k in keys if len(k)), default=0)
    return dict(
        ell_kmax=int(kmax), ell_tiles=int(tiles),
        ell_zero_slot_share=1.0 - tiles / slots if slots else 0.0,
        gather_reach=reach,
    )


def csr_arrays_to_block_ell(
    indptr, indices, data, n_rows: int, n_cols: int, br: int, bc: int,
    nbr: int, kmax: int,
):
    """Raw CSR arrays -> lane-major Block-ELL with caller-fixed (nbr, kmax)
    padding.

    Returns ``(blocks (nbg, kmax*bc, br, LANES), ell_idx (nbr, kmax))``
    (see :mod:`repro.kernels.bsr_spmbv.kernel` for the layout): tile slot k
    of block row ``g*LANES + l`` occupies ``blocks[g, k*bc:(k+1)*bc, :, l]``,
    slots filled in ascending block-column order.  The caller fixes
    ``nbr``/``kmax`` so per-rank conversions can be stacked into one
    (p, nbg, kmax*bc, br, LANES) device array; unused slots stay zero with
    block-column id 0 (safe: zero tiles contribute nothing).
    """
    indptr = np.asarray(indptr, dtype=np.int64)
    indices = np.asarray(indices, dtype=np.int64)
    data = np.asarray(data)
    nbg = max(1, -(-nbr // LANES))
    kbc = kmax * bc
    blocks = np.zeros((nbg, kbc, br, LANES), dtype=data.dtype)
    ell_idx = np.zeros((nbr, kmax), dtype=np.int32)
    nnz = int(indptr[min(n_rows, len(indptr) - 1)])
    if nnz == 0:
        return blocks, ell_idx
    nbc = (n_cols + bc - 1) // bc
    rows, key, head, uniq = _tile_keys(indptr, indices, n_rows, n_cols, br, bc)
    # each tile's slot within its block row (uniq is sorted: rows grouped)
    t_row = uniq // nbc
    t_slot = np.arange(len(uniq)) - np.searchsorted(t_row, t_row)
    if len(uniq) and int(t_slot.max()) >= kmax:
        bad = int(t_row[np.argmax(t_slot)])
        raise ValueError(f"block row {bad} overflows kmax={kmax}")
    ell_idx[t_row, t_slot] = (uniq % nbc).astype(np.int32)
    # slot of every nonzero: look up each run head, repeat over its run
    run_slot = t_slot[np.searchsorted(uniq, key[head])]
    starts = np.flatnonzero(head)
    slot = np.repeat(run_slot, np.diff(np.append(starts, nnz)))
    brow = rows // br
    kc = slot * bc + indices[:nnz] % bc
    flat = (((brow // LANES) * kbc + kc) * br + rows % br) * LANES + brow % LANES
    blocks.reshape(-1)[flat] = data[:nnz]
    return blocks, ell_idx


def block_ell_meta(a: CSRMatrix, br: int, bc: int) -> dict:
    """Tile analysis of the CSR -> Block-ELL conversion — JSON-serializable.

    This is the *choice* part of the conversion (which tile grid, how many
    tile slots per block row, how much zero padding) separated from the
    *fill* part (scattering nonzeros into the slots): persisting the meta
    lets a rebuilt handle skip the analysis pass and direct-fill via
    :func:`csr_arrays_to_block_ell` (the serve layer's eviction-aware warm
    start).  ``pad_hist[k]`` counts block rows holding exactly k tiles —
    the padding histogram behind the ``kmax`` waste; ``counters`` are
    :func:`tile_counters`.
    """
    indptr = np.asarray(a.indptr, dtype=np.int64)
    indices = np.asarray(a.indices, dtype=np.int64)
    n, m = a.shape
    n_pad = (n + br - 1) // br * br
    m_pad = (m + bc - 1) // bc * bc
    nbr, nbc = n_pad // br, m_pad // bc
    tiles = _tile_keys(indptr, indices, n, m, br, bc)[3]
    per_row = np.bincount((tiles // nbc).astype(np.int64), minlength=nbr)
    kmax = int(per_row.max()) if len(tiles) else 0
    return dict(
        br=int(br), bc=int(bc), shape=[int(n), int(m)], nnz=int(a.nnz),
        nbr=int(nbr), nbc=int(nbc), kmax=kmax,
        n_pad=int(n_pad), m_pad=int(m_pad),
        pad_hist=np.bincount(per_row, minlength=kmax + 1).tolist(),
        counters=tile_counters([tiles], nbc, nbr, kmax),
    )


def _meta_matches(meta: dict | None, a: CSRMatrix, br: int, bc: int) -> bool:
    if not isinstance(meta, dict):
        return False
    try:
        return (
            int(meta["br"]) == br
            and int(meta["bc"]) == bc
            and [int(s) for s in meta["shape"]] == [int(s) for s in a.shape]
            and int(meta["nnz"]) == a.nnz
            and int(meta["kmax"]) >= 0
        )
    except (KeyError, TypeError, ValueError):
        return False


def block_ell_arrays(a: CSRMatrix, br: int, bc: int, meta: dict | None = None):
    """CSR -> Block-ELL device arrays, optionally skipping the analysis.

    Returns ``(blocks, indices, m_pad, meta, analyzed)``.  With a valid
    ``meta`` (from :func:`block_ell_meta` of the *same* matrix/tile) the
    tile-counting analysis is skipped and the nonzeros are direct-filled
    into the known (nbr, kmax) layout (``analyzed=False``); a stale or
    missing meta triggers a fresh analysis (``analyzed=True``), never an
    error.  The produced layout is bit-identical to the historical
    CSR -> BSR -> Block-ELL path (both fill tiles in ascending block-column
    order per block row).
    """
    analyzed = not _meta_matches(meta, a, br, bc)
    if analyzed:
        meta = block_ell_meta(a, br, bc)
    n, m = a.shape
    blocks, indices = csr_arrays_to_block_ell(
        a.indptr, a.indices, a.data, n, m, br, bc,
        nbr=int(meta["nbr"]), kmax=int(meta["kmax"]),
    )
    return (
        jnp.asarray(blocks), jnp.asarray(indices), int(meta["m_pad"]),
        meta, analyzed,
    )


def make_block_ell_apply_from_arrays(blocks, indices, m_pad: int, n: int,
                                     use_pallas: bool | None = None):
    """``apply(V: (n, t)) -> (n, t)`` over precomputed Block-ELL arrays —
    the closure :func:`make_block_ell_apply` builds, minus the conversion."""

    def apply(v):
        vp = jnp.pad(v, ((0, m_pad - v.shape[0]), (0, 0)))
        w = bsr_spmbv(blocks, indices, vp, use_pallas=use_pallas)
        return w[:n]

    return apply


def make_block_ell_apply(
    a: CSRMatrix, block: int | tuple[int, int] = 8, use_pallas: bool | None = None
):
    """Build the sequential solver's SpMBV closure over the Block-ELL kernel.

    Converts ``a`` once (CSR -> BSR -> Block-ELL) and returns
    ``apply(V: (n, t)) -> (n, t)`` that pads V to the tile grid, runs
    :func:`bsr_spmbv`, and slices back to true rows.  ``block`` is an int
    for square tiles or an explicit (br, bc) pair — e.g. the
    ``ell_block`` a :class:`repro.tune.TunedConfig` selected.
    """
    br, bc = (block, block) if isinstance(block, int) else block
    b = csr_to_bsr(a, br, bc)
    blocks, indices = bsr_to_block_ell(b)
    n = a.shape[0]
    m_pad = b.shape[1]

    def apply(v):
        vp = jnp.pad(v, ((0, m_pad - v.shape[0]), (0, 0)))
        w = bsr_spmbv(blocks, indices, vp, use_pallas=use_pallas)
        return w[:n]

    return apply


def bsr_spmbv(blocks, indices, v, use_pallas: bool | None = None):
    """W = A @ V.  Pallas kernel on TPU; interpret-mode Pallas or the jnp
    oracle elsewhere (``use_pallas=True`` forces interpret-mode validation).
    GPU hosts fall back to the oracle with an explicit warn-once (see
    :mod:`repro.kernels.dispatch`)."""
    use_pallas, interpret = resolve_dispatch("bsr_spmbv", use_pallas)
    if use_pallas:
        return bsr_spmbv_pallas(blocks, indices, v, interpret=interpret)
    return bsr_spmbv_ref(blocks, indices, v)
