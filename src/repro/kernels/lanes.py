"""Block vectors as the row-pass Pallas kernels see them: the (t, n) view.

XLA keeps a narrow (n, t) array in layout ``{0,1}``: in HBM it lies as
(t, n), lane-dense along n.  So ``x.T`` is a bitcast, not a copy, and the
row-pass kernels (``fused_gram``, ``block_update``/``ecg_tail``) take each
block vector as that (t, n) array in (t, L) blocks: t on the sublanes as a
full-dimension block, L block-vector rows on the lanes.  Their grid runs
over ``pl.cdiv(n, L)`` blocks, the last one ragged: Pallas drops the
writes past n, and a reduction masks the lanes past n.
"""

from __future__ import annotations

import numpy as np

LANES = 128
#: bytes of one (t, L) block in VMEM; the tail's eight operands, double
#: buffered, then take 8 MiB of v5e's 16 MiB default scoped VMEM
BLOCK_BYTES = 512 * 1024


def lane_block(n: int, t: int, dtype, block_rows: int | None = None) -> int:
    """L, block-vector rows per grid step: ``block_rows`` rounded up to a
    multiple of 128 (by default as many as fit :data:`BLOCK_BYTES` with t
    padded to whole sublane tiles), or all n when that covers them."""
    itemsize = np.dtype(dtype).itemsize
    if block_rows is None:
        sublanes = 32 // itemsize
        tp = -(-t // sublanes) * sublanes
        block_rows = max(LANES, BLOCK_BYTES // (tp * itemsize) // LANES * LANES)
    rows = -(-block_rows // LANES) * LANES
    return n if rows >= n else rows
