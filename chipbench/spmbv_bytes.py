"""Operations and bytes one Block-ELL SpMBV needs, from its shapes.

The least the product ``W = A V`` has to move: the stored Block-ELL tiles
(``nbr_pad`` block rows of ``kmax`` tiles of ``br`` x ``bc``, zero slots
included, since the layout stores them), the ``(nbr, kmax)`` int32
block-column ids, V read once and W written once (``nbr_pad · br`` rows of
``t`` columns each).  Operations: a multiply and an add for every stored
tile entry and column.
"""

from __future__ import annotations


def spmbv_bytes(nbr_pad: int, kmax: int, br: int, bc: int, t: int,
                itemsize: int = 4) -> int:
    tiles = nbr_pad * kmax * br * bc * itemsize
    ids = nbr_pad * kmax * 4
    rows = nbr_pad * br
    return tiles + ids + 2 * rows * t * itemsize


def spmbv_flops(nbr_pad: int, kmax: int, br: int, bc: int, t: int) -> int:
    return 2 * nbr_pad * kmax * br * bc * t


def from_kernel_shapes(tiles: tuple, gathered: tuple, bc: int, itemsize: int = 4):
    """(flops, bytes) of one call of the lane-major kernel, from its operand
    shapes: tiles ``(nbg, kmax·bc, br, lanes)`` and gathered V
    ``(nbg, kmax·bc, t, lanes)``."""
    nbg, kbc, br, lanes = tiles
    t = gathered[2]
    nbr_pad, kmax = nbg * lanes, kbc // bc
    return (spmbv_flops(nbr_pad, kmax, br, bc, t),
            spmbv_bytes(nbr_pad, kmax, br, bc, t, itemsize))
