"""Public ops: fused ECG block updates (Pallas on TPU, oracle elsewhere).

``block_update`` is the historical two-output op (X/R only); ``ecg_tail`` is
the full per-iteration tail used by the solver hot path when
``backend="pallas"`` — it additionally produces Z = AP − P·d − P_old·d_old
in the same row pass, so P and AP stream from HBM once per iteration.
"""

from __future__ import annotations

from repro.kernels.block_update.kernel import block_update_pallas, ecg_tail_pallas
from repro.kernels.block_update.ref import block_update_ref, ecg_tail_ref
from repro.kernels.dispatch import resolve_dispatch


def block_update(x, r, p, ap, c, use_pallas: bool | None = None,
                 block_rows: int | None = None):
    use_pallas, interpret = resolve_dispatch("block_update", use_pallas)
    if use_pallas:
        return block_update_pallas(x, r, p, ap, c, block_rows=block_rows, interpret=interpret)
    return block_update_ref(x, r, p, ap, c)


def ecg_tail(x, r, p, ap, p_old, c, d, d_old, use_pallas: bool | None = None,
             block_rows: int | None = None):
    """Fused tail of one ECG iteration; see :func:`ecg_tail_ref` for the math."""
    use_pallas, interpret = resolve_dispatch("ecg_tail", use_pallas)
    if use_pallas:
        return ecg_tail_pallas(
            x, r, p, ap, p_old, c, d, d_old, block_rows=block_rows, interpret=interpret
        )
    return ecg_tail_ref(x, r, p, ap, p_old, c, d, d_old)
