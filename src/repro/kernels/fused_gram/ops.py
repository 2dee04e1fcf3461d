"""Public op: fused ECG gram products (Pallas on TPU, oracle elsewhere).

Hot-path wiring: with ``backend="pallas"`` this op IS allreduce #2's local
compute — ``repro.core.ecg.ecg_solve`` wraps it in ``allreduce`` and
``repro.sparse.spmbv.distributed_ecg`` runs it per device inside the
shard_map ``gram2``, feeding exactly one psum (the 3t² payload of §3.1).
"""

from __future__ import annotations

from repro.kernels.dispatch import resolve_dispatch
from repro.kernels.fused_gram.kernel import fused_gram_pallas
from repro.kernels.fused_gram.ref import fused_gram_ref


def fused_gram(p, r, ap, ap_old, use_pallas: bool | None = None,
               block_rows: int | None = None):
    use_pallas, interpret = resolve_dispatch("fused_gram", use_pallas)
    if use_pallas:
        return fused_gram_pallas(p, r, ap, ap_old, block_rows=block_rows, interpret=interpret)
    return fused_gram_ref(p, r, ap, ap_old)
