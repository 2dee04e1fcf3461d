"""The SpMBV's gather of V: which device events are it, and the bytes it
needs, from the kernel's shapes.

The program traces the gather (``_gather_lanes`` of the Block-ELL kernel:
the padded block-column ids, V spelled as rows of ``bc·t``, the gather and
the transposes onto the lanes) under the ``jax.named_scope``
``ecg.gather``, inside ``ecg.spmbv``.  ``scopes.STAGES`` leaves it out, so
its events stay in the SpMBV stage.  A program that names no gather gives
no gather metric.

The least the gather must move, per call of the kernel it feeds: the
``(nbr_pad, kmax)`` int32 block-column ids read once, and each of the
``nbr_pad · kmax · bc`` gathered rows of V (``t`` wide) read once and
written once, as the gathered ``(nbg, kmax·bc, t, lanes)`` operand.
"""

from __future__ import annotations

from chipbench import scopes, trace

SCOPE = "ecg.gather"


def is_gather(instr: trace.Instr | None) -> bool:
    return instr is not None and SCOPE in instr.op_name.split("/")


def gather_ns(r, ops: list[trace.Op]) -> float:
    """Device time of the solve program's gather events among one chip's
    ``ops`` in the traced solve."""
    return sum(o.dur for o in scopes.solve_ops(r, ops) if is_gather(r.hlo.find(o)))


def gather_bytes(nbr_pad: int, kmax: int, bc: int, t: int, itemsize: int = 4) -> int:
    ids = nbr_pad * kmax * 4
    rows = nbr_pad * kmax * bc * t * itemsize
    return ids + 2 * rows


def from_kernel_shapes(gathered: tuple, bc: int, itemsize: int = 4) -> int:
    """Bytes of the gather that feeds one kernel call whose gathered V
    operand is ``(nbg, kmax·bc, t, lanes)``."""
    nbg, kbc, t, lanes = gathered
    return gather_bytes(nbg * lanes, kbc // bc, bc, t, itemsize)
