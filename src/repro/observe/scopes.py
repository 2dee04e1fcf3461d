"""Names of the ECG iteration's stages inside the compiled solve program.

Each stage is traced under a ``jax.named_scope`` of one of these names, so
every HLO instruction it lowers to carries the name as one ``/``-separated
component of its ``op_name`` metadata (``jit(run)/while/body/ecg.gram/...``)
and a profiler event can be tied to the stage that issued it.  A named
scope adds metadata only: the compiled program is otherwise the same.

The scopes are opened where the stages are built once, so that every
iteration scheme gets them: the reduction and update closures of
:func:`repro.core.ecg.make_ecg_runner`, the factorizations
(``_chol_inv_apply``, ``rank_revealing_apply``), the breakdown guard of
``_guarded_while``, the distributed halo exchange and the SpMBV kernel's
gather of V (``_gather_lanes``).  The glue between
stages inside each scheme's ``iterate`` is left unscoped.
"""

from __future__ import annotations

import jax

SPMBV = "ecg.spmbv"        # gather of V, the Block-ELL kernel, local/remote split
GATHER = "ecg.gather"      # the gather of V into the kernel's layout (inside SPMBV)
EXCHANGE = "ecg.exchange"  # halo pack, ppermute rounds, unpack (inside SPMBV)
GRAM = "ecg.gram"          # the Gram products and their psums
FACTOR = "ecg.factor"      # t x t (pivoted) Cholesky and the TRSMs
UPDATE = "ecg.update"      # the X/R/Z update
CHECK = "ecg.check"        # residual-norm psum, loop condition, breakdown guard


def scoped(name: str, fn):
    """``fn`` traced under ``jax.named_scope(name)``; None stays None."""
    if fn is None:
        return None

    def in_scope(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)

    return in_scope
