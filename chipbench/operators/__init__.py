"""The benchmark's own operator generators, one module per generator.

A configuration names its generator (``"operator": {"generator": ...}``);
``harness.Bench.operator`` loads ``operators/<generator>.py`` and calls its
``generate(dtype=..., **params)``, which returns a :class:`HostCSR`.  The
copies live here so that a change to the program cannot change the
benchmark's data.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class HostCSR:
    """A sparse matrix as plain host arrays (rows sorted by column)."""

    indptr: np.ndarray   # (n + 1,) int64
    indices: np.ndarray  # (nnz,) int32
    data: np.ndarray     # (nnz,) in the configuration's precision
    n: int

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])
