"""Content fingerprinting of CSR operators — the registry key.

A solver session is worth caching exactly as long as the *matrix values*
are unchanged; object identity is useless across requests (every client
re-assembles its CSR) and ``(shape, nnz)`` collides trivially.  The
fingerprint therefore hashes the mathematical content:

* shape and data dtype;
* the row pointer (row lengths);
* column indices and values **canonicalized within each row** — two
  assemblies of the same matrix that emit a row's entries in different
  orders (a very common artifact of FEM assembly order) fingerprint
  identically, while perturbing any single stored value changes the key.

blake2b (128-bit digest) over the raw array bytes: collision probability
is negligible at any realistic registry size, and hashing is a single
pass over the CSR arrays — microseconds next to one solve.
"""

from __future__ import annotations

import hashlib

import numpy as np


def fingerprint_csr(a) -> str:
    """Hex content fingerprint of a :class:`~repro.sparse.csr.CSRMatrix`."""
    indptr = np.ascontiguousarray(np.asarray(a.indptr, dtype=np.int64))
    indices = np.asarray(a.indices, dtype=np.int64)
    data = np.asarray(a.data)
    n_rows = len(indptr) - 1
    # within-row canonical column order (stable for the extremely unlikely
    # duplicate-entry case: lexsort keys are (secondary, primary)); rows
    # already in order — what assemblers usually emit — skip the sort
    step = np.diff(indices)
    starts = indptr[1:-1]
    step[starts[(starts > 0) & (starts < len(indices))] - 1] = 0  # row changes
    if not np.all(step >= 0):
        row_of = np.repeat(np.arange(n_rows, dtype=np.int64), np.diff(indptr))
        order = np.lexsort((indices, row_of))
        indices, data = indices[order], data[order]
    h = hashlib.blake2b(digest_size=16)
    h.update(np.asarray(a.shape, dtype=np.int64).tobytes())
    h.update(data.dtype.str.encode())
    h.update(indptr.tobytes())
    h.update(np.ascontiguousarray(indices).tobytes())
    h.update(np.ascontiguousarray(data).tobytes())
    return h.hexdigest()


def operator_nbytes(a) -> int:
    """Byte footprint of the CSR arrays — the registry's eviction currency.

    A built session holds more than the CSR (plan index arrays, Block-ELL
    copies, compiled programs), but those all scale with the CSR footprint,
    so budgeting on it gives stable, explainable eviction behavior.
    """
    return int(sum(
        np.asarray(x).nbytes for x in (a.indptr, a.indices, a.data)
    ))
