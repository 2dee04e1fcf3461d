"""The plain float64 reference, independent of the program.

* :func:`matvec` / :func:`true_relres`: ``A @ x`` over the benchmark's own
  host CSR arrays in float64 numpy, in blocks of rows.  This decides each
  solve's ``correct``: the relative residual ``‖b − A x‖ / ‖b‖`` of the
  answer the timed path returned.
* :func:`ecg_solve`: classic enlarged CG (the paper's Algorithm 3) in plain
  ``jax.numpy``, with every product taken at one stated precision.  At
  ``"highest"`` it is a second witness of what float32 reaches; at
  ``"high"`` (three bfloat16 passes, written out so that it means the same
  on every backend) it is the control that ``control.py`` runs in the
  program's place, which has to come out not correct.
"""

from __future__ import annotations

import numpy as np

from chipbench.operators import HostCSR

_ROWS_PER_BLOCK = 1 << 18


def matvec(a: HostCSR, x: np.ndarray) -> np.ndarray:
    """``A @ x`` in float64 (x of shape (n,) or (n, k))."""
    x = np.asarray(x, np.float64)
    y = np.zeros((a.n,) + x.shape[1:], np.float64)
    for lo in range(0, a.n, _ROWS_PER_BLOCK):
        hi = min(lo + _ROWS_PER_BLOCK, a.n)
        s, e = int(a.indptr[lo]), int(a.indptr[hi])
        if e == s:
            continue
        prod = a.data[s:e].astype(np.float64).reshape((-1,) + (1,) * (x.ndim - 1)) \
            * x[a.indices[s:e]]
        counts = np.diff(a.indptr[lo : hi + 1])
        nz = counts > 0
        y[lo:hi][nz] = np.add.reduceat(prod, (a.indptr[lo:hi] - s)[nz], axis=0)
    return y


def true_relres(a: HostCSR, x: np.ndarray, b: np.ndarray) -> float:
    """``‖b − A x‖₂ / ‖b‖₂`` in float64."""
    b64 = np.asarray(b, np.float64)
    return float(np.linalg.norm(b64 - matvec(a, x)) / np.linalg.norm(b64))


def tiles(a: HostCSR, bs: int):
    """Square ``bs`` x ``bs`` tiles of ``a``: ``(nbr, kmax, bs, bs)`` values
    and ``(nbr, kmax)`` block-column ids (unused slots: zero tile, id 0)."""
    rows = np.repeat(np.arange(a.n, dtype=np.int64), np.diff(a.indptr))
    cols = a.indices.astype(np.int64)
    nbr = -(-a.n // bs)
    key = (rows // bs) * nbr + cols // bs
    uniq, tile_of = np.unique(key, return_inverse=True)
    trow = uniq // nbr
    slot = np.arange(len(uniq)) - np.searchsorted(trow, trow)
    kmax = int(slot.max()) + 1
    vals = np.zeros((nbr, kmax, bs, bs), a.data.dtype)
    vals[trow[tile_of], slot[tile_of], rows % bs, cols % bs] = a.data
    idx = np.zeros((nbr, kmax), np.int32)
    idx[trow, slot] = uniq % nbr
    return vals, idx


def _products(precision: str):
    """``mm(spec, x, y)``: an einsum at the named precision."""
    import jax
    import jax.numpy as jnp

    if precision == "highest":
        return lambda spec, x, y: jnp.einsum(
            spec, x, y, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")

    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    def mm(spec, x, y):
        (xh, xl), (yh, yl) = split(x), split(y)
        one = lambda p, q: jnp.einsum(spec, p, q, preferred_element_type=jnp.float32)
        return one(xh, yl) + one(xl, yh) + one(xh, yh)

    return mm


def ecg_solve(vals, idx, b, *, t: int, tol: float, max_iters: int,
              precision: str = "highest"):
    """Classic ECG on the tiled operator from ``x0 = 0``; returns
    ``(x, iterations, converged)``.  ``tol`` bounds ``‖r‖ / ‖b‖``, where r
    is the recursive residual, as in the program."""
    import jax
    import jax.numpy as jnp

    mm = _products(precision)
    nbr, kmax, bs, _ = vals.shape
    n = b.shape[0]
    vals = jnp.asarray(vals, jnp.float32)
    idx = jnp.asarray(idx)

    def apply(v):  # (n, t) -> (n, t)
        vp = jnp.pad(v, ((0, nbr * bs - n), (0, 0))).reshape(nbr, bs, -1)
        return mm("rkij,rkjt->rit", vals, vp[idx]).reshape(nbr * bs, -1)[:n]

    def run(b):
        b = b.astype(jnp.float32)
        sub = (jnp.arange(n) * t) // n
        r = b[:, None] * jax.nn.one_hot(sub, t, dtype=jnp.float32)
        zeros = jnp.zeros((n, t), jnp.float32)
        bnorm = jnp.linalg.norm(b)

        def step(c):
            az = apply(c["z"])
            g = mm("ni,nj->ij", c["z"], az)
            cinv = jnp.linalg.inv(jnp.linalg.cholesky(g).T)  # G = Cᵀ C
            p = mm("ni,ij->nj", c["z"], cinv)
            ap = mm("ni,ij->nj", az, cinv)
            coef = mm("ni,nj->ij", p, c["r"])
            x = c["x"] + mm("ni,ij->nj", p, coef)
            r = c["r"] - mm("ni,ij->nj", ap, coef)
            z = ap - mm("ni,ij->nj", p, mm("ni,nj->ij", ap, ap)) \
                - mm("ni,ij->nj", c["p"], mm("ni,nj->ij", c["ap"], ap))
            return dict(x=x, r=r, z=z, p=p, ap=ap, k=c["k"] + 1,
                        rn=jnp.linalg.norm(r.sum(axis=1)) / bnorm)

        init = dict(x=zeros, r=r, z=r, p=zeros, ap=zeros, k=jnp.int32(0),
                    rn=jnp.float32(1.0))
        out = jax.lax.while_loop(
            lambda c: (c["rn"] > tol) & (c["k"] < max_iters), step, init)
        return out["x"].sum(axis=1), out["k"], out["rn"] <= tol

    with jax.default_matmul_precision(precision):
        x, k, ok = jax.jit(run)(jnp.asarray(b))
    return np.asarray(x), int(k), bool(ok)
