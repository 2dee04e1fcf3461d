"""Iterations per solve (``SolveResult.n_iters``), over the window."""


def read(r):
    return r.iters / len(r.solves) if r.solves else None
