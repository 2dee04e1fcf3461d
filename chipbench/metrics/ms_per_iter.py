"""Solve time per iteration over the window, in milliseconds: the solves'
host-clock times (each from dispatch to its host copy) over their
iterations."""


def read(r):
    return 1e3 * sum(s.seconds for s in r.solves) / r.iters if r.iters else None
