"""Time to tolerance per right-hand side: the window's wall time over the
solves completed in it (each from dispatch to its host copy)."""


def read(r):
    return r.window_s / len(r.solves) if r.solves else None
