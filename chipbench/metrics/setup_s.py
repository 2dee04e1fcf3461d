"""Process start to window start: generation, ``ECGSolver.build``, compile,
warm-up and the draw of the traffic's pool."""


def read(r):
    return r.timings["setup_s"]
