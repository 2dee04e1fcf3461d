"""Host time to lower and compile the cell's solve program
(``ECGSolver.lowered_text``): a cache hit after the first run in a
checkout."""


def read(r):
    return r.timings.get("compile_s")
