"""Host time of ``ECGSolver.build``: the program's own top-level ``build``
span (partition, exchange plan, Block-ELL conversion and the rest of what
the handle builds), recorded by the ``repro.observe`` tracer the harness
installs in traced runs."""


def read(r):
    spans = [s.dur for s in r.spans if s.name == "build" and s.dur is not None]
    return float(sum(spans)) if spans else None
