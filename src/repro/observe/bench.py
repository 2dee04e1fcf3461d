"""The one warmup + median-of-k wall timer, as measured tracer spans.

Before this module the repo carried the same seeded timing loop in three
places (``repro.analysis.ecg_bench._timeit``, ``benchmarks/common.timed``,
an inline loop in ``benchmarks/serve_bench.py``); they now all route
here, so every benchmark measures with identical discipline *and* every
measurement is a span a sink can export — run any sweep with a tracer
installed and the timing loop itself shows up in ``chrome://tracing``.
"""

from __future__ import annotations

import numpy as np

from repro.observe.tracer import Tracer

#: sink-less tracer whose spans are measured and dropped — the timing
#: backend when the caller installs no (enabled) tracer of their own
_MEASURER = Tracer()


def _sync(out):
    """Block until a jax result is actually materialized (no-op for host
    values) — the timed region must include device compute, not just the
    async dispatch."""
    try:
        import jax

        jax.block_until_ready(out)
    except Exception:
        pass
    return out


def timed_median(fn, *args, repeats: int = 3, warmup: int = 1,
                 label: str = "timed", tracer=None, sync=True, **kw):
    """``(result, median wall seconds per call)`` over ``repeats`` timed
    calls of ``fn(*args, **kw)``.

    warmup: untimed leading calls (compile/caches; 0 to time cold).
    tracer: each timed call becomes one ``timed/<label>`` span on it; a
            None or disabled tracer falls back to a sink-less measuring
            tracer (pure timing, zero records).
    sync:   ``jax.block_until_ready`` the result inside the timed region
            (set False for host-only callables to skip the import).
    """
    if repeats < 1:
        raise ValueError(f"repeats must be >= 1, got {repeats}")
    tr = tracer if (tracer is not None and tracer.enabled) else _MEASURER
    out = None
    for _ in range(warmup):
        out = fn(*args, **kw)
        if sync:
            _sync(out)
    ts = []
    for i in range(repeats):
        with tr.span(f"timed/{label}", cat="bench", rep=i) as sp:
            out = fn(*args, **kw)
            if sync:
                _sync(out)
        ts.append(sp.dur)
    return out, float(np.median(ts))


def timed_median_us(fn, *args, **kw) -> float:
    """Median wall **microseconds** per call — the historical ``_timeit``
    signature the kernel/comm sweeps print."""
    _, s = timed_median(fn, *args, **kw)
    return s * 1e6
