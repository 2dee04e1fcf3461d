"""Device time per iteration (of the traced solve) of the SpMBV's gather of
V, in milliseconds, on the chip where it is largest: the events under the
program's ``ecg.gather`` scope (``chipbench/gather.py``), a part of
``spmbv_ms_per_iter``."""

from chipbench import gather


def read(r):
    if r.trace is None or not r.traced_iters:
        return None
    worst = max((gather.gather_ns(r, ops) for ops in r.trace.devices.values()), default=0.0)
    return worst * 1e-6 / r.traced_iters if worst else None
