"""Share of the traced solve, in percent, in which no operation ran on the
device, on the chip where it is largest: 1 − (union of the device's
operation intervals) / (the solve's length, from dispatch to host copy)."""

from chipbench import trace


def read(r):
    if r.trace is None or not r.trace.devices:
        return None
    t0, t1 = r.window_ns
    return 100.0 * max(trace.idle_share(ops, t0, t1) for ops in r.trace.devices.values())
