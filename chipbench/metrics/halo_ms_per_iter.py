"""Device time per iteration (of the traced solve) of the halo exchange's collective permutes
(their start and done halves), in milliseconds, on the chip where it is
largest."""

from chipbench import trace


def read(r):
    if r.trace is None or not r.traced_iters:
        return None
    t0, t1 = r.window_ns
    per = [trace.time_by(trace.clip(ops, t0, t1),
                         lambda o: trace.is_collective(r.hlo.find(o), "collective-permute"))
           for ops in r.trace.devices.values()]
    if not any(n for _, n in per):
        return None
    return max(ns for ns, _ in per) * 1e-6 / r.traced_iters
