"""Device time per iteration (of the traced solve) of the block-vector kernels (the fused Gram
product and the fused X/R/Z update), in milliseconds, on the chip where it
is largest."""

from chipbench import trace

KERNELS = ("fused_gram", "ecg_tail", "block_update")


def read(r):
    if r.trace is None or not r.traced_iters:
        return None
    t0, t1 = r.window_ns
    worst = None
    for ops in r.trace.devices.values():
        ns, n = trace.time_by(
            trace.clip(ops, t0, t1),
            lambda o: any(trace.is_pallas(r.hlo.find(o), k) for k in KERNELS))
        if n:
            worst = max(worst or 0.0, ns)
    return None if worst is None else worst * 1e-6 / r.traced_iters
