"""The SpMBV kernel's share of its HBM roofline, in percent: the bytes one
call needs (``spmbv_bytes``: stored Block-ELL tiles and block-column ids,
V read once, W written once, from the kernel's operand shapes) at the
chip's peak HBM bandwidth, over the device time per call of the kernel's
events, summed over the chips.

The events are the Pallas kernel's own (the ``tpu_custom_call`` issued by
``bsr_spmbv_pallas``).  The XLA gather of V that feeds the kernel is not
attributed to them: the program names no scope over the whole SpMBV yet.
"""

from chipbench import spmbv_bytes, trace


def read(r):
    if r.trace is None or not r.peaks:
        return None
    t0, t1 = r.window_ns
    need = spent = 0.0
    bc = r.config["solver"]["ell_block"][1]
    for ops in r.trace.devices.values():
        for o in trace.clip(ops, t0, t1):
            ins = r.hlo.find(o)
            if not trace.is_pallas(ins, "bsr_spmbv"):
                continue
            tiles, gathered = ins.shapes()[:2]
            need += spmbv_bytes.from_kernel_shapes(tiles, gathered, bc)[1]
            spent += o.dur * 1e-9
    if not spent:
        return None
    return 100.0 * need / r.peaks["hbm_bytes_per_s"] / spent
