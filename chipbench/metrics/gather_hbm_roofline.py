"""The SpMBV gather's share of its HBM roofline, in percent: the bytes the
gathers of the traced solve need (``chipbench/gather.py``: the block-column
ids read, the gathered rows of V read and written, from the shapes of each
Block-ELL kernel call they feed) at the chip's peak HBM bandwidth, over the
device time of the events under the ``ecg.gather`` scope, summed over the
chips."""

from chipbench import gather, scopes, trace


def read(r):
    if r.trace is None or not r.peaks:
        return None
    bc = r.config["solver"]["ell_block"][1]
    need = spent = 0.0
    for ops in r.trace.devices.values():
        for o in scopes.solve_ops(r, ops):
            ins = r.hlo.find(o)
            if trace.is_pallas(ins, "bsr_spmbv"):
                need += gather.from_kernel_shapes(ins.shapes()[1], bc)
            elif gather.is_gather(ins):
                spent += o.dur * 1e-9
    if not need or not spent:
        return None
    return 100.0 * need / r.peaks["hbm_bytes_per_s"] / spent
