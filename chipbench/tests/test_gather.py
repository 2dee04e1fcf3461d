"""The gather metrics (``chipbench/gather.py``, ``metrics/gather_*.py``)
on a hand-written compiled program and trace: the events under
``ecg.gather``, the bytes from the kernel's operand shapes, and nothing at
all from a program that names no gather."""

import pytest

from chipbench import gather, harness, trace as tr
from chipbench.harness import Bench
from chipbench.tests.small import ROOT

HLO = """\
HloModule jit_run, entry_computation_layout={()->f32[8]{0}}

ENTRY %main.1 () -> f32[8] {
  %copy.1 = f32[8]{0} copy(), metadata={op_name="jit(run)/while/body/ecg.spmbv/jit(bsr_spmbv_pallas)/ecg.gather/transpose"}
  %fusion.2 = f32[8]{0} fusion(), kind=kLoop, calls=%f, metadata={op_name="jit(run)/while/body/ecg.spmbv/jit(bsr_spmbv_pallas)/ecg.gather/gather"}
  %bsr_spmbv.3 = f32[462,16,8,128]{3,2,1,0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[462,80,16,128]{3,2,1,0}, f32[462,80,8,128]{3,2,1,0}}, metadata={op_name="jit(run)/while/body/ecg.spmbv/jit(bsr_spmbv_pallas)/bsr_spmbv/pallas_call"}
  %copy.4 = f32[8]{0} copy(), metadata={op_name="jit(run)/while/body/ecg.spmbv/jit(bsr_spmbv_pallas)/transpose"}
  ROOT %add.5 = f32[8]{0} add(), metadata={op_name="jit(run)/while/body/ecg.gatherx/add"}
}
"""

#: two iterations on one chip: each a relayout (100 ns) and the gather
#: (300 ns) under the scope, the kernel, and two events outside it
OPS = [tr.Op(name, float(start), float(dur), "jit_run") for name, start, dur in [
    ("copy.1", 0, 100), ("fusion.2", 100, 300), ("bsr_spmbv.3", 400, 500),
    ("copy.4", 900, 50), ("add.5", 950, 50),
    ("copy.1", 1000, 100), ("fusion.2", 1100, 300), ("bsr_spmbv.3", 1400, 500),
    ("copy.4", 1900, 50), ("add.5", 1950, 50),
]]


def readings(hlo_text):
    bench = Bench(ROOT)
    cell = bench.cell("audikw1_1chip.rhs_stream")
    solves = [harness.Solve(None, None, 2, True, 1.0)]
    return harness.Readings(
        cell=cell, config=bench.config(cell["config"]), traffic={}, solves=solves,
        window_s=1.0, timings={}, spans=[],
        trace=tr.Trace(devices={"/device:TPU:0": OPS}, annotations=[]),
        window_ns=(0.0, 2000.0), hlo=tr.HloIndex(hlo_text),
        peaks={"hbm_bytes_per_s": 819e9})


def read(metric, r):
    return Bench(ROOT).reader(metric)(r)


def test_gather_events_and_time():
    r = readings(HLO)
    names = {o.name for o in OPS if gather.is_gather(r.hlo.find(o))}
    assert names == {"copy.1", "fusion.2"}  # a whole component only
    assert read("gather_ms_per_iter", r) == pytest.approx(400e-6)


def test_gather_bytes_and_roofline():
    # the audikw_1 surrogate on one chip: 462 lane groups of 128 block rows,
    # 5 tiles of 16 columns, t = 8: ids, then V's gathered rows read and written
    per_call = gather.from_kernel_shapes((462, 80, 8, 128), bc=16)
    assert per_call == 59136 * 5 * 4 + 2 * 59136 * 5 * 16 * 8 * 4
    assert gather.gather_bytes(2, 3, 2, 4) == 2 * 3 * 4 + 2 * 2 * 3 * 2 * 4 * 4
    want = 100.0 * 2 * per_call / 819e9 / (800e-9)
    assert read("gather_hbm_roofline", readings(HLO)) == pytest.approx(want)


def test_program_without_the_scope_gives_nothing():
    r = readings(HLO.replace("ecg.gather/", ""))
    assert read("gather_ms_per_iter", r) is None
    assert read("gather_hbm_roofline", r) is None
