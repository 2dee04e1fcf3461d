"""The stage of each device event (``chipbench/scopes.py``): assignment from
the compiled program's ``op_name``, the exchange's exposed part, and the
stage metrics of whole traced runs on the CPU."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import scopes, trace as tr
from chipbench.tests.small import ROOT

HLO = """\
HloModule jit_run, entry_computation_layout={()->f32[8]{0}}

ENTRY %main.1 () -> f32[8] {
  %gather.1 = f32[8]{0} fusion(), kind=kLoop, calls=%f, metadata={op_name="jit(run)/while/body/ecg.spmbv/jit(bsr_spmbv_pallas)/gather"}
  %bsr_spmbv.2 = f32[8]{0} custom-call(%gather.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/while/body/ecg.spmbv/jit(bsr_spmbv_pallas)/bsr_spmbv/pallas_call"}
  %cp.3 = f32[8]{0} collective-permute(%gather.1), channel_id=1, metadata={op_name="jit(run)/while/body/ecg.spmbv/shard_map/ecg.exchange/ppermute"}
  %ar.4 = f32[8]{0} all-reduce(%gather.1), channel_id=2, metadata={op_name="jit(run)/while/body/ecg.gram/shard_map/psum"}
  %chol.5 = f32[8]{0} custom-call(%ar.4), custom_call_target="Cholesky", metadata={op_name="jit(run)/while/body/ecg.factor/cholesky"}
  %tail.6 = f32[8]{0} custom-call(%ar.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/while/body/ecg.update/jit(ecg_tail_pallas)/ecg_tail/pallas_call"}
  %select.7 = f32[8]{0} select(%ar.4, %ar.4, %ar.4), metadata={op_name="jit(run)/while/body/ecg.check/select_n"}
  %copy.8 = f32[8]{0} copy(%ar.4)
  %add.9 = f32[8]{0} add(%ar.4, %ar.4), metadata={op_name="jit(run)/while/body/ecg.gramx/add"}
  ROOT %add.10 = f32[8]{0} add(%ar.4, %ar.4), metadata={op_name="jit(run)/while/body/add"}
}
"""


def op(name, start, dur, module="jit_run"):
    return tr.Op(name, float(start), float(dur), module)


def test_stage_of_each_instruction():
    hlo = tr.HloIndex(HLO)
    got = {name: scopes.stage(hlo.find(op(name, 0, 1)))
           for name in hlo.by_module["jit_run"]}
    assert got == {
        "gather.1": "ecg.spmbv", "bsr_spmbv.2": "ecg.spmbv",
        "cp.3": "ecg.exchange",  # nested in ecg.spmbv: the inner scope
        "ar.4": "ecg.gram", "chol.5": "ecg.factor", "tail.6": "ecg.update",
        "select.7": "ecg.check",
        "copy.8": "unscoped",  # no op_name
        "add.9": "unscoped",   # a whole component only
        "add.10": "unscoped",
    }
    assert scopes.stage(None) == "unscoped"
    assert scopes.names_stages(hlo)
    assert not scopes.names_stages(tr.HloIndex(HLO.replace("ecg.", "ecg_")))
    # the readers' selections are untouched by the stage components
    assert tr.is_pallas(hlo.find(op("bsr_spmbv.2", 0, 1)), "bsr_spmbv")
    assert tr.is_pallas(hlo.find(op("tail.6", 0, 1)), "ecg_tail")


@pytest.mark.parametrize("others,exposed", [
    ([op("x", 0, 30)], 0.0),                      # fully hidden
    ([op("x", 15, 20)], 5.0),                     # half hidden
    ([op("x", 30, 5), op("y", 0, 5)], 10.0),      # not hidden
    ([op("x", 10, 2), op("y", 16, 2)], 6.0),      # two holes punched
])
def test_exchange_exposed_by_hand(others, exposed):
    mine = [op("cp", 10, 4), op("cp", 12, 8)]     # union [10, 20]
    assert scopes.uncovered_ns(mine, others) == pytest.approx(exposed)


#: one traced whole run through ``drive.py``, with the harness's readings
#: kept, printing the result line and each chip's time by stage
_STAGE_RUN = r"""
import json, sys
from chipbench import harness, scopes, trace
from chipbench.tests import drive

seen = []
attach = harness._attach_trace


def keep(r, *args, **kwargs):
    attach(r, *args, **kwargs)
    seen.append(r)


harness._attach_trace = keep
drive.main(sys.argv[1:])
r = seen[0]
t0, t1 = r.window_ns
(module,) = r.hlo.by_module
print(json.dumps({
    "iters": r.traced_iters,
    "stage_ns": scopes.stage_ns(r),
    "program_ns": {dev: sum(o.dur for o in trace.clip(ops, t0, t1) if o.module == module)
                   for dev, ops in r.trace.devices.items()},
    "report": scopes.report(r),
}))
"""

SIX = ("spmbv_ms_per_iter", "gram_ms_per_iter", "factor_ms_per_iter",
       "update_ms_per_iter", "check_ms_per_iter", "unscoped_ms_per_iter")


@pytest.mark.parametrize("devices", [1, 4])
def test_stage_metrics_of_a_traced_run(devices):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _STAGE_RUN, "--trace", "1", "--devices", str(devices)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    result, stages = (json.loads(line) for line in out.stdout.strip().splitlines()[-2:])
    assert result["correct"] is True
    m = {k: v["value"] for k, v in result["metrics"].items()}
    per = 1e-6 / stages["iters"]
    for dev, by in stages["stage_ns"].items():
        # the six stage metrics add up to the solve program's device time
        assert sum(by.values()) == pytest.approx(stages["program_ns"][dev], rel=1e-9)
        assert stages["report"][dev]["total_ms_per_iter"] == pytest.approx(
            stages["program_ns"][dev] * per)
        assert (by["ecg.exchange"] > 0) == (devices > 1)
    worst = lambda *keys: max(sum(by[k] for k in keys)
                              for by in stages["stage_ns"].values()) * per
    assert m["spmbv_ms_per_iter"] == pytest.approx(worst("ecg.spmbv", "ecg.exchange"))
    assert m["unscoped_ms_per_iter"] == pytest.approx(worst("unscoped"))
    assert all(m[k] > 0 for k in SIX)
    across = {"exchange_ms_per_iter", "exchange_exposed_ms_per_iter"}
    assert (across & set(m)) == (across if devices > 1 else set())
    if devices > 1:
        assert 0 < m["exchange_exposed_ms_per_iter"] <= m["exchange_ms_per_iter"] * (1 + 1e-9)
