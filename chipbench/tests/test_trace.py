"""The reduction from a profiler trace and compiled HLO to the per-layer
metrics' numbers (``chipbench/trace.py``), and the SpMBV's byte count."""

from pathlib import Path

import pytest

from chipbench import spmbv_bytes, trace as tr

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded():
    return tr.load(DATA / "cpu_trace.xplane.pb")


@pytest.fixture(scope="module")
def hlo():
    return tr.HloIndex((DATA / "cpu_trace_hlo.txt").read_text())


def op(name, start, dur, module=""):
    return tr.Op(name, float(start), float(dur), module)


def test_recorded_trace_has_ops_and_annotation(recorded):
    (dev,) = recorded.devices.values()
    # three calls of one program: a matmul, a tanh, a sum (two fusions)
    assert [o.name for o in dev].count("dot_general.1") == 3
    assert len(dev) == 12
    sol = recorded.annotation("bench/solve")
    assert sol is not None
    assert all(sol.start <= o.start and o.end <= sol.end for o in dev)


def test_busy_union_and_idle_share_by_hand():
    ops = [op("a", 0, 10), op("b", 5, 10), op("c", 30, 10), op("d", 45, 100)]
    # [0, 15] ∪ [30, 40] ∪ [45, 60] inside the window [0, 60]
    assert tr.busy_intervals(ops, 0, 60) == [(0, 15), (30, 40), (45, 60)]
    assert tr.busy_ns(ops, 0, 60) == 40
    assert tr.idle_share(ops, 0, 60) == pytest.approx(20 / 60)
    assert tr.idle_gaps(ops, 0, 60) == [(15, 30), (40, 45)]
    assert (-5, 0) in tr.idle_gaps(ops, -5, 60)


def test_leaves_drop_enclosing_events():
    loop = op("while.1", 0, 100)
    body = [op("fusion.1", 10, 20), op("fusion.2", 40, 20)]
    assert tr.leaves([loop] + body) == body
    assert tr.busy_ns(tr.leaves([loop] + body), 0, 100) == 40


def test_recorded_idle_share_matches_sum_of_gaps(recorded):
    (dev,) = recorded.devices.values()
    sol = recorded.annotation("bench/solve")
    share = tr.idle_share(dev, sol.start, sol.end)
    gaps = sum(e - s for s, e in tr.idle_gaps(dev, sol.start, sol.end))
    assert 0 < share < 1
    assert share == pytest.approx(gaps / (sol.end - sol.start))
    # the program's ops do not overlap on one device: busy = sum of durations
    assert tr.busy_ns(dev, sol.start, sol.end) == pytest.approx(sum(o.dur for o in dev))


def test_match_events_by_name_through_hlo(recorded, hlo):
    (dev,) = recorded.devices.values()
    found = {o.name: hlo.find(o) for o in dev}
    assert all(found.values())
    assert found["dot_general.1"].opcode == "dot"
    assert found["wrapped_tanh"].op_name.endswith("/tanh")
    assert hlo.find(op("dot_general.1", 0, 1, "jit_other")) is not None  # name only
    assert hlo.find(op("no-such-op", 0, 1)) is None


def test_per_call_kernel_time(recorded, hlo):
    (dev,) = recorded.devices.values()
    dots = [o.dur for o in dev if o.name == "dot_general.1"]
    pick = lambda o: hlo.find(o).opcode == "dot"
    total, calls = tr.time_by(dev, pick)
    assert calls == 3 and total / calls == pytest.approx(sum(dots) / 3)
    assert tr.time_by(dev, lambda o: False) == (0.0, 0)


def test_pallas_kernel_and_shapes_from_v5e_hlo():
    idx = tr.HloIndex((DATA / "v5e_spmbv_hlo.txt").read_text())
    instrs = [i for mod in idx.by_module.values() for i in mod.values()]
    kernels = [i for i in instrs if tr.is_pallas(i, "bsr_spmbv")]
    assert len(kernels) == 1
    (k,) = kernels
    assert k.shapes() == [(4, 80, 16, 128), (4, 80, 8, 128)]
    assert k.shape.startswith("f32[4,16,8,128]")
    assert not tr.is_pallas(k, "fused_gram")
    assert not any(tr.is_collective(i, "all-reduce") for i in instrs)


def test_collective_opcodes():
    mk = lambda opcode: tr.Instr("x", opcode, "", "", "", "")
    assert tr.is_collective(mk("all-reduce-start"), "all-reduce")
    assert tr.is_collective(mk("collective-permute-done"), "collective-permute")
    assert not tr.is_collective(mk("all-gather"), "all-reduce")
    assert not tr.is_collective(None, "all-reduce")


def test_spmbv_bytes_by_hand():
    # 2 block rows of 3 tile slots, 2x2 tiles, t = 4, float32:
    # tiles 2*3*2*2*4 = 96 B, ids 2*3*4 = 24 B, V and W 4 rows * 4 cols * 4 B
    assert spmbv_bytes.spmbv_bytes(2, 3, 2, 2, 4) == 96 + 24 + 64 + 64
    assert spmbv_bytes.spmbv_flops(2, 3, 2, 2, 4) == 2 * 2 * 3 * 2 * 2 * 4
    # the lane-major kernel's operands: (nbg, kmax*bc, br, lanes) tiles and
    # (nbg, kmax*bc, t, lanes) gathered V
    flops, nbytes = spmbv_bytes.from_kernel_shapes((1, 6, 2, 2), (1, 6, 4, 2), bc=2)
    assert (flops, nbytes) == (192, 248)
    # the paper's DG operator on one chip: 640 lane groups of 128 block rows
    _, full = spmbv_bytes.from_kernel_shapes((640, 80, 16, 128), (640, 80, 8, 128), 16)
    assert full == 81920 * 5 * 256 * 4 + 81920 * 5 * 4 + 2 * 1310720 * 8 * 4
