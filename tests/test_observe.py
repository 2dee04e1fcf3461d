"""repro.observe: tracer, sinks, rolling metrics, drift, and the no-op gate.

The observability layer's contract is two-sided: with a tracer installed,
spans/counters faithfully describe the build/solve/serve pipeline (span
nesting, exception-closing, schema-valid Chrome export, atomic JSONL
append); with the default null tracer, instrumented code is byte-for-byte
a no-op — same solutions, same iteration counts, same lowered HLO for the
hot loop.
"""

import dataclasses
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.observe import (
    ChromeTraceSink,
    JsonlSink,
    MemorySink,
    NULL_TRACER,
    NullTracer,
    RollingWindow,
    Span,
    Tracer,
    coerce_tracer,
    get_tracer,
    open_sink,
    set_tracer,
    timed_median,
    timed_median_us,
)
from repro.solver import ECGSolver, SolverConfig
from repro.sparse import fd_laplace_2d


@pytest.fixture
def fake_clock():
    """Deterministic injectable clock: every read advances 1.0s."""

    class Clock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            self.t += 1.0
            return self.t

    return Clock()


# ------------------------------------------------------------------ tracer
class TestTracer:
    def test_span_records_name_cat_attrs_duration(self, fake_clock):
        sink = MemorySink()
        tr = Tracer(sinks=[sink], clock=fake_clock)
        with tr.span("build/partition", cat="build", p=8) as sp:
            sp.args["rows"] = 100
        (span,) = sink.spans
        assert span.name == "build/partition" and span.cat == "build"
        assert span.args == dict(p=8, rows=100)
        assert span.t0 == 1.0 and span.dur == 1.0  # two clock reads

    def test_nesting_depth_and_close_order(self):
        sink = MemorySink()
        tr = Tracer(sinks=[sink])
        assert tr.open_spans == 0
        with tr.span("outer"):
            assert tr.open_spans == 1
            with tr.span("inner"):
                assert tr.open_spans == 2
        assert tr.open_spans == 0
        # sinks see spans in close order: child before parent
        assert [s.name for s in sink.spans] == ["inner", "outer"]
        inner, outer = sink.spans
        assert outer.t0 <= inner.t0
        assert inner.t0 + inner.dur <= outer.t0 + outer.dur + 1e-9

    def test_exception_closes_span_and_propagates(self):
        sink = MemorySink()
        tr = Tracer(sinks=[sink])
        with pytest.raises(ValueError, match="boom"):
            with tr.span("build"):
                raise ValueError("boom")
        (span,) = sink.spans
        assert span.dur is not None  # closed despite the raise
        assert span.args["error"] == "ValueError"
        assert tr.open_spans == 0

    def test_begin_end_explicit_pair(self, fake_clock):
        sink = MemorySink()
        tr = Tracer(sinks=[sink], clock=fake_clock)
        sp = tr.begin("solve/dispatch", cat="solve")
        assert tr.open_spans == 1 and sp.dur is None
        tr.end(sp, iters=42)
        assert tr.open_spans == 0
        assert sink.spans[0].dur == 1.0 and sink.spans[0].args["iters"] == 42

    def test_emit_explicit_timestamps(self):
        sink = MemorySink()
        tr = Tracer(sinks=[sink])
        tr.emit("serve/queue_wait", 10.0, 2.5, cat="serve", request_id=3)
        (span,) = sink.spans
        assert span.t0 == 10.0 and span.dur == 2.5
        assert span.args == dict(request_id=3)

    def test_metrics_fan_to_sinks(self, fake_clock):
        sink = MemorySink()
        tr = Tracer(sinks=[sink], clock=fake_clock)
        tr.counter("solver.solves", 3)
        tr.gauge("model_drift", 1.2, strategy="3step")
        tr.instant("solve/reseed", k=7)
        kinds = [m["kind"] for m in sink.metrics]
        assert kinds == ["counter", "gauge", "instant"]
        assert sink.counter_value("solver.solves") == 3
        assert sink.metrics[1]["attrs"] == dict(strategy="3step")

    def test_multiple_sinks_all_receive(self):
        s1, s2 = MemorySink(), MemorySink()
        tr = Tracer(sinks=[s1, s2])
        with tr.span("x"):
            pass
        tr.counter("c", 1)
        assert len(s1.spans) == len(s2.spans) == 1
        assert len(s1.metrics) == len(s2.metrics) == 1


class TestNullTracer:
    def test_everything_is_a_noop(self):
        tr = NullTracer()
        assert not tr.enabled
        with tr.span("anything", cat="x", big=1) as sp:
            sp.args["dropped"] = True  # silently discarded
            sp.args.update(also="dropped")
            assert sp.args.setdefault("k", "default") == "default"
        assert dict(sp.args) == {}
        tr.counter("c", 1)
        tr.gauge("g", 2.0)
        tr.instant("i")
        tr.emit("e", 0.0, 1.0)
        tr.close()

    def test_shared_context_no_allocation(self):
        tr = NullTracer()
        assert tr.span("a") is tr.span("b")  # one shared ctx object
        assert tr.begin("a") is tr.begin("b")

    def test_ambient_tracer_install_restore(self):
        assert get_tracer() is NULL_TRACER
        mine = Tracer(sinks=[MemorySink()])
        prev = set_tracer(mine)
        try:
            assert prev is NULL_TRACER
            assert get_tracer() is mine
            assert coerce_tracer(None) is mine
            other = Tracer()
            assert coerce_tracer(other) is other
        finally:
            set_tracer(prev)
        assert get_tracer() is NULL_TRACER


# ------------------------------------------------------------------- sinks
class TestChromeTraceSink:
    def _trace(self, tmp_path, fake_clock):
        path = tmp_path / "trace.json"
        sink = ChromeTraceSink(str(path))
        tr = Tracer(sinks=[sink], clock=fake_clock)
        with tr.span("build", cat="build", n=100):
            with tr.span("build/tune", cat="build"):
                pass
            tr.counter("solver.builds", 1)
        tr.gauge("model_drift", 1.1, strategy="3step")
        tr.close()
        with open(path) as fh:
            return json.load(fh)

    def test_schema_valid_and_monotonic(self, tmp_path, fake_clock):
        doc = self._trace(tmp_path, fake_clock)
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        events = doc["traceEvents"]
        assert len(events) == 4
        ts = [e["ts"] for e in events]
        assert ts == sorted(ts)  # sorted at export time
        assert ts[0] == 0.0  # relative to the first event, not perf_counter
        for e in events:
            assert {"name", "ph", "ts", "pid", "tid", "args"} <= set(e)
            assert e["ph"] in ("X", "C", "i")
            if e["ph"] == "X":
                assert e["dur"] >= 0
            if e["ph"] == "i":
                assert e["s"] == "p"

    def test_event_kinds(self, tmp_path, fake_clock):
        events = self._trace(tmp_path, fake_clock)["traceEvents"]
        by_name = {e["name"]: e for e in events}
        # spans -> complete events with microsecond durations
        assert by_name["build"]["ph"] == "X"
        assert by_name["build/tune"]["dur"] == pytest.approx(1e6)  # 1 clock s
        # counter -> ph C keyed by the counter name
        assert by_name["solver.builds"]["ph"] == "C"
        assert by_name["solver.builds"]["args"] == {"solver.builds": 1}
        # gauge -> instant event carrying value + attrs
        assert by_name["model_drift"]["ph"] == "i"
        assert by_name["model_drift"]["args"] == dict(value=1.1,
                                                      strategy="3step")

    def test_out_of_order_emit_still_sorted(self, tmp_path):
        path = tmp_path / "t.json"
        sink = ChromeTraceSink(str(path))
        tr = Tracer(sinks=[sink])
        with tr.span("drain"):
            pass
        tr.emit("queue_wait", tr.clock() - 5.0, 5.0)  # began before drain
        tr.close()
        with open(path) as fh:
            ts = [e["ts"] for e in json.load(fh)["traceEvents"]]
        assert ts == sorted(ts)


class TestJsonlSink:
    def test_append_one_record_per_line(self, tmp_path):
        path = tmp_path / "log.jsonl"
        tr = Tracer(sinks=[JsonlSink(str(path))])
        with tr.span("build", cat="build", n=9):
            pass
        tr.counter("c", 2, warm=True)
        tr.close()
        lines = path.read_text().splitlines()
        assert len(lines) == 2
        span, counter = (json.loads(ln) for ln in lines)
        assert span["type"] == "span" and span["name"] == "build"
        assert span["args"] == dict(n=9)
        assert counter == dict(type="counter", name="c", value=2,
                               ts=counter["ts"], args=dict(warm=True))

    def test_append_is_atomic_across_writers(self, tmp_path):
        """Two sinks on one file (the forked-benchmark case): interleaved
        closes must still yield whole records, never partial lines."""
        path = tmp_path / "shared.jsonl"
        a, b = JsonlSink(str(path)), JsonlSink(str(path))
        tra, trb = Tracer(sinks=[a]), Tracer(sinks=[b])
        for i in range(50):
            tra.counter("from_a", i, pad="x" * 256)
            trb.counter("from_b", i, pad="y" * 256)
        tra.close()
        trb.close()
        lines = path.read_text().splitlines()
        assert len(lines) == 100
        records = [json.loads(ln) for ln in lines]  # every line parses
        assert sum(r["name"] == "from_a" for r in records) == 50
        assert sum(r["name"] == "from_b" for r in records) == 50

    def test_append_preserves_existing_log(self, tmp_path):
        path = tmp_path / "log.jsonl"
        for run in range(2):
            tr = Tracer(sinks=[JsonlSink(str(path))])
            tr.counter("run", run)
            tr.close()
        records = [json.loads(ln) for ln in path.read_text().splitlines()]
        assert [r["value"] for r in records] == [0, 1]

    def test_close_idempotent(self, tmp_path):
        sink = JsonlSink(str(tmp_path / "x.jsonl"))
        sink.close()
        sink.close()  # second close must not raise on the dead fd

    def test_open_sink_dispatch(self, tmp_path):
        assert isinstance(open_sink(tmp_path / "a.jsonl"), JsonlSink)
        assert isinstance(open_sink(tmp_path / "a.json"), ChromeTraceSink)


# ---------------------------------------------------------- rolling window
class TestRollingWindow:
    def test_empty_snapshot(self):
        w = RollingWindow(window_s=10.0)
        snap = w.snapshot(now=100.0)
        assert snap["rate_rps"] == 0.0 and snap["n"] == 0
        assert snap["p50"] is None and snap["mean"] is None

    def test_percentiles_and_rate(self):
        w = RollingWindow(window_s=10.0)
        for i in range(10):
            w.add(ts=float(i), value=float(i))
        snap = w.snapshot(now=9.0)
        assert snap["n"] == 10 and snap["rate_rps"] == 1.0
        assert snap["p50"] == 4.5 and snap["mean"] == 4.5
        assert snap["p50"] <= snap["p95"] <= snap["p99"] <= 9.0

    def test_old_samples_age_out(self):
        w = RollingWindow(window_s=10.0)
        w.add(ts=0.0, value=111.0)
        for i in range(5):
            w.add(ts=50.0 + i, value=1.0)
        snap = w.snapshot(now=55.0)
        assert snap["n"] == 5  # the t=0 sample fell out of the window
        assert snap["p99"] == 1.0


# ------------------------------------------------------------- timed_median
class TestTimedMedian:
    def test_returns_result_and_positive_median(self):
        calls = []
        out, s = timed_median(lambda x: calls.append(x) or 42, 1,
                              repeats=3, warmup=2, sync=False)
        assert out == 42 and s > 0
        assert len(calls) == 5  # warmup + repeats

    def test_spans_on_enabled_tracer(self):
        sink = MemorySink()
        tr = Tracer(sinks=[sink])
        timed_median(lambda: None, repeats=3, warmup=0, label="unit",
                     tracer=tr, sync=False)
        spans = sink.by_name("timed/unit")
        assert len(spans) == 3
        assert [s.args["rep"] for s in spans] == [0, 1, 2]

    def test_disabled_tracer_still_measures(self):
        # a NullTracer caller must not break timing (the original bug:
        # null spans report dur=0.0, not a measurement)
        _, s = timed_median(lambda: sum(range(200)), repeats=2,
                            tracer=NULL_TRACER, sync=False)
        assert s > 0
        assert timed_median_us(lambda: None, repeats=2, sync=False) > 0

    def test_rejects_zero_repeats(self):
        with pytest.raises(ValueError, match="repeats"):
            timed_median(lambda: None, repeats=0)


# ------------------------------------------- solver integration + no-op gate
@pytest.fixture(scope="module")
def seq_problem():
    a = fd_laplace_2d(12)
    rng = np.random.default_rng(7)
    return a, rng.standard_normal(a.shape[0])


class TestSolverTracing:
    def test_build_and_solve_spans(self, seq_problem):
        a, b = seq_problem
        sink = MemorySink()
        solver = ECGSolver.build(a, config=SolverConfig(t=4, tol=1e-8),
                                 tracer=Tracer(sinks=[sink]))
        res = solver.solve(b)
        names = [s.name for s in sink.spans]
        assert "build" in names
        assert "solve/dispatch" in names and "solve/finalize" in names
        (seg,) = [s for s in sink.spans if s.name == "solve/segment"]
        assert seg.args["width"] == 4
        assert seg.args["iters"] == res.n_iters
        assert sink.counter_value("solver.builds") == 1
        assert sink.counter_value("solver.solves") == 1

    def test_tracing_off_is_bit_identical(self, seq_problem):
        a, b = seq_problem
        cfg = SolverConfig(t=4, tol=1e-8)
        plain = ECGSolver.build(a, config=cfg)
        traced = ECGSolver.build(a, config=cfg,
                                 tracer=Tracer(sinks=[MemorySink()]))
        r0, r1 = plain.solve(b), traced.solve(b)
        assert np.array_equal(np.asarray(r0.x), np.asarray(r1.x))
        assert r0.n_iters == r1.n_iters
        assert bool(r0.converged) == bool(r1.converged)

    def test_hot_loop_hlo_unchanged_by_tracing(self, seq_problem):
        """Spans sit at dispatch boundaries: the jitted while-loop lowers
        to the same module with tracing on or off."""
        a, b = seq_problem
        cfg = SolverConfig(t=4, tol=1e-8)
        plain = ECGSolver.build(a, config=cfg)
        traced = ECGSolver.build(a, config=cfg,
                                 tracer=Tracer(sinks=[MemorySink()]))
        b_dev = jnp.asarray(b)
        x0 = jnp.zeros_like(b_dev)
        txt0 = plain._jit(plain.t, "fresh").lower(b_dev, x0).as_text()
        txt1 = traced._jit(traced.t, "fresh").lower(b_dev, x0).as_text()
        assert txt0 == txt1

    def test_spans_mirror_into_the_profiler(self, seq_problem, tmp_path):
        """An enabled tracer's spans are profiler annotations on the device
        trace's clock, where a ``bench/clock`` anchor would have moved them;
        the null tracer opens none."""
        from chipbench import trace as tr

        a, b = seq_problem
        cfg = SolverConfig(t=4, tol=1e-8)
        sink = MemorySink()
        traced = ECGSolver.build(a, config=cfg, tracer=Tracer(sinks=[sink]))
        plain = ECGSolver.build(a, config=cfg)
        traced.solve(b), plain.solve(b)  # compile before the profile
        n0 = len(sink.spans)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("bench/clock"):
                clock_perf = time.perf_counter()
            traced.solve(b)
            plain.solve(b)
        finally:
            jax.profiler.stop_trace()
        path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
        clock = tr.load(path).annotation("bench/clock")
        got = tr.load(path, annotation_prefix="solve/").annotations
        want = sorted((s for s in sink.spans[n0:]
                       if s.name in ("solve/dispatch", "solve/finalize")),
                      key=lambda s: s.t0)
        assert [g.name for g in got] == [s.name for s in want]
        assert [s.name for s in want] == ["solve/dispatch", "solve/finalize"]
        for g, s in zip(got, want):
            start = clock.start + (s.t0 - clock_perf) * 1e9
            assert abs(g.start - start) < 0.5e6
            assert abs(g.end - (start + s.dur * 1e9)) < 0.5e6

    def test_with_config_clone_shares_tracer(self, seq_problem):
        a, _ = seq_problem
        tr = Tracer(sinks=[MemorySink()])
        solver = ECGSolver.build(a, config=SolverConfig(t=4), tracer=tr)
        clone = solver.with_config(tol=1e-6)
        assert clone._tracer is tr


class TestIterTrace:
    def test_rows_match_history(self, seq_problem):
        a, b = seq_problem
        solver = ECGSolver.build(a, config=SolverConfig(t=4, tol=1e-8))
        res = solver.solve(b)
        rows = res.iter_trace()
        assert len(rows) == res.n_iters + 1
        assert [r["k"] for r in rows] == list(range(res.n_iters + 1))
        hist = np.asarray(res.res_hist)
        for r in rows:
            assert r["resnorm"] == float(hist[r["k"]])
            assert np.isfinite(r["resnorm"])
        # the padded NaN tail past convergence is excluded
        assert rows[-1]["resnorm"] <= 1e-8 * rows[0]["resnorm"] * 10

    def test_padding_and_event_decoding(self, seq_problem):
        from repro.core.cg import EV_RECOVERY, EV_RESEED

        a, b = seq_problem
        solver = ECGSolver.build(a, config=SolverConfig(t=4, tol=1e-8))
        res = solver.solve(b)
        crafted = dataclasses.replace(
            res,
            res_hist=jnp.asarray([4.0, 2.0, 1.0, np.nan, np.nan]),
            active_hist=np.asarray([4, 4, 2, -1, -1]),
            event_hist=np.asarray(
                [0, EV_RECOVERY, EV_RECOVERY | EV_RESEED, -1, -1]
            ),
        )
        rows = crafted.iter_trace()
        assert len(rows) == 3  # NaN padding cuts the trace
        assert rows[0]["events"] == ()
        assert rows[1]["events"] == ("recovery",)
        assert rows[2]["events"] == ("recovery", "reseed")
        assert rows[2]["active"] == 2

    def test_all_finite_history(self, seq_problem):
        """A history with no padding (max_iters hit) keeps every row."""
        a, b = seq_problem
        solver = ECGSolver.build(
            a, config=SolverConfig(t=4, tol=1e-30, max_iters=5)
        )
        res = solver.solve(b)
        rows = res.iter_trace()
        assert len(rows) == np.asarray(res.res_hist).size


# ------------------------------------------------------------------- drift
class TestDriftHelpers:
    def test_hlo_collective_bytes_parses_both_forms(self):
        from repro.observe.drift import hlo_collective_bytes

        txt = "\n".join([
            "  %x = f64[3,4]{1,0} collective-permute(%a), channel_id=1",
            "  %y = (f32[8]{0}, f32[8]{0}) collective-permute-start(%b)",
            "  %z = f32[8]{0} collective-permute-done(%y)",
            "  %w = f64[2,2]{1,0} add(%c, %d)",
        ])
        # f64[3,4] = 96B and f32[8] = 32B, each x p=4; -done not counted
        assert hlo_collective_bytes(txt, p=4) == (96 + 32) * 4
        assert hlo_collective_bytes("", p=4) == 0

    def test_calibrated_drift_normalizes_by_median(self):
        from repro.observe.drift import calibrated_drift

        rows = [dict(time_drift=2.0), dict(time_drift=4.0),
                dict(time_drift=8.0)]
        out = calibrated_drift(rows)
        assert [r["calibrated_time_drift"] for r in out] == [0.5, 1.0, 2.0]
        assert "calibrated_time_drift" not in rows[0]  # copies, not mutation
        assert calibrated_drift([dict(time_drift=None)])[0][
            "calibrated_time_drift"] is None

    def test_predicted_iteration_seconds_needs_mesh(self, seq_problem):
        from repro.observe.drift import bytes_drift, predicted_iteration_seconds

        a, _ = seq_problem
        solver = ECGSolver.build(a, config=SolverConfig(t=4))
        with pytest.raises(ValueError, match="distributed"):
            predicted_iteration_seconds(solver)
        with pytest.raises(ValueError, match="distributed"):
            bytes_drift(solver)


# ------------------------------------------------------------ stage scopes
#: lowers the solve program of each iteration scheme on ``argv[1]`` CPU
#: devices, with the Pallas kernels' bodies interpreted in place of the
#: jnp oracles, and prints each compiled HLO text
_LOWER_SCHEMES = r"""
import importlib, json, sys
import jax.numpy as jnp
for name in ("bsr_spmbv", "fused_gram", "block_update", "halo_pack"):
    importlib.import_module(f"repro.kernels.{name}.ops").resolve_dispatch = (
        lambda op, use: (True, True))
from repro.launch.mesh import make_solver_mesh
from repro.solver import ECGSolver, KernelConfig, SolverConfig
from repro.solver.config import MethodConfig
from repro.sparse.matrices import dg_laplace_2d

devices = int(sys.argv[1])
a = dg_laplace_2d((4, 4), block=16, dtype=jnp.float32)
mesh = make_solver_mesh(devices) if devices > 1 else None
out = {}
for method in ("classic", "pipelined", "sstep"):
    cfg = SolverConfig(t=4, tol=1e-6, max_iters=50, tune="off",
                       kernel=KernelConfig(backend="pallas", ell_block=(16, 16)),
                       method=MethodConfig(name=method, s=2 if method == "sstep" else 1))
    out[method] = ECGSolver.build(a, mesh, cfg).lowered_text()
print(json.dumps(out))
"""

#: the stage scope each kernel (by its ``pallas_call`` name) runs under
KERNEL_STAGE = {"bsr_spmbv": "ecg.spmbv", "fused_gram": "ecg.gram",
                "ecg_tail": "ecg.update", "halo_pack": "ecg.exchange",
                "halo_unpack": "ecg.exchange"}


@pytest.fixture(scope="module")
def lowered_schemes():
    """devices -> {method: compiled HLO text}, each device count lowered
    once in a child process (which fixes its own CPU device count)."""
    root = Path(__file__).resolve().parents[1]
    cache = {}

    def get(devices):
        if devices not in cache:
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       PYTHONPATH=str(root / "src"),
                       XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
            out = subprocess.run([sys.executable, "-c", _LOWER_SCHEMES, str(devices)],
                                 env=env, capture_output=True, text=True, timeout=600)
            assert out.returncode == 0, out.stderr[-4000:]
            cache[devices] = json.loads(out.stdout.strip().splitlines()[-1])
        return cache[devices]

    return get


def _stages(op_name: str) -> list[str]:
    return [c for c in op_name.split("/") if c.startswith("ecg.")]


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("method", ["classic", "pipelined", "sstep"])
def test_loop_body_carries_stage_scopes(lowered_schemes, method, devices):
    """Every kernel, collective, Cholesky and triangular solve of the
    compiled loop body names its stage; the stages present are the
    iteration's, the exchange only across devices, and the exchange and the
    gather of V inside the SpMBV."""
    from chipbench import trace as tr

    hlo = tr.HloIndex(lowered_schemes(devices)[method])
    body = [i for m in hlo.by_module.values() for i in m.values()
            if re.match(r"jit\(\w+\)/while/body/", i.op_name)]
    kernels, collectives = set(), 0
    for i in body:
        stages = _stages(i.op_name)
        comps = i.op_name.split("/")
        for kernel, stage in KERNEL_STAGE.items():
            if kernel in comps:
                kernels.add(kernel)
                assert stages[-1:] == [stage], i
        if i.opcode.startswith("all-reduce"):
            collectives += 1
            assert stages[-1:] in (["ecg.gram"], ["ecg.check"]), i
        if i.opcode.startswith("collective-permute"):
            collectives += 1
            assert stages[-1:] == ["ecg.exchange"], i
        if "potrf" in i.target or "trsm" in i.target or i.opcode in (
                "cholesky", "triangular-solve"):
            assert stages[-1:] == ["ecg.factor"], i
        for inner in ("ecg.exchange", "ecg.gather"):
            if inner in stages:
                assert "ecg.spmbv" in stages[:stages.index(inner)], i
    present = {s for i in body for s in _stages(i.op_name)}
    expected = {"ecg.spmbv", "ecg.gather", "ecg.gram", "ecg.factor", "ecg.update",
                "ecg.check"}
    assert present == expected | ({"ecg.exchange"} if devices > 1 else set())
    want_kernels = {"bsr_spmbv"}
    if method != "sstep":  # s-step reduces mixed widths on the jnp path
        want_kernels |= {"fused_gram", "ecg_tail"}
    if devices > 1:
        want_kernels |= {"halo_pack", "halo_unpack"}
    assert kernels == want_kernels
    assert (collectives > 0) == (devices > 1)
