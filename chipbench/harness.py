"""One run of one benchmark cell: set-up, the measured window, the check.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file that :class:`Bench` finds by the name
``BENCHMARK.json`` gives it:

* ``configs/<config>.json`` (the path is the configuration's ``file``):
  the operator's generator and sizes, precision, layout and solver
  settings;
* ``traffic/<traffic>.json``: how right-hand sides are drawn from the seed,
  the tolerance and the loop, read by :func:`rhs_for`;
* ``limits/<cell>.json``: the limit of each number the check compares;
* ``metrics/<metric>.py``: a reader, ``read(r: Readings) -> float | None``,
  for every metric, end-to-end or per-layer;
* ``operators/<generator>.py``: the operator's generator.

A run (:func:`run_cell`): generate the operator, ``ECGSolver.build``,
compile the solve program (from the persistent cache after the first run
in a checkout), warm up with a solve of ``b = 0`` (which runs the same
program and exits the loop at once), draw the traffic's pool of
right-hand sides.  That is the set-up.  Then the window: closed-loop
solves over the pool in the order the seed gives, each from dispatch to
its host copy, in whole passes over the pool until a pass ends ``seconds``
after the window opened.  A solve is never cut, and every seed does the
same work.  With ``trace`` the profiler records the window's first solve
whole.  After the window: the peak device memory, then every answer's
float64 true residual against the limit.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from chipbench import reference
from chipbench.operators import HostCSR

BENCH_DIR = Path(__file__).resolve().parent

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(f"chipbench_{path.parent.name}_{path.stem}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` and the files it names, looked up by name."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.dir = self.root / BENCH_DIR.name
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def limits(self, cell: str) -> dict:
        return json.loads((self.dir / "limits" / f"{cell}.json").read_text())

    def metrics(self, cell: str, kind: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.spec[kind]
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        """``metrics/<metric>.py``'s ``read`` function."""
        return _module(self.dir / "metrics" / f"{metric}.py").read

    def operator(self, config: dict) -> HostCSR:
        op = dict(config["operator"])
        mod = _module(self.dir / "operators" / f"{op.pop('generator')}.py")
        return mod.generate(dtype=np.dtype(config["dtype"]), **op)


def rhs_for(traffic: dict, n: int, i: int, dtype) -> np.ndarray:
    """Right-hand side ``i`` of the traffic's pool: a unit-norm Gaussian
    (``"rhs": "unit_gaussian"``) drawn from the pool's own seed."""
    if traffic["rhs"] != "unit_gaussian" or traffic["x0"] != "zero":
        raise ValueError(f"unknown traffic {traffic['rhs']!r} from {traffic['x0']!r}")
    b = np.random.default_rng([int(traffic["pool_seed"]), int(i)]).standard_normal(n)
    return (b / np.linalg.norm(b)).astype(dtype)


def pool_order(traffic: dict, seed: int) -> list[int]:
    """The order in which a run with ``seed`` solves the pool: every seed
    solves the same right-hand sides, so every run does the same work."""
    return [int(i) for i in np.random.default_rng(int(seed)).permutation(traffic["pool"])]


@dataclasses.dataclass
class Solve:
    b: np.ndarray
    x: np.ndarray
    iters: int
    converged: bool
    seconds: float
    relres: float | None = None


@dataclasses.dataclass
class Readings:
    """What a per-layer reader may read."""

    cell: dict
    config: dict
    traffic: dict
    solves: list            # [Solve] of the window; the first is the traced one
    window_s: float
    timings: dict           # set-up phases on the host clock, seconds
    spans: list             # repro.observe spans (trace runs)
    trace: object = None    # trace.Trace of the window's solve
    window_ns: tuple = ()   # (t0, t1) of the traced window on the trace clock
    hlo: object = None      # trace.HloIndex of the solve program
    peaks: dict = dataclasses.field(default_factory=dict)
    busy_s: float = 0.0     # device busy time in the traced window, mean over chips
    traced_s: float = 0.0   # length of the traced window

    @property
    def iters(self) -> int:
        return sum(s.iters for s in self.solves)

    @property
    def traced_iters(self) -> int:
        return self.solves[0].iters if self.solves else 0


def solver_config(config: dict, traffic: dict):
    from repro.solver import CommConfig, KernelConfig, SolverConfig
    from repro.solver.config import MethodConfig, TuneConfig

    s = config["solver"]
    if s["precondition"] != "none":
        raise ValueError("only unpreconditioned cells are defined")
    return SolverConfig(
        t=s["t"], tol=float(traffic["tol"]), max_iters=s["max_iters"],
        comm=CommConfig(strategy=s["strategy"]),
        kernel=KernelConfig(backend=s["backend"], ell_block=tuple(s["ell_block"])),
        tune=TuneConfig(mode=s["tune"]),
        method=MethodConfig(name=s["method"]),
    )


def devices_for(cell: dict, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devs[0].platform!r}; the "
                     "benchmark measures the chip and has no CPU fallback")
    if len(devs) < cell["chips"]:
        raise NoChip(f"cell {cell['name']} asks for {cell['chips']} chips, "
                     f"JAX found {len(devs)}")
    return devs


class _CompileCount:
    """XLA compilations (cache hits included) while installed."""

    def __init__(self):
        self.n = 0

    def __call__(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.n += 1


def _annotate(on: bool, name: str):
    import jax

    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


def run_cell(bench: Bench, cell_name: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, out_dir: Path,
             require_chip: bool = True) -> dict:
    """One run of a cell; returns the result object (see ``run.py``)."""
    cell = bench.cell(cell_name)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    limits = bench.limits(cell_name)
    devs = devices_for(cell, require_chip)

    import jax
    import jax.numpy as jnp

    from repro.observe import MemorySink, Tracer
    from repro.observe.tracer import NULL_TRACER
    from repro.solver import ECGSolver
    from repro.sparse.csr import CSRMatrix

    compiles = _CompileCount()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        timings: dict = {}
        sink = MemorySink() if trace else None
        tracer = Tracer(sinks=[sink]) if trace else NULL_TRACER
        dtype = np.dtype(config["dtype"])

        t0 = time.perf_counter()
        host = bench.operator(config)
        a = CSRMatrix(jnp.asarray(host.indptr, jnp.int32), jnp.asarray(host.indices),
                      jnp.asarray(host.data), (host.n, host.n))
        timings["generate_s"] = time.perf_counter() - t0
        if host.n != config["rows"] or host.nnz != config["nnz"]:
            raise ValueError(f"operator has {host.n} rows, {host.nnz} nonzeros; "
                             f"the configuration states {config['rows']}, {config['nnz']}")

        mesh = None
        layout = config["layout"]
        used = devs[: layout["devices"]]
        if layout["devices"] > 1:
            from repro.launch.mesh import make_solver_mesh

            mesh = make_solver_mesh(layout["devices"], ppn=layout["ppn"])

        t0 = time.perf_counter()
        solver = ECGSolver.build(a, mesh, solver_config(config, traffic), tracer=tracer)
        timings["build_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        hlo_text = solver.lowered_text()
        timings["compile_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        warm = solver.solve(np.zeros(host.n, dtype))
        solver.unshard(warm.x)
        timings["warmup_s"] = time.perf_counter() - t0
        compiles_setup = compiles.n
        traces_setup = solver.stats.traces

        order = pool_order(traffic, seed)
        pool = [rhs_for(traffic, host.n, i, dtype) for i in range(traffic["pool"])]
        if trace:
            prof_dir = out_dir / "profile"
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(prof_dir), profiler_options=opts)
            with _annotate(True, "bench/clock"):
                clock_perf = time.perf_counter()

        # closed loop over the pool in the seed's order, whole passes only,
        # until a pass ends `seconds` after the window opened
        solves: list[Solve] = []
        t_window = time.perf_counter()
        timings["setup_s"] = t_window - t_start
        while True:
            for i in order:
                tracing = trace and not solves
                s0 = time.perf_counter()
                with _annotate(tracing, "bench/solve"):
                    with _annotate(tracing, "bench/solve/dispatch"):
                        res = solver.solve(pool[i])
                    with _annotate(tracing, "bench/solve/unshard"):
                        x = solver.unshard(res.x)
                s1 = time.perf_counter()
                solves.append(Solve(pool[i], x, int(res.n_iters), bool(res.converged),
                                    s1 - s0))
                if tracing:
                    jax.profiler.stop_trace()
            if s1 - t_window >= seconds:
                break
        window_s = s1 - t_window
        compiles_window = compiles.n - compiles_setup
        retraces = solver.stats.traces - traces_setup

        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
        del solver, res, warm, a
        gc.collect()

        readings = Readings(cell=cell, config=config, traffic=traffic, solves=solves,
                            window_s=window_s, timings=timings,
                            spans=list(sink.spans) if sink else [])
        if trace:
            _attach_trace(readings, prof_dir, hlo_text, clock_perf, devs[0].device_kind,
                          require_chip)
            shutil.rmtree(prof_dir, ignore_errors=True)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)

    for s in solves:
        s.relres = reference.true_relres(host, s.x, s.b)
    failed = sum(1 for s in solves
                 if not s.converged or s.relres > limits["true_relres_max"])
    checks = {
        "true_relres_max": {"value": max(s.relres for s in solves),
                            "limit": limits["true_relres_max"]},
        "solves_not_converged": {"value": sum(not s.converged for s in solves),
                                 "limit": 0},
        "compiles_in_window": {"value": compiles_window + retraces, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    detail = {
        "cell": cell_name, "seed": seed,
        "cache_dir": jax.config.jax_compilation_cache_dir,
        "timings": timings, "window_s": window_s,
        "compiles_setup": compiles_setup, "compiles_window": compiles_window,
        "retraces_window": retraces,
        "solves": [{"iters": s.iters, "seconds": s.seconds, "converged": s.converged,
                    "true_relres": s.relres} for s in solves],
        "memory_peak_bytes": peak,
    }
    print(json.dumps({"detail": detail}), flush=True)

    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs),
              "memory_peak_bytes": int(peak)}
    metrics = {}
    for m in bench.metrics(cell_name, "per_layer" if trace else "end_to_end"):
        v = bench.reader(m["name"])(readings)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": correct, "attempted": len(solves), "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=readings.busy_s, window_s=readings.traced_s)
        result["breakdown"] = _breakdown(readings)
    result["checks"] = checks
    return result


def _attach_trace(r: Readings, prof_dir: Path, hlo_text: str, clock_perf: float,
                  device_kind: str, require_chip: bool) -> None:
    """Read the profile of the window's solve into ``r``."""
    from chipbench import peaks, trace as tr

    path = next(prof_dir.glob("plugins/profile/*/*.xplane.pb"))
    t = tr.load(path)
    sol = t.annotation("bench/solve")
    clock = t.annotation("bench/clock")
    # repro.observe spans (perf_counter clock) onto the trace's clock
    for s in r.spans:
        if s.dur is not None:
            t.annotations.append(tr.Op(s.name, clock.start + (s.t0 - clock_perf) * 1e9,
                                       s.dur * 1e9))
    # the chips the cell ran on: those with operations in the traced solve
    t.devices = {k: ops for k, ops in t.devices.items() if tr.clip(ops, sol.start, sol.end)}
    r.trace = t
    r.window_ns = (sol.start, sol.end)
    r.hlo = tr.HloIndex(hlo_text)
    r.peaks = peaks.peaks(device_kind) if require_chip else {}
    busy = [tr.busy_ns(ops, *r.window_ns) for ops in t.devices.values()]
    r.busy_s = float(np.mean(busy)) / 1e9 if busy else 0.0
    r.traced_s = (sol.end - sol.start) / 1e9


def _breakdown(r: Readings) -> dict:
    """The traced solve's ten costliest device operations (seconds, mean
    over chips) and its ten longest idle gaps on the most idle chip, each
    named by the host span open in it."""
    from chipbench import trace as tr

    t0, t1 = r.window_ns
    ops: dict = {}
    ndev = max(1, len(r.trace.devices))
    for dev_ops in r.trace.devices.values():
        for o in tr.clip(dev_ops, t0, t1):
            ins = r.hlo.find(o)
            key = f"{o.name} {ins.op_name.split('/')[-1]}" if ins and ins.op_name else o.name
            ops[key] = ops.get(key, 0.0) + o.dur / 1e9 / ndev
    worst = max(r.trace.devices.values(), key=lambda o: tr.idle_share(o, t0, t1))
    gaps = [[tr.label_at(r.trace.annotations, (s + e) / 2) or "(no host span)",
             (e - s) / 1e9] for s, e in tr.idle_gaps(worst, t0, t1)[:10]]
    return {
        "device_ops": sorted(([k, v] for k, v in ops.items()), key=lambda kv: -kv[1])[:10],
        "idle_gaps": gaps,
    }


def main(args, *, t_start: float, root: Path) -> int:
    import jax

    # the persistent compilation cache lives at one fixed path inside the
    # checkout (the path is part of the cache key), whatever the environment
    # names, so only a checkout's first run of a cell compiles
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out_dir = root / ".bench_out" / f"{args.workload}.{args.seed}"
    try:
        result = run_cell(Bench(root), args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start, out_dir=out_dir)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name}: {c['value']!r} <= {c['limit']!r} {ok}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
