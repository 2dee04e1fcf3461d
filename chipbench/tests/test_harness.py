"""The harness: lookup by name from files alone, the refusal without a
chip, and whole runs on the CPU at a small size (in child processes, which
keep JAX's device count and compilation cache out of the test process)."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import harness
from chipbench.tests.small import BENCH, ROOT, small_bench


def child_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), str(ROOT / "src")])
    env.pop("XLA_FLAGS", None)
    return env


def drive(*args, timeout=600):
    out = subprocess.run([sys.executable, str(BENCH / "tests" / "drive.py"), *args],
                         env=child_env(), capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def digest(d: Path) -> dict:
    return {str(p.relative_to(d)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(d.rglob("*.py"))}


def test_new_entries_found_by_name_from_files_alone(tmp_path):
    root, cell = small_bench(tmp_path)
    bench_dir = root / BENCH.name
    code = digest(bench_dir)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    # a later change adds a configuration, a traffic mix, a cell, its
    # limits and a per-layer metric: new files and new entries only
    cfg = json.loads((bench_dir / "configs" / "dg_small.json").read_text())
    (bench_dir / "configs" / "dg_wide.json").write_text(
        json.dumps(dict(cfg, name="dg_wide", solver=dict(cfg["solver"], t=16))))
    (bench_dir / "traffic" / "rhs_tight.json").write_text(json.dumps(
        dict(json.loads((bench_dir / "traffic" / "rhs_stream.json").read_text()), tol=1e-5)))
    (bench_dir / "limits" / "dg_wide.rhs_tight.json").write_text('{"true_relres_max": 1e-4}')
    (bench_dir / "metrics" / "solves_in_window.py").write_text(
        '"""Solves the window held."""\n\n\ndef read(r):\n    return len(r.solves)\n')
    spec["configs"].append({"name": "dg_wide", "source": "test", "reduced": [],
                            "file": f"{BENCH.name}/configs/dg_wide.json", "why": "test"})
    spec["workloads"].append({"name": "dg_wide.rhs_tight", "config": "dg_wide",
                              "traffic": "rhs_tight", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "solves_in_window", "unit": "solves",
                              "better": "higher", "source": "host_clock",
                              "layer": "solve loop", "moves": "solve_s",
                              "workloads": ["dg_wide.rhs_tight"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    b = harness.Bench(root)
    w = b.cell("dg_wide.rhs_tight")
    assert b.config(w["config"])["solver"]["t"] == 16
    assert b.traffic(w["traffic"])["tol"] == 1e-5
    assert b.limits("dg_wide.rhs_tight")["true_relres_max"] == 1e-4
    names = [m["name"] for m in b.metrics("dg_wide.rhs_tight", "per_layer")]
    assert names == ["solves_in_window"]
    assert "solves_in_window" not in [m["name"] for m in b.metrics(cell, "per_layer")]
    r = harness.Readings(cell=w, config={}, traffic={}, solves=[None, None],
                         window_s=1.0, timings={}, spans=[])
    assert b.reader("solves_in_window")(r) == 2
    assert b.operator(b.config(w["config"])).n == 1024
    assert digest(bench_dir) == {**code, "metrics/solves_in_window.py":
                                 digest(bench_dir)["metrics/solves_in_window.py"]}


def test_every_metric_has_a_reader_and_every_cell_its_files():
    b = harness.Bench(ROOT)
    for m in b.spec["end_to_end"] + b.spec["per_layer"]:
        assert callable(b.reader(m["name"])), m["name"]
    for w in b.spec["workloads"]:
        assert b.config(w["config"])["name"] == w["config"]
        assert b.traffic(w["traffic"])["rhs"] == "unit_gaussian"
        assert b.limits(w["name"])["true_relres_max"] > 0


def test_run_refuses_without_a_tpu():
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "dg_ex21_1chip.rhs_stream",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        env=child_env(), capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_run_refuses_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / BENCH.name,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = child_env()
    env["PYTHONPATH"] = ""
    out = subprocess.run(
        [sys.executable, str(tmp_path / BENCH.name / "run.py"), "--workload",
         "dg_ex21_1chip.rhs_stream", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


@pytest.mark.parametrize("traced,devices", [(0, 1), (1, 1), (1, 4)])
def test_whole_run_on_cpu(traced, devices):
    r = drive("--trace", str(traced), "--devices", str(devices))
    assert r["correct"] is True
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert r["checks"]["compiles_in_window"]["value"] == 0
    assert r["device"]["platform"] == "cpu"
    b = harness.Bench(ROOT)
    if traced:
        # the CPU has no peak table and no Pallas kernels: those readers
        # find nothing and their metrics are left out
        assert {"build_s", "compile_s", "iters_per_solve", "ms_per_iter",
                "idle_share"} <= set(r["metrics"])
        assert "spmbv_hbm_roofline" not in r["metrics"]
        # the collectives exist only across devices
        across = {"halo_ms_per_iter", "allreduce_ms_per_iter"}
        assert (across <= set(r["metrics"])) == (devices > 1)
        assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
        assert r["breakdown"]["device_ops"] and r["breakdown"]["idle_gaps"]
    else:
        assert set(r["metrics"]) == {m["name"] for m in b.spec["end_to_end"]}
