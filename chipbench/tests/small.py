"""A copy of the benchmark at a size the CPU runs in seconds, for the tests:
the same harness, traffic and readers, with a DG operator of (8, 8)
elements and a cell per layout."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def small_bench(tmp: Path, devices: int = 1) -> tuple[Path, str]:
    """Write a benchmark root under ``tmp`` whose one cell runs the DG
    operator at (8, 8) elements on ``devices`` devices; returns (root,
    cell name)."""
    root = Path(tmp)
    shutil.copytree(BENCH, root / BENCH.name,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    real = json.loads((BENCH / "configs" / "dg_ex21_1chip.json").read_text())
    config = dict(real, name="dg_small", operator=dict(real["operator"], elements=[8, 8]),
                  rows=1024, nnz=73728)
    if devices > 1:
        config["layout"] = {"devices": devices, "ppn": devices // 2}
    (root / BENCH.name / "configs" / "dg_small.json").write_text(json.dumps(config))
    cell = "dg_small.rhs_stream"
    shutil.copy(BENCH / "limits" / "dg_ex21_1chip.rhs_stream.json",
                root / BENCH.name / "limits" / f"{cell}.json")
    spec["configs"] = [{"name": "dg_small", "source": "test", "reduced": ["elements"],
                        "file": f"{BENCH.name}/configs/dg_small.json", "why": "test"}]
    spec["workloads"] = [{"name": cell, "config": "dg_small", "traffic": "rhs_stream",
                          "chips": devices, "why": "test"}]
    if devices > 1:
        # the readers of the exchange's collectives, which only a cell across
        # devices reads
        known = {m["name"] for m in spec["per_layer"]}
        spec["per_layer"] += [
            {"name": name, "unit": "ms", "better": "lower", "source": "device_trace",
             "layer": "distributed executor", "moves": "solve_s"}
            for name in ("halo_ms_per_iter", "allreduce_ms_per_iter") if name not in known]
    for m in spec["per_layer"] + spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [cell]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root, cell
