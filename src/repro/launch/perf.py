import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimb driver: re-lower chosen cells with optimization levers and
record hypothesis → change → before → after (EXPERIMENTS.md §Perf).

    PYTHONPATH=src python -m repro.launch.perf --out experiments/perf.json

ECG mode — measure the solver hot path instead of the transformer cells
(kernel-vs-oracle + overlap-vs-blocking, on an 8-device (2x4) sub-mesh):

    PYTHONPATH=src python -m repro.launch.perf --ecg --out experiments/ecg_perf.json
"""

import argparse
import json
import time
from pathlib import Path

import jax

from repro.configs import SHAPE_CELLS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.dryrun import _lower_cell, _unit_layers, _n_units
from repro.analysis.roofline import (
    CellCost,
    cost_from_compiled,
    roofline_from_cost,
    model_flops,
)

# (cell, iteration-name, overrides, hypothesis)
ITERATIONS = [
    # ---------------- stablelm train_4k: memory-dominant dense baseline ----
    ("stablelm_1_6b", "train_4k", "baseline", {}, "paper-faithful baseline"),
    (
        "stablelm_1_6b", "train_4k", "attn_chunk",
        dict(attn_chunk=512),
        "memory-dom 4.71s: naive attention makes ~6 HBM passes over the S² "
        "score matrix (bf16 write, mask, fp32 convert, softmax, bf16 cast, PV "
        "read) ≈ 1.0e12 B/dev of 3.9e12 total; online-softmax tiles cut this "
        "to ~2 tile passes → predict memory −25..35%",
    ),
    (
        "stablelm_1_6b", "train_4k", "attn+loss_chunk",
        dict(attn_chunk=512, loss_chunk=512),
        "fp32 (B,S,V/16) logits + lse make ~4 passes ≈ 2e10 B/dev → predict "
        "additional memory −1..3% (small; vocab already TP-sharded)",
    ),
    # ------------- phi3-medium train_4k: worst collective term (25.4s) -----
    ("phi3_medium_14b", "train_4k", "baseline", {}, "paper-faithful baseline"),
    (
        "phi3_medium_14b", "train_4k", "gqa_fix+attn_chunk",
        dict(gqa_shard_fix=True, attn_chunk=512),
        "collective-dom 25.4s: kv=10 repeat under a seq-sharded residual "
        "forces GSPMD involuntary full remats (full-tensor all-gathers) per "
        "layer; pinning K/V to gathered-then-head-TP layout + tiled attention "
        "→ predict collective −25..45%, memory −25%",
    ),
    (
        "phi3_medium_14b", "train_4k", "no_seq_parallel",
        dict(gqa_shard_fix=True, attn_chunk=512, seq_parallel=False),
        "remaining collective: SP all-gathers activations (S/16→S) every layer "
        "fwd+bwd; disabling SP trades +16x layer-boundary activation memory "
        "for −2 all-gathers/layer → predict collective −20%, temp +",
    ),
    # ------------- phi3.5-moe train_4k: collective-bound EP (paper analogue)
    ("phi35_moe_42b", "train_4k", "baseline", {}, "paper-faithful baseline"),
    (
        "phi35_moe_42b", "train_4k", "gqa_fix+attn_chunk",
        dict(gqa_shard_fix=True, attn_chunk=512),
        "collective-dom 13.1s with kv=8: same involuntary-remat pathology as "
        "phi3-medium → predict collective −20..35%",
    ),
    (
        "phi35_moe_42b", "train_4k", "moe_scatter_combine",
        dict(gqa_shard_fix=True, attn_chunk=512, moe_scatter_combine=True),
        "EP combine is a full (B,S,D) all-reduce per layer, but the residual "
        "stream is seq-sharded (SP): reduce-scatter straight into the sharded "
        "layout moves half the bytes (RS=(p-1)/p vs AR=2(p-1)/p) — the "
        "paper's 'shape the collective to the data layout' discipline applied "
        "to MoE → predict collective −10..20%",
    ),
    # --------------------------------- round 2 (from coll_breakdown data) --
    (
        "stablelm_1_6b", "train_4k", "dense_scatter",
        dict(attn_chunk=512, loss_chunk=512, dense_scatter_combine=True),
        "AR is 106 GB/dev — dominated by row-parallel dx/out psums of "
        "(B,S,D) per layer; reduce-scatter into the SP layout halves those "
        "bytes → predict all-reduce −30..45%, collective −20..30%",
    ),
    (
        "phi3_medium_14b", "train_4k", "attn_seq_shard",
        dict(gqa_shard_fix=True, attn_chunk=512, attn_seq_shard=True),
        "AG is 521 GB/dev — the uneven 40/16 head sharding forces padded "
        "full-tensor regathers of q/k/v/o every layer (fwd+bwd+remat). "
        "Sharding attention by QUERY POSITIONS over 'model' removes head "
        "padding entirely and aligns with the seq-sharded residual → predict "
        "all-gather −50%+, collective −35%, useful-flops ratio up",
    ),
    (
        "phi3_medium_14b", "train_4k", "attn_seq+dense_scatter",
        dict(gqa_shard_fix=True, attn_chunk=512, attn_seq_shard=True,
             dense_scatter_combine=True),
        "stack the RS-combine on the MLP down-proj (d_ff=17920 divides 16 "
        "even though heads don't) → predict further all-reduce −20%",
    ),
    (
        "phi35_moe_42b", "train_4k", "moe+dense_scatter",
        dict(gqa_shard_fix=True, attn_chunk=512, moe_scatter_combine=True,
             dense_scatter_combine=True),
        "attention out-proj (32 heads, even) still all-reduces (B,S,D); "
        "RS-combine it like the MoE outputs → predict all-reduce −15%",
    ),
]


def run_iteration(arch, shape, overrides):
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.with_(**overrides)
    kind, seq, batch = SHAPE_CELLS[shape]
    mesh = make_production_mesh(multi_pod=False)
    chips = int(mesh.devices.size)

    compiled, _, t_comp = _lower_cell(cfg, mesh, kind, seq, batch)
    ma = compiled.memory_analysis()
    c1, *_ = _lower_cell(_unit_layers(cfg, 1), mesh, kind, seq, batch)
    c2, *_ = _lower_cell(_unit_layers(cfg, 2), mesh, kind, seq, batch)
    cost = CellCost.extrapolate(cost_from_compiled(c1), cost_from_compiled(c2), _n_units(cfg))
    rl = roofline_from_cost(cost, chips, model_flops(cfg, kind, seq, batch))
    return dict(
        compile_s=round(t_comp, 1),
        temp_gib=round(ma.temp_size_in_bytes / 2**30, 2),
        roofline=rl.as_dict(),
        coll_breakdown={k: round(v / 1e9, 2) for k, v in cost.coll_breakdown.items()},
    )


def run_ecg_sweep(out_path: Path, only: str | None = None):
    """ECG hot-path measurements (uses 8 of the forced host devices)."""
    import numpy as np

    from repro.analysis.ecg_bench import kernel_vs_oracle, overlap_vs_blocking_sweep
    from repro.launch.runtime import solver_dtype
    from repro.sparse import dg_laplace_2d

    dtype = solver_dtype()
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:8]).reshape(2, 4), ("node", "proc")
    )
    a = dg_laplace_2d((16, 12), block=8, dtype=dtype)
    rows = overlap_vs_blocking_sweep(a, mesh, ts=(4, 8)) + kernel_vs_oracle()
    if only:
        rows = [r for r in rows if only in r["name"]]
    for r in rows:
        print(f"ECG {r['name']}: {r['us']:.1f}us  {r['derived']}", flush=True)
    out_path.write_text(json.dumps(rows, indent=1))
    print("ecg perf pass done", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="output JSON (default: experiments/perf.json, or "
                         "experiments/ecg_perf.json with --ecg)")
    ap.add_argument("--only", default=None, help="substring filter on cell/iteration")
    ap.add_argument("--ecg", action="store_true",
                    help="run the ECG kernel/overlap sweep instead of the cells")
    args = ap.parse_args()
    if args.ecg:
        out_path = Path(args.out or "experiments/ecg_perf.json")
        out_path.parent.mkdir(parents=True, exist_ok=True)
        run_ecg_sweep(out_path, args.only)
        return
    args.out = args.out or "experiments/perf.json"
    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results = json.loads(out_path.read_text()) if out_path.exists() else []
    done = {(r["arch"], r["shape"], r["iteration"]) for r in results if "error" not in r}

    for arch, shape, name, overrides, hypothesis in ITERATIONS:
        key = (arch, shape, name)
        if key in done:
            continue
        if args.only and args.only not in f"{arch}/{shape}/{name}":
            continue
        print(f"PERF {arch} x {shape} :: {name}", flush=True)
        t0 = time.time()
        try:
            rec = run_iteration(arch, shape, overrides)
            rl = rec["roofline"]
            print(
                f"  {time.time()-t0:.0f}s  compute={rl['compute_s']:.3g} "
                f"memory={rl['memory_s']:.3g} collective={rl['collective_s']:.3g} "
                f"dominant={rl['dominant']} frac={rl['roofline_fraction']:.3f} "
                f"temp={rec['temp_gib']}GiB",
                flush=True,
            )
        except Exception as e:  # noqa: BLE001
            rec = dict(error=f"{type(e).__name__}: {e}")
            print(f"  FAIL {rec['error'][:200]}", flush=True)
        rec |= dict(arch=arch, shape=shape, iteration=name,
                    overrides={k: str(v) for k, v in overrides.items()},
                    hypothesis=hypothesis)
        results = [r for r in results if (r["arch"], r["shape"], r["iteration"]) != key]
        results.append(rec)
        out_path.write_text(json.dumps(results, indent=1))
    print("perf pass done", flush=True)


if __name__ == "__main__":
    main()
