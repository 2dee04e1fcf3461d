"""Pod-aware hierarchical collectives — the paper's node-aware schemes applied
to multi-pod gradient reduction (DESIGN.md §4, beyond-paper).

The 2-step node-aware exchange (paper Fig 2.6) maps onto an allreduce as:

    step 1 (fast tier):  reduce-scatter over the intra-pod "data" axis
                         — every chip now owns a 1/|data| shard of the sum
    step 2 (slow tier):  all-reduce over the "pod" axis on shards only
                         — slow-tier bytes drop by |data|× vs a flat ring
    step 3 (fast tier):  all-gather over "data" to restore the full tensor

Total fast-tier bytes are unchanged vs a flat all-reduce; slow-tier (DCI)
bytes per chip drop from 2·(P-1)/P·n to 2·(pods-1)/pods·n/|data| — exactly
the deduplication the paper's 2-step scheme buys on MPI clusters.

``tiered_collective_bytes`` classifies the collectives of a compiled HLO by
whether their replica groups cross the pod boundary, so the dry-run can
report slow-tier traffic separately.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.analysis.roofline import _SHAPE_RE, _shape_bytes


def hierarchical_allreduce(x, mesh: Mesh, pod_axis: str = "pod", fast_axis: str = "data"):
    """2-step pod-aware allreduce of a replicated array (see module doc).

    Falls back to a plain psum when the mesh has no pod axis or the leading
    dim does not divide the fast axis.
    """
    names = mesh.axis_names
    if pod_axis not in names:
        return shard_map(
            lambda v: jax.lax.psum(v, fast_axis),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
        )(x)
    fast = mesh.shape[fast_axis]
    if x.shape[0] % fast:
        return shard_map(
            lambda v: jax.lax.psum(v, (pod_axis, fast_axis)),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
        )(x)

    def body(v):
        # step 1: fast-tier reduce-scatter (chips end up with 1/|data| shards)
        shard = jax.lax.psum_scatter(v, fast_axis, scatter_dimension=0, tiled=True)
        # step 2: slow-tier all-reduce on shards only
        shard = jax.lax.psum(shard, pod_axis)
        # step 3: fast-tier all-gather
        return jax.lax.all_gather(shard, fast_axis, axis=0, tiled=True)

    return shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)(x)


def tiered_collective_bytes(hlo_text: str, pod_size: int) -> dict[str, int]:
    """Split collective payload bytes into intra-pod vs cross-pod tiers by
    inspecting replica_groups: a group crosses pods iff it contains device
    ids from different ``id // pod_size`` blocks."""
    out = {"intra_pod": 0, "cross_pod": 0}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.-]+\s*=\s*(.+?)\s+([\w-]+)\(", line)
        if not m:
            continue
        rt, op = m.groups()
        base = op.removesuffix("-start").removesuffix("-done")
        if base not in (
            "all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute",
        ) or op.endswith("-done"):
            continue
        nbytes = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(rt))
        crosses = False
        gm = re.search(r"replica_groups=\{?\{([0-9,{} ]*)\}", line)
        if gm:
            first_group = gm.group(1).split("}")[0]
            ids = [int(t) for t in first_group.replace("{", "").split(",") if t.strip().isdigit()]
            pods = {i // pod_size for i in ids}
            crosses = len(pods) > 1
        else:
            sm = re.search(r"source_target_pairs=\{\{(\d+),(\d+)", line)
            if sm:
                a, b = int(sm.group(1)), int(sm.group(2))
                crosses = a // pod_size != b // pod_size
        out["cross_pod" if crosses else "intra_pod"] += nbytes
    return out
