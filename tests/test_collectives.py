"""Pod-aware hierarchical collectives (beyond-paper, DESIGN.md §4)."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.collectives.hierarchical import tiered_collective_bytes

ROOT = Path(__file__).resolve().parents[1]

HLO = """
  %ar1 = bf16[64,8]{1,0} all-reduce(%a), replica_groups={{0,1,2,3},{4,5,6,7}}
  %ar2 = bf16[64,8]{1,0} all-reduce(%b), replica_groups={{0,4},{1,5},{2,6},{3,7}}
  %cp = bf16[8,8]{1,0} collective-permute(%c), source_target_pairs={{0,4},{4,0}}
"""


class TestTierClassifier:
    def test_intra_vs_cross(self):
        got = tiered_collective_bytes(HLO, pod_size=4)
        assert got["intra_pod"] == 64 * 8 * 2
        assert got["cross_pod"] == 64 * 8 * 2 + 8 * 8 * 2


@pytest.mark.slow
def test_hierarchical_allreduce_matches_flat():
    """2-step pod-aware allreduce == plain psum, and its slow-tier bytes are
    |data|x smaller (verified from lowered HLO)."""
    worker = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.collectives.hierarchical import hierarchical_allreduce, tiered_collective_bytes

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
x = jnp.arange(32.0).reshape(8, 4)
flat = shard_map(lambda v: jax.lax.psum(v, ("pod", "data")), mesh=mesh,
                 in_specs=P(), out_specs=P(), check_vma=False)
want = flat(x)
got = hierarchical_allreduce(x, mesh)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)

# slow-tier bytes: the hierarchical version's all-reduce (the only op that
# crosses pods) carries 1/|data| of the flat all-reduce payload
from repro.analysis.roofline import collective_bytes
txt_h = jax.jit(lambda v: hierarchical_allreduce(v, mesh)).lower(x).compile().as_text()
txt_f = jax.jit(flat).lower(x).compile().as_text()
cb_h, cb_f = collective_bytes(txt_h), collective_bytes(txt_f)
assert cb_f["all-reduce"] > 0
assert cb_h["all-reduce"] * 2 <= cb_f["all-reduce"], (cb_h, cb_f)
assert cb_h["reduce-scatter"] > 0 and cb_h["all-gather"] > 0
print("hierarchical ok", cb_h, cb_f)
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run([sys.executable, "-c", worker], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "hierarchical ok" in proc.stdout
