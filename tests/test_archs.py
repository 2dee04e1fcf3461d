"""Per-architecture smoke tests: reduced config, one real train step + one
decode step on CPU, asserting shapes and finiteness.  Full configs are
exercised only via the dry-run (ShapeDtypeStruct, no allocation)."""

import numpy as np
import jax
from jax.sharding import AxisType
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, SHAPE_CELLS, get_config, get_smoke, get_shapes
from repro.models.registry import model_api, serve_input_specs
from repro.models.common import MeshAxes
from repro.train import build_train_step, AdamWConfig, init_opt_state, DataConfig, batch_at


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)


PUBLISHED_SIZES = {
    "phi3_medium_14b": 14.7e9,
    "stablelm_1_6b": 1.6e9,
    "granite_20b": 20e9,
    "granite_8b": 8e9,
    "mamba2_780m": 0.78e9,
    "whisper_medium": 0.77e9,
    "zamba2_1_2b": 1.2e9,
    "phi35_moe_42b": 42e9,
    "olmoe_1b_7b": 6.9e9,
    "paligemma_3b": 2.6e9,  # text backbone (vision tower stubbed)
}


class TestConfigs:
    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_param_count_matches_published(self, arch):
        cfg = get_config(arch)
        assert abs(cfg.param_count() - PUBLISHED_SIZES[arch]) / PUBLISHED_SIZES[arch] < 0.15

    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_assigned_dims(self, arch):
        cfg = get_config(arch)
        # spot-check the assignment table
        table = {
            "phi3_medium_14b": (40, 5120, 40, 10, 17920, 100352),
            "stablelm_1_6b": (24, 2048, 32, 32, 5632, 100352),
            "granite_20b": (52, 6144, 48, 1, 24576, 49152),
            "granite_8b": (36, 4096, 32, 8, 14336, 49152),
            "mamba2_780m": (48, 1536, 0, 0, 0, 50280),
            "whisper_medium": (24, 1024, 16, 16, 4096, 51865),
            "zamba2_1_2b": (38, 2048, 32, 32, 8192, 32000),
            "phi35_moe_42b": (32, 4096, 32, 8, 6400, 32064),
            "olmoe_1b_7b": (16, 2048, 16, 16, 1024, 50304),
            "paligemma_3b": (18, 2048, 8, 1, 16384, 257216),
        }
        l, d, h, kv, f, v = table[arch]
        assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.vocab) == (
            l, d, h, kv, f, v,
        )

    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_shape_cells_defined(self, arch):
        shapes = get_shapes(arch)
        assert set(shapes) == set(SHAPE_CELLS)
        if arch in ("mamba2_780m", "zamba2_1_2b"):
            assert shapes["long_500k"] == "run"
        else:
            assert shapes["long_500k"].startswith("skip:")


class TestSmoke:
    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_train_step(self, mesh, arch):
        cfg = get_smoke(arch).with_(dtype=jnp.float32)
        api = model_api(cfg)
        bundle = build_train_step(
            cfg, mesh, AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10), batch=2, seq=32
        )
        params = api.init_params(cfg, jax.random.key(0))
        before = [np.asarray(x) for x in jax.tree.leaves(params)]  # pre-donation copy
        opt = init_opt_state(params)
        dcfg = DataConfig(vocab=cfg.vocab, batch=2, seq=32)
        extra = {k: v for k, v in bundle.abstract_batch.items() if k not in ("tokens", "labels")}
        batch = batch_at(dcfg, 0, extra=extra)
        params2, opt2, metrics = bundle.step_fn(params, opt, batch)
        assert np.isfinite(float(metrics["loss"])), arch
        assert np.isfinite(float(metrics["grad_norm"])), arch
        assert float(metrics["grad_norm"]) > 0
        # params actually changed
        delta = max(
            float(np.abs(np.asarray(a, np.float32) - b).max())
            for a, b in zip(jax.tree.leaves(params2), before)
        )
        assert delta > 0, arch

    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_loss_decreases(self, mesh, arch):
        cfg = get_smoke(arch).with_(dtype=jnp.float32)
        api = model_api(cfg)
        bundle = build_train_step(
            cfg, mesh, AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=30, weight_decay=0.0),
            batch=4, seq=32,
        )
        params = api.init_params(cfg, jax.random.key(0))
        opt = init_opt_state(params)
        dcfg = DataConfig(vocab=cfg.vocab, batch=4, seq=32)
        extra = {k: v for k, v in bundle.abstract_batch.items() if k not in ("tokens", "labels")}
        losses = []
        for step in range(12):
            batch = batch_at(dcfg, step, extra=extra)
            params, opt, metrics = bundle.step_fn(params, opt, batch)
            losses.append(float(metrics["loss"]))
        assert np.mean(losses[-3:]) < np.mean(losses[:3]) - 0.05, (arch, losses)

    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_decode_step(self, mesh, arch):
        cfg = get_smoke(arch).with_(dtype=jnp.float32)
        api = model_api(cfg)
        params = api.init_params(cfg, jax.random.key(0))
        cache = api.init_cache(cfg, 2, 16)
        step = jax.jit(api.decode_step(cfg, mesh))
        logits, cache2 = step(
            params, cache, {"token": jnp.array([1, 2], jnp.int32), "pos": jnp.zeros(2, jnp.int32)}
        )
        assert logits.shape == (2, cfg.vocab_padded), arch
        assert bool(jnp.isfinite(logits[:, : cfg.vocab]).all()), arch
        assert jax.tree.structure(cache2) == jax.tree.structure(cache)
