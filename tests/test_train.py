"""Training substrate: optimizer math, data determinism, checkpoint/restart,
fault tolerance, elasticity."""

import os
import signal
import numpy as np
import jax
from jax.sharding import AxisType
import jax.numpy as jnp
import pytest

from repro.models.common import ArchConfig
from repro.models.registry import model_api
from repro.train import (
    AdamWConfig,
    init_opt_state,
    apply_adamw,
    build_train_step,
    DataConfig,
    batch_at,
    save_checkpoint,
    restore_checkpoint,
    latest_step,
    install_preemption_handler,
)
from repro.train.optimizer import lr_at, zero1_specs
from jax.sharding import PartitionSpec as P


TINY = ArchConfig(
    name="tiny", family="dense", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
    d_ff=128, vocab=256, dtype=jnp.float32, remat=False,
)


class TestOptimizer:
    def test_adamw_matches_reference_math(self):
        cfg = AdamWConfig(lr=0.1, b1=0.9, b2=0.99, eps=1e-8, weight_decay=0.0,
                          grad_clip=1e9, warmup_steps=0, total_steps=10**9, min_lr_ratio=1.0)
        params = {"w": jnp.asarray([1.0, -2.0])}
        grads = {"w": jnp.asarray([0.5, 0.5])}
        state = init_opt_state(params)
        new, state, stats = apply_adamw(cfg, params, grads, state)
        # step 1: mhat = g, nhat = g^2  => delta = g/(|g|+eps) = sign(g)
        np.testing.assert_allclose(np.asarray(new["w"]), [0.9, -2.1], rtol=1e-5)
        assert float(stats["grad_norm"]) == pytest.approx(np.sqrt(0.5), rel=1e-5)

    def test_grad_clip(self):
        cfg = AdamWConfig(lr=0.0, grad_clip=1.0)
        params = {"w": jnp.ones(4)}
        grads = {"w": jnp.full(4, 100.0)}
        _, _, stats = apply_adamw(cfg, params, grads, init_opt_state(params))
        assert float(stats["grad_norm"]) == pytest.approx(200.0)

    def test_lr_schedule(self):
        cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110, min_lr_ratio=0.1)
        assert float(lr_at(cfg, 5)) == pytest.approx(0.5)
        assert float(lr_at(cfg, 10)) == pytest.approx(1.0)
        assert float(lr_at(cfg, 110)) == pytest.approx(0.1, rel=1e-3)

    def test_zero1_spreads_over_data(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        from repro.models.common import MeshAxes
        axes = MeshAxes.from_mesh(mesh)
        specs = {"w": P(None, "model")}
        shapes = {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32)}
        # data axis size 1 here, but the rule must still fire structurally
        out = zero1_specs(specs, axes, shapes)
        assert out["w"] == P("data", "model")


class TestData:
    def test_deterministic(self):
        cfg = DataConfig(vocab=100, batch=4, seq=16, seed=3)
        a = batch_at(cfg, 7)
        b = batch_at(cfg, 7)
        assert np.array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))

    def test_steps_differ(self):
        cfg = DataConfig(vocab=100, batch=4, seq=16, seed=3)
        a = batch_at(cfg, 1)
        b = batch_at(cfg, 2)
        assert not np.array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))

    def test_labels_are_shifted_tokens(self):
        cfg = DataConfig(vocab=100, batch=2, seq=8, seed=0)
        b = batch_at(cfg, 0)
        assert np.array_equal(np.asarray(b["tokens"][:, 1:]), np.asarray(b["labels"][:, :-1]))

    def test_learnable_structure(self):
        # Markov repeats: P(label == token) must be well above 1/vocab
        cfg = DataConfig(vocab=1000, batch=8, seq=128, seed=1, repeat_p=0.3)
        b = batch_at(cfg, 0)
        frac = float((np.asarray(b["tokens"]) == np.asarray(b["labels"])).mean())
        assert frac > 0.15


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.ones(4, jnp.int32)}}
        save_checkpoint(tmp_path, 42, tree, extra={"note": "hi"})
        assert latest_step(tmp_path) == 42
        restored, meta = restore_checkpoint(tmp_path, tree)
        assert meta["extra"]["note"] == "hi"
        for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_latest_pointer_advances(self, tmp_path):
        tree = {"a": jnp.zeros(2)}
        save_checkpoint(tmp_path, 1, tree)
        save_checkpoint(tmp_path, 2, tree)
        assert latest_step(tmp_path) == 2
        _, meta = restore_checkpoint(tmp_path, tree, step=1)
        assert meta["step"] == 1

    def test_restore_onto_different_mesh_shape(self, tmp_path):
        """Elasticity: save under one sharding, restore under another."""
        mesh_a = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        tree = {"w": jax.device_put(jnp.arange(16.0).reshape(4, 4),
                                    jax.NamedSharding(mesh_a, P(None, None)))}
        save_checkpoint(tmp_path, 3, tree)
        mesh_b = jax.make_mesh((1,), ("x",))
        sh = {"w": jax.NamedSharding(mesh_b, P("x", None))}
        restored, _ = restore_checkpoint(tmp_path, tree, shardings=sh)
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(16.0).reshape(4, 4))
        assert restored["w"].sharding.mesh.axis_names == ("x",)

    def test_resume_training_exact(self, tmp_path):
        """Train 4 steps straight == train 2, checkpoint, restore, train 2."""
        mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        api = model_api(TINY)
        bundle = build_train_step(TINY, mesh, AdamWConfig(lr=1e-3), batch=2, seq=16, donate=False)
        dcfg = DataConfig(vocab=TINY.vocab, batch=2, seq=16)

        params = api.init_params(TINY, jax.random.key(0))
        opt = init_opt_state(params)
        for step in range(4):
            params, opt, _ = bundle.step_fn(params, opt, batch_at(dcfg, step))
        straight = [np.asarray(x) for x in jax.tree.leaves(params)]

        params = api.init_params(TINY, jax.random.key(0))
        opt = init_opt_state(params)
        for step in range(2):
            params, opt, _ = bundle.step_fn(params, opt, batch_at(dcfg, step))
        save_checkpoint(tmp_path, 2, {"params": params, "opt": opt})
        (restored, ), meta = restore_checkpoint(tmp_path, ({"params": params, "opt": opt},))
        params, opt = restored["params"], restored["opt"]
        for step in range(meta["step"], 4):
            params, opt, _ = bundle.step_fn(params, opt, batch_at(dcfg, step))
        resumed = [np.asarray(x) for x in jax.tree.leaves(params)]
        for a, b in zip(straight, resumed):
            np.testing.assert_allclose(a, b, atol=1e-6)

    def test_preemption_handler(self, tmp_path):
        calls = []
        install_preemption_handler(lambda: calls.append(1))
        with pytest.raises(SystemExit) as e:
            os.kill(os.getpid(), signal.SIGTERM)
            signal.sigtimedwait([], 0)  # let the handler run (sync delivery)
        assert calls == [1]
        assert e.value.code == 143
        signal.signal(signal.SIGTERM, signal.SIG_DFL)


class TestMicrobatching:
    def test_accumulation_matches_full_batch(self):
        mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        api = model_api(TINY)
        params = api.init_params(TINY, jax.random.key(1))
        dcfg = DataConfig(vocab=TINY.vocab, batch=4, seq=16)
        batch = batch_at(dcfg, 0)
        opt_cfg = AdamWConfig(lr=1e-3, weight_decay=0.0)
        b1 = build_train_step(TINY, mesh, opt_cfg, batch=4, seq=16, microbatches=1, donate=False)
        b2 = build_train_step(TINY, mesh, opt_cfg, batch=4, seq=16, microbatches=2, donate=False)
        p1, _, m1 = b1.step_fn(params, init_opt_state(params), batch)
        p2, _, m2 = b2.step_fn(params, init_opt_state(params), batch)
        assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
