"""Training infrastructure: optimizer, microbatching, compression, data,
checkpointing (incl. elastic re-shard), faults."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import manager as ckpt
from repro.configs import get_config, reduce_config
from repro.data.pipeline import DataConfig, host_slice, make_batch
from repro.models import build_model
from repro.train.compress import (
    CompressionConfig,
    compress_and_reduce,
    init_error_state,
)
from repro.train.optimizer import (
    OptimizerConfig,
    apply_optimizer,
    init_opt_state,
    lr_schedule,
)
from repro.sharding import make_mesh
from repro.train.step import TrainConfig, init_train_state, make_train_step

KEY = jax.random.key(0)


class TestOptimizer:
    @pytest.mark.parametrize("name", ["adamw", "lion", "sgdm"])
    def test_quadratic_descent(self, name):
        cfg = OptimizerConfig(name=name, lr=0.1, weight_decay=0.0,
                              warmup_steps=0, decay_steps=100)
        params = {"w": jnp.array([3.0, -2.0])}
        state = init_opt_state(params)
        for _ in range(60):
            grads = {"w": 2 * params["w"]}  # grad of |w|^2
            params, state, _ = apply_optimizer(cfg, params, grads, state)
        assert float(jnp.linalg.norm(params["w"])) < 0.3

    def test_grad_clip(self):
        cfg = OptimizerConfig(lr=1.0, grad_clip=1.0, weight_decay=0.0,
                              warmup_steps=0)
        params = {"w": jnp.zeros(3)}
        state = init_opt_state(params)
        _, _, metrics = apply_optimizer(cfg, params,
                                        {"w": jnp.full(3, 100.0)}, state)
        assert float(metrics["grad_norm"]) > 100

    def test_lr_schedule_shape(self):
        cfg = OptimizerConfig(lr=1e-3, warmup_steps=10, decay_steps=100,
                              min_lr_ratio=0.1)
        lrs = [float(lr_schedule(cfg, s)) for s in range(0, 100, 10)]
        assert lrs[0] < lrs[1]  # warmup rises
        assert lrs[-1] < lrs[2]  # cosine decays
        assert lrs[-1] >= 1e-4 * 0.99  # floors at min_lr_ratio

    def test_bf16_moments(self):
        cfg = OptimizerConfig(lr=0.1, moment_dtype="bfloat16",
                              weight_decay=0.0, warmup_steps=0)
        params = {"w": jnp.array([1.0])}
        state = init_opt_state(params, jnp.bfloat16)
        params, state, _ = apply_optimizer(cfg, params, {"w": jnp.array([1.0])},
                                           state)
        assert state.mu["w"].dtype == jnp.bfloat16


class TestMicrobatching:
    def test_equivalent_to_full_batch(self):
        """mean-of-microbatch-grads == full-batch grad (linear loss in batch)."""
        cfg = reduce_config(get_config("phi3-mini-3.8b"))
        model = build_model(cfg)
        batch = {"tokens": jax.random.randint(KEY, (4, 16), 0, cfg.vocab_size)}
        ocfg = OptimizerConfig(lr=1e-2, warmup_steps=0, decay_steps=10,
                               weight_decay=0.0)
        out = {}
        for mb in (1, 4):
            tcfg = TrainConfig(optimizer=ocfg, remat=False, microbatches=mb,
                               z_loss=0.0)
            state = init_train_state(model, KEY, tcfg)
            state, metrics = jax.jit(make_train_step(model, tcfg))(state, batch)
            out[mb] = (jax.tree.leaves(state.params)[0], metrics["loss"])
        np.testing.assert_allclose(np.asarray(out[1][1]), np.asarray(out[4][1]),
                                   rtol=1e-4)
        np.testing.assert_allclose(np.asarray(out[1][0]), np.asarray(out[4][0]),
                                   rtol=1e-3, atol=1e-5)


class TestCompression:
    def test_int8_error_feedback_converges(self):
        """With error feedback, compressed SGD still drives a quadratic to 0."""
        w = jnp.array([2.0, -3.0, 1.5])
        ccfg = CompressionConfig(kind="int8")
        err = init_error_state({"w": w})
        for _ in range(200):
            g = {"w": 2 * w}
            red, err = compress_and_reduce(ccfg, g, err, lambda x: x)
            w = w - 0.05 * red["w"]
        assert float(jnp.linalg.norm(w)) < 0.05

    def test_int8_unbiased_on_average(self):
        g = {"w": jax.random.normal(KEY, (256,)) * 1e-3}
        ccfg = CompressionConfig(kind="int8")
        err = init_error_state(g)
        red, err2 = compress_and_reduce(ccfg, g, err, lambda x: x)
        # quantization error is bounded by scale/2 and captured in err state
        scale = float(jnp.max(jnp.abs(g["w"]))) / 127
        assert float(jnp.max(jnp.abs(err2["w"]))) <= scale
        np.testing.assert_allclose(np.asarray(red["w"] + err2["w"]),
                                   np.asarray(g["w"]), rtol=1e-5, atol=1e-8)

    def test_topk_sparsity(self):
        g = {"w": jnp.arange(100.0)}
        ccfg = CompressionConfig(kind="topk", topk_ratio=0.1)
        red, err = compress_and_reduce(ccfg, g, init_error_state(g), lambda x: x)
        assert int(jnp.sum(red["w"] != 0)) <= 11


class TestData:
    def test_determinism_and_recompute(self):
        """Any host can recompute any shard at any step — byte-identical."""
        cfg = reduce_config(get_config("phi3-mini-3.8b"))
        dcfg = DataConfig(seed=7, vocab_size=cfg.vocab_size)
        a = make_batch(dcfg, cfg, 8, 32, step=5)
        b = make_batch(dcfg, cfg, 8, 32, step=5)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        c = make_batch(dcfg, cfg, 8, 32, step=6)
        assert not np.array_equal(a["tokens"], c["tokens"])

    def test_host_slicing_partitions(self):
        cfg = reduce_config(get_config("phi3-mini-3.8b"))
        dcfg = DataConfig(seed=0, vocab_size=cfg.vocab_size)
        full = make_batch(dcfg, cfg, 8, 16, step=0)
        parts = [host_slice(full, h, 4)["tokens"] for h in range(4)]
        np.testing.assert_array_equal(np.concatenate(parts), full["tokens"])

    def test_learnable_structure(self):
        dcfg = DataConfig(seed=0, vocab_size=64)
        toks = make_batch(dcfg, reduce_config(get_config("phi3-mini-3.8b")),
                          4, 64, 0)["tokens"]
        # even positions follow the bigram rule
        np.testing.assert_array_equal(toks[:, 1::2],
                                      (toks[:, 0:-1:2] * 7 + 3) % 64)


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.ones(4)}}
        ckpt.save(str(tmp_path), 10, tree)
        out = ckpt.restore(str(tmp_path), tree)
        np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))

    def test_commit_marker_required(self, tmp_path):
        tree = {"a": jnp.ones(2)}
        path = ckpt.save(str(tmp_path), 5, tree)
        os.remove(os.path.join(path, "COMMIT"))
        assert ckpt.latest_step(str(tmp_path)) is None
        with pytest.raises(FileNotFoundError):
            ckpt.restore(str(tmp_path), tree)

    def test_keep_n_gc(self, tmp_path):
        tree = {"a": jnp.ones(2)}
        for s in range(6):
            ckpt.save(str(tmp_path), s, tree, keep=2)
        assert ckpt.committed_steps(str(tmp_path)) == [4, 5]

    def test_elastic_reshard_across_meshes(self, tmp_path):
        """Save on one sharding layout, restore onto another (different
        device partitioning) — the elastic-restart path."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh1 = make_mesh((1,), ("data",))
        tree = {"w": jnp.arange(16.0).reshape(4, 4)}
        ckpt.save(str(tmp_path), 1, tree)
        shard = {"w": NamedSharding(mesh1, P("data", None))}
        out = ckpt.restore(str(tmp_path), tree, shardings=shard)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(tree["w"]))
        assert out["w"].sharding == shard["w"]

    def test_train_state_resume_continuity(self, tmp_path):
        """Training N steps == training k, checkpointing, resuming, N-k."""
        cfg = reduce_config(get_config("phi3-mini-3.8b"))
        model = build_model(cfg)
        tcfg = TrainConfig(optimizer=OptimizerConfig(lr=1e-3, warmup_steps=0,
                                                     decay_steps=100),
                           remat=False, z_loss=0.0)
        dcfg = DataConfig(seed=0, vocab_size=cfg.vocab_size)
        step_fn = jax.jit(make_train_step(model, tcfg))

        def batch_at(s):
            return {k: jnp.asarray(v)
                    for k, v in make_batch(dcfg, cfg, 4, 16, s).items()}

        sA = init_train_state(model, KEY, tcfg)
        for s in range(4):
            sA, _ = step_fn(sA, batch_at(s))

        sB = init_train_state(model, KEY, tcfg)
        for s in range(2):
            sB, _ = step_fn(sB, batch_at(s))
        ckpt.save(str(tmp_path), 2, sB)
        sB2 = ckpt.restore(str(tmp_path), sB)
        for s in range(2, 4):
            sB2, _ = step_fn(sB2, batch_at(s))

        for a, b in zip(jax.tree.leaves(sA.params), jax.tree.leaves(sB2.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


class TestFaults:
    def test_step_guard_warn_and_abort(self):
        import time
        from repro.launch.faults import StepGuard
        g = StepGuard(deadline_s=0.001, on_breach="warn")
        with g.step(0):
            time.sleep(0.01)
        assert g.breaches == 1
        g2 = StepGuard(deadline_s=0.001, on_breach="abort")
        with pytest.raises(TimeoutError):
            with g2.step(0):
                time.sleep(0.01)

    def test_reseed_lost_lanes(self):
        from repro.launch.faults import reseed_lost_lanes
        x = jnp.zeros((8, 3))
        lost = jnp.array([True] * 4 + [False] * 4)
        out = reseed_lost_lanes(KEY, x, lost, -1.0, 1.0)
        assert float(jnp.abs(out[:4]).sum()) > 0  # reseeded
        np.testing.assert_array_equal(np.asarray(out[4:]), np.zeros((4, 3)))
