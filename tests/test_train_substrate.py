"""Optimizer / data / checkpoint / train-step unit tests."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.checkpoint import CheckpointManager, restore, save
from repro.data import DataConfig, SyntheticStream
from repro.optim import AdamW, cosine_schedule, ef_int8_compress, ef_int8_init
from repro.optim.adamw import global_norm


# ----------------------------------------------------------------- optimizer
class TestAdamW:
    def test_quadratic_converges(self):
        opt = AdamW(lr=0.1, weight_decay=0.0, clip_norm=None)
        params = {"x": jnp.array([5.0, -3.0])}
        state = opt.init(params)
        loss = lambda p: jnp.sum(p["x"] ** 2)
        for _ in range(200):
            g = jax.grad(loss)(params)
            params, state, _ = opt.update(g, state, params)
        assert float(loss(params)) < 1e-3

    def test_clip_norm(self):
        opt = AdamW(lr=0.0, clip_norm=1.0)
        params = {"x": jnp.zeros(3)}
        state = opt.init(params)
        g = {"x": jnp.array([100.0, 0.0, 0.0])}
        _, _, stats = opt.update(g, state, params)
        assert float(stats["grad_norm"]) == pytest.approx(100.0)

    def test_weight_decay_only_matrices(self):
        opt = AdamW(lr=0.1, weight_decay=1.0, clip_norm=None)
        params = {"w": jnp.ones((2, 2)), "b": jnp.ones((2,))}
        state = opt.init(params)
        zero_g = jax.tree.map(jnp.zeros_like, params)
        new, _, _ = opt.update(zero_g, state, params)
        assert float(jnp.abs(new["w"] - 1.0).max()) > 0.0  # decayed
        assert float(jnp.abs(new["b"] - 1.0).max()) == 0.0  # exempt

    def test_cosine_schedule_shape(self):
        lr = cosine_schedule(1.0, 10, 100, final_frac=0.1)
        assert float(lr(0)) == 0.0
        assert float(lr(10)) == pytest.approx(1.0)
        assert float(lr(100)) == pytest.approx(0.1, rel=1e-2)
        assert float(lr(55)) < float(lr(20))


# --------------------------------------------------------------- compression
class TestCompression:
    def test_roundtrip_small_error(self):
        g = {"w": jnp.linspace(-1, 1, 256)}
        ef = ef_int8_init(g)
        deq, ef = ef_int8_compress(g, ef)
        assert float(jnp.abs(deq["w"] - g["w"]).max()) < 1e-2

    def test_error_feedback_unbiased_over_time(self):
        """Repeatedly compressing the same gradient: the SUM of delivered
        gradients tracks the sum of true gradients (EF property)."""
        g = {"w": jnp.array([0.3e-3, -1.7e-3, 0.9e-3, 2.2e-3])}
        ef = ef_int8_init(g)
        delivered = jnp.zeros(4)
        n = 50
        for _ in range(n):
            deq, ef = ef_int8_compress(g, ef)
            delivered += deq["w"]
        np.testing.assert_allclose(
            np.asarray(delivered / n), np.asarray(g["w"]), rtol=0.02, atol=1e-6
        )

    def test_sgd_with_ef8_matches_uncompressed_direction(self):
        key = jax.random.PRNGKey(0)
        w_true = jax.random.normal(key, (16,))
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
        y = x @ w_true
        loss = lambda w: jnp.mean((x @ w - y) ** 2)
        w_a = jnp.zeros(16)
        w_b = jnp.zeros(16)
        ef = ef_int8_init({"w": w_b})
        for _ in range(600):
            g = jax.grad(loss)(w_a)
            w_a -= 0.01 * g
            g2 = jax.grad(loss)(w_b)
            deq, ef = ef_int8_compress({"w": g2}, ef)
            w_b -= 0.01 * deq["w"]
        assert float(loss(w_a)) < 1e-3
        # EF compression converges to comparable loss (within 5x)
        assert float(loss(w_b)) < max(5 * float(loss(w_a)), 1e-3)


# ----------------------------------------------------------------------- data
class TestData:
    def test_deterministic_and_resumable(self):
        cfg = DataConfig(vocab_size=97, seq_len=16, global_batch=4, seed=7)
        s1, s2 = SyntheticStream(cfg), SyntheticStream(cfg)
        for step in (0, 5, 1000):
            b1, b2 = s1.batch(step), s2.batch(step)
            np.testing.assert_array_equal(b1["tokens"], b2["tokens"])

    def test_steps_differ(self):
        cfg = DataConfig(vocab_size=97, seq_len=16, global_batch=4)
        s = SyntheticStream(cfg)
        assert not np.array_equal(s.batch(0)["tokens"], s.batch(1)["tokens"])

    def test_targets_shifted(self):
        cfg = DataConfig(vocab_size=97, seq_len=16, global_batch=2)
        b = SyntheticStream(cfg).batch(3)
        np.testing.assert_array_equal(b["targets"][:, :-1], b["tokens"][:, 1:])
        assert (b["targets"][:, -1] == -1).all()

    def test_host_slice(self):
        cfg = DataConfig(vocab_size=97, seq_len=8, global_batch=8)
        s = SyntheticStream(cfg)
        full = s.batch(0)
        part = s.batch(0, host_slice=slice(2, 6))
        np.testing.assert_array_equal(part["tokens"], full["tokens"][2:6])

    def test_frontend_embeds(self):
        cfg = DataConfig(
            vocab_size=97, seq_len=16, global_batch=2, frontend_tokens=4, d_model=8
        )
        b = SyntheticStream(cfg).batch(0)
        assert b["ext_embeds"].shape == (2, 4, 8)
        assert b["tokens"].shape == (2, 12)

    @given(st.integers(min_value=0, max_value=10_000))
    @settings(max_examples=20, deadline=None)
    def test_property_pure_function_of_step(self, step):
        cfg = DataConfig(vocab_size=31, seq_len=8, global_batch=2, seed=3)
        b1 = SyntheticStream(cfg).batch(step)
        b2 = SyntheticStream(cfg).batch(step)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])


# ----------------------------------------------------------------- checkpoint
class TestCheckpoint:
    def _tree(self, seed=0):
        k = jax.random.PRNGKey(seed)
        return {
            "params": {"w": jax.random.normal(k, (4, 4)), "b": jnp.ones(4)},
            "opt": {"step": jnp.int32(7), "mu": {"w": jnp.zeros((4, 4))}},
        }

    def test_save_restore_roundtrip(self, tmp_path):
        tree = self._tree()
        path = save(str(tmp_path), 7, tree)
        template = jax.tree.map(jnp.zeros_like, tree)
        out = restore(path, template)
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_manager_keep_and_latest(self, tmp_path):
        m = CheckpointManager(str(tmp_path), keep=2)
        tree = self._tree()
        for s in (10, 20, 30):
            m.save(s, tree)
        assert m.steps() == [20, 30]
        assert m.latest_step() == 30

    def test_async_save(self, tmp_path):
        m = CheckpointManager(str(tmp_path), keep=2)
        m.save_async(5, self._tree())
        m.wait()
        assert m.latest_step() == 5

    def test_partial_checkpoint_ignored(self, tmp_path):
        m = CheckpointManager(str(tmp_path), keep=3)
        m.save(10, self._tree())
        # simulate a crash mid-write: directory without manifest
        os.makedirs(tmp_path / "step_00000020")
        assert m.latest_step() == 10

    def test_shape_mismatch_raises(self, tmp_path):
        path = save(str(tmp_path), 1, {"w": jnp.zeros((2, 2))})
        with pytest.raises(ValueError):
            restore(path, {"w": jnp.zeros((3, 3))})

    def test_restore_latest_none_when_empty(self, tmp_path):
        m = CheckpointManager(str(tmp_path))
        step, tree = m.restore_latest({"x": jnp.zeros(1)})
        assert step is None and tree is None


# ------------------------------------------------------------------ train_step
class TestTrainStep:
    def test_microbatch_accumulation_matches_full(self):
        from repro.configs import smoke_config
        from repro.models import Model
        from repro.train import make_train_step

        cfg = smoke_config("granite-3-8b")
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt = AdamW(lr=1e-3, clip_norm=None, weight_decay=0.0)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
        batch = {
            "tokens": tokens,
            "targets": jnp.roll(tokens, -1, 1).at[:, -1].set(-1),
        }
        outs = {}
        for mb in (1, 2):
            step = make_train_step(model, opt, microbatches=mb)
            p, s, _, metrics = jax.jit(step)(
                params, opt.init(params), {}, batch
            )
            outs[mb] = (metrics["loss"], p)
        # bf16 forward: small tolerance on loss, params close
        assert float(jnp.abs(outs[1][0] - outs[2][0])) < 2e-2
        for a, b in zip(jax.tree.leaves(outs[1][1]), jax.tree.leaves(outs[2][1])):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32), atol=2e-2
            )

    def test_loss_decreases_over_steps(self):
        from repro.configs import smoke_config
        from repro.data import DataConfig, SyntheticStream
        from repro.models import Model
        from repro.train import make_train_step

        cfg = smoke_config("qwen2-1.5b")
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt = AdamW(lr=3e-3)
        state = opt.init(params)
        step_fn = jax.jit(make_train_step(model, opt))
        stream = SyntheticStream(
            DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=8)
        )
        losses = []
        for i in range(30):
            b = stream.batch(i)
            params, state, _, m = step_fn(params, state, {}, b)
            losses.append(float(m["loss"]))
        assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3, losses


# ------------------------------------------------------- fault-tolerant loop
class TestTrainLoopFaultTolerance:
    """Retry-path regressions: duplicate-free history after rollback, a
    consecutive (not cumulative) failure budget, and honest per-step
    throughput in the history."""

    def _run(self, tmp_path, *, steps, failure_hook=None, max_failures=3,
             ckpt_every=4, log_every=1):
        from repro.configs.base import ModelConfig
        from repro.data import DataConfig
        from repro.models import Model
        from repro.train import TrainLoopConfig, train_loop

        cfg = ModelConfig(
            name="loop-test", family="dense", n_layers=2, d_model=32,
            n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64, remat="none",
        )
        return train_loop(
            Model(cfg),
            DataConfig(vocab_size=64, seq_len=16, global_batch=4),
            TrainLoopConfig(
                steps=steps, ckpt_dir=str(tmp_path), ckpt_every=ckpt_every,
                keep=3, peak_lr=1e-3, warmup=2, log_every=log_every,
                max_failures=max_failures,
            ),
            failure_hook=failure_hook,
        )

    def test_rollback_dedupes_history(self, tmp_path):
        """A failure past a checkpoint replays steps; the returned history
        must not contain duplicate step numbers."""
        state = {"fired": False}

        def boom(step):
            if step == 6 and not state["fired"]:
                state["fired"] = True
                raise RuntimeError("injected fault")

        res = self._run(tmp_path, steps=10, failure_hook=boom)
        assert state["fired"] and res["failures"] == 1
        steps = [h["step"] for h in res["history"]]
        assert steps == sorted(set(steps)), steps
        assert res["final_step"] == 10

    def test_transient_faults_spread_across_run_survive(self, tmp_path):
        """More total faults than max_failures, but each retry succeeds:
        the consecutive budget must NOT kill the run (the old cumulative
        counter did)."""
        fired = set()

        def boom(step):
            if step in (3, 5, 7, 9) and step not in fired:
                fired.add(step)
                raise RuntimeError(f"transient fault @ {step}")

        res = self._run(tmp_path, steps=12, failure_hook=boom, max_failures=2)
        assert len(fired) == 4
        assert res["failures"] == 4  # total is still reported
        assert res["final_step"] == 12

    def test_persistent_failure_exhausts_budget(self, tmp_path):
        """A step that keeps failing must still raise after max_failures
        consecutive attempts."""
        attempts = []

        def boom(step):
            if step == 5:
                attempts.append(step)
                raise RuntimeError("persistent fault")

        with pytest.raises(RuntimeError, match="persistent fault"):
            self._run(tmp_path, steps=10, failure_hook=boom, max_failures=2)
        assert len(attempts) == 3  # budget + the final fatal attempt

    def test_history_dt_is_per_step(self, tmp_path):
        """history[*]['dt_s'] must be per-step time, not the whole
        log_every window (the old behavior over-reported by log_every x)."""
        sleep_s = 0.05

        def slow(step):
            time.sleep(sleep_s)

        res = self._run(
            tmp_path, steps=11, failure_hook=slow, log_every=5
        )
        entries = {h["step"]: h["dt_s"] for h in res["history"]}
        # steady-state windows (steps 1-5 and 6-10) cover 5 steps each of
        # >= 50ms: per-step must sit near one step's cost, far below the
        # ~250ms window total the bug reported
        for s in (5, 10):
            assert sleep_s <= entries[s] < 3 * sleep_s, entries


class TestNonFiniteLoss:
    """PR 6 satellite: a NaN/Inf loss is a *failed step*, not a number to
    log — it must consume the failure budget through the same rollback
    path a crash does (the old loop logged the NaN and kept training on
    poisoned optimizer state)."""

    def test_nan_loss_consumes_failure_budget(self, tmp_path):
        from repro.configs.base import ModelConfig
        from repro.core import NonFiniteLossError
        from repro.data import DataConfig
        from repro.models import Model
        from repro.train import TrainLoopConfig, train_loop

        cfg = ModelConfig(
            name="nan-test", family="dense", n_layers=2, d_model=32,
            n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64, remat="none",
        )
        # an absurd peak LR diverges to NaN within a couple of steps;
        # rollback replays the same data and LR schedule, so the NaN is
        # persistent and must exhaust the consecutive-failure budget
        with pytest.raises(NonFiniteLossError, match="non-finite loss"):
            train_loop(
                Model(cfg),
                DataConfig(vocab_size=64, seq_len=16, global_batch=4),
                TrainLoopConfig(
                    steps=10, ckpt_dir=str(tmp_path), ckpt_every=4, keep=3,
                    peak_lr=1e6, warmup=2, log_every=1, max_failures=2,
                ),
            )

    def test_healthy_run_logs_only_finite_losses(self, tmp_path):
        from repro.configs.base import ModelConfig
        from repro.data import DataConfig
        from repro.models import Model
        from repro.train import TrainLoopConfig, train_loop

        cfg = ModelConfig(
            name="nan-test", family="dense", n_layers=2, d_model=32,
            n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64, remat="none",
        )
        res = train_loop(
            Model(cfg),
            DataConfig(vocab_size=64, seq_len=16, global_batch=4),
            TrainLoopConfig(
                steps=8, ckpt_dir=str(tmp_path), ckpt_every=4,
                peak_lr=1e-3, warmup=2, log_every=1,
            ),
        )
        assert res["failures"] == 0
        losses = [h["loss"] for h in res["history"]]
        assert losses and all(np.isfinite(losses)), losses
