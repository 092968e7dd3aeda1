"""Optimizers, checkpointing (atomic/async/elastic), trainer fault
tolerance, gradient compression, data pipeline."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data import ShardedLoader, StragglerSimulator, SyntheticLMDataset
from repro.optim import (adafactor, adamw, clip_by_global_norm,
                         compressed_gradient, cosine_schedule, global_norm,
                         int8_dequantize, int8_quantize)
from repro.train import (CheckpointManager, FailureInjector, Trainer,
                         TrainerConfig, make_train_step)


# --------------------------------------------------------------------------
# optimizers
# --------------------------------------------------------------------------

@pytest.mark.parametrize("make_opt", [
    lambda: adamw(lambda s: 0.05, weight_decay=0.0),
    lambda: adafactor(lambda s: 0.5),
])
def test_optimizer_decreases_quadratic(make_opt):
    opt_init, opt_update = make_opt()
    params = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(8, 4)),
                               jnp.float32),
              "b": jnp.ones((4,), jnp.float32)}
    target = jax.tree.map(lambda p: p * 0.0, params)

    def loss(p):
        return sum(jnp.sum((a - b) ** 2) for a, b in
                   zip(jax.tree.leaves(p), jax.tree.leaves(target)))

    state = opt_init(params)
    l0 = float(loss(params))
    for _ in range(60):
        g = jax.grad(loss)(params)
        params, state = opt_update(g, state, params)
    assert float(loss(params)) < 0.2 * l0


def test_adafactor_memory_factored():
    _, _ = adafactor(lambda s: 1e-3)
    opt_init, _ = adafactor(lambda s: 1e-3)
    p = {"m": jnp.zeros((64, 32)), "v": jnp.zeros((16,))}
    st_ = opt_init(p)
    assert st_.inner["m"]["r"].shape == (64,)
    assert st_.inner["m"]["c"].shape == (32,)
    assert st_.inner["v"]["v"].shape == (16,)


def test_clip_by_global_norm():
    tree = {"a": jnp.full((10,), 10.0)}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert float(global_norm(clipped)) <= 1.0 + 1e-5
    assert float(norm) > 1.0


def test_cosine_schedule_shape():
    lr = cosine_schedule(1.0, warmup=10, total=100)
    assert float(lr(jnp.int32(0))) == 0.0
    assert abs(float(lr(jnp.int32(10))) - 1.0) < 1e-6
    assert float(lr(jnp.int32(100))) < 1e-6
    assert float(lr(jnp.int32(55))) < float(lr(jnp.int32(20)))


# --------------------------------------------------------------------------
# gradient compression
# --------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_error_feedback_preserves_mass(seed):
    """dense + residual_new == g + residual_old (nothing lost, only delayed)."""
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    res = jnp.asarray(rng.normal(size=(64,)), jnp.float32)
    dense, new_res = compressed_gradient(g, res, k_frac=0.1)
    np.testing.assert_allclose(np.asarray(dense + new_res),
                               np.asarray(g + res), atol=1e-6)
    assert int((np.asarray(dense) != 0).sum()) <= 7  # ~10% of 64, top-k


def test_int8_quantization_error_bounded():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(1000,)), jnp.float32)
    q, scale = int8_quantize(g)
    back = int8_dequantize(q, scale)
    assert float(jnp.abs(back - g).max()) <= float(scale) / 2 + 1e-7


# --------------------------------------------------------------------------
# checkpointing
# --------------------------------------------------------------------------

def _state(seed=0):
    rng = np.random.default_rng(seed)
    return {"params": {"w": jnp.asarray(rng.normal(size=(6, 3)), jnp.float32)},
            "opt": {"m": jnp.zeros((6, 3)), "step": jnp.int32(7)}}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    s = _state()
    mgr.save(3, s)
    restored, step = mgr.restore(s)
    assert step == 3
    for a, b in zip(jax.tree.leaves(s), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomicity_ignores_incomplete(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _state(1))
    # simulate a crashed writer: directory without _COMPLETE
    bad = tmp_path / "step_00000002"
    bad.mkdir()
    (bad / "manifest.json").write_text("{}")
    assert mgr.latest_step() == 1


def test_checkpoint_async_and_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    for step in (1, 2, 3, 4):
        mgr.save_async(step, _state(step))
    mgr.wait()
    assert mgr.all_steps() == [3, 4]


def test_checkpoint_restore_latest_of_many(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=5)
    for step in (2, 5, 9):
        mgr.save(step, _state(step))
    _, step = mgr.restore(_state())
    assert step == 9


# --------------------------------------------------------------------------
# trainer fault tolerance
# --------------------------------------------------------------------------

def _tiny_training(tmp_path, fail_steps=()):
    from repro.configs import get_config, reduced
    from repro.models import build_params
    cfg = reduced(get_config("qwen3-1.7b")).replace(n_units=1)
    params = build_params(cfg, jax.random.PRNGKey(0))
    opt_init, opt_update = adamw(cosine_schedule(1e-3, 2, 30))
    step = jax.jit(make_train_step(cfg, opt_update))
    ds = SyntheticLMDataset(cfg.vocab_size, 16, 4)
    loader = ShardedLoader(ds)
    trainer = Trainer(step, params, opt_init(params), loader,
                      TrainerConfig(total_steps=12, checkpoint_every=4,
                                    checkpoint_dir=str(tmp_path),
                                    log_every=1),
                      failure_injector=FailureInjector(fail_steps))
    return trainer


def test_trainer_runs_and_checkpoints(tmp_path):
    tr = _tiny_training(tmp_path)
    out = tr.run()
    assert out["final_step"] == 12
    assert out["restarts"] == 0
    assert tr.ckpt.latest_step() == 12
    losses = [m["loss"] for m in out["metrics"]]
    # per-batch loss on synthetic data is noisy; compare windowed means
    assert np.mean(losses[-4:]) < np.mean(losses[:4])


def test_trainer_recovers_from_failures(tmp_path):
    tr = _tiny_training(tmp_path, fail_steps=(5, 9))
    out = tr.run()
    assert out["final_step"] == 12
    assert out["restarts"] == 2


def test_trainer_gives_up_after_max_retries(tmp_path):
    tr = _tiny_training(tmp_path)
    tr.inject = FailureInjector(())

    class AlwaysFail:
        remaining = None
        def check(self, step):
            if step == 3:
                raise RuntimeError("permanent failure")
    tr.inject = AlwaysFail()
    tr.tcfg.max_retries = 2
    with pytest.raises(RuntimeError):
        tr.run()


# --------------------------------------------------------------------------
# data pipeline
# --------------------------------------------------------------------------

def test_dataset_deterministic_and_sharded():
    a = SyntheticLMDataset(100, 8, 16, n_shards=4, shard_id=1, seed=3)
    b = SyntheticLMDataset(100, 8, 16, n_shards=4, shard_id=1, seed=3)
    c = SyntheticLMDataset(100, 8, 16, n_shards=4, shard_id=2, seed=3)
    np.testing.assert_array_equal(a.batch(5)["inputs"], b.batch(5)["inputs"])
    assert not np.array_equal(a.batch(5)["inputs"], c.batch(5)["inputs"])
    assert a.batch(0)["inputs"].shape == (4, 8)


def test_straggler_speculative_reissue():
    ds = SyntheticLMDataset(50, 4, 2, seed=0)
    loader = ShardedLoader(ds, straggler_timeout_s=0.05,
                           straggler=StragglerSimulator(slow_every=3,
                                                        delay_s=0.5))
    batches = []
    for i, b in enumerate(loader.iterate(0, 6)):
        batches.append(b)
    assert len(batches) == 6
    assert loader.reissues >= 1
    # reissued batches are identical to what the slow worker would produce
    np.testing.assert_array_equal(batches[2]["inputs"], ds.batch(2)["inputs"])
