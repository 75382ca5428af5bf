"""Checkpoint/resume tests (SURVEY.md §2 #17, §5): full-session restore
reproduces the exact training trajectory."""

import numpy as np
import pytest

import jax

from orion_tpu.config import GRPOConfig, PPOConfig
from orion_tpu.models import (ScalarHeadModel, Transformer, init_params,
                              init_scalar_params)
from orion_tpu.trainers import GRPOTrainer, PPOTrainer

from test_trainers import lucky_token_reward, prompt_stream, tiny_model_cfg, _mk


def _grpo(tmp_path, every=2):
    cfg = _mk(GRPOConfig, group_size=2, kl_coef=0.0, num_epochs=1,
              minibatch_size=4,
              checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=every)
    cfg.model.vocab_size = 260  # ByteTokenizer ids
    model = Transformer(cfg.model)
    params = init_params(model, jax.random.key(0), cfg.model)
    return cfg, GRPOTrainer(cfg, model, params,
                            reward_fn=lucky_token_reward, eos_token_id=None)


def _prompt_iter(seed=0):
    """Checkpointable iterator (the real data-layer component)."""
    from orion_tpu.data import ByteTokenizer, build_prompt_iterator

    return build_prompt_iterator("synthetic", ByteTokenizer(), batch_size=2,
                                 max_prompt_len=24, synthetic_size=12,
                                 seed=seed)


def test_resume_reproduces_trajectory(tmp_path):
    # Run A: 6 iterations straight through, checkpoints every 2.
    cfg, tr_a = _grpo(tmp_path)
    it_a = _prompt_iter()
    hist_a = tr_a.train(it_a, num_iterations=6)

    # Run B: fresh trainer restores the step-4 checkpoint and runs 2 more.
    cfg_b, tr_b = _grpo(tmp_path)
    it_b = _prompt_iter()
    # restore() picks the latest step (6); restore 4 explicitly to test
    # mid-run resume
    out = tr_b.ckpt.restore(step=4, state_template=tr_b.state)
    tr_b.state = out["state"]
    extra = out["extra"]
    tr_b.global_iter = extra["global_iter"]
    import jax.numpy as jnp

    tr_b._rng = jax.random.wrap_key_data(jnp.asarray(extra["rng"], jnp.uint32))
    from orion_tpu.trainers.base import _np_state_from_json

    tr_b._np_rng.set_state(_np_state_from_json(extra["np_rng"]))
    it_b.load_state(extra["data"])
    tr_b.sync_weights()
    hist_b = tr_b.train(it_b, num_iterations=2)

    # Iterations 5-6 of run A must match run B's two iterations exactly.
    for a, b in zip(hist_a[4:], hist_b):
        assert a["reward_mean"] == pytest.approx(b["reward_mean"], abs=1e-6)
        assert a["loss"] == pytest.approx(b["loss"], abs=1e-5)


def test_resume_api_restores_latest(tmp_path):
    cfg, tr_a = _grpo(tmp_path)
    it_a = _prompt_iter()
    tr_a.train(it_a, num_iterations=4)
    step_a = tr_a.global_iter
    leaf_a = np.asarray(jax.tree.leaves(tr_a.state.params)[0])

    cfg_b, tr_b = _grpo(tmp_path)
    it_b = _prompt_iter()
    assert tr_b.resume(it_b) is True
    assert tr_b.global_iter == step_a
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(tr_b.state.params)[0]), leaf_a)
    assert it_b.state() == it_a.state()


def test_resume_restores_ppo_critic_and_kl(tmp_path):
    cfg = _mk(PPOConfig, num_epochs=1, adaptive_kl=True,
              checkpoint_dir=str(tmp_path / "c"), checkpoint_every=2)
    model = Transformer(cfg.model)
    params = init_params(model, jax.random.key(0), cfg.model)
    critic = ScalarHeadModel(cfg.model)
    cparams = init_scalar_params(critic, jax.random.key(1))
    tr = PPOTrainer(cfg, model, params, critic, cparams,
                    reward_fn=lucky_token_reward, eos_token_id=None)
    tr.train(prompt_stream(8, 4), num_iterations=2)
    kl_after = tr.kl_ctl.value
    critic_leaf = np.asarray(jax.tree.leaves(tr.critic_state.params)[0])

    tr2 = PPOTrainer(cfg, model,
                     init_params(model, jax.random.key(2), cfg.model),
                     critic, init_scalar_params(critic, jax.random.key(3)),
                     reward_fn=lucky_token_reward, eos_token_id=None)
    assert tr2.resume() is True
    assert tr2.kl_ctl.value == pytest.approx(kl_after)
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(tr2.critic_state.params)[0]), critic_leaf)


def test_no_checkpoint_returns_false(tmp_path):
    cfg, tr = _grpo(tmp_path)
    assert tr.resume() is False


def test_orbax_is_imported_by_who_checkpoints():
    """orbax takes seconds to import and is set-up time of every
    process: a job that never checkpoints must not pay for it, and
    ``orion_tpu.utils.CheckpointManager`` still resolves."""
    import subprocess
    import sys

    code = ("import sys; import orion_tpu.launch, orion_tpu.trainers; "
            "assert not any(m.startswith('orbax') for m in sys.modules); "
            "from orion_tpu.utils import CheckpointManager; "
            "assert 'orbax.checkpoint' in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)
