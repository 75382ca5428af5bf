"""Scalar heads: critic value model and reward model (SURVEY.md §2 #6-7).

Both are the backbone plus a Dense(1) head over final-norm hidden
states.  The critic reads per-token values over the response; the reward
model reads the value at the last real token of each sequence.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from orion_tpu.config import ModelConfig
from orion_tpu.models.transformer import Transformer, _dense, _dt


class ActorCriticModel(nn.Module):
    """Policy + value head on ONE shared trunk (PPOConfig.share_backbone).

    Drop-in replacement for ``Transformer`` in every BaseTrainer /
    RolloutEngine code path — ``__call__(ids, positions, cache)`` returns
    ``(logits, cache)`` exactly like the plain policy.  Pass
    ``with_values=True`` to additionally get per-position values
    [B, L] f32 from the value head: one trunk pass then serves both the
    policy and value losses, halving PPO's train-side backbone FLOPs and
    HBM residency vs a separate critic — the difference between a
    1B-policy PPO session (policy + ref + Adam moments) fitting on a
    single 16G v5e chip or not.  ``skip_lm_head=True`` with
    ``with_values=True`` gives a values-only forward (no vocab
    projection — at Llama-3 scale the largest matmul in the model).

    The value-head kernel is created unconditionally (``self.param``),
    so init/loading produce one stable param tree regardless of which
    outputs a given apply requests.
    """

    cfg: ModelConfig

    @nn.compact
    def __call__(self, input_ids, positions, cache=None,
                 with_values: bool = False, skip_lm_head: bool = False,
                 logits_positions=None, token_mask=None,
                 remat_keep: tuple = (), visible=None):
        logits, new_cache, hidden = Transformer(self.cfg, name="backbone")(
            input_ids, positions, cache, return_hidden=True,
            skip_lm_head=skip_lm_head, logits_positions=logits_positions,
            token_mask=token_mask, remat_keep=remat_keep, visible=visible)
        vk = self.param(
            "value_head",
            nn.with_logical_partitioning(
                nn.initializers.normal(
                    stddev=1.0 / self.cfg.hidden_size ** 0.5),
                ("embed", "norm")),
            (self.cfg.hidden_size, 1), _dt(self.cfg.param_dtype))
        if not with_values:
            return logits, new_cache
        values = jnp.einsum(
            "ble,eo->blo", hidden.astype(jnp.float32),
            vk.astype(jnp.float32))[..., 0]
        return logits, values, new_cache


def wrap_actor_critic_params(backbone_params, cfg: ModelConfig,
                             rng: Optional[jax.Array] = None):
    """Lift plain-Transformer policy params (random init or
    models.hf_loader output) into the ActorCriticModel tree:
    {"backbone": ..., "value_head": ...} with a fresh head."""
    rng = rng if rng is not None else jax.random.key(0)
    head = jax.random.normal(
        rng, (cfg.hidden_size, 1), _dt(cfg.param_dtype))
    head = head / cfg.hidden_size ** 0.5
    return {"backbone": backbone_params, "value_head": head}


class ScalarHeadModel(nn.Module):
    """Backbone + scalar head → per-position values [B, L] (f32)."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, input_ids, positions):
        _, _, hidden = Transformer(self.cfg, name="backbone")(
            input_ids, positions, return_hidden=True, skip_lm_head=True)
        head = nn.Dense(
            features=1, use_bias=False, dtype=_dt(self.cfg.dtype),
            param_dtype=_dt(self.cfg.param_dtype),
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=1.0 / self.cfg.hidden_size ** 0.5),
                ("embed", "norm")),
            name="score_head")
        values = head(hidden)[..., 0]
        return values.astype(jnp.float32)


def score_last_token(values: jnp.ndarray, lengths: jnp.ndarray) -> jnp.ndarray:
    """Gather values at the last real token: values [B, L], lengths [B]."""
    idx = jnp.clip(lengths - 1, 0, values.shape[1] - 1)
    return jnp.take_along_axis(values, idx[:, None], axis=1)[:, 0]


def init_scalar_params(model: ScalarHeadModel, rng: jax.Array,
                       unbox: bool = True):
    ids = jnp.zeros((1, 2), jnp.int32)
    variables = model.init(rng, ids, ids)
    params = variables["params"]
    return nn.meta.unbox(params) if unbox else params
