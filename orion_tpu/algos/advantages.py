"""Advantage estimation: GAE (PPO), leave-one-out (RLOO), group-relative
(GRPO) — SURVEY.md §2 #1-4.

All token-level tensors are [B, T] over completion tokens with a f32
mask (1.0 = real token).  GAE runs as a reverse ``lax.scan`` over the
time axis — compiler-friendly, no Python loop over T.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def masked_mean(x: jnp.ndarray, mask: jnp.ndarray,
                axis=None) -> jnp.ndarray:
    return jnp.sum(x * mask, axis=axis) / jnp.maximum(
        jnp.sum(mask, axis=axis), 1.0)


def masked_whiten(x: jnp.ndarray, mask: jnp.ndarray,
                  shift_mean: bool = True, eps: float = 1e-8) -> jnp.ndarray:
    mean = masked_mean(x, mask)
    var = masked_mean((x - mean) ** 2, mask)
    whitened = (x - mean) * jax.lax.rsqrt(var + eps)
    if not shift_mean:
        whitened = whitened + mean
    return whitened * mask


def per_token_rewards(scores: jnp.ndarray, kl: jnp.ndarray,
                      mask: jnp.ndarray, kl_coef: float,
                      reward_clip: float = 0.0) -> jnp.ndarray:
    """Dense reward tensor: -kl_coef·KL at every completion token plus
    the (clipped) sequence score at the last real token."""
    if reward_clip > 0:
        scores = jnp.clip(scores, -reward_clip, reward_clip)
    rewards = -kl_coef * kl * mask
    last_idx = jnp.maximum(jnp.sum(mask, axis=1).astype(jnp.int32) - 1, 0)
    B = scores.shape[0]
    rewards = rewards.at[jnp.arange(B), last_idx].add(scores)
    return rewards * mask


@functools.partial(jax.jit, static_argnames=("gamma", "lam"))
def gae(rewards: jnp.ndarray, values: jnp.ndarray, mask: jnp.ndarray,
        gamma: float, lam: float) -> tuple:
    """Generalized advantage estimation over [B, T] tensors.

    V beyond the last real token is treated as 0 (sequences terminate).
    Returns (advantages, returns) both [B, T] f32, masked.

    Jitted: PPO calls this eagerly once per iteration, and an eager
    ``lax.scan`` over a fresh ``step`` closure is a new program every
    call — one recompile per training iteration (found by the
    RecompileSentinel in chip_smoke.py's steady window).
    """
    rewards = rewards.astype(jnp.float32) * mask
    values = values.astype(jnp.float32) * mask
    next_values = jnp.concatenate(
        [values[:, 1:], jnp.zeros_like(values[:, :1])], axis=1)
    next_mask = jnp.concatenate(
        [mask[:, 1:], jnp.zeros_like(mask[:, :1])], axis=1)
    deltas = rewards + gamma * next_values * next_mask - values

    def step(carry, xs):
        delta_t, m_t = xs
        adv = delta_t + gamma * lam * carry * m_t
        return adv, adv

    # scan over time reversed; carry is adv[t+1] gated by next-token mask
    _, adv_rev = jax.lax.scan(
        step, jnp.zeros(rewards.shape[0], jnp.float32),
        (deltas.T[::-1], next_mask.T[::-1]))
    advantages = adv_rev[::-1].T * mask
    returns = (advantages + values) * mask
    return advantages, returns


def rloo_advantages(scores: jnp.ndarray, group_size: int) -> jnp.ndarray:
    """Leave-one-out baseline (RLOO): scores [B] with B = n_prompts*k,
    rows grouped k-consecutive per prompt.  adv_i = r_i - mean(r_{j≠i})."""
    k = group_size
    groups = scores.reshape(-1, k)
    baseline = (jnp.sum(groups, axis=1, keepdims=True) - groups) / (k - 1)
    return (groups - baseline).reshape(-1)


def grpo_advantages(scores: jnp.ndarray, group_size: int,
                    normalize_std: bool = True,
                    eps: float = 1e-4) -> jnp.ndarray:
    """Group-relative advantages (GRPO): center by group mean, optionally
    normalize by group std ("dr_grpo" skips the std division)."""
    groups = scores.reshape(-1, group_size)
    centered = groups - jnp.mean(groups, axis=1, keepdims=True)
    if normalize_std:
        centered = centered / (jnp.std(groups, axis=1, keepdims=True) + eps)
    return centered.reshape(-1)
