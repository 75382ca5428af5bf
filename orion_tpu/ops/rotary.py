"""Rotary position embeddings.

Supports full rotary (Llama) and partial rotary (GPT-NeoX ``rotary_pct``,
e.g. 0.25 for Pythia).  Uses the non-interleaved "rotate_half" layout both
model families share in their canonical implementations.
"""

from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp


def rope_table(rotary_dim: int, theta: float,
               params: Optional[dict] = None) -> tuple:
    """(inv_freq [rotary_dim / 2] float32 or None, factor) of one layer
    type's rotary parameters (``ModelConfig.rope_parameters``' entry,
    under the published keys): None and 1.0 for no entry or
    ``rope_type: default`` (:func:`rope_cos_sin`'s own table at
    ``theta``, the entry's ``rope_theta`` as :func:`apply_rotary` reads
    it), else ``rope_type: yarn`` as ``transformers`` computes it:
    ``pos_i = theta^(2i / d)``; the correction range ``low =
    max(floor(corr(beta_fast)), 0)``, ``high = min(ceil(corr(beta_slow)),
    d - 1)`` with ``corr(n) = d ln(original_max_position_embeddings /
    (2 pi n)) / (2 ln theta)``; ``ramp_i = clip((i - low) / (high - low),
    0, 1)``; ``inv_freq_i = ramp_i / (factor pos_i) + (1 - ramp_i) /
    pos_i`` (the fast features keep their frequency, the slow ones are
    interpolated); ``attention_factor`` (absent: ``0.1 ln factor + 1``)
    multiplies cos and sin."""
    if not params or params.get("rope_type", "default") == "default":
        return None, 1.0
    d, base = rotary_dim, float(theta)
    factor = float(params["factor"])

    def corr(rotations):
        return (d * math.log(params["original_max_position_embeddings"]
                             / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(params["beta_fast"])), 0)
    high = min(math.ceil(corr(params["beta_slow"])), d - 1)
    if low == high:
        high += 0.001                      # as transformers: no 0 / 0
    pos = base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    inv_freq = ramp / (factor * pos) + (1 - ramp) / pos
    return inv_freq, float(params.get("attention_factor",
                                      0.1 * math.log(factor) + 1.0))


def rope_cos_sin(positions: jnp.ndarray, rotary_dim: int, theta: float,
                 inv_freq: Optional[jnp.ndarray] = None,
                 factor: float = 1.0) -> tuple:
    """cos/sin tables for integer positions.

    positions: [B, L] int32 → cos, sin: [B, L, rotary_dim] float32.
    ``inv_freq`` [rotary_dim / 2]: the frequencies (none: the default
    table at ``theta``); ``factor`` multiplies cos and sin
    (:func:`rope_table`).
    """
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (
            jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [B,L,rd/2]
    emb = jnp.concatenate([angles, angles], axis=-1)  # [B,L,rd]
    if factor != 1.0:
        return jnp.cos(emb) * factor, jnp.sin(emb) * factor
    return jnp.cos(emb), jnp.sin(emb)


def _rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rotary(q: jnp.ndarray, k: jnp.ndarray, positions: jnp.ndarray,
                 rotary_dim: int, theta: float,
                 params: Optional[dict] = None) -> tuple:
    """Apply (possibly partial) rotary embedding.

    q: [B, L, Hq, D], k: [B, L, Hk, D], positions: [B, L].
    Only the first ``rotary_dim`` features of each head are rotated.
    ``params``: the layer type's rotary parameters (:func:`rope_table`;
    none: the default table at ``theta``).
    """
    theta = (params or {}).get("rope_theta", theta)
    cos, sin = rope_cos_sin(positions, rotary_dim, theta,
                            *rope_table(rotary_dim, theta, params))  # [B,L,rd]
    cos = cos[:, :, None, :]  # broadcast over heads
    sin = sin[:, :, None, :]

    def rot(x):
        xr, xp = x[..., :rotary_dim], x[..., rotary_dim:]
        xr32 = xr.astype(jnp.float32)
        xr = (xr32 * cos + _rotate_half(xr32) * sin).astype(x.dtype)
        return jnp.concatenate([xr, xp], axis=-1) if xp.shape[-1] else xr

    return rot(q), rot(k)
