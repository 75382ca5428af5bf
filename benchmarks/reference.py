"""Plain reference: the GPT-NeoX forward pass in float32 ``jax.numpy``.

Written from the published description of the architecture (Black et
al., "GPT-NeoX-20B", 2022, section 2; the Pythia suite uses it
unchanged): token embedding, then per layer LayerNorm -> attention with
rotary position embedding on the first ``rotary_pct`` of each head's
features (the non-interleaved "rotate half" pairing of feature i with
i + rotary_dim/2), LayerNorm -> MLP with exact GELU, the two added to
the residual stream in parallel (``use_parallel_residual``) or one after
the other; a final LayerNorm and an untied output projection.

No kernel, no cache, no scan, no batching: one sequence at a time, the
whole causal attention matrix materialised.  It imports nothing from
``orion_tpu``.  Every matrix product runs under
``jax.default_matmul_precision("highest")`` — on a TPU a float32
product is otherwise computed in bfloat16 passes.

Departure from the published code, which is about layout and not
mathematics: the published model stores query, key and value as one
fused matrix with the three interleaved per head; here they are three
matrices ``wq``, ``wk``, ``wv`` (the same numbers, split).

``shape`` is a dict with the published keys ``hidden_size``,
``num_attention_heads``, ``rotary_pct``, ``rotary_emb_base``,
``layer_norm_eps`` and ``use_parallel_residual``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def rotate(x, positions, rotary_dim, base):
    """x: [L, heads, head_dim].  Rotates the first ``rotary_dim``
    features of every head by the position's angles."""
    half = rotary_dim // 2
    inv_freq = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32)
                               * 2.0 / rotary_dim))
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rotary_dim:]], axis=-1)


def attention(x, w, positions, shape):
    L, hidden = x.shape
    heads = int(shape["num_attention_heads"])
    d = hidden // heads
    rotary_dim = int(d * float(shape["rotary_pct"]))
    base = float(shape["rotary_emb_base"])
    q = (x @ w["wq"] + w["bq"]).reshape(L, heads, d)
    k = (x @ w["wk"] + w["bk"]).reshape(L, heads, d)
    v = (x @ w["wv"] + w["bv"]).reshape(L, heads, d)
    q = rotate(q, positions, rotary_dim, base)
    k = rotate(k, positions, rotary_dim, base)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = positions[None, :, None] >= positions[None, None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(L, hidden)
    return out @ w["wo"] + w["bo"]


def mlp(x, w):
    h = jax.nn.gelu(x @ w["w_in"] + w["b_in"], approximate=False)
    return h @ w["w_out"] + w["b_out"]


def layer(x, w, positions, shape):
    """One transformer layer on x [L, hidden], all float32."""
    eps = float(shape["layer_norm_eps"])
    with jax.default_matmul_precision(HIGHEST):
        a = attention(layer_norm(x, w["ln1_g"], w["ln1_b"], eps), w,
                      positions, shape)
        if shape["use_parallel_residual"]:
            m = mlp(layer_norm(x, w["ln2_g"], w["ln2_b"], eps), w)
            return x + a + m
        x = x + a
        return x + mlp(layer_norm(x, w["ln2_g"], w["ln2_b"], eps), w)


def embed(ids, w):
    return w["embed"][ids].astype(jnp.float32)


def head(x, w, shape):
    """Final LayerNorm and the untied output projection: logits
    [L, vocab]."""
    with jax.default_matmul_precision(HIGHEST):
        x = layer_norm(x, w["lnf_g"], w["lnf_b"],
                       float(shape["layer_norm_eps"]))
        return x @ w["w_head"]


def forward(weights, ids, shape):
    """weights: {"embed", "layers": [layer dicts], "lnf_g", "lnf_b",
    "w_head"}, float32.  ids: [L] int.  Returns logits [L, vocab]."""
    positions = jnp.arange(ids.shape[0])
    x = embed(ids, weights)
    for w in weights["layers"]:
        x = layer(x, w, positions, shape)
    return head(x, weights, shape)


def next_token_logprobs(logits, ids):
    """log p(ids[t+1] | ids[:t+1]) for t = 0 .. L-2, at temperature 1."""
    logp = jax.nn.log_softmax(logits[:-1].astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, ids[1:, None], axis=-1)[:, 0]
