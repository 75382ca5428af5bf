"""Plain reference: the Olmo-Hybrid block's forward pass in float32
``jax.numpy``.

Written from the published configuration keys (``model_type:
olmo_hybrid``; the catalog row of allenai/Olmo-Hybrid-7B) and the
family's modelling code AS REMEMBERED (there is no network here; every
remembered or chosen point is listed in the configuration file under
``assumed``):

- block (the OLMo 2 / 3 order): ``h = x + N_a(Mixer(x))``, ``y = h +
  N_f(MLP(h))``, ``N`` an RMSNorm with a learned scale; no norm before a
  sublayer; a final RMSNorm and an untied output projection; ``MLP(h) =
  W_down(silu(W_gate h) * W_up h)``; no bias anywhere;
- the mixer of layer ``i`` (0-based) is ``layer_types[i]``;
- **full_attention**: ``q = RMSNorm(x W_q)``, ``k = RMSNorm(x W_k)``,
  each one norm over the WHOLE projection before the split into
  ``num_attention_heads`` heads of ``hidden_size / heads``; ``v = x
  W_v``; ``softmax(q k^T / sqrt(d) + causal) v``; ``W_o``.  Nothing is
  rotated (``rope_parameters.rope_theta`` is null).  ``rotated=True``
  computes what this model is NOT, the same layer with a half-split
  rotary embedding over the whole head at ``theta`` 500 000, for a
  comparison that asks which of the two a program computes;
- **linear_attention** (Gated DeltaNet, ``linear_num_key_heads`` =
  ``linear_num_value_heads`` heads H, key size dk, value size dv):
  ``q~, k~, v~ = x W_q, x W_k, x W_v`` (H dk, H dk, H dv wide), each
  through its own depthwise causal convolution of
  ``linear_conv_kernel_dim`` taps over the sequence, then SiLU; per
  head ``q = l2norm(q~) / sqrt(dk)``, ``k = l2norm(k~)``, ``v = v~``.
  One log decay a head ``g_t = -exp(A_log_h) * softplus((x W_a)_h +
  dt_bias_h)``, ``alpha_t = exp(g_t)``; step ``beta_t = 2 sigmoid((x
  W_b)_h)`` (``linear_allow_neg_eigval``: the transition's eigenvalue
  along ``k`` is ``1 - beta`` and reaches -1; else ``sigmoid``).  State
  ``S`` [dk, dv], zero before the first token: ``S' = alpha_t S``; ``S =
  S' + beta_t k_t (v_t - S'^T k_t)^T``; ``o_t = S^T q_t``.  Output ``W_o
  concat_h(RMSNorm_dv(o_t; w) * silu((x W_z)_h))``, the norm's weight of
  dv shared by the heads.  No position enters it.

The recurrence runs token by token (``lax.scan`` over the positions of
one sequence, the body the three lines above), the attention matrix is
materialised whole.  No kernel, no cache, no chunking, no batching.  It
imports nothing from ``orion_tpu``; the norms, the token-by-token
convolution over the positions that hold a token, the embedding, the
head and the logprobs are those of ``reference_kimi_linear.py`` beside
this file (what is this model's own is here: the recurrence with one
decay a head, the mixers, the block's order).  Every matrix product runs
under ``jax.default_matmul_precision("highest")``.

Departures from the published code, none of them mathematics:

- ``mask`` [L] bool: a position that holds no token leaves the
  recurrence untouched (``alpha = 1``, ``beta = 0``) and the
  convolution's window skips it, so that the state after a right-padded
  prompt is the state after its last token (attention needs no mask: a
  real token never attends to a later position);
- the three convolutions' weights arrive as ``[taps, channels]``, the
  tap that multiplies the current token last; the MLP's gate and up as
  two matrices.

``shape`` is the configuration file: the published keys.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp

HIGHEST = "highest"
ROTATED_THETA = 500000.0


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location(
        "orionbench_" + name, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


kimi = _sibling("reference_kimi_linear")
rms_norm, l2norm, short_conv = kimi.rms_norm, kimi.l2norm, kimi.short_conv
embed, head = kimi.embed, kimi.head
next_token_logprobs = kimi.next_token_logprobs


def mixer_kind(shape: dict, i: int) -> str:
    """``"linear_attention"`` or ``"full_attention"`` for layer ``i``
    (0-based), by the published list."""
    kind = shape["layer_types"][i]
    if kind not in ("linear_attention", "full_attention"):
        raise ValueError(f"layer {i}: unknown layer type {kind!r}")
    return kind


def delta_rule(q, k, v, g, beta, mask, state=None):
    """The recurrence, token by token.  q, k [L, H, dk]; v [L, H, dv];
    g [L, H] (log decay, <= 0, one a head); beta [L, H]; mask [L] bool.
    Returns (o [L, H, dv], the state after the last position
    [H, dk, dv])."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    if state is None:
        state = jnp.zeros((H, dk, dv), jnp.float32)

    def step(S, inp):
        q_t, k_t, v_t, g_t, b_t, m_t = inp
        alpha = jnp.where(m_t, jnp.exp(g_t), 1.0)               # [H]
        b_t = jnp.where(m_t, b_t, 0.0)                          # [H]
        S = alpha[:, None, None] * S
        pred = jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + b_t[:, None, None] * k_t[:, :, None] \
            * (v_t - pred)[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta, mask))
    return o, state


def gated_delta_net(x, w, shape, mask):
    """The linear-attention mixer on x [L, hidden] (not normed: the
    block norms after)."""
    H = int(shape["linear_num_key_heads"])
    if int(shape["linear_num_value_heads"]) != H:
        raise ValueError("value heads that share a key head are not "
                         "written here")
    dk, dv = (int(shape["linear_key_head_dim"]),
              int(shape["linear_value_head_dim"]))
    L = x.shape[0]

    def branch(name, d):
        return jax.nn.silu(short_conv(x @ w["w" + name], w["conv_" + name],
                                      mask)).reshape(L, H, d)

    q = l2norm(branch("q", dk)) / jnp.sqrt(jnp.float32(dk))
    k = l2norm(branch("k", dk))
    v = branch("v", dv)
    g = -jnp.exp(w["A_log"])[None, :] * jax.nn.softplus(
        x @ w["w_a"] + w["dt_bias"])                             # [L, H]
    top = 2.0 if shape["linear_allow_neg_eigval"] else 1.0
    beta = top * jax.nn.sigmoid(x @ w["w_b"])                    # [L, H]
    o, _ = delta_rule(q, k, v, g, beta, mask)
    gate = jax.nn.silu((x @ w["w_z"]).reshape(L, H, dv))
    o = rms_norm(o, w["o_norm_g"], float(shape["rms_norm_eps"])) * gate
    return o.reshape(L, H * dv) @ w["wo"]


def rotate_half(x, positions, theta):
    """x [L, heads, d]: the half-split rotary embedding over the whole
    head (feature j pairs with j + d / 2)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(d // 2, dtype=jnp.float32)
                                * 2.0 / d))
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def full_attention(x, w, shape, rotated: bool = False):
    """Multi-head attention on x [L, hidden], whole."""
    L = x.shape[0]
    heads = int(shape["num_attention_heads"])
    d = int(shape["hidden_size"]) // heads
    eps = float(shape["rms_norm_eps"])
    q = rms_norm(x @ w["wq"], w["q_norm_g"], eps).reshape(L, heads, d)
    k = rms_norm(x @ w["wk"], w["k_norm_g"], eps).reshape(L, heads, d)
    v = (x @ w["wv"]).reshape(L, heads, d)
    if rotated:
        pos = jnp.arange(L)
        q, k = (rotate_half(t, pos, ROTATED_THETA) for t in (q, k))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    pos = jnp.arange(L)
    causal = pos[None, :, None] >= pos[None, None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v)
    return out.reshape(L, heads * d) @ w["wo"]


def swiglu(h, w):
    return (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def layer(x, w, shape, kind: str, mask=None, rotated: bool = False):
    """One block on x [L, hidden], float32."""
    eps = float(shape["rms_norm_eps"])
    if mask is None:
        mask = jnp.ones((x.shape[0],), bool)
    with jax.default_matmul_precision(HIGHEST):
        if kind == "linear_attention":
            a = gated_delta_net(x, w, shape, mask)
        else:
            a = full_attention(x, w, shape, rotated)
        h = x + rms_norm(a, w["na_g"], eps)
        return h + rms_norm(swiglu(h, w), w["nf_g"], eps)


def forward(weights, ids, shape, mask=None, rotated: bool = False):
    """weights: {"embed", "layers": [layer dicts], "nf_g", "w_head"},
    float32; layer ``i`` is of ``mixer_kind(shape, i)``.  ids: [L].
    Logits [L, V]."""
    x = embed(ids, weights)
    for i, w in enumerate(weights["layers"]):
        x = layer(x, w, shape, mixer_kind(shape, i), mask, rotated)
    return head(x, weights, shape)
