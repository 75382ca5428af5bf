"""Plain reference: the Kimi-Linear block's forward pass in float32
``jax.numpy``, and the chip's share of it.

Written from the published configuration keys (``model_type:
kimi_linear``; the catalog row of Kimi-Linear-48B-A3B-Instruct) and the
published modelling code's mathematics AS REMEMBERED (there is no
network here; every remembered point is listed in the configuration
file under ``assumed``):

- every norm is RMSNorm with a learned scale, no bias anywhere; block:
  ``a = x + Mixer(N1(x))``, ``y = a + FFN(N2(a))``; a final RMSNorm and
  an untied output projection;
- the mixer of layer ``i`` (1-based) is KDA where ``i`` is in
  ``linear_attn_config.kda_layers`` and latent attention where it is in
  ``full_attn_layers``;
- **KDA** (``num_heads`` heads of ``head_dim`` d, here 32 of 128):
  ``q~, k~, v~ = x W_q, x W_k, x W_v``; each through its own depthwise
  causal convolution of ``short_conv_kernel_size`` taps over the
  sequence, then SiLU; per head ``q = l2norm(q~) / sqrt(d)``, ``k =
  l2norm(k~)``, ``v = v~``.  Decay per head and key channel ``g_t =
  -exp(A_log_h) * softplus((x W_fa W_fb)_h + dt_bias_h)``, ``alpha_t =
  exp(g_t)``; step ``beta_t = sigmoid(x W_b)_h``.  State ``S`` [d, d],
  zero before the first token: ``S' = Diag(alpha_t) S``; ``S = S' +
  beta_t k_t (v_t - S'^T k_t)^T``; ``o_t = S^T q_t``.  Output ``W_o
  concat_h(RMSNorm_d(o_t; w) * sigmoid((x W_ga W_gb)_h))``, the norm's
  weight of d shared by the heads.  No position enters it;
- **latent attention** as in ``reference_dsv3`` (``q_lora_rank: null``)
  with ``mla_use_nope``: no rotation, the ``qk_rope_head_dim`` features
  all heads share enter the scores as projected; scores over
  ``sqrt(nope + rope)``, causal softmax;
- FFN of the first ``first_k_dense_replace`` layers: SwiGLU of
  ``intermediate_size``; of the others the sigmoid-routed expert layer
  of ``reference_dsv3`` at this model's numbers (``num_experts``
  outputs, ``num_experts_per_token`` selected over ONE group with the
  correction bias, gates renormalised and times
  ``routed_scaling_factor``, ``num_shared_experts`` shared).

The recurrence runs token by token (``lax.scan`` over the positions of
one sequence, the body the three lines above), the attention matrix is
materialised whole, every held expert is computed for every token.  No
kernel, no cache, no chunking, no batching.  It imports nothing from
``orion_tpu``; the expert layer, the norms and the head are those of
``reference_dsv3.py`` beside this file.  Every matrix product runs
under ``jax.default_matmul_precision("highest")``.

Departures from the published code, none of them mathematics: those of
``reference_dsv3`` (the chip's share ``held``, an expert's gate and up
as one matrix, the bias given and fixed, ``selected`` handed in), and

- ``mask`` [L] bool: a position that holds no token leaves the
  recurrence untouched (``alpha = 1``, ``beta = 0``) and the
  convolution's window skips it, so that the state after a right-padded
  prompt is the state after its last token;
- the three convolutions' weights arrive as ``[taps, channels]``, the
  tap that multiplies the current token last.

``shape`` is the configuration file: the published keys, among them the
nested ``linear_attn_config``.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location(
        "orionbench_" + name, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


dsv3 = _sibling("reference_dsv3")
rms_norm, swiglu = dsv3.rms_norm, dsv3.swiglu
embed, head = dsv3.embed, dsv3.head
next_token_logprobs = dsv3.next_token_logprobs


def moe_shape(shape: dict) -> dict:
    """This model's router numbers under the keys ``reference_dsv3``
    reads."""
    return {"num_experts_per_tok": shape["num_experts_per_token"],
            "routed_scaling_factor": shape["routed_scaling_factor"],
            "rms_norm_eps": shape["rms_norm_eps"]}


def mixer_kind(shape: dict, i: int) -> str:
    """"kda" or "latent" for layer ``i`` (0-based here, 1-based in the
    published lists)."""
    lin = shape["linear_attn_config"]
    if i + 1 in lin["kda_layers"]:
        return "kda"
    if i + 1 in lin["full_attn_layers"]:
        return "latent"
    raise ValueError(f"layer {i + 1} is in neither published list")


def short_conv(x, weight, mask):
    """Depthwise causal convolution over the positions that hold a
    token.  x [L, C]; weight [taps, C], the current token's tap last;
    mask [L] bool.  Token by token: the window holds the last ``taps -
    1`` inputs of real tokens (zeros before the first) and does not
    move at a position that holds none."""
    taps = weight.shape[0]

    def step(window, inp):
        x_t, m_t = inp
        full = jnp.concatenate([window, x_t[None]], axis=0)   # [taps, C]
        y_t = jnp.sum(full * weight, axis=0)
        return jnp.where(m_t, full[1:], window), y_t

    _, y = jax.lax.scan(step, jnp.zeros((taps - 1, x.shape[1]), x.dtype),
                        (x, mask))
    return y


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


def delta_rule(q, k, v, g, beta, mask, state=None):
    """The recurrence, token by token.  q, k [L, H, dk]; v [L, H, dv];
    g [L, H, dk] (log decay, <= 0); beta [L, H]; mask [L] bool.
    Returns (o [L, H, dv], the state after the last position
    [H, dk, dv])."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    if state is None:
        state = jnp.zeros((H, dk, dv), jnp.float32)

    def step(S, inp):
        q_t, k_t, v_t, g_t, b_t, m_t = inp
        alpha = jnp.where(m_t, jnp.exp(g_t), 1.0)               # [H, dk]
        b_t = jnp.where(m_t, b_t, 0.0)                          # [H]
        S = alpha[:, :, None] * S
        pred = jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + b_t[:, None, None] * k_t[:, :, None] \
            * (v_t - pred)[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta, mask))
    return o, state


def kda(h, w, shape, mask):
    """The KDA mixer on h [L, hidden] (normed)."""
    lin = shape["linear_attn_config"]
    H, d = int(lin["num_heads"]), int(lin["head_dim"])
    L = h.shape[0]

    def branch(name):
        return jax.nn.silu(short_conv(h @ w["w" + name], w["conv_" + name],
                                      mask)).reshape(L, H, d)

    q = l2norm(branch("q")) / jnp.sqrt(jnp.float32(d))
    k = l2norm(branch("k"))
    v = branch("v")
    g = -jnp.exp(w["A_log"])[None, :, None] * jax.nn.softplus(
        (h @ w["w_fa"] @ w["w_fb"] + w["dt_bias"]).reshape(L, H, d))
    beta = jax.nn.sigmoid(h @ w["w_b"])                          # [L, H]
    o, _ = delta_rule(q, k, v, g, beta, mask)
    gate = jax.nn.sigmoid((h @ w["w_ga"] @ w["w_gb"]).reshape(L, H, d))
    o = rms_norm(o, w["o_norm_g"], float(shape["rms_norm_eps"])) * gate
    return o.reshape(L, H * d) @ w["wo"]


def latent_nope(h, w, shape):
    """Latent attention without rotation (``mla_use_nope``)."""
    L = h.shape[0]
    heads = int(shape["num_attention_heads"])
    rank = int(shape["kv_lora_rank"])
    dn, dr, dv = (int(shape["qk_nope_head_dim"]),
                  int(shape["qk_rope_head_dim"]), int(shape["v_head_dim"]))
    q = (h @ w["wq"]).reshape(L, heads, dn + dr)
    kva = h @ w["wkva"]
    c = rms_norm(kva[:, :rank], w["kva_g"], float(shape["rms_norm_eps"]))
    kv = (c @ w["wkvb"]).reshape(L, heads, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(kva[:, None, rank:],
                                        (L, heads, dr))], axis=-1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.float32(dn + dr))
    pos = jnp.arange(L)
    causal = pos[None, :, None] >= pos[None, None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, kv[..., dn:])
    return out.reshape(L, heads * dv) @ w["wo"]


def layer(x, w, shape, kind: str, held=None, selected=None,
          probe: bool = False, mask=None):
    """One block on x [L, hidden], float32.  ``kind``: "kda" or
    "latent" (or "latent_rotated": what this model is NOT, latent
    attention with ``reference_dsv3``'s rotation, for a comparison that
    asks which of the two a program computes); ``held`` None: a leading
    dense layer (``w["gate_up"]``, ``w["down"]``).  Returns ``(y, probe
    info | None)`` under ``probe``, else ``y``."""
    eps = float(shape["rms_norm_eps"])
    if mask is None:
        mask = jnp.ones((x.shape[0],), bool)
    with jax.default_matmul_precision(HIGHEST):
        h = rms_norm(x, w["n1_g"], eps)
        if kind == "kda":
            a = x + kda(h, w, shape, mask)
        elif kind == "latent_rotated":
            a = x + dsv3.attention(h, w, jnp.arange(x.shape[0]), shape)
        else:
            a = x + latent_nope(h, w, shape)
        z = rms_norm(a, w["n2_g"], eps)
        if held is None:
            y = a + swiglu(z, w["gate_up"], w["down"])
            return (y, None) if probe else y
        out = dsv3.expert_ffn(z, w, moe_shape(shape), held, selected,
                              probe=probe)
        if not probe:
            return a + out
        return a + out[0], out[1]


def forward(weights, ids, shape, held, mask=None):
    """weights: {"embed", "layers": [layer dicts], "nf_g", "w_head"},
    float32; layer ``i`` is of ``mixer_kind(shape, i)`` and dense where
    it has ``gate_up``.  ids: [L].  Logits [L, V]."""
    x = embed(ids, weights)
    for i, w in enumerate(weights["layers"]):
        x = layer(x, w, shape, mixer_kind(shape, i),
                  None if "gate_up" in w else held, mask=mask)
    return head(x, weights, shape)
