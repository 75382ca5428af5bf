"""Plain reference: LFM2-MoE's (``model_type: lfm2_moe``) forward pass in
float32 ``jax.numpy``.

Written from the published configuration keys (the catalog row of
LiquidAI/LFM2-8B-A1B) and the equations of ISSUE 49; every remembered or
chosen point is listed in the configuration file under ``assumed``.

Block ``l``: ``h = x + Op_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``
(``operator_norm``, ``ffn_norm``, eps ``norm_eps``).  Behind the last
block ``embedding_norm`` (RMSNorm), then the head: the embedding matrix
transposed (tied).

- **conv** (``layer_types`` entry ``conv``) on ``u = RMSNorm(x)``:
  ``[b | c | z] = u W_in`` (hidden -> 3 hidden, no bias, the thirds in
  this order); ``s_t = b_t * z_t``; ``v_t = sum_{j=0..taps-1} w_j *
  s_{t - taps + 1 + j}``, depthwise, causal, ``conv_L_cache`` taps, no
  bias, ``s`` zero before the sequence, NO activation; ``Op = (c * v)
  W_out``.
- **full_attention**: q, k, v without bias (``num_attention_heads``,
  ``num_key_value_heads`` heads of hidden / heads); an RMSNorm over each
  head's width of q and of k (a learned scale all heads share, eps
  ``norm_eps``); a half-split rotary embedding over the whole head
  (``rope_theta``); causal softmax at scale ``head^-1/2``, query head h
  reading key-value head ``h // (Hq / Hkv)``; ``W_o``.
- **FFN**: the first ``num_dense_layers`` layers ``W_2 (silu(W_1 x) *
  W_3 x)`` at ``intermediate_size``; the others ``p = sigmoid(x W_g)``
  (all published experts), the ``num_experts_per_tok`` largest of ``p +
  bias`` (``use_expert_bias``), ``g_e = routed_scaling_factor * p_e /
  (sum_sel p + 1e-6)`` (``norm_topk_prob``), ``sum_sel g_e W_2e
  (silu(W_1e x) * W_3e x)`` at ``moe_intermediate_size``; no shared
  expert; the bias selects and never gates.

The convolution runs token by token (``reference_kimi_linear.short_conv``:
a ``lax.scan`` over the positions of one sequence), the attention matrix
is materialised whole, every HELD expert is computed for every token
with gates zero where it is not selected.  No kernel, no cache, no
chunking, no batching.  It imports nothing from ``orion_tpu``; the
norm, the embedding and the logprobs are ``reference_dsv3.py``'s, the
rotation ``reference_keye_dsa.py``'s, the selection probe
``reference_nemotron_h.py``'s (the same router: a sigmoid and a bias),
beside this file.  Every matrix product runs under
``jax.default_matmul_precision("highest")``.

**The share.**  ``shape`` is the configuration file: the published
keys, ``num_experts`` counting the experts HELD here (``source_values``
has the published count, the router's width) and ``held = (offset,
count)`` saying which.  What the absent experts would add is left out.
Given every expert it is the whole model.

Departures from the published code, none of them mathematics:

- ``mask`` [L] bool: the convolution's window skips a position that
  holds no token (right padding; such a position's own output is
  nobody's);
- the taps arrive as ``[taps, channels]``, the tap that multiplies the
  current token last; an expert's ``W_1 | W_3`` as one ``[D, 2 I]``.

Arguments that compute what this model is NOT, for comparisons that ask
which of two a program computes: ``gates="biased"`` (the bias enters
the gates), ``taps="reversed"``, ``c_gate=False`` (``Op = v W_out``),
``rotary=False``, ``qk_norm=False``.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp

HIGHEST = "highest"
GATE_EPS = 1e-6          # the published denominator: sum_sel p + 1e-6


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location(
        "orionbench_" + name, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


dsv3 = _sibling("reference_dsv3")
rms_norm, embed, swiglu = dsv3.rms_norm, dsv3.embed, dsv3.swiglu
next_token_logprobs = dsv3.next_token_logprobs
rotate = _sibling("reference_keye_dsa").rotate
short_conv = _sibling("reference_kimi_linear").short_conv
selection_probe = _sibling("reference_nemotron_h").selection_probe


def layer_kinds(shape: dict) -> list:
    """[(``conv`` | ``full_attention``, ``dense`` | ``experts``)] of the
    layers held here: the first ``num_hidden_layers`` entries of
    ``layer_types``, the first ``num_dense_layers`` of them dense."""
    dense = int(shape["num_dense_layers"])
    return [(t, "dense" if i < dense else "experts") for i, t in
            enumerate(shape["layer_types"][:int(shape["num_hidden_layers"])])]


def head_dim(shape: dict) -> int:
    return int(shape["hidden_size"]) // int(shape["num_attention_heads"])


def conv(u, w, mask, taps: str = "published", c_gate: bool = True):
    """The gated short convolution on u [L, hidden], normed."""
    b, c, z = jnp.split(u @ w["w_in"], 3, axis=-1)
    weight = w["conv_w"][::-1] if taps == "reversed" else w["conv_w"]
    v = short_conv(b * z, weight, mask)
    return (c * v if c_gate else v) @ w["w_out"]


def attention(u, w, shape, rotary: bool = True, qk_norm: bool = True):
    """Grouped-query attention on u [L, hidden], normed."""
    L = u.shape[0]
    Hq, Hkv = (int(shape["num_attention_heads"]),
               int(shape["num_key_value_heads"]))
    d, eps = head_dim(shape), float(shape["norm_eps"])
    q = (u @ w["wq"]).reshape(L, Hq, d)
    k = (u @ w["wk"]).reshape(L, Hkv, d)
    v = (u @ w["wv"]).reshape(L, Hkv, d)
    if qk_norm:
        q, k = rms_norm(q, w["q_g"], eps), rms_norm(k, w["k_g"], eps)
    pos = jnp.arange(L)
    if rotary:
        q, k = (rotate(t, pos, float(shape["rope_theta"])) for t in (q, k))
    k, v = (jnp.repeat(t, Hq // Hkv, axis=1) for t in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = pos[None, :, None] >= pos[None, None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(L, Hq * d) @ w["wo"]


def route(x, w, shape, selected=None, gates: str = "published"):
    """(selected [L, k] over all experts, gates [L, k]).  ``selected``
    given: those experts instead of the k largest of ``p + bias``."""
    k = int(shape["num_experts_per_tok"])
    p = jax.nn.sigmoid(x @ w["w_router"])
    biased = p + w["router_bias"][None, :]
    if selected is None:
        _, selected = jax.lax.top_k(biased, k)
    chosen = jnp.take_along_axis(biased if gates == "biased" else p,
                                 selected, axis=-1)
    return selected, float(shape["routed_scaling_factor"]) * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + GATE_EPS)


def expert_ffn(x, w, shape, held, selected=None, probe: bool = False,
               gates: str = "published"):
    """The expert layer on x [L, hidden], normed, for the routed experts
    ``held = (offset, count)``: ``w["e_gate_up"]`` [count, D, 2 I] and
    ``w["e_down"]`` [count, I, D] are theirs.  ``probe``: also
    ``reference_nemotron_h.selection_probe``'s reading of ``selected``."""
    offset, count = held
    idx, g = route(x, w, shape, selected, gates)
    every = jax.vmap(lambda gu, dn: swiglu(x, gu, dn), out_axes=1)(
        w["e_gate_up"], w["e_down"])                       # [L, count, D]
    weight = jnp.sum(
        jax.nn.one_hot(idx - offset, count, dtype=jnp.float32)
        * g[..., None], axis=1)       # one_hot of an index outside is zero
    out = jnp.einsum("lhd,lh->ld", every, weight)
    return (out, selection_probe(x, w, idx)) if probe else out


def layer(x, w, shape, kind, held=None, mask=None, selected=None,
          probe: bool = False, gates: str = "published",
          taps: str = "published", c_gate: bool = True, rotary: bool = True,
          qk_norm: bool = True):
    """One block on x [L, hidden], float32; ``kind`` its entry of
    :func:`layer_kinds`.  Returns ``(y, probe info | None)`` under
    ``probe``, else ``y``."""
    if mask is None:
        mask = jnp.ones((x.shape[0],), bool)
    eps, info = float(shape["norm_eps"]), None
    with jax.default_matmul_precision(HIGHEST):
        u = rms_norm(x, w["n1_g"], eps)
        h = x + (conv(u, w, mask, taps, c_gate) if kind[0] == "conv"
                 else attention(u, w, shape, rotary, qk_norm))
        f = rms_norm(h, w["n2_g"], eps)
        if kind[1] == "dense":
            out = swiglu(f, w["gate_up"], w["down"])
        else:
            out = expert_ffn(f, w, shape, held, selected, probe, gates)
            if probe:
                out, info = out
    return (h + out, info) if probe else h + out


def head(x, w, shape):
    """``embedding_norm`` and the tied head: logits [L, V]."""
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, w["nf_g"], float(shape["norm_eps"])) \
            @ w["embed"].astype(jnp.float32).T


def forward(weights, ids, shape, held, mask=None, **variant):
    """weights: {"embed", "layers": [one dict a layer], "nf_g"}, float32.
    ids: [L].  Logits [L, V]."""
    x = embed(ids, weights)
    for kind, w in zip(layer_kinds(shape), weights["layers"]):
        x = layer(x, w, shape, kind, held, mask, **variant)
    return head(x, weights, shape)


def loss(weights, ids, shape, held, mask=None):
    """Mean next-token negative log-likelihood over the positions that
    hold a token (for the tests' gradient comparison)."""
    lp = next_token_logprobs(forward(weights, ids, shape, held, mask), ids)
    m = jnp.ones_like(lp) if mask is None else mask[1:].astype(lp.dtype)
    return -jnp.sum(lp * m) / jnp.sum(m)
