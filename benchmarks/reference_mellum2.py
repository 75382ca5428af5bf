"""Plain reference of Mellum2-12B-A2.5B-Instruct (model_type ``mellum``;
the catalog row of the model-configs guide): grouped-query attention
whose layers alternate three sliding-window ones (plain rotary) with one
full one (YaRN rotary), over softmax-routed experts.  Straightforward
``jax.numpy``, float32, ``default_matmul_precision("highest")``; no
kernel, no cache, no batching; one sequence at a time; a ``[L, L]``
boolean mask per layer type.  It imports nothing of the program.

One block on x [L, hidden] of type ``layer_types[l]`` (RMSNorm eps
``rms_norm_eps``, pre-norm):

1. ``h = RMSNorm(x)``; ``q = h Wq`` as [L, heads, 128], ``k = h Wk``,
   ``v = h Wv`` as [L, kv heads, 128], no bias; RMSNorm over the 128 of
   each head of q and of k (one weight of 128 each, all heads).  Query
   head i reads key/value head ``i // (heads / kv heads)``.
2. Rotation of all 128 of a head, half-split pairs (feature i with i +
   64), by ``position * inv_freq_i`` under the layer type's entry of
   ``rope_parameters`` (:func:`rope_table`).  ``default``:
   ``inv_freq_i = theta^(-2i/128)``, cos and sin as they are.  ``yarn``,
   as ``transformers`` computes it: ``pos_i = theta^(2i/128)``;
   ``corr(n) = 128 ln(original_max_position_embeddings / (2 pi n)) / (2
   ln theta)``; ``low = max(floor(corr(beta_fast)), 0)``, ``high =
   min(ceil(corr(beta_slow)), 127)``; ``ramp_i = clip((i - low) / (high
   - low), 0, 1)``; ``inv_freq_i = ramp_i / (factor pos_i) + (1 -
   ramp_i) / pos_i``; cos and sin are multiplied by ``attention_factor``
   (q AND k carry it, so a full layer's scores carry its square).
3. Scores ``q . k / sqrt(128)``, float32 softmax over the keys s that
   the query at position t sees: ``s <= t`` on a ``full_attention``
   layer; ``s <= t`` and ``t - s < sliding_window`` on a
   ``sliding_attention`` layer (itself and the ``sliding_window - 1``
   before it).  ``y = x + concat(o) Wo``.
4. ``z = RMSNorm(y)``; ``p = softmax(z Wr)`` over all experts, float32;
   the ``num_experts_per_tok`` largest; ``g = p_top / sum(p_top)``
   (``norm_topk_prob``); ``out = y + sum over e in (top and held) of g_e
   Wdown_e (silu(z Wgate_e) * (z Wup_e))``, no shared expert, no scaling
   factor, no capacity, no dropped token.  What the absent experts would
   add is left out; a position that holds no token is routed nowhere.

Final RMSNorm, untied head.  ``intermediate_size`` belongs to no layer
(``mlp_layer_types`` is ``sparse`` throughout).

ASSUMED, the published config being silent (the configuration file
lists them under ``assumed``): the per-head q/k RMSNorm before the
rotation (the backbone's keys are the Qwen3-MoE config's, whose
attention has it); the window's edge (``t - s < sliding_window``: the
``transformers`` sliding mask).  LEFT OUT: the multi-token-prediction
head the model card mentions (config.json has no key for it, so no
equation can be written).

For the comparison that decides ``correct``
(``reference_check_mellum2.py``) :func:`layer` can also be computed as
what the model is NOT: ``window_on`` (the layer types the window
applies on), ``window_keys`` (another window than ``sliding_window``),
``rope_of`` ((layer type, the layer type whose rotary entry it takes
instead) pairs), ``attention_factor=False`` (cos and sin as they are on
every layer), ``norm_gates=False`` (``g = p_top``).
"""

from __future__ import annotations

import importlib.util
import math
import os

import jax
import jax.numpy as jnp

HIGHEST = "highest"
SLIDING, FULL = "sliding_attention", "full_attention"


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location(
        "orionbench_" + name, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


dsv3 = _sibling("reference_dsv3")
rms_norm, embed, swiglu, head = (dsv3.rms_norm, dsv3.embed, dsv3.swiglu,
                                 dsv3.head)
next_token_logprobs = dsv3.next_token_logprobs


def layer_types(shape: dict) -> list:
    """The types of the layers held here: the first
    ``num_hidden_layers`` entries of ``layer_types``."""
    return list(shape["layer_types"][:int(shape["num_hidden_layers"])])


def rope_table(params: dict, d: int):
    """(inv_freq [d / 2], the factor on cos and sin) of one entry of
    ``rope_parameters``: module point 2."""
    base = float(params["rope_theta"])
    i = jnp.arange(d // 2, dtype=jnp.float32)
    pos = base ** (2.0 * i / d)
    if params.get("rope_type", "default") == "default":
        return 1.0 / pos, 1.0
    factor = float(params["factor"])

    def corr(rotations):
        return d * math.log(float(params["original_max_position_embeddings"])
                            / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(float(params["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(params["beta_slow"]))), d - 1)
    ramp = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    inv_freq = ramp / (factor * pos) + (1.0 - ramp) / pos
    return inv_freq, float(params.get("attention_factor",
                                      0.1 * math.log(factor) + 1.0))


def rotate(x, positions, inv_freq, factor: float):
    """x [L, heads, d]: feature i pairs with i + d/2 (half-split), both
    rotated by ``position * inv_freq_i``, cos and sin times ``factor``."""
    d = x.shape[-1]
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = factor * jnp.cos(angle)[:, None, :]
    sin = factor * jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def seen(L: int, window=None):
    """[L, L] bool: query t (rows) sees key s iff ``s <= t`` and, under
    ``window``, ``t - s < window``."""
    t, s = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    return (s <= t) if window is None else (s <= t) & (t - s < window)


def attention(u, w, shape, layer_type: str, q_block=None,
              window_on=(SLIDING,), window_keys=None, rope_of=(),
              attention_factor: bool = True):
    """Points 1 to 3 on u = RMSNorm(x) [L, hidden]: concat(o) Wo.
    ``q_block``: the queries a block at a time, so that no [heads, L, L]
    array exists."""
    L = u.shape[0]
    Hq, Hkv = (int(shape["num_attention_heads"]),
               int(shape["num_key_value_heads"]))
    d, eps = int(shape["head_dim"]), float(shape["rms_norm_eps"])
    inv_freq, factor = rope_table(
        shape["rope_parameters"][dict(rope_of).get(layer_type, layer_type)],
        d)
    if not attention_factor:
        factor = 1.0
    pos = jnp.arange(L)
    q = rotate(rms_norm((u @ w["wq"]).reshape(L, Hq, d), w["q_g"], eps),
               pos, inv_freq, factor)
    k = rotate(rms_norm((u @ w["wk"]).reshape(L, Hkv, d), w["k_g"], eps),
               pos, inv_freq, factor)
    v = (u @ w["wv"]).reshape(L, Hkv, d)
    window = None
    if layer_type in window_on:
        window = int(window_keys or shape["sliding_window"])
    mask = seen(L, window)
    g = Hq // Hkv
    n = L if q_block is None or L % q_block else q_block

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, n, axis=0).reshape(
            n, Hkv, g, d)
        rows = jax.lax.dynamic_slice_in_dim(mask, start, n, axis=0)
        scores = jnp.einsum("qhgd,khd->hgqk", qb, k) / jnp.sqrt(
            jnp.float32(d))
        probs = jax.nn.softmax(
            jnp.where(rows[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", probs, v).reshape(n, Hq * d)

    out = jax.lax.map(block, jnp.arange(0, L, n))
    return out.reshape(L, Hq * d) @ w["wo"]


def route(z, w, shape, selected=None, norm_gates: bool = True):
    """(selected [L, k] over all experts, gates [L, k], the router's own
    top-k [L, k]).  ``selected`` given: those experts in place of the k
    largest (gates from the probabilities as ever)."""
    k = int(shape["num_experts_per_tok"])
    probs = jax.nn.softmax(z @ w["w_router"], axis=-1)
    _, own = jax.lax.top_k(probs, k)
    selected = own if selected is None else selected
    chosen = jnp.take_along_axis(probs, selected, axis=-1)
    if norm_gates:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return selected, chosen, own


def expert_ffn(z, w, shape, held, mask=None, selected=None,
               norm_gates: bool = True):
    """Point 4's sum for the experts ``held = (offset, count)``;
    ``w["e_gate_up"]`` [count, D, 2 I] (gate then up) and ``w["e_down"]``
    [count, I, D] are theirs.  Returns (sum [L, D], the router's own
    top-k [L, k])."""
    offset, count = held
    idx, gates, own = route(z, w, shape, selected, norm_gates)
    weight = jnp.sum(
        jax.nn.one_hot(idx - offset, count, dtype=jnp.float32)
        * gates[..., None], axis=1)   # one_hot of an index outside is zero
    if mask is not None:
        weight = weight * mask[:, None]
    out = jnp.zeros_like(z)
    for e in range(count):
        out = out + weight[:, e:e + 1] * swiglu(z, w["e_gate_up"][e],
                                                w["e_down"][e])
    return out, own


def layer(x, w, shape, layer_type: str, held, mask=None, selected=None,
          q_block=None, info: bool = False, norm_gates: bool = True,
          **variant):
    """One block on x [L, hidden], float32, of ``layer_type``.  ``info``:
    also the router's own top-k [L, k].  ``variant``:
    :func:`attention`'s."""
    eps = float(shape["rms_norm_eps"])
    with jax.default_matmul_precision(HIGHEST):
        y = x + attention(rms_norm(x, w["n1_g"], eps), w, shape, layer_type,
                          q_block, **variant)
        f, own = expert_ffn(rms_norm(y, w["n2_g"], eps), w, shape, held,
                            mask, selected, norm_gates)
    return (y + f, own) if info else y + f


def forward(weights, ids, shape, held, mask=None, **variant):
    """weights: {"embed", "layers": [one dict a layer], "nf_g",
    "w_head"}, float32.  ids [L].  Logits [L, V]."""
    x = embed(ids, weights)
    for layer_type, w in zip(layer_types(shape), weights["layers"]):
        x = layer(x, w, shape, layer_type, held, mask, **variant)
    return head(x, weights, shape)


def loss(weights, ids, shape, held, mask=None):
    """Mean next-token negative log-likelihood over the positions that
    hold a token (for the tests' gradient comparison)."""
    lp = next_token_logprobs(forward(weights, ids, shape, held, mask), ids)
    m = jnp.ones_like(lp) if mask is None else mask[1:].astype(lp.dtype)
    return -jnp.sum(lp * m) / jnp.sum(m)
