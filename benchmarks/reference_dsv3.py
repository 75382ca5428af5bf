"""Plain reference: the DeepSeek-V3 block's forward pass in float32
``jax.numpy``, and the chip's share of it.

Written from the published configuration keys (``model_type:
deepseek_v3``; kanana-2-30b-a3b-instruct-2601 uses it with
``q_lora_rank: null``) and the published modelling code's mathematics:

- every norm is RMSNorm with a learned scale, no bias anywhere;
- block: ``a = x + Attn(N1(x))``, ``y = a + FFN(N2(a))``; a final
  RMSNorm and an untied output projection;
- latent attention: ``q = h W_q`` per head is ``[q_nope ; q_rope]``;
  ``h W_kva = [c_raw ; k_rope_raw]``, ``c = RMSNorm(c_raw)``;
  ``c W_kvb`` per head is ``[k_nope ; v]``; rotary (``rope_theta``) on
  ``q_rope`` of every head and on the one ``k_rope_raw`` all heads
  share; ``rope_interleave``: the rotary features are stored as adjacent
  pairs and brought to the half-split layout before the rotation;
  ``rope_scaling: null``; scores ``q . [k_nope ; k_rope] / sqrt(nope +
  rope)``, causal softmax, ``o = P v``, ``Attn = concat(o) W_o``;
- FFN of the first ``first_k_dense_replace`` layers: SwiGLU of
  ``intermediate_size``;
- FFN of the others: ``s = sigmoid(z W_r)``; the ``num_experts_per_tok``
  largest of ``s + b`` are selected (``n_group = topk_group = 1``: the
  group limit is the identity); gates ``w_i = routed_scaling_factor *
  s_i / (sum of the selected s_j + 1e-20)`` (``norm_topk_prob``): the
  bias ``b`` takes part in the selection only; ``FFN = sum_i w_i E_i(z)
  + S(z)``, ``E_i`` a SwiGLU of ``moe_intermediate_size``, ``S`` one
  SwiGLU of ``n_shared_experts`` times that.  No capacity, no drops, no
  auxiliary loss.

No kernel, no cache, no scan, no batching: one sequence at a time, the
whole causal attention matrix materialised, every held expert computed
for every token.  It imports nothing from ``orion_tpu``.  Every matrix
product runs under ``jax.default_matmul_precision("highest")``.

Departures from the published code, none of them mathematics:

- the chip's share: ``held = (offset, count)`` names the consecutive
  experts whose weights are given; the router still scores, selects and
  normalises over all its outputs, and the sum runs over the selected
  experts that are held.  What the absent ones would add is left out.
  ``held`` covering every expert is the uncut model;
- an expert's gate and up projections arrive as one matrix ``[D, 2I]``
  (gate first), the experts stacked on a leading axis;
- ``b`` (``e_score_correction_bias``) is given, fixed: the published
  pre-training updates it from the load, the modelling code holds it as
  a buffer;
- ``selected`` may hand a layer the experts each token uses instead of
  its own top-k (gates still from the scores): how the comparison with
  a program in bfloat16 follows that program's selection where the two
  differ within rounding (``reference_check_dsv3``).

``shape`` is a dict with the published keys ``num_attention_heads``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``rope_theta``, ``rms_norm_eps``,
``num_experts_per_tok`` and ``routed_scaling_factor``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gain


def rotate_interleaved(x, positions, base):
    """x: [L, heads, d], the d rotary features stored as adjacent pairs
    (x0, x1), (x2, x3), ...: pair j is rotated by ``position *
    base**(-2j/d)``.  Returned in the half-split layout (first elements
    of all pairs, then second elements), as the published code leaves
    it: query and key go through the same permutation, so their dot
    product does not see it."""
    d = x.shape[-1]
    inv_freq = 1.0 / (base ** (jnp.arange(d // 2, dtype=jnp.float32)
                               * 2.0 / d))
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(h, w, positions, shape):
    L = h.shape[0]
    heads = int(shape["num_attention_heads"])
    rank = int(shape["kv_lora_rank"])
    dn, dr, dv = (int(shape["qk_nope_head_dim"]),
                  int(shape["qk_rope_head_dim"]), int(shape["v_head_dim"]))
    base, eps = float(shape["rope_theta"]), float(shape["rms_norm_eps"])
    q = (h @ w["wq"]).reshape(L, heads, dn + dr)
    kva = h @ w["wkva"]
    c = rms_norm(kva[:, :rank], w["kva_g"], eps)
    kv = (c @ w["wkvb"]).reshape(L, heads, dn + dv)
    q_rope = rotate_interleaved(q[..., dn:], positions, base)
    k_rope = rotate_interleaved(kva[:, None, rank:], positions, base)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (L, heads, dr))], axis=-1)
    q = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.float32(dn + dr))
    causal = positions[None, :, None] >= positions[None, None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, kv[..., dn:])
    return out.reshape(L, heads * dv) @ w["wo"]


def swiglu(z, w_gate_up, w_down):
    gate, up = jnp.split(z @ w_gate_up, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_down


def route(z, w, shape, selected=None):
    """(selected [L, k] over all experts, gates [L, k], scores [L, E]).
    ``selected`` given: those experts instead of the k largest of
    ``scores + bias`` (gates from the scores as ever)."""
    k = int(shape["num_experts_per_tok"])
    scores = jax.nn.sigmoid(z @ w["w_router"])
    if selected is None:
        _, selected = jax.lax.top_k(scores + w["router_bias"][None, :], k)
    chosen = jnp.take_along_axis(scores, selected, axis=-1)
    gates = float(shape["routed_scaling_factor"]) * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return selected, gates, scores


def expert_ffn(z, w, shape, held, selected=None, probe: bool = False):
    """The expert layer's FFN on z [L, D] for the experts ``held =
    (offset, count)``; ``w["e_gate_up"]`` [count, D, 2I] and
    ``w["e_down"]`` [count, I, D] are theirs.

    ``probe=True`` also returns what a comparison with a program that
    computes in a lower precision needs to know about the discrete
    selection (see ``reference_check_dsv3``), per token.  With
    ``noise(e) = score'(e) * sqrt(sum_i z_i^2 W_ie^2)``, the size of
    expert e's score error for a unit relative error of every element
    of z, independent:

    - ``margin``: the gap between the k-th and the (k+1)-th largest
      biased score of the reference's own selection, over the joint
      noise of the two;
    - ``excess`` [L, k]: for each expert of ``selected``, how far its
      biased score lies BELOW the reference's k-th largest, over the
      joint noise of the two (0 or less: the reference selects it too);
    - ``exchanged``: whether ``selected`` is another set than the
      reference's own.
    """
    offset, count = held
    idx, gates, scores = route(z, w, shape, selected)
    every = jax.vmap(lambda gu, dn: swiglu(z, gu, dn), out_axes=1)(
        w["e_gate_up"], w["e_down"])                       # [L, count, D]
    weight = jnp.sum(
        jax.nn.one_hot(idx - offset, count, dtype=jnp.float32)
        * gates[..., None], axis=1)   # one_hot of an index outside is zero
    out = jnp.einsum("lhd,lh->ld", every, weight)
    out = out + swiglu(z, w["s_gate_up"], w["s_down"])
    if not probe:
        return out
    k = idx.shape[1]
    biased = scores + w["router_bias"][None, :]
    top, top_idx = jax.lax.top_k(biased, k + 1)
    unit = jnp.sqrt(jnp.square(z) @ jnp.square(w["w_router"]))
    noise = scores * (1.0 - scores) * unit                      # [L, E]

    def joint(a, b):
        return jnp.sqrt(jnp.square(jnp.take_along_axis(noise, a, axis=-1))
                        + jnp.square(jnp.take_along_axis(noise, b, axis=-1)))

    kth, nxt = top_idx[:, k - 1:k], top_idx[:, k:k + 1]
    margin = (top[:, k - 1] - top[:, k]) / joint(kth, nxt)[:, 0]
    excess = (top[:, k - 1:k] - jnp.take_along_axis(biased, idx, axis=-1)) \
        / joint(idx, jnp.broadcast_to(kth, idx.shape))
    exchanged = jnp.any(jnp.sort(idx, axis=-1)
                        != jnp.sort(top_idx[:, :k], axis=-1), axis=-1)
    return out, {"margin": margin, "excess": excess, "exchanged": exchanged}


def layer(x, w, positions, shape, held=None, selected=None,
          probe: bool = False):
    """One block on x [L, hidden], float32.  ``held`` None: a leading
    dense layer (``w["gate_up"]``, ``w["down"]``)."""
    eps = float(shape["rms_norm_eps"])
    with jax.default_matmul_precision(HIGHEST):
        a = x + attention(rms_norm(x, w["n1_g"], eps), w, positions, shape)
        z = rms_norm(a, w["n2_g"], eps)
        if held is None:
            y = a + swiglu(z, w["gate_up"], w["down"])
            return (y, None) if probe else y
        if not probe:
            return a + expert_ffn(z, w, shape, held, selected)
        f, info = expert_ffn(z, w, shape, held, selected, probe=True)
        return a + f, info


def embed(ids, w):
    return w["embed"][ids].astype(jnp.float32)


def head(x, w, shape):
    """Final RMSNorm and the untied output projection: logits [L, V]."""
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, w["nf_g"], float(shape["rms_norm_eps"])) \
            @ w["w_head"]


def forward(weights, ids, shape, held):
    """weights: {"embed", "layers": [layer dicts, the dense ones first],
    "nf_g", "w_head"}, float32.  ids: [L].  Logits [L, V]."""
    positions = jnp.arange(ids.shape[0])
    x = embed(ids, weights)
    for w in weights["layers"]:
        x = layer(x, w, positions, shape,
                  None if "gate_up" in w else held)
    return head(x, weights, shape)


def next_token_logprobs(logits, ids):
    """log p(ids[t+1] | ids[:t+1]) for t = 0 .. L-2, at temperature 1."""
    logp = jax.nn.log_softmax(logits[:-1].astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, ids[1:, None], axis=-1)[:, 0]
