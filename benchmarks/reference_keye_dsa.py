"""Plain reference of Keye-VL-2.0-30B-A3B's language model (model_type
``KeyeVL2``; the catalog row of the model-configs guide): grouped-query
attention that a learned indexer cuts to ``sa_config.topk`` keys a
query, over softmax-routed experts.  Straightforward ``jax.numpy``,
float32, ``default_matmul_precision("highest")``; no kernel, no cache,
no batching; one sequence at a time.  It imports nothing of the program.

One block on x [S, hidden] (RMSNorm eps ``rms_norm_eps``, pre-norm):

1. ``h = RMSNorm(x)``; ``q = h Wq`` as [S, heads, 128], ``k = h Wk``,
   ``v = h Wv`` as [S, kv heads, 128]; RMSNorm over the 128 of each head
   of q and of k (one weight of 128 each, all heads); rotary on all 128
   (half-split pairs), ``rope_theta``.  Query head i reads key/value
   head ``i // (heads / kv heads)``.
2. Indexer: ``qI = h WIq`` as [S, 16, 64]; ``kI = LayerNorm_64(h WIk)``
   [S, 64], one head for all 16; the same rotary on all 64 of both;
   ``w = h WIw`` [S, 16].  ``I[t, s] = (16 x 64)^(-1/2) sum_j w[t, j]
   ReLU(qI[t, j] . kI[s])`` for ``s <= t``, minus infinity otherwise.
3. ``S_t`` = the positions of the ``min(topk, t + 1)`` largest ``I[t,
   .]``, the lower position first on a tie (``jax.lax.top_k``: a stable
   sort, EXACT).
4. ``o[t, i] = sum over s in S_t of softmax over S_t (q[t, i] . k[s, i
   // g] / sqrt(128)) v[s, i // g]``; ``y = x + concat(o) Wo``.
5. ``z = RMSNorm(y)``; ``p = softmax(z Wr)`` over all experts, float32;
   the top ``num_experts_per_tok``; ``g = p_top / sum(p_top)``
   (``norm_topk_prob``); ``out = y + sum over e in (top and held) of g_e
   Wdown_e (silu(z Wgate_e) * (z Wup_e))``.  What the absent experts
   would add is left out; a position that holds no token is routed
   nowhere.

Set by the family's convention, the published config being silent (the
configuration file lists them under ``assumed``): the per-head q/k
RMSNorm (Qwen3-MoE's); the indexer's query from ``h`` (DeepSeek-V3.2-Exp
takes it from a low-rank query this model does not have); the LayerNorm
(with bias) on ``kI``; rotary on the whole 64 of the indexer's heads;
``q_chunk_size`` / ``kv_chunk_size`` read as the tiling of the indexer's
scores, which changes no equation (``q_block`` below tiles the queries
the same way).  For token ids alone the three M-RoPE components
(``mrope_section`` [16, 24, 24]) are equal: the ordinary rotation with
64 frequencies.

DEPARTURES from the published recipe, both the program's too: the
indexer is held FIXED under RL (its input is not differentiated, its
three matrices and its norm take no gradient, and no alignment loss is
added, where DeepSeek-V3.2-Exp trains it by a separate KL term):
:func:`loss` differentiates through the selected attention alone.  And
the published FP8 quantisation and Hadamard rotation of ``qI``, ``kI``
are left out (the rotation is an orthogonal map of both: no product
changes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gain


def layer_norm(x, gain, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gain + bias


def rotate(x, positions, base):
    """x [S, heads, d]: feature j pairs with j + d/2 (half-split), both
    rotated by ``position * base**(-2j/d)``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (base ** (jnp.arange(d // 2, dtype=jnp.float32)
                               * 2.0 / d))
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def sa_sizes(shape: dict):
    sa = shape["sa_config"]
    return (int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"]),
            int(sa["topk"]))


def _rows(t, start, n: int):
    return jax.lax.dynamic_slice_in_dim(t, start, n, axis=0)


def index_scores(h, w, positions, shape, start=0, n=None):
    """I[t, s] of module point 2 for the ``n`` queries from ``start``
    (all by default): [n, S] float32, minus infinity where s > t."""
    S = h.shape[0]
    heads, dim, _ = sa_sizes(shape)
    base, eps = float(shape["rope_theta"]), float(shape["rms_norm_eps"])
    n = S if n is None else n
    hq, pq = _rows(h, start, n), _rows(positions, start, n)
    qi = rotate((hq @ w["wiq"]).reshape(n, heads, dim), pq, base)
    ki = rotate(layer_norm(h @ w["wik"], w["ik_g"], w["ik_b"], eps)[
        :, None, :], positions, base)[:, 0]
    wt = hq @ w["wiw"]                                        # [n, heads]
    s = jnp.einsum("qhd,kd->qhk", qi, ki)
    scores = jnp.einsum("qhk,qh->qk", jax.nn.relu(s), wt) \
        * float(heads * dim) ** -0.5
    return jnp.where(pq[:, None] >= positions[None, :], scores, -jnp.inf)


def select(scores, topk: int):
    """[rows, S] bool: the ``min(topk, valid)`` largest of each row, the
    lower position first on a tie.  Exact."""
    k = min(topk, scores.shape[1])
    vals, idx = jax.lax.top_k(scores, k)
    keep = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], idx].max(vals > -jnp.inf)
    return keep


def window_selection(positions, topk: int):
    """The WRONG selection of the check's control: the last ``topk``
    keys of every query (a sliding window), [S, S] bool."""
    d = positions[:, None] - positions[None, :]
    return (d >= 0) & (d < topk)


def attention(h, w, positions, shape, selection=None, q_block=None):
    """Points 1 to 4 on h = RMSNorm(x): (concat(o) Wo [S, hidden], the
    selection used [S, S] bool).  ``selection`` given: that one in place
    of the indexer's own (rows = queries, True = attend; the causal rule
    is applied besides).  ``q_block``: the queries a block at a time, so
    that no [heads, S, S] array exists."""
    S = h.shape[0]
    heads, kv = int(shape["num_attention_heads"]), \
        int(shape["num_key_value_heads"])
    d = int(shape["head_dim"])
    base, eps = float(shape["rope_theta"]), float(shape["rms_norm_eps"])
    topk = sa_sizes(shape)[2]
    q = rotate(rms_norm((h @ w["wq"]).reshape(S, heads, d), w["q_g"], eps),
               positions, base)
    k = rotate(rms_norm((h @ w["wk"]).reshape(S, kv, d), w["k_g"], eps),
               positions, base)
    v = (h @ w["wv"]).reshape(S, kv, d)
    g = heads // kv
    n = S if q_block is None or S % q_block else q_block

    def block(start):
        pq = _rows(positions, start, n)
        if selection is None:
            sel = select(index_scores(h, w, positions, shape, start, n), topk)
        else:
            sel = _rows(selection, start, n) & (pq[:, None]
                                                >= positions[None, :])
        qb = _rows(q, start, n).reshape(n, kv, g, d)
        scores = jnp.einsum("qhgd,khd->hgqk", qb, k) / jnp.sqrt(
            jnp.float32(d))
        probs = jax.nn.softmax(
            jnp.where(sel[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", probs, v).reshape(
            n, heads * d), sel

    out, sel = jax.lax.map(block, jnp.arange(0, S, n))
    return out.reshape(S, heads * d) @ w["wo"], sel.reshape(S, S)


def route(z, w, shape, experts=None):
    """(experts [S, k] over all, gates [S, k], the router's own top-k
    [S, k]).  ``experts`` given: those in place of the k largest
    (gates from the probabilities as ever)."""
    k = int(shape["num_experts_per_tok"])
    probs = jax.nn.softmax(z @ w["w_router"], axis=-1)
    _, own = jax.lax.top_k(probs, k)
    experts = own if experts is None else experts
    chosen = jnp.take_along_axis(probs, experts, axis=-1)
    return experts, chosen / jnp.sum(chosen, axis=-1, keepdims=True), own


def swiglu(z, w_gate_up, w_down):
    gate, up = jnp.split(z @ w_gate_up, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_down


def expert_ffn(z, w, shape, held, token_mask=None, experts=None):
    """Point 5's sum for the experts ``held = (offset, count)``;
    ``w["e_gate_up"]`` [count, D, 2I] (gate then up) and ``w["e_down"]``
    [count, I, D] are theirs.  Returns (sum [S, D], the router's own
    top-k [S, k])."""
    offset, count = held
    idx, gates, own = route(z, w, shape, experts)
    weight = jnp.sum(
        jax.nn.one_hot(idx - offset, count, dtype=jnp.float32)
        * gates[..., None], axis=1)   # one_hot of an index outside is zero
    if token_mask is not None:
        weight = weight * token_mask[:, None]
    out = jnp.zeros_like(z)
    for e in range(count):
        out = out + weight[:, e:e + 1] * swiglu(z, w["e_gate_up"][e],
                                                w["e_down"][e])
    return out, own


def layer(x, w, positions, shape, held, token_mask=None, selection=None,
          experts=None, q_block=None, info: bool = False):
    """One block on x [S, hidden], float32.  ``info``: also {"selection"
    [S, S] bool: the keys each query attended to, "experts" [S, k]: the
    router's own top-k}."""
    eps = float(shape["rms_norm_eps"])
    with jax.default_matmul_precision(HIGHEST):
        a, sel = attention(rms_norm(x, w["n1_g"], eps), w, positions, shape,
                           selection, q_block)
        y = x + a
        f, own = expert_ffn(rms_norm(y, w["n2_g"], eps), w, shape, held,
                            token_mask, experts)
    return (y + f, {"selection": sel, "experts": own}) if info else y + f


def embed(ids, w):
    return w["embed"][ids].astype(jnp.float32)


def head(x, w, shape):
    """Final RMSNorm and the untied output projection: logits [S, V]."""
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, w["nf_g"], float(shape["rms_norm_eps"])) \
            @ w["w_head"]


def forward(weights, ids, shape, held, token_mask=None, selections=None):
    """weights: {"embed", "layers": [layer dicts], "nf_g", "w_head"},
    float32.  ids [S].  ``selections``: one [S, S] bool a layer to use in
    place of the indexer's own.  Logits [S, V]."""
    positions = jnp.arange(ids.shape[0])
    x = embed(ids, weights)
    for i, w in enumerate(weights["layers"]):
        x = layer(x, w, positions, shape, held, token_mask,
                  None if selections is None else selections[i])
    return head(x, weights, shape)


def next_token_logprobs(logits, ids):
    """log p(ids[t+1] | ids[:t+1]) for t = 0 .. S-2, at temperature 1."""
    logp = jax.nn.log_softmax(logits[:-1].astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, ids[1:, None], axis=-1)[:, 0]


def loss(weights, ids, shape, held, token_mask=None):
    """Mean next-token negative log-likelihood over the real tokens; its
    gradient (``jax.grad``) is the reference for the tests.  The
    indexer's weights get none: the selection is discrete (see the
    module docstring's departures)."""
    logp = next_token_logprobs(forward(weights, ids, shape, held,
                                       token_mask), ids)
    if token_mask is None:
        return -jnp.mean(logp)
    m = token_mask[1:].astype(jnp.float32)
    return -jnp.sum(logp * m) / jnp.sum(m)
