"""Plain reference of SDAR-30B-A3B-Chat (model_type ``sdar_moe``; the
catalog row of the model-configs guide): the Qwen3-MoE block, generating
by diffusion over blocks.  Straightforward ``jax.numpy``, float32,
``default_matmul_precision("highest")``; no kernel, no cache, no
batching; one sequence at a time; every mask an explicit boolean
matrix.  It imports nothing of the program.

Let ``Bd`` be the block length, ``blk(p) = p // Bd`` on absolute
positions (blocks are aligned to position 0), ``S`` the denoising steps
a block, ``MASK`` one id of the vocabulary.

*Block* on x [L, hidden] (RMSNorm eps ``rms_norm_eps``, pre-norm, no
bias): ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; final
RMSNorm, untied head.  NO SHIFT: the logit AT position p scores the
token AT p.

*Attn*: ``q, k, v = z Wq, z Wk, z Wv`` (heads / kv heads / kv heads of
``head_dim``); an RMSNorm over each head's features of q and of k (one
weight each, all heads); rotary on all of a head (half-split pairs,
``rope_theta``) at the entry's TRUE position; scale ``head_dim^-1/2``;
softmax in float32 over the keys the mask leaves; ``Wo``.  Query head i
reads key/value head ``i // (heads / kv heads)``.

*Visible keys*, clean stream (:func:`clean_mask`): key j is visible to
query p iff ``blk(j) <= blk(p)``.  A noisy stream's query at position p
(:func:`stream_mask`) sees the clean keys j with ``blk(j) < blk(p)`` and
the keys of its OWN stream with ``blk(j) = blk(p)``; clean queries see
clean keys only.

*MoE*: ``p = softmax(z Wr)`` over all experts, float32; the top
``num_experts_per_tok`` (the lower index first on a tie); ``g = p_top /
sum(p_top)``; ``sum over e in (top and held) of g_e Wdown_e (silu(z
Wgate_e) * (z Wup_e))``.  What the absent experts would add is left
out; an entry that holds no token is routed nowhere.

*Generation* (:func:`generate`), one row, THE WHOLE SEQUENCE RUN AGAIN
AT EVERY STEP: for each block from ``blk(prompt_len)`` on, the state
shows the prompt's tokens that lie in it and ``MASK`` elsewhere; for ``s
= 0 .. S-1`` the logits of the block's positions, ``MASK``'s set to
minus infinity, a candidate a masked position ``argmax(l / temperature
+ noise)`` (Gumbel noise handed in: a draw from ``softmax(l /
temperature)``), its probability under that distribution the
confidence; the ``Bd / S`` masked positions of the highest confidence
are revealed (the lowest position on a tie).  A position past
``prompt_len + T`` is never revealed and stays ``MASK``.  A row ends
with the block that holds a stop token or its last new position.

*Trace log-probability* (:func:`trace_logprobs`): the noisy state
``z^(s)`` of a block shows position j iff j is a prompt's or ``step(j) <
s``; ``lp_p = log softmax(f(z^(step(p)) of blk(p) | clean blocks <
blk(p)))_p [y_p]``, as ``S`` SEPARATE forwards of ``[clean ; one noisy
stream]`` (the program makes one forward of all streams: another
layout).  Values: the value head on the final-norm hidden state at the
same entry.

DEPARTURES, both the program's too: TraceRL reads a token's value and
orders generalised advantage estimation by denoising STEP; here a value
is read at the noisy entry its log-probability is, and GAE runs over
completion positions in order.  :func:`ppo_loss` is the trainer's
clipped objective on these log-probabilities and values.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = "highest"
NEG = -1e10       # what bars MASK from a distribution (finite: entropy)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gain


def rotate(x, positions, base):
    """x [L, heads, d]: feature j pairs with j + d/2 (half-split), both
    rotated by ``position * base**(-2j/d)``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (base ** (jnp.arange(d // 2, dtype=jnp.float32)
                               * 2.0 / d))
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def clean_mask(positions, block: int):
    """[L, L] bool: key j visible to query p iff blk(j) <= blk(p)."""
    b = positions // block
    return b[None, :] <= b[:, None]


def causal_mask(positions):
    """The WRONG mask of the check's control: j <= p."""
    return positions[None, :] <= positions[:, None]


def stream_mask(clean_positions, noisy_positions, block: int):
    """[Lc + Ln, Lc + Ln] bool for the row ``[clean ; one noisy
    stream]``: rows are queries."""
    bc, bn = clean_positions // block, noisy_positions // block
    lc, ln = bc.shape[0], bn.shape[0]
    top = jnp.concatenate([bc[None, :] <= bc[:, None],
                           jnp.zeros((lc, ln), bool)], axis=1)
    bottom = jnp.concatenate([bc[None, :] < bn[:, None],
                              bn[None, :] == bn[:, None]], axis=1)
    return jnp.concatenate([top, bottom], axis=0)


def attention(h, w, positions, shape, mask):
    """``Attn`` of the module text on h = RMSNorm(x): [L, hidden]."""
    L = h.shape[0]
    heads, kv = int(shape["num_attention_heads"]), \
        int(shape["num_key_value_heads"])
    d = int(shape["head_dim"])
    base, eps = float(shape["rope_theta"]), float(shape["rms_norm_eps"])
    q = rotate(rms_norm((h @ w["wq"]).reshape(L, heads, d), w["q_g"], eps),
               positions, base)
    k = rotate(rms_norm((h @ w["wk"]).reshape(L, kv, d), w["k_g"], eps),
               positions, base)
    v = (h @ w["wv"]).reshape(L, kv, d)
    g = heads // kv
    scores = jnp.einsum("qhgd,khd->hgqk", q.reshape(L, kv, g, d), k) \
        / jnp.sqrt(jnp.float32(d))
    probs = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf),
                           axis=-1)
    return jnp.einsum("hgqk,khd->qhgd", probs, v).reshape(L, heads * d) \
        @ w["wo"]


def route(z, w, shape, experts=None):
    """(experts [L, k] over all, gates [L, k], the router's own top-k).
    ``experts`` given: those in place of the k largest."""
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
    """The sum for the experts ``held = (offset, count)``;
    ``w["e_gate_up"]`` [count, D, 2I] (gate then up) and ``w["e_down"]``
    [count, I, D] are theirs.  (sum [L, D], the router's own top-k)."""
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


def layer(x, w, positions, shape, held, mask, token_mask=None, experts=None,
          info: bool = False):
    """One block on x [L, hidden]; ``info``: also the router's top-k."""
    eps = float(shape["rms_norm_eps"])
    with jax.default_matmul_precision(HIGHEST):
        y = x + attention(rms_norm(x, w["n1_g"], eps), w, positions, shape,
                          mask)
        f, own = expert_ffn(rms_norm(y, w["n2_g"], eps), w, shape, held,
                            token_mask, experts)
    return (y + f, own) if info else y + f


def embed(ids, w):
    return w["embed"][ids].astype(jnp.float32)


def final_norm(x, w, shape):
    return rms_norm(x, w["nf_g"], float(shape["rms_norm_eps"]))


def head(x, w, shape):
    """Final RMSNorm and the untied output projection: logits [L, V]."""
    with jax.default_matmul_precision(HIGHEST):
        return final_norm(x, w, shape) @ w["w_head"]


def hidden(weights, ids, positions, mask, shape, held, token_mask=None,
           experts=None):
    """The last block's output [L, hidden] (before the final norm).
    weights: {"embed", "layers": [layer dicts], "nf_g", "w_head"}."""
    x = embed(ids, weights)
    for i, w in enumerate(weights["layers"]):
        x = layer(x, w, positions, shape, held, mask, token_mask,
                  None if experts is None else experts[i])
    return x


def forward(weights, ids, shape, held, block=None, token_mask=None):
    """The clean forward of ids [L]: logits [L, V].  ``block`` None: the
    configuration's ``block_length``; 0: the plain causal mask (the
    control)."""
    positions = jnp.arange(ids.shape[0])
    block = int(shape["block_length"]) if block is None else block
    mask = clean_mask(positions, block) if block else causal_mask(positions)
    return head(hidden(weights, ids, positions, mask, shape, held,
                       token_mask), weights, shape)


def bar(logits, mask_id: int):
    return logits.at[..., mask_id].set(NEG)


def rule(shape):
    return (int(shape["block_length"]), int(shape["denoising_steps"]),
            int(shape["mask_token_id"]))


def generate(weights, prompt, new_tokens: int, shape, held, noise,
             temperature: float = 1.0, stop_ids=()):
    """Generation by the module text's loop: the whole sequence again at
    every step.  prompt [len] real tokens; ``noise`` [blocks, S, Bd, V]
    Gumbel noise, block i of it for the i-th block generated.  Returns a
    dict of numpy-convertible arrays over the T new positions: tokens,
    step (S where never revealed), lp (under ``softmax(l /
    temperature)``), plp (under ``softmax(l)``), ``n`` the completion's
    length, and ``gap`` [blocks, S]: the least distance between the
    confidence of a revealed position and of one left masked at that
    step (inf where none is left): a reveal is decided beyond rounding
    only where it is large."""
    Bd, S, MASK = rule(shape)
    per_step = Bd // S
    n_prompt, T = int(prompt.shape[0]), int(new_tokens)
    last = n_prompt + T
    seq = [int(t) for t in prompt]
    tokens, step, lp, plp = ({} for _ in range(4))
    gaps, n, done = [], T, False
    i = 0
    while not done:
        start = (n_prompt // Bd + i) * Bd
        pos = list(range(start, start + Bd))
        z = [seq[p] if p < n_prompt else MASK for p in pos]
        masked = [n_prompt <= p < last for p in pos]
        gap_row = []
        for s in range(S):
            ids = jnp.asarray(seq[:start] + z, jnp.int32)
            logits = bar(forward(weights, ids, shape, held)[start:], MASK)
            logp_t = jax.nn.log_softmax(logits / temperature, axis=-1)
            logp = jax.nn.log_softmax(logits, axis=-1)
            cand = jnp.argmax(logits / temperature + noise[i, s], axis=-1)
            conf = [float(jnp.exp(logp_t[j, cand[j]])) if masked[j] else -1.0
                    for j in range(Bd)]
            order = sorted(range(Bd), key=lambda j: (-conf[j], j))
            take = [j for j in order[:per_step] if masked[j]]
            rest = [conf[j] for j in range(Bd) if masked[j] and j not in take]
            gap_row.append(min((conf[j] for j in take), default=float("inf"))
                           - max(rest) if rest and take else float("inf"))
            for j in take:
                z[j], masked[j] = int(cand[j]), False
                t = pos[j] - n_prompt
                tokens[t], step[t] = z[j], s
                lp[t], plp[t] = float(logp_t[j, cand[j]]), \
                    float(logp[j, cand[j]])
        gaps.append(gap_row)
        seq = seq[:start] + z
        stops = [p for p, t in zip(pos, z)
                 if n_prompt <= p < last and t in stop_ids]
        if stops:
            n, done = stops[0] - n_prompt + 1, True
        elif pos[-1] + 1 >= last:
            done = True
        i += 1
    return {"tokens": [tokens.get(t, 0) for t in range(T)],
            "step": [step.get(t, S) for t in range(T)],
            "lp": [lp.get(t, 0.0) for t in range(T)],
            "plp": [plp.get(t, 0.0) for t in range(T)],
            "n": n, "gap": gaps, "sequence": seq}


def stream_rows(ids, n_prompt: int, steps_of, shape, s: int,
                all_masked: bool = False):
    """The row ``[clean ; noisy stream s]`` of ids [L] (prompt, then the
    T completion positions whose reveal steps are ``steps_of`` [T]):
    (row ids, positions, the noisy window's positions).  The window runs
    from the prompt's last block through the block of the last new
    position; a position past it holds MASK.  ``all_masked``: every
    completion position masked whatever its step (the control's state
    ``z^(0)``)."""
    Bd, S, MASK = rule(shape)
    L, T = int(ids.shape[0]), int(steps_of.shape[0])
    start = n_prompt // Bd * Bd
    stop = ((n_prompt + T - 1) // Bd + 1) * Bd
    wpos = jnp.arange(start, stop)
    rel = wpos - n_prompt
    step_w = jnp.where(rel < 0, -1, jnp.where(
        rel < T, steps_of[jnp.clip(rel, 0, T - 1)], S))
    shown = (rel < 0) if all_masked else (step_w < s)
    z = jnp.where(shown, ids[jnp.clip(wpos, 0, L - 1)], MASK)
    return (jnp.concatenate([ids, z.astype(ids.dtype)]),
            jnp.concatenate([jnp.arange(L), wpos]), wpos)


def trace_hidden(weights, ids, n_prompt: int, steps_of, shape, held,
                 experts=None, token_mask=None, all_masked: bool = False):
    """The final-norm hidden state each completion token is scored from,
    [T, hidden], by S separate forwards of ``[clean ; one noisy
    stream]``.  ``experts``: per stream s, [layers, L + window, k] to
    follow in place of the router's own.  ``token_mask`` [L]: the clean
    entries that hold a token (the noisy window's all do)."""
    Bd, S, _ = rule(shape)
    L, T = int(ids.shape[0]), int(steps_of.shape[0])
    rows = []
    for s in range(S):
        row, positions, wpos = stream_rows(ids, n_prompt, steps_of, shape, s,
                                           all_masked)
        mask = stream_mask(jnp.arange(L), wpos, Bd)
        tm = None if token_mask is None else jnp.concatenate(
            [token_mask, jnp.ones(wpos.shape, bool)])
        x = hidden(weights, row, positions, mask, shape, held, tm,
                   None if experts is None else experts[s])
        # the window's entry of completion position t
        at = L + (n_prompt - int(wpos[0])) + jnp.arange(T)
        rows.append(final_norm(x, weights, shape)[at])
    pick = jnp.clip(steps_of, 0, S - 1)
    return jnp.take_along_axis(jnp.stack(rows), pick[None, :, None],
                               axis=0)[0]


def trace_logprobs(weights, ids, n_prompt: int, steps_of, shape, held,
                   experts=None, token_mask=None, value_head=None,
                   all_masked: bool = False):
    """``lp`` [T] of the module text; with ``value_head`` [hidden, 1]
    also the values [T]; always the logits' spread over the vocabulary
    (mean standard deviation: the check's error model reads it)."""
    _, _, MASK = rule(shape)
    T = int(steps_of.shape[0])
    h = trace_hidden(weights, ids, n_prompt, steps_of, shape, held, experts,
                     token_mask, all_masked)
    with jax.default_matmul_precision(HIGHEST):
        logits = bar(h @ weights["w_head"], MASK)
        values = None if value_head is None else (h @ value_head)[:, 0]
    targets = ids[n_prompt + jnp.arange(T)]
    lp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                             targets[:, None], axis=-1)[:, 0]
    return lp, values, jnp.mean(jnp.std(
        jnp.where(jnp.arange(logits.shape[-1]) == MASK, 0.0, logits),
        axis=-1))


def causal_logprobs(weights, ids, n_prompt: int, new_tokens: int, shape,
                    held):
    """The control: one plain causal forward, no shift undone: the logit
    at p - 1 scores the token at p, as an autoregressive model's."""
    _, _, MASK = rule(shape)
    logits = bar(forward(weights, ids, shape, held, block=0), MASK)
    at = n_prompt + jnp.arange(new_tokens)
    return jnp.take_along_axis(jax.nn.log_softmax(logits[at - 1], axis=-1),
                               ids[at][:, None], axis=-1)[:, 0]


def masked_mean(x, mask):
    return jnp.sum(x * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def ppo_loss(lp, values, old_lp, old_values, advantages, returns, mask,
             clip_ratio: float, value_clip: float, vf_coef: float):
    """The trainer's clipped objective on [T] (or [B, T]) arrays: the
    policy's surrogate plus ``vf_coef`` times the clipped value loss."""
    ratio = jnp.exp((lp - old_lp) * mask)
    policy = masked_mean(jnp.maximum(
        -advantages * ratio,
        -advantages * jnp.clip(ratio, 1.0 - clip_ratio, 1.0 + clip_ratio)),
        mask)
    clipped = old_values + jnp.clip(values - old_values, -value_clip,
                                    value_clip)
    value = 0.5 * masked_mean(jnp.maximum((values - returns) ** 2,
                                          (clipped - returns) ** 2), mask)
    return policy + vf_coef * value
