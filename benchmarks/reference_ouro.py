"""Plain reference: Ouro's looped forward pass in float32 ``jax.numpy``.

Written from the published configuration keys (``model_type: ouro``; the
catalog row of ByteDance/Ouro-2.6B) and, where no key says, from the
family's paper ("Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741) and released modelling code AS REMEMBERED (there is no
network here; every such point is marked ASSUMED below and listed in the
configuration file under ``assumed``):

- ``x_0 = E[ids]`` (ASSUMED: no embedding scale).
- For pass ``t = 1 .. total_ut_steps``, for layer ``l = 1 .. L``, with
  the SAME parameters in every pass:

  - ``h = N1_l(x)``; ``q = h Wq``, ``k = h Wk``, ``v = h Wv``
    (``num_attention_heads`` = ``num_key_value_heads`` heads of
    ``head_dim``; ASSUMED: no bias, no q/k norm: no key names either);
    a rotation of the whole head in half-split pairs, ``inv_freq_i =
    rope_theta^(-2i / head_dim)``, at the token's position, the same in
    every pass; scores ``q . k / sqrt(head_dim)``, a float32 softmax
    over the keys ``s <= p`` OF THIS PASS (``k``, ``v`` computed in pass
    ``t`` from pass ``t``'s stream); ``a = x + N2_l(o Wo)``;
  - ``z = N3_l(a)``; ``y = a + N4_l(Wdown(silu(Wgate z) * Wup z))``, no
    bias.  ``N1 .. N4`` are RMSNorms with learned scales, eps
    ``rms_norm_eps`` (ASSUMED: the sandwich order, the paper's "RMSNorm
    before and after both sublayers"; the released code's
    ``input_layernorm``, ``input_layernorm_2``,
    ``post_attention_layernorm``, ``post_attention_layernorm_2``);
  - after layer ``L``: ``x = Nf(y_L)``, the ONE final RMSNorm applied
    after every pass, its output both the next pass's input and pass
    ``t``'s hidden state ``H_t`` (ASSUMED: the released loop norms
    inside the pass loop);
  - the gate ``lambda_t = sigmoid(H_t w_g + b_g)`` (ASSUMED: a
    ``Linear(hidden, 1)`` with its default bias).

- Exit masses (ASSUMED: the paper's exit distribution and its
  cumulative-mass rule): ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``
  for ``t`` below the last pass, which takes ``prod_{j<last} (1 -
  lambda_j)``.  The hidden state used is that of the first pass whose
  cumulative mass reaches ``early_exit_threshold``; at the published 1
  that is the last pass for every token (the cumulative mass is below 1
  before it whenever any gate is below 1; a tie at float32's 1.0 still
  takes the last pass).
- Logits ``H_last W_head`` (untied).

Two Python loops (passes, layers) over ONE list of layer parameters, an
``[L, L]`` boolean causal mask materialised whole.  No kernel, no cache,
no scan, no batching: one sequence ``ids`` [L].  It imports nothing from
``orion_tpu``.  Every matrix product runs under
``jax.default_matmul_precision("highest")``.

Departures from the published model, none of them this file's
mathematics: everything marked ASSUMED; and the objective: pre-training
minimises the expected loss over exit passes with an entropy term, RL
here trains the last pass's distribution (the gate's parameters receive
no gradient from it).

``shape`` is the configuration file: the published keys.  ``weights``:
``{"embed" [V, E], "layers": [{"n1", "n2", "n3", "n4" [E], "wq", "wk",
"wv" [E, H D], "wo" [H D, E], "w_gate", "w_up" [E, F], "w_down" [F,
E]}], "nf" [E], "w_g" [E], "b_g" [], "w_head" [E, V]}``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def rms_norm(x, g, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def rotate(x, positions, theta: float):
    """Half-split rotary over the whole head: x [L, H, D]."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, w, shape, positions):
    """Multi-head causal attention over this pass's own keys."""
    H, D = int(shape["num_attention_heads"]), int(shape["head_dim"])
    L = h.shape[0]
    with jax.default_matmul_precision(HIGHEST):
        q = (h @ w["wq"]).reshape(L, H, D)
        k = (h @ w["wk"]).reshape(L, H, D)
        v = (h @ w["wv"]).reshape(L, H, D)
        theta = float(shape["rope_theta"])
        q, k = rotate(q, positions, theta), rotate(k, positions, theta)
        scores = jnp.einsum("phd,shd->hps", q, k) / D ** 0.5
        causal = positions[None, :] <= positions[:, None]        # [L, L]
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                               axis=-1)
        return jnp.einsum("hps,shd->phd", probs, v).reshape(L, H * D) \
            @ w["wo"]


def swiglu(z, w):
    with jax.default_matmul_precision(HIGHEST):
        return (jax.nn.silu(z @ w["w_gate"]) * (z @ w["w_up"])) @ w["w_down"]


def layer(x, w, shape, positions):
    """One sandwich-norm block: [L, E] -> [L, E]."""
    eps = float(shape["rms_norm_eps"])
    a = x + rms_norm(attention(rms_norm(x, w["n1"], eps), w, shape,
                               positions), w["n2"], eps)
    return a + rms_norm(swiglu(rms_norm(a, w["n3"], eps), w), w["n4"], eps)


def gate(hidden, weights):
    """``lambda`` [L] of one pass's hidden state."""
    with jax.default_matmul_precision(HIGHEST):
        return jax.nn.sigmoid(hidden @ weights["w_g"] + weights["b_g"])


def exit_masses(lams: list):
    """[passes, L] from the passes' gates: the last takes what is left."""
    masses, stay = [], jnp.ones_like(lams[0])
    for lam in lams[:-1]:
        masses.append(lam * stay)
        stay = stay * (1.0 - lam)
    return jnp.stack(masses + [stay])


def head(hidden, weights):
    with jax.default_matmul_precision(HIGHEST):
        return hidden @ weights["w_head"]


def forward(weights, ids, shape):
    """(logits [L, V], H_last [L, E], exit masses [passes, L]) of one
    sequence ``ids`` [L]."""
    eps = float(shape["rms_norm_eps"])
    positions = jnp.arange(ids.shape[0])
    x = weights["embed"][ids].astype(jnp.float32)
    lams = []
    for _ in range(int(shape["total_ut_steps"])):
        for w in weights["layers"]:
            x = layer(x, w, shape, positions)
        x = rms_norm(x, weights["nf"], eps)
        lams.append(gate(x, weights))
    return head(x, weights), x, exit_masses(lams)


def next_token_logprobs(logits, ids):
    """[L-1]: log p(ids[t+1] | ids[:t+1])."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp[:-1], ids[1:, None], axis=-1)[:, 0]
