"""Plain reference: the Nemotron-H (Nemotron-3) language model's forward
pass in float32 ``jax.numpy``.

Written from the published configuration keys (``model_type:
nemotron_h``; the catalog row of
nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16) and the family's
modelling code AS REMEMBERED (there is no network here; every remembered
or chosen point is listed in the configuration file under ``assumed``).

Every published layer is ``h <- h + part(RMSNorm(h))`` (eps
``layer_norm_epsilon``), ``part`` one of three by the layer's character
in ``hybrid_override_pattern``; a final RMSNorm and an untied output
projection; no bias but the convolution's.

- **M, Mamba-2** (H = ``mamba_num_heads``, P = ``mamba_head_dim``, G =
  ``n_groups``, N = ``ssm_state_size``, ``conv_kernel`` taps).  With u
  the normed input: ``[z | xBC | dt] = u W_in`` of widths H P, H P + 2 G
  N, H; ``xBC <- silu(conv(xBC) + b_conv)``, ONE depthwise causal
  convolution over all H P + 2 G N channels; ``[x | B | C] = xBC``, x as
  [H, P], B and C as [G, N], head h reads group ``h // (H / G)``;
  ``dt_t = softplus(dt_t + dt_bias)``, ``a_t = exp(dt_t A)``, ``A =
  -exp(A_log)``, one scalar a head; ``S_t = a_t S_{t-1} + (dt_t x_t)
  B_t^T`` with S [P, N], zero before the first token; ``y_t = S_t C_t +
  D x_t``; ``out = RMSNorm_g(y * silu(z)) W_out``: the gate first, then
  a norm over each group's (H / G) P channels with a learned weight.
- **\\*, attention**: ``num_attention_heads`` query heads against
  ``num_key_value_heads`` key-value heads of ``head_dim``, no bias, no
  q/k norm, causal, scale ``head_dim^-1/2``, a half-split rotary
  embedding over the whole head at ``rope_theta`` (ASSUMED to be
  applied: the config states ``rope_theta`` and ``partial_rotary_factor``
  1, and earlier models of the family ran these layers without
  positions; ``rotary=False`` computes that other model).
- **E, LatentMoE**: ``s = sigmoid(x W_r)``; the ``num_experts_per_tok``
  largest of ``s + b`` (no group limit: ``n_group = topk_group = 1``);
  ``g_e = routed_scaling_factor * s_e / sum_chosen s``; ``x_l = x W_1``
  (hidden -> ``moe_latent_size``); expert e: ``W_down,e relu(W_up,e
  v)^2``, no gate (``mlp_hidden_act: relu2``); the routed part ``(sum_e
  g_e E_e(x_l)) W_2`` (latent -> hidden); the shared expert on x
  itself, ``W_sd relu(W_su x)^2``; the layer's output is their sum
  (ASSUMED: router and shared expert read x, only the routed experts the
  latent).  No auxiliary loss.

The recurrence runs token by token (``lax.scan`` over the positions of
one sequence, the body the two lines above), the attention matrix is
materialised whole, every held expert is computed for every token.  No
kernel, no cache, no chunking, no batching.  It imports nothing from
``orion_tpu``; the norm, the embedding, the head and the logprobs are
``reference_dsv3.py``'s and the token-by-token convolution over the
positions that hold a token ``reference_kimi_linear.py``'s, beside this
file.  Every matrix product runs under
``jax.default_matmul_precision("highest")``.

**The share.**  ``shape`` is the configuration file: the published keys,
with ``mamba_num_heads``, ``n_groups``, ``num_attention_heads``,
``num_key_value_heads`` counting the heads HELD here and ``held =
(offset, count)`` the routed experts held here.  What the absent heads
and experts would add is left out: a mixer's output is the sum over the
held heads' rows of its output projection, the expert layer's the held
experts' part plus the shared expert.  Given every head and expert it is
the whole model.

Departures from the published code, none of them mathematics:

- ``mask`` [L] bool: a position that holds no token leaves S untouched
  (``dt = 0``) and the convolution's window skips it;
- the convolution's weight arrives as ``[taps, channels]``, the tap that
  multiplies the current token last;
- the multi-token-prediction module (``num_nextn_predict_layers`` 1,
  ``mtp_hybrid_override_pattern`` "*E") is not here: its equations are
  not in the config, it stands behind layer 88 and takes no part in a
  policy's logprobs.

Two arguments compute what this model is NOT, for comparisons that ask
which of two a program computes: ``act`` ("relu": the square skipped;
"silu_gate": ``silu(u) * u``, what a SwiGLU's code makes of one
product) and ``group_map="interleaved"`` (head h reads group ``h % G``:
B and C taken a head at a time, not a group).
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
rms_norm, embed = dsv3.rms_norm, dsv3.embed
next_token_logprobs = dsv3.next_token_logprobs
short_conv = _sibling("reference_kimi_linear").short_conv

ACTS = {"relu2": lambda u: jnp.square(jax.nn.relu(u)),
        "relu": jax.nn.relu,
        "silu_gate": lambda u: jax.nn.silu(u) * u}


def layer_chars(shape: dict) -> str:
    """The characters of the layers held here: the pattern's first
    ``num_hidden_layers``."""
    return shape["hybrid_override_pattern"][:int(shape["num_hidden_layers"])]


def moe_shape(shape: dict) -> dict:
    """The router's numbers under the keys ``reference_dsv3.route``
    reads."""
    return {"num_experts_per_tok": shape["num_experts_per_tok"],
            "routed_scaling_factor": shape["routed_scaling_factor"]}


def ssm_scan(x, dt, A, B, C, D, state=None, group_map: str = "block"):
    """The recurrence, token by token.  x [L, H, P]; dt [L, H] (after
    the softplus; 0 where a position holds no token); A, D [H]; B, C
    [L, G, N].  Returns (y [L, H, P], the state after the last position
    [H, P, N])."""
    H, P = x.shape[1:]
    G, N = B.shape[1:]
    heads = jnp.arange(H)
    group = heads // (H // G) if group_map == "block" else heads % G
    if state is None:
        state = jnp.zeros((H, P, N), jnp.float32)

    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[group][:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, C_t[group]) + D[:, None] * x_t

    state, y = jax.lax.scan(step, state, (x, dt, B, C))
    return y, state


def mamba2(u, w, shape, mask, group_map: str = "block"):
    """The Mamba-2 mixer on u [L, hidden], normed."""
    H, P = int(shape["mamba_num_heads"]), int(shape["mamba_head_dim"])
    G, N = int(shape["n_groups"]), int(shape["ssm_state_size"])
    L, d_in = u.shape[0], H * P
    z, xBC, dt = jnp.split(u @ w["w_in"], (d_in, 2 * d_in + 2 * G * N),
                           axis=-1)
    xBC = jax.nn.silu(short_conv(xBC, w["conv_w"], mask) + w["conv_b"])
    x, B, C = jnp.split(xBC, (d_in, d_in + G * N), axis=-1)
    dt = jnp.where(mask[:, None], jax.nn.softplus(dt + w["dt_bias"]), 0.0)
    y, _ = ssm_scan(x.reshape(L, H, P), dt, -jnp.exp(w["A_log"]),
                    B.reshape(L, G, N), C.reshape(L, G, N), w["D"],
                    group_map=group_map)
    y = (y.reshape(L, d_in) * jax.nn.silu(z)).reshape(L, G, d_in // G)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + float(shape["layer_norm_epsilon"]))
    return (y.reshape(L, d_in) * w["norm_g"]) @ w["w_out"]


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


def attention(u, w, shape, rotary: bool = True):
    """Grouped-query attention on u [L, hidden], normed: query head h
    reads key-value head ``h // (Hq / Hkv)``."""
    L = u.shape[0]
    Hq, Hkv = (int(shape["num_attention_heads"]),
               int(shape["num_key_value_heads"]))
    d = int(shape["head_dim"])
    q = (u @ w["wq"]).reshape(L, Hq, d)
    k = (u @ w["wk"]).reshape(L, Hkv, d)
    v = (u @ w["wv"]).reshape(L, Hkv, d)
    pos = jnp.arange(L)
    if rotary:
        q, k = (rotate_half(t, pos, float(shape["rope_theta"]))
                for t in (q, k))
    k, v = (jnp.repeat(t, Hq // Hkv, axis=1) for t in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = pos[None, :, None] >= pos[None, None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(L, Hq * d) @ w["wo"]


def latent_moe(x, w, shape, held, selected=None, probe: bool = False,
               act: str = "relu2"):
    """The expert layer on x [L, hidden], normed, for the routed experts
    ``held = (offset, count)``: ``w["e_up"]`` [count, latent, I] and
    ``w["e_down"]`` [count, I, latent] are theirs.  ``selected`` and
    ``probe`` as ``reference_dsv3.expert_ffn``'s (the selection is its
    ``route``: the same router)."""
    offset, count = held
    f = ACTS[act]
    idx, gates, _ = dsv3.route(x, w, moe_shape(shape), selected)
    x_l = x @ w["w_fc1"]
    every = jax.vmap(lambda up, dn: f(x_l @ up) @ dn, out_axes=1)(
        w["e_up"], w["e_down"])                        # [L, count, latent]
    weight = jnp.sum(
        jax.nn.one_hot(idx - offset, count, dtype=jnp.float32)
        * gates[..., None], axis=1)   # one_hot of an index outside is zero
    out = jnp.einsum("lhd,lh->ld", every, weight) @ w["w_fc2"] \
        + f(x @ w["s_up"]) @ w["s_down"]
    return (out, selection_probe(x, w, idx)) if probe else out


def selection_probe(x, w, idx):
    """What a comparison with a program in a lower precision needs to
    know of the discrete selection ``idx`` [L, k], per token: ``margin``,
    ``excess`` [L, k] and ``exchanged`` as ``reference_dsv3.expert_ffn``
    defines them (the same router, the same arithmetic)."""
    k = idx.shape[1]
    scores = jax.nn.sigmoid(x @ w["w_router"])
    biased = scores + w["router_bias"][None, :]
    top, top_idx = jax.lax.top_k(biased, k + 1)
    unit = jnp.sqrt(jnp.square(x) @ jnp.square(w["w_router"]))
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
    return {"margin": margin, "excess": excess, "exchanged": exchanged}


def layer(x, w, shape, char: str, held=None, mask=None, selected=None,
          probe: bool = False, act: str = "relu2",
          group_map: str = "block", rotary: bool = True):
    """One published layer on x [L, hidden], float32: ``x + part(
    RMSNorm(x; w["n_g"]))``, ``char`` its character in the pattern.  Returns ``(y,
    probe info | None)`` under ``probe``, else ``y``."""
    if mask is None:
        mask = jnp.ones((x.shape[0],), bool)
    info = None
    with jax.default_matmul_precision(HIGHEST):
        u = rms_norm(x, w["n_g"], float(shape["layer_norm_epsilon"]))
        if char == "M":
            out = mamba2(u, w, shape, mask, group_map)
        elif char == "*":
            out = attention(u, w, shape, rotary)
        elif char == "E":
            out = latent_moe(u, w, shape, held, selected, probe, act)
            if probe:
                out, info = out
        else:
            raise ValueError(f"unknown layer character {char!r}")
    return (x + out, info) if probe else x + out


def head(x, w, shape):
    """Final RMSNorm and the untied output projection: logits [L, V]."""
    with jax.default_matmul_precision(HIGHEST):
        return rms_norm(x, w["nf_g"], float(shape["layer_norm_epsilon"])) \
            @ w["w_head"]


def forward(weights, ids, shape, held, mask=None, **variant):
    """weights: {"embed", "layers": [one dict a published layer], "nf_g",
    "w_head"}, float32; layer ``i`` is ``layer_chars(shape)[i]``.  ids:
    [L].  Logits [L, V]."""
    x = embed(ids, weights)
    for char, w in zip(layer_chars(shape), weights["layers"]):
        x = layer(x, w, shape, char, held, mask, **variant)
    return head(x, weights, shape)
