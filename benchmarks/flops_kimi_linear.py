"""Operations the chip's share of a ``kimi_linear`` model needs per
token, from its configuration file alone (the published keys;
``num_experts`` counts the experts held here, ``source_values`` has the
router's published width).

As ``flops_dsv3.py``: only matrix-product parameters count (2
operations each per token), the embedding is a gather, the routed
experts count by the (token, choice) pairs computed HERE
(``moe_pairs_here / moe_pairs_total`` from the program's counters), the
latent layers' attention is the expanded form over the context, and
recomputation under remat is not counted.  A KDA layer adds its three
depthwise convolutions (2 operations a tap and channel) and the
recurrence's OWN operations per head and token: the decay of the state
(``dk * dv``), the prediction ``S^T k``, the rank-one update and the
output ``S^T q`` (``2 * dk * dv`` each): ``7 * dk * dv``.  What the
chunked form computes on top of that (the chunk's pair products and the
inverse of its triangular system, ``ops/kda.py``) is the
implementation's to pay, as padding inside a kernel is.
"""

from __future__ import annotations


def _lin(model: dict) -> dict:
    return model["linear_attn_config"]


def kda_params(model: dict) -> float:
    """Matrix-product parameters of a KDA mixer."""
    h = float(model["hidden_size"])
    heads, d = float(_lin(model)["num_heads"]), float(_lin(model)["head_dim"])
    wide = heads * d
    return (3.0 * h * wide            # q, k, v
            + wide * h                # o
            + 2.0 * (h * d + d * wide)   # the decay's and the output's gates
            + h * heads)              # beta


def kda_flops_per_token_outside_products(model: dict) -> float:
    """The convolutions and the recurrence's own operations."""
    heads, d = float(_lin(model)["num_heads"]), float(_lin(model)["head_dim"])
    taps = float(_lin(model)["short_conv_kernel_size"])
    return 2.0 * taps * 3.0 * heads * d + heads * 7.0 * d * d


def latent_params(model: dict) -> float:
    h, heads = float(model["hidden_size"]), float(model["num_attention_heads"])
    rank = float(model["kv_lora_rank"])
    dn, dr, dv = (float(model["qk_nope_head_dim"]),
                  float(model["qk_rope_head_dim"]), float(model["v_head_dim"]))
    return (h * heads * (dn + dr)            # q_proj
            + h * (rank + dr)                # kv_a_proj_with_mqa
            + rank * heads * (dn + dv)       # kv_b_proj
            + heads * dv * h)                # o_proj


def router_width(model: dict) -> float:
    return float(model.get("source_values", {}).get(
        "num_experts", model["num_experts"]))


def expert_params(model: dict) -> float:
    """One routed expert: gate, up and down."""
    return 3.0 * float(model["hidden_size"]) * float(
        model["moe_intermediate_size"])


def ffn_params_outside_experts(model: dict, dense: bool) -> float:
    h = float(model["hidden_size"])
    if dense:
        return 3.0 * h * float(model["intermediate_size"])
    return (3.0 * h * float(model["num_shared_experts"])
            * float(model["moe_intermediate_size"])
            + h * router_width(model))


def layers_of(model: dict):
    """[(mixer, dense)] for the layers held here: mixer "kda" or
    "latent" by the published 1-based lists."""
    lin = _lin(model)
    return [("kda" if i + 1 in lin["kda_layers"] else "latent",
             i < int(model["first_k_dense_replace"]))
            for i in range(int(model["num_hidden_layers"]))]


def matmul_params(model: dict) -> float:
    """Every matrix-product parameter this share holds: what an
    initialised model's tree counts, without embedding, norm scales,
    convolutions, ``A_log``, ``dt_bias`` and the selection bias."""
    total = float(model["hidden_size"]) * float(model["vocab_size"])
    for mixer, dense in layers_of(model):
        total += kda_params(model) if mixer == "kda" else latent_params(model)
        total += ffn_params_outside_experts(model, dense)
        if not dense:
            total += float(model["num_experts"]) * expert_params(model)
    return total


def forward_flops_per_token(model: dict, context: float,
                            held_share: float) -> float:
    """``held_share``: the share of a token's selected experts that are
    held here (1/32 where 8 of 256 are held and the routing is even)."""
    heads = float(model["num_attention_heads"])
    per_key = heads * (float(model["qk_nope_head_dim"])
                       + float(model["qk_rope_head_dim"])
                       + float(model["v_head_dim"]))
    routed = float(model["num_experts_per_token"]) * held_share \
        * expert_params(model)
    flops = 2.0 * float(model["hidden_size"]) * float(model["vocab_size"])
    for mixer, dense in layers_of(model):
        if mixer == "kda":
            flops += 2.0 * kda_params(model) \
                + kda_flops_per_token_outside_products(model)
        else:
            flops += 2.0 * latent_params(model) + 2.0 * per_key * context
        flops += 2.0 * (ffn_params_outside_experts(model, dense)
                        + (0.0 if dense else routed))
    return flops


def ppo_iteration_flops(model: dict, samples: int, prompt_len: int,
                        new_tokens: int, num_epochs: int,
                        held_share: float) -> float:
    """One synchronous PPO iteration with a shared actor-critic trunk,
    as ``flops.ppo_iteration_flops`` counts it: rollout, two experience
    forwards, and forward + backward (3x) per epoch; causal attention
    sees half the sequence on average."""
    seq = prompt_len + new_tokens
    fwd = forward_flops_per_token(model, seq / 2.0, held_share)
    return samples * seq * fwd * (1.0 + 2.0 + 3.0 * num_epochs)
