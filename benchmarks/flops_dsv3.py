"""Operations the chip's share of a ``deepseek_v3`` model needs per
token, from its configuration file alone (the published keys;
``n_routed_experts`` counts the experts held here, ``source_values``
has the router's published width).

Only matrix-product parameters count (2 operations each per token), as
in ``flops.py``; the embedding is a gather.  The routed experts count by
the (token, choice) pairs that are computed HERE: a token selects
``num_experts_per_tok`` of all experts, and the share of those pairs
that lands on a held expert is read from the program's counters
(``moe_pairs_here / moe_pairs_total``), not assumed.  Attention is the
expanded form the training path runs (keys of nope + rope, values of
``v_head_dim``, every head); recomputation under remat is not counted.
"""

from __future__ import annotations


def attention_params(model: dict) -> float:
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
        "n_routed_experts", model["n_routed_experts"]))


def expert_params(model: dict) -> float:
    """One routed expert: gate, up and down."""
    return 3.0 * float(model["hidden_size"]) * float(
        model["moe_intermediate_size"])


def expert_layer_params_outside_experts(model: dict) -> float:
    h = float(model["hidden_size"])
    shared = 3.0 * h * float(model["n_shared_experts"]) * float(
        model["moe_intermediate_size"])
    return attention_params(model) + shared + h * router_width(model)


def dense_layer_params(model: dict) -> float:
    return attention_params(model) + 3.0 * float(model["hidden_size"]) \
        * float(model["intermediate_size"])


def layers_of(model: dict):
    """(leading dense layers, expert layers)."""
    dense = min(int(model["first_k_dense_replace"]),
                int(model["num_hidden_layers"]))
    return dense, int(model["num_hidden_layers"]) - dense


def matmul_params(model: dict) -> float:
    """Every matrix-product parameter this share holds: what an
    initialised model's tree counts, without embedding, norm scales and
    the selection bias."""
    dense, moe = layers_of(model)
    return (dense * dense_layer_params(model)
            + moe * (expert_layer_params_outside_experts(model)
                     + float(model["n_routed_experts"])
                     * expert_params(model))
            + float(model["hidden_size"]) * float(model["vocab_size"]))


def forward_flops_per_token(model: dict, context: float,
                            held_share: float) -> float:
    """``held_share``: the share of a token's selected experts that are
    held here (1/8 where 16 of 128 are held and the routing is even)."""
    dense, moe = layers_of(model)
    heads = float(model["num_attention_heads"])
    per_key = heads * (float(model["qk_nope_head_dim"])
                       + float(model["qk_rope_head_dim"])
                       + float(model["v_head_dim"]))
    routed = float(model["num_experts_per_tok"]) * held_share \
        * expert_params(model)
    params = (dense * dense_layer_params(model)
              + moe * (expert_layer_params_outside_experts(model) + routed)
              + float(model["hidden_size"]) * float(model["vocab_size"]))
    return 2.0 * params + 2.0 * (dense + moe) * per_key * context


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
