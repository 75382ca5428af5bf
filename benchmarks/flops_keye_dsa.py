"""Operations the chip's share of a ``keye_dsa`` model (Keye-VL-2.0's
language model) needs per training iteration, from its configuration
file and the lengths the program's spans carry.  One function of shapes,
the same whatever implements the model:

- matrix-product parameters count 2 operations a token (as ``flops.py``;
  the embedding is a gather); the routed experts by the (token, choice)
  pairs computed HERE (``held_share``: ``moe_pairs_here /
  moe_pairs_total`` of the program's counters, not assumed);
- attention over the SELECTED keys: a (query, kept key) pair costs ``2
  heads (head_dim + head_dim)`` forward.  A program that computes every
  causal pair and masks does more arithmetic for the same work and
  reads lower; one that skips reads higher, under 100% either way;
- the indexer's scores over the CAUSAL pairs (it has to score a key to
  reject it): ``2 indexer_heads (indexer_head_dim + 1)`` a pair, in
  forwards only (the selection takes no gradient);

with the pairs counted from real lengths: ``keys_valid`` and
``keys_selected`` are the sums, over the real queries of ONE
whole-sequence forward of the iteration's batch, of a query's valid
keys and of those it keeps (the ``update`` span's ``sa_keys_valid`` /
``sa_keys_selected``).  Recomputation under remat is not counted.
"""

from __future__ import annotations


def attention_params(model: dict) -> float:
    h, d = float(model["hidden_size"]), float(model["head_dim"])
    heads, kv = (float(model["num_attention_heads"]),
                 float(model["num_key_value_heads"]))
    return h * heads * d + 2.0 * h * kv * d + heads * d * h


def indexer_params(model: dict) -> float:
    sa, h = model["sa_config"], float(model["hidden_size"])
    heads, dim = float(sa["indexer_num_heads"]), float(sa["indexer_head_dim"])
    return h * heads * dim + h * dim * float(sa["indexer_num_kv_heads"]) \
        + h * heads


def router_width(model: dict) -> float:
    return float(model.get("source_values", {}).get(
        "num_experts", model["num_experts"]))


def expert_params(model: dict) -> float:
    """One routed expert: gate, up and down."""
    return 3.0 * float(model["hidden_size"]) * float(
        model["moe_intermediate_size"])


def layer_params_outside_experts(model: dict) -> float:
    return attention_params(model) + indexer_params(model) \
        + float(model["hidden_size"]) * router_width(model)


def matmul_params(model: dict) -> float:
    """Every matrix-product parameter this share holds: what an
    initialised model's tree counts, without embedding and norms."""
    return (float(model["num_hidden_layers"])
            * (layer_params_outside_experts(model)
               + float(model["num_experts"]) * expert_params(model))
            + float(model["hidden_size"]) * float(model["vocab_size"]))


def whole_model_params(model: dict) -> float:
    """The published model's parameters (every layer, every expert, both
    embeddings, the norms): 30.6 B."""
    src = dict(model, **model.get("source_values", {}))
    h, d = float(src["hidden_size"]), float(src["head_dim"])
    norms = 2.0 * h + 2.0 * d + 2.0 * float(
        src["sa_config"]["indexer_head_dim"])
    return (float(src["num_hidden_layers"])
            * (layer_params_outside_experts(src) + norms
               + float(src["num_experts"]) * expert_params(src))
            + 2.0 * h * float(src["vocab_size"]) + h)


def pair_flops(model: dict):
    """(a kept (query, key) pair of the attention forward, a causal
    (query, key) pair of the indexer)."""
    sa = model["sa_config"]
    kept = 2.0 * float(model["num_attention_heads"]) * 2.0 * float(
        model["head_dim"])
    scored = 2.0 * float(sa["indexer_num_heads"]) * (
        float(sa["indexer_head_dim"]) + 1.0)
    return kept, scored


def ppo_iteration_flops(model: dict, samples: int, prompt_len: int,
                        new_tokens: int, num_epochs: int,
                        held_share: float, keys_valid: float,
                        keys_selected: float) -> float:
    """One synchronous PPO iteration with a shared actor-critic trunk,
    as ``flops.ppo_iteration_flops`` counts it: the rollout as one
    forward over the whole sequences, two experience forwards, and
    forward + backward (3x) per epoch; the indexer in the forwards
    alone.  ``samples * (prompt_len + new_tokens)`` tokens pass the
    products (padding included: the program multiplies it too, but
    routes it to no expert: ``held_share`` is of all pairs)."""
    layers = float(model["num_hidden_layers"])
    tokens = float(samples) * float(prompt_len + new_tokens)
    routed = float(model["num_experts_per_tok"]) * held_share \
        * expert_params(model)
    params = layers * (attention_params(model)
                       + float(model["hidden_size"]) * router_width(model)
                       + routed) \
        + float(model["hidden_size"]) * float(model["vocab_size"])
    kept, scored = pair_flops(model)
    passes = 1.0 + 2.0 + 3.0 * num_epochs
    forwards = 1.0 + 2.0 + 1.0 * num_epochs
    return (passes * (2.0 * params * tokens
                      + layers * kept * float(keys_selected))
            + forwards * layers * (2.0 * indexer_params(model) * tokens
                                   + scored * float(keys_valid)))
