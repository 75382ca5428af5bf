"""Operations of a training iteration and bytes of a decode step of the
chip's share of a ``mellum`` model, from its configuration file (the
published keys; ``num_hidden_layers``, ``num_experts`` and
``vocab_size`` as cut, ``layer_types`` whole: the layers held are its
first ``num_hidden_layers`` entries) and what the PROGRAM says of the
batch on its spans (``models/transformer.py::WindowAttention.
forward_attrs`` / ``decode_attrs``): the real tokens, the keys their
queries see on a layer of either kind, the layers of either kind, the
experts held.  Nothing here hard-codes a count.

As ``flops_lfm2.py``: only matrix-product parameters count (2 operations
each per token), the embedding is a gather, recomputation under remat is
not counted.  What differs: tokens are the REAL ones (``seq_tokens``: a
position that holds no token is no useful work), and attention is
counted by (query, key) pairs from the real lengths: a windowed query at
position t has exactly ``min(t + 1, sliding_window)`` keys, a full one
``t + 1`` (``window_keys_seen``, ``causal_keys``: sums over the batch
for ONE layer), 4 x heads x head_dim operations a pair (``q . k`` and
``p v``): never the dense S^2, so masked keys are no part of the count
and a share of the peak from it cannot pass 100%.  The routed experts
count by the (token, choice) pairs computed HERE (``held_share``:
``moe_pairs_here / moe_pairs_total`` from the program's counters, 8 / 64
where the routing is even); the router (all published outputs:
``source_values.num_experts``) by every token; the untied head over the
rows held, at the positions whose logits are read (``head_tokens``: the
completions').
"""

from __future__ import annotations

SLIDING, FULL = "sliding_attention", "full_attention"
#: the span attributes the counts read -> what stands in from the file
KEYS = ("window_layers", "full_layers", "sliding_window", "experts_held",
        "seq_tokens", "window_keys_seen", "causal_keys")


def published(model: dict, key: str) -> float:
    return float(model.get("source_values", {}).get(key, model[key]))


def layer_counts(model: dict) -> dict:
    """{window_layers, full_layers} of the layers held, from the file."""
    types = model["layer_types"][:int(model["num_hidden_layers"])]
    return {"window_layers": float(types.count(SLIDING)),
            "full_layers": float(types.count(FULL))}


def keys_seen(lens, window: int) -> dict:
    """{window_keys_seen, causal_keys, seq_tokens} of sequences of
    ``lens`` real tokens on one layer, as the program's spans count them
    (for a caller without spans: the tests, a forecast)."""
    seen = causal = 0
    for n in lens:
        m = min(int(n), int(window))
        seen += m * (m + 1) // 2 + (int(n) - m) * int(window)
        causal += int(n) * (int(n) + 1) // 2
    return {"window_keys_seen": float(seen), "causal_keys": float(causal),
            "seq_tokens": float(sum(int(n) for n in lens))}


def attention_params(model: dict) -> float:
    h, d = float(model["hidden_size"]), float(model["head_dim"])
    return h * d * (2.0 * float(model["num_attention_heads"])
                    + 2.0 * float(model["num_key_value_heads"]))


def expert_params(model: dict) -> float:
    """One routed expert: gate, up and down."""
    return 3.0 * float(model["hidden_size"]) * float(
        model["moe_intermediate_size"])


def router_params(model: dict) -> float:
    return float(model["hidden_size"]) * published(model, "num_experts")


def matmul_params(model: dict, experts_held: float = None) -> float:
    """Every matrix-product parameter this share holds: what an
    initialised model's tree counts (embedding and untied head both),
    without norm scales."""
    held = float(model["num_experts"] if experts_held is None
                 else experts_held)
    layers = float(model["num_hidden_layers"])
    return (2.0 * float(model["hidden_size"]) * float(model["vocab_size"])
            + layers * (attention_params(model) + router_params(model)
                        + held * expert_params(model)))


def whole_model_params(model: dict) -> float:
    """The published model by the same count: every layer, expert and
    row of the vocabulary (12.1 B for Mellum2-12B-A2.5B)."""
    whole = dict(model, **model.get("source_values", {}))
    whole.pop("source_values", None)
    return matmul_params(whole)


def pair_flops(model: dict) -> float:
    """Operations one (query, key) pair costs a layer: ``q . k`` and
    ``p v`` over every query head."""
    return 4.0 * float(model["num_attention_heads"]) * float(
        model["head_dim"])


def forward_flops(model: dict, counts: dict, held_share: float,
                  head_tokens: float) -> float:
    """One forward over the batch ``counts`` describes (:data:`KEYS`):
    products over its real tokens, attention over the pairs its two
    kinds of layer see, the head at ``head_tokens`` positions."""
    layers = counts["window_layers"] + counts["full_layers"]
    per_token = layers * 2.0 * (
        attention_params(model) + router_params(model)
        + float(model["num_experts_per_tok"]) * held_share
        * expert_params(model))
    pairs = (counts["window_layers"] * counts["window_keys_seen"]
             + counts["full_layers"] * counts["causal_keys"])
    return (counts["seq_tokens"] * per_token + pairs * pair_flops(model)
            + head_tokens * 2.0 * float(model["hidden_size"])
            * float(model["vocab_size"]))


def ppo_iteration_flops(model: dict, samples: int, new_tokens: int,
                        num_epochs: int, held_share: float,
                        counts: dict) -> float:
    """One synchronous PPO iteration with a shared actor-critic trunk,
    as ``flops.ppo_iteration_flops`` counts it: the rollout (prefill and
    ``new_tokens`` steps go over the same tokens and pairs as one whole
    forward; every step reads a logit row), two experience forwards, and
    forward + backward (3x) per epoch, each reading the completions'
    logits."""
    fwd = forward_flops(model, counts, held_share,
                        float(samples) * float(new_tokens))
    return fwd * (1.0 + 2.0 + 3.0 * float(num_epochs))


def slot_bytes(model: dict, dtype_bytes: float = 2.0) -> float:
    """K and V of one token on one layer: 2048 bytes at 4 key heads of
    128 in bfloat16."""
    return 2.0 * float(model["num_key_value_heads"]) * float(
        model["head_dim"]) * dtype_bytes


def decode_step_bytes(weight_bytes: float, batch_slot_bytes: float,
                      attrs: dict) -> float:
    """What one decode step moves: the decode copy of the weights once
    and, for every row, the slots the step reads on its layers of either
    kind (``kv_slots_read_window`` / ``kv_slots_read_full``: means over
    rows and steps from the real lengths, so the sum over the steps is
    ``new_tokens`` times this).  ``batch_slot_bytes``: K and V of one
    slot of one layer over the whole batch."""
    return weight_bytes + batch_slot_bytes * (
        float(attrs["window_layers"]) * float(attrs["kv_slots_read_window"])
        + float(attrs["full_layers"]) * float(attrs["kv_slots_read_full"]))
