"""Operations the chip's share of an ``lfm2_moe`` model needs per token,
from its configuration file (the published keys; ``num_hidden_layers``,
``num_experts`` and ``vocab_size`` as cut, ``layer_types`` whole: the
layers held are its first ``num_hidden_layers`` entries) and the share
the PROGRAM says it holds: the ``update`` span's ``experts_held``
(``models/transformer.py::ShortConv.forward_attrs``).  The file's own
``num_experts`` (those held, listed in ``reduced``) stands in where a
span lacks it; nothing here hard-codes a count.

As ``flops_nemotron_h.py``: only matrix-product parameters count (2
operations each per token), the embedding is a gather, attention on its
layers is counted over the context, recomputation under remat is not
counted.  A convolution layer adds what lies between its two
projections: ``b * z`` and ``c * v`` (one operation a channel each) and
the taps (2 operations a tap and channel): ``(2 conv_L_cache + 2)
hidden``.  The routed experts count by the (token, choice) pairs
computed HERE (``held_share``: ``moe_pairs_here / moe_pairs_total`` from
the program's counters, 8 / 32 where the routing is even); the router
(all published outputs: ``source_values.num_experts``) and the dense
layers' MLPs by every token; the tied head over the rows held.
"""

from __future__ import annotations

KEYS = {"experts_held": "num_experts"}


def share(model: dict, held: dict = None) -> dict:
    """{experts_held} as floats: the program's where ``held`` has it,
    the configuration file's count else."""
    held = held or {}
    return {k: float(held.get(k, model[key])) for k, key in KEYS.items()}


def layer_kinds(model: dict) -> list:
    """[(layer type, whether its feed-forward half is dense)] of the
    layers held."""
    dense = int(model["num_dense_layers"])
    return [(t, i < dense) for i, t in enumerate(
        model["layer_types"][:int(model["num_hidden_layers"])])]


def published(model: dict, key: str) -> float:
    return float(model.get("source_values", {}).get(key, model[key]))


def head_dim(model: dict) -> float:
    return float(model["hidden_size"]) / float(model["num_attention_heads"])


def conv_params(model: dict) -> float:
    """The in-projection (hidden -> 3 hidden) and the out-projection."""
    return 4.0 * float(model["hidden_size"]) ** 2


def conv_flops_per_token_outside_products(model: dict) -> float:
    """The two gates and the taps."""
    return (2.0 * float(model["conv_L_cache"]) + 2.0) \
        * float(model["hidden_size"])


def attention_params(model: dict) -> float:
    h, d = float(model["hidden_size"]), head_dim(model)
    return h * d * (2.0 * float(model["num_attention_heads"])
                    + 2.0 * float(model["num_key_value_heads"]))


def dense_mlp_params(model: dict) -> float:
    return 3.0 * float(model["hidden_size"]) * float(
        model["intermediate_size"])


def expert_params(model: dict) -> float:
    """One routed expert: gate, up and down."""
    return 3.0 * float(model["hidden_size"]) * float(
        model["moe_intermediate_size"])


def router_params(model: dict) -> float:
    return float(model["hidden_size"]) * published(model, "num_experts")


def mixer_params(model: dict, layer_type: str) -> float:
    return conv_params(model) if layer_type == "conv" \
        else attention_params(model)


def matmul_params(model: dict, held: dict = None) -> float:
    """Every matrix-product parameter this share holds: what an
    initialised model's tree counts, the embedding once (it is the
    head), without norm scales, taps and the selection bias."""
    s = share(model, held)
    total = float(model["hidden_size"]) * float(model["vocab_size"])
    for layer_type, dense in layer_kinds(model):
        total += mixer_params(model, layer_type) + (
            dense_mlp_params(model) if dense else router_params(model)
            + s["experts_held"] * expert_params(model))
    return total


def whole_model_params(model: dict) -> float:
    """The published model by the same count: every layer, expert and
    row of the vocabulary (8.34 B for LFM2-8B-A1B, the head tied)."""
    whole = dict(model, **model.get("source_values", {}))
    whole.pop("source_values", None)
    return matmul_params(whole)


def forward_flops_per_token(model: dict, context: float, held_share: float,
                            held: dict = None) -> float:
    """``held_share``: the share of a token's selected experts that are
    held here."""
    per_key = 2.0 * float(model["num_attention_heads"]) * head_dim(model)
    flops = 2.0 * float(model["hidden_size"]) * float(model["vocab_size"])
    for layer_type, dense in layer_kinds(model):
        flops += 2.0 * mixer_params(model, layer_type)
        flops += conv_flops_per_token_outside_products(model) \
            if layer_type == "conv" else 2.0 * per_key * context
        flops += 2.0 * (dense_mlp_params(model) if dense
                        else router_params(model)
                        + float(model["num_experts_per_tok"]) * held_share
                        * expert_params(model))
    return flops


def ppo_iteration_flops(model: dict, samples: int, prompt_len: int,
                        new_tokens: int, num_epochs: int,
                        held_share: float, held: dict = None) -> float:
    """One synchronous PPO iteration with a shared actor-critic trunk,
    as ``flops.ppo_iteration_flops`` counts it: rollout, two experience
    forwards, and forward + backward (3x) per epoch; causal attention
    sees half the sequence on average."""
    seq = prompt_len + new_tokens
    fwd = forward_flops_per_token(model, seq / 2.0, held_share, held)
    return samples * seq * fwd * (1.0 + 2.0 + 3.0 * num_epochs)
