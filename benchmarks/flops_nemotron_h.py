"""Operations the chip's share of a ``nemotron_h`` model needs per
token, from its configuration file (the published keys;
``num_hidden_layers``, ``vocab_size`` as cut, ``hybrid_override_pattern``
whole: the layers held are its first ``num_hidden_layers`` characters)
and the share the PROGRAM says it holds: ``held`` = the ``update``
span's ``heads_held``, ``groups_held``, ``attn_heads_held``,
``kv_heads_held``, ``experts_held`` (``trainers/base.py::
share_counters``).  The file's own counts (``mamba_num_heads``,
``n_groups``, ``num_attention_heads``, ``num_key_value_heads``,
``n_routed_experts``: those held, listed in ``reduced``) stand in where
a span lacks one; nothing here hard-codes a count.

As ``flops_kimi_linear.py``: only matrix-product parameters count (2
operations each per token), the embedding is a gather, attention on its
one layer is counted over the context, recomputation under remat is not
counted.  An M layer adds its convolution (2 operations a tap and
channel) and the recurrence's OWN operations per head and token: the
decay of the state (``P N``), the rank-one update and the output ``S C``
(``2 P N`` each): ``5 P N``, and ``D x`` (``2 P``).  What the chunked
form computes on top of that (a chunk's ``C B^T`` and decay-weighted
products, ``ops/mamba2.py``) is the implementation's to pay.  The routed
experts count by the (token, choice) pairs computed HERE
(``held_share``: ``moe_pairs_here / moe_pairs_total`` from the program's
counters, 8 / 512 where the routing is even), in the latent; the latent
projections, the shared expert and the router (all published outputs:
``source_values.n_routed_experts``) by every token.
"""

from __future__ import annotations

KEYS = {"heads_held": "mamba_num_heads", "groups_held": "n_groups",
        "attn_heads_held": "num_attention_heads",
        "kv_heads_held": "num_key_value_heads",
        "experts_held": "n_routed_experts"}


def share(model: dict, held: dict = None) -> dict:
    """{heads_held, groups_held, attn_heads_held, kv_heads_held,
    experts_held} as floats: the program's where ``held`` has them, the
    configuration file's counts else."""
    held = held or {}
    return {k: float(held.get(k, model[key])) for k, key in KEYS.items()}


def layer_chars(model: dict) -> str:
    return model["hybrid_override_pattern"][:int(model["num_hidden_layers"])]


def router_width(model: dict) -> float:
    return float(model.get("source_values", {}).get(
        "n_routed_experts", model["n_routed_experts"]))


def mamba_params(model: dict, s: dict) -> float:
    """Matrix-product parameters of an M layer: the one input projection
    (z | x | B | C | dt) and the output projection."""
    h = float(model["hidden_size"])
    d_in = s["heads_held"] * float(model["mamba_head_dim"])
    gn = s["groups_held"] * float(model["ssm_state_size"])
    return h * (2.0 * d_in + 2.0 * gn + s["heads_held"]) + d_in * h


def mamba_flops_per_token_outside_products(model: dict, s: dict) -> float:
    """The convolution and the recurrence's own operations."""
    p, n = float(model["mamba_head_dim"]), float(model["ssm_state_size"])
    channels = s["heads_held"] * p + 2.0 * s["groups_held"] * n
    return 2.0 * float(model["conv_kernel"]) * channels \
        + s["heads_held"] * (5.0 * p * n + 2.0 * p)


def attention_params(model: dict, s: dict) -> float:
    h, d = float(model["hidden_size"]), float(model["head_dim"])
    return h * d * (2.0 * s["attn_heads_held"] + 2.0 * s["kv_heads_held"])


def expert_params(model: dict) -> float:
    """One routed expert: up and down, in the latent."""
    return 2.0 * float(model["moe_latent_size"]) * float(
        model["moe_intermediate_size"])


def expert_layer_params_outside_experts(model: dict) -> float:
    """Latent projections, shared expert, router."""
    h = float(model["hidden_size"])
    return (2.0 * h * float(model["moe_latent_size"])
            + 2.0 * h * float(model["n_shared_experts"])
            * float(model["moe_shared_expert_intermediate_size"])
            + h * router_width(model))


def matmul_params(model: dict, held: dict = None) -> float:
    """Every matrix-product parameter this share holds: what an
    initialised model's tree counts, without embedding, norm scales,
    convolutions, ``A_log``, ``D``, ``dt_bias`` and the selection
    bias."""
    s = share(model, held)
    total = float(model["hidden_size"]) * float(model["vocab_size"])
    for c in layer_chars(model):
        total += {"M": lambda: mamba_params(model, s),
                  "*": lambda: attention_params(model, s),
                  "E": lambda: expert_layer_params_outside_experts(model)
                  + s["experts_held"] * expert_params(model)}[c]()
    return total


def forward_flops_per_token(model: dict, context: float, held_share: float,
                            held: dict = None) -> float:
    """``held_share``: the share of a token's selected experts that are
    held here."""
    s = share(model, held)
    per_key = 2.0 * s["attn_heads_held"] * float(model["head_dim"])
    flops = 2.0 * float(model["hidden_size"]) * float(model["vocab_size"])
    for c in layer_chars(model):
        if c == "M":
            flops += 2.0 * mamba_params(model, s) \
                + mamba_flops_per_token_outside_products(model, s)
        elif c == "*":
            flops += 2.0 * attention_params(model, s) \
                + 2.0 * per_key * context
        else:
            flops += 2.0 * (expert_layer_params_outside_experts(model)
                            + float(model["num_experts_per_tok"])
                            * held_share * expert_params(model))
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
