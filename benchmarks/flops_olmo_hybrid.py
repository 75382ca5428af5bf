"""Operations an ``olmo_hybrid`` model needs per token, from its
configuration file alone (the published keys; ``num_hidden_layers`` and
``vocab_size`` as cut, ``layer_types`` whole: the layers held are its
first ``num_hidden_layers`` entries).

As ``flops_kimi_linear.py``: only matrix-product parameters count (2
operations each per token), the embedding is a gather, the
full-attention layers' attention is counted over the context, and
recomputation under remat is not counted.  A GDN layer adds its three
depthwise convolutions (2 operations a tap and channel) and the
recurrence's OWN operations per head and token at the true head sizes:
the decay of the state (``dk * dv``), the prediction ``S^T k``, the
rank-one update and the output ``S^T q`` (``2 * dk * dv`` each): ``7 *
dk * dv``.  What the chunked form computes on top of that (a chunk's
pair products and the inverse of its triangular system, ``ops/kda.py``)
and the zero channels that pad 96 x 192 to the kernels' 128 x 256 are
the implementation's to pay.
"""

from __future__ import annotations


def _gdn_dims(model: dict):
    return (float(model["linear_num_key_heads"]),
            float(model["linear_key_head_dim"]),
            float(model["linear_value_head_dim"]))


def gdn_params(model: dict) -> float:
    """Matrix-product parameters of a GDN mixer."""
    h = float(model["hidden_size"])
    heads, dk, dv = _gdn_dims(model)
    return (h * heads * (2.0 * dk + dv)   # q, k, v
            + 2.0 * h * heads * dv        # z (the output gate), o
            + 2.0 * h * heads)            # a (decay), b (step size)


def gdn_flops_per_token_outside_products(model: dict) -> float:
    """The convolutions and the recurrence's own operations."""
    heads, dk, dv = _gdn_dims(model)
    taps = float(model["linear_conv_kernel_dim"])
    return 2.0 * taps * heads * (2.0 * dk + dv) + heads * 7.0 * dk * dv


def attention_params(model: dict) -> float:
    return 4.0 * float(model["hidden_size"]) ** 2      # q, k, v, o


def mlp_params(model: dict) -> float:
    return 3.0 * float(model["hidden_size"]) * float(
        model["intermediate_size"])


def layers_of(model: dict) -> list:
    """The mixers of the layers held here: ``"gdn"`` or ``"attention"``."""
    kinds = {"linear_attention": "gdn", "full_attention": "attention"}
    return [kinds[t] for t in
            model["layer_types"][:int(model["num_hidden_layers"])]]


def matmul_params(model: dict) -> float:
    """Every matrix-product parameter: what an initialised model's tree
    counts, without embedding, norm scales, convolutions, ``A_log`` and
    ``dt_bias``."""
    total = float(model["hidden_size"]) * float(model["vocab_size"])
    for mixer in layers_of(model):
        total += gdn_params(model) if mixer == "gdn" \
            else attention_params(model)
        total += mlp_params(model)
    return total


def forward_flops_per_token(model: dict, context: float) -> float:
    # q.k and p.v over the context, every head of hidden / heads
    per_key = 2.0 * float(model["hidden_size"])
    flops = 2.0 * float(model["hidden_size"]) * float(model["vocab_size"])
    for mixer in layers_of(model):
        if mixer == "gdn":
            flops += 2.0 * gdn_params(model) \
                + gdn_flops_per_token_outside_products(model)
        else:
            flops += 2.0 * attention_params(model) + 2.0 * per_key * context
        flops += 2.0 * mlp_params(model)
    return flops


def ppo_iteration_flops(model: dict, samples: int, prompt_len: int,
                        new_tokens: int, num_epochs: int) -> float:
    """One synchronous PPO iteration with a shared actor-critic trunk,
    as ``flops.ppo_iteration_flops`` counts it: rollout, two experience
    forwards, and forward + backward (3x) per epoch; causal attention
    sees half the sequence on average."""
    seq = prompt_len + new_tokens
    fwd = forward_flops_per_token(model, seq / 2.0)
    return samples * seq * fwd * (1.0 + 2.0 + 3.0 * num_epochs)
