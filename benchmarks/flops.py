"""Operations a model needs per token, from its configuration alone.

A copy of ``bench.py``'s arithmetic (``flops_per_sample``), with one
departure: ``bench.py`` counts 2 operations per parameter including the
input embedding table, which is a gather and not a matrix product; here
only matrix-product parameters count (the per-layer projections and the
output head).  Recomputation under remat is not counted (the MFU
convention).
"""

from __future__ import annotations


def matmul_params(model: dict) -> float:
    """Parameters that take part in matrix products of one forward pass
    of a GPT-NeoX model: q, k, v, o (4 h^2) and the two MLP matrices
    (2 h ffn) per layer, and the untied output head (h vocab)."""
    h = float(model["hidden_size"])
    ffn = float(model["intermediate_size"])
    layers = float(model["num_hidden_layers"])
    return layers * (4.0 * h * h + 2.0 * h * ffn) \
        + h * float(model["vocab_size"])


def forward_flops_per_token(model: dict, context: float) -> float:
    """2 per matmul parameter, plus attention scores and values against
    ``context`` keys: 2 * 2 * h * context per layer."""
    h = float(model["hidden_size"])
    layers = float(model["num_hidden_layers"])
    return 2.0 * matmul_params(model) + 4.0 * layers * h * context


def ppo_iteration_flops(model: dict, samples: int, prompt_len: int,
                        new_tokens: int, num_epochs: int = 1) -> float:
    """One synchronous PPO iteration with a shared actor-critic trunk:
    rollout (prefill + one forward per new token), two experience
    forwards over the whole sequence (policy+values, reference), and a
    forward+backward (3x a forward) per epoch.  Causal attention sees
    on average half the sequence."""
    seq = prompt_len + new_tokens
    fwd = forward_flops_per_token(model, seq / 2.0)
    rollout = fwd * seq
    experience = 2.0 * fwd * seq
    update = num_epochs * 3.0 * fwd * seq
    return samples * (rollout + experience + update)
