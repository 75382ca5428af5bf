"""Operations of a training iteration and bytes of a decode step of an
``ouro`` configuration (a stack of ``num_hidden_layers`` blocks run
``total_ut_steps`` times over with one set of weights), from its
configuration file (the published keys; ``num_hidden_layers`` as cut)
and what the PROGRAM says of the batch on its spans
(``models/transformer.py::update_attrs`` / ``decode_attrs``,
``rollout/engine.py::dispatch_attrs``): the passes, the layer visits,
the real tokens and their causal keys, the bytes a step reads once a
pass and once.  Nothing here hard-codes a count.

As ``flops_mellum2.py``: only matrix-product parameters count (2
operations each per token), the embedding is a gather, recomputation
under remat is NOT useful work and is not counted.  Every product of a
block is counted once a VISIT (``layer_visits`` = passes x layers: the
weights are shared, the work is not), the untied head ONCE, at the
positions whose logits are read (``head_tokens``: the completions').
Tokens are the REAL ones (``seq_tokens``), and attention is counted by
(query, key) pairs from the real lengths: a query at position t has
exactly ``t + 1`` keys a visit (``causal_keys``: the sum over the batch
for ONE visit), 4 x heads x head_dim operations a pair (``q . k`` and
``p v``): never the dense S^2, so masked keys are no part of the count
and a share of the peak from it cannot pass 100%.  The exit gate (one
product of ``hidden_size`` a token a pass, where it is read at all) is
left out: 1 part in 50 000 of a visit.
"""

from __future__ import annotations

#: the span attributes the iteration's count reads (``update``)
KEYS = ("ut_steps", "layer_visits", "seq_tokens", "causal_keys")
#: and a decode step's bytes (``rollout.dispatch``)
STEP_KEYS = ("ut_steps", "layer_visits", "stack_weight_bytes",
             "once_weight_bytes", "cache_bytes", "kv_step_slots", "batch")


def attention_params(model: dict) -> float:
    h, d = float(model["hidden_size"]), float(model["head_dim"])
    return h * d * (2.0 * float(model["num_attention_heads"])
                    + 2.0 * float(model["num_key_value_heads"]))


def mlp_params(model: dict) -> float:
    """Gate, up and down."""
    return 3.0 * float(model["hidden_size"]) * float(
        model["intermediate_size"])


def layer_params(model: dict) -> float:
    return attention_params(model) + mlp_params(model)


def matmul_params(model: dict) -> float:
    """Every matrix-product parameter held: what an initialised model's
    tree counts (embedding and untied head both, each block once),
    without norm scales and the exit gate."""
    return (2.0 * float(model["hidden_size"]) * float(model["vocab_size"])
            + float(model["num_hidden_layers"]) * layer_params(model))


def whole_model_params(model: dict) -> float:
    """The published model by the same count: every layer (2.67 B for
    Ouro-2.6B)."""
    whole = dict(model, **model.get("source_values", {}))
    whole.pop("source_values", None)
    return matmul_params(whole)


def pair_flops(model: dict) -> float:
    """Operations one (query, key) pair costs a visit: ``q . k`` and
    ``p v`` over every query head."""
    return 4.0 * float(model["num_attention_heads"]) * float(
        model["head_dim"])


def causal_keys(lens) -> dict:
    """{seq_tokens, causal_keys} of sequences of ``lens`` real tokens on
    one visit, as the program's spans count them (for a caller without
    spans: the tests, a forecast)."""
    return {"seq_tokens": float(sum(int(n) for n in lens)),
            "causal_keys": float(sum(int(n) * (int(n) + 1) // 2
                                     for n in lens))}


def forward_flops(model: dict, counts: dict, head_tokens: float) -> float:
    """One forward over the batch ``counts`` describes (:data:`KEYS`):
    every block's products over its real tokens and attention over their
    causal pairs once a visit, the head at ``head_tokens`` positions
    once."""
    visits = float(counts["layer_visits"])
    return (counts["seq_tokens"] * visits * 2.0 * layer_params(model)
            + counts["causal_keys"] * visits * pair_flops(model)
            + head_tokens * 2.0 * float(model["hidden_size"])
            * float(model["vocab_size"]))


def ppo_iteration_flops(model: dict, samples: int, new_tokens: int,
                        num_epochs: int, counts: dict) -> float:
    """One synchronous PPO iteration with a shared actor-critic trunk,
    as ``flops.ppo_iteration_flops`` counts it: the rollout (prefill and
    ``new_tokens`` steps go over the same tokens and pairs as one whole
    forward; every step reads a logit row), two experience forwards, and
    forward + backward (3x) per epoch, each reading the completions'
    logits."""
    fwd = forward_flops(model, counts, float(samples) * float(new_tokens))
    return fwd * (1.0 + 2.0 + 3.0 * float(num_epochs))


def slot_bytes(model: dict, dtype_bytes: float = 2.0) -> float:
    """K and V of one token on one visit: 8192 bytes at 16 key heads of
    128 in bfloat16."""
    return 2.0 * float(model["num_key_value_heads"]) * float(
        model["head_dim"]) * dtype_bytes


def decode_step_bytes(attrs: dict, slots: float) -> float:
    """What one decode step moves, from the ``rollout.dispatch`` span's
    attributes (:data:`STEP_KEYS`): the blocks' weights once a pass, what
    stands behind the stack once, and for every row and visit the slots
    the step read (``kv_step_slots``: the mean over the steps from the
    real lengths, so the sum over the steps is ``new_tokens`` times
    this).  ``slots``: the cache's slots a row (``cache_bytes`` is every
    visit's entry over the whole batch)."""
    slot_batch = float(attrs["cache_bytes"]) / (
        float(attrs["layer_visits"]) * slots)
    return (float(attrs["ut_steps"]) * float(attrs["stack_weight_bytes"])
            + float(attrs["once_weight_bytes"])
            + float(attrs["kv_step_slots"]) * slot_batch
            * float(attrs["layer_visits"]))
