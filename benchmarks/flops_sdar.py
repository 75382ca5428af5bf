"""Operations the chip's share of an ``sdar_moe`` model (SDAR: the
Qwen3-MoE block generating by diffusion over blocks) needs per training
iteration, from its configuration file and what the program's spans
carry.  One function of shapes, the same whatever implements the model:

- matrix-product parameters count 2 operations an entry (as
  ``flops.py``; the embedding is a gather); the routed experts by the
  (entry, choice) pairs computed HERE (``held_share``: ``moe_pairs_here
  / moe_pairs_total`` of the program's counters, not assumed);
- attention by the (query, key) pairs the masks leave: a pair costs ``2
  heads (head_dim + head_dim)`` forward.  In the rollout a query of a
  block's forward has the keys through its block's end
  (``decode_pairs`` of ``rollout.dispatch``: prefill, and
  ``denoising_steps + 1`` forwards of every row's every block); in a
  trace forward (experience twice, the update once an epoch) a clean or
  a noisy query at position p has ``(p // block + 1) block`` keys, clean
  ones and its own block's (``trace_pairs`` of the ``update`` span);
- the head over the rows it is computed on: ``denoising_steps`` forwards
  a block of ``block_length`` rows a sequence in the rollout (the commit
  forward has none), the ``new_tokens`` noisy entries gathered a
  sequence in a trace forward: never a [B, L, V] array.

The entries: the rollout's prefill over the padded prompts and
``denoise_forwards * block_length`` a sequence; a trace forward's
``row_tokens`` (the clean stream, the noisy streams and what the noisy
part is padded by: the program multiplies it all, and routes padding to
no expert: ``held_share`` is of all pairs).  Recomputation under remat
is not counted.
"""

from __future__ import annotations


def attention_params(model: dict) -> float:
    h, d = float(model["hidden_size"]), float(model["head_dim"])
    heads, kv = (float(model["num_attention_heads"]),
                 float(model["num_key_value_heads"]))
    return h * heads * d + 2.0 * h * kv * d + heads * d * h


def router_width(model: dict) -> float:
    return float(model.get("source_values", {}).get(
        "num_experts", model["num_experts"]))


def expert_params(model: dict) -> float:
    """One routed expert: gate, up and down."""
    return 3.0 * float(model["hidden_size"]) * float(
        model["moe_intermediate_size"])


def matmul_params(model: dict) -> float:
    """Every matrix-product parameter this share holds: what an
    initialised model's tree counts, without embedding and norms."""
    h = float(model["hidden_size"])
    return (float(model["num_hidden_layers"])
            * (attention_params(model) + h * router_width(model)
               + float(model["num_experts"]) * expert_params(model))
            + h * float(model["vocab_size"]))


def whole_model_params(model: dict) -> float:
    """The published model's parameters (every layer, every expert, both
    embeddings, the norms): 30.5 B."""
    src = dict(model, **model.get("source_values", {}))
    h, d = float(src["hidden_size"]), float(src["head_dim"])
    return (float(src["num_hidden_layers"])
            * (attention_params(src) + h * router_width(src) + 2.0 * h
               + 2.0 * d + float(src["num_experts"]) * expert_params(src))
            + 2.0 * h * float(src["vocab_size"]) + h)


def pair_flops(model: dict) -> float:
    """A (query, key) pair of the attention forward."""
    return 2.0 * float(model["num_attention_heads"]) * 2.0 * float(
        model["head_dim"])


def ppo_iteration_flops(model: dict, samples: int, prompt_len: int,
                        new_tokens: int, num_epochs: int,
                        held_share: float, rollout: dict,
                        forward: dict) -> float:
    """One synchronous PPO iteration with a shared actor-critic trunk.
    ``rollout``: the ``rollout.dispatch`` span's ``denoise_forwards``,
    ``blocks``, ``block_length``, ``denoising_steps``, ``decode_pairs``;
    ``forward``: the ``update`` span's ``row_tokens`` and
    ``trace_pairs`` (of one trace forward of the whole batch).  Two
    experience forwards and forward + backward (3x) an epoch."""
    layers, h = float(model["num_hidden_layers"]), float(model["hidden_size"])
    vocab = float(model["vocab_size"])
    per_entry = layers * (
        attention_params(model) + h * router_width(model)
        + float(model["num_experts_per_tok"]) * held_share
        * expert_params(model))
    pair = layers * pair_flops(model)
    block = float(rollout["block_length"])
    roll_entries = float(samples) * (
        float(prompt_len) + float(rollout["denoise_forwards"]) * block)
    roll_head = float(samples) * (
        1.0 + float(rollout["blocks"]) * float(rollout["denoising_steps"])
        * block)
    generate = (2.0 * per_entry * roll_entries + 2.0 * h * vocab * roll_head
                + pair * float(rollout["decode_pairs"]))
    trace = (2.0 * per_entry * float(forward["row_tokens"])
             + 2.0 * h * vocab * float(samples) * float(new_tokens)
             + pair * float(forward["trace_pairs"]))
    return generate + (2.0 + 3.0 * num_epochs) * trace


def span_counts(ctx):
    """{"rollout": ..., "forward": ...}: medians over the traced
    iterations of what the ``rollout.dispatch`` and ``update`` spans
    carry of a block-diffusion model, or None where the program's spans
    carry none (a program without the counters)."""
    hs = ctx.lib("host_spans")
    spans = hs.of_run(ctx)
    if spans is None:
        return None

    def med(name, keys):
        rows = [sp.stats for sp in spans.whole(name)
                if all(k in sp.stats for k in keys)]
        if not rows:
            return None
        return {k: hs.median([float(r[k]) for r in rows]) for k in keys}

    rollout = med("rollout.dispatch", (
        "denoise_forwards", "blocks", "block_length", "denoising_steps",
        "decode_pairs", "weight_bytes", "cache_bytes", "kv_step_slots"))
    forward = med("update", ("row_tokens", "trace_pairs"))
    if rollout is None or forward is None:
        return None
    return {"rollout": rollout, "forward": forward}
