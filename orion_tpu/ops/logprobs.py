"""Logprob utilities shared by the rollout engine and the trainers.

The classic RLHF bug class is trainer/sampler logprob mismatch
(SURVEY.md §4 "Parity"); these helpers are the single source of truth
for how logprobs are computed (always f32) and how completion tokens
align with logits in the packed layout.

Packed layout: a sequence row is [prompt(0..len-1) | completion(len..
len+clen-1) | pad].  An autoregressive model's logits at index i
predict token i+1, so the logprob of completion token j (absolute index
len+j) reads from logits index len+j-1: ``token_logprobs``,
``completion_logprobs``, ``completion_window_positions`` and
``windowed_completion_logprobs`` ASSUME THAT SHIFT.  A block-diffusion
model's logit AT a position scores the token AT it, in the state of its
block at the step the token was revealed: :func:`trace_streams` lays
those states out and says where to read (no shift);
``entropy_from_logits`` and ``pack_sequences`` hold for both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def token_logprobs(logits: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """logp[b, t] = log P(tokens[b, t+1] | logits[b, t]).

    logits: [B, L, V] (any float dtype; softmax in f32),
    tokens: [B, L] → returns [B, L-1] f32.
    """
    logps = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(
        logps, tokens[:, 1:, None], axis=-1)[..., 0]


def completion_logprobs(logits: jnp.ndarray, sequences: jnp.ndarray,
                        prompt_lens: jnp.ndarray,
                        max_new_tokens: int) -> jnp.ndarray:
    """Per-completion-token logprobs from a full forward over packed
    sequences.  Returns [B, T] aligned with the engine's completions
    (caller masks positions >= completion length)."""
    all_lp = token_logprobs(logits, sequences)  # [B, L-1]; lp of token t+1 at t
    # completion token j sits at abs index prompt_len + j; its logprob is
    # all_lp[:, prompt_len + j - 1].
    idx = prompt_lens[:, None] + jnp.arange(max_new_tokens)[None, :] - 1
    idx = jnp.clip(idx, 0, all_lp.shape[1] - 1)
    return jnp.take_along_axis(all_lp, idx, axis=1)


def completion_window_positions(prompt_lens: jnp.ndarray,
                                max_new_tokens: int,
                                seq_len: int) -> jnp.ndarray:
    """Logit positions that predict the completion tokens: completion
    token j (abs index prompt_len+j) is predicted by the logits at
    prompt_len+j-1.  Returns [B, T] indices into the sequence axis.

    Passing these as ``Transformer(..., logits_positions=...)`` computes
    the vocab projection ONLY at these T positions instead of all L —
    at ppo1b shapes that cuts the biggest matmul in the model (and its
    [B, L, V] f32 logits, 2.5 GB at L=384) to the T=128 completion
    window, in both the experience pass and the update fwd+bwd."""
    idx = prompt_lens[:, None] + jnp.arange(max_new_tokens)[None, :] - 1
    return jnp.clip(idx, 0, seq_len - 1)


def windowed_completion_logprobs(logits_w: jnp.ndarray,
                                 sequences: jnp.ndarray,
                                 prompt_lens: jnp.ndarray,
                                 max_new_tokens: int) -> jnp.ndarray:
    """Per-completion-token logprobs from windowed logits ([B, T, V]
    taken at ``completion_window_positions``).  Numerically identical to
    ``completion_logprobs`` on the full logits (tested)."""
    logps = jax.nn.log_softmax(logits_w.astype(jnp.float32), axis=-1)
    tgt = prompt_lens[:, None] + jnp.arange(max_new_tokens)[None, :]
    tgt = jnp.clip(tgt, 0, sequences.shape[1] - 1)
    targets = jnp.take_along_axis(sequences, tgt, axis=1)
    return jnp.take_along_axis(logps, targets[..., None], axis=-1)[..., 0]


def entropy_from_logits(logits: jnp.ndarray) -> jnp.ndarray:
    """Per-position entropy, f32: [B, L, V] → [B, L]."""
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    p = jnp.exp(logp)
    return -jnp.sum(p * logp, axis=-1)


def pack_sequences(prompt_ids: jnp.ndarray, prompt_lens: jnp.ndarray,
                   completions: jnp.ndarray) -> jnp.ndarray:
    """Right-pack prompts and completions contiguously.

    prompt_ids: [B, P] right-padded, completions: [B, T] →
    sequences [B, P+T] where row b is
    [prompt(0..len_b-1) | completion(0..T-1) | junk-from-overlap].
    Callers mask with lengths; the completion window is written at
    offset len_b so real tokens are contiguous (matching the KV-cache
    slot layout the decode loop produced).
    """
    B, P = prompt_ids.shape
    T = completions.shape[1]
    seq = jnp.zeros((B, P + T), prompt_ids.dtype)
    seq = seq.at[:, :P].set(prompt_ids)
    return jax.vmap(
        lambda s, c, l: jax.lax.dynamic_update_slice(s, c, (l,))
    )(seq, completions, prompt_lens)


def trace_streams(sequences: jnp.ndarray, prompt_lens: jnp.ndarray,
                  reveal_step: jnp.ndarray, block: int, steps: int,
                  mask_id: int, blocks: int, noisy_len: int) -> dict:
    """The one row a sequence that scores a block-diffusion completion's
    sampling trace: ``[clean ; z^(0) ; ... ; z^(steps-1) ; padding]``.

    sequences [B, L] packed; reveal_step [B, T] (rollout/engine.py:
    the denoising step at which each completion position was revealed,
    ``steps`` where never).  The clean stream is the sequence itself.
    Noisy stream s holds the ``blocks`` blocks of ``block`` positions
    from the prompt's last block on (every block a completion position
    can lie in), each token at its true position: the token where it is
    a prompt's or was revealed BEFORE step s, the mask token elsewhere
    (past the last new position too: never revealed).  Padded with mask
    tokens to ``noisy_len`` entries, which hold no token.

    Returns ids, positions, see (``models.transformer.Visible``: a clean
    query sees through its block's end, a noisy one the clean stream up
    to its block's start and its own block), token_mask, all [B, L +
    noisy_len]; ``read_at`` [B, T]: the row entry whose logits score
    completion token t and whose hidden state its value is read from,
    entry (reveal_step[t], position of t)."""
    B, L = sequences.shape
    T = reveal_step.shape[1]
    W = blocks * block
    i32 = jnp.int32
    start = prompt_lens // block * block                          # [B]
    wpos = start[:, None] + jnp.arange(W, dtype=i32)[None, :]     # [B, W]
    rel = wpos - prompt_lens[:, None]
    step_w = jnp.where(
        rel < 0, -1,
        jnp.where(rel < T, jnp.take_along_axis(
            reveal_step.astype(i32), jnp.clip(rel, 0, T - 1), axis=1), steps))
    tok_w = jnp.take_along_axis(sequences, jnp.clip(wpos, 0, L - 1), axis=1)
    s_idx = jnp.arange(steps, dtype=i32)[None, :, None]
    noisy = jnp.where(step_w[:, None, :] < s_idx, tok_w[:, None, :],
                      mask_id).reshape(B, steps * W)
    fill = noisy_len - steps * W

    def padded(x, value):
        return jnp.pad(x, ((0, 0), (0, fill)), constant_values=value)

    clean_pos = jnp.broadcast_to(jnp.arange(L, dtype=i32), (B, L))
    return {
        "ids": jnp.concatenate(
            [sequences, padded(noisy.astype(sequences.dtype), mask_id)], 1),
        "positions": jnp.concatenate(
            [clean_pos, padded(jnp.tile(wpos, (1, steps)), 0)], 1),
        "see": jnp.concatenate(
            [clean_pos // block * block + (block - 1),
             padded(jnp.tile(wpos // block * block - 1, (1, steps)), -1)], 1),
        "token_mask": jnp.concatenate(
            [clean_pos < (prompt_lens + T)[:, None],
             padded(jnp.ones((B, steps * W), bool), False)], 1),
        "read_at": L + jnp.clip(reveal_step.astype(i32), 0, steps - 1) * W
        + (prompt_lens - start)[:, None] + jnp.arange(T, dtype=i32)[None, :],
    }
