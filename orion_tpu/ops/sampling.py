"""Token sampling: temperature / top-k / top-p, with logprob capture.

Returns the logprob of the sampled token under the *actual* sampling
distribution (post temperature + truncation + penalties) — this is the
behavioral policy used for importance ratios in the off-policy/async
path; trainers additionally recompute logprobs under the training graph
(SURVEY.md §4 "logprob parity").  Logprobs are computed in f32 (bf16
softmax drift is hard-part #4 in SURVEY.md §7).

Generation controls (the vLLM-equivalent sampling-params surface):
``repetition_penalty`` (HF/vLLM convention: seen tokens' positive
logits divided by the penalty, negative multiplied) with the seen-set
supplied by the engine as a [B, V] mask, and ``forbid`` (a [B, V] mask
of tokens barred from this step — how engines implement
``min_new_tokens`` by suppressing EOS).  Both transform the SAMPLING
distribution only: ``policy_logprobs`` stays the raw untempered policy,
so the off-policy importance ratio remains correct under any controls.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = jnp.float32(-1e10)


def _mask_top_k(logits: jnp.ndarray, top_k: int) -> jnp.ndarray:
    vals, _ = jax.lax.top_k(logits, top_k)
    threshold = vals[..., -1:]
    return jnp.where(logits < threshold, _NEG_INF, logits)


def _mask_top_p(logits: jnp.ndarray, top_p: float) -> jnp.ndarray:
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Keep tokens while cumulative prob *before* them is < top_p
    # (always keeps the top token).
    keep_sorted = (cum - probs) < top_p
    n_keep = jnp.sum(keep_sorted, axis=-1, keepdims=True)
    # Threshold = smallest kept logit.
    idx = jnp.clip(n_keep - 1, 0, logits.shape[-1] - 1)
    threshold = jnp.take_along_axis(sorted_logits, idx, axis=-1)
    return jnp.where(logits < threshold, _NEG_INF, logits)


def apply_repetition_penalty(logits: jnp.ndarray, seen: jnp.ndarray,
                             penalty: float) -> jnp.ndarray:
    """HF/vLLM repetition penalty: for tokens in the seen set, positive
    logits are divided by ``penalty`` and negative ones multiplied —
    both push the token down for penalty > 1."""
    penalized = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(seen, penalized, logits)


def seen_from_prompts(prompt_ids: jnp.ndarray, prompt_lens: jnp.ndarray,
                      vocab_size: int) -> jnp.ndarray:
    """[B, V] bool seen-set from right-padded prompts (HF/vLLM: the
    repetition penalty covers prompt tokens too).  Pad positions index
    vocab_size and drop."""
    B, P = prompt_ids.shape
    positions = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (B, P))
    safe = jnp.where(positions < prompt_lens[:, None], prompt_ids,
                     vocab_size)
    return jnp.zeros((B, vocab_size), bool).at[
        jnp.arange(B)[:, None], safe].set(True, mode="drop")


def eos_forbid_mask(batch: int, vocab_size: int, eos_id,
                    under_min, stop_ids: tuple = ()) -> jnp.ndarray:
    """[B, V] bool mask suppressing EVERY terminator (eos + configured
    stop_token_ids, vLLM min_tokens semantics) for sequences still
    under min_new_tokens (``under_min``: scalar or [B] bool)."""
    m = jnp.zeros((batch, vocab_size), bool)
    for t in (eos_id, *stop_ids):
        if t is not None:
            m = m.at[:, int(t)].set(under_min)
    return m


def is_stop_token(tokens: jnp.ndarray, eos_id,
                  stop_ids: tuple) -> jnp.ndarray:
    """[B] bool: token terminates its sequence (eos or any of the
    configured stop_token_ids).  eos_id None with no stop_ids => all
    False."""
    done = jnp.zeros(tokens.shape, bool)
    if eos_id is not None:
        done = tokens == eos_id
    for sid in stop_ids:
        done = done | (tokens == int(sid))
    return done


def bar_token(logits: jnp.ndarray, token_id: int) -> jnp.ndarray:
    """``logits`` [..., V] with ``token_id`` out of the distribution:
    the mask token of a block-diffusion model, which a denoiser never
    emits.  It is barred in the POLICY itself, by the engine's draw and
    by the trainers' log-probabilities alike (a large finite value: the
    entropy stays finite), not in the sampling transform only as
    ``forbid`` is."""
    return logits.at[..., token_id].set(_NEG_INF.astype(logits.dtype))


def transformed_logits(logits: jnp.ndarray, temperature: float,
                       top_k: int = 0, top_p: float = 1.0) -> jnp.ndarray:
    """The sampling-distribution transform pipeline of sample_tokens
    (temperature → top-k → top-p), factored out for callers that need
    the full transformed distribution rather than one draw — the
    speculative-sampling acceptance test evaluates p(token) under
    EXACTLY the distribution sample_tokens would draw from.
    temperature must be > 0 (greedy has no sampling distribution)."""
    logits = logits.astype(jnp.float32) / temperature
    if top_k > 0:
        logits = _mask_top_k(logits, top_k)
    if top_p < 1.0:
        logits = _mask_top_p(logits, top_p)
    return logits


def sample_tokens(rng: jax.Array, logits: jnp.ndarray, temperature: float,
                  top_k: int = 0, top_p: float = 1.0,
                  seen: Optional[jnp.ndarray] = None,
                  repetition_penalty: float = 1.0,
                  forbid: Optional[jnp.ndarray] = None) -> tuple:
    """Sample next tokens from [B, V] logits.

    Returns (tokens [B] int32, sample_logprobs [B] f32,
    policy_logprobs [B] f32).  ``sample_logprobs`` is the logprob under
    the *actual* sampling distribution (post temperature, truncation,
    repetition penalty, and forbidden-token suppression);
    ``policy_logprobs`` is under the raw untempered policy — the
    behavior-policy logprob the async off-policy importance ratio needs
    (SURVEY.md §3b).  temperature == 0.0 means greedy (over the
    transformed distribution, so controls still bind).

    seen: [B, V] bool — tokens already in the sequence, penalized by
      ``repetition_penalty`` when != 1.0.
    forbid: [B, V] bool — tokens suppressed this step (−inf).
    """
    logits = logits.astype(jnp.float32)
    raw_logps = jax.nn.log_softmax(logits, axis=-1)
    # A repetition penalty with no seen-set applies NO transform, so it
    # must not flip greedy decoding into delta-distribution logprob
    # accounting (ADVICE r4).
    transformed = (seen is not None and repetition_penalty != 1.0) \
        or forbid is not None
    if seen is not None and repetition_penalty != 1.0:
        logits = apply_repetition_penalty(logits, seen,
                                          repetition_penalty)
    if forbid is not None:
        logits = jnp.where(forbid, _NEG_INF, logits)

    def take(logps, tokens):
        return jnp.take_along_axis(logps, tokens[:, None], axis=-1)[:, 0]

    if temperature == 0.0:
        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        plp = take(raw_logps, tokens)
        # Greedy over a TRANSFORMED distribution is a delta: the honest
        # behavior logprob is log 1 = 0 (raw lp could be tiny for a
        # penalty-displaced argmax, which would bias importance
        # ratios).  Untransformed greedy keeps the raw lp — the
        # engines' historical (and diagnostically useful) convention.
        lp = jnp.zeros_like(plp) if transformed else plp
        return tokens, lp, plp
    logits = logits / temperature
    if top_k > 0:
        logits = _mask_top_k(logits, top_k)
    if top_p < 1.0:
        logits = _mask_top_p(logits, top_p)
    if temperature == 1.0 and top_k <= 0 and top_p >= 1.0 and \
            not transformed:
        logps = raw_logps  # sampling dist == policy dist: one softmax
    else:
        logps = jax.nn.log_softmax(logits, axis=-1)
    tokens = jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)
    return tokens, take(logps, tokens), take(raw_logps, tokens)
