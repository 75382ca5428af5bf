"""The rollout engine — TPU-native equivalent of the reference's vLLM
generation engine (SURVEY.md §2 #5, §3c).

Design (XLA-first, static shapes):
- one jitted program per (batch, prompt_len, max_new_tokens) bucket:
  prefill (full-seq forward filling the KV cache) then a
  ``lax.while_loop`` decode with per-sequence EOS early exit — the loop
  terminates as soon as every sequence is done, so wall-clock tracks the
  longest completion, not the static bound;
- per-token logprobs captured in f32 under the *actual* sampling
  distribution (temperature/top-k/top-p applied);
- ``load_weights`` is the weight hot-reload channel the trainer calls
  between steps (in async mode the weight-sync channel lands here);
- right-padded prompts with per-sequence lengths; the cache write path
  overwrites the padded tail slot-by-slot during decode (see
  models.transformer.Attention).

The paged-KV upgrade (block tables + Pallas paged attention) slots in
behind the same interface via RolloutConfig.paged.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from orion_tpu.config import ModelConfig, RolloutConfig
from orion_tpu.models.transformer import init_cache
from orion_tpu.ops.logprobs import pack_sequences
from orion_tpu.ops.sampling import sample_tokens
from orion_tpu.resilience import fault_point


@dataclasses.dataclass
class GenerationResult:
    """Everything downstream consumers (scoring, trainers) need."""

    sequences: jnp.ndarray        # [B, P+T] packed prompt+completion
    completions: jnp.ndarray      # [B, T] completion tokens (pad after EOS)
    completion_mask: jnp.ndarray  # [B, T] 1.0 for real completion tokens
    completion_lens: jnp.ndarray  # [B] number of real completion tokens
    logprobs: jnp.ndarray         # [B, T] f32 sampling-distribution logprobs
    policy_logprobs: jnp.ndarray  # [B, T] f32 raw (untempered) policy logprobs
    prompt_lens: jnp.ndarray      # [B]
    total_lens: jnp.ndarray       # [B] prompt + completion lengths

    def _fields(self) -> dict:
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)}

    def to_host(self) -> "GenerationResult":
        """Numpy copy of every field via ONE batched device→host
        transfer.  Every separate fetch is its own blocking device
        round trip; host consumers (reward fns, stats, detokenization)
        must use this copy, never per-field ``np.asarray``."""
        return GenerationResult(**jax.device_get(self._fields()))


class RolloutEngine:
    """Batched autoregressive generation with KV cache + logprob capture."""

    def __init__(self, model: Any, model_cfg: ModelConfig,
                 cfg: RolloutConfig, eos_token_id: Optional[int] = None,
                 pad_token_id: int = 0):
        self.model = model
        self.model_cfg = model_cfg
        self.cfg = cfg
        cfg.check_stop_ids(model_cfg.vocab_size, eos_token_id)
        self.eos_token_id = eos_token_id
        self.pad_token_id = pad_token_id
        self._params = None
        self._cache_bytes: dict = {}
        from orion_tpu.models.transformer import make_decode_twin

        self._decode_model, self._decode_cfg = make_decode_twin(
            model, model_cfg)
        if model_cfg.latent_attention:
            for on, missing in (
                    (cfg.paged, "rollout.paged: there is no latent paged "
                     "cache (ops/paged_kv.py and the Pallas paged-decode "
                     "kernel hold per-head K/V pages)"),
                    (cfg.quantize_kv, "rollout.quantize_kv: there is no "
                     "int8 latent cache (ops/quant.py scales per head)"),
                    (cfg.quantize_weights, "rollout.quantize_weights: "
                     "there are no int8 expert stacks or absorbed int8 "
                     "kv_b_proj (ops/quant.py quantises Dense kernels)")):
                if on:
                    raise ValueError(
                        f"arch={model_cfg.arch!r} cannot run with {missing}")
        if cfg.quantize_weights:
            # int8 decode twin (ops/quant.py): same architecture, Dense
            # layers read int8 kernels.  Params are quantized inside
            # _generate (once per call, amortized over every step).
            self._decode_cfg = dataclasses.replace(
                self._decode_cfg, quantize_dense=True)
            self._decode_model = type(self._decode_model)(self._decode_cfg)
        if cfg.speculative_k > 0:
            if cfg.paged:
                raise ValueError(
                    "speculative_k > 0 requires the dense cache "
                    "(paged=False): the draft chunk writes k+1 "
                    "positions past the current length, outside a "
                    "paged reservation")
            if cfg.repetition_penalty != 1.0 or cfg.min_new_tokens:
                raise ValueError(
                    "speculative_k > 0 does not compose with "
                    "repetition_penalty / min_new_tokens yet")
            # Verify chunks are k+1 queries wide; at that width the
            # flash kernel's sub-8-row MXU tiles lose to the XLA
            # einsum (measured on-chip r5: chunk cost 2.5x -> 1.55x a
            # plain decode step).  A separate twin pins the reference
            # path for the CHUNK apply only — prefill (Lq = P) stays
            # on the main twin so it keeps the flash kernel; both
            # twins share the same params.
            self._spec_verify_model = type(self._decode_model)(
                dataclasses.replace(self._decode_cfg,
                                    attention_impl="reference"))
        self._generate_jit = jax.jit(
            self._generate, static_argnames=("max_new_tokens",))
        self._generate_spec_jit = jax.jit(
            self._generate_spec, static_argnames=("max_new_tokens",))

    # -- weight hot-reload channel (trainer → rollout) ------------------
    def load_weights(self, params: Any) -> None:
        """Install new policy weights.  In sync mode this is a reference
        swap (zero copy — the arrays already live on the mesh); in async
        mode the weight-sync channel device_puts a fresh snapshot here
        (SURVEY.md §2 #11)."""
        self._params = params

    def cache_bytes(self, batch: int, prompt_len: int,
                    max_new_tokens: Optional[int] = None) -> int:
        """Bytes of the cache ``_generate`` allocates for such a batch
        (shapes only, nothing is placed)."""
        T = int(max_new_tokens or self.cfg.max_new_tokens)
        if self.cfg.paged:
            return 0
        key = (batch, prompt_len + T)
        if key not in self._cache_bytes:
            cache = jax.eval_shape(
                lambda: init_cache(self._decode_cfg, *key,
                                   dtype=jnp.dtype(self._decode_cfg.dtype),
                                   quantized=self.cfg.quantize_kv))
            self._cache_bytes[key] = sum(
                x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
        return self._cache_bytes[key]

    # -- generation -----------------------------------------------------
    def generate(self, prompt_ids: jnp.ndarray, prompt_lens: jnp.ndarray,
                 rng: jax.Array, params: Any = None,
                 max_new_tokens: Optional[int] = None) -> GenerationResult:
        # Named fault point (orion_tpu.resilience): a chaos plan can
        # kill generation here deterministically — the supervised
        # recovery path in the async orchestrator trains against this.
        fault_point("rollout.generate")
        params = params if params is not None else self._params
        if params is None:
            raise ValueError("no weights loaded: call load_weights() first")
        T = int(max_new_tokens or self.cfg.max_new_tokens)
        if self.cfg.speculative_k > 0:
            out = self._generate_spec_jit(params, prompt_ids, prompt_lens,
                                          rng, max_new_tokens=T)
            # diagnostic: verify-forward count (device scalar; fetch
            # lazily — bench/AB scripts read it, trainers ignore it)
            self.last_spec_steps = out.pop("spec_steps")
        else:
            out = self._generate_jit(params, prompt_ids, prompt_lens, rng,
                                     max_new_tokens=T)
        return GenerationResult(**out)

    def _generate(self, params, prompt_ids, prompt_lens, rng,
                  max_new_tokens: int):
        cfg = self.cfg
        B, P = prompt_ids.shape
        T = max_new_tokens
        eos = self.eos_token_id
        pad = self.pad_token_id
        sample = partial(sample_tokens, temperature=cfg.temperature,
                         top_k=cfg.top_k, top_p=cfg.top_p)

        # Engine weights are read once per decode step; the shared prep
        # (compute-dtype cast OUTSIDE the decode loop — every step then
        # reads 2 bytes/param instead of 4 + a per-op cast, flax's
        # per-layer promote_dtype is NOT hoisted out of while_loop by
        # XLA, measured ~2x decode bandwidth — plus unstack + optional
        # int8) lives in one place for all engine paths.
        from orion_tpu.models.transformer import prep_decode_params

        params = prep_decode_params(params, self.model_cfg,
                                    cfg.quantize_weights)

        if cfg.paged:
            from orion_tpu.ops.paged_kv import init_paged_cache

            mc = self._decode_cfg
            cache = init_paged_cache(
                mc.num_layers, B, P + T, mc.num_kv_heads, mc.head_dim,
                cfg.page_size, cfg.num_pages,
                dtype=jnp.dtype(mc.dtype), stacked=mc.scan_layers,
                quantized=cfg.quantize_kv)
        else:
            cache = init_cache(self._decode_cfg, B, P + T,
                               dtype=jnp.dtype(self._decode_cfg.dtype),
                               quantized=cfg.quantize_kv)
        positions = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (B, P))
        # the dropless expert layer routes a prompt's padding nowhere
        pad_kw = {"token_mask": positions < prompt_lens[:, None]} \
            if self.model_cfg.n_routed_experts > 0 else {}
        with jax.named_scope("prefill"):
            # Only the last real prompt token's logits are needed (they
            # predict completion[0]) — logits_positions skips the other
            # P-1 rows of the vocab projection and the [B, P, V] f32
            # logits buffer (1.6 GB at ppo1b shapes).
            logits, cache = self._decode_model.apply(
                {"params": params}, prompt_ids, positions, cache,
                logits_positions=(prompt_lens - 1)[:, None], **pad_kw)
        last = logits[:, 0]
        V = last.shape[-1]
        # Generation controls (static per compile): repetition penalty
        # carries a [B, V] seen-set (prompt tokens included, HF/vLLM
        # convention); min_new_tokens suppresses EOS until each
        # sequence has generated that many tokens.
        from orion_tpu.ops.sampling import (eos_forbid_mask, is_stop_token,
                                            seen_from_prompts)

        pen = cfg.repetition_penalty != 1.0
        min_new = cfg.effective_min_new(eos)
        bidx = jnp.arange(B)
        seen = seen_from_prompts(prompt_ids, prompt_lens, V) if pen \
            else jnp.zeros((B, 1), bool)  # carried but unused when off

        def ctrl_kwargs(seen, n_generated):
            kw = {}
            if pen:
                kw["seen"] = seen
                kw["repetition_penalty"] = cfg.repetition_penalty
            if min_new > 0:
                kw["forbid"] = eos_forbid_mask(
                    B, V, eos, n_generated < min_new,
                    cfg.stop_token_ids)
            return kw

        rng, sub = jax.random.split(rng)
        tok0, lp0, plp0 = sample(sub, last, **ctrl_kwargs(seen, 0))
        if pen:
            seen = seen.at[bidx, tok0].set(True)

        tokens = jnp.full((B, T), pad, jnp.int32).at[:, 0].set(tok0)
        logps = jnp.zeros((B, T), jnp.float32).at[:, 0].set(lp0)
        plogps = jnp.zeros((B, T), jnp.float32).at[:, 0].set(plp0)
        done = is_stop_token(tok0, eos, cfg.stop_token_ids)
        comp_len = jnp.ones((B,), jnp.int32)

        def cond(c):
            t, _, _, _, done, _, _, _, _ = c
            return (t < T) & ~jnp.all(done)

        def body(c):
            t, cur_tok, cur_pos, rng, done, tokens, logps, plogps, state = c
            cache, comp_len, seen = state
            step_logits, cache = self._decode_model.apply(
                {"params": params}, cur_tok[:, None], cur_pos[:, None],
                cache)
            rng, sub = jax.random.split(rng)
            nxt, lp, plp = sample(sub, step_logits[:, 0],
                                  **ctrl_kwargs(seen, t))
            nxt = jnp.where(done, pad, nxt)
            lp = jnp.where(done, 0.0, lp)
            plp = jnp.where(done, 0.0, plp)
            if pen:
                seen = seen.at[bidx, jnp.where(done, V, nxt)].set(
                    True, mode="drop")
            tokens = tokens.at[:, t].set(nxt, mode="drop")
            logps = logps.at[:, t].set(lp, mode="drop")
            plogps = plogps.at[:, t].set(plp, mode="drop")
            comp_len = comp_len + (~done).astype(jnp.int32)
            done = done | is_stop_token(nxt, eos, cfg.stop_token_ids)
            return (t + 1, nxt, cur_pos + 1, rng, done, tokens, logps,
                    plogps, (cache, comp_len, seen))

        init = (jnp.int32(1), tok0, prompt_lens, rng, done, tokens, logps,
                plogps, (cache, comp_len, seen))
        with jax.named_scope("decode"):
            _, _, _, _, done, tokens, logps, plogps, \
                (cache, comp_len, seen) = \
                jax.lax.while_loop(cond, body, init)

        mask = (jnp.arange(T)[None, :] < comp_len[:, None]).astype(jnp.float32)
        sequences = pack_sequences(prompt_ids, prompt_lens, tokens)
        return dict(
            sequences=sequences,
            completions=tokens,
            completion_mask=mask,
            completion_lens=comp_len,
            logprobs=logps,
            policy_logprobs=plogps,
            prompt_lens=prompt_lens,
            total_lens=prompt_lens + comp_len,
        )

    def _generate_spec(self, params, prompt_ids, prompt_lens, rng,
                       max_new_tokens: int):
        """Decode with n-gram (prompt-lookup) speculative drafting:
        each verify step drafts ``speculative_k`` tokens by matching
        the trailing ``spec_ngram``-gram against earlier sequence
        content, runs ONE chunked forward over the k+1 candidate
        positions, and accepts a prefix — decode reads the full weight
        set once per verify step instead of once per token, so the
        speedup is ≈ mean tokens emitted per step on an HBM-bound
        decode.

        Acceptance is EXACT in both modes:
          - temperature=0: accept drafts agreeing with argmax of the
            SAME logits plain greedy would produce — output is
            bit-identical to sequential greedy regardless of draft
            quality (a bad draft only costs speed);
          - temperature>0: delta-draft speculative sampling (the
            deterministic-draft case of Leviathan et al.): accept
            draft x with probability p(x) under the tempered/truncated
            sampling distribution; on rejection resample from p with x
            excluded.  The emitted token's MARGINAL distribution is
            exactly p, so ``logprobs`` (= log p(token), the behavior
            logprob the async importance ratio needs) stays correct —
            the token stream differs from the sequential path only in
            which RNG draws produced it, not in distribution.

        Cache consistency (both modes): each chunk writes k+1
        consecutive positions starting exactly at the first stale
        position (the previous step's bonus-token slot), so rejected-
        draft KV is always overwritten before any query position can
        attend it (queries at position p only attend keys <= p, and
        the chunk writes before attending — the same property chunked
        prefill relies on).  The cache is allocated k positions past
        P+T because the final step's chunk may probe past the budget;
        those writes land in the slack and are never attended.
        """
        cfg = self.cfg
        gamma = int(cfg.speculative_k)
        n = int(cfg.spec_ngram)
        B, P = prompt_ids.shape
        T = max_new_tokens
        eos = self.eos_token_id
        pad = self.pad_token_id

        from orion_tpu.models.transformer import prep_decode_params

        params = prep_decode_params(params, self.model_cfg,
                                    cfg.quantize_weights)

        from orion_tpu.ops.sampling import (is_stop_token, sample_tokens,
                                            transformed_logits)

        stochastic = cfg.temperature != 0.0

        # Chunk slack past the budget (init_cache rounds the cache
        # length itself to a multiple of 8 for Mosaic tiling; the seq
        # buffer here tracks the same width so draft windows can read
        # to the end of the cache).
        cap = -(-(P + T + gamma) // 8) * 8
        cache = init_cache(self._decode_cfg, B, cap,
                           dtype=jnp.dtype(self._decode_cfg.dtype),
                           quantized=cfg.quantize_kv)
        positions = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (B, P))
        with jax.named_scope("prefill"):
            logits, cache = self._decode_model.apply(
                {"params": params}, prompt_ids, positions, cache,
                logits_positions=(prompt_lens - 1)[:, None])
        rng, sub = jax.random.split(rng)
        # first token: one ordinary draw from the sampling distribution
        # (greedy argmax at temperature 0) — drafting starts after it
        tok0, lp0, plp0 = sample_tokens(
            sub, logits[:, 0], temperature=cfg.temperature,
            top_k=cfg.top_k, top_p=cfg.top_p)

        bidx = jnp.arange(B)
        tokens = jnp.full((B, T), pad, jnp.int32).at[:, 0].set(tok0)
        logps = jnp.zeros((B, T), jnp.float32).at[:, 0].set(lp0)
        plogps = jnp.zeros((B, T), jnp.float32).at[:, 0].set(plp0)
        done = is_stop_token(tok0, eos, cfg.stop_token_ids) | (T <= 1)
        comp_len = jnp.ones((B,), jnp.int32)
        # full-sequence buffer (draft source): prompt + generated
        seq = jnp.full((B, cap), pad, jnp.int32)
        seq = jax.lax.dynamic_update_slice(seq, prompt_ids, (0, 0))
        seq = seq.at[bidx, prompt_lens].set(tok0)
        ln = prompt_lens + 1            # total content length
        cur = tok0                      # last token, KV not yet written

        n_win = cap - n - gamma + 1     # draftable window starts
        w_idx = jnp.arange(n_win)

        def draft_fn(seq, ln):
            # trailing n-gram of each row
            tgt = jnp.stack(
                [jnp.take_along_axis(seq, (ln - n + i)[:, None],
                                     axis=1)[:, 0] for i in range(n)],
                axis=1)                                     # [B, n]
            eq = jnp.ones((B, n_win), bool)
            for i in range(n):
                eq &= seq[:, i: i + n_win] == tgt[:, i: i + 1]
            # latest PRIOR occurrence whose FULL gamma-token
            # continuation lies inside the content — a match at the
            # content edge would draft pads past it (a period-1 cycle
            # then accepts ~1/gamma instead of the full chunk; found
            # measuring the continuous port, PR 10)
            valid = eq & (w_idx[None, :] + n + gamma <= ln[:, None])
            score = jnp.where(valid, w_idx[None, :], -1)
            s = jnp.max(score, axis=1)                      # [B], -1 = none
            s0 = jnp.maximum(s, 0)
            drafts = jnp.stack(
                [jnp.take_along_axis(seq, (s0 + n + i)[:, None],
                                     axis=1)[:, 0] for i in range(gamma)],
                axis=1)                                     # [B, gamma]
            # no match -> draft pads; they are verified like any draft
            return jnp.where((s >= 0)[:, None], drafts, pad)

        def cond(c):
            it, done = c[0], c[5]
            return (it < T) & ~jnp.all(done)

        def body(c):
            (it, rng, seq, ln, cur, done, comp_len, tokens, logps,
             plogps, cache) = c
            drafts = draft_fn(seq, ln)
            chunk = jnp.concatenate([cur[:, None], drafts], axis=1)
            # done rows idle in place: ln is frozen (n_emit 0), so
            # their chunk rewrites the same slack slots, never attended
            pos = (ln - 1)[:, None] + jnp.arange(gamma + 1,
                                                 dtype=jnp.int32)
            step_logits, cache = self._spec_verify_model.apply(
                {"params": params}, chunk, pos, cache)
            raw_lsm = jax.nn.log_softmax(
                step_logits.astype(jnp.float32), axis=-1)   # [B, g+1, V]
            if not stochastic:
                # greedy acceptance: emitted = the model's own argmax
                p_lsm = raw_lsm
                e = jnp.argmax(raw_lsm, axis=-1).astype(jnp.int32)
                acc = jnp.cumprod(
                    (drafts == e[:, :gamma]).astype(jnp.int32), axis=1)
                m = jnp.sum(acc, axis=1)                    # [B] 0..gamma
            else:
                # delta-draft speculative sampling: accept draft x
                # w.p. p(x); on rejection resample from p excluding x;
                # after a full accept, one ordinary bonus draw.  The
                # marginal of every emitted token is exactly p.
                t_logits = transformed_logits(
                    step_logits, cfg.temperature, cfg.top_k, cfg.top_p)
                p_lsm = jax.nn.log_softmax(t_logits, axis=-1)
                rng, k_u, k_cat = jax.random.split(rng, 3)
                u = jax.random.uniform(k_u, (B, gamma))
                p_draft = jnp.exp(jnp.take_along_axis(
                    p_lsm[:, :gamma], drafts[..., None],
                    axis=-1)[..., 0])                       # [B, gamma]
                acc = jnp.cumprod((u < p_draft).astype(jnp.int32),
                                  axis=1)
                m = jnp.sum(acc, axis=1)                    # [B] 0..gamma
                # per-position correction draws: position j < gamma →
                # residual (draft excluded); position gamma → bonus
                excl = jnp.full((B, gamma + 1, t_logits.shape[-1]),
                                False).at[
                    bidx[:, None], jnp.arange(gamma)[None, :],
                    drafts].set(True)
                resampled = jax.random.categorical(
                    k_cat, jnp.where(excl, jnp.float32(-1e10), t_logits),
                    axis=-1).astype(jnp.int32)              # [B, g+1]
                e = jnp.where(
                    jnp.arange(gamma + 1)[None, :] < m[:, None],
                    jnp.pad(drafts, ((0, 0), (0, 1))), resampled)
            lp_e = jnp.take_along_axis(p_lsm, e[..., None],
                                       axis=-1)[..., 0]     # [B, g+1]
            plp_e = jnp.take_along_axis(raw_lsm, e[..., None],
                                        axis=-1)[..., 0]
            stopped = jnp.zeros((B,), bool)
            n_emit = jnp.zeros((B,), jnp.int32)
            last_tok = cur
            for j in range(gamma + 1):
                e_j = e[:, j]
                valid = (~done) & (j <= m) & ~stopped & (comp_len + j < T)
                wi = jnp.where(valid, comp_len + j, T)
                tokens = tokens.at[bidx, wi].set(e_j, mode="drop")
                logps = logps.at[bidx, wi].set(lp_e[:, j], mode="drop")
                plogps = plogps.at[bidx, wi].set(plp_e[:, j],
                                                 mode="drop")
                si = jnp.where(valid, ln + j, cap)
                seq = seq.at[bidx, si].set(e_j, mode="drop")
                stopped = stopped | (valid & is_stop_token(
                    e_j, eos, cfg.stop_token_ids))
                n_emit = n_emit + valid
                last_tok = jnp.where(valid, e_j, last_tok)
            comp_len = comp_len + n_emit
            ln = ln + n_emit
            done = done | stopped | (comp_len >= T)
            return (it + 1, rng, seq, ln, last_tok, done, comp_len,
                    tokens, logps, plogps, cache)

        init = (jnp.int32(1), rng, seq, ln, cur, done, comp_len, tokens,
                logps, plogps, cache)
        with jax.named_scope("spec_decode"):
            (it, rng, seq, ln, cur, done, comp_len, tokens, logps,
             plogps, cache) = jax.lax.while_loop(cond, body, init)

        mask = (jnp.arange(T)[None, :] < comp_len[:, None]).astype(
            jnp.float32)
        sequences = pack_sequences(prompt_ids, prompt_lens, tokens)
        return dict(
            sequences=sequences,
            completions=tokens,
            completion_mask=mask,
            completion_lens=comp_len,
            logprobs=logps,
            policy_logprobs=plogps,
            prompt_lens=prompt_lens,
            total_lens=prompt_lens + comp_len,
            spec_steps=it - 1,
        )
