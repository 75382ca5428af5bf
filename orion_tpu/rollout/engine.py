"""The fixed-batch rollout engine — TPU-native equivalent of the
reference's vLLM generation engine (SURVEY.md §2 #5, §3c).

One decode path (XLA-first, static shapes):
- one jitted program per (batch, prompt_len, max_new_tokens) bucket:
  prefill (full-seq forward filling the KV cache) then a
  ``lax.while_loop`` of one-token steps with per-sequence EOS early
  exit — the loop terminates as soon as every sequence is done, so
  wall-clock tracks the longest completion, not the static bound;
- per-token logprobs captured in f32 under the *actual* sampling
  distribution (temperature/top-k/top-p applied), and the raw policy
  logprobs beside them;
- ``load_weights`` is the weight hot-reload channel the trainer calls
  between steps (in async mode the weight-sync channel lands here);
- right-padded prompts with per-sequence lengths; the cache write path
  overwrites the padded tail slot-by-slot during decode (see
  models.transformer.Attention);
- the cache is dense: per layer what its mixer states
  (models.transformer.MIXERS: ``cache_entry``, [B, P+T] slots where it
  is indexed by position, a state handed from prefill to decode where
  it is not, {} for a block without a mixer), int8 under
  ``quantize_kv``, or paged under ``RolloutConfig.paged`` (block tables
  + the Pallas paged-decode kernel; slower than dense for a fixed
  batch, ROADMAP D3(a)); what a model's kinds cannot run is refused
  with their own reasons (models.transformer.cannot_run); a one-token
  step reads the dense cache's filled prefix in blocks
  (models.transformer.prefix_step: slots fill from 0 up, so what lies
  past the batch's furthest position is never fetched).  The decode
  loop is the same for every kind.

Speculative decoding is not here: a lockstep batch advances at its
slowest row's acceptance, and it lost on the chip (PERF.md section 6,
PR 30).  ``speculative_k > 0`` is the continuous engine's
(rollout/continuous.py: per slot, adaptive k) and is refused below.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from orion_tpu.config import ModelConfig, RolloutConfig
from orion_tpu.models.transformer import (MIXERS, cache_entry, cache_slots,
                                         cannot_run, decode_attrs, init_cache,
                                         make_decode_twin, prep_decode_params)
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
        if cfg.speculative_k > 0:
            raise ValueError(
                "rollout.speculative_k > 0 needs rollout.engine=continuous: "
                "the fixed-batch engine has one decode path, one token a "
                "step")
        self.eos_token_id = eos_token_id
        self.pad_token_id = pad_token_id
        self._params = None
        self._cache_bytes: dict = {}
        self._weight_bytes: Optional[int] = None
        self._decode_model, self._decode_cfg = make_decode_twin(
            model, model_cfg)
        for form in ("paged", "quantize_kv", "quantize_weights"):
            why = getattr(cfg, form) and cannot_run(model_cfg, form)
            if why:
                raise ValueError(f"arch={model_cfg.arch!r} cannot run with "
                                 f"rollout.{form}: {why}")
        if cfg.quantize_weights:
            # int8 decode twin (ops/quant.py): same architecture, Dense
            # layers read int8 kernels.  Params are quantized inside
            # _generate (once per call, amortized over every step).
            self._decode_cfg = dataclasses.replace(
                self._decode_cfg, quantize_dense=True)
            self._decode_model = type(self._decode_model)(self._decode_cfg)
        self._generate_jit = jax.jit(
            self._generate, static_argnames=("max_new_tokens",))

    # -- weight hot-reload channel (trainer → rollout) ------------------
    def load_weights(self, params: Any) -> None:
        """Install new policy weights.  In sync mode this is a reference
        swap (zero copy — the arrays already live on the mesh); in async
        mode the weight-sync channel device_puts a fresh snapshot here
        (SURVEY.md §2 #11)."""
        self._params = params

    def _cache_shapes(self, batch: int, slots: int) -> dict:
        """Bytes of what ``_generate`` allocates for such a batch, from
        shapes (nothing is placed): ``cache_bytes``, what is indexed by
        position (keys and values, or latents); ``state_bytes``, what
        is not (recurrent states and convolution inputs: read and
        written whole at every decode step); with an indexer,
        ``index_cache_bytes``: its keys' part of ``cache_bytes``."""
        key = (batch, slots)
        if key not in self._cache_bytes:
            mc = self._decode_cfg
            mixers = [m for m, _ in mc.layer_kinds() if m]
            entries = jax.eval_shape(lambda: [
                cache_entry(mc, m, batch, slots, jnp.dtype(mc.dtype),
                            quantized=self.cfg.quantize_kv) for m in mixers])
            sizes = {"cache_bytes": 0, "state_bytes": 0}
            for kind, entry in zip(map(MIXERS.get, mixers), entries):
                for leaf, x in entry.items():
                    size = x.size * x.dtype.itemsize
                    sizes[kind.cache_kind + "_bytes"] += size
                    if leaf in kind.index_leaves:
                        sizes["index_cache_bytes"] = size + sizes.get(
                            "index_cache_bytes", 0)
            self._cache_bytes[key] = sizes
        return self._cache_bytes[key]

    def dispatch_attrs(self, prompts_shape, lens, params: Any = None) -> dict:
        """What the ``rollout.dispatch`` span carries of a batch of
        prompts of ``prompts_shape`` with ``lens`` real tokens, from
        shapes and lengths: what a decode step touches of the cache
        (:meth:`_cache_shapes`; 0 under ``paged``, whose pool is sized
        apart), ``weight_bytes`` (the copy of the weights it reads,
        ``prep_decode_params``) and what the model says of its steps
        (``models.transformer.decode_attrs``)."""
        params = params if params is not None else self._params
        if self._weight_bytes is None and params is not None:
            tree = jax.eval_shape(
                lambda p: prep_decode_params(p, self.model_cfg,
                                             self.cfg.quantize_weights),
                params)
            self._weight_bytes = sum(x.size * x.dtype.itemsize
                                     for x in jax.tree.leaves(tree))
        weights = {"weight_bytes": self._weight_bytes or 0}
        if self.cfg.paged:
            return {"cache_bytes": 0, "state_bytes": 0, **weights,
                    **decode_attrs(self.model_cfg)}
        T = self.cfg.max_new_tokens
        slots = cache_slots(prompts_shape[1] + T)
        return {**self._cache_shapes(prompts_shape[0], slots), **weights,
                **decode_attrs(self.model_cfg, lens, slots, T)}

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
        return GenerationResult(**self._generate_jit(
            params, prompt_ids, prompt_lens, rng, max_new_tokens=T))

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
        # the dropless expert layer routes a prompt's padding nowhere,
        # and a recurrent layer's state stops at the last real token
        pad_kw = {"token_mask": positions < prompt_lens[:, None]} \
            if self.model_cfg.takes_token_mask else {}
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
