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
- the cache is dense ([B, P+T] per layer; the latent form for
  ``latent_attention``; per layer by kind for a pattern model; with the
  indexer's keys beside k and v under sparse attention), int8
  under ``quantize_kv``, or paged under
  ``RolloutConfig.paged`` (block tables + the Pallas paged-decode
  kernel; slower than dense for a fixed batch, ROADMAP D3(a)); a
  one-token step reads the dense cache's filled prefix in blocks
  (models.transformer.prefix_step: slots fill from 0 up, so what lies
  past the batch's furthest position is never fetched);
- a recurrent layer (``ModelConfig.recurrent``: the delta rule's or a
  state-space layer) has no slot to overwrite: its cache entry is a
  state, and prefill (given ``token_mask``) hands decode each row's
  state and last convolution inputs after its last real prompt token
  (models.transformer.KimiDeltaAttention, Mamba2).  A block without a
  mixer caches nothing ({}).  The decode loop is the same.

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
from orion_tpu.models.transformer import (PREFIX_STEP_MIXERS, cache_slots,
                                         init_cache, prefix_lengths,
                                         prefix_step_slots)
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
        from orion_tpu.models.transformer import make_decode_twin

        self._decode_model, self._decode_cfg = make_decode_twin(
            model, model_cfg)
        if model_cfg.pattern:
            latent = model_cfg.latent_attention
            sparse = model_cfg.arch == "keye_dsa"
            relu2 = model_cfg.moe_activation == "relu2"
            state = ", and a recurrent state is not made of pages" \
                if model_cfg.recurrent else ""
            for on, missing in (
                    (cfg.paged, "rollout.paged: "
                     + ("there is no latent paged cache (ops/paged_kv.py "
                        "and the Pallas paged-decode kernel hold per-head "
                        "K/V pages)" if latent else
                        "there is no selection inside paged attention nor "
                        "a page pool for the indexer's keys "
                        "(ops/paged_kv.py)" if sparse else
                        "init_paged_cache gives every layer pages")
                     + state),
                    (cfg.quantize_kv, "rollout.quantize_kv: there is no "
                     + ("int8 latent cache (ops/quant.py scales per head)"
                        if latent else "int8 cache under a selection (the "
                        "gathered step reads rows of bf16 keys and values)"
                        if sparse else "int8 cache for a model whose "
                        "layers do not all hold keys and values")
                     + (", nor an int8 form of a float32 recurrent state"
                        if model_cfg.recurrent else "")),
                    (cfg.quantize_weights, "rollout.quantize_weights: "
                     + ("there are no int8 expert stacks or absorbed int8 "
                        "kv_b_proj (ops/quant.py quantises Dense kernels)"
                        if latent else "there are no int8 expert stacks, "
                        "and an int8 indexer would select other keys than "
                        "the update's" if sparse else
                        "there are no int8 expert stacks, and ops/quant.py "
                        "was not run on experts without a gate or on a "
                        "state-space layer's one input projection"
                        if relu2 else
                        "the int8 Dense twins do not reach "
                        "this block (no QuantDense decode twin was run "
                        "against its reference)"))):
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
        self._generate_jit = jax.jit(
            self._generate, static_argnames=("max_new_tokens",))

    # -- weight hot-reload channel (trainer → rollout) ------------------
    def load_weights(self, params: Any) -> None:
        """Install new policy weights.  In sync mode this is a reference
        swap (zero copy — the arrays already live on the mesh); in async
        mode the weight-sync channel device_puts a fresh snapshot here
        (SURVEY.md §2 #11)."""
        self._params = params

    def _cache_shapes(self, batch: int, prompt_len: int,
                      max_new_tokens: Optional[int] = None):
        T = int(max_new_tokens or self.cfg.max_new_tokens)
        key = (batch, prompt_len + T)
        if key not in self._cache_bytes:
            cache = jax.eval_shape(
                lambda: init_cache(self._decode_cfg, *key,
                                   dtype=jnp.dtype(self._decode_cfg.dtype),
                                   quantized=self.cfg.quantize_kv))
            sizes = {"cache": 0, "state": 0, "index": 0}
            for layer in cache:       # the decode twin's: one per layer
                kind = "state" if "S" in layer else "cache"
                sizes[kind] += sum(x.size * x.dtype.itemsize
                                   for x in jax.tree.leaves(layer))
                if "ki" in layer:
                    sizes["index"] += (layer["ki"].size
                                       * layer["ki"].dtype.itemsize)
            self._cache_bytes[key] = sizes
        return self._cache_bytes[key]

    def cache_bytes(self, batch: int, prompt_len: int,
                    max_new_tokens: Optional[int] = None) -> int:
        """Bytes of what ``_generate`` allocates for such a batch that
        is indexed by position: the keys and values, or latents, of
        every slot of every layer that has them (shapes only, nothing
        is placed; 0 under ``paged``, whose pool is sized apart)."""
        if self.cfg.paged:
            return 0
        return self._cache_shapes(batch, prompt_len, max_new_tokens)["cache"]

    def index_cache_bytes(self, batch: int, prompt_len: int,
                          max_new_tokens: Optional[int] = None) -> int:
        """The part of :meth:`cache_bytes` that is a sparse-attention
        indexer's keys (one head of ``sa_index_head_dim`` a slot and
        layer), which a decode step reads up to where it is filled,
        where it reads only the selected rows of the keys and values.
        0 for a model without an indexer."""
        if self.cfg.paged:
            return 0
        return self._cache_shapes(batch, prompt_len, max_new_tokens)["index"]

    def state_bytes(self, batch: int, prompt_len: int,
                    max_new_tokens: Optional[int] = None) -> int:
        """Bytes of the per-sequence state that is NOT indexed by
        position (the recurrent layers' states and convolution inputs):
        read and written whole at every decode step.  0 for a model
        without such layers."""
        if self.cfg.paged:
            return 0
        return self._cache_shapes(batch, prompt_len, max_new_tokens)["state"]

    def kv_step_read(self, lens, prompt_len: int,
                     max_new_tokens: Optional[int] = None) -> dict:
        """{kv_step_form, kv_step_slots}: how a one-token step's
        attention reads a dense slot cache after prompts of ``lens``
        real tokens (``models/transformer.py::prefix_step``): ``prefix``
        (the filled blocks) / ``whole`` (a cache of one block), and the
        slots one row's step then reads a layer, the mean over the
        steps.  {} where no step goes through ``prefix_step``: under
        ``paged``, or a model whose mixers are not among
        ``PREFIX_STEP_MIXERS`` (a selection, recurrent layers alone).
        Host numbers, from shapes and lengths; not under an ``sa_`` name
        (``trainers/base.py::sa_step_read``)."""
        if self.cfg.paged or not any(
                m in PREFIX_STEP_MIXERS
                for m, _ in self.model_cfg.layer_kinds()):
            return {}
        T = int(max_new_tokens or self.cfg.max_new_tokens)
        slots = cache_slots(prompt_len + T)
        return {"kv_step_form":
                "prefix" if len(prefix_lengths(slots)) > 1 else "whole",
                "kv_step_slots": prefix_step_slots(lens, slots, T)}

    def weight_bytes(self, params: Any = None) -> int:
        """Bytes of the copy of the weights a decode step reads
        (``prep_decode_params``: the compute dtype, int8 kernels under
        ``quantize_weights``), from shapes."""
        from orion_tpu.models.transformer import prep_decode_params

        params = params if params is not None else self._params
        if params is None:
            return 0
        if self._weight_bytes is None:
            tree = jax.eval_shape(
                lambda p: prep_decode_params(p, self.model_cfg,
                                             self.cfg.quantize_weights),
                params)
            self._weight_bytes = sum(x.size * x.dtype.itemsize
                                     for x in jax.tree.leaves(tree))
        return self._weight_bytes

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
