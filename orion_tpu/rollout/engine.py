"""The fixed-batch rollout engine — TPU-native equivalent of the
reference's vLLM generation engine (SURVEY.md §2 #5, §3c).

One jitted program per (batch, prompt_len, max_new_tokens) bucket
(XLA-first, static shapes): prefill (full-seq forward filling the KV
cache) then ONE ``lax.while_loop`` with per-sequence early exit — the
loop terminates as soon as every sequence is done, so wall-clock tracks
the longest completion, not the static bound.  Which loop follows from
the model's description, not from a rollout flag:

- an autoregressive model: one-token steps, a token a row a step;
- a block-diffusion model (``ModelConfig.block_length``,
  :meth:`RolloutEngine._generate_blocks`): a row's positions in blocks;
  a block starts as the mask token wherever the prompt does not reach
  and takes ``denoising_steps`` forwards of its ``block_length``
  positions, each of which reveals the masked positions whose sampled
  candidate is the most probable; its final tokens' keys and values are
  committed inside the next block's first forward, whose rows they ride
  in front of: a step yields no fixed token a row, a row ends at a
  block's end, and the result carries at which step every token was
  revealed (``reveal_step``), without which no trainer can score it.

Both:
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
  batch, ROADMAP D3(a)); what a model cannot run is refused with its
  own reasons (models.transformer.cannot_run); a step reads the dense
  cache's filled prefix in blocks (models.transformer.prefix_step:
  slots fill from 0 up, so what lies past the batch's furthest position
  is never fetched).

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
import numpy as np

from orion_tpu.config import ModelConfig, RolloutConfig
from orion_tpu.models.transformer import (MIXERS, cache_entry, cache_slots,
                                         cannot_run, decode_attrs, init_cache,
                                         make_decode_twin, prep_decode_params,
                                         sown, ut_weight_reads)
from orion_tpu.ops.logprobs import pack_sequences
from orion_tpu.ops.moe import step_form
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
    # a block-diffusion model's sampling trace: [B, T] int, the
    # denoising step of its block at which each completion position was
    # revealed (``denoising_steps`` where it never was); None otherwise
    reveal_step: Optional[jnp.ndarray] = None
    # a model with the dropless expert layer: [2] int, the held experts'
    # stacks the steps read and held, over expert layers and steps run
    # (counted where a step reads only the stacks its rows selected,
    # ops.moe.step_form; equal where every step reads them all); None
    # for any other model, and from an engine that does not count
    expert_stacks: Optional[jnp.ndarray] = None

    def _fields(self) -> dict:
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)}

    def to_host(self) -> "GenerationResult":
        """Numpy copy of every field via ONE batched device→host
        transfer.  Every separate fetch is its own blocking device
        round trip; host consumers (reward fns, stats, detokenization)
        must use this copy, never per-field ``np.asarray``."""
        return GenerationResult(**jax.device_get(self._fields()))


def _weight_reads(tree, model_cfg: ModelConfig) -> dict:
    """``weight_bytes`` of a decode copy of the weights (shapes), and
    what the model says a step reads of it how often
    (``models.transformer.ut_weight_reads``)."""
    return {"weight_bytes": sum(x.size * x.dtype.itemsize
                                for x in jax.tree.leaves(tree)),
            **ut_weight_reads(model_cfg, tree)}


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
                "the fixed-batch engine steps a lockstep batch, one token "
                "or one block a row, and verifies no draft"
                + (f" ({cannot_run(model_cfg, 'speculative')})"
                   if model_cfg.block_length else ""))
        if model_cfg.block_length and (
                cfg.repetition_penalty != 1.0
                or cfg.effective_min_new(eos_token_id) > 0):
            raise ValueError(
                "rollout.repetition_penalty / min_new_tokens are defined on "
                "tokens generated in order; a block-diffusion model reveals "
                "a block's positions out of order")
        self.eos_token_id = eos_token_id
        self.pad_token_id = pad_token_id
        self._params = None
        self._cache_bytes: dict = {}
        self._weight_bytes: Optional[dict] = None
        self._decode_model, self._decode_cfg = make_decode_twin(
            model, model_cfg)
        self._expert_layers = sum(
            ffn == "experts" for _, ffn in model_cfg.layer_kinds())
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
        ``index_cache_bytes``: its keys' part of ``cache_bytes``.  A stack
        run several times over keeps every pass's entries
        (``cache_bytes``) and says what one pass's are
        (``cache_bytes_a_pass``)."""
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
            if mc.total_ut_steps > 1:
                sizes["cache_bytes_a_pass"] = sizes["cache_bytes"]
                sizes["cache_bytes"] *= mc.total_ut_steps
            self._cache_bytes[key] = sizes
        return self._cache_bytes[key]

    def dispatch_attrs(self, prompts_shape, lens, params: Any = None) -> dict:
        """What the ``rollout.dispatch`` span carries of a batch of
        prompts of ``prompts_shape`` with ``lens`` real tokens, from
        shapes and lengths: what a decode step touches of the cache
        (:meth:`_cache_shapes`; 0 under ``paged``, whose pool is sized
        apart), ``weight_bytes`` (the copy of the weights it reads,
        ``prep_decode_params``; :func:`_weight_reads`) and what the model
        says of its steps (``models.transformer.decode_attrs``)."""
        params = params if params is not None else self._params
        if self._weight_bytes is None and params is not None:
            tree = jax.eval_shape(
                lambda p: prep_decode_params(p, self.model_cfg,
                                             self.cfg.quantize_weights),
                params)
            self._weight_bytes = _weight_reads(tree, self.model_cfg)
        weights = dict(self._weight_bytes or {"weight_bytes": 0})
        if self.cfg.paged:
            return {"cache_bytes": 0, "state_bytes": 0, **weights,
                    **decode_attrs(self.model_cfg)}
        T = self.cfg.max_new_tokens
        slots = cache_slots(self._positions(prompts_shape[1], T))
        return {**self._cache_shapes(prompts_shape[0], slots), **weights,
                **decode_attrs(self.model_cfg, lens, slots, T,
                               self.cfg.quantize_kv)}

    def _positions(self, prompt_len: int, new_tokens: int) -> int:
        """The positions a row of the cache must hold: prompt and new
        tokens; for a block-diffusion model whole blocks, as far as a
        lockstep row's last block can reach."""
        mc = self.model_cfg
        if not mc.block_length:
            return prompt_len + new_tokens
        return prompt_len + mc.blocks_spanned(new_tokens) * mc.block_length

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
        out = self._generate_jit(params, prompt_ids, prompt_lens, rng,
                                 max_new_tokens=T)
        if self._expert_layers:
            # the einsum form counts nothing: every step read every stack
            out.setdefault("expert_stacks", np.ones((2,), np.int32))
        return GenerationResult(**out)

    def _generate(self, params, prompt_ids, prompt_lens, rng,
                  max_new_tokens: int):
        if self.model_cfg.block_length:
            return self._generate_blocks(params, prompt_ids, prompt_lens,
                                         rng, max_new_tokens)
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

        # what a step's expert layers count of themselves rides the
        # loop; a model that counts nothing runs the step as it was
        mc = self.model_cfg
        counted = bool(self._expert_layers and step_form(
            B, mc.num_experts_per_tok, mc.n_routed_experts,
            mc.moe_intermediate_size))

        def cond(c):
            t, _, _, _, done, _, _, _, _ = c
            return (t < T) & ~jnp.all(done)

        def body(c):
            t, cur_tok, cur_pos, rng, done, tokens, logps, plogps, state = c
            cache, comp_len, seen, *read = state
            step = partial(self._decode_model.apply, {"params": params},
                           cur_tok[:, None], cur_pos[:, None], cache)
            if counted:
                (step_logits, cache), sowed = step(mutable=["intermediates"])
                read = [read[0] + sum(sown(sowed, "moe_step_read"))]
            else:
                step_logits, cache = step()
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
                    plogps, (cache, comp_len, seen, *read))

        init = (jnp.int32(1), tok0, prompt_lens, rng, done, tokens, logps,
                plogps, (cache, comp_len, seen, *[jnp.int32(0)] * counted))
        with jax.named_scope("decode"):
            t, _, _, _, done, tokens, logps, plogps, \
                (cache, comp_len, seen, *read) = \
                jax.lax.while_loop(cond, body, init)

        mask = (jnp.arange(T)[None, :] < comp_len[:, None]).astype(jnp.float32)
        sequences = pack_sequences(prompt_ids, prompt_lens, tokens)
        stacks = {"expert_stacks": jnp.stack(
            [read[0], (t - 1) * self._expert_layers * mc.experts_held])} \
            if counted else {}
        return dict(
            **stacks,
            sequences=sequences,
            completions=tokens,
            completion_mask=mask,
            completion_lens=comp_len,
            logprobs=logps,
            policy_logprobs=plogps,
            prompt_lens=prompt_lens,
            total_lens=prompt_lens + comp_len,
        )

    def _generate_blocks(self, params, prompt_ids, prompt_lens, rng,
                         max_new_tokens: int):
        """``_generate`` for a block-diffusion model (the same jitted
        program, so the same name in a trace).  Rows run in lockstep,
        row b's i-th block being block ``prompt_lens[b] // block_length
        + i`` of its positions (blocks are aligned to position 0: the
        prompt's last ``len % block_length`` tokens share the first
        block with the first new ones).

        Prefill writes the prompt's keys and values under the clean
        rule.  Then per block: its state shows the prompt's tokens that
        lie in it and the mask token elsewhere; ``denoising_steps``
        forwards of its ``block_length`` positions against the cache of
        the earlier blocks and its own slots (both directions), each
        followed by a draw of one candidate a still-masked position from
        ``softmax(logits / temperature)`` (top-k / top-p as for a
        one-token step; the mask token's logit barred) and the reveal of
        the ``block_length / denoising_steps`` masked positions whose
        candidate is the most probable under that distribution (the
        lowest position on a tie); the other candidates are thrown away.
        The slots then hold the keys and values of the block's state
        BEFORE its last reveal: the block is committed by the next
        block's first forward, which carries its ``block_length`` final
        tokens in front of its own rows (``2 * block_length`` positions
        a row: a forward writes its rows' keys and values before any of
        its queries reads, so the next block's queries see the final
        keys), and reads logits off its own rows alone.  The first block
        a row generates has none before it to commit (the prompt's are
        prefill's): its riding rows stand past the cache's end, where
        nothing is written and no query sees; the last block the loop
        runs is never committed: nothing reads its slots.  A
        position past ``prompt_len + max_new_tokens`` is never revealed
        (it stays the mask token, so the trace of what was revealed is
        whole), a row is done when a finished block holds a stop token
        or its last new position.  What follows the first stop token has
        completion mask 0; the tokens its block revealed there stay in
        ``sequences`` (the states other tokens were drawn from showed
        them) and are padding in ``completions``."""
        from orion_tpu.ops.sampling import bar_token, is_stop_token

        cfg, mc = self.cfg, self._decode_cfg
        B, P = prompt_ids.shape
        T = max_new_tokens
        Bd, S, mask_id = mc.block_length, mc.denoising_steps, mc.mask_id
        per_step = Bd // S
        eos, pad = self.eos_token_id, self.pad_token_id
        sample = partial(sample_tokens, temperature=cfg.temperature,
                         top_k=cfg.top_k, top_p=cfg.top_p)
        params = prep_decode_params(params, self.model_cfg, False)
        apply = partial(self._decode_model.apply, {"params": params})

        n_blocks = mc.blocks_spanned(T)         # the most a row needs
        slots = self._positions(P, T)
        cache = init_cache(mc, B, slots, dtype=jnp.dtype(mc.dtype))
        positions = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (B, P))
        with jax.named_scope("prefill"):
            # the blocks the prompt fills are final; the slots of its
            # last, partial block are written again by that block's own
            # forwards before any query sees them
            _, cache = apply(
                prompt_ids, positions, cache,
                logits_positions=jnp.zeros((B, 1), jnp.int32),
                token_mask=positions < prompt_lens[:, None])

        first = prompt_lens // Bd                       # [B] block index
        last = prompt_lens + T                          # [B] one past
        rows = jnp.arange(B)[:, None]
        offs = jnp.arange(Bd, dtype=jnp.int32)

        def blank(fill, dtype):
            return jnp.full((B, slots), fill, dtype)

        seq = blank(pad, jnp.int32).at[:, :P].set(jnp.where(
            positions < prompt_lens[:, None], prompt_ids, pad))
        out0 = {"seq": seq, "lp": blank(0.0, jnp.float32),
                "plp": blank(0.0, jnp.float32), "step": blank(S, jnp.int32)}

        def cond(c):
            i, done = c[0], c[1]
            return (i < n_blocks) & ~jnp.all(done)

        def body(c):
            i, done, comp_len, cache, rng, out, prev = c
            pos = (first + i)[:, None] * Bd + offs                # [B, Bd]
            new = (pos >= prompt_lens[:, None]) & (pos < last[:, None])
            z = jnp.where(pos < prompt_lens[:, None], out["seq"][rows, pos],
                          mask_id)
            rec = {"lp": jnp.zeros((B, Bd), jnp.float32),
                   "plp": jnp.zeros((B, Bd), jnp.float32),
                   "step": jnp.full((B, Bd), S, jnp.int32)}
            # the block before (``prev``: its final tokens) rides in front
            # of this one's first forward; the first block has none: its
            # riding rows stand past the cache's end
            ride = jnp.concatenate(
                [jnp.where(i > 0, pos - Bd, cache_slots(slots) + offs), pos],
                axis=1)

            def denoise(s, c, riding: bool = False):
                z, masked, cache, rng, rec = c
                with jax.named_scope("denoise"):
                    if riding:
                        logits, cache = apply(
                            jnp.concatenate([prev, z], axis=1), ride, cache,
                            logits_positions=jnp.broadcast_to(
                                Bd + offs, (B, Bd)))
                    else:
                        logits, cache = apply(z, pos, cache)
                rng, sub = jax.random.split(rng)
                cand, lp, plp = sample(
                    sub, bar_token(logits, mask_id).reshape(B * Bd, -1))
                cand, lp, plp = (t.reshape(B, Bd) for t in (cand, lp, plp))
                # the most probable candidates among the masked positions,
                # the lower position first on a tie (top_k is stable)
                conf = jnp.where(masked, jnp.exp(lp), -1.0)
                top, where = jax.lax.top_k(conf, per_step)
                reveal = jnp.zeros((B, Bd), bool).at[rows, where].set(
                    top >= 0.0)
                z = jnp.where(reveal, cand, z)
                rec = {"lp": jnp.where(reveal, lp, rec["lp"]),
                       "plp": jnp.where(reveal, plp, rec["plp"]),
                       "step": jnp.where(reveal, s, rec["step"])}
                return z, masked & ~reveal, cache, rng, rec

            live = new & ~done[:, None]
            state = denoise(0, (z, live, cache, rng, rec), riding=True)
            z, _, cache, rng, rec = jax.lax.fori_loop(1, S, denoise, state)
            # dropped where a row is done or the position is not new
            at = jnp.where(live, pos, slots)
            out = {"seq": out["seq"].at[rows, at].set(z, mode="drop"),
                   **{n: out[n].at[rows, at].set(rec[n], mode="drop")
                      for n in rec}}
            stop = live & is_stop_token(z.reshape(-1), eos,
                                        cfg.stop_token_ids).reshape(B, Bd)
            # up to the first stop token of the block, else all of it
            upto = jnp.where(jnp.any(stop, axis=1),
                             pos[:, 0] + jnp.argmax(stop, axis=1) + 1,
                             jnp.minimum(pos[:, -1] + 1, last))
            comp_len = jnp.where(done, comp_len, upto - prompt_lens)
            done = done | jnp.any(stop, axis=1) | (pos[:, -1] + 1 >= last)
            return i + 1, done, comp_len, cache, rng, out, z

        init = (jnp.int32(0), jnp.zeros((B,), bool),
                jnp.zeros((B,), jnp.int32), cache, rng, out0,
                jnp.full((B, Bd), mask_id, jnp.int32))
        with jax.named_scope("decode"):
            _, _, comp_len, _, _, out, _ = jax.lax.while_loop(
                cond, body, init)

        at = prompt_lens[:, None] + jnp.arange(T)[None, :]
        real = jnp.arange(T)[None, :] < comp_len[:, None]

        def window(name):
            return jnp.take_along_axis(out[name], at, axis=1)

        return dict(
            sequences=out["seq"][:, :P + T],
            completions=jnp.where(real, window("seq"), pad),
            completion_mask=real.astype(jnp.float32),
            completion_lens=comp_len,
            logprobs=jnp.where(real, window("lp"), 0.0),
            policy_logprobs=jnp.where(real, window("plp"), 0.0),
            prompt_lens=prompt_lens,
            total_lens=prompt_lens + comp_len,
            reveal_step=window("step"),
        )
