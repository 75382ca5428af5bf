"""Continuous-batching generation engine (SURVEY.md §2 #5, §3c).

TPU-native counterpart of vLLM's continuous batching: a fixed number of
engine *slots* decode in lockstep inside jitted segments, while the
native scheduler (orion_tpu/runtime) admits waiting requests into freed
slots **between** segments — XLA's static-shape regime makes token-level
admission impossible, so admission happens at segment granularity.

Device state is one persistent paged-KV pool (per layer) + a block
table; each slot's pages are assigned by the scheduler, so a retiring
sequence's pages are recycled into the next admission with no cache
reshuffling.  The per-segment jitted program is the same model decode
step the simple engine uses (paged Pallas attention), batched over all
slots; empty slots ride along masked.

PR 8 turned this into a standing generation SERVICE:

- ``submit()`` / ``step()`` are the request-level surface — requests
  arrive over time (with optional priority / deadline), each ``step``
  runs one wave, and completions stream back as they finish.
  ``generate()`` remains the run-to-completion wrapper.
- Pages are allocated ON DEMAND and recycled mid-flight: admission
  grants pages for the prompt + first token only, each wave extends
  in-flight sequences by one segment's worth against the scheduler's
  watermark, and a harvested request's pages free at that segment
  boundary.  When the pool still runs dry the engine preempts the
  youngest decoding request (restart-by-recompute, vLLM style).
- Cross-request prefix caching: full prompt pages are chain-hashed;
  hash-matched prefixes share the retired requests' pages read-only
  (refcounted in the scheduler) and skip their prefill — the k-clone
  shared-prompt machinery generalized to arbitrary common prefixes.
  The cache is dropped whenever new weights land.
- Chunked prefill: ``chunked_prefill_tokens`` bounds how much prompt a
  single wave forwards, so admitting a long prompt interleaves with
  decode segments instead of stalling every in-flight slot.

PR 10 added speculative decoding v2 — the dense engine's n-gram
draft/verify ported onto the paged per-slot machinery:

- Per-slot draft/verify: each decoding slot independently drafts up
  to ``speculative_k`` tokens by prompt-lookup against its own
  device-side sequence buffer, and ONE paged forward verifies all
  slots' k+1 candidate positions in lockstep.  The scheduler reserves
  ``k`` verify-slack positions per extension (``extend(..., slack)``)
  so rejected-draft KV lands inside the reservation and is rolled
  back in place (overwritten by the next chunk, never freed).
- Full sampler composition: repetition_penalty / min_new_tokens /
  EOS + stop-in-chunk are applied per candidate position with the
  seen-set updated INSIDE the chunk, so greedy output is
  token-identical to the sequential path and temperature>0 keeps the
  exact delta-draft marginal (Leviathan-style acceptance).
- Adaptive k: a per-request acceptance EMA decides per wave whether
  the verify chunk pays for itself; waves whose decoding slots all
  draft below ``spec_breakeven`` run the plain segment instead (cold
  workloads degrade to ~zero overhead), and cold slots riding a hot
  wave keep drafting for free — which is also how they re-probe.

PR 12 made the service MULTI-TENANT and STREAMING:

- Token streaming: ``submit(..., stream=True)`` delivers completion
  tokens INCREMENTALLY as waves harvest them — via ``poll(req_id)``
  (pull) or an ``on_tokens`` callback fired inside ``step()`` (push).
  Streaming changes only what the host FETCHES per wave (the token
  buffer rides the existing lagged flags snapshot), never what the
  device computes, so the streamed token sequence is bit-exact
  against ``generate()`` for the same seed.  A preempted streaming
  request restarts its stream (``StreamChunk.restarted``: discard
  earlier chunks — restart-by-recompute re-derives them).
- Per-tenant QoS: ``submit(..., tenant=...)`` tags requests with an
  admission class.  ``configure_tenant`` registers a weighted-fair
  share (scheduler-level WFQ layered UNDER the fifo/priority/EDF
  policy), a token-bucket rate limit, and a per-tenant queue cap;
  ``cfg.max_queued_requests`` adds a global waiting watermark.  A
  refused submit raises the typed :class:`EngineOverloaded` carrying
  queue depth + a retry-after hint (load shedding fails fast instead
  of queueing without bound).  Per-tenant TTFT/queue-wait percentiles
  ride ``server_stats()`` as ``tenant_<name>_*`` keys.
- ``cancel(req_id)`` aborts an in-flight request (waiting: dequeued;
  decoding: pages freed via the preemption machinery; mid-chunked-
  prefill: deferred one wave to the activation boundary).

PR 17 made the prefix cache TIERED: when the device pool LRU-evicts an
unreferenced cached page, the scheduler records a (hash, page) event
and the engine copies that page's KV into a host-RAM tier
(:class:`~orion_tpu.rollout.host_cache.HostKVCache`, byte-budgeted by
``cfg.host_cache_bytes``) BEFORE the next pool-donating dispatch can
overwrite it; a later ``submit`` whose chain hashes miss the device
cache but hit host re-admits the page device-side (one pool upload)
and its prefill skips exactly as a device hit would — bit-identical KV
by hash construction, so tokens and logprobs match the cold path.
Both tiers flush together on weight reload.  ``submit(...,
logprobs=True)`` additionally streams per-token sampling logprobs in
every :class:`StreamChunk`, riding the same lagged snapshot as the
streamed tokens.

Flow per wave (one ``step()``):
  apply deferred cancels -> admit -> spill evicted pages to host ->
  chunk-prefill admitted/partial prompts (final chunks sample their
  first token) -> extend in-flight reservations (preempting if dry)
  -> spill again -> decode segment of K tokens OR speculative verify
  segment (jitted) -> harvest finished slots (one wave lagged), free
  their pages, emit stream chunks, return completions.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
from functools import partial
from typing import Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from orion_tpu import obs
from orion_tpu.config import ModelConfig, RolloutConfig
from orion_tpu.obs import RequestTelemetry
from orion_tpu.ops.sampling import (apply_repetition_penalty,
                                    eos_forbid_mask, is_stop_token,
                                    sample_tokens, seen_from_prompts,
                                    transformed_logits)
from orion_tpu.runtime import PyScheduler, Scheduler

# slot lifecycle: empty -> prefilling (admitted, prompt KV being
# written chunk by chunk) -> decoding (first token sampled, segments
# advance it) -> empty (harvested or preempted).
_EMPTY, _PREFILL, _DECODE = 0, 1, 2


# Host-tier page movement (PR 17): spill/re-admit/handoff batches move
# many pages at once, and an eager per-page `pool[page]` read or
# `.at[page].set` write costs one dispatch PER layer-key — at CPU/TPU
# dispatch latency that overhead alone can exceed the prefill the tier
# skips.  One jitted program per direction keeps any batch at a single
# dispatch; callers pad the index vector to a power of two so the
# compiled-program space stays a handful of buckets.
@jax.jit
def _gather_pages(pools, idx):
    return [{k: v[idx] for k, v in p.items()} for p in pools]


@jax.jit
def _scatter_pages(pools, idx, rows):
    return [{k: v.at[idx].set(rows[i][k]) for k, v in p.items()}
            for i, p in enumerate(pools)]


@dataclasses.dataclass
class CompletedRequest:
    req_id: int
    tokens: np.ndarray          # [n] completion token ids
    logprobs: np.ndarray        # [n] sampling-dist logprobs (f32)
    policy_logprobs: np.ndarray  # [n] raw (untempered) policy logprobs


@dataclasses.dataclass
class StreamChunk:
    """One increment of a streaming request's completion (PR 12).

    ``tokens`` holds the completion tokens emitted since the previous
    chunk.  ``restarted`` means the request was preempted (restart-by-
    recompute): every previously delivered chunk is void and this
    chunk restarts the stream from completion position 0.  The final
    chunk has ``done=True`` and carries the full
    :class:`CompletedRequest` (tokens + logprobs), which is bit-exact
    against what ``generate()`` returns for the same seed.

    ``logprobs`` (PR 17): for requests submitted with
    ``logprobs=True``, the sampling-dist logprob of each token in
    ``tokens`` (same length, same order, bit-exact against the
    completed record's ``logprobs``); None otherwise."""

    req_id: int
    tokens: np.ndarray
    done: bool = False
    restarted: bool = False
    completed: Optional[CompletedRequest] = None
    logprobs: Optional[np.ndarray] = None


class EngineOverloaded(RuntimeError):
    """Typed backpressure (PR 12): admission refused by a QoS gate —
    the global waiting watermark (``cfg.max_queued_requests``), a
    tenant's queue cap, or a tenant's rate limit.  Carries the
    observed queue depth and a retry-after hint so clients back off
    with information instead of guessing; the serving gateway
    forwards both to remote clients."""

    def __init__(self, reason: str, queue_depth: int = 0,
                 retry_after: float = 0.0,
                 tenant: Optional[str] = None):
        super().__init__(reason)
        self.queue_depth = int(queue_depth)
        self.retry_after = float(retry_after)
        self.tenant = tenant


class ContinuousBatchingEngine:
    """Throughput-oriented generation over a stream of requests."""

    # Trainers may pass unique prompts + group_size to generate_batch
    # instead of pre-repeating each prompt k times (VERDICT r4 missing
    # #3): the engine prefills each unique prompt ONCE and the k clones
    # share its read-only prompt pages.
    supports_groups = True

    def __init__(self, model, model_cfg: ModelConfig, cfg: RolloutConfig,
                 eos_token_id: Optional[int] = None, pad_token_id: int = 0,
                 segment_len: Optional[int] = None,
                 mesh: Optional[Mesh] = None):
        from orion_tpu.models.transformer import cannot_run

        why = cannot_run(model_cfg, "continuous")
        if why:
            raise ValueError(
                f"the continuous engine cannot run arch={model_cfg.arch!r}"
                ": its page pool, block tables and the Pallas paged-decode "
                "kernel hold per-head K/V pages of one head_dim in every "
                f"layer; {why} (use rollout.engine=simple)")
        self.mc = model_cfg
        self.cfg = cfg
        cfg.check_stop_ids(model_cfg.vocab_size, eos_token_id)
        self.eos = eos_token_id
        self.pad = pad_token_id
        self.segment_len = (cfg.segment_len if segment_len is None
                            else segment_len)
        # -- speculative decoding v2 (per-slot draft/verify, PR 10) ----
        self._spec_k = int(cfg.speculative_k)
        self._spec = self._spec_k > 0
        # One verify wave runs segment_len chunks: a slot accepting
        # nothing still advances one token per chunk — the same pace
        # the plain segment gives it — while a fully-accepting slot
        # advances (k+1)x.  (The first cut ran seg//(k+1) chunks so a
        # wave's MAX advance matched the plain segment; measured on
        # the arrivals trace that made every cold slot crawl at 1/(k+1)
        # of its plain pace and the whole trace LOST — the lockstep
        # wave must never slow its slowest row.)  The price is larger
        # per-wave extents (est_len grows by seg*(k+1) per wave,
        # approaching lifetime reservation under long budgets), which
        # the watermark + preemption machinery already bounds.
        self._spec_steps = self.segment_len
        # Draft source width: prompt + full budget (+k so the n-gram
        # window arithmetic never reads past the end).
        self._seq_cap = (cfg.max_prompt_len + cfg.max_new_tokens
                         + self._spec_k)
        # Prefix caching needs the skipped prefix to be history-free
        # for sampling state; the repetition-penalty seen-set is built
        # from the full prompt the cached path never forwards.  Same
        # for chunked prefill.  Degrade loudly, never silently.
        self._prefix_cache_on = (cfg.prefix_cache
                                 and cfg.repetition_penalty == 1.0)
        self._chunk = (cfg.chunked_prefill_tokens
                       if cfg.repetition_penalty == 1.0 else 0)
        if cfg.repetition_penalty != 1.0 and (
                cfg.prefix_cache or cfg.chunked_prefill_tokens):
            import warnings

            warnings.warn(
                "continuous engine: repetition_penalty != 1.0 disables "
                "prefix_cache and chunked_prefill_tokens (the penalty's "
                "seen-set needs the full prompt forward)", stacklevel=2)
        # Host-RAM KV tier (PR 17): spill LRU-evicted prefix-cache
        # pages instead of dropping them.  Rides the device prefix
        # cache's hash machinery, so it is only meaningful (and only
        # armed) when that cache is on — degrade loudly, never
        # silently.
        self._host_cache = None
        if cfg.host_cache_bytes > 0:
            if self._prefix_cache_on:
                from orion_tpu.rollout.host_cache import HostKVCache

                self._host_cache = HostKVCache(cfg.host_cache_bytes)
            else:
                import warnings

                warnings.warn(
                    "continuous engine: host_cache_bytes ignored — the "
                    "host KV tier requires the prefix cache "
                    "(prefix_cache=True, repetition_penalty=1.0)",
                    stacklevel=2)
        # Sharded engine (VERDICT r3 missing #2): with a mesh, the
        # decode twin's params shard via the standard tensor rules, the
        # paged pools shard over kv-heads on the tensor axis, and the
        # per-device paged-attention kernel runs on its local kv-head
        # slice (paged_decode_attention_sharded) — an 8B bf16 policy
        # (~16 GB) cannot decode on one v5e chip, so multi-device decode
        # is the flagship-config requirement, not an optimization.
        self.mesh = mesh
        from orion_tpu.models.transformer import make_decode_twin

        # All applies go through the (possibly unrolled-twin) decode
        # model; the scan-layout original is deliberately NOT kept —
        # the per-layer pools below match the unrolled cache layout.
        self._decode_model, dcfg = make_decode_twin(model, model_cfg)
        if cfg.quantize_weights:
            import dataclasses as _dc

            dcfg = _dc.replace(dcfg, quantize_dense=True)
            self._decode_model = type(self._decode_model)(dcfg)
        self._quantize_weights = cfg.quantize_weights
        self.slots = cfg.max_batch_size
        ps = cfg.page_size
        # NOT widened by the speculative slack: a wider block table
        # inflates the paged-attention gather on EVERY forward
        # (measured ~4% serving overhead for one extra page column).
        # Verify slack instead comes from extend()'s slack pages
        # where the request's own lifetime leaves room, and the chunk
        # clamps its write positions at the table edge for maximal
        # requests (see _spec_segment_fn: the clamped position's KV is
        # provably never attended by an emitted token's query).
        self.pages_per_seq = -(-(cfg.max_prompt_len + cfg.max_new_tokens)
                               // ps)
        self.num_pages = cfg.num_pages or self.slots * self.pages_per_seq
        wm = (cfg.page_watermark if cfg.page_watermark >= 0
              else self.slots)
        self._watermark = wm
        self.sched = Scheduler(self.num_pages, ps, self.slots,
                               watermark=wm, policy=cfg.admission_policy)
        # which twin serves, as the sched.* spans label it
        self._sched_impl = ("python" if isinstance(self.sched, PyScheduler)
                            else "native")

        # One extra scratch page (index num_pages): inactive/done slots
        # point their whole block table at it, so their masked lockstep
        # writes can never touch a live request's pages.
        self._scratch = self.num_pages
        shape = (self.num_pages + 1, model_cfg.num_kv_heads, ps,
                 model_cfg.head_dim)
        sshape = (self.num_pages + 1, model_cfg.num_kv_heads, 1, ps)
        dt = jnp.int8 if cfg.quantize_kv else jnp.dtype(model_cfg.dtype)

        # Pools always use the unrolled per-layer layout: decode runs
        # through the unrolled twin regardless of cfg.scan_layers.
        # One layout definition, parameterized over the allocator (the
        # mesh branch allocates directly sharded).
        def pool(alloc_kv, alloc_scale):
            out = {"k_pages": alloc_kv(), "v_pages": alloc_kv()}
            if cfg.quantize_kv:
                out["k_scales"] = alloc_scale()
                out["v_scales"] = alloc_scale()
            return out

        if mesh is not None:
            tp = dict(mesh.shape).get("tensor", 1)
            if tp > 1 and model_cfg.num_kv_heads % tp:
                # Replicated pools + a plain (GSPMD-opaque) kernel mean
                # the ENTIRE pool is all-gathered every decode step —
                # the exact regression the sharded engine exists to
                # prevent.  Degrade loudly, never silently.
                import warnings

                warnings.warn(
                    f"continuous engine: tensor={tp} does not divide "
                    f"num_kv_heads={model_cfg.num_kv_heads}; paged "
                    "pools will be REPLICATED per device and decode "
                    "attention falls back to the gathering path — "
                    "pick a tensor degree dividing the kv heads",
                    stacklevel=2)
            kv_spec = (P(None, "tensor") if tp > 1 and
                       model_cfg.num_kv_heads % tp == 0 else P())
            mk = jax.jit(lambda: jnp.zeros(shape, dt),
                         out_shardings=NamedSharding(mesh, kv_spec))
            mks = jax.jit(lambda: jnp.zeros(sshape, jnp.float32),
                          out_shardings=NamedSharding(mesh, kv_spec))
            self._pools = [pool(mk, mks)
                           for _ in range(model_cfg.num_layers)]
            from orion_tpu.models.sharded import mesh_shardings_for

            init_args = (jnp.zeros((1, 2), jnp.int32),
                         jnp.zeros((1, 2), jnp.int32))
            self._param_shardings = mesh_shardings_for(
                self._decode_model, mesh, init_args)
        else:
            self._pools = [pool(partial(jnp.zeros, shape, dt),
                                partial(jnp.zeros, sshape, jnp.float32))
                           for _ in range(model_cfg.num_layers)]
            self._param_shardings = None
        self._bt = np.full((self.slots, self.pages_per_seq), self._scratch,
                           np.int32)
        self._bt_dev = None     # device copy of _bt, rebuilt when dirty
        self._params = None

        # -- service state (submit/step) --------------------------------
        self._state = None                      # device per-slot state
        self._slot_req = np.full(self.slots, -1, np.int64)
        self._slot_seq = np.full(self.slots, -1, np.int64)
        self._phase = np.zeros(self.slots, np.int8)
        self._est_len = np.zeros(self.slots, np.int64)  # host len bound
        self._reqinfo: dict = {}    # member id -> (ids, budget, head, j, k)
        self._prefilling: dict = {}  # head id -> {"off": next position}
        self._admit_seq: dict = {}   # member id -> admission counter
        self._admit_counter = 0
        self._pending_flags = None   # lagged (done, n_new, slot_seq) snap
        self._early_out: List[CompletedRequest] = []  # pressure-harvested
        self._rng = None
        self.preemptions = 0         # recompute-restarts (metrics)
        self.prefix_cached_pages = 0  # prompt pages served from cache
        # -- multi-tenant QoS + streaming (PR 12) ----------------------
        # Tenant names map to dense scheduler ids in first-seen order;
        # per-tenant QoS envelopes (weight / rate bucket / queue cap)
        # are registered via configure_tenant and default to
        # weight-1 / unlimited for unseen tenants.
        self._tenant_ids: dict = {}      # name -> scheduler tenant id
        self._tenant_qos: dict = {}      # name -> qos dict
        self._tenant_queued: dict = {}   # name -> waiting member count
        self._req_tenant: dict = {}      # member id -> tenant name
        self._streams: dict = {}         # member id -> stream state
        self._cancels: set = set()       # deferred (mid-prefill) aborts
        self.shed_requests = 0           # EngineOverloaded refusals
        self.cancelled_requests = 0
        # -- blue/green weight rollout (PR 18) -------------------------
        # weight_version counts distinct snapshots installed (every
        # _prep_params identity-cache MISS); the prefill tier stamps
        # its KV offers with it so pages computed under old weights are
        # dropped instead of injected after a reload.  _draining gates
        # submit() while the rollout coordinator cycles this engine.
        self._weight_version = 0
        self._draining = False
        # -- adaptive-k host state (speculative v2) --------------------
        # Two signals drive the per-wave verify decision:
        # (1) DRAFTABILITY — each segment program reports, per slot,
        #     whether the trailing n-gram has a prior occurrence with
        #     a full k-token continuation (the precondition for any
        #     draft to exist).  On random text the match simply never
        #     appears, so the engine runs plain waves at ~zero
        #     overhead without needing to pay a verify chunk to learn
        #     it; on structured/cyclic text the match appears the
        #     moment the pattern recurs.
        # (2) A per-request acceptance-rate EMA (accepted/drafted,
        #     0..1), created by the request's FIRST drafted wave: a
        #     draftable-but-unproven request probes once, then its
        #     own EMA decides.  Drafted counts only cover genuinely
        #     matched rows, so riding a hot wave without a match
        #     never poisons a request's EMA.
        # The cumulative per-slot (drafted, accepted, resampled)
        # device counters are snapshotted with the lagged done flags
        # and differenced against _spec_prev on fetch; the global EMA
        # is a workload gauge for server_stats, not a decision input.
        self._accept_ema: dict = {}
        self._spec_global_ema = 0.0
        self._spec_prev = np.zeros((self.slots, 3), np.int64)
        self._spec_match = np.zeros(self.slots, bool)
        self._waves_since_spec = 0
        self.spec_drafted = 0        # draft tokens verified (engine life)
        self.spec_accepted = 0       # draft tokens accepted + emitted
        self.spec_resampled = 0      # correction/bonus tokens emitted
        # Request-lifecycle telemetry (orion_tpu.obs): submit/admit/
        # first-token/preempt/finish clocks + queue-wait/TTFT/tok-s/
        # occupancy histograms.  Host-dict cost per REQUEST transition,
        # not per token; the tracing instants inside are no-ops unless
        # the process tracer is enabled.
        self.telemetry = RequestTelemetry()
        if cfg.harvest_lag >= 0:
            self._harvest_lag = cfg.harvest_lag
        else:
            # Auto: 1 on TPU (the flag fetch overlaps the next
            # segment), 0 elsewhere.  What the fetch costs on the local
            # chip, and so whether the lag earns its extra masked
            # segment per finished request, has not been measured
            # (ROADMAP D4); chip_smoke.py prints the value in force.
            from orion_tpu.ops.pallas import target_platform

            with self._ctx():
                self._harvest_lag = 1 if target_platform() == "tpu" else 0

        self._jit_prefill = jax.jit(self._prefill_fn,
                                    donate_argnums=(1, 3),
                                    static_argnames=("Pw", "K",
                                                     "do_copy"))
        self._jit_chunk = jax.jit(self._chunk_fn, donate_argnums=(1,),
                                  static_argnames=("C",))
        self._jit_segment = jax.jit(self._segment_fn,
                                    donate_argnums=(1, 3),
                                    static_argnames=("n_steps",))
        self._jit_spec_segment = jax.jit(
            self._spec_segment_fn, donate_argnums=(1, 3),
            static_argnames=("n_steps", "k"))
        # Per-wave flag snapshot as ONE dispatch: the snapshot arrays
        # must be copies (the state buffers are donated into the next
        # segment), and 2-3 separate jnp.copy calls cost a host
        # dispatch each on the serving hot path.
        self._jit_snap = jax.jit(
            lambda *xs: tuple(
                jnp.logical_or(x, False) if x.dtype == bool else x + 0
                for x in xs))

    def _ctx(self):
        """Ambient-mesh context for jit dispatch: tracing under the mesh
        lets the model's paged decode pick the tensor-sharded kernel."""
        return self.mesh if self.mesh is not None else \
            contextlib.nullcontext()

    def _init_state(self):
        """Per-slot device state: decode cursor + ON-DEVICE completion
        buffers.  The r2 host driver fetched [S, n] token/logprob
        arrays and ran Python slot×token loops every segment (VERDICT
        r2 weak #3); now tokens accumulate device-side and the host
        fetches (done, n_new) — two small vectors — per wave, plus the
        finished rows only when a request completes."""
        S, T = self.slots, self.cfg.max_new_tokens
        state = {
            "cur_tok": jnp.zeros((S,), jnp.int32),
            "lengths": jnp.zeros((S,), jnp.int32),
            "done": jnp.ones((S,), bool),   # empty slots are "done"
            "n_new": jnp.zeros((S,), jnp.int32),
            "budget": jnp.full((S,), T, jnp.int32),  # per-request cap
            "toks": jnp.full((S, T), self.pad, jnp.int32),
            "lps": jnp.zeros((S, T), jnp.float32),
            "plps": jnp.zeros((S, T), jnp.float32),
        }
        if self.cfg.repetition_penalty != 1.0:
            # per-slot seen-token set (prompt + generated), reset at
            # admission — the repetition-penalty state.
            state["seen"] = jnp.zeros((S, self.mc.vocab_size), bool)
        if self._spec:
            # Draft source: per-slot prompt+generated token buffer
            # (prompt rows scattered in by the prefill program,
            # device-appended after) + cumulative [drafted, accepted,
            # resampled] counters — ONE [S, 3] array so the per-wave
            # snapshot costs one copy dispatch, not three — that the
            # adaptive-k EMA and server stats difference per wave.
            state["seq"] = jnp.full((S, self._seq_cap), self.pad,
                                    jnp.int32)
            # Columns: cumulative [drafted, accepted, resampled] plus
            # the draftability gauge (trailing n-gram has a prior
            # occurrence with a full k continuation, recomputed by
            # every segment program) — one array so the per-wave
            # snapshot and fetch cost one item, not four.
            state["spec_counts"] = jnp.zeros((S, 4), jnp.int32)
        if self.mesh is not None:  # replicated across the rollout group
            state = jax.device_put(
                state, NamedSharding(self.mesh, P()))
        return state

    # -- weight hot-reload channel (trainer → rollout) ------------------
    def _prep_params(self, params):
        """Compute-dtype cast (+ unstack + int8 quantization when
        enabled) as ONE jitted program.  The transforms are idempotent
        — the per-call copies inside _prefill_fn/_segment_fn see an
        already-processed tree and pass it through — so generate(...,
        params=raw_tree) overrides still work.

        Identity-cached: the async rollout worker passes the SAME
        weight snapshot for every batch until a new version lands, and
        re-running the cast+quantize pass (a full read of the weights)
        per batch bought nothing.  A cache MISS means new weights: the
        prefix cache (KV computed under the old weights) is dropped."""
        if params is getattr(self, "_prep_src", None):
            return self._prep_out
        if not hasattr(self, "_jit_prep"):
            from orion_tpu.models.transformer import prep_decode_params

            def prep(p):
                return prep_decode_params(p, self.mc,
                                          self._quantize_weights)

            # With a mesh the prepared decode tree lands directly in the
            # tensor-sharded layout — this IS the train→rollout reshard
            # (XLA lowers the layout change to ICI transfers).
            self._jit_prep = jax.jit(
                prep, out_shardings=self._param_shardings)
        # Drop the previous cache FIRST: holding the old raw snapshot +
        # old prepared tree while materializing the new one would put
        # four weight-sized trees on the rollout mesh at refresh time.
        self._prep_src = None
        self._prep_out = None
        with self._ctx():
            out = self._jit_prep(params)
        self._prep_src = params
        self._prep_out = out
        # Cached prefix KV is weight-dependent: new weights, new cache
        # — BOTH tiers, plus any undrained eviction events (their
        # pages hold old-weights KV that must never spill under a
        # still-matching hash).
        self.sched.clear_cache()
        self.sched.drain_evictions()
        if self._host_cache is not None:
            self._host_cache.clear()
        self._weight_version += 1
        return out

    def load_weights(self, params) -> None:
        """Install policy weights (same contract as RolloutEngine):
        the f32 master tree is cast to the compute dtype ONCE here, so
        every decode step reads 2 bytes/param instead of 4 (int8 when
        quantize_weights is on)."""
        self._params = self._prep_params(params)

    def dispatch_attrs(self, prompts_shape, lens, params=None) -> dict:
        """``RolloutEngine.dispatch_attrs``' names for the trainer's
        ``rollout.dispatch`` span: the page pool is this engine's own
        and is sized apart from a batch, so the bytes are 0."""
        from orion_tpu.models.transformer import decode_attrs

        return {"cache_bytes": 0, "state_bytes": 0, "weight_bytes": 0,
                **decode_attrs(self.mc)}

    # -- blue/green rollout surface (PR 18) ------------------------------
    @property
    def weight_version(self) -> int:
        """Monotonic count of distinct snapshots installed.  Anything
        derived from the weights (prefill-tier KV offers) records it
        at creation and is invalid once it moves."""
        return self._weight_version

    def params_snapshot(self):
        """The raw param tree last handed to :meth:`load_weights` —
        what the rollout coordinator retains as the rollback target
        until the fleet-wide commit point."""
        return getattr(self, "_prep_src", None)

    def reload_weights(self, params) -> int:
        """Forced param swap for the blue/green RELOAD step: busts the
        identity cache first, so even re-installing the IDENTICAL tree
        object (the rollback path) takes the full reload path — cast /
        quantize, BOTH KV tiers cleared, eviction backlog drained,
        version bumped.  Returns the new :attr:`weight_version`."""
        self._prep_src = None
        self._params = self._prep_params(params)
        return self._weight_version

    @property
    def draining(self) -> bool:
        return self._draining

    def drain(self, on: bool = True) -> None:
        """Blue/green admission gate: while draining, ``submit`` sheds
        with a typed :class:`EngineOverloaded` (callers route to
        another engine or retry after the drain).  In-flight requests
        keep decoding — the pump must keep calling ``step`` until
        :attr:`pending` hits zero."""
        self._draining = bool(on)

    def inflight_ids(self) -> List[int]:
        """Ids of every request submitted but not yet completed
        (waiting, prefilling, or decoding) — the migration set when a
        drain hits its deadline."""
        return sorted(self._reqinfo)

    @staticmethod
    def _bucket(n: int, cap: int) -> int:
        """Next power-of-2 ≥ n (≤ cap): bounds prefill recompiles to
        log2(slots) programs while wasting <2x compute on odd waves."""
        b = 1
        while b < n:
            b *= 2
        return min(b, cap)

    def _page_hashes(self, ids: np.ndarray) -> Tuple[int, ...]:
        """Chain hash per cacheable FULL prompt page: page i's hash
        covers tokens [0, (i+1)*page_size), so equal hashes imply the
        whole prefix (and its KV, which is causal) is bit-identical.
        Capped at (plen-1)//page_size pages so a fully-cached prompt
        still re-forwards >= 1 token for its first-sample logits."""
        if not self._prefix_cache_on:
            return ()
        ps = self.cfg.page_size
        n = max(0, (len(ids) - 1) // ps)
        out, h = [], b""
        for i in range(n):
            h = hashlib.blake2b(
                h + ids[i * ps:(i + 1) * ps].tobytes(),
                digest_size=8).digest()
            out.append(int.from_bytes(h, "little") & ((1 << 63) - 1))
        return tuple(out)

    # -- host-RAM KV tier (PR 17) ---------------------------------------
    def _fetch_pages(self, pages):
        """Copy the device KV of ``pages`` to host numpy arrays — ONE
        jitted gather dispatch + ONE device transfer for the whole
        batch, however many pages (eager per-page indexing costs a
        ~0.5ms dispatch per layer-key, which multiplied by a spill
        batch is more than the prefill the tier exists to skip).  Page
        counts pad to the next power of two so the gather program
        space stays a handful of buckets.  Must run BEFORE any
        pool-donating dispatch in the same wave: an eviction event's
        page is only intact until the next pool write.  Returns one
        per-page list of per-layer ``{key: array}`` dicts."""
        n = len(pages)
        idx = np.asarray(pages, np.int32)
        pad = 1
        while pad < n:
            pad *= 2
        if pad > n:
            idx = np.concatenate([idx, np.full(pad - n, idx[-1],
                                               np.int32)])
        rows = jax.device_get(_gather_pages(self._pools,
                                            jnp.asarray(idx)))
        return [[{k: np.asarray(v[i]) for k, v in layer.items()}
                 for layer in rows] for i in range(n)]

    def _fetch_page(self, page: int):
        return self._fetch_pages([page])[0]

    def _upload_pages(self, pages, rows) -> None:
        """Write host-tier KV back into the device pools at ``pages``
        (``rows[i]`` is the per-layer dict list for ``pages[i]``) —
        ONE jitted scatter dispatch for the whole batch, padded to a
        power of two by repeating the last page (duplicate scatter
        indices carry identical rows, so the repeat is a no-op).
        Runs IMMEDIATELY after the ``insert_cached`` calls that staged
        these pages — deferring past the next allocation would let an
        eviction of one of them re-spill whatever garbage the pool
        held there."""
        n = len(pages)
        idx = list(pages)
        stack = list(rows)
        while len(idx) & (len(idx) - 1):
            idx.append(idx[-1])
            stack.append(stack[-1])
        batch = [{k: jnp.asarray(np.stack([r[i][k] for r in stack]))
                  for k in stack[0][i]}
                 for i in range(len(self._pools))]
        self._pools = _scatter_pages(
            self._pools, jnp.asarray(np.asarray(idx, np.int32)), batch)

    def _upload_page(self, page: int, layers) -> None:
        self._upload_pages([page], [layers])

    def _drain_spills(self) -> None:
        """Drain the scheduler's pending LRU-eviction events and spill
        each evicted page's KV to the host tier.  Called right after
        the allocating phases of a wave (admission, extension) and
        before the next donating dispatch.  With the tier off the
        events are drained and discarded (the buffer must never grow
        unbounded).  A ``kv.spill`` fault drops that one spill — a
        degraded-but-correct outcome (the next hit re-prefills)."""
        events = self.sched.drain_evictions()
        hc = self._host_cache
        if not events or hc is None:
            return
        from orion_tpu.resilience import fault_point
        from orion_tpu.resilience.inject import InjectedFault

        keep = []
        for h, page in events:
            try:
                fault_point("kv.spill")
            except InjectedFault:
                continue
            keep.append((h, page))
        if keep:
            rows = self._fetch_pages([page for _, page in keep])
            for (h, _), data in zip(keep, rows):
                hc.put(h, data)
        obs.instant("kv.spill_batch", pages=len(events),
                    host_entries=len(hc))

    def _readmit_from_host(self, hashes) -> None:
        """Promote the longest host-tier-resident prefix of ``hashes``
        back into the device cache so the upcoming admission's cached-
        matching loop hits it.  Chain order only — a later page's KV is
        meaningless without every earlier one device-resident.  Inserts
        go into genuinely FREE pages only (churn guard: re-admission
        must never evict warmer device-cached pages), and the whole
        staged chain uploads in ONE batched dispatch before this
        returns — i.e. before any later allocation could evict one of
        the staged pages and re-spill garbage."""
        hc = self._host_cache
        staged = []
        for h in hashes:
            if self.sched.cache_lookup(h) >= 0:
                continue  # already device-cached: nothing to upload
            if self.sched.free_pages < 1:
                break
            data = hc.get(h)
            if data is None:
                break  # chain broken: later hashes cannot hit either
            page = self.sched.insert_cached(h)
            if page < 0:
                break
            staged.append((h, page, data))
        if not staged:
            return
        self._upload_pages([page for _, page, _ in staged],
                           [data for _, _, data in staged])
        for h, page, _ in staged:
            # Promoted device-side: drop the host copy (it re-spills
            # on its next device eviction) so one page's KV is never
            # double-resident against the byte budget.
            hc.pop(h)
            hc.readmits += 1
            obs.instant("kv.readmit", page=page)

    def _match_windows(self, seq, ln):
        """[S, n_win] bool: window starts whose n-gram equals each
        slot's trailing n-gram AND whose k-token continuation lies
        fully inside the content (shared by the draft lookup and the
        per-segment draftability gauge)."""
        S = self.slots
        n, k = int(self.cfg.spec_ngram), self._spec_k
        n_win = self._seq_cap - n - k + 1
        w_idx = jnp.arange(n_win)
        tgt = jnp.stack(
            [jnp.take_along_axis(
                seq, jnp.maximum(ln - n + i, 0)[:, None],
                axis=1)[:, 0] for i in range(n)], axis=1)       # [S, n]
        eq = jnp.ones((S, n_win), bool)
        for i in range(n):
            eq &= seq[:, i: i + n_win] == tgt[:, i: i + 1]
        # A match must carry its FULL k-token continuation inside the
        # content: the latest occurrence overlapping the content edge
        # would draft pad garbage past it (measured: it capped cyclic
        # acceptance at ~1/k — the cycle's one-period-earlier
        # occurrence is the right source).
        return eq & (w_idx[None, :] + n + k <= ln[:, None]) \
            & (ln >= n)[:, None]

    # -- jitted programs ------------------------------------------------
    def _cache(self, pools, bt):
        return [{**p, "block_tables": bt} for p in pools]

    def _strip(self, cache):
        """Drop block tables from the post-apply cache → pool state."""
        return [{k: v for k, v in c.items() if k != "block_tables"}
                for c in cache]

    def _chunk_fn(self, params, pools, packed, C: int):
        """One INTERMEDIATE prefill chunk: write prompt KV for C
        consecutive positions per row (positions offs[b] ..
        offs[b]+C-1, all real prompt tokens — rows whose remainder fits
        in a chunk go through _prefill_fn instead), attending causally
        to everything already in the pool.  No sampling, no state: only
        the pools change.  Pad rows ride on all-scratch tables.

        ``packed`` [B, 1 + pages_per_seq + C] int32 carries offs, the
        block-table rows and the chunk ids in ONE host->device upload
        (each separate array cost a dispatch on the serving hot
        path)."""
        from orion_tpu.models.transformer import maybe_unstack_for_decode

        params = maybe_unstack_for_decode(params, self.mc)
        offs = packed[:, 0]
        bt_rows = packed[:, 1:1 + self.pages_per_seq]
        chunk_ids = packed[:, 1 + self.pages_per_seq:]
        B = packed.shape[0]
        positions = offs[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        cache = self._cache(pools, bt_rows)
        # Project logits at one position only — they are discarded, and
        # [B, 1, V] keeps the (model-largest) vocab matmul out of the
        # chunk's cost.
        _, cache = self._decode_model.apply(
            {"params": params}, chunk_ids, positions, cache,
            logits_positions=jnp.zeros((B, 1), jnp.int32))
        return self._strip(cache)

    def _prefill_fn(self, params, pools, packed, state, rng,
                    Pw: int, K: int, do_copy: bool = True):
        """FINAL admission chunk for a wave of requests: write the last
        (or only) span of prompt KV in one jitted program, then scatter
        each request's first sampled token straight into the per-slot
        DEVICE state — admission costs zero host fetches.

        ``offs`` [B] is each row's chunk start: 0 for a one-shot
        prefill, the chunk cursor for chunked prefill, cached_pages *
        page_size when a prefix-cache hit skipped the shared prefix.
        The attention mask is position-based over the gathered pool, so
        history (cached pages + earlier chunks) is attended exactly.

        Group sampling (VERDICT r4 missing #3): each row may fan out to
        K clone slots sharing its prompt.  The prompt is prefilled ONCE
        through the primary clone's block table (bt_rows); the fully-
        filled prompt pages are physically shared by every clone's
        table, and the partial last prompt page — which decode will
        append to, so it cannot be shared — is replicated into each
        secondary clone's first private page by a page-granular
        gather/scatter (copy_src → copy_dst; ~1 page/layer/clone, noise
        next to the k× prefill FLOPs saved).  Each clone then samples
        its OWN first token from the shared last-position logits.

        Every per-row int input rides ONE ``packed`` [B, cols] int32
        upload (profiled on the serving loop: 8-9 separate ~KB arrays
        cost a host dispatch each, which dominated the activation
        path).  Column layout (host twin in ``_activate``):
        [0] prompt_lens; [1] offs; [2:2+K] slot indices (pad entries
        slot = S, out of bounds -> their scatters drop); [.. +K]
        budgets; [.. +K] copy_src; [.. +K] copy_dst page indices
        (no-op entries point at the scratch page); [.. +pages_per_seq]
        primary block-table rows (pad rows wholly scratch);
        [.. +Pw] prompt tokens offs[b] .. offs[b]+Pw-1 right-padded,
        Pw bucketed to the wave's max REMAINING prompt span; spec mode
        appends [.. +seq_cap] the FULL prompt row for the draft
        buffer.  Returns (pools, state).
        """
        B = packed.shape[0]
        prompt_lens = packed[:, 0]
        offs = packed[:, 1]
        slot_idx = packed[:, 2:2 + K]
        budgets = packed[:, 2 + K:2 + 2 * K]
        copy_src = packed[:, 2 + 2 * K:2 + 3 * K]
        copy_dst = packed[:, 2 + 3 * K:2 + 4 * K]
        base = 2 + 4 * K
        bt_rows = packed[:, base:base + self.pages_per_seq]
        base += self.pages_per_seq
        prompt_ids = packed[:, base:base + Pw]
        seq_rows = packed[:, base + Pw:]
        from orion_tpu.models.transformer import maybe_unstack_for_decode

        params = maybe_unstack_for_decode(params, self.mc)
        positions = offs[:, None] + jnp.arange(Pw, dtype=jnp.int32)[None, :]
        cache = self._cache(pools, bt_rows)
        # Vocab projection only at the last real prompt token (its
        # logits predict completion[0]) — see RolloutEngine prefill.
        logits, cache = self._decode_model.apply(
            {"params": params}, prompt_ids, positions, cache,
            logits_positions=(prompt_lens - 1 - offs)[:, None])
        pools_w = self._strip(cache)
        if do_copy:
            # Partial-prompt-page replication AFTER the prompt KV is
            # written (data dependence orders it under XLA).  Duplicate
            # scratch destinations are benign: scratch content is never
            # read.  Static-gated: solo-only waves (PPO, k=1) skip the
            # gather/scatter entirely instead of copying scratch pages.
            src = copy_src.reshape(-1)
            dst = copy_dst.reshape(-1)
            pools_w = [{key: arr.at[dst].set(arr[src])
                        for key, arr in p.items()} for p in pools_w]
        last = logits[:, 0]
        V = last.shape[-1]
        BK = B * K
        # Every clone samples from its group's shared logits.
        flat = jnp.broadcast_to(last[:, None, :], (B, K, V)).reshape(BK, V)
        slot_flat = slot_idx.reshape(-1)
        budget_flat = budgets.reshape(-1)
        lens_flat = jnp.broadcast_to(prompt_lens[:, None], (B, K)).reshape(-1)
        pen = self.cfg.repetition_penalty != 1.0
        min_new = self.cfg.effective_min_new(self.eos)
        kw = {}
        if pen:
            # wave-level seen set from the admitted prompts (offs are
            # all zero here: the penalty disables chunking/caching, so
            # the full prompt is present in this program)
            wave_seen = seen_from_prompts(prompt_ids, prompt_lens, V)
            seen_flat = jnp.broadcast_to(
                wave_seen[:, None, :], (B, K, V)).reshape(BK, V)
            kw = {"seen": seen_flat,
                  "repetition_penalty": self.cfg.repetition_penalty}
        if min_new > 0:
            # generated count is 0 at admission: EOS always suppressed
            kw["forbid"] = eos_forbid_mask(BK, V, self.eos, True,
                                           self.cfg.stop_token_ids)
        tok0, lp0, plp0 = sample_tokens(
            rng, flat, temperature=self.cfg.temperature,
            top_k=self.cfg.top_k, top_p=self.cfg.top_p, **kw)
        d0 = is_stop_token(tok0, self.eos, self.cfg.stop_token_ids)
        st = dict(state)
        if pen:
            seen_flat = seen_flat.at[jnp.arange(BK), tok0].set(True)
            st["seen"] = st["seen"].at[slot_flat].set(seen_flat,
                                                      mode="drop")
        st["cur_tok"] = st["cur_tok"].at[slot_flat].set(tok0, mode="drop")
        if "seq" in st:
            # Draft buffer: scatter each clone's FULL prompt row
            # (seq_rows [B, seq_cap], host-assembled — prefix-cache
            # hits and chunked prefill skip forwarding parts of the
            # prompt, but the n-gram lookup needs all of it), append
            # the first sampled token at the prompt length, and zero
            # the speculative counters for the fresh occupant.
            rows_rep = jnp.broadcast_to(
                seq_rows[:, None, :], (B, K, seq_rows.shape[1])
            ).reshape(BK, -1)
            st["seq"] = st["seq"].at[slot_flat].set(rows_rep,
                                                    mode="drop")
            st["seq"] = st["seq"].at[slot_flat, lens_flat].set(
                tok0, mode="drop")
            st["spec_counts"] = st["spec_counts"].at[slot_flat].set(
                0, mode="drop")
        st["lengths"] = st["lengths"].at[slot_flat].set(lens_flat,
                                                        mode="drop")
        st["budget"] = st["budget"].at[slot_flat].set(budget_flat,
                                                      mode="drop")
        st["done"] = st["done"].at[slot_flat].set(
            d0 | (budget_flat <= 1), mode="drop")
        st["n_new"] = st["n_new"].at[slot_flat].set(1, mode="drop")
        st["toks"] = st["toks"].at[slot_flat, 0].set(tok0, mode="drop")
        st["lps"] = st["lps"].at[slot_flat, 0].set(lp0, mode="drop")
        st["plps"] = st["plps"].at[slot_flat, 0].set(plp0, mode="drop")
        return pools_w, st

    def _segment_fn(self, params, pools, bt, state, rng, n_steps: int):
        """Decode n_steps tokens for all slots in lockstep, accumulating
        completions into the per-slot DEVICE buffers (state["toks"/
        "lps"/"plps"] at cursor state["n_new"]).  Live slots advance
        their cursor and cache position; done slots idle in place
        (their masked writes drop, their cache position stays put so a
        finished request can never overrun its page reservation —
        which also lets the host use a FIXED segment length).
        Returns (pools, state)."""
        S = self.slots
        T = self.cfg.max_new_tokens
        pad = self.pad
        from orion_tpu.models.transformer import maybe_unstack_for_decode

        params = maybe_unstack_for_decode(params, self.mc)
        s_idx = jnp.arange(S)

        def body(i, c):
            pools, st, rng = c
            cache = self._cache(pools, bt)
            # cur_tok was sampled for position `lengths`; write it
            # there and predict the next token.
            positions = st["lengths"][:, None]
            logits, cache = self._decode_model.apply(
                {"params": params}, st["cur_tok"][:, None], positions,
                cache)
            rng, sub = jax.random.split(rng)
            V = logits.shape[-1]
            pen = self.cfg.repetition_penalty != 1.0
            min_new = self.cfg.effective_min_new(self.eos)
            kw = {}
            if pen:
                kw = {"seen": st["seen"],
                      "repetition_penalty": self.cfg.repetition_penalty}
            if min_new > 0:
                kw["forbid"] = eos_forbid_mask(
                    S, V, self.eos, st["n_new"] < min_new,
                    self.cfg.stop_token_ids)
            nxt, lp, plp = sample_tokens(
                sub, logits[:, 0], temperature=self.cfg.temperature,
                top_k=self.cfg.top_k, top_p=self.cfg.top_p, **kw)
            live = ~st["done"]
            nxt = jnp.where(live, nxt, pad)
            lp = jnp.where(live, lp, 0.0)
            plp = jnp.where(live, plp, 0.0)
            # dead slots write at T (out of bounds) -> scatter drops.
            wi = jnp.where(live, st["n_new"], T)
            st = dict(st)
            if pen:
                st["seen"] = st["seen"].at[
                    s_idx, jnp.where(live, nxt, V)].set(True, mode="drop")
            st["toks"] = st["toks"].at[s_idx, wi].set(nxt, mode="drop")
            st["lps"] = st["lps"].at[s_idx, wi].set(lp, mode="drop")
            st["plps"] = st["plps"].at[s_idx, wi].set(plp, mode="drop")
            st["n_new"] = st["n_new"] + live
            st["lengths"] = st["lengths"] + live
            st["cur_tok"] = jnp.where(live, nxt, st["cur_tok"])
            done = st["done"] | (st["n_new"] >= st["budget"])
            done = done | (live & is_stop_token(nxt, self.eos,
                                                self.cfg.stop_token_ids))
            st["done"] = done
            return (self._strip(cache), st, rng)

        n0, l0 = state["n_new"], state["lengths"]
        pools, state, _ = jax.lax.fori_loop(
            0, n_steps, body, (pools, state, rng))
        if "seq" in state:
            # Plain segments still feed the draft buffer (cold
            # adaptive-k waves must leave drafts warm for the next
            # probing verify wave) — as ONE post-loop batched scatter
            # of the segment's emissions (already accumulated in the
            # toks buffer) instead of a per-step scatter, then the
            # draftability gauge: computed once per segment and
            # fetched with the lagged flags, so on unstructured text
            # the engine never pays a verify chunk to learn that no
            # draft exists.
            state = dict(state)
            j = jnp.arange(n_steps, dtype=jnp.int32)[None, :]
            vals = jnp.take_along_axis(
                state["toks"], jnp.minimum(n0[:, None] + j, T - 1),
                axis=1)
            si = jnp.where(j < (state["n_new"] - n0)[:, None],
                           l0[:, None] + 1 + j, self._seq_cap)
            state["seq"] = state["seq"].at[
                jnp.arange(S)[:, None], si].set(vals, mode="drop")
            state["spec_counts"] = state["spec_counts"].at[:, 3].set(
                jnp.any(self._match_windows(
                    state["seq"], state["lengths"] + 1), axis=1))
        return pools, state

    def _spec_segment_fn(self, params, pools, bt, state, rng,
                         n_steps: int, k: int):
        """Speculative verify segment: ``n_steps`` iterations, each
        drafting k tokens per slot by prompt-lookup over the per-slot
        ``seq`` buffer and verifying all k+1 candidate positions in
        ONE paged forward (the chunk writes KV at positions lengths ..
        lengths+k; rejected-draft KV is stale only at positions past
        the new content length and the NEXT chunk starts exactly
        there, so it is always overwritten before any query can
        attend it — the dense engine's invariant on the paged pool,
        with the k slack positions covered by the scheduler's
        extend-slack reservation).

        Acceptance is exact in both modes (greedy: the emitted token
        is always the model's own transformed-argmax; temperature>0:
        delta-draft speculative sampling — accept draft x w.p. p(x),
        resample from p∖{x} on rejection, ordinary bonus draw after a
        full accept, so every emitted token's marginal is exactly p).
        Sampler composition is per POSITION: the repetition-penalty
        seen-set and the min_new_tokens EOS-forbid mask are updated
        between candidate positions inside the chunk, so the
        transformed distribution at each position is identical to
        what the sequential path would compute — which is what makes
        greedy output token-identical and the stochastic marginal
        exact under the full control stack.

        Done slots ride masked exactly as in the plain segment: their
        lengths freeze, their chunk rewrites the same k+1 reserved
        slack positions every iteration, and their emissions drop.
        """
        S = self.slots
        T = self.cfg.max_new_tokens
        V = self.mc.vocab_size
        pad = self.pad
        cfg = self.cfg
        eos = self.eos
        n = int(cfg.spec_ngram)
        capW = self._seq_cap
        stochastic = cfg.temperature != 0.0
        pen = cfg.repetition_penalty != 1.0
        min_new = cfg.effective_min_new(eos)
        from orion_tpu.models.transformer import maybe_unstack_for_decode

        params = maybe_unstack_for_decode(params, self.mc)
        s_idx = jnp.arange(S)
        n_win = capW - n - k + 1
        w_idx = jnp.arange(n_win)

        def draft_fn(seq, ln):
            # Trailing n-gram per slot, matched against every window
            # start; the latest PRIOR occurrence's continuation is the
            # draft (vLLM prompt-lookup as pure XLA, per slot).
            valid = self._match_windows(seq, ln)
            score = jnp.where(valid, w_idx[None, :], -1)
            s = jnp.max(score, axis=1)                  # [S], -1 = none
            s0 = jnp.maximum(s, 0)
            drafts = jnp.stack(
                [jnp.take_along_axis(seq, (s0 + n + i)[:, None],
                                     axis=1)[:, 0] for i in range(k)],
                axis=1)                                 # [S, k]
            # no match -> draft pads; verified like any other draft
            # (a lucky pad accept is still a correct emission, it
            # just doesn't count toward the acceptance EMA)
            return jnp.where((s >= 0)[:, None], drafts, pad), s >= 0

        def body(i, c):
            pools, st, rng = c
            live0 = ~st["done"]
            drafts, matched = draft_fn(st["seq"], st["lengths"] + 1)
            chunk = jnp.concatenate([st["cur_tok"][:, None], drafts],
                                    axis=1)
            # Write positions clamp at the block-table edge: a maximal
            # request (plen+budget == table capacity) has no room for
            # draft slack, and an unclamped position would index past
            # the table (XLA clamps the page gather onto the LAST real
            # page — clobbering live KV).  Clamping is safe: every
            # EMITTED token's query sits at position <= capacity-2 and
            # attends keys <= itself, so the clamped position's
            # (garbage) KV is only ever attended by discarded queries.
            pos = jnp.minimum(
                st["lengths"][:, None] + jnp.arange(
                    k + 1, dtype=jnp.int32)[None, :],
                self.pages_per_seq * self.cfg.page_size - 1)
            cache = self._cache(pools, bt)
            logits, cache = self._decode_model.apply(
                {"params": params}, chunk, pos, cache)
            raw_lsm = jax.nn.log_softmax(
                logits.astype(jnp.float32), axis=-1)    # [S, k+1, V]
            if not (pen or min_new > 0):
                return self._spec_verify_fast(
                    st, cache, rng, drafts, matched, live0, logits,
                    raw_lsm, k, stochastic)
            rng, sub = jax.random.split(rng)
            keys = jax.random.split(sub, 2 * (k + 1))
            # Candidate positions unrolled (k is static): position j's
            # controls see the tokens accepted at positions < j.
            accepting = live0
            stopped = jnp.zeros((S,), bool)
            n_new = st["n_new"]
            lengths = st["lengths"]
            cur = st["cur_tok"]
            seen = st["seen"] if pen else None
            toks, lps, plps = st["toks"], st["lps"], st["plps"]
            seq = st["seq"]
            acc_cnt = jnp.zeros((S,), jnp.int32)
            res_cnt = jnp.zeros((S,), jnp.int32)
            ctrl = pen or min_new > 0
            for j in range(k + 1):
                lg = logits[:, j].astype(jnp.float32)
                raw_j = raw_lsm[:, j]
                if pen:
                    lg = apply_repetition_penalty(
                        lg, seen, cfg.repetition_penalty)
                if min_new > 0:
                    forbid = eos_forbid_mask(S, V, eos, n_new < min_new,
                                             cfg.stop_token_ids)
                    lg = jnp.where(forbid, jnp.float32(-1e10), lg)
                if not stochastic:
                    # Greedy: the emitted token is the transformed
                    # argmax itself — a draft only decides whether the
                    # NEXT position's chunk context was right.
                    e_j = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                    plp_j = jnp.take_along_axis(
                        raw_j, e_j[:, None], axis=-1)[:, 0]
                    # Greedy over a transformed distribution is a
                    # delta: behavior logprob 0 (sample_tokens'
                    # convention, bit-matched here).
                    lp_j = jnp.zeros_like(plp_j) if ctrl else plp_j
                    acc_j = (drafts[:, j] == e_j) if j < k else None
                else:
                    t_lg = transformed_logits(lg, cfg.temperature,
                                              cfg.top_k, cfg.top_p)
                    p_lsm = jax.nn.log_softmax(t_lg, axis=-1)
                    if j < k:
                        d_j = drafts[:, j]
                        u = jax.random.uniform(keys[2 * j], (S,))
                        p_d = jnp.exp(jnp.take_along_axis(
                            p_lsm, d_j[:, None], axis=-1)[:, 0])
                        acc_j = u < p_d
                        # Rejection resamples from p with the draft
                        # excluded (delta-draft residual).
                        excl = jnp.zeros((S, V), bool).at[
                            s_idx, d_j].set(True)
                        resamp = jax.random.categorical(
                            keys[2 * j + 1],
                            jnp.where(excl, jnp.float32(-1e10), t_lg),
                            axis=-1).astype(jnp.int32)
                        e_j = jnp.where(acc_j, d_j, resamp)
                    else:
                        acc_j = None  # bonus draw after a full accept
                        e_j = jax.random.categorical(
                            keys[2 * j + 1], t_lg,
                            axis=-1).astype(jnp.int32)
                    lp_j = jnp.take_along_axis(
                        p_lsm, e_j[:, None], axis=-1)[:, 0]
                    plp_j = jnp.take_along_axis(
                        raw_j, e_j[:, None], axis=-1)[:, 0]
                valid = accepting & ~stopped & (n_new < st["budget"])
                wi = jnp.where(valid, n_new, T)
                toks = toks.at[s_idx, wi].set(e_j, mode="drop")
                lps = lps.at[s_idx, wi].set(lp_j, mode="drop")
                plps = plps.at[s_idx, wi].set(plp_j, mode="drop")
                si = jnp.where(valid, lengths + 1, capW)
                seq = seq.at[s_idx, si].set(e_j, mode="drop")
                if pen:
                    seen = seen.at[s_idx, jnp.where(valid, e_j, V)].set(
                        True, mode="drop")
                stopped = stopped | (valid & is_stop_token(
                    e_j, eos, cfg.stop_token_ids))
                n_new = n_new + valid
                lengths = lengths + valid
                cur = jnp.where(valid, e_j, cur)
                if j < k:
                    # EMA accounting covers genuinely-matched rows
                    # only: an unmatched row riding a hot wave drafts
                    # pads, and a lucky pad accept must not report a
                    # draft success (emission-wise it counts as a
                    # resample, keeping the reconcile invariant
                    # emitted == accepted + resampled).
                    acc_cnt = acc_cnt + (valid & acc_j & matched)
                    res_cnt = res_cnt + (valid & ~(acc_j & matched))
                    accepting = accepting & valid & acc_j
                else:
                    res_cnt = res_cnt + valid
            st = dict(st)
            st["toks"], st["lps"], st["plps"] = toks, lps, plps
            st["seq"] = seq
            if pen:
                st["seen"] = seen
            st["n_new"] = n_new
            st["lengths"] = lengths
            st["cur_tok"] = cur
            st["done"] = st["done"] | stopped | (n_new >= st["budget"])
            st["spec_counts"] = st["spec_counts"].at[:, :3].add(
                jnp.stack(
                    [jnp.where(live0 & matched, k, 0).astype(jnp.int32),
                     acc_cnt, res_cnt], axis=1))
            return (self._strip(cache), st, rng)

        pools, state, _ = jax.lax.fori_loop(
            0, n_steps, body, (pools, state, rng))
        state = dict(state)
        state["spec_counts"] = state["spec_counts"].at[:, 3].set(
            jnp.any(self._match_windows(
                state["seq"], state["lengths"] + 1), axis=1))
        return pools, state

    def _spec_verify_fast(self, st, cache, rng, drafts, matched, live0,
                          logits, raw_lsm, k, stochastic):
        """Vectorized accept/emit for the NO-control case (no
        repetition penalty, no min_new): all k+1 candidate positions
        are scored, accepted and scattered in batched ops instead of
        an unrolled per-position loop.  Semantically identical to the
        unrolled path (same greedy argmax per position, same
        delta-draft acceptance rule, same stop/budget gating) — it
        exists because the chunk program is op-count-bound off-chip
        and the unrolled sampler tripled its cost.  The control path
        cannot vectorize: position j's penalty seen-set depends on the
        tokens accepted before it."""
        S = self.slots
        T = self.cfg.max_new_tokens
        cfg = self.cfg
        eos = self.eos
        capW = self._seq_cap
        s_idx = jnp.arange(S)
        j_idx = jnp.arange(k + 1, dtype=jnp.int32)
        if not stochastic:
            e = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S,k+1]
            plp_e = jnp.take_along_axis(raw_lsm, e[..., None],
                                        axis=-1)[..., 0]
            lp_e = plp_e
            acc = (drafts == e[:, :k])
        else:
            t_lg = transformed_logits(logits, cfg.temperature,
                                      cfg.top_k, cfg.top_p)
            p_lsm = jax.nn.log_softmax(t_lg, axis=-1)
            rng, k_u, k_cat = jax.random.split(rng, 3)
            u = jax.random.uniform(k_u, (S, k))
            p_d = jnp.exp(jnp.take_along_axis(
                p_lsm[:, :k], drafts[..., None], axis=-1)[..., 0])
            acc = u < p_d
            # rejection resamples from p with the draft excluded;
            # position k is the ordinary bonus draw (no exclusion)
            excl = jnp.zeros((S, k + 1, t_lg.shape[-1]), bool).at[
                s_idx[:, None], jnp.arange(k)[None, :], drafts].set(True)
            resamp = jax.random.categorical(
                k_cat, jnp.where(excl, jnp.float32(-1e10), t_lg),
                axis=-1).astype(jnp.int32)
            m = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1),
                        axis=1)
            e = jnp.where(j_idx[None, :] < m[:, None],
                          jnp.pad(drafts, ((0, 0), (0, 1))), resamp)
            lp_e = jnp.take_along_axis(p_lsm, e[..., None],
                                       axis=-1)[..., 0]
            plp_e = jnp.take_along_axis(raw_lsm, e[..., None],
                                        axis=-1)[..., 0]
        # accepted-prefix gate: position 0 always reachable, position
        # j>0 reachable iff drafts 0..j-1 accepted (greedy: equalled
        # the argmax; stochastic: passed the u < p(draft) test)
        acc_prefix = jnp.cumprod(acc.astype(jnp.int32), axis=1)
        reach = jnp.concatenate(
            [jnp.ones((S, 1), jnp.int32), acc_prefix], axis=1) > 0
        stop_e = is_stop_token(e.reshape(-1), eos,
                               cfg.stop_token_ids).reshape(S, k + 1)
        # emitted before any stop in the accepted prefix (exclusive
        # prefix-OR), within budget, live
        stop_before = jnp.cumsum(
            (reach & stop_e).astype(jnp.int32), axis=1) \
            - (reach & stop_e)
        valid = (live0[:, None] & reach & (stop_before == 0)
                 & (st["n_new"][:, None] + j_idx < st["budget"][:, None]))
        n_emit = jnp.sum(valid, axis=1, dtype=jnp.int32)
        wi = jnp.where(valid, st["n_new"][:, None] + j_idx, T)
        si = jnp.where(valid, st["lengths"][:, None] + 1 + j_idx, capW)
        st = dict(st)
        st["toks"] = st["toks"].at[s_idx[:, None], wi].set(e, mode="drop")
        st["lps"] = st["lps"].at[s_idx[:, None], wi].set(lp_e,
                                                         mode="drop")
        st["plps"] = st["plps"].at[s_idx[:, None], wi].set(plp_e,
                                                           mode="drop")
        st["seq"] = st["seq"].at[s_idx[:, None], si].set(e, mode="drop")
        last_i = jnp.maximum(n_emit - 1, 0)
        last_e = jnp.take_along_axis(e, last_i[:, None], axis=1)[:, 0]
        st["cur_tok"] = jnp.where(n_emit > 0, last_e, st["cur_tok"])
        st["n_new"] = st["n_new"] + n_emit
        st["lengths"] = st["lengths"] + n_emit
        st["done"] = (st["done"] | jnp.any(valid & stop_e, axis=1)
                      | (st["n_new"] >= st["budget"]))
        # EMA accounting covers genuinely-matched rows only; every
        # other emission is a resample so emitted == accepted +
        # resampled always reconciles.
        acc_cnt = jnp.sum(valid[:, :k] & acc & matched[:, None], axis=1,
                          dtype=jnp.int32)
        st["spec_counts"] = st["spec_counts"].at[:, :3].add(
            jnp.stack(
                [jnp.where(live0 & matched, k, 0).astype(jnp.int32),
                 acc_cnt, n_emit - acc_cnt], axis=1))
        return (self._strip(cache), st, rng)

    # -- request-level service API --------------------------------------
    def reset_rng(self, rng: jax.Array) -> None:
        """Seed (or reseed) the service sampling stream.  ``generate``
        does this per call; standing-service users do it once."""
        self._rng = rng

    def configure_tenant(self, tenant, weight: int = 1,
                         rate_limit: float = 0.0,
                         burst: Optional[float] = None,
                         max_queued: int = 0,
                         max_running: int = 0) -> None:
        """Register (or update) a tenant's QoS envelope (PR 12):

        - ``weight`` — weighted-fair admission share (scheduler WFQ:
          under contention a weight-4 tenant is admitted ~4x the
          tokens of a weight-1 tenant);
        - ``rate_limit`` — submits per second (token bucket of depth
          ``burst``, default max(rate, 1)); 0 = unlimited;
        - ``max_queued`` — per-tenant cap on WAITING requests; 0 =
          unlimited;
        - ``max_running`` — per-tenant concurrency cap (engine slots
          its admitted requests may occupy at once) — the reserved-
          capacity lever: a best-effort flood capped at 2 of 8 slots
          can never occupy the paying tenant's headroom between its
          arrivals; 0 = unlimited.

        Exceeding the rate limit or a queue cap sheds the submit with
        :class:`EngineOverloaded`.  Unregistered tenants get weight 1
        and no limits."""
        from orion_tpu.obs import TokenBucket

        name = str(tenant)
        if int(weight) < 1:
            raise ValueError(f"tenant weight must be >= 1, got {weight}")
        tid = self._tenant_ids.setdefault(name, len(self._tenant_ids))
        self.sched.set_tenant(tid, int(weight), int(max_running))
        bucket = None
        if rate_limit > 0:
            bucket = TokenBucket(rate_limit,
                                 burst if burst is not None
                                 else max(float(rate_limit), 1.0))
        # rate_limit / max_running ride along so the envelope can be
        # read BACK (the autopilot's shed rung snapshots it before
        # clamping and restores it verbatim on relax).
        self._tenant_qos[name] = {"weight": int(weight), "bucket": bucket,
                                  "max_queued": int(max_queued),
                                  "rate_limit": float(rate_limit),
                                  "max_running": int(max_running)}

    def apply_setpoints(self, page_watermark: Optional[int] = None,
                        chunked_prefill_tokens: Optional[int] = None,
                        spec_breakeven: Optional[float] = None) -> dict:
        """Retune the serving knobs of a LIVE engine (the SLO
        autopilot's actuator; PR 13).  Each knob is optional; only the
        ones passed change.  Returns ``{knob: (old, new)}`` for every
        knob whose effective value actually changed — the empty dict
        means the call was a no-op, which the controller uses to avoid
        counting phantom setpoint changes.

        - ``page_watermark`` re-aims the scheduler's admission-headroom
          reserve (takes effect at the next admit; in-flight
          reservations untouched);
        - ``chunked_prefill_tokens`` re-caps the prefill chunk budget
          for FUTURE admissions (a repetition penalty != 1.0 still
          forces 0 — same rule as construction, degrade loudly);
        - ``spec_breakeven`` moves the speculative-decoding breakeven
          threshold the per-wave spec gate reads live.
        """
        changed: dict = {}
        if page_watermark is not None:
            new_wm = int(page_watermark)
            if new_wm < 0:
                raise ValueError(
                    f"page_watermark must be >= 0, got {new_wm}")
            if new_wm != self._watermark:
                self.sched.set_watermark(new_wm)
                changed["page_watermark"] = (self._watermark, new_wm)
                self._watermark = new_wm
        if chunked_prefill_tokens is not None:
            new_ct = int(chunked_prefill_tokens)
            if new_ct < 0:
                raise ValueError(
                    f"chunked_prefill_tokens must be >= 0, got {new_ct}")
            eff = new_ct if self.cfg.repetition_penalty == 1.0 else 0
            if eff != new_ct:
                import warnings

                warnings.warn(
                    "apply_setpoints: repetition_penalty != 1.0 forces "
                    "chunked_prefill_tokens to 0 (the penalty's "
                    "seen-set needs the full prompt forward)",
                    stacklevel=2)
            if eff != self._chunk:
                changed["chunked_prefill_tokens"] = (self._chunk, eff)
                self._chunk = eff
        if spec_breakeven is not None:
            new_be = float(spec_breakeven)
            if new_be < 1.0:
                raise ValueError(
                    f"spec_breakeven must be >= 1.0, got {new_be}")
            if new_be != self.cfg.spec_breakeven:
                changed["spec_breakeven"] = (self.cfg.spec_breakeven,
                                             new_be)
                # The per-wave spec gate reads cfg.spec_breakeven live,
                # so the config object IS the knob's storage.
                self.cfg.spec_breakeven = new_be
        return changed

    def _retry_after_hint(self) -> float:
        """Backpressure hint: the recent mean queue wait approximates
        how long the backlog takes to drain one admission's worth."""
        qw = self.telemetry.queue_wait_s
        return max(0.05, float(qw.mean)) if qw.count else 0.25

    def _shed(self, reason: str, depth: int, retry_after: float,
              tenant: str) -> None:
        self.shed_requests += 1
        self.telemetry.record_shed(tenant)
        raise EngineOverloaded(reason, queue_depth=depth,
                               retry_after=retry_after, tenant=tenant)

    def submit(self, req_id: int, ids, budget: Optional[int] = None,
               k: int = 1, priority: int = 0,
               deadline: Optional[int] = None, tenant="default",
               stream: bool = False, on_tokens=None,
               logprobs: bool = False) -> None:
        """Enqueue a request (or a k-clone sampling group with ids
        req_id .. req_id+k-1).  budget ≤ cfg.max_new_tokens caps the
        completion; priority/deadline feed the scheduler's admission
        policy (cfg.admission_policy); ``tenant`` names the QoS class
        (weighted-fair admission + the configure_tenant limits).
        ``stream=True`` delivers completion tokens incrementally via
        ``poll(req_id)``, or pushes them through ``on_tokens(chunk)``
        from inside ``step()`` when a callback is given; with
        ``logprobs=True`` each chunk also carries the per-token
        sampling logprobs (PR 17 — bit-exact against the completed
        record).  Completions come back from later ``step()`` calls in
        finish order either way.  Raises :class:`EngineOverloaded`
        when a QoS gate refuses admission (nothing is enqueued — the
        caller may retry after ``retry_after``)."""
        cfg = self.cfg
        ids = np.asarray(ids, np.int32)
        budget = int(cfg.max_new_tokens if budget is None else budget)
        k = int(k)
        name = str(tenant)
        if len(ids) < 1 or len(ids) > cfg.max_prompt_len:
            raise ValueError(
                f"prompt {req_id}: length {len(ids)} outside "
                f"[1, max_prompt_len={cfg.max_prompt_len}]")
        if not 1 <= budget <= cfg.max_new_tokens:
            raise ValueError(
                f"request {req_id}: budget {budget} outside "
                f"[1, max_new_tokens={cfg.max_new_tokens}]")
        if not 1 <= k <= self.slots:
            raise ValueError(
                f"request {req_id}: group of {k} clones can never "
                f"be admitted (max_slots={self.slots})")
        for j in range(k):
            if req_id + j in self._reqinfo:
                raise ValueError(f"request id {req_id + j} already "
                                 "in flight")
        # QoS gates AFTER validation, BEFORE any state mutation: a shed
        # request leaves zero residue (retry-safe), a malformed one
        # still gets its ValueError.  Order: global watermark, tenant
        # queue cap, then the rate bucket (a queue-refused submit must
        # not burn rate tokens).
        total_waiting = sum(self._tenant_queued.values())
        if self._draining:
            # Blue/green drain: a typed shed, not an error — the
            # gateway routes around a draining engine, and a direct
            # caller backs off exactly like any other overload.
            self._shed(
                "engine draining for weight rollout",
                total_waiting, self._retry_after_hint(), name)
        if cfg.max_queued_requests and \
                total_waiting + k > cfg.max_queued_requests:
            self._shed(
                f"engine overloaded: {total_waiting} requests waiting "
                f"(max_queued_requests={cfg.max_queued_requests})",
                total_waiting, self._retry_after_hint(), name)
        qos = self._tenant_qos.get(name)
        if qos is not None:
            tq = self._tenant_queued.get(name, 0)
            if qos["max_queued"] and tq + k > qos["max_queued"]:
                self._shed(
                    f"tenant {name!r} overloaded: {tq} requests "
                    f"waiting (max_queued={qos['max_queued']})",
                    tq, self._retry_after_hint(), name)
            if qos["bucket"] is not None:
                wait = qos["bucket"].try_acquire(k)
                if wait > 0:
                    self._shed(
                        f"tenant {name!r} rate-limited: retry in "
                        f"{wait:.3f}s", tq, wait, name)
        tid = self._tenant_ids.setdefault(name, len(self._tenant_ids))
        # Per-tenant SLO accounting only for REAL tenants (registered,
        # or explicitly named on submit): the trainer/generate() path
        # runs everything under the implicit "default" tenant, and
        # routing it per-tenant would just shadow every global
        # histogram with a duplicate tenant_default_* column set.
        slo_tenant = (name if (qos is not None or name != "default")
                      else None)
        dl = -1 if deadline is None else int(deadline)
        hashes = self._page_hashes(ids)
        if self._host_cache is not None and hashes:
            self._readmit_from_host(hashes)
        if k > 1:
            self.sched.add_group(req_id, len(ids), budget, k,
                                 priority=priority, deadline=dl,
                                 prefix_hashes=hashes, tenant=tid)
        else:
            self.sched.add(req_id, len(ids), budget, priority=priority,
                           deadline=dl, prefix_hashes=hashes, tenant=tid)
        for j in range(k):
            self._reqinfo[req_id + j] = (ids, budget, req_id, j, k)
            self._req_tenant[req_id + j] = name
            self._tenant_queued[name] = \
                self._tenant_queued.get(name, 0) + 1
            if stream:
                self._streams[req_id + j] = {
                    "emitted": 0, "chunks": [], "restarted": False,
                    "done": False, "completed": None, "cb": on_tokens,
                    "lp": bool(logprobs), "lp_chunks": []}
            if slo_tenant is not None:
                self.telemetry.mark(req_id + j, "submit",
                                    prompt_len=len(ids), budget=budget,
                                    tenant=slo_tenant)
            else:
                self.telemetry.mark(req_id + j, "submit",
                                    prompt_len=len(ids), budget=budget)

    @property
    def pending(self) -> int:
        """Requests submitted but not yet returned by ``step``."""
        return len(self._reqinfo)

    def _preempt_req(self, rid: int, count: bool = True) -> None:
        """Recompute-preemption: drop the victim's pages/slot back to
        the pool and requeue it (the scheduler keeps its arrival
        position); its partial completion is discarded and it restarts
        from the prompt when readmitted.  The victim's zombie slot
        keeps lockstep-decoding into the scratch page until the slot is
        re-seeded by a later admission — masked work, never a hazard.
        ``count=False`` skips the preemption metrics (the cancel path
        reuses this machinery to evict a decoding request but is not a
        recompute-restart)."""
        slot = self.sched.slot(rid)
        self.sched.preempt(rid)
        ids, budget, head, j, k = self._reqinfo[rid]
        # A requeued group clone restarts as a SOLO request (its group
        # mates keep their shared pages via the scheduler refcounts).
        self._reqinfo[rid] = (ids, budget, rid, 0, 1)
        self._slot_req[slot] = -1
        self._slot_seq[slot] = -1
        self._phase[slot] = _EMPTY
        self._admit_seq.pop(rid, None)
        self._accept_ema.pop(rid, None)  # re-seeded at readmission
        self._bt[slot, :] = self._scratch
        self._bt_dev = None
        # Back to waiting: the tenant's queue-cap ledger re-counts it.
        name = self._req_tenant.get(rid)
        if name is not None:
            self._tenant_queued[name] = \
                self._tenant_queued.get(name, 0) + 1
        # A streaming victim restarts its stream: everything delivered
        # so far is discarded by the client (restart-by-recompute will
        # re-derive it) and the next chunk carries ``restarted``.
        st = self._streams.get(rid)
        if st is not None:
            st["emitted"] = 0
            st["chunks"] = []
            st["lp_chunks"] = []
            st["restarted"] = True
        if count:
            self.preemptions += 1
            self.telemetry.preempt(rid)

    # -- request abort (PR 12) ------------------------------------------
    def _in_prefill(self, rid: int) -> bool:
        return any(rid == r
                   for e in self._prefilling.values()
                   for r, _slot in e["slots"].values())

    def _drop_request(self, rid: int) -> None:
        """Forget every engine-side trace of an aborted request (its
        scheduler entry must already be gone)."""
        name = self._req_tenant.pop(rid, None)
        if name is not None:
            self._tenant_queued[name] = \
                max(0, self._tenant_queued.get(name, 0) - 1)
        del self._reqinfo[rid]
        self._admit_seq.pop(rid, None)
        self._accept_ema.pop(rid, None)
        self._streams.pop(rid, None)
        self.telemetry.drop(rid)
        self.cancelled_requests += 1

    def cancel(self, req_id: int) -> bool:
        """Abort an in-flight request (PR 12 — the gateway's CANCEL
        path).  A waiting request is dequeued immediately; a decoding
        request is evicted through the preemption machinery (pages
        freed at this step boundary) and dequeued; a request
        mid-chunked-prefill is deferred one wave (its pages are being
        written by an in-flight group program) and aborted at the next
        ``step()``.  Returns True when the abort completed now, False
        when deferred.  Raises KeyError for unknown ids and ValueError
        for k-clone group members (groups share prompt pages; abort
        the whole group by cancelling each clone after activation)."""
        rid = int(req_id)
        if rid not in self._reqinfo:
            raise KeyError(rid)
        ids, budget, head, j, k = self._reqinfo[rid]
        if k > 1:
            raise ValueError(
                f"request {rid} is a k-clone group member; group "
                "cancellation is not supported mid-prefill")
        if self._in_prefill(rid):
            self._cancels.add(rid)
            return False
        try:
            slot = self.sched.slot(rid)
        except KeyError:
            slot = None
        if slot is not None and self._phase[slot] == _DECODE \
                and int(self._slot_req[slot]) == rid:
            # Evict via the preemption machinery (frees pages + slot,
            # requeues as waiting), then drop the requeued entry.  A
            # finished-but-unharvested request takes the same path:
            # its pending done-flag snapshot is disarmed by the
            # admission-seq pairing once the slot resets.
            self._preempt_req(rid, count=False)
        self.sched.cancel(rid)
        self._drop_request(rid)
        return True

    def poll(self, req_id: int) -> Optional[StreamChunk]:
        """Drain a streaming request's buffered output (pull surface —
        push callers pass ``on_tokens`` to submit instead).  Returns
        None when nothing new arrived since the last poll; the final
        chunk has ``done=True`` and the full :class:`CompletedRequest`
        attached, after which the request id is forgotten.  Raises
        KeyError for ids not submitted with ``stream=True`` (or
        already drained)."""
        rid = int(req_id)
        st = self._streams.get(rid)
        if st is None:
            raise KeyError(f"request {rid} is not streaming "
                           "(or its stream already drained)")
        if st["cb"] is not None:
            raise ValueError(
                f"request {rid} streams through its on_tokens "
                "callback; poll() is for callback-less streams")
        if not st["chunks"] and not st["done"] and not st["restarted"]:
            return None
        toks = (np.concatenate(st["chunks"])
                if st["chunks"] else np.empty(0, np.int32))
        lps = None
        if st["lp"]:
            lps = (np.concatenate(st["lp_chunks"])
                   if st["lp_chunks"] else np.empty(0, np.float32))
        chunk = StreamChunk(req_id=rid, tokens=toks, done=st["done"],
                            restarted=st["restarted"],
                            completed=st["completed"], logprobs=lps)
        st["chunks"] = []
        st["lp_chunks"] = []
        st["restarted"] = False
        if st["done"]:
            del self._streams[rid]
        return chunk

    def _extend_running(self, spec_wave: bool = False) -> None:
        """Grow every decoding slot's reservation to cover the next
        segment (on-demand allocation), preempting youngest-first when
        the pool runs dry.  A speculative wave advances by at most
        n_steps chunks of k+1 tokens and additionally reserves k
        verify-slack positions per slot (``extend(..., slack)``) so
        rejected-draft writes land inside the reservation."""
        if spec_wave:
            seg = self._spec_steps * (self._spec_k + 1)
            slack = self._spec_k
        else:
            seg = self.segment_len
            slack = 0
        cap_pos = self.pages_per_seq * self.cfg.page_size
        for slot in range(self.slots):
            if self._phase[slot] != _DECODE:
                continue
            rid = int(self._slot_req[slot])
            ids, budget, _, _, _ = self._reqinfo[rid]
            target = min(len(ids) + budget,
                         int(self._est_len[slot]) + seg)
            # Slack pages only where the request's lifetime leaves
            # room inside the block-table width — a maximal request's
            # overhang is clamped at the table edge by the verify
            # chunk instead (never-attended positions).
            eff_slack = max(0, min(slack,
                                   cap_pos - len(ids) - budget))
            while True:
                got = self.sched.extend(rid, target, eff_slack)
                if got >= 0:
                    break
                victims = [r for r, s in self._admit_seq.items()
                           if r != rid
                           and self._phase[self.sched.slot(r)] == _DECODE]
                if self._pending_flags is not None:
                    # A lagged done-flag may be holding a finished
                    # request's pages: harvest it NOW before preempting
                    # live work (or discarding the finished request's
                    # own completed output by self-preemption).
                    drained = self._harvest_pending()
                    if drained:
                        self._early_out.extend(drained)
                        continue
                if victims:
                    self._preempt_req(
                        max(victims, key=lambda r: self._admit_seq[r]))
                    continue
                if self._prefilling:
                    # The pool is held by mid-chunked-prefill
                    # admissions (not preemptable mid-write without
                    # group-state surgery): restart THIS request
                    # instead of killing the standing service — it
                    # requeues at its arrival position and recomputes
                    # once the prefills land and pages free up.
                    self._preempt_req(rid)
                    got = None
                    break
                raise RuntimeError(
                    f"page pool exhausted: {self.num_pages} pages "
                    f"cannot cover request {rid} even after "
                    "preempting all others — raise num_pages or "
                    "lower max_batch_size")
            if got is None:
                continue
            if got > 0:
                pages = self.sched.pages(rid)
                self._bt[slot, :len(pages)] = pages
                self._bt_dev = None
            self._est_len[slot] = target

    def _activate(self, entries, rng) -> None:
        """Run the FINAL prefill chunk for `entries` (head id ->
        rows_info dict) and flip their slots to decoding."""
        cfg = self.cfg
        S = self.slots
        ps = cfg.page_size
        nb = self._bucket(len(entries), S)
        kmax = self._bucket(max(e["k"] for e in entries.values()), S)
        span = max(len(e["ids"]) - e["off"] for e in entries.values())
        Pw = min(max(16, self._bucket(span, cfg.max_prompt_len)),
                 cfg.max_prompt_len)
        # ONE packed [nb, cols] int32 upload for the whole activation
        # wave (column layout documented in _prefill_fn; each separate
        # array cost a host dispatch on the serving hot path).
        pps = self.pages_per_seq
        base = 2 + 4 * kmax
        cols = base + pps + Pw + (self._seq_cap if self._spec else 0)
        packed = np.empty((nb, cols), np.int32)
        packed[:, 0] = 1                       # prompt_lens
        packed[:, 1] = 0                       # offs
        packed[:, 2:2 + kmax] = S              # slots: pad -> OOB
        packed[:, 2 + kmax:2 + 2 * kmax] = cfg.max_new_tokens
        packed[:, 2 + 2 * kmax:base] = self._scratch   # copy src/dst
        packed[:, base:base + pps] = self._scratch     # bt rows
        packed[:, base + pps:] = self.pad      # prompt (+ seq) rows
        rows = packed[:, base + pps:base + pps + Pw]
        lens_w = packed[:, 0]
        offs_w = packed[:, 1]
        bt_w = packed[:, base:base + pps]
        slot_w = packed[:, 2:2 + kmax]
        budget_w = packed[:, 2 + kmax:2 + 2 * kmax]
        copy_src = packed[:, 2 + 2 * kmax:2 + 3 * kmax]
        copy_dst = packed[:, 2 + 3 * kmax:2 + 4 * kmax]
        for b, e in enumerate(entries.values()):
            ids, k, off = e["ids"], e["k"], e["off"]
            plen = len(ids)
            shared = plen // ps if k > 1 else 0
            for j in range(k):
                rid, slot = e["slots"][j]
                pages = self.sched.pages(rid)
                self._bt[slot, : len(pages)] = pages
                # Unreserved tail → scratch page: prefill writes KV
                # for every padded position, and a short reservation
                # would otherwise wrap pad-position writes onto its
                # *last real page*, clobbering prompt KV (ADVICE r1).
                self._bt[slot, len(pages):] = self._scratch
                self._slot_req[slot] = rid
                self._phase[slot] = _DECODE
                self._est_len[slot] = plen
                slot_w[b, j] = slot
                budget_w[b, j] = e["budget"]
                if j > 0 and plen % ps != 0:
                    # The partial last prompt page is decode-appended,
                    # so each secondary clone gets a private copy of
                    # the primary's.
                    copy_src[b, j] = bt_w[b, shared]
                    copy_dst[b, j] = self._bt[slot, shared]
                if j == 0:
                    bt_w[b] = self._bt[slot]
            rows[b, :plen - off] = ids[off:]
            lens_w[b] = plen
            offs_w[b] = off
        self._bt_dev = None
        if self._spec:
            # Draft-source rows: the host knows every FULL prompt
            # (prefix-cache hits and chunked prefill skip forwarding
            # parts of it, but the n-gram lookup needs all of it);
            # they ride the same packed upload and the prefill program
            # scatters them into the activated slots' seq rows.
            seq_w = packed[:, base + pps + Pw:]
            for b, e in enumerate(entries.values()):
                seq_w[b, :len(e["ids"])] = e["ids"]
                for j in range(e["k"]):
                    rid, slot = e["slots"][j]
                    # Fresh occupant: no EMA yet (its first MATCHED
                    # wave probes and creates one), counter snapshot
                    # and draftability reset with the device state
                    # (prefill zeroes the counters; the first segment
                    # recomputes the match bit from the new seq row).
                    self._accept_ema.pop(rid, None)
                    self._spec_prev[slot, :] = 0
                    self._spec_match[slot] = False
        has_groups = any(e["k"] > 1 for e in entries.values())
        with self._ctx():
            pools, state = self._jit_prefill(
                self._params, self._pools, jnp.asarray(packed),
                self._state, rng, Pw=Pw, K=kmax, do_copy=has_groups)
        self._pools, self._state = pools, state
        for e in entries.values():
            for rid, _slot in e["slots"].values():
                # The final chunk just sampled this request's first
                # token (dispatch time — TTFT measured to the host-loop
                # boundary, consistent with queue wait).
                self.telemetry.mark(rid, "first_token")

    def _prefill_wave(self, rng) -> None:
        """Advance every mid-prefill prompt by one chunk: rows whose
        remainder exceeds the chunk budget run one INTERMEDIATE chunk
        (KV only); the rest run their FINAL chunk (+ sampling) and
        start decoding.  With chunking disabled every admission is a
        final chunk — the pre-PR8 one-shot wave."""
        chunk = self._chunk
        inter, final = {}, {}
        tokens = 0
        for head, e in self._prefilling.items():
            remaining = len(e["ids"]) - e["off"]
            if chunk > 0 and remaining > chunk:
                inter[head] = e
                tokens += chunk
            else:
                final[head] = e
                tokens += remaining
        with obs.span("engine.prefill_wave", rows=len(inter) + len(final),
                      tokens=tokens, final=len(final)):
            self._dispatch_prefill(inter, final, chunk, rng)
        self._prefilling = {h: e for h, e in self._prefilling.items()
                            if h not in final}

    def _dispatch_prefill(self, inter, final, chunk, rng) -> None:
        if inter:
            nb = self._bucket(len(inter), self.slots)
            pps = self.pages_per_seq
            packed = np.empty((nb, 1 + pps + chunk), np.int32)
            packed[:, 0] = 0                       # offs
            packed[:, 1:1 + pps] = self._scratch   # bt rows
            packed[:, 1 + pps:] = self.pad         # chunk ids
            for b, (head, e) in enumerate(inter.items()):
                off = e["off"]
                packed[b, 1 + pps:] = e["ids"][off:off + chunk]
                packed[b, 0] = off
                pages = self.sched.pages(head)
                packed[b, 1:1 + len(pages)] = pages
                e["off"] = off + chunk
            with self._ctx():
                self._pools = self._jit_chunk(
                    self._params, self._pools, jnp.asarray(packed),
                    C=chunk)
        if final:
            self._activate(final, rng)

    def step(self) -> List[CompletedRequest]:
        """Run ONE wave of the standing service: harvest-lagged flag
        processing, admission, one prefill chunk, reservation growth,
        one decode segment.  Returns requests that completed."""
        if self._params is None:
            raise ValueError("no weights loaded: call load_weights() first")
        if self._rng is None:
            raise ValueError("no sampling stream: call reset_rng() first")
        if self._state is None:
            self._state = self._init_state()
        # One span per wave (no-op when nothing records): the serving
        # timeline's unit of work.  Its children name each layer
        # boundary of the wave for what it is — host work (sched.admit,
        # sched.extend), a dispatch (engine.prefill_wave,
        # engine.segment: the host's enqueue, not the device's time) or
        # a wait (engine.harvest: the thread blocks on the flag fetch).
        with obs.span("engine.step", pending=len(self._reqinfo)):
            return self._step_wave()

    def _admit(self) -> int:
        """Scheduler admission plus the slot bookkeeping of every
        admitted request; returns how many were admitted."""
        admitted = self.sched.admit()
        if (not admitted and not self.sched.running
                and not self._prefilling and self.sched.waiting):
            raise RuntimeError(
                f"{self.sched.waiting} request(s) can never be "
                f"scheduled: pool of {self.num_pages} pages is too "
                "small for a single request's admission")
        for rid, slot in admitted:
            ids, budget, head, j, k = self._reqinfo[rid]
            self._slot_req[slot] = rid
            self._slot_seq[slot] = self._admit_counter
            self._phase[slot] = _PREFILL
            self._admit_seq[rid] = self._admit_counter
            self._admit_counter += 1
            name = self._req_tenant.get(rid)
            if name is not None:  # left the waiting queue: QoS ledger
                self._tenant_queued[name] = \
                    max(0, self._tenant_queued.get(name, 0) - 1)
            self.telemetry.mark(rid, "admit", slot=slot)
            if j == 0:
                cached = self.sched.cached_count(rid)
                self.prefix_cached_pages += cached
                # Prefix-cache hit fraction over the CACHEABLE pages
                # (full prompt pages, capped so >=1 token re-forwards).
                cacheable = max(0, (len(ids) - 1) // self.cfg.page_size)
                if cacheable > 0 and self._prefix_cache_on:
                    self.telemetry.record_prefix_hit(cached / cacheable)
                e = self._prefilling.setdefault(
                    head, {"ids": ids, "budget": budget, "k": k,
                           "off": cached * self.cfg.page_size,
                           "slots": {}})
                e["slots"][j] = (rid, slot)
            else:
                self._prefilling[head]["slots"][j] = (rid, slot)
        return len(admitted)

    def _step_wave(self) -> List[CompletedRequest]:
        self._early_out = []

        # -- deferred aborts: a cancel that landed mid-chunked-prefill
        #    is applied at this wave boundary (activation flipped the
        #    request to decoding, where the preemption machinery can
        #    free its pages safely) ---------------------------------------
        for rid in list(self._cancels):
            self._cancels.discard(rid)
            if rid in self._reqinfo:
                self.cancel(rid)

        # -- admission (between jitted segments) ------------------------
        with obs.span("sched.admit", impl=self._sched_impl) as sp:
            admitted = self._admit()
            sp.set(admitted=admitted, waiting=int(self.sched.waiting))

        # -- host-tier spill: admission may have LRU-evicted cached
        #    pages; their KV is still intact ONLY until the prefill
        #    dispatch below donates the pools ---------------------------
        self._drain_spills()

        # -- prefill (one chunk per wave; final chunks sample) ----------
        if self._prefilling:
            self._rng, sub = jax.random.split(self._rng)
            self._prefill_wave(sub)

        # -- speculative wave decision (adaptive k) ---------------------
        # Made BEFORE reservation growth: a verify wave advances by
        # chunk extents and needs k slack positions per slot.
        spec_wave = self._spec_wave_decision()

        # -- on-demand reservation growth (may preempt) -----------------
        with obs.span("sched.extend") as sp:
            before = self.preemptions
            self._extend_running(spec_wave)
            sp.set(preempted=self.preemptions - before)
        # Extension evictions spill here, before the segment dispatch
        # below donates the pools.
        self._drain_spills()
        # Page-pool occupancy at the wave's peak (post-extension):
        # the headroom signal behind watermark/preemption tuning.
        self.telemetry.record_occupancy(
            1.0 - self.sched.available_pages / max(self.num_pages, 1))

        # -- decode segment (fixed length: done slots idle in place,
        #    so no reservation-overrun risk) ----------------------------
        decoding = self._phase == _DECODE
        if decoding.any():
            self._rng, sub = jax.random.split(self._rng)
            if self._bt_dev is None:
                self._bt_dev = jnp.asarray(self._bt)
            with self._ctx(), obs.span(
                    "engine.segment", live=int(decoding.sum()),
                    spec_k=self._spec_k if spec_wave else 0):
                if spec_wave:
                    self._pools, self._state = self._jit_spec_segment(
                        self._params, self._pools, self._bt_dev,
                        self._state, sub, n_steps=self._spec_steps,
                        k=self._spec_k)
                    self._waves_since_spec = 0
                else:
                    self._pools, self._state = self._jit_segment(
                        self._params, self._pools, self._bt_dev,
                        self._state, sub, n_steps=self.segment_len)
                    if self._spec:
                        self._waves_since_spec += 1
            # snapshot this wave's flags (tiny copies — the state
            # buffers themselves get donated to the next segment)
            # PAIRED with the slot→ADMISSION-SEQ mapping at snapshot
            # time: a done flag may only ever harvest the admission it
            # was measured for.  The pairing keys on the engine-unique
            # admission counter, NOT the request id — callers legally
            # reuse ids across generate() calls, and an id-keyed guard
            # let a stale snapshot from the previous occupant harvest
            # a same-id successor one wave early (with the stale
            # occupant's n_new reading past the successor's buffer).
            # Only DECODE-phase slots are paired: a slot admitted but
            # still mid-chunked-prefill carries the previous occupant's
            # (or init) done flag, and its admission seq already
            # matches — snapshotting it would false-harvest the
            # activation one wave later with a stale n_new.
            # Speculative mode: the cumulative per-slot [drafted,
            # accepted, resampled] counters + the draftability bit
            # (column 3) ride the same lagged snapshot (same pairing
            # guard): the host differences the counters against its
            # previous fetch to feed the acceptance EMAs and engine
            # totals, and the match bit feeds the next wave's verify
            # decision.
            # Streaming (PR 12): when a streaming request occupies a
            # decode slot, the wave's token buffer rides the SAME
            # lagged snapshot (one extra [S, T] device copy; ~50 KB at
            # the tiny shape) so incremental emission shares the flag
            # fetch's pairing guard — tokens can only ever be emitted
            # for the admission they were decoded under.  Non-streaming
            # traffic pays nothing.
            stream_live = lp_live = False
            if self._streams:
                for s in range(self.slots):
                    if self._phase[s] != _DECODE:
                        continue
                    sst = self._streams.get(int(self._slot_req[s]))
                    if sst is not None:
                        stream_live = True
                        if sst["lp"]:
                            lp_live = True
                            break
            snap_in = [self._state["done"], self._state["n_new"]]
            if self._spec:
                snap_in.append(self._state["spec_counts"])
            if stream_live:
                snap_in.append(self._state["toks"])
            if lp_live:
                # logprob streaming (PR 17): one more [S, T] copy rides
                # the snapshot only when a live stream asked for it.
                snap_in.append(self._state["lps"])
            snap = self._jit_snap(*snap_in)
            flags = {"done": snap[0], "n_new": snap[1],
                     "seq": np.where(self._phase == _DECODE,
                                     self._slot_seq, -1)}
            i = 2
            if self._spec:
                flags["counts"] = snap[i]
                i += 1
            if stream_live:
                flags["toks"] = snap[i]
                i += 1
            if lp_live:
                flags["lps"] = snap[i]
        else:
            flags = None

        # -- harvest: with harvest_lag=1 the flag fetch rides out the
        #    NEXT segment's device execution instead of idling the chip
        #    for a device→host round trip every wave (finished slots
        #    decode at most one extra masked segment; their buffers are
        #    stable once done).  With harvest_lag=0 this wave's flags
        #    are fetched immediately and the slot recycles a full
        #    segment earlier.  Pages free
        #    HERE — the segment boundary where the finish is observed —
        #    and are available to the very next admission.
        if self._harvest_lag == 0:
            self._pending_flags = flags
            flags = None
        out = self._early_out + self._harvest_pending()
        self._early_out = []
        self._pending_flags = flags
        return out

    def _spec_wave_decision(self) -> bool:
        """Adaptive k, decided per wave on the host from two cheap
        signals that rode the last flags fetch:

        - DRAFTABILITY: a slot whose trailing n-gram has no prior
          occurrence cannot draft at all — on unstructured text this
          stays False and the engine runs plain waves at ~zero
          overhead, without paying a verify chunk to learn it;
        - the per-request acceptance EMA: a draftable request with no
          EMA yet probes (one verify wave creates it); a proven
          request runs verify iff 1 + ema*k clears the chunk-cost
          breakeven (emitted tokens per verify step).

        Cold slots riding a hot wave draft only when matched, so
        their EMA reflects real draft quality and a warming request
        re-qualifies on its own evidence.  ``spec_probe_period``
        additionally forces a probe wave after that many consecutive
        plain waves so a proven-cold engine re-detects a workload
        shift."""
        if not self._spec:
            return False
        decoding = [(int(self._slot_req[s]), s)
                    for s in range(self.slots)
                    if self._phase[s] == _DECODE]
        if not decoding:
            return False
        if not self.cfg.spec_adaptive:
            return True
        if (self.cfg.spec_probe_period
                and self._waves_since_spec >= self.cfg.spec_probe_period
                and any(self._spec_match[s] for _, s in decoding)):
            # Periodic probe for MATCHED-but-proven-cold requests (a
            # workload shift re-detected): with no draftable slot at
            # all a probe would draft only pads and update nothing —
            # truly unstructured traffic stays probe-free.
            return True
        k, be = self._spec_k, self.cfg.spec_breakeven
        # Wave economics: a verify wave costs ~spec_breakeven plain
        # waves (the chunk-vs-step cost ratio), paid by EVERY decoding
        # slot, so it must clear breakeven on the WAVE MEAN — an
        # unmatched or proven-cold slot contributes its guaranteed 1
        # token per chunk, a proven-hot slot 1 + ema*k.  (The first
        # cut ran a verify wave whenever ANY slot was hot; with one
        # hot row among many cold ones that taxed the whole wave for
        # one row's gain and lost on mixed traffic.)
        exp_tokens = 0.0
        for rid, s in decoding:
            if not self._spec_match[s]:
                exp_tokens += 1.0
                continue
            ema = self._accept_ema.get(rid)
            if ema is None:
                # Draftable but unproven: probe — one verify wave
                # creates the EMA that prices this request from then
                # on.  (Unmatched rows can never reach this, so
                # unstructured traffic stays probe-free.)
                return True
            exp_tokens += 1.0 + ema * k
        return exp_tokens >= be * len(decoding)

    # EMA smoothing: per-request fast (a few waves to converge),
    # global slow (the workload prior new requests inherit).
    _EMA_REQ = 0.7
    _EMA_GLOBAL = 0.2

    def _spec_accounting(self, snap_seq, counts_h) -> None:
        """Difference the fetched cumulative [drafted, accepted,
        resampled] counters against the previous fetch (per slot,
        guarded by the admission-seq pairing exactly like the done
        flags), feed the acceptance EMAs + engine totals, and latch
        each slot's draftability bit (column 3) for the next wave
        decision."""
        for s in range(self.slots):
            if self._phase[s] != _DECODE or self._slot_seq[s] != snap_seq[s]:
                continue
            self._spec_match[s] = bool(counts_h[s, 3])
            d = int(counts_h[s, 0]) - int(self._spec_prev[s, 0])
            a = int(counts_h[s, 1]) - int(self._spec_prev[s, 1])
            r = int(counts_h[s, 2]) - int(self._spec_prev[s, 2])
            if d <= 0 and r <= 0:
                continue  # plain wave: counters unchanged
            self._spec_prev[s] = counts_h[s, :3]
            self.spec_drafted += d
            self.spec_accepted += a
            self.spec_resampled += r
            if d > 0:
                rate = a / d
                rid = int(self._slot_req[s])
                prev = self._accept_ema.get(rid)
                # First drafted wave SETS the EMA (no optimistic prior
                # to blend away a clean cold verdict); later waves
                # blend fast so a forming/breaking cycle re-qualifies
                # or disqualifies within a couple of waves.
                self._accept_ema[rid] = (rate if prev is None else
                                         self._EMA_REQ * rate
                                         + (1 - self._EMA_REQ) * prev)
                self._spec_global_ema = (
                    self._EMA_GLOBAL * rate
                    + (1 - self._EMA_GLOBAL) * self._spec_global_ema)

    def _emit_stream_chunks(self, toks_h, n_new_h, snap_seq,
                            lps_h=None) -> None:
        """Route this snapshot's newly decoded tokens (and, for
        ``logprobs=True`` streams, their sampling logprobs) to their
        streaming requests (buffered for ``poll``, or pushed through
        the submit-time callback).  Guarded by the same admission-seq
        pairing as the done flags: a slot's tokens only ever stream to
        the admission they were decoded for."""
        for s in range(self.slots):
            if self._phase[s] != _DECODE or self._slot_seq[s] != snap_seq[s]:
                continue
            rid = int(self._slot_req[s])
            st = self._streams.get(rid)
            if st is None:
                continue
            n = int(n_new_h[s])
            lo = st["emitted"]
            if n <= lo:
                continue
            new = np.asarray(toks_h[s, lo:n], np.int32).copy()
            new_lp = None
            if st["lp"] and lps_h is not None:
                new_lp = np.asarray(lps_h[s, lo:n], np.float32).copy()
            st["emitted"] = n
            if st["cb"] is not None:
                restarted = st["restarted"]
                st["restarted"] = False
                st["cb"](StreamChunk(req_id=rid, tokens=new,
                                     restarted=restarted,
                                     logprobs=new_lp))
            else:
                st["chunks"].append(new)
                if new_lp is not None:
                    st["lp_chunks"].append(new_lp)

    def _finish_stream(self, rid: int, rows_t, rows_l, n: int,
                       completed: CompletedRequest) -> None:
        """Final stream delivery for a harvested request: whatever the
        per-wave snapshots had not yet emitted, plus the completed
        record, with ``done=True``."""
        st = self._streams.get(rid)
        if st is None:
            return
        lo = st["emitted"]
        tail = np.asarray(rows_t[lo:n], np.int32).copy()
        tail_lp = (np.asarray(rows_l[lo:n], np.float32).copy()
                   if st["lp"] else None)
        st["emitted"] = n
        st["done"] = True
        st["completed"] = completed
        if st["cb"] is not None:
            restarted = st["restarted"]
            st["cb"](StreamChunk(req_id=rid, tokens=tail, done=True,
                                 restarted=restarted,
                                 completed=completed, logprobs=tail_lp))
            del self._streams[rid]  # pushed: nothing left to poll
        else:
            st["chunks"].append(tail)
            if tail_lp is not None:
                st["lp_chunks"].append(tail_lp)

    def _harvest_pending(self) -> List[CompletedRequest]:
        """Process the pending snapshot (if any): emit stream chunks,
        fetch the finished slots' completion rows, retire them with
        the scheduler (pages free here), and return the completions.
        Clears the pending snapshot."""
        if self._pending_flags is None:
            return []
        with obs.span("engine.harvest") as sp:
            out = self._harvest_flags()
            sp.set(finished=len(out),
                   tokens=sum(len(c.tokens) for c in out))
        return out

    def _harvest_flags(self) -> List[CompletedRequest]:
        out: List[CompletedRequest] = []
        pf = self._pending_flags
        self._pending_flags = None
        fetch = {k: pf[k]
                 for k in ("done", "n_new", "counts", "toks", "lps")
                 if k in pf}
        fetched = jax.device_get(fetch)
        done_h, n_new_h = fetched["done"], fetched["n_new"]
        snap_seq = pf["seq"]
        counts_h = fetched.get("counts")
        if counts_h is not None:
            self._spec_accounting(snap_seq, counts_h)
        if "toks" in fetched:
            self._emit_stream_chunks(fetched["toks"], n_new_h, snap_seq,
                                     fetched.get("lps"))
        finished = [s for s in range(self.slots)
                    if self._slot_req[s] >= 0
                    and self._phase[s] == _DECODE
                    and bool(done_h[s])
                    and self._slot_seq[s] == snap_seq[s]]
        if finished:
            # One whole-buffer fetch: a gather program per
            # finished-count compiles a fresh executable per count
            # (profiled at ~0.3 s of in-loop compiles on the CPU
            # serving trace), and the full [S, T] buffers are tiny
            # (~50 KB at the 1B shape) next to any fetch's fixed
            # cost.
            rows_h = jax.device_get({
                "t": self._state["toks"], "l": self._state["lps"],
                "p": self._state["plps"]})
            for s in finished:
                rid = int(self._slot_req[s])
                n = int(n_new_h[s])
                out.append(CompletedRequest(
                    req_id=rid,
                    tokens=rows_h["t"][s][:n].astype(np.int32),
                    logprobs=rows_h["l"][s][:n].astype(np.float32),
                    policy_logprobs=rows_h["p"][s][:n].astype(
                        np.float32)))
                self._finish_stream(rid, rows_h["t"][s], rows_h["l"][s],
                                    n, out[-1])
                self._req_tenant.pop(rid, None)
                self.sched.finish(rid)
                self.telemetry.finish(rid, n)
                if self._spec:
                    drafted = int(counts_h[s, 0])
                    if drafted > 0:
                        self.telemetry.record_spec_acceptance(
                            int(counts_h[s, 1]) / drafted)
                    self._accept_ema.pop(rid, None)
                    self._spec_prev[s, :] = 0
                del self._reqinfo[rid]
                self._admit_seq.pop(rid, None)
                self._slot_req[s] = -1
                self._slot_seq[s] = -1
                self._phase[s] = _EMPTY
                self._bt[s, :] = self._scratch  # free pages
                self._bt_dev = None
        return out

    # -- serving telemetry readout --------------------------------------
    def server_stats(self) -> dict:
        """Flat numeric request-lifecycle summary: queue-wait / TTFT /
        tok-per-s / prefix-hit / occupancy p50-p95-p99-mean-count plus
        the engine counters.  The shape bench JSON lines and
        MetricsWriter rows consume (``BaseTrainer.train`` writes it
        ``serving_``-prefixed at the end of a run)."""
        stats = self.telemetry.summary()
        stats["preempted_requests"] = float(self.preemptions)
        stats["prefix_cached_pages"] = float(self.prefix_cached_pages)
        stats["page_pool_size"] = float(self.num_pages)
        # Multi-tenant QoS counters (PR 12): per-tenant SLO histograms
        # already ride telemetry.summary() as tenant_<name>_* keys.
        stats["shed_requests"] = float(self.shed_requests)
        stats["cancelled_requests"] = float(self.cancelled_requests)
        # Speculative decoding v2 counters (zero when spec is off):
        # drafted/accepted reconcile with emitted tokens as
        # accepted + resampled == tokens emitted by verify segments.
        stats["spec_drafted"] = float(self.spec_drafted)
        stats["spec_accepted"] = float(self.spec_accepted)
        stats["spec_resampled"] = float(self.spec_resampled)
        stats["spec_accept_ema"] = (float(self._spec_global_ema)
                                    if self._spec else 0.0)
        # Host-RAM KV tier (PR 17): stable shape — zeros when off.
        if self._host_cache is not None:
            stats.update(self._host_cache.stats())
        else:
            stats.update({k: 0.0 for k in (
                "host_cache_entries", "host_cache_bytes",
                "host_cache_hits", "host_cache_misses",
                "host_cache_spills", "host_cache_evictions",
                "host_cache_readmits")})
        return stats

    def reset_spec_state(self) -> None:
        """Forget the adaptive-k evidence (per-request EMAs, global
        workload EMA, draftability bits, probe clock) — measurement
        windows that must start from the same adaptive prior (benches,
        A/B tests) call this between passes.  Engine counters and
        telemetry are separate (``reset_server_stats``)."""
        self._accept_ema.clear()
        self._spec_global_ema = 0.0
        self._spec_match[:] = False
        self._waves_since_spec = 0

    def reset_server_stats(self) -> None:
        """Drop accumulated telemetry/counters — including every
        per-tenant histogram/counter (``tenant_<name>_*``) — for bench
        measurement windows; in-flight request marks survive."""
        self.telemetry.reset()
        self.preemptions = 0
        self.prefix_cached_pages = 0
        self.shed_requests = 0
        self.cancelled_requests = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_resampled = 0
        if self._host_cache is not None:
            # Counters only — resident entries are warm state a bench
            # window must keep (that warmth is what it measures).
            self._host_cache.reset_counters()

    # -- host driver ----------------------------------------------------
    def generate(self, requests: Iterable[Tuple[int, np.ndarray]],
                 rng: jax.Array, params=None) -> List[CompletedRequest]:
        """Run all requests to completion; returns them in finish order.

        requests: iterable of (req_id, prompt_ids 1-D int array) or
        (req_id, prompt_ids, max_new_budget) — a per-request token
        budget ≤ cfg.max_new_tokens (the ragged-workload case this
        engine exists for: a finished slot's pages recycle into the
        next admission instead of idling to the batch max) — or
        (req_id, prompt_ids, max_new_budget, k): a sampling GROUP of k
        clones with ids req_id .. req_id+k-1 drawing independent
        completions from one shared prompt.  Caller must keep the
        implied id ranges disjoint.

        This is the run-to-completion convenience wrapper over the
        request-level service surface: ``submit`` every request, then
        ``step`` until drained.
        """
        if params is not None:
            self._params = self._prep_params(params)
        if self._params is None:
            raise ValueError("no weights loaded: call load_weights() first")
        self.reset_rng(rng)
        # Validate EVERY request before the first submit: the scheduler
        # is long-lived engine state, so a mid-loop raise would leave
        # earlier requests enqueued and poison every later generate()
        # call (stale ids admitted with no prompt entry).
        reqs = []
        seen = set(self._reqinfo)
        for r in requests:
            req_id, ids = r[0], np.asarray(r[1], np.int32)
            budget = int(r[2]) if len(r) > 2 and r[2] is not None \
                else self.cfg.max_new_tokens
            k = int(r[3]) if len(r) > 3 else 1
            for j in range(max(k, 1)):
                if req_id + j in seen:
                    raise ValueError(
                        f"request id {req_id + j} already in flight")
                seen.add(req_id + j)
            if len(ids) > self.cfg.max_prompt_len:
                raise ValueError(f"prompt {req_id} longer than "
                                 f"max_prompt_len={self.cfg.max_prompt_len}")
            if not 1 <= budget <= self.cfg.max_new_tokens:
                raise ValueError(
                    f"request {req_id}: budget {budget} outside "
                    f"[1, max_new_tokens={self.cfg.max_new_tokens}]")
            if not 1 <= k <= self.slots:
                raise ValueError(
                    f"request {req_id}: group of {k} clones can never "
                    f"be admitted (max_slots={self.slots})")
            reqs.append((req_id, ids, budget, k))
        for req_id, ids, budget, k in reqs:
            self.submit(req_id, ids, budget=budget, k=k)
        out: List[CompletedRequest] = []
        while self.sched.waiting or self.sched.running:
            out.extend(self.step())
        return out

    # -- trainer-facing batch API (GenerationResult contract) -----------
    def generate_batch(self, prompt_ids, prompt_lens, rng: jax.Array,
                       params=None, max_new_tokens: Optional[int] = None,
                       group_size: int = 1):
        """RolloutEngine-compatible surface (VERDICT r1 next #5): run the
        batch as a request stream through the continuous scheduler and
        pack the completions into a padded GenerationResult — so any
        trainer can select this engine via RolloutConfig.engine.

        group_size=k > 1 (VERDICT r4 missing #3): prompt_ids holds the
        UNIQUE prompts; each is sampled k times via shared-prefix group
        admission (one prefill + one physical copy of the fully-filled
        prompt pages per group) and the result rows come back in the
        repeated layout the group trainers use — row i*k+j is clone j
        of prompt i, exactly matching np.repeat(prompts, k, axis=0)
        order.  RolloutConfig.group_prefix_sharing=False falls back to
        k independent solo requests (the A/B baseline).

        max_new_tokens, if given, must equal cfg.max_new_tokens (the
        page reservations are sized for it)."""
        from orion_tpu.ops.logprobs import pack_sequences
        from orion_tpu.resilience import fault_point
        from orion_tpu.rollout.engine import GenerationResult

        # Same named fault point as RolloutEngine.generate — chaos
        # plans target the trainer-facing dispatch of either engine.
        fault_point("rollout.generate")
        if max_new_tokens is not None and \
                max_new_tokens != self.cfg.max_new_tokens:
            raise ValueError(
                f"continuous engine reserves pages for max_new_tokens="
                f"{self.cfg.max_new_tokens}; got {max_new_tokens}")
        k = int(group_size)
        if k < 1:
            raise ValueError(f"group_size must be >= 1, got {k}")
        prompt_ids = np.asarray(prompt_ids)
        prompt_lens = np.asarray(prompt_lens, np.int32)
        B = prompt_ids.shape[0]
        T = self.cfg.max_new_tokens
        if k > 1 and self.cfg.group_prefix_sharing:
            reqs = [(i * k, prompt_ids[i, : prompt_lens[i]], None, k)
                    for i in range(B)]
        else:
            reqs = [(i * k + j, prompt_ids[i, : prompt_lens[i]])
                    for i in range(B) for j in range(k)]
        by_id = {r.req_id: r for r in self.generate(reqs, rng, params)}
        if k > 1:
            prompt_ids = np.repeat(prompt_ids, k, axis=0)
            prompt_lens = np.repeat(prompt_lens, k, axis=0)
            B = B * k

        tokens = np.full((B, T), self.pad, np.int32)
        logps = np.zeros((B, T), np.float32)
        plogps = np.zeros((B, T), np.float32)
        comp_len = np.zeros((B,), np.int32)
        for i in range(B):
            r = by_id[i]
            n = len(r.tokens)
            tokens[i, :n] = r.tokens
            logps[i, :n] = r.logprobs
            plogps[i, :n] = r.policy_logprobs
            comp_len[i] = n
        mask = (np.arange(T)[None, :] < comp_len[:, None]).astype(np.float32)
        sequences = np.asarray(pack_sequences(
            jnp.asarray(prompt_ids), jnp.asarray(prompt_lens),
            jnp.asarray(tokens)))
        return GenerationResult(
            sequences=sequences, completions=tokens,
            completion_mask=mask, completion_lens=comp_len,
            logprobs=logps, policy_logprobs=plogps,
            prompt_lens=prompt_lens, total_lens=prompt_lens + comp_len)
