"""Trainer base: optimizer construction, train state, the experience
pipeline skeleton, and the sync weight-sync channel.

Control flow contract (SURVEY.md §3a): each iteration is
  prompts → rollout.generate → score → advantages → minibatch updates
  → weight-sync → metrics.
Algorithm subclasses implement ``build_experience`` (experience from a
finished generation — it must not generate, so the async orchestrator
can call it on the learner side) and ``loss_fn`` (pure jittable loss
over a minibatch); the base class owns prompt prep, generation,
minibatching, the jitted update step, and logging.  Do NOT override
``make_experience`` — it is the sync-mode composition of those hooks.
"""

from __future__ import annotations

import json
import resource
import sys
from typing import Any, Callable, Dict, Iterator, Optional

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax

from orion_tpu.config import OptimizerConfig, TrainConfig
from orion_tpu.models.transformer import (Transformer, remat_keep,
                                         remat_tag_bytes, rl_fixed, sown,
                                         stream_attrs, trace_inputs,
                                         trace_row_length, update_attrs)
from orion_tpu.ops.logprobs import completion_logprobs, entropy_from_logits
from orion_tpu.rollout import GenerationResult, RolloutEngine


@flax.struct.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jnp.ndarray

    @staticmethod
    def create(params: Any, tx: optax.GradientTransformation) -> "TrainState":
        state = TrainState(params=params, opt_state=tx.init(params),
                           step=jnp.zeros((), jnp.int32))
        return _commit_to_params_mesh(state)


def _commit_to_params_mesh(state: "TrainState") -> "TrainState":
    """Pin every TrainState leaf to the params' mesh (scalars/counters
    replicated).  optax.init creates its counters eagerly on the default
    device as UNcommitted arrays; jit tolerates that, but an orbax
    restore brings them back COMMITTED there, and a committed cpu:0
    counter next to mesh-committed params is a cross-device jit error —
    the elastic-resume failure mode (SURVEY.md §5 failure recovery)."""
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = None
    for x in jax.tree.leaves(state.params):
        sh = getattr(x, "sharding", None)
        if isinstance(sh, NamedSharding):
            mesh = sh.mesh
            break
    if mesh is None:
        return state
    repl = NamedSharding(mesh, PartitionSpec())

    def fix(x):
        sh = getattr(x, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh == mesh:
            return x
        return jax.device_put(x, repl)

    return jax.tree.map(fix, state)


def make_schedule(cfg: OptimizerConfig):
    base = cfg.learning_rate
    if cfg.schedule == "constant" and cfg.warmup_steps == 0:
        return base
    if cfg.schedule != "constant" and cfg.total_steps <= 0:
        raise ValueError(
            f"schedule={cfg.schedule!r} needs optimizer.total_steps > 0 "
            "(the decay horizon); total_steps=0 only works with 'constant'")
    warmup = optax.linear_schedule(0.0, base, max(cfg.warmup_steps, 1))
    rest_steps = max(cfg.total_steps - cfg.warmup_steps, 1)
    if cfg.schedule == "cosine":
        rest = optax.cosine_decay_schedule(base, rest_steps)
    elif cfg.schedule == "linear":
        rest = optax.linear_schedule(base, 0.0, rest_steps)
    else:
        rest = optax.constant_schedule(base)
    return optax.join_schedules([warmup, rest], [cfg.warmup_steps])


def split_group_layout(prompt_ids, prompt_lens, k: int):
    """Recover the unique prompts from prepare_prompts' repeated i*k+j
    layout (used to hand a group-capable engine B/k unique prompts +
    group_size instead of B pre-repeated clones).  Validates the layout
    — the single shared guard for the sync trainer and the async
    rollout worker."""
    ids = np.asarray(prompt_ids)
    lens = np.asarray(prompt_lens)
    uids, ulens = ids[::k], lens[::k]
    if not (np.array_equal(ids, np.repeat(uids, k, axis=0))
            and np.array_equal(lens, np.repeat(ulens, k))):
        raise ValueError(
            f"group_size={k} passed but prompts are not in the "
            "repeated i*k+j layout prepare_prompts produces")
    return uids, ulens


def dispatch_generate_batch(engine, prompt_ids, prompt_lens, rng,
                            group_size: int = 1, **kw):
    """THE group-aware dispatch onto a generate_batch-style engine,
    shared by the sync trainer and the async rollout worker: a
    group-capable engine gets the B/k unique prompts + group_size (so
    it can share prompt pages across each group's clones); anything
    else gets the repeated batch unchanged.  Output layout is the
    repeated i*k+j order either way."""
    k = int(group_size)
    if k > 1 and getattr(engine, "supports_groups", False):
        uids, ulens = split_group_layout(prompt_ids, prompt_lens, k)
        return engine.generate_batch(uids, ulens, rng, group_size=k, **kw)
    return engine.generate_batch(prompt_ids, prompt_lens, rng, **kw)


def make_optimizer(cfg: OptimizerConfig) -> optax.GradientTransformation:
    if cfg.nu_dtype is not None:
        from orion_tpu.algos.optim import adamw_lp

        tx = adamw_lp(make_schedule(cfg), b1=cfg.betas[0], b2=cfg.betas[1],
                      eps=cfg.eps, weight_decay=cfg.weight_decay,
                      mu_dtype=cfg.mu_dtype, nu_dtype=cfg.nu_dtype)
    else:
        tx = optax.adamw(make_schedule(cfg), b1=cfg.betas[0],
                         b2=cfg.betas[1], eps=cfg.eps,
                         weight_decay=cfg.weight_decay,
                         mu_dtype=cfg.mu_dtype)
    if cfg.grad_clip > 0:
        tx = optax.chain(optax.clip_by_global_norm(cfg.grad_clip), tx)
    return tx


#: Held back from the device's free bytes when the update's checkpoints
#: are given what they may keep: the allocator's fragmentation, and what
#: the compiler lays out otherwise once tensors are kept.
_REMAT_MARGIN_BYTES = 512 << 20


def _device_free_bytes(tree) -> Optional[int]:
    """``bytes_limit - bytes_in_use`` of the fullest of this process's
    devices that hold ``tree``; None where a device does not report
    them (the CPU)."""
    devices = set()
    for x in jax.tree.leaves(tree):
        devices |= x.devices()
    free = []
    for d in devices:
        if d.process_index != jax.process_index():
            continue
        stats = d.memory_stats() or {}
        if "bytes_limit" not in stats or "bytes_in_use" not in stats:
            return None
        free.append(stats["bytes_limit"] - stats["bytes_in_use"])
    return min(free) if free else None


def _stamp(span, gc_totals: tuple, compile_totals: tuple) -> tuple:
    """(wall, this thread's CPU clock, its collector's seconds, its
    compile clock) at the START of ``span``, ``gc_totals`` and
    ``compile_totals`` being ``obs.gc_totals()`` and
    ``obs.compile_totals()`` read there: the four clocks the trainer
    loop stamps an iteration on."""
    return span.start, span.cpu_start, gc_totals[1], compile_totals


def hold_fixed(updates, cfg_model):
    """The optimizer's updates with those of the parameters that RL
    holds fixed set to zero: the ones the model names by prefix
    (``models.transformer.rl_fixed``).  No gradient reaches them,
    so this only keeps weight decay off them; a model that names none
    gets its updates back as they are."""
    fixed = rl_fixed(cfg_model)
    if not fixed:
        return updates
    return jax.tree_util.tree_map_with_path(
        lambda path, u: jnp.zeros_like(u) if any(
            str(getattr(k, "key", "")).startswith(fixed) for k in path)
        else u, updates)


def state_out_shardings(state: "TrainState"):
    """``out_shardings`` for a donated TrainState: mesh-sharded leaves
    keep the layout they came in with; the rest stay unspecified.
    Left to itself GSPMD re-lays some leaves out (norm scales come back
    fsdp-sharded), and every program that takes the params — generate,
    the experience forwards, the update itself — compiles a second time
    in iteration 1 (seen by chip_smoke.py's sentinel on a 2x2 mesh)."""
    from jax.sharding import NamedSharding

    return jax.tree.map(
        lambda x: x.sharding if isinstance(x.sharding, NamedSharding)
        else None, state)


def moe_load_stats(loads: list, pairs_per_layer: int,
                   block_rows: int, combines: list = ()) -> dict:
    """Counters of the dropless expert layer for one forward, from the
    ``moe_load`` arrays its layers sowed ([held] or, scanned, [layers,
    held] pairs routed to each expert held here): the pairs computed
    here and all pairs routed (over all layers), the largest and the
    mean load of a held expert (over layers and experts: max over mean
    is the padding a grouped product pays), and what the grouped form
    moved for them: the rows of a block (``block_rows``,
    ops/moe.py::block_rows; 0 for the dense form) over all layers, and
    the most blocks a layer ran (1: its held pairs fit the first); from
    the ``moe_combine`` arrays ([2] a layer: work items, rows placed),
    how full the combine kernel ran: the most items a layer call took
    and the rows placed over the rows the items' products span (both 0
    for the dense form)."""
    from orion_tpu.ops.pallas.moe_combine import chunk_rows

    load = jnp.concatenate([x.reshape(-1, x.shape[-1]) for x in loads])
    items, placed = jnp.concatenate(
        [x.reshape(-1, 2) for x in combines] or [jnp.zeros((1, 2))]).T
    blocks = jnp.zeros((), jnp.int32)
    if block_rows:      # block 0 always runs
        blocks = jnp.max(jnp.maximum(
            1, -(-jnp.sum(load, axis=-1) // block_rows)))
    return {
        "moe_pairs_here": jnp.sum(load).astype(jnp.float32),
        "moe_pairs_total": jnp.float32(pairs_per_layer * load.shape[0]),
        "moe_load_max": jnp.max(load).astype(jnp.float32),
        "moe_load_mean": jnp.mean(load.astype(jnp.float32)),
        "moe_block_rows": jnp.float32(block_rows * load.shape[0]),
        "moe_blocks_max": blocks.astype(jnp.float32),
        "moe_combine_items": jnp.max(items).astype(jnp.float32),
        "moe_combine_fill": (jnp.sum(placed) / jnp.maximum(
            jnp.sum(items) * chunk_rows(block_rows), 1)).astype(jnp.float32),
    }


def step_read_pct(expert_stacks) -> dict:
    """``moe_step_read_pct``: of the held experts' stacks, over expert
    layers and the rollout's one-token steps, the share the steps read
    (``GenerationResult.expert_stacks``: 100 where the expert layer
    reads every stack at every step; {} where the rollout counted
    none)."""
    if expert_stacks is None:
        return {}
    read, held = (float(v) for v in expert_stacks)
    return {"moe_step_read_pct": 100.0 * read / max(held, 1.0)}


def ut_exit_stats(masses, read_at, mask) -> dict:
    """Counters of a stack run several times over for one forward, from
    the exit masses it sowed (``ut_exit_mass`` [passes, B, L]) read at
    ``read_at`` [B, T], means over the tokens ``mask`` [B, T] marks
    (none: all of them): ``ut_exit_mass_<t>``, the mass on pass t, and
    ``ut_passes_per_token``, the passes this forward ran (as many as it
    sowed masses for: at ``early_exit_threshold`` 1, the only one a
    configuration may state, every token's hidden state is the last
    pass's, so the counter mirrors ``total_ut_steps`` until a program
    ends a token's passes early)."""
    at = jnp.take_along_axis(masses, read_at[None], axis=2)
    w = jnp.ones(read_at.shape, jnp.float32) if mask is None \
        else mask.astype(jnp.float32)
    n = jnp.maximum(jnp.sum(w), 1.0)
    stats = {f"ut_exit_mass_{t + 1}": jnp.sum(m * w) / n
             for t, m in enumerate(at)}
    stats["ut_passes_per_token"] = jnp.float32(len(at))
    return stats


def _read_at(extra, read_at):
    """A forward's per-position outputs beyond the logits ([B, row]: the
    values) at ``read_at`` [B, T]; anything else as it is."""
    return tuple(jnp.take_along_axis(x, read_at, axis=1)
                 if getattr(x, "ndim", 0) == 2 else x for x in extra)


class BaseTrainer:
    """Shared machinery; see PPOTrainer/GRPOTrainer/... for algorithms.

    Args:
      cfg: algorithm config (TrainConfig subclass).
      model: the policy Transformer (also used for ref logprobs).
      params: policy params (on-mesh or host; used as-is).
      ref_params: frozen reference policy params (None => snapshot of
        ``params`` at construction — the standard init-KL anchoring).
      reward_fn: host callable (GenerationResult, batch_meta) -> np [B]
        sequence scores.  Model-based rewards wrap ModelReward.
      eos/pad token ids: generation termination.
    """

    needs_ref = True
    #: What the blocks' checkpoints keep in the update (model.remat),
    #: and the row that says so: chosen once, before the update's first
    #: trace (:meth:`_choose_remat_keep`).
    _remat_keep: tuple = ()
    _remat_info: Optional[dict] = None
    #: The stamps of the first iterations (at most eight), until the
    #: first of them that compiled nothing has its ``setup`` row (None
    #: from then on).
    _setup_begins: Optional[list] = None

    def __init__(self, cfg: TrainConfig, model: Transformer, params: Any,
                 reward_fn: Optional[Callable] = None,
                 ref_params: Any = None,
                 eos_token_id: Optional[int] = None, pad_token_id: int = 0):
        self.cfg = cfg
        self.model = model
        self.tx = make_optimizer(cfg.optimizer)
        self.state = TrainState.create(params, self.tx)
        self.reward_fn = reward_fn
        if self.needs_ref:
            # Real buffer copy: the update step donates the policy params,
            # so an aliasing snapshot would be invalidated.  Optionally
            # stored reduced-precision (cfg.ref_param_dtype) — the ref
            # only runs forward, and the cast IS a copy.
            rdt = cfg.ref_param_dtype
            if ref_params is not None:
                self.ref_params = ref_params
            elif rdt is not None:
                # astype(same_dtype) is an ALIAS in jax, not a copy —
                # jnp.copy when the dtype already matches, or donation
                # would delete the ref out from under us.
                def _snap(x):
                    dt = jnp.dtype(rdt)
                    if jnp.issubdtype(x.dtype, jnp.floating) and \
                            x.dtype != dt:
                        return x.astype(dt)
                    return jnp.copy(x)

                self.ref_params = jax.tree.map(_snap, params)
            else:
                self.ref_params = jax.tree.map(jnp.copy, params)
        else:
            self.ref_params = None
        if cfg.rollout.engine == "continuous":
            from orion_tpu.parallel.sharding import ambient_mesh
            from orion_tpu.rollout.continuous import ContinuousBatchingEngine

            # Sync-mode trainer built under `with mesh:` — give the
            # engine the same mesh so its decode shards with the
            # trainer's params instead of collapsing to one device.
            m = ambient_mesh()
            m = m if m is not None and not m.empty and m.size > 1 else None
            self.engine = ContinuousBatchingEngine(
                model, cfg.model, cfg.rollout, eos_token_id=eos_token_id,
                pad_token_id=pad_token_id,
                segment_len=cfg.rollout.segment_len, mesh=m)
        elif cfg.rollout.engine == "simple":
            self.engine = RolloutEngine(model, cfg.model, cfg.rollout,
                                        eos_token_id=eos_token_id,
                                        pad_token_id=pad_token_id)
        else:
            raise ValueError(
                f"rollout.engine must be 'simple' or 'continuous', "
                f"got {cfg.rollout.engine!r}")
        self.engine.load_weights(params)
        self.metrics_history: list = []
        # Deferred-stats pipeline (sync train() only): when True,
        # build_experience/update_epochs leave stats as device scalars;
        # train() piggybacks their fetch on the NEXT iteration's
        # generation fetch, so each iteration blocks on exactly ONE
        # device→host round-trip (the old loop blocked on three per
        # iteration).  The async orchestrator calls
        # build_experience/update_epochs directly and keeps the eager
        # (False) behavior.
        self._defer_stats = False
        self._pending_fetch = None
        self._pending_meta = None
        # models.transformer.update_attrs of the batch last fetched
        self._update_attrs: dict = {}
        self._fetch_s = (0.0, 0.0)   # (fetch.wait, fetch.copy) last taken
        self._rng = jax.random.key(cfg.seed)
        self._np_rng = np.random.RandomState(cfg.seed)
        self._jit_logprobs = jax.jit(
            self._logprobs_fn, static_argnames=("max_new",))
        # ``_jit_epochs`` is what runs (and what a harness may wrap);
        # ``_update_jit`` stays the jit itself, for _choose_remat_keep
        self._update_jit = self._jit_epochs = self._jit_update(
            self._epochs_fn, (self.state,))
        self.global_iter = 0
        self.ckpt = None
        if cfg.checkpoint_dir and cfg.checkpoint_every:
            from orion_tpu.utils.checkpoint import CheckpointManager

            self.ckpt = CheckpointManager(
                cfg.checkpoint_dir, max_to_keep=cfg.checkpoint_keep,
                save_attempts=cfg.resilience.checkpoint_save_attempts,
                wait_deadline=cfg.resilience.checkpoint_wait_deadline,
                retry_seed=cfg.seed)
        # Deterministic chaos arming (orion_tpu.resilience.inject): a
        # config-carried fault plan installs process-wide here; the
        # ORION_FAULT_PLAN env var is the zero-code alternative.
        if cfg.resilience.fault_plan:
            from orion_tpu.resilience import install_plan, plan_from_spec

            install_plan(plan_from_spec(cfg.resilience.fault_plan,
                                        seed=cfg.resilience.fault_seed))
        else:
            # Eager env arming: a typo'd ORION_FAULT_PLAN point
            # ("rollout.genrate") must raise HERE, at arm time — the
            # lazy first-hit path would silently arm nothing until a
            # fault point fires, which for a misspelled point is never.
            from orion_tpu.resilience.inject import (install_plan,
                                                     plan_from_env)

            env_plan = plan_from_env()
            if env_plan is not None:
                install_plan(env_plan)
        self.writer = None
        if cfg.log_dir:
            from orion_tpu.utils.metrics import MetricsWriter

            self.writer = MetricsWriter(cfg.log_dir)
        # Observability (orion_tpu.obs): cfg.obs.trace arms the span
        # tracer (+ flight recorder, dumping into log_dir) for this
        # process; close() releases it like the recompile sentinel.
        from orion_tpu import obs as _obs

        self._obs = _obs.install_from_config(cfg)
        # The collector's hook is there whether or not obs.trace is on:
        # every metrics row carries host_gc_s (obs/gcwatch.py).
        self._gc_watch = _obs.install_gc_watch()
        # and the compile watch's: every row carries compile_s /
        # compiles / cache_misses, and the first steady iteration writes
        # the one ``setup`` row (obs/compilewatch.py)
        self._compile_watch = _obs.install_compile_watch()
        self._setup_begins = []
        # Opt-in runtime guards (orion_tpu.analysis.runtime_guards):
        # recompile sentinel installs here; the transfer guard wraps
        # the train() loop body.
        from orion_tpu.analysis.runtime_guards import install_from_config

        self._recompile_sentinel = install_from_config(cfg)

    def close(self) -> None:
        """Release process-global hooks (the recompile sentinel, the
        obs tracer/flight recorder, the collector's hook, the hold on
        the compile watch) and close the metrics writer —
        THE trainer/orchestrator exit path for every sink.  Idempotent;
        also runs
        from __del__ so sweep scripts constructing many trainers don't
        accumulate handlers, but an explicit close() is the reliable
        path."""
        sentinel = getattr(self, "_recompile_sentinel", None)
        if sentinel is not None:
            sentinel.uninstall()
            self._recompile_sentinel = None
        # the hooks first: a collection or a compile they report wants
        # the tracer that was there while it ran
        for attr in ("_gc_watch", "_compile_watch", "_obs"):
            held = getattr(self, attr, None)
            if held is not None:
                held.uninstall()
                setattr(self, attr, None)
        writer = getattr(self, "writer", None)
        if writer is not None:
            writer.close()
            self.writer = None

    def __del__(self):  # pragma: no cover - interpreter-dependent
        try:
            self.close()
        except Exception:
            pass

    # ------------------------------------------------------------------
    # jitted helpers
    # ------------------------------------------------------------------
    def _policy_apply(self, params, sequences, positions, **apply_kw):
        """(apply outputs, aux, moe): policy forward + the GShard
        router's load-balance auxiliary loss (mean over layers; 0.0 for
        dense models and for the dropless deepseek_v3 layer, which has
        none).  Loss paths add ``cfg.model.router_aux_coef * aux`` —
        without it a num_experts>0 run has zero load-balancing pressure
        and experts silently collapse.  ``moe``: the dropless layer's
        counters of this forward (:func:`moe_load_stats`; {} for every
        other model), which loss paths put into their stats.
        ``apply_kw`` passes through to the module (e.g.
        with_values=True on ActorCriticModel) — the single source of
        truth for the aux aggregation."""
        mc = self.cfg.model
        moe = {}
        # getattr: tests/bench borrows this method for an object of its own
        keep = getattr(self, "_remat_keep", ())
        if keep:
            apply_kw["remat_keep"] = keep
        if mc.num_experts > 0:
            out, inter = self.model.apply(
                {"params": params}, sequences, positions,
                mutable=["intermediates"], **apply_kw)
            # Only the router's 'moe_aux_loss' sows feed the loss — any
            # other sown diagnostic (activation stats, attention probes)
            # must NOT silently shift the training objective (ADVICE r2).
            leaves = sown(inter, "moe_aux_loss")
            if not leaves:
                raise ValueError(
                    "num_experts > 0 but no 'moe_aux_loss' intermediates "
                    "were sown — router aux loss would be silently zero")
            aux = sum(jnp.mean(x) for x in leaves) / len(leaves)
        elif mc.n_routed_experts > 0:
            # The dropless layer (deepseek_v3) has no auxiliary loss;
            # the loads its layers sow become the forward's counters.
            out, inter = self.model.apply(
                {"params": params}, sequences, positions,
                mutable=["intermediates"], **apply_kw)
            from orion_tpu.ops.moe import block_rows

            moe = moe_load_stats(sown(inter, "moe_load"),
                                 sequences.size * mc.num_experts_per_tok,
                                 block_rows(mc, sequences.size),
                                 sown(inter, "moe_combine"))
            aux = jnp.zeros((), jnp.float32)
        elif mc.total_ut_steps > 1:
            # A looped stack sows its exit masses [passes, B, L]; the
            # caller that knows which tokens count reduces them
            # (_windowed_forward).
            out, inter = self.model.apply(
                {"params": params}, sequences, positions,
                mutable=["intermediates"], **apply_kw)
            moe = {"ut_exit_mass": sown(inter, "ut_exit_mass")[0]}
            aux = jnp.zeros((), jnp.float32)
        else:
            out = self.model.apply({"params": params}, sequences,
                                   positions, **apply_kw)
            aux = jnp.zeros((), jnp.float32)
        return out, aux, moe

    def _windowed_forward(self, params, sequences, prompt_lens,
                          max_new: int, with_entropy: bool = True,
                          reveal_step=None, mask=None, **apply_kw):
        """Shared completion-window forward: the vocab projection runs
        only at the T completion positions (ops.logprobs.completion_
        window_positions) — the [B, L, V] f32 logits at full length are
        the biggest tensor in the pipeline and 2/3 of them were thrown
        away (r3 perf).  Returns (lp [B,T], ent [B,T] | None, extra
        apply outputs, aux, moe) where ``extra`` carries whatever the
        module returned beyond logits (e.g. values for
        ActorCriticModel), each read at the T positions the logits are
        (the value of completion token t: the hidden state its
        log-probability is computed from), and ``aux``, ``moe`` are
        _policy_apply's (a looped stack's counters are means over the
        window's tokens that ``mask`` [B, T] marks: :func:`ut_exit_stats`).
        A block-diffusion model's completion is scored along its sampling
        trace ``reveal_step`` (:meth:`_trace_forward`)."""
        from orion_tpu.ops.logprobs import (completion_window_positions,
                                            windowed_completion_logprobs)

        if self.cfg.model.block_length:
            return self._trace_forward(params, sequences, prompt_lens,
                                       reveal_step, with_entropy, **apply_kw)
        L = sequences.shape[1]
        positions = jnp.broadcast_to(
            jnp.arange(L, dtype=jnp.int32), sequences.shape)
        widx = completion_window_positions(prompt_lens, max_new, L)
        if self.cfg.model.takes_token_mask:
            # behind prompt + completion window a row is padding, whatever
            # the completion's length: the dropless expert layer routes
            # those positions nowhere, a recurrent mixer passes them by
            apply_kw["token_mask"] = positions < (prompt_lens
                                                  + max_new)[:, None]
        out, aux, moe = self._policy_apply(
            params, sequences, positions, logits_positions=widx,
            **apply_kw)
        logits_w, extra = out[0], _read_at(out[1:], widx)
        if "ut_exit_mass" in moe:
            moe = ut_exit_stats(moe["ut_exit_mass"], widx, mask)
        lp = windowed_completion_logprobs(logits_w, sequences, prompt_lens,
                                          max_new)
        ent = entropy_from_logits(logits_w) if with_entropy else None
        return lp, ent, extra, aux, moe

    def _trace_forward(self, params, sequences, prompt_lens, reveal_step,
                       with_entropy: bool = True, **apply_kw):
        """:meth:`_windowed_forward` for a block-diffusion model: token p
        of a completion is scored at its own position, given the state
        of its block at the step it was revealed and the clean blocks
        before it: by construction the distribution it was drawn from.
        ONE forward does it for all tokens: the row ``[clean ; one noisy
        stream a denoising step]`` under the two-part mask
        (``models.transformer.trace_inputs``), logits and values read at
        the T noisy entries (step(p), p) only.  The
        mask token is barred from the distribution as the engine bars
        it."""
        from orion_tpu.ops.sampling import bar_token

        mc = self.cfg.model
        if reveal_step is None:
            raise ValueError(
                "a block-diffusion completion is scored along its sampling "
                "trace: pass the rollout's reveal_step")
        T = reveal_step.shape[1]
        ids, positions, kw = trace_inputs(mc, sequences, prompt_lens,
                                          reveal_step)
        out, aux, moe = self._policy_apply(params, ids, positions, **kw,
                                           **apply_kw)
        logits_w = bar_token(out[0], mc.mask_id)
        extra = _read_at(out[1:], kw["logits_positions"])
        at = jnp.clip(prompt_lens[:, None] + jnp.arange(T)[None, :], 0,
                      sequences.shape[1] - 1)
        targets = jnp.take_along_axis(sequences, at, axis=1)
        lp = jnp.take_along_axis(
            jax.nn.log_softmax(logits_w, axis=-1), targets[..., None],
            axis=-1)[..., 0]
        ent = entropy_from_logits(logits_w) if with_entropy else None
        return lp, ent, extra, aux, moe

    @staticmethod
    def _trace_kw(source, name: str = "reveal_step") -> dict:
        """``{"reveal_step": ...}`` where ``source`` (a rollout's result
        or a minibatch) carries a block-diffusion model's sampling trace
        under ``name``; {} for every other model."""
        trace = source.get(name) if isinstance(source, dict) \
            else getattr(source, name, None)
        return {} if trace is None else {"reveal_step": trace}

    def _logprobs_fn(self, params, sequences, prompt_lens, max_new: int,
                     reveal_step=None):
        """Completion logprobs + entropy (+ MoE aux loss and counters)
        under the training graph, over the completion window."""
        lp, ent, _, aux, moe = self._windowed_forward(
            params, sequences, prompt_lens, max_new,
            reveal_step=reveal_step)
        return lp, (ent, aux, moe)

    def loss_fn(self, params, mb: Dict[str, jnp.ndarray]):
        raise NotImplementedError

    def _update_fn(self, state: TrainState, experience, idx):
        mb = jax.tree.map(lambda x: jnp.take(x, idx, axis=0), experience)
        (loss, stats), grads = jax.value_and_grad(
            self.loss_fn, has_aux=True)(state.params, mb)
        updates, opt_state = self.tx.update(grads, state.opt_state,
                                            state.params)
        params = optax.apply_updates(
            state.params, hold_fixed(updates, self.cfg.model))
        stats = dict(stats)
        stats["grad_norm"] = optax.global_norm(grads)
        stats["loss"] = loss
        return TrainState(params=params, opt_state=opt_state,
                          step=state.step + 1), stats

    # ------------------------------------------------------------------
    # experience pipeline
    # ------------------------------------------------------------------
    def next_rng(self) -> jax.Array:
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def generate(self, prompt_ids, prompt_lens,
                 rng: Optional[jax.Array] = None,
                 group_size: int = 1) -> GenerationResult:
        """group_size=k > 1 tells a group-capable engine that the
        (prepare_prompts-repeated) batch is really B/k unique prompts ×
        k clones: the continuous engine then prefills each unique
        prompt once and shares its prompt pages across the clones
        (VERDICT r4 missing #3).  Output layout is identical either
        way — row i*k+j is clone j of prompt i."""
        rng = self.next_rng() if rng is None else rng
        if hasattr(self.engine, "generate_batch"):
            # Continuous engine: host-driven admission loop; it takes
            # host prompt arrays directly.  params=None -> the engine
            # uses the compute-dtype copy installed by sync_weights /
            # construction (an explicit tree here would be re-cast every
            # iteration for nothing).
            return dispatch_generate_batch(
                self.engine, prompt_ids, prompt_lens, rng,
                group_size=group_size)
        # One batched host→device transfer for both prompt arrays,
        # replicated on the params mesh when there is one
        # (multi-controller correctness — see replicated_put).
        from orion_tpu.utils.placement import replicated_put

        ids, lens = replicated_put((prompt_ids, prompt_lens),
                                   self.state.params)
        return self.engine.generate(ids, lens, rng,
                                    params=self.state.params)

    def _score_result(self, result, host, meta) -> np.ndarray:
        """One place for the device-vs-host reward dispatch (the
        wants_device_result contract) — used by make_experience,
        evaluate, and the async rollout loop."""
        from orion_tpu import obs

        wants_device = getattr(self.reward_fn, "wants_device_result",
                               False)
        with obs.span("reward.score", n=int(host.completion_lens.shape[0])):
            return self.score(result if wants_device else host, meta)

    def score(self, result: GenerationResult, batch: dict) -> np.ndarray:
        """Sequence-level scores [B] as host f32.  ``result`` should be
        the host copy (``GenerationResult.to_host()``) unless the reward
        fn sets ``wants_device_result`` (model-based rewards score on
        device and pay one fetch for the scalar scores instead).

        Resilience: the call runs through the ``reward.call`` fault
        point and (``resilience.reward_attempts`` > 1) a seeded retry;
        non-finite scores are surfaced loudly here — the async
        orchestrator quarantines the batch before the optimizer ever
        sees it (``resilience.quarantine_nonfinite``)."""
        if self.reward_fn is None:
            raise ValueError("no reward_fn configured")
        from orion_tpu.resilience import fault_point

        def _call():
            fault_point("reward.call")
            return self.reward_fn(result, batch)

        rcfg = self.cfg.resilience
        if rcfg.reward_attempts > 1:
            scores = rcfg.retry_policy(rcfg.reward_attempts,
                                       seed=self.cfg.seed).call(_call)
        else:
            scores = _call()
        scores = np.asarray(scores, np.float32).reshape(-1)
        n_bad = int((~np.isfinite(scores)).sum())
        if n_bad:
            import warnings

            warnings.warn(
                f"reward_fn emitted {n_bad}/{scores.size} non-finite "
                "scores — the async path quarantines this batch; the "
                "sync path would feed them to the update step",
                stacklevel=2)
        return scores

    def prepare_prompts(self, batch: dict):
        """(prompt_ids, prompt_lens, meta) — group trainers (GRPO/RLOO/
        Online-DPO) repeat each prompt ``cfg.group_size`` times; PPO has
        no group axis.  Runs host-side (rollout worker in async mode)."""
        k = getattr(self.cfg, "group_size", 1)
        ids = np.asarray(batch["prompt_ids"])
        lens = np.asarray(batch["prompt_lens"])
        meta = {key: np.asarray(v) for key, v in batch.items()
                if key not in ("prompt_ids", "prompt_lens")}
        if k > 1:
            ids = np.repeat(ids, k, axis=0)
            lens = np.repeat(lens, k, axis=0)
            meta = {key: np.repeat(v, k, axis=0) for key, v in meta.items()}
        return ids, lens, meta

    def behavior_logprobs(self, result: GenerationResult) -> jnp.ndarray:
        """old_logprobs for the importance ratio.

        Sync mode: recomputed under the *current* training graph, so the
        clipped ratio is exactly 1 on the first epoch (no sampler/
        trainer drift in the objective).  Async mode: the engine's
        *sampling-distribution* logprobs — temperature/top-k/top-p
        applied — because that tempered/truncated distribution is the
        behavior policy the tokens were actually drawn from; using the
        raw policy logprob would bias the off-policy correction whenever
        temperature != 1 or truncation is active (SURVEY.md §3b).
        ``result.policy_logprobs`` (raw) stays available for diagnostics.
        """
        if self.cfg.async_mode:
            return result.logprobs
        T = result.completions.shape[1]
        lp, _ = self._jit_logprobs(
            self.state.params, result.sequences, result.prompt_lens,
            max_new=T, **self._trace_kw(result))
        return lp

    def build_experience(self, result: GenerationResult, scores,
                         host: Optional[GenerationResult] = None):
        """(experience dict, stats dict) from a finished generation.

        ``result`` — device (or host, in async mode) arrays for the
        jitted experience math; ``scores`` — host np [B]; ``host`` — the
        one-fetch host copy for stats (falls back to ``result``).
        Algorithm-specific; must not generate (async mode calls it on
        the learner with a result produced by the rollout worker)."""
        raise NotImplementedError

    def make_experience(self, batch: dict):
        """Synchronous pipeline front half: prompts → generate → score →
        experience (SURVEY.md §3a).  Exactly one device→host fetch of
        the generation (plus one scalar fetch for model-based rewards);
        any stats tree staged in ``self._pending_fetch`` (the deferred
        previous-iteration stats) rides the same fetch for free."""
        from orion_tpu import obs

        # Each span is named for what it is: a *dispatch* is the host's
        # enqueue of asynchronous device work, the *fetch* (_fetch) is
        # the one place this thread blocks on the device.
        with obs.span("rollout.dispatch") as sp:
            ids, lens, meta = self.prepare_prompts(batch)
            sp.set(batch=int(ids.shape[0]), prompt_len=int(ids.shape[1]),
                   **self.engine.dispatch_attrs(ids.shape, lens,
                                                self.state.params))
            result = self.generate(
                ids, lens, group_size=getattr(self.cfg, "group_size", 1))
        pend, self._pending_fetch = self._pending_fetch, None
        fetched = self._fetch({"r": result._fields(), "p": pend})
        if self._pending_meta is not None:
            # Finalize the previous iteration NOW — before this
            # iteration's build_experience reads kl_ctl.value — so the
            # KL controller sees iteration i's KL before iteration
            # i+1's rewards are shaped, exactly like the eager path.
            meta_p, self._pending_meta = self._pending_meta, None
            self._finalize_iteration(meta_p, fetched["p"],
                                     end=meta_p["end"])
        host = GenerationResult(**fetched["r"])
        # what the update span and the row say of the model's forward
        # over this batch, from the lengths the fetch brought
        shape = (host.prompt_lens, host.sequences.shape[1],
                 host.completions.shape[1])
        self._update_attrs = update_attrs(self.cfg.model, host.total_lens,
                                          *shape)
        scores = self._score_result(result, host, meta)
        with obs.span("experience.dispatch") as sp:
            streams = stream_attrs(self.cfg.model, *shape)
            if streams:     # what the rollout's forwards placed
                streams["completion_tokens"] = int(
                    np.sum(host.completion_lens))
            sp.set(**streams)
            experience, stats = self.build_experience(result, scores,
                                                      host=host)
        return experience, {**stats, **step_read_pct(host.expert_stacks)}

    def _fetch(self, tree: dict):
        """``jax.device_get(tree)`` of ``{"r": the rollout's result,
        "p": the pending update's statistics or None}`` as the span
        ``rollout.fetch``, the one place this thread blocks on the
        device, with the thread's time in it told apart: ``fetch.wait``
        ends when the rollout's results are ready on the device
        (``update_ready_us``: how far into it the pending statistics
        were; 0 without any), ``fetch.copy`` runs from there until the
        tree is numpy on the host.  Still ONE batched transfer: every
        leaf's host copy is started first (the calls return at once),
        as ``device_get`` does inside, so the statistics' copies overlap
        the rollout and nothing is enqueued behind the wait.  The
        device is idle for the copy's length every iteration: nothing
        is dispatched before it returns."""
        from orion_tpu import obs

        with obs.span("rollout.fetch") as sp:
            with obs.timed("fetch.wait") as sp_wait:
                for x in jax.tree.leaves(tree):
                    start = getattr(x, "copy_to_host_async", None)
                    if start is not None:
                        start()
                update_ready = 0.0
                if tree["p"] is not None:
                    jax.block_until_ready(tree["p"])
                    update_ready = sp_wait.elapsed()
                jax.block_until_ready(tree["r"])
                sp_wait.set(update_ready_us=round(update_ready * 1e6))
            with obs.timed("fetch.copy") as sp_copy:
                fetched = jax.device_get(tree)
                nbytes = sum(int(getattr(x, "nbytes", 0))
                             for x in jax.tree.leaves(fetched))
            sp.set(bytes=nbytes,
                   **step_read_pct(fetched["r"].get("expert_stacks")))
        self._fetch_s = (sp_wait.duration, sp_copy.duration)
        return fetched

    def _epochs_fn(self, state: TrainState, experience, idx_mat):
        """All epochs×minibatches as ONE program: lax.scan threads the
        TrainState through every minibatch update.  One dispatch, one
        H2D (idx_mat), one D2H (stacked stats) per update_epochs call —
        per-minibatch host round trips leave the device idle between
        minibatches."""
        return jax.lax.scan(
            lambda st, idx: self._update_fn(st, experience, idx),
            state, idx_mat)

    def _jit_update(self, fn, states):
        """jit of an update program ``fn(*states, experience,
        idx_mat) -> (*states, stats)``: the TrainStates are donated and
        come back under their own shardings."""
        return jax.jit(
            fn, donate_argnums=tuple(range(len(states))),
            out_shardings=(*map(state_out_shardings, states), None))

    def _update_program(self):
        """(jitted program, TrainStates it takes first) of the update
        that :meth:`_run_epochs` dispatches."""
        return self._update_jit, (self.state,)

    def _choose_remat_keep(self, experience, idx_mat) -> None:
        """``model.remat`` recomputes what does not fit: which of the
        blocks' tagged tensors (models/transformer.py, ``REMAT_TAGS``)
        the update keeps follows from the bytes the device has free
        now, less what the update takes with nothing kept (its program
        compiled for that reading; warm, from the compile cache), less
        a margin.  Taken once, before the update first runs: where
        nothing fits the program compiled for the reading is the update,
        else it is traced once more with the names to keep, and there
        is one update program from then on.  A device that reports
        nothing (the CPU) gives no budget, and nothing is kept."""
        from orion_tpu import obs

        self._remat_info = info = {
            "remat_kept": "", "remat_kept_bytes": 0, "remat_budget_bytes": 0}
        if not self.cfg.model.remat:
            return
        # a phase of set-up: the compile for the reading, and the
        # clear_cache() that makes the update trace again
        with obs.setup_phase("setup.remat_probe"):
            self._probe_remat_keep(info, experience, idx_mat)

    def _probe_remat_keep(self, info: dict, experience, idx_mat) -> None:
        free = _device_free_bytes(self.state.params)
        if free is None:
            return
        program, states = self._update_program()
        mem = program.lower(
            *states, experience, idx_mat).compile().memory_analysis()
        # what the runtime sets aside to run it: the program's peak
        # beyond its arguments (the donated state is updated in place)
        need = (mem.peak_memory_in_bytes - mem.argument_size_in_bytes
                + mem.generated_code_size_in_bytes)
        budget = max(0, free - need - _REMAT_MARGIN_BYTES)
        seqs = [v for k, v in experience.items() if k.endswith("sequences")]
        seq_len = max(v.shape[1] for v in seqs)
        if self.cfg.model.block_length:
            # the update's forward goes over the trace's whole row
            new = next(v.shape[1] for k, v in experience.items()
                       if k.endswith("mask"))
            seq_len = trace_row_length(self.cfg.model, seq_len, new)
        tags = remat_tag_bytes(
            self.cfg.model, rows=idx_mat.shape[1] * len(seqs),
            seq_len=seq_len, lane=128)
        self._remat_keep = remat_keep(tags, budget)
        if self._remat_keep:
            program.clear_cache()    # traced with nothing kept
        info.update(
            # "+" and no comma: the profiler encodes a span's attributes
            # as name#k=v,k=v# and cuts a value at its first comma
            remat_kept="+".join(self._remat_keep), remat_budget_bytes=budget,
            remat_kept_bytes=sum(b for t, b in tags
                                 if t in self._remat_keep))

    def _run_epochs(self, experience, idx_mat):
        """Dispatch the scanned epoch program; PPO (extra critic state)
        overrides this hook.  Returns stacked per-minibatch stats."""
        self.state, stats = self._jit_epochs(self.state, experience, idx_mat)
        return stats

    def update_epochs(self, experience: Dict[str, jnp.ndarray],
                      defer: bool = False) -> dict:
        """num_epochs passes of shuffled minibatches (hot loop #2).
        ``defer=True`` (sync train loop) returns the stacked
        per-minibatch DEVICE stats without fetching — the fetch rides
        the next iteration's generation round-trip."""
        B = int(experience["prompt_lens"].shape[0])
        mb = self.cfg.minibatch_size
        assert B % mb == 0, f"batch {B} not divisible by minibatch {mb}"
        perms = np.stack([self._np_rng.permutation(B)
                          for _ in range(self.cfg.num_epochs)])
        # explicit H2D put: stays legal under TrainConfig.transfer_guard
        # ("disallow" only rejects IMPLICIT transfers)
        idx_mat = jax.device_put(perms.reshape(-1, mb).astype(np.int32))
        if self._remat_info is None:
            self._choose_remat_keep(experience, idx_mat)
        stats = self._run_epochs(experience, idx_mat)
        if defer:
            return stats
        host = jax.device_get(stats)  # ONE batched transfer
        return {k: float(np.mean(v)) for k, v in host.items()}

    def _on_host_stats(self, stats: dict, n_samples: int) -> None:
        """Hook: called by the deferred-stats pipeline once an
        iteration's stats land on host (PPO updates its KL controller
        here — same position in the update order as the eager path:
        always before the NEXT iteration's build_experience)."""

    def sync_weights(self) -> None:
        """Trainer → rollout weight sync (SURVEY.md §2 #11).  Sync mode:
        the engine shares the mesh, so this is a reference swap; the
        async orchestrator overrides this with the ICI broadcast."""
        self.engine.load_weights(self.state.params)

    # ------------------------------------------------------------------
    # held-out evaluation (TrainConfig.eval_every)
    # ------------------------------------------------------------------
    def evaluate(self, eval_iter: Iterator[dict],
                 n_batches: Optional[int] = None) -> dict:
        """Generate + score on held-out prompts; NO parameter update.

        Uses a dedicated RNG stream (seed ⊕ global_iter) so running (or
        skipping) evaluation never perturbs the training trajectory —
        ``next_rng`` is untouched.  Returns eval_-prefixed scalar stats.
        """
        n_batches = (self.cfg.eval_batches if n_batches is None
                     else n_batches)
        if n_batches < 1:
            raise ValueError(
                f"eval needs >= 1 batch, got eval_batches={n_batches} "
                "(disable evaluation with eval_every=0, not "
                "eval_batches=0)")
        rng = jax.random.fold_in(
            jax.random.key(self.cfg.seed + 424242), self.global_iter)
        rewards, lens = [], []
        for i in range(n_batches):
            batch = next(eval_iter)
            ids, plens, meta = self.prepare_prompts(batch)
            rng, sub = jax.random.split(rng)
            result = self.generate(
                ids, plens, rng=sub,
                group_size=getattr(self.cfg, "group_size", 1))
            host = result.to_host()
            scores = self._score_result(result, host, meta)
            rewards.append(np.asarray(scores, np.float32))
            lens.append(np.asarray(host.completion_lens, np.float32))
        rewards = np.concatenate(rewards)
        lens = np.concatenate(lens)
        return {
            "eval_reward_mean": float(rewards.mean()),
            "eval_reward_std": float(rewards.std()),
            "eval_completion_len_mean": float(lens.mean()),
            "eval_n_samples": int(rewards.shape[0]),
        }

    def _should_eval(self, eval_iter) -> bool:
        """THE eval-schedule predicate — used by both _maybe_evaluate
        and the deferred-stats train loop (which must flush pending
        stats before an eval so the logged series stays ordered); a
        schedule change edits exactly one place."""
        return bool(eval_iter is not None and self.cfg.eval_every and
                    self.global_iter % self.cfg.eval_every == 0)

    def _maybe_evaluate(self, eval_iter) -> None:
        """train()-loop hook: run + log held-out eval on schedule."""
        if not self._should_eval(eval_iter):
            return
        stats = self.evaluate(eval_iter)
        stats["iteration"] = self.global_iter
        self.metrics_history.append(stats)
        if self.writer is not None:
            self.writer.write(self.global_iter, stats)
        if self.cfg.log_every:
            print(f"[orion-tpu] eval@{self.global_iter} "
                  f"reward={stats['eval_reward_mean']:.4g} "
                  f"len={stats['eval_completion_len_mean']:.1f}",
                  flush=True)

    # ------------------------------------------------------------------
    # checkpoint/resume (SURVEY.md §2 #17)
    # ------------------------------------------------------------------
    def _extra_state(self, prompt_iter=None, data_state=None,
                     eval_iter=None) -> dict:
        extra = {
            "global_iter": self.global_iter,
            "rng": np.asarray(jax.random.key_data(self._rng)).tolist(),
            "np_rng": _np_state_to_json(self._np_rng.get_state()),
        }
        kl_ctl = getattr(self, "kl_ctl", None)
        if kl_ctl is not None:
            extra["kl_coef"] = float(kl_ctl.value)
        if data_state is not None:
            # Pre-snapshotted cursor (async mode: taken on the rollout
            # thread, the iterator's only consumer).
            extra["data"] = data_state
        elif prompt_iter is not None and hasattr(prompt_iter, "state"):
            extra["data"] = prompt_iter.state()
        if eval_iter is not None and hasattr(eval_iter, "state"):
            extra["eval_data"] = eval_iter.state()
        return extra

    def save_checkpoint(self, prompt_iter=None, data_state=None,
                        eval_iter=None, wait: bool = False) -> None:
        """``wait=True`` blocks until the write lands — the preemption
        path's guarantee that exit-0 cannot race the async writer."""
        if self.ckpt is None:
            raise ValueError("configure checkpoint_dir + checkpoint_every")
        self.ckpt.save(self.global_iter, self.state,
                       critic_state=getattr(self, "critic_state", None),
                       extra=self._extra_state(prompt_iter, data_state,
                                               eval_iter),
                       wait=wait)

    def resume(self, prompt_iter=None, eval_iter=None) -> bool:
        """Restore the latest checkpoint if one exists.  Returns True if
        training state was restored."""
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return False
        out = self.ckpt.restore(
            state_template=self.state,
            critic_template=getattr(self, "critic_state", None))
        # Orbax-assembled buffers are not safe to feed into multi-device
        # XLA computations while another thread (the async rollout
        # worker) is dispatching: on CPU backends this segfaults
        # natively inside the first device_put/jit that touches them.
        # A jitted on-device copy re-materialises every leaf as an
        # XLA-allocated array with the same sharding; a host round-trip
        # also works but costs a full transfer on real TPUs.
        _recopy = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))
        self.state = _recopy(out["state"])
        jax.block_until_ready(jax.tree_util.tree_leaves(self.state))
        if "critic_state" in out and out["critic_state"] is not None:
            self.critic_state = _recopy(out["critic_state"])
            jax.block_until_ready(
                jax.tree_util.tree_leaves(self.critic_state))
        extra = out.get("extra") or {}
        self.global_iter = int(extra.get("global_iter", 0))
        if "rng" in extra:
            self._rng = jax.random.wrap_key_data(
                jnp.asarray(extra["rng"], jnp.uint32))
        if "np_rng" in extra:
            self._np_rng.set_state(_np_state_from_json(extra["np_rng"]))
        if "kl_coef" in extra and getattr(self, "kl_ctl", None) is not None:
            self.kl_ctl.value = float(extra["kl_coef"])
        if "data" in extra and prompt_iter is not None and \
                hasattr(prompt_iter, "load_state"):
            prompt_iter.load_state(extra["data"])
        if "eval_data" in extra and eval_iter is not None and \
                hasattr(eval_iter, "load_state"):
            eval_iter.load_state(extra["eval_data"])
        self.sync_weights()
        return True

    # ------------------------------------------------------------------
    def train(self, prompt_iter: Iterator[dict],
              num_iterations: Optional[int] = None,
              eval_iter: Optional[Iterator[dict]] = None) -> list:
        """The outer loop (SURVEY.md §3a).

        ``num_iterations`` means "run this many more"; without it the
        horizon is ``cfg.total_iterations`` *total*, counted by
        ``global_iter`` — so a resumed run executes only the remaining
        iterations and LR schedules stay on their decay horizon.
        ``eval_iter``: held-out prompt stream for the cfg.eval_every
        evaluation loop (launch.py builds it from data.eval_split).
        """
        from orion_tpu import obs

        if num_iterations is not None:
            n = num_iterations
        else:
            n = max(0, self.cfg.total_iterations - self.global_iter)
        prof = _ProfileWindow(self.cfg)
        # Deferred-stats pipeline: iteration i dispatches its update and
        # immediately starts iteration i+1's generation; i's stats are
        # fetched as a free rider on i+1's generation fetch.  Each
        # iteration blocks on exactly one device round-trip, and the
        # device never idles waiting for a stats fetch.  The KL
        # controller update keeps its eager-path position (before the
        # next build_experience).
        from orion_tpu.analysis.runtime_guards import guard_scope

        from orion_tpu.resilience import preemption_requested

        pending = None
        self._defer_stats = True
        usage = resource.getrusage(resource.RUSAGE_THREAD)
        try:
            for it in range(n):
                # Preemption (resilience.preemption): the in-flight
                # step finished — flush its stats, checkpoint through
                # the retried-save path (waited: exit-0 must not race
                # the async writer), and stop cleanly.
                if preemption_requested():
                    if pending is not None:
                        fetched = jax.device_get(pending["dev"])
                        self._finalize_iteration(pending, fetched)
                        pending = None
                    if self.ckpt is not None:
                        self.save_checkpoint(prompt_iter,
                                             eval_iter=eval_iter,
                                             wait=True)
                    break
                prof.step(it)
                # Every stamp of a metrics row is the start or the end
                # of a span (obs.timed measures with tracing off), so
                # all differences are taken on one clock.  An iteration
                # is stamped on four: the wall, this thread's CPU time,
                # its collector's seconds and its compile clock
                # (_stamp).  The batch
                # fetch is a sibling BEFORE train.iteration, not its
                # child: whoever starts or stops a profiler from inside
                # the iterator then cuts this small span, and the
                # window holds whole train.iteration spans.
                gc_begin = obs.gc_totals()
                compiled = obs.compile_totals()
                with obs.timed("data.next_batch", it=it) as sp_data:
                    batch = next(prompt_iter)
                begin = _stamp(sp_data, gc_begin, compiled)
                if self._setup_begins is not None and \
                        len(self._setup_begins) < 8:
                    self._setup_begins.append((begin[0], compiled))
                with obs.span("train.iteration", it=it) as sp_it:
                    gc_it = obs.gc_totals()
                    if pending is not None:
                        self._pending_fetch = pending["dev"]
                        # steady-state wall attribution: iteration i
                        # ends where iteration i+1 begins.
                        # make_experience finalizes the pending
                        # iteration right after the batched fetch
                        # (before build_experience reads the KL
                        # coefficient).
                        pending["end"] = begin
                        self._pending_meta = pending
                        pending = None
                    with guard_scope(self.cfg.transfer_guard), \
                            jax.named_scope("experience"), \
                            obs.timed("experience", it=it):
                        experience, exp_stats = self.make_experience(batch)
                    with guard_scope(self.cfg.transfer_guard), \
                            jax.named_scope("update"), \
                            obs.span("update", it=it) as sp_upd:
                        upd_dev = self.update_epochs(experience, defer=True)
                        sp_upd.set(**(self._remat_info or {}),
                                   **self._update_attrs)
                    with obs.timed("weight_sync"):
                        self.sync_weights()
                    self.global_iter += 1
                    pending = {
                        "dev": {"exp": exp_stats, "upd": upd_dev},
                        "n": int(experience["prompt_lens"].shape[0]),
                        "it": it, "giter": self.global_iter,
                        "begin": begin, "fetch_s": self._fetch_s,
                        "model": self._update_attrs,
                    }
                    # Held-out eval on schedule (generates with the
                    # freshest weights — sync_weights already ran).
                    # Eval runs BEFORE a same-step checkpoint so the
                    # saved eval cursor includes this step's eval —
                    # otherwise a resume replays it, and the resumed
                    # run's eval-reward series diverges from an
                    # uninterrupted one.
                    do_eval = self._should_eval(eval_iter)
                    do_ckpt = (self.ckpt is not None and
                               self.global_iter % self.cfg.checkpoint_every
                               == 0)
                    if do_eval or do_ckpt:
                        # Materialize this iteration's stats first —
                        # the logged series stays in order around evals
                        # (ADVICE r4) and a checkpointed KL coefficient
                        # includes this iteration's measured KL
                        # (identical to the eager path).  Costs one
                        # extra fetch on eval/checkpoint iterations
                        # only.
                        fetched = jax.device_get(pending["dev"])
                        self._finalize_iteration(pending, fetched)
                        pending = None
                    if do_eval:
                        self._maybe_evaluate(eval_iter)
                    if do_ckpt:
                        self.save_checkpoint(prompt_iter,
                                             eval_iter=eval_iter)
                    # What the wall of a long iteration went to, beside
                    # the span's own cpu_us: the collector (all
                    # generations, this thread), and the kernel's count
                    # of this thread being taken off the CPU and of its
                    # page faults that went to disk.
                    was, usage = usage, resource.getrusage(
                        resource.RUSAGE_THREAD)
                    gc_n, gc_s = obs.gc_totals()
                    compiled = obs.compile_totals().since(compiled)
                    sp_it.set(gc_us=round((gc_s - gc_it[1]) * 1e6),
                              gc_n=gc_n - gc_it[0],
                              compile_us=round(compiled.seconds * 1e6),
                              compiles=compiled.programs,
                              nivcsw=usage.ru_nivcsw - was.ru_nivcsw,
                              majflt=usage.ru_majflt - was.ru_majflt)
            if pending is not None:  # flush the last iteration's stats
                fetched = jax.device_get(pending["dev"])
                self._finalize_iteration(pending, fetched)
        except BaseException as e:
            # Forensics before the crash surfaces (no-op unless
            # cfg.obs armed the flight recorder).
            obs.flight_dump("unhandled-exception",
                            {"error": repr(e), "loop": "sync",
                             "global_iter": self.global_iter})
            raise
        finally:
            self._defer_stats = False
            self._pending_fetch = None
            self._pending_meta = None
            # The profiler stop lives in the finally: an exception
            # escaping the loop used to leave jax.profiler's trace
            # session dangling, poisoning the NEXT start_trace (the
            # obs tracer's export or a later profiled run).
            prof.stop()
        if prof.traced:
            # Surface the trace dir in the final metrics row so users
            # can find the artifact without grepping the config.
            if self.metrics_history:
                self.metrics_history[-1]["profile_dir"] = prof.dir
            if self.writer is not None:
                self.writer.write(self.global_iter,
                                  {"profile_dir": prof.dir})
        self._write_serving_stats()
        if self.ckpt is not None:
            self.ckpt.wait()
        return self.metrics_history

    def _write_serving_stats(self, engine=None) -> None:
        """Serving-telemetry summary row (continuous engine only):
        queue wait / TTFT / tok/s / occupancy histograms flow through
        MetricsWriter as p50/p95/p99 columns at the end of a train
        call.  ``engine`` lets the async orchestrator report ITS
        rollout-group engine (the one that actually served) instead of
        the trainer's sync-path engine; the pool path has no local
        engine — each worker process owns its own telemetry."""
        engine = self.engine if engine is None else engine
        stats_fn = getattr(engine, "server_stats", None)
        if stats_fn is None or self.writer is None:
            return
        stats = {f"serving_{k}": v for k, v in stats_fn().items()}
        if stats:
            self.writer.write(self.global_iter, stats)

    def _finalize_iteration(self, pending: dict, fetched: dict,
                            end: Optional[tuple] = None) -> None:
        """Materialize a deferred iteration's stats (host side): merge
        experience + update stats, run the KL-controller hook, log.
        The iteration runs from ``pending["begin"]`` to ``end``, both
        :func:`_stamp` s — in steady state ``end`` is the next
        iteration's beginning, i.e. ``samples_per_sec`` is the honest
        end-to-end rate including the deferred update's device
        execution; a flush (the stats were just fetched) passes none
        and the iteration runs up to this call.

        The row accounts for the iteration's wall ``iter_s`` (what
        ``samples_per_sec`` divides by): ``fetch_wait_s`` the thread
        waited for the device (the previous update and this rollout),
        ``fetch_copy_s`` it took the results to the host with the
        device idle, ``host_cpu_s`` is this thread's CPU time over the
        iteration (the host's WORK: a dispatch that blocks is wall and
        not this) of which ``host_gc_s`` went to the collector, and
        ``compile_s`` it traced, lowered, compiled and loaded
        ``compiles`` programs, ``cache_misses`` of them past the
        persistent cache (obs/compilewatch.py; 0 in a steady iteration).
        What is left of a long iteration the thread neither worked nor
        waited on a named thing.  A phase's device time is read from a
        profiler trace.  The first iteration that compiled nothing ends
        set-up: its row is followed by the one ``setup`` row."""
        from orion_tpu import obs

        def scal(v):
            return float(np.mean(v)) if hasattr(v, "ndim") else v

        with obs.timed("stats.finalize", it=pending["it"]) as sp:
            if end is None:
                end = _stamp(sp, obs.gc_totals(), obs.compile_totals())
            stats = {k: scal(v) for k, v in fetched["upd"].items()}
            stats.update({k: scal(v) for k, v in fetched["exp"].items()})
            self._on_host_stats(stats, pending["n"])
            iter_s, host_cpu_s, host_gc_s = (
                b - a for a, b in zip(pending["begin"][:3], end))
            compiled = end[3].since(pending["begin"][3])
            iter_s = max(iter_s, 1e-9)
            stats.update({
                "iteration": pending["it"],
                "iter_s": iter_s,
                "fetch_wait_s": pending["fetch_s"][0],
                "fetch_copy_s": pending["fetch_s"][1],
                "host_cpu_s": host_cpu_s,
                "host_gc_s": host_gc_s,
                "compile_s": compiled.seconds,
                "compiles": compiled.programs,
                "cache_misses": compiled.misses,
                "samples_per_sec": pending["n"] / iter_s,
                **(self._remat_info or {}), **pending["model"],
            })
            sp.set(**{k: v for k, v in stats.items()
                      if k.startswith(("moe_", "ut_"))})
            self.metrics_history.append(stats)
            if self.writer is not None:
                # giter: the global counter at dispatch time — monotone
                # across resumed runs (a loop-local index would rewrite
                # steps 1..n of the metrics log after every resume).
                self.writer.write(pending["giter"], stats)
            if self.cfg.log_every and \
                    pending["it"] % self.cfg.log_every == 0:
                self.log(stats)
            if self._setup_begins is not None and not compiled.seconds:
                self._write_setup_row(pending)

    def _write_setup_row(self, pending: dict) -> None:
        """Set-up is over: ``pending`` is the first iteration in which
        this thread compiled nothing.  Where it went, once: a row of
        ``metrics.jsonl`` (``"setup": 1``, under the iteration's global
        step) and a JSON line on stderr; not an entry of
        ``metrics_history``, which holds iterations
        (obs/compilewatch.py::SetupAccount.row has the keys)."""
        from orion_tpu import obs

        begins, self._setup_begins = self._setup_begins, None
        row = obs.setup_row(
            begins, (pending["begin"][0], pending["begin"][3]))
        row["iteration"] = pending["it"]
        if self.writer is not None:
            self.writer.write(pending["giter"], row, jsonl_only=True)
        print(json.dumps(row), file=sys.stderr, flush=True)

    def log(self, stats: dict) -> None:
        keys = ("iteration", "reward_mean", "loss", "kl", "samples_per_sec")
        msg = " ".join(f"{k}={stats[k]:.4g}" for k in keys if k in stats)
        print(f"[orion-tpu] {msg}", flush=True)


class _ProfileWindow:
    """Starts/stops a jax.profiler trace over the configured iteration
    window (SURVEY.md §5 tracing).  Dumps xplane + perfetto trace under
    ``cfg.profile_dir`` — viewable in tensorboard / Perfetto (and
    mergeable next to the orion_tpu.obs span traces).

    Hardened (ISSUE 9 satellite): jax.profiler keeps ONE process-global
    trace session, so a dangling ``start_trace`` — ours after a
    mid-window crash, or another component's — used to poison every
    later window.  ``start`` failures now disable the window loudly
    instead of killing the run, ``stop`` is idempotent and never masks
    the loop's real exception, and callers run it from their
    ``finally``.  ``traced`` records whether a trace was captured so
    the trainer can surface ``profile_dir`` in the final metrics row.
    """

    def __init__(self, cfg: TrainConfig):
        self.dir = cfg.profile_dir
        self.start_it = cfg.profile_start
        self.stop_it = cfg.profile_start + cfg.profile_steps
        self.active = False
        self.traced = False

    def step(self, it: int) -> None:
        if self.dir is None or self.stop_it <= self.start_it:
            return
        if it == self.start_it and not self.active:
            try:
                jax.profiler.start_trace(self.dir)
            except Exception as e:
                # Another trace session is live (dangling from a crash
                # elsewhere, or a concurrent profiler): skip THIS
                # window loudly rather than abort the training run.
                import warnings

                warnings.warn(
                    f"profile window could not start_trace({self.dir!r})"
                    f": {e!r} — window skipped (a dangling session from "
                    "an earlier crash?)", stacklevel=2)
                self.dir = None
                return
            self.active = True
            self.traced = True
        elif it == self.stop_it and self.active:
            self.stop()

    def stop(self) -> None:
        """Idempotent; safe under an in-flight exception (a failed
        stop must never mask the loop's real error)."""
        if not self.active:
            return
        self.active = False
        try:
            jax.profiler.stop_trace()
        except Exception:  # pragma: no cover - dangling-session races
            pass


def _np_state_to_json(state: tuple) -> list:
    name, keys, pos, has_gauss, cached = state
    return [name, np.asarray(keys).tolist(), int(pos), int(has_gauss),
            float(cached)]


def _np_state_from_json(data: list) -> tuple:
    name, keys, pos, has_gauss, cached = data
    return (name, np.asarray(keys, np.uint32), int(pos), int(has_gauss),
            float(cached))
