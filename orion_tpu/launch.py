"""Training + serving entrypoint (SURVEY.md §2 #16, layer map
"CLI / launch").

Usage:
  python -m orion_tpu.launch <algo> [--config cfg.yaml] [key=value ...]
  algo ∈ {ppo, grpo, rloo, online_dpo, serve}

Cross-process rollout pool (PR 10): with ``async_mode=true
resilience.pool_size=N`` (N > 0) the launcher itself spawns N rollout
worker PROCESSES — each re-execs this entrypoint with the same config
plus ``ORION_POOL_WORKER_PORT``/``_RANK`` env routing it into
:func:`run_pool_worker` — and trains through ``PoolOrchestrator``
(elastic membership, per-worker heartbeats, dead-worker discard; see
orchestration/remote.py).  ``pool_size=0`` (default) keeps async mode
on the in-process rollout thread.

Serving gateway (PR 12, ROADMAP item 1 shipped-core):
``python -m orion_tpu.launch serve [--port N] [--tenants SPEC]
[--engines N] [--rollout] [key=value ...]`` builds the continuous
engine (a fleet of them with ``--engines``; ``--rollout`` arms the
PR 18 blue/green weight-rollout coordinator) from the same config
surface (``rollout.*``, ``hf_path``/``model_preset``) through the same
engine construction the pool workers use, and fronts it with a
:class:`~orion_tpu.orchestration.gateway.ServingGateway` — remote
clients submit/stream/cancel over the framed ``ORTP`` channel, with
per-tenant QoS from ``--tenants "paid:weight=4,rate=100;free:..."``.
SIGTERM/SIGINT drain through the preemption handler (exit 0).

Examples (the five SPEC configs, BASELINE.json):
  # 5: GRPO math with rule-based reward, fully offline
  python -m orion_tpu.launch grpo data.dataset=synthetic reward=math \
      total_iterations=20
  # 1: Pythia-1B PPO on TL;DR (needs local HF caches)
  python -m orion_tpu.launch ppo model_preset=pythia_1b \
      hf_path=/path/to/pythia-1b data.dataset=tldr \
      data.tokenizer=/path/to/pythia-1b reward=model:/path/to/rm
  # 4: async decoupled rollout/learner
  python -m orion_tpu.launch grpo async_mode=true rollout_devices=4
  # PPO with the shared actor-critic trunk (1B-on-one-chip layout)
  python -m orion_tpu.launch ppo share_backbone=true \
      optimizer.mu_dtype=bfloat16 optimizer.nu_dtype=bfloat16 \
      ref_param_dtype=bfloat16 model.remat=true model.scan_layers=true
  # continuous-batching rollout engine (slot recycling, ragged lengths)
  python -m orion_tpu.launch grpo rollout.engine=continuous

Multi-host bring-up: set JAX_COORDINATOR/process env and
``jax.distributed.initialize()`` runs before mesh construction.
"""

from __future__ import annotations

import functools
import os
import sys
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from orion_tpu import obs
from orion_tpu.config import (GRPOConfig, OnlineDPOConfig, PPOConfig,
                              RLOOConfig, RolloutConfig, load_config)
from orion_tpu.data import build_prompt_iterator
from orion_tpu.data.prompts import load_tokenizer
from orion_tpu.models import (ScalarHeadModel, Transformer)
from orion_tpu.models.hf_loader import load_hf_pretrained
from orion_tpu.models.sharded import make_sharded_model
from orion_tpu.parallel.mesh import make_mesh
from orion_tpu.rewards import MathVerifierReward, ModelReward
from orion_tpu.trainers import (GRPOTrainer, OnlineDPOTrainer, PPOTrainer,
                                RLOOTrainer)
from orion_tpu.utils.platform import enable_compile_cache

ALGOS = {
    "ppo": (PPOConfig, PPOTrainer),
    "grpo": (GRPOConfig, GRPOTrainer),
    "rloo": (RLOOConfig, RLOOTrainer),
    "online_dpo": (OnlineDPOConfig, OnlineDPOTrainer),
}

_INIT_ARGS = (jnp.zeros((1, 2), jnp.int32), jnp.zeros((1, 2), jnp.int32))

# The imports are done, and the eager compile the line above costs on a
# TPU: closes ``setup.import`` (obs/compilewatch.py).
obs.setup_imported()


def _len_range(data):
    """(lo, hi) for long synthetic prompts, or None (DataConfig)."""
    if data.synthetic_max_len <= 0:
        return None
    if not 0 < data.synthetic_min_len <= data.synthetic_max_len:
        raise ValueError(
            "data.synthetic_min_len..synthetic_max_len must be a range of "
            f"positive lengths (got {data.synthetic_min_len}.."
            f"{data.synthetic_max_len})")
    return data.synthetic_min_len, data.synthetic_max_len


def _holds_compile_watch(fn):
    """``fn`` under a hold on the process's compile watch
    (obs/compilewatch.py): taken before anything is built, released
    when the process body returns or raises."""
    @functools.wraps(fn)
    def held(*args, **kw):
        with obs.install_compile_watch():
            return fn(*args, **kw)
    return held


def build_reward(cfg, tokenizer, mesh):
    spec = cfg.reward
    if spec == "math":
        # decode_fn receives ragged per-sequence token lists.
        return MathVerifierReward(tokenizer.batch_decode)
    if spec == "length":
        max_new = cfg.rollout.max_new_tokens

        def length_reward(result, meta):
            return np.asarray(result.completion_lens, np.float32) / max_new

        return length_reward
    if spec.startswith("model:"):
        # SPEC config 2: separate reward model scored as an XLA forward
        # program on the same mesh (SURVEY.md §2 #6).
        path = spec.split(":", 1)[1]
        from orion_tpu.models.hf_loader import (config_from_hf,
                                                load_hf_scalar_model)
        from transformers import AutoConfig

        rm_cfg = config_from_hf(AutoConfig.from_pretrained(path))
        rm = ScalarHeadModel(rm_cfg)
        host = load_hf_scalar_model(path, rm_cfg)
        params, _ = make_sharded_model(rm, mesh, jax.random.key(1),
                                       _INIT_ARGS, host_params=host)
        return ModelReward(rm, params)
    if spec.startswith("judge:"):
        # Generative pairwise judge (SURVEY.md §2 #2 "RM/judge"): a
        # causal LM prompted for an A/B verdict through the rollout
        # engine — requires group_size=2 sampling (Online-DPO pairs).
        if getattr(cfg, "group_size", None) != 2:
            raise ValueError(
                "reward=judge:... scores PAIRS: it requires "
                f"group_size=2, got {getattr(cfg, 'group_size', None)} "
                "(the judge compares the two completions of each "
                "prompt)")
        path = spec.split(":", 1)[1]
        from orion_tpu.models.hf_loader import config_from_hf
        from orion_tpu.rewards import JudgeReward
        from transformers import AutoConfig

        j_cfg = config_from_hf(AutoConfig.from_pretrained(path))
        judge = Transformer(j_cfg)
        host = load_hf_pretrained(path, j_cfg)
        params, _ = make_sharded_model(judge, mesh, jax.random.key(2),
                                       _INIT_ARGS, host_params=host)
        # The judge must read/write ITS OWN vocabulary: prefer the
        # tokenizer shipped with the judge checkpoint; only fall back
        # to the policy tokenizer when the vocabularies provably match
        # (a cross-family tokenizer would encode the comparison prompt
        # into the wrong ids and every verdict would be noise).
        try:
            j_tok = load_tokenizer(path)
        except (OSError, ValueError):
            j_tok = tokenizer
            if getattr(tokenizer, "vocab_size", None) is not None and \
                    tokenizer.vocab_size > j_cfg.vocab_size:
                raise ValueError(
                    f"reward=judge:{path}: judge ships no tokenizer and "
                    f"the policy tokenizer (vocab {tokenizer.vocab_size})"
                    f" does not fit the judge vocab {j_cfg.vocab_size}")
            import warnings

            # A size check cannot prove the vocabularies MATCH — a
            # cross-family tokenizer with a smaller vocab would encode
            # the comparison prompt into wrong ids and every verdict
            # would be noise.  Degrade loudly, never silently.
            warnings.warn(
                f"reward=judge:{path}: judge ships no tokenizer; "
                "reusing the POLICY tokenizer.  This is only correct "
                "when the judge shares the policy's vocabulary — a "
                "cross-family judge will produce noise verdicts.",
                stacklevel=2)
        judge_ctx = (cfg.rollout.max_prompt_len
                     + 2 * cfg.rollout.max_new_tokens + 128)
        if judge_ctx + 4 > j_cfg.max_seq_len:
            raise ValueError(
                f"reward=judge:{path}: comparison prompts need "
                f"{judge_ctx}+4 tokens of context but the judge's "
                f"max_seq_len is {j_cfg.max_seq_len}; shrink "
                "rollout.max_prompt_len/max_new_tokens or pick a "
                "longer-context judge")
        rcfg = RolloutConfig(max_prompt_len=judge_ctx,
                             max_new_tokens=4, temperature=0.0)
        return JudgeReward(judge, j_cfg, params, j_tok,
                           rollout_cfg=rcfg)
    raise ValueError(f"unknown reward spec: {spec!r}")


def build_rollout_engine(cfg, tokenizer):
    """The policy decode engine a non-learner process runs: shared by
    the pool workers (PR 10) and the serving gateway (PR 12), so both
    speak the same ``rollout.*`` config surface.  Returns (engine,
    eos_id, pad_id)."""
    from orion_tpu.rollout import RolloutEngine

    eos = getattr(tokenizer, "eos_token_id", None)
    pad = getattr(tokenizer, "pad_token_id", 0) or 0
    model = Transformer(cfg.model)
    if cfg.rollout.engine == "continuous":
        from orion_tpu.rollout.continuous import ContinuousBatchingEngine

        engine = ContinuousBatchingEngine(
            model, cfg.model, cfg.rollout, eos_token_id=eos,
            pad_token_id=pad, segment_len=cfg.rollout.segment_len)
    else:
        engine = RolloutEngine(model, cfg.model, cfg.rollout,
                               eos_token_id=eos, pad_token_id=pad)
    return engine, eos, pad


@_holds_compile_watch
def run_pool_worker(cfg, port: int, rank: int,
                    host: str = "localhost",
                    n_batches: Optional[int] = None) -> int:
    """Rollout-worker process body: a policy decode engine + reward
    scorer behind a :class:`PoolWorkerClient` generation loop.  No
    optimizer, no reference model — weights arrive from the learner
    (initial snapshot rides the HELLO ack, updates stream as WEIGHTS
    frames), experience leaves as TRAJ frames, and the protocol shape
    (staleness gate, version tags, crash-vs-leave semantics, SIGTERM
    graceful leave) lives in the client.  Reused in-process by the
    tier-1 launch smoke (threads instead of processes — the same
    harness the pool tests drive).  Returns batches sent."""
    import threading

    from orion_tpu.orchestration.remote import PoolWorkerClient
    from orion_tpu.resilience.preemption import install_handler
    from orion_tpu.trainers.base import dispatch_generate_batch

    tokenizer = load_tokenizer(cfg.data.tokenizer)
    if cfg.data.tokenizer in (None, "byte"):
        cfg.model.vocab_size = max(cfg.model.vocab_size, 260)
    engine, eos, pad = build_rollout_engine(cfg, tokenizer)
    # Model-backed rewards shard on this process's own local mesh;
    # host rewards (math/length) never touch one.
    mesh = (make_mesh(cfg.mesh)
            if cfg.reward.startswith(("model:", "judge:")) else None)
    reward_fn = build_reward(cfg, tokenizer, mesh)
    wants_device = getattr(reward_fn, "wants_device_result", False)
    # Each worker owns a disjoint prompt shard (seed-offset stream) —
    # pool mode's data contract (the learner's prompt_iter feeds only
    # the degraded sync path).
    prompt_iter = build_prompt_iterator(
        cfg.data.dataset, tokenizer, cfg.rollout_batch_size,
        cfg.rollout.max_prompt_len, split=cfg.data.split,
        seed=cfg.seed + 7919 * (rank + 1),
        use_chat_template=cfg.data.use_chat_template,
        system_prompt=cfg.data.system_prompt,
        synthetic_size=cfg.data.synthetic_size,
        synthetic_len_range=_len_range(cfg.data),
        synthetic_vocab=cfg.data.synthetic_vocab,
        data_dir=cfg.data.data_dir)
    k = int(getattr(cfg, "group_size", 1))
    # SIGTERM on a worker = graceful leave (the learner sees a LEAVE,
    # not a crash).  Signal handlers only install on the main thread —
    # the in-process test harness runs this body on a daemon thread
    # and polls nothing.
    handler = None
    if threading.current_thread() is threading.main_thread():
        handler = install_handler()

    def gen(i: int, version: int, params_host):
        batch = next(prompt_iter)
        ids = np.asarray(batch["prompt_ids"])
        lens = np.asarray(batch["prompt_lens"], np.int32)
        meta = {key: np.asarray(v) for key, v in batch.items()
                if key not in ("prompt_ids", "prompt_lens")}
        if k > 1:
            ids = np.repeat(ids, k, axis=0)
            lens = np.repeat(lens, k, axis=0)
            meta = {key: np.repeat(v, k, axis=0)
                    for key, v in meta.items()}
        params = jax.device_put(params_host)
        rng = jax.random.fold_in(
            jax.random.key(cfg.seed + 4242 + 1000003 * rank), i)
        if hasattr(engine, "generate_batch"):
            result = dispatch_generate_batch(engine, ids, lens, rng,
                                             group_size=k, params=params)
        else:
            result = engine.generate(jnp.asarray(ids),
                                     jnp.asarray(lens), rng,
                                     params=params)
        host = result.to_host()
        scores = reward_fn(result if wants_device else host, meta)
        return {"result": host._fields(),
                "scores": np.asarray(scores, np.float32)}

    client = PoolWorkerClient.from_config(
        cfg.resilience, port, host=host,
        name=f"launch-worker-{rank}", seed=cfg.seed + rank)
    return client.run(gen, n_batches=n_batches, preemption=handler)


@_holds_compile_watch
def run_serve(cfg, port: int = 0, tenant_spec: Optional[str] = None,
              host: str = "localhost", stop=None,
              on_ready=None, n_engines: int = 1,
              rollout: bool = False, gateways: int = 1) -> Any:
    """Serving-gateway process body (PR 12): the continuous engine as
    a network service.  Builds the engine through the same machinery
    the pool workers use (:func:`build_rollout_engine`), loads weights
    (HF checkpoint via ``hf_path`` or a seeded random init), fronts it
    with a :class:`ServingGateway`, and pumps until ``stop`` fires or
    SIGTERM/SIGINT arrives (graceful drain, exit 0).

    ``--engines N`` (PR 18) builds a fleet of N identical engines
    behind ONE gateway (deterministic least-pending routing);
    ``--rollout`` attaches a
    :class:`~orion_tpu.orchestration.rollout_controller.WeightRolloutCoordinator`
    so a version-tagged param push rolls through the fleet blue/green
    with zero observed downtime (``cfg.rollout_update`` knobs).

    ``--gateways N`` (PR 20) fronts the SAME engine fleet with N
    gateway replicas sharing one
    :class:`~orion_tpu.orchestration.replica.EdgeCoordinator`:
    prefix-affine routing, shared admission gates, and client
    failover across the live edge.  The primary replica pumps on this
    thread (and owns the engines while it lives); the others run
    background pumps and inherit ownership if it dies.  With an
    explicit ``--port`` the replicas listen on ``port .. port+N-1``;
    port 0 gives every replica an ephemeral port (clients learn the
    edge set from the HELLO ack / FRAME_EDGE pushes either way).

    ``on_ready(gateway)`` is the in-process harness hook (the tier-1
    smoke learns the ephemeral port from it); ``stop`` is any object
    with ``is_set()``."""
    import threading

    from orion_tpu.models import init_params
    from orion_tpu.orchestration.gateway import (ServingGateway,
                                                 parse_tenant_spec)
    from orion_tpu.resilience.preemption import install_handler

    enable_compile_cache()
    tokenizer = load_tokenizer(cfg.data.tokenizer)
    if cfg.data.tokenizer in (None, "byte"):
        cfg.model.vocab_size = max(cfg.model.vocab_size, 260)
    if cfg.rollout.engine != "continuous":
        # Streaming delivery and tenant QoS live on the continuous
        # engine's submit/step surface; serving never uses the
        # fixed-batch engine.
        cfg.rollout.engine = "continuous"
    engines = []
    for rank in range(max(1, int(n_engines))):
        eng, _eos, _pad = build_rollout_engine(cfg, tokenizer)
        engines.append(eng)
    if cfg.hf_path:
        params = load_hf_pretrained(cfg.hf_path, cfg.model)
        params = jax.device_put(params)
    else:
        params = init_params(Transformer(cfg.model),
                             jax.random.key(cfg.seed), cfg.model)
    for rank, eng in enumerate(engines):
        eng.load_weights(params)
        eng.reset_rng(jax.random.key(cfg.seed + 1 + rank))
    engine = engines[0]
    tenants = parse_tenant_spec(tenant_spec) if tenant_spec else None
    autopilot = None
    if cfg.controller.enabled:
        # Closed-loop SLO autopilot (PR 13): the gateway pump drives
        # its ticks, so the one thread that owns the engines also owns
        # every setpoint/QoS actuation.  The full fleet goes in (PR
        # 20): signals merge, actuations fan out — and with replicas,
        # the ONE shared instance is ticked by whichever replica owns
        # the engines.
        from orion_tpu.orchestration.autopilot import SLOAutopilot

        autopilot = SLOAutopilot(cfg.controller, engine=engines)
    n_gateways = max(1, int(gateways))
    edge = None
    if n_gateways > 1:
        from orion_tpu.orchestration.replica import EdgeCoordinator

        edge = EdgeCoordinator(engines)
    replicas = []
    for rank in range(n_gateways):
        rport = port + rank if port else 0
        replicas.append(ServingGateway(
            engines, port=rport, host=host, tenants=tenants,
            autopilot=autopilot, edge=edge))
    gw = replicas[0]
    if rollout:
        # Fleet weight-rollout coordinator (PR 18): ticked from the
        # engine-owning pump; a learner thread stages pushes via
        # ``gw.rollout.begin(params, version)``.  With an edge the
        # attach writes through to ``edge.rollout``, so the roll
        # survives any one replica's death.
        from orion_tpu.orchestration.rollout_controller import (
            WeightRolloutCoordinator)

        WeightRolloutCoordinator(gateway=gw, cfg=cfg.rollout_update,
                                 autopilot=autopilot)
    handler = None
    if threading.current_thread() is threading.main_thread():
        handler = install_handler()
    print(f"[serve] gateway listening on {host}:{gw.port} "
          f"(engines={len(engines)}, gateways={n_gateways}, "
          f"slots={engine.slots}, pages={engine.num_pages}, "
          f"rollout={'on' if rollout else 'off'})",
          flush=True)
    if on_ready is not None:
        on_ready(gw)
    # obs.trace=true: the ring (and its flight recorder) for this
    # serving process; its spans are written out when the serve ends.
    obs_session = obs.install_from_config(cfg)
    try:
        for rep in replicas[1:]:
            rep.start()
        gw.serve_forever(stop=stop, preemption=handler)
    finally:
        # Secondaries first: each leaves the edge gracefully and
        # forwards leftover engine work to the (still live) owner.
        for rep in reversed(replicas[1:]):
            rep.close()
        gw.close()
        if obs_session is not None:
            obs_session.uninstall()
    return gw.stats


def _require_pool_worker_routing(ranks) -> None:
    """One process per chip: raise unless every same-host pool worker
    is routed off the TPU this (learner) process holds."""
    if jax.default_backend() != "tpu" or \
            os.environ.get("ORION_POOL_WORKER_PLATFORM"):
        return
    unrouted = [r for r in ranks
                if not os.environ.get(f"ORION_POOL_WORKER_ENV_{r}")]
    if unrouted:
        raise RuntimeError(
            f"pool workers {unrouted} would inherit this process's "
            "environment and contend for the TPU it already holds "
            "(one process per chip): set ORION_POOL_WORKER_PLATFORM "
            "(e.g. cpu) or ORION_POOL_WORKER_ENV_<rank> (e.g. "
            "TPU_VISIBLE_DEVICES=<n>) to route them elsewhere")


def spawn_pool_workers(algo: str, argv: list, port: int, n: int) -> list:
    """Spawn ``n`` rollout worker processes re-execing this entrypoint
    with the same CLI args; env vars route them into
    :func:`run_pool_worker`.  Returns the Popen handles (the tier-1
    smoke monkeypatches this with the in-process thread harness).

    Device placement: children inherit the parent's environment, and
    a chip belongs to one process at a time — the learner (this
    process) already holds the local TPU, so a child that asks for it
    hangs.  Same-host workers must be pointed elsewhere with
    ``ORION_POOL_WORKER_PLATFORM`` (exported to the children as their
    ``JAX_PLATFORMS``, e.g. ``cpu``) or per-rank device isolation via
    ``ORION_POOL_WORKER_ENV_<rank>`` (``KEY=V,KEY2=V2``, e.g.
    ``TPU_VISIBLE_DEVICES``).  With the parent on a TPU and neither
    variable set, this raises BEFORE spawning anything."""
    import subprocess

    from orion_tpu.resilience import fault_point

    _require_pool_worker_routing(range(n))
    worker_platform = os.environ.get("ORION_POOL_WORKER_PLATFORM")
    procs = []
    for rank in range(n):
        # Chaos boundary: process spawn can fail in the wild (fork
        # limits, exec errors) and is also how the SLO autopilot's
        # respawn path gets exercised under an armed FaultPlan.
        fault_point("worker.spawn")
        env = dict(os.environ)
        env["ORION_POOL_WORKER_PORT"] = str(port)
        env["ORION_POOL_WORKER_RANK"] = str(rank)
        if worker_platform:
            env["JAX_PLATFORMS"] = worker_platform
        extra = os.environ.get(f"ORION_POOL_WORKER_ENV_{rank}")
        if extra:
            for kv in extra.split(","):
                key, _, val = kv.partition("=")
                env[key.strip()] = val
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "orion_tpu.launch", algo] + list(argv),
            env=env))
    return procs


def _retire_pool_worker(pool, procs: list) -> int:
    """Retire half of the elastic-capacity actuator pair (PR 17):
    GOODBYE the newest live pool member (``WorkerPool.retire_member``
    — LIFO, so the longest-warmed workers keep serving) and sweep
    already-exited children out of the reap list so a long elastic run
    does not accumulate zombie Popen handles.  The retired worker
    exits through its normal graceful path (finish in-flight batch →
    leave), so its queued trajectories stay consumable; the final
    ``_reap_pool_workers`` at shutdown waits for stragglers.  Raises
    when there is nothing to retire — the autopilot records that as a
    ``retire_failed`` event instead of silently counting a no-op as a
    scale-down."""
    wid = pool.retire_member()
    if wid is None:
        raise RuntimeError("retire requested but the pool has no live "
                           "members")
    # poll() reaps an exited child (clears the zombie) and returns
    # None for one still running — keep those for the exit reap.
    procs[:] = [p for p in procs if p.poll() is None]
    return wid


def _reap_pool_workers(procs: list, timeout: float = 60.0) -> None:
    """Wait for GOODBYE'd workers to exit; escalate to terminate/kill
    so a wedged worker can never hang the launcher's exit."""
    import subprocess

    for p in procs:
        try:
            p.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.terminate()
            try:
                p.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                p.kill()


def build_trainer(algo: str, cfg, mesh, tokenizer):
    _, trainer_cls = ALGOS[algo]
    shared = algo == "ppo" and cfg.share_backbone
    rng = jax.random.key(cfg.seed)
    host = load_hf_pretrained(cfg.hf_path, cfg.model) if cfg.hf_path else None
    if shared:
        from orion_tpu.models.heads import (ActorCriticModel,
                                            wrap_actor_critic_params)

        model = ActorCriticModel(cfg.model)
        if host is not None:
            host = wrap_actor_critic_params(host, cfg.model,
                                            jax.random.fold_in(rng, 1))
    else:
        model = Transformer(cfg.model)
    params, _ = make_sharded_model(model, mesh, rng, _INIT_ARGS,
                                   host_params=host)
    reward_fn = build_reward(cfg, tokenizer, mesh)
    eos = getattr(tokenizer, "eos_token_id", None)
    pad = getattr(tokenizer, "pad_token_id", 0) or 0
    kw = dict(reward_fn=reward_fn, eos_token_id=eos, pad_token_id=pad)
    if algo == "ppo" and not shared:
        critic = ScalarHeadModel(cfg.model)
        critic_params, _ = make_sharded_model(
            critic, mesh, jax.random.fold_in(rng, 1), _INIT_ARGS)
        return trainer_cls(cfg, model, params, critic, critic_params, **kw)
    return trainer_cls(cfg, model, params, **kw)


def _trainer(algo: str, cfg, mesh, tokenizer, prompt_iter, eval_iter):
    """The trainer, built and resumed: two phases of set-up."""
    with obs.setup_phase("setup.build_trainer"):
        trainer = build_trainer(algo, cfg, mesh, tokenizer)
    with obs.setup_phase("setup.resume"):
        trainer.resume(prompt_iter, eval_iter=eval_iter)
    return trainer


@_holds_compile_watch
def main(argv: Optional[list] = None) -> Any:
    obs.setup_begin()
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or (argv[0] not in ALGOS and argv[0] != "serve"):
        print(f"usage: python -m orion_tpu.launch "
              f"{{{'|'.join(ALGOS)}|serve}} "
              "[--config cfg.yaml] [key=value ...]", file=sys.stderr)
        raise SystemExit(2)
    algo = argv.pop(0)
    raw_argv = list(argv)  # worker processes re-exec with these
    enable_compile_cache()
    yaml_path = None
    if "--config" in argv:
        i = argv.index("--config")
        yaml_path = argv[i + 1]
        del argv[i:i + 2]
    serve_port, tenant_spec, n_engines, rollout = 0, None, 1, False
    n_gateways = 1
    if algo == "serve":
        if "--port" in argv:
            i = argv.index("--port")
            serve_port = int(argv[i + 1])
            del argv[i:i + 2]
        if "--tenants" in argv:
            i = argv.index("--tenants")
            tenant_spec = argv[i + 1]
            del argv[i:i + 2]
        if "--engines" in argv:
            i = argv.index("--engines")
            n_engines = int(argv[i + 1])
            del argv[i:i + 2]
        if "--gateways" in argv:
            i = argv.index("--gateways")
            n_gateways = int(argv[i + 1])
            del argv[i:i + 2]
        if "--rollout" in argv:
            argv.remove("--rollout")
            rollout = True
    cfg_cls, _ = ALGOS.get(algo, (GRPOConfig, None))
    with obs.setup_phase("setup.config"):
        cfg = load_config(cfg_cls, yaml_path=yaml_path, cli_args=argv)

    if algo == "serve":
        return run_serve(cfg, port=serve_port, tenant_spec=tenant_spec,
                         host=os.environ.get("ORION_SERVE_HOST",
                                             "localhost"),
                         n_engines=n_engines, rollout=rollout,
                         gateways=n_gateways)

    # Rollout-worker process (spawned by the pool branch below): the
    # env routing keeps the CLI surface unchanged — a worker re-parses
    # the exact same config and runs the generation loop instead of
    # training.
    worker_port = os.environ.get("ORION_POOL_WORKER_PORT")
    if worker_port is not None:
        return run_pool_worker(
            cfg, int(worker_port),
            int(os.environ.get("ORION_POOL_WORKER_RANK", "0")),
            host=os.environ.get("ORION_POOL_WORKER_HOST", "localhost"))

    if os.environ.get("JAX_COORDINATOR_ADDRESS"):
        with obs.setup_phase("setup.mesh"):
            jax.distributed.initialize()

    with obs.setup_phase("setup.config"):
        tokenizer = load_tokenizer(cfg.data.tokenizer)
        if cfg.data.tokenizer in (None, "byte"):
            cfg.model.vocab_size = max(cfg.model.vocab_size, 260)
        else:
            tok_vocab = len(tokenizer)
            if tok_vocab > cfg.model.vocab_size:
                # XLA gather clamps out-of-range ids silently — training on
                # garbage embeddings with no error.  Fail loudly instead.
                raise ValueError(
                    f"tokenizer vocab {tok_vocab} exceeds model.vocab_size "
                    f"{cfg.model.vocab_size}; set model_preset/hf_path or "
                    "model.vocab_size to match the tokenizer")

        prompt_iter = build_prompt_iterator(
            cfg.data.dataset, tokenizer, cfg.rollout_batch_size,
            cfg.rollout.max_prompt_len, split=cfg.data.split, seed=cfg.seed,
            use_chat_template=cfg.data.use_chat_template,
            system_prompt=cfg.data.system_prompt,
            synthetic_size=cfg.data.synthetic_size,
            synthetic_len_range=_len_range(cfg.data),
            synthetic_vocab=cfg.data.synthetic_vocab,
            data_dir=cfg.data.data_dir)
        eval_iter = None
        if cfg.eval_every:
            if cfg.eval_batches < 1:
                # Catch it HERE, not hours in at the first scheduled eval.
                raise ValueError(
                    f"eval_every={cfg.eval_every} needs eval_batches >= 1 "
                    f"(got {cfg.eval_batches}); disable eval with "
                    "eval_every=0")
            # Held-out split (synthetic: a disjoint seed stream).
            eval_iter = build_prompt_iterator(
                cfg.data.dataset, tokenizer, cfg.rollout_batch_size,
                cfg.rollout.max_prompt_len,
                split=(cfg.data.split if cfg.data.dataset == "synthetic"
                       else cfg.data.eval_split),
                seed=cfg.seed + 1000003,
                use_chat_template=cfg.data.use_chat_template,
                system_prompt=cfg.data.system_prompt,
                synthetic_size=cfg.data.synthetic_size,
                synthetic_len_range=_len_range(cfg.data),
                synthetic_vocab=cfg.data.synthetic_vocab,
                data_dir=cfg.data.data_dir)

    if cfg.async_mode and cfg.resilience.pool_size > 0:
        # Cross-process rollout pool (PR 10): the
        # launcher spawns resilience.pool_size worker processes itself
        # — each re-execs this entrypoint with the same args plus the
        # ORION_POOL_WORKER_* env routing — and trains through
        # PoolOrchestrator, which waits for that quorum, supervises
        # membership, and GOODBYEs the workers on completion.  The
        # train mesh keeps every local device (workers are separate
        # processes with their own).
        from orion_tpu.orchestration.async_orchestrator import (
            PoolOrchestrator)

        # before the trainer is built: a doomed spawn must not cost a
        # model init first
        _require_pool_worker_routing(range(cfg.resilience.pool_size))
        with obs.setup_phase("setup.mesh"):
            mesh = make_mesh(cfg.mesh)
        with mesh:
            trainer = _trainer(algo, cfg, mesh, tokenizer, prompt_iter,
                               eval_iter)
            orch = PoolOrchestrator(trainer)  # pool built from config
            procs = spawn_pool_workers(algo, raw_argv, orch.pool.port,
                                       cfg.resilience.pool_size)
            if orch.autopilot is not None:
                # Elastic respawn actuator: one more worker process
                # through the exact spawn path used at startup.  The
                # Popen handle joins the reap list so the launcher's
                # exit discipline covers controller-spawned workers
                # too.
                orch.autopilot.spawn_fn = lambda: procs.extend(
                    spawn_pool_workers(algo, raw_argv, orch.pool.port, 1))
                # Retire actuator (PR 17): the other half of elastic
                # capacity — GOODBYE one worker through the pool and
                # sweep exited Popen handles so scale-down cycles do
                # not leak zombies until launcher exit.
                orch.autopilot.retire_fn = lambda: _retire_pool_worker(
                    orch.pool, procs)
            try:
                return orch.train(prompt_iter, eval_iter=eval_iter)
            finally:
                trainer.close()
                orch.pool.shutdown(goodbye=True)
                _reap_pool_workers(procs)

    if cfg.async_mode:
        from orion_tpu.orchestration import AsyncOrchestrator, split_devices

        with obs.setup_phase("setup.mesh"):
            n_roll = cfg.rollout_devices or max(1, len(jax.devices()) // 2)
            rollout_devs, train_devs = split_devices(jax.devices(), n_roll)
            mesh = make_mesh(cfg.mesh, devices=train_devs)
        with mesh:
            trainer = _trainer(algo, cfg, mesh, tokenizer, prompt_iter,
                               eval_iter)
            orch = AsyncOrchestrator(trainer, rollout_devs)
            try:
                return orch.train(prompt_iter, eval_iter=eval_iter)
            finally:
                # Route the exit through the trainer's sinks (metrics
                # writer flush+close, obs tracer/flight recorder,
                # recompile sentinel) — crash or clean.
                trainer.close()

    with obs.setup_phase("setup.mesh"):
        mesh = make_mesh(cfg.mesh)
    with mesh:
        trainer = _trainer(algo, cfg, mesh, tokenizer, prompt_iter,
                           eval_iter)
        try:
            return trainer.train(prompt_iter, eval_iter=eval_iter)
        finally:
            trainer.close()


if __name__ == "__main__":
    main()
