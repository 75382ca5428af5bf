"""Benchmark: RLHF samples/sec (rollout + update) on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "samples/sec", "vs_baseline": N,
   "tokens_per_sec": N, "mfu": N, "compile_8b": "...",
   "median_samples_per_sec": N, "iteration_rates": [...],
   "device": {"platform": ..., "kind": ..., "count": N}}

``value`` is the wall-clock mean over ONE measured window;
``median_samples_per_sec`` is the per-iteration median and every
per-iteration rate is printed.  The bench needs a TPU: without one it
exits non-zero and prints no result — a CPU run is never published
under the chip's metric name.  Any failure (the 8B lowering leg
included) raises and exits non-zero.  One process: nothing is spawned.

The BASELINE metric (BASELINE.json) is "PPO samples/sec (rollout+update)
at 1B and 8B".  The default preset is **ppo1b**: PPO at the Pythia-1B
shape (shared-backbone critic — the layout that fits policy+ref+Adam on
one 16G chip), flash attention, remat, scanned layers, bf16 Adam
moments.  The 8B leg is a lowering-only check (AOT lowering of the full
llama3_8b update step — one chip can't hold 8B training state; the
multi-chip path is exercised by dryrun_multichip).

No published reference number is recoverable (BASELINE.json.published
== {}, see BASELINE.md), so ``vs_baseline`` is reported against the
first value this bench recorded for the SAME preset (BENCH_SELF.json),
i.e. self-improvement, 1.0 on a preset's first run.

Presets (env ORION_BENCH_PRESET): "ppo1b" (default), "small" (~320M
GRPO), "tiny".  ORION_BENCH_ITERS to change the measured iteration
count; ORION_BENCH_PROFILE=dir to dump a jax.profiler trace of the
measured window.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

# Peak dense bf16 FLOP/s of ONE chip, keyed by ``device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16).
# A device that is not in the table is an error, not a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def param_count(tree) -> int:
    import jax

    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def _length_reward(result, batch):
    # Rule-style host reward: rewards longer distinct completions.
    toks = np.asarray(result.completions)
    return np.asarray(
        [len(np.unique(t)) for t in toks], np.float32) / toks.shape[1]


def _preset():
    name = os.environ.get("ORION_BENCH_PRESET", "ppo1b")
    from orion_tpu.config import (GRPOConfig, ModelConfig, OptimizerConfig,
                                  PPOConfig)

    if name == "ppo1b":
        cfg = PPOConfig()
        cfg.model = ModelConfig.pythia_1b()
        cfg.model.max_seq_len = 512
        cfg.model.remat = True
        cfg.model.scan_layers = True
        cfg.share_backbone = True
        cfg.ref_param_dtype = "bfloat16"
        cfg.optimizer = OptimizerConfig(
            learning_rate=1e-6, mu_dtype="bfloat16", nu_dtype="bfloat16")
        cfg.rollout.max_prompt_len = 256
        cfg.rollout.max_new_tokens = 128
        # int8 decode (weights + KV cache): decode is bandwidth-bound
        # once the scatter cache write landed; measured r3 on-chip:
        # 5.13 -> 3.06 ms/step (see PERF.md).  Training math is
        # unaffected (old-logprobs recomputed under the training graph).
        cfg.rollout.quantize_weights = True
        cfg.rollout.quantize_kv = True
        # B sweep on-chip (r5, int8 KV moved the old B=48 OOM wall):
        # B=32 -> 17.35 samples/s, 48 -> 18.40, 64 -> 18.50 (plateau —
        # decode rows are ~free, the update scales linearly).  48 keeps
        # HBM headroom (B=64's 8B-compile leg took 57 s under memory
        # pressure vs 6 s at 48).
        cfg.rollout_batch_size = 48
        # mb sweep on-chip: 4 -> 1161 ms, 8 -> 960, 16 -> 875; mb=32
        # fits since int8 KV but is SLOWER (17.24 vs 18.50 at B=64).
        cfg.minibatch_size = 16
        cfg.num_epochs = 1
        cfg.kl_coef = 0.05
    elif name == "small":
        cfg = GRPOConfig()
        # ~320M llama-arch model: real MXU/HBM load, <16G HBM with
        # policy + ref + Adam state resident.
        cfg.model = ModelConfig(
            arch="llama", vocab_size=32000, hidden_size=1024,
            intermediate_size=4096, num_layers=16, num_heads=16,
            num_kv_heads=8, max_seq_len=1024)
        cfg.rollout.max_prompt_len = 128
        cfg.rollout.max_new_tokens = 128
        # B sweep on-chip (r5): 8 -> 51.4, 16 -> 59.8, 32 -> 63.3
        # samples/s (flattening); 16 balances iteration latency vs
        # throughput.
        cfg.rollout_batch_size = 16
        cfg.group_size = 4
        cfg.minibatch_size = 8
        cfg.num_epochs = 1
    else:
        cfg = GRPOConfig()
        cfg.model = ModelConfig.tiny()
        cfg.rollout.max_prompt_len = 16
        cfg.rollout.max_new_tokens = 16
        cfg.rollout_batch_size = 4
        cfg.group_size = 2
        cfg.minibatch_size = 4
        cfg.num_epochs = 1
    cfg.rollout.temperature = 1.0
    # Shape-sweep knobs (r5): decode is bandwidth-bound, so extra
    # rollout rows are nearly free until the KV pool or the update's
    # activation memory bites — int8 KV (r4) moved that wall past the
    # old B=48 OOM.  Overrides apply to any preset.
    if os.environ.get("ORION_BENCH_B"):
        cfg.rollout_batch_size = int(os.environ["ORION_BENCH_B"])
    if os.environ.get("ORION_BENCH_MB"):
        cfg.minibatch_size = int(os.environ["ORION_BENCH_MB"])
    if os.environ.get("ORION_BENCH_PAGED") == "1":
        # A/B the paged decode kernel against the dense cache at the
        # bench shape (paged KV is block-gathered by the fused Pallas
        # kernel instead of attended densely).
        cfg.rollout.paged = True
    # Staged on-chip A/B (r5): ORION_BENCH_SPEC=k turns on n-gram
    # speculative decoding for the rollout (exact in both greedy and
    # stochastic modes — see PERF.md).  Off by default until the
    # acceptance rate is measured on-chip at the bench shapes.
    spec = int(os.environ.get("ORION_BENCH_SPEC", "0"))
    if spec:
        cfg.rollout.speculative_k = spec
    return name, cfg


def build_trainer(name, cfg):
    import jax

    if name == "ppo1b":
        from orion_tpu.models import ActorCriticModel, init_params
        from orion_tpu.trainers import PPOTrainer

        model = ActorCriticModel(cfg.model)
        params = init_params(model, jax.random.key(0), cfg.model)
        return PPOTrainer(cfg, model, params, reward_fn=_length_reward,
                          eos_token_id=1, pad_token_id=0)
    from orion_tpu.models import Transformer, init_params
    from orion_tpu.trainers import GRPOTrainer

    model = Transformer(cfg.model)
    params = init_params(model, jax.random.key(0), cfg.model)
    return GRPOTrainer(cfg, model, params, reward_fn=_length_reward,
                       eos_token_id=1, pad_token_id=0)


def flops_per_sample(n_params, cfg, mean_new: float) -> float:
    """Model-FLOPs accounting (MFU convention: remat recompute NOT
    counted).  2N per token forward, 6N per token fwd+bwd; attention
    term included (small at these lengths)."""
    m = cfg.model
    P = cfg.rollout.max_prompt_len
    seq = P + cfg.rollout.max_new_tokens
    att_tok = 4.0 * m.num_layers * m.head_dim * m.num_heads * seq
    fwd_tok = 2.0 * n_params + att_tok
    # rollout: prefill over P + one fwd per generated token
    rollout = fwd_tok * (P + mean_new)
    # experience forwards over the packed sequence:
    #   shared-backbone PPO: fused old_lp+values pass + ref pass = 2
    #   GRPO: old_lp pass + ref pass = 2
    experience = 2 * fwd_tok * seq
    # update: fwd+bwd per epoch (group trainers update every sample too)
    update = cfg.num_epochs * 3 * fwd_tok * seq
    return rollout + experience + update


def lower_8b_check() -> str:
    """AOT-lower the FULL llama3_8b shared-backbone PPO update step
    (tracing+lowering only — no 8B buffers are allocated).  Returns a
    short status string for the bench JSON.  The multi-chip sharded
    variant (with .compile()) runs in __graft_entry__.dryrun_multichip;
    both share orion_tpu.utils.compile_check."""
    from orion_tpu.utils.compile_check import lower_8b_update

    return lower_8b_update(mesh=None, compile=False)


def main() -> None:
    import jax

    from orion_tpu.utils.platform import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py needs a TPU; jax found {dev.platform!r} "
            f"({dev.device_kind}).  A CPU run is not a benchmark.")
    if dev.device_kind not in PEAK_BF16_FLOPS:
        raise SystemExit(
            f"bench.py has no peak FLOP/s for device_kind "
            f"{dev.device_kind!r}; add it to PEAK_BF16_FLOPS with its "
            "source")
    enable_compile_cache()

    name, cfg = _preset()
    trainer = build_trainer(name, cfg)
    n_params = param_count(trainer.state.params)

    rs = np.random.RandomState(0)
    B, P = cfg.rollout_batch_size, cfg.rollout.max_prompt_len

    def batch():
        return {
            "prompt_ids": rs.randint(
                2, cfg.model.vocab_size, (B, P)).astype(np.int32),
            "prompt_lens": np.full((B,), P, np.int32),
        }

    group = getattr(cfg, "group_size", 1) if name != "ppo1b" else 1
    n_samples = B * group
    # Warmup iteration triggers all compiles (prefill, decode loop,
    # logprob recompute, update); measured iterations reuse the cache.
    trainer.train(iter([batch()]), num_iterations=1)

    # 12 iterations: the r3 deferred-stats pipeline overlaps iteration
    # i's update with i+1's generation, so the last iteration always
    # pays an un-overlapped flush — more iterations = closer to the
    # steady-state rate a real run sees (r5 on-chip: the flush is
    # ~0.7 s once per run; at 6 iters it shaved ~5% off the mean).
    iters = int(os.environ.get("ORION_BENCH_ITERS", "12"))
    prof_dir = os.environ.get("ORION_BENCH_PROFILE")
    if prof_dir:
        jax.profiler.start_trace(prof_dir)
    t0 = time.perf_counter()
    hist = trainer.train(iter([batch() for _ in range(iters)]),
                         num_iterations=iters)
    jax.block_until_ready(trainer.state.params)
    dt = time.perf_counter() - t0  # orion: ignore[naked-timer] the bench wall window IS the metric (params blocked above)
    if prof_dir:
        jax.profiler.stop_trace()
    hist = list(hist[-iters:])
    rates = [float(x["samples_per_sec"]) for x in hist
             if "samples_per_sec" in x]
    # Primary value is WALL-CLOCK (comparable with BENCH_SELF); the
    # median per-iteration rate is reported alongside.
    value = n_samples * iters / dt
    median_rate = float(np.median(rates)) if rates else value

    mean_new = float(np.mean(
        [h.get("completion_len_mean", cfg.rollout.max_new_tokens)
         for h in hist]))
    toks_per_sec = value * mean_new
    algo = "ppo" if name == "ppo1b" else "grpo"
    fps = flops_per_sample(n_params, cfg, mean_new)
    mfu = value * fps / PEAK_BF16_FLOPS[dev.device_kind]

    compile_8b = ""
    if name == "ppo1b" and os.environ.get("ORION_BENCH_8B", "1") != "0":
        compile_8b = lower_8b_check()

    self_path = os.path.join(os.path.dirname(__file__), "BENCH_SELF.json")
    key = f"{algo}_samples_per_sec_{name}"
    # Shape overrides define a DIFFERENT workload: give them their own
    # baseline key so a sweep can neither poison the canonical
    # preset's BENCH_SELF entry nor report vs_baseline across shapes.
    if os.environ.get("ORION_BENCH_B") or os.environ.get("ORION_BENCH_MB"):
        key += f"_B{cfg.rollout_batch_size}_mb{cfg.minibatch_size}"
    base = {}
    if os.path.exists(self_path):
        with open(self_path) as f:
            base = json.load(f)
    if key not in base:
        base[key] = value
        with open(self_path, "w") as f:
            json.dump(base, f, indent=1)
    vs = value / base[key] if base[key] else 1.0

    # Per-iteration rate distribution via the obs Histogram machinery
    # (ISSUE 9): the p50/p95 spread is readable off the JSON line.
    from orion_tpu.utils.metrics import Histogram

    rate_hist = Histogram()
    for r in rates:
        rate_hist.record(r)

    out = {
        "metric": f"{algo.upper()} samples/sec (rollout+update), "
                  f"preset={name} ({n_params/1e9:.2f}B params, "
                  f"epochs={cfg.num_epochs}), {dev.platform}",
        "value": round(value, 4),
        "unit": "samples/sec",
        "vs_baseline": round(vs, 4),
        "tokens_per_sec": round(toks_per_sec, 1),
        "mfu": round(mfu, 4),
        "median_samples_per_sec": round(median_rate, 4),
        "iteration_rates": [round(r, 2) for r in rates],
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "rollout_batch_size": cfg.rollout_batch_size,
        "minibatch_size": cfg.minibatch_size,
    }
    out.update({k: round(float(v), 3)
                for k, v in rate_hist.summary("iter_samples_per_sec").items()})
    if compile_8b:
        out["compile_8b"] = compile_8b
    print(json.dumps(out))


if __name__ == "__main__":
    main()
