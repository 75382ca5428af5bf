"""Shared-prefix group admission A/B (VERDICT r4 missing #3 / next #2).

GRPO-style workload: B unique prompts × k completions each.  Baseline
admits the k clones as independent requests (k full prefills + k×
prompt pages); the grouped path prefills each unique prompt once and
shares its fully-filled prompt pages across the clones.

Shape chosen so PREFILL dominates (long prompts, short completions) —
that is the component this optimization targets; the ragged decode
story is scripts/bench_ragged.py's job.

Runs on whatever backend jax has and says which.  A CPU run gives
counts (prefills, pages), never a speed; only a chip run times prefill.

Run: python scripts/bench_group_prefill.py
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import numpy as np

B = int(os.environ.get("GP_B", "8"))        # unique prompts
K = int(os.environ.get("GP_K", "8"))        # completions per prompt
P = int(os.environ.get("GP_P", "256"))      # prompt length
T = int(os.environ.get("GP_T", "16"))       # completion budget
REPS = int(os.environ.get("GP_REPS", "3"))


def build_engine(mc, model, share: bool):
    from orion_tpu.config import RolloutConfig
    from orion_tpu.rollout.continuous import ContinuousBatchingEngine

    rcfg = RolloutConfig(
        max_prompt_len=P, max_new_tokens=T, temperature=1.0,
        page_size=32, max_batch_size=B * K, segment_len=8,
        group_prefix_sharing=share)
    return ContinuousBatchingEngine(model, mc, rcfg, eos_token_id=None,
                                    segment_len=8)


def instrument_prefill(eng):
    """Wrap the engine's jitted prefill with a blocking wall-clock
    accumulator.  On the CPU harness the decode segments run the paged
    Pallas kernel in INTERPRET mode and dominate end-to-end time by an
    order of magnitude, hiding exactly the component this A/B targets;
    timing the prefill dispatch (blocked to completion) isolates it.
    The forced block slightly overstates prefill cost for both arms
    equally — the comparison stays fair."""
    inner = eng._jit_prefill
    acc = {"s": 0.0, "calls": 0}

    def timed(*a, **kw):
        t0 = time.perf_counter()
        pools, state = inner(*a, **kw)
        jax.block_until_ready(state)
        acc["s"] += time.perf_counter() - t0  # orion: ignore[naked-timer] bench wall window, blocked above
        acc["calls"] += 1
        return pools, state

    eng._jit_prefill = timed
    return acc


def build_model():
    """GP_MODEL=tiny (default, CPU harness) or pythia1b (on-chip: the
    tiny model's prefill programs finish in microseconds, so it times
    dispatch overhead, not prefill compute — the compute-bound
    comparison needs a real model)."""
    from orion_tpu.config import ModelConfig

    name = os.environ.get("GP_MODEL", "tiny")
    if name == "pythia1b":
        mc = ModelConfig.pythia_1b()
        mc.dtype = "bfloat16"
    else:
        mc = ModelConfig.tiny(vocab_size=1024, hidden_size=128,
                              intermediate_size=512, num_layers=2,
                              num_heads=4, num_kv_heads=4,
                              dtype="float32")
    mc.max_seq_len = max(mc.max_seq_len, P + T)
    return mc


def run(eng, params, prompts, lens, tag):
    acc = instrument_prefill(eng)
    # warm-up compiles, then timed reps
    eng.generate_batch(prompts, lens, jax.random.key(0), params=params,
                       group_size=K)
    times = []
    pre = []
    for r in range(REPS):
        acc["s"] = 0.0
        t0 = time.perf_counter()
        out = eng.generate_batch(prompts, lens, jax.random.key(r + 1),
                                 params=params, group_size=K)
        jax.block_until_ready(out.completions)
        times.append(time.perf_counter() - t0)  # orion: ignore[naked-timer] bench wall window, blocked above
        pre.append(acc["s"])
        assert out.completions.shape[0] == B * K
    best, best_pre = min(times), min(pre)
    calls = acc["calls"] // (REPS + 1)  # per-generate_batch average
    print(f"  {tag:24s} total {best*1e3:8.1f} ms   prefill "
          f"{best_pre*1e3:8.1f} ms / {calls} call(s)  "
          f"({B}x{K} prompts, P={P}, T={T})", flush=True)
    return best, best_pre


def main():
    from orion_tpu.models import Transformer, init_params

    mc = build_model()
    model = Transformer(mc)
    params = init_params(model, jax.random.key(0), mc)
    rs = np.random.RandomState(0)
    lens = rs.randint(P // 2, P + 1, size=B).astype(np.int32)
    prompts = np.zeros((B, P), np.int32)
    for i in range(B):
        prompts[i, : lens[i]] = rs.randint(2, mc.vocab_size, lens[i])

    print(f"[group-prefill A/B] backend={jax.devices()[0].platform}",
          flush=True)
    t_solo, p_solo = run(build_engine(mc, model, False), params, prompts,
                         lens, "repeated (baseline)")
    t_grp, p_grp = run(build_engine(mc, model, True), params, prompts,
                       lens, "shared-prefix groups")
    print(f"  prefill speedup: {p_solo / p_grp:.2f}x   "
          f"end-to-end: {t_solo / t_grp:.2f}x", flush=True)


if __name__ == "__main__":
    main()
