"""Decode-step anatomy on the real chip (VERDICT r3 task #1).

Times the ppo1b decode loop piece by piece so optimization follows
measurement, not guesswork.

Timing methodology: every measurement (a) ends in a small dependent
``np.asarray`` fetch (a wait that cannot return before the device
finishes), and (b) runs the component at TWO rep counts inside one
jitted fori_loop and reports the differenced slope — the constant
dispatch and fetch overheads cancel.  Negative/noisy slopes mean "too
small to measure" (sub-ms).

Run on the TPU box:  python scripts/profile_decode.py
Env: PROF_B (default 32), PROF_P (256), PROF_T (128).
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

B = int(os.environ.get("PROF_B", "32"))
P = int(os.environ.get("PROF_P", "256"))
T = int(os.environ.get("PROF_T", "128"))
LO, HI = 8, 40


def timed_fetch(fn, *args, n=5):
    np.asarray(fn(*args))  # warmup/compile
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        np.asarray(fn(*args))
        ts.append(time.perf_counter() - t0)  # orion: ignore[naked-timer] bench wall window, blocked above
    return float(np.median(ts))


def per_rep(make_fn, *args, label=""):
    t_lo = timed_fetch(make_fn(LO), *args)
    t_hi = timed_fetch(make_fn(HI), *args)
    slope = (t_hi - t_lo) / (HI - LO)
    print(f"{label}: {slope*1e3:8.2f} ms/step   "
          f"(lo={t_lo*1e3:.0f} ms, hi={t_hi*1e3:.0f} ms)")
    return slope


def main():
    from orion_tpu.config import ModelConfig, RolloutConfig
    from orion_tpu.models import Transformer, init_params
    from orion_tpu.models.transformer import (init_cache, make_decode_twin,
                                              maybe_unstack_for_decode)
    from orion_tpu.ops.sampling import sample_tokens
    from orion_tpu.rollout.engine import RolloutEngine

    mc = ModelConfig.pythia_1b()
    mc.max_seq_len = 512
    mc.scan_layers = True
    model = Transformer(mc)
    params = init_params(model, jax.random.key(0), mc)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    print(f"model: pythia-1b ({n_params/1e9:.2f}B), B={B} P={P} T={T}")

    # Fetch overhead (constant subtracted implicitly by differencing; shown
    # for context only).
    f0 = jax.jit(lambda x: x + 1.0)
    rtt = timed_fetch(f0, jnp.float32(1.0))
    print(f"scalar dispatch+fetch overhead: {rtt*1e3:.2f} ms")

    rc = RolloutConfig(max_prompt_len=P, max_new_tokens=T, temperature=1.0)
    engine = RolloutEngine(model, mc, rc, eos_token_id=None, pad_token_id=0)
    engine.load_weights(params)

    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(2, mc.vocab_size, (B, P)), jnp.int32)
    lens = jnp.full((B,), P, jnp.int32)

    # ---- 0. full engine generate (prefill + T steps + packing) --------
    def gen():
        r = engine.generate(ids, lens, jax.random.key(1))
        return np.asarray(r.completion_lens)  # real fetch

    gen()
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        gen()  # host-complete: gen() ends in np.asarray
        ts.append(time.perf_counter() - t0)  # orion: ignore[bench-no-block, naked-timer]
    t_gen = float(np.median(ts))
    print(f"engine.generate end-to-end: {t_gen*1e3:.0f} ms "
          f"({(t_gen - rtt)/T*1e3:.2f} ms/step upper bound after fetch overhead)")

    # ---- component setup: bf16 decode twin, dense cache ---------------
    dmodel, dcfg = make_decode_twin(model, mc)
    bf16 = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    dparams = jax.jit(lambda p: maybe_unstack_for_decode(p, mc))(bf16)
    cache0 = init_cache(dcfg, B, P + T, dtype=jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (B, P))

    @jax.jit
    def prefill(dparams, cache):
        return dmodel.apply({"params": dparams}, ids, positions, cache)

    logits0, cache = prefill(dparams, cache0)
    cache = jax.tree.map(jnp.asarray, cache)
    tok0 = jnp.argmax(logits0[:, -1], -1).astype(jnp.int32)

    # prefill timing: two chained reps vs one (differenced)
    def mk_prefill(n):
        @jax.jit
        def f(dparams, cache):
            def body(i, c):
                cache, acc = c
                lg, cache = dmodel.apply({"params": dparams}, ids,
                                         positions, cache)
                return (cache, acc + lg[:, -1, 0])
            _, acc = jax.lax.fori_loop(0, n, body,
                                       (cache, jnp.zeros((B,), jnp.float32)))
            return acc
        return f

    t_lo = timed_fetch(mk_prefill(1), dparams, cache0, n=3)
    t_hi = timed_fetch(mk_prefill(3), dparams, cache0, n=3)
    print(f"prefill ({P} toks): {(t_hi - t_lo)/2*1e3:8.1f} ms")

    # ---- 1. full decode step (model + sample + cache write) -----------
    def steps_factory(model):
        def mk(n):
            @jax.jit
            def f(params_, cache, tok, rng):
                def body(i, c):
                    cache, tok, rng, acc = c
                    pos = jnp.full((B, 1), P + i, jnp.int32)
                    logits, cache = model.apply({"params": params_},
                                                tok[:, None], pos, cache)
                    rng, sub = jax.random.split(rng)
                    nxt, lp, _ = sample_tokens(sub, logits[:, 0],
                                               temperature=1.0)
                    return (cache, nxt, rng, acc + lp)

                _, _, _, acc = jax.lax.fori_loop(
                    0, n, body, (cache, tok, rng,
                                 jnp.zeros((B,), jnp.float32)))
                return acc
            return f
        return mk

    t_step = per_rep(steps_factory(dmodel), dparams, cache, tok0,
                     jax.random.key(2), label="full decode step")

    # ---- 1b. full decode step, int8 weight-only twin ------------------
    # (the deployed rollout config: RolloutConfig.quantize_weights)
    import dataclasses as _dc

    from orion_tpu.ops.quant import quantize_params_int8

    qmodel = type(dmodel)(_dc.replace(dcfg, quantize_dense=True))
    qparams = jax.jit(quantize_params_int8)(dparams)
    per_rep(steps_factory(qmodel), qparams, cache, tok0,
            jax.random.key(2), label="full decode step (int8 weights)")

    # ---- 2. matmul stack only (every Dense + lm_head, no attention) ---
    def layer_mats(p, x):
        att = p["attn"]
        q = x @ att["q_proj"]["kernel"] + att["q_proj"]["bias"]
        k = x @ att["k_proj"]["kernel"] + att["k_proj"]["bias"]
        v = x @ att["v_proj"]["kernel"] + att["v_proj"]["bias"]
        o = q @ att["o_proj"]["kernel"] + att["o_proj"]["bias"]
        m = p["mlp"]
        h = x @ m["up_proj"]["kernel"] + m["up_proj"]["bias"]
        h = jax.nn.gelu(h)
        d = h @ m["down_proj"]["kernel"] + m["down_proj"]["bias"]
        return x + o + d + 0.0 * (k[:, :1] + v[:, :1])

    def mk_matmuls(n):
        @jax.jit
        def f(dparams, x0):
            def body(i, c):
                x, acc = c
                for li in range(mc.num_layers):
                    x = layer_mats(dparams[f"layers_{li}"], x)
                    x = x / (1.0 + jnp.abs(x).max())
                logits = x @ dparams["lm_head"]["kernel"]
                return (x, acc + logits[0, 0].astype(jnp.float32))
            _, acc = jax.lax.fori_loop(0, n, body,
                                       (x0, jnp.float32(0.0)))
            return acc
        return f

    x0 = jnp.ones((B, mc.hidden_size), jnp.bfloat16)
    t_mat = per_rep(mk_matmuls, dparams, x0, label="matmul stack + lm_head")

    # ---- 3. attention-over-cache only ---------------------------------
    H, D = mc.num_heads, mc.head_dim
    Lc = P + T

    def mk_attn(n):
        from orion_tpu.ops.attention import reference_attention_gqa

        @jax.jit
        def f(cache, q):
            def body(i, acc):
                pos = jnp.full((B, 1), P + 1, jnp.int32)
                out = 0.0
                for li in range(mc.num_layers):
                    lc = cache[li]
                    slots = jnp.arange(Lc)[None, None, :]
                    mask = slots <= pos[:, :, None]
                    o = reference_attention_gqa(
                        q + 0.001 * i, lc["k"], lc["v"], mask,
                        1.0 / D ** 0.5)
                    out = out + o
                return acc + out[:, 0, 0, 0].astype(jnp.float32)
            return jax.lax.fori_loop(0, n, body,
                                     jnp.zeros((B,), jnp.float32))
        return f

    q1 = jnp.ones((B, 1, H, D), jnp.bfloat16)
    t_att = per_rep(mk_attn, cache, q1,
                    label=f"attention over cache (L={Lc})")

    # ---- 4. sampling only ---------------------------------------------
    def mk_sample(n):
        @jax.jit
        def f(logits, rng):
            def body(i, c):
                rng, acc = c
                rng, sub = jax.random.split(rng)
                t, lp, plp = sample_tokens(sub, logits + i,
                                           temperature=1.0)
                return (rng, acc + lp)
            return jax.lax.fori_loop(
                0, n, body, (rng, jnp.zeros((B,), jnp.float32)))[1]
        return f

    lg = jnp.asarray(rs.randn(B, mc.vocab_size), jnp.float32)
    t_smp = per_rep(mk_sample, lg, jax.random.key(3),
                    label="sampling ([B,V] f32)")

    # ---- summary -------------------------------------------------------
    bw = 577e9  # measured device bandwidth (x*2 slope), not peak
    wr = 2 * n_params / bw * 1e3
    cr = (2 * B * Lc * mc.num_kv_heads * mc.head_dim * 2 *
          mc.num_layers) / bw * 1e3
    print(f"\nfloors at measured {bw/1e9:.0f} GB/s: weights {wr:.2f} ms, "
          f"full-cache read {cr:.2f} ms")
    other = t_step - t_mat - t_att - t_smp
    print(f"residual (rotary/norms/cache-write/loop): {other*1e3:.2f} ms")


if __name__ == "__main__":
    main()
