"""Continuous-batching engine tests (SURVEY.md §2 #5, §3c): more
requests than slots, ragged prompts, EOS retirement, page recycling —
each request's output must equal a solo run of the simple engine."""

import jax
import jax.numpy as jnp
import numpy as np

from orion_tpu.config import ModelConfig, RolloutConfig
from orion_tpu.models import Transformer, init_params
from orion_tpu.rollout import RolloutEngine
from orion_tpu.rollout.continuous import ContinuousBatchingEngine


def _setup(eos=None, max_new=10, slots=2, max_prompt=12):
    cfg = ModelConfig.tiny(dtype="float32")
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(0), cfg)
    rcfg = RolloutConfig(max_prompt_len=max_prompt, max_new_tokens=max_new,
                         temperature=0.0, page_size=4, max_batch_size=slots)
    eng = ContinuousBatchingEngine(model, cfg, rcfg, eos_token_id=eos,
                                   segment_len=4)
    solo = RolloutEngine(model, cfg,
                         RolloutConfig(max_new_tokens=max_new,
                                       temperature=0.0, paged=True,
                                       page_size=4),
                         eos_token_id=eos)
    solo.load_weights(params)
    return cfg, model, params, eng, solo


def _solo_completion(solo, ids, max_new):
    r = solo.generate(jnp.asarray(ids[None, :]),
                      jnp.asarray([len(ids)], np.int32), jax.random.key(0))
    n = int(r.completion_lens[0])
    return np.asarray(r.completions[0, :n])


def test_continuous_matches_solo_greedy():
    cfg, model, params, eng, solo = _setup()
    rng = np.random.RandomState(0)
    reqs = [(i, rng.randint(1, cfg.vocab_size, rng.randint(3, 12)))
            for i in range(7)]  # 7 requests, 2 slots
    out = eng.generate(reqs, jax.random.key(1), params)
    assert sorted(r.req_id for r in out) == list(range(7))
    for r in out:
        ids = dict(reqs)[r.req_id]
        expect = _solo_completion(solo, np.asarray(ids, np.int32), 10)
        np.testing.assert_array_equal(r.tokens, expect,
                                      err_msg=f"req {r.req_id}")


def test_continuous_eos_and_recycling():
    # eos id chosen so greedy decode hits it sometimes on a tiny model
    cfg, model, params, eng, solo = _setup(eos=5, max_new=12, slots=2)
    rng = np.random.RandomState(3)
    reqs = [(i, rng.randint(1, cfg.vocab_size, rng.randint(2, 12)))
            for i in range(6)]
    out = eng.generate(reqs, jax.random.key(2), params)
    assert sorted(r.req_id for r in out) == list(range(6))
    hit_eos = 0
    for r in out:
        ids = dict(reqs)[r.req_id]
        expect = _solo_completion(solo, np.asarray(ids, np.int32), 12)
        np.testing.assert_array_equal(r.tokens, expect,
                                      err_msg=f"req {r.req_id}")
        if 5 in r.tokens:
            hit_eos += 1
            assert r.tokens[-1] == 5  # trimmed at EOS
    # All pages recycled at the end: every page is either free or
    # parked (unreferenced) in the prefix cache — nothing stranded.
    assert eng.sched.available_pages == eng.num_pages
    assert eng.sched.running == 0 and eng.sched.waiting == 0


def test_continuous_short_reservation_no_prompt_clobber():
    """max_new_tokens << max_prompt_len: the page reservation is smaller
    than the block-table width, so prefill's pad-position writes spill
    past the reserved pages.  They must land on the scratch page — not
    wrap onto the request's last real page and clobber prompt KV
    (ADVICE r1 high; this exact shape was previously untested)."""
    cfg, model, params, eng, solo = _setup(max_new=2, max_prompt=16,
                                           slots=2)
    rng = np.random.RandomState(7)
    # Prompts short enough that ceil((plen+2)/4) < ceil(16/4) pages.
    reqs = [(i, rng.randint(1, cfg.vocab_size, rng.randint(3, 8)))
            for i in range(5)]
    out = eng.generate(reqs, jax.random.key(4), params)
    assert sorted(r.req_id for r in out) == list(range(5))
    for r in out:
        ids = dict(reqs)[r.req_id]
        expect = _solo_completion(solo, np.asarray(ids, np.int32), 2)
        np.testing.assert_array_equal(r.tokens, expect,
                                      err_msg=f"req {r.req_id}")


def test_continuous_rejects_oversized_prompt():
    cfg, model, params, eng, _ = _setup()
    import pytest

    with pytest.raises(ValueError, match="longer than"):
        eng.generate([(0, np.ones(13, np.int32))], jax.random.key(0), params)


def test_per_request_budgets_ragged():
    """Per-request max_new budgets (the ragged-workload case): each
    request stops at its own budget and frees its slot for waiting
    work; reservations shrink with the budget."""
    cfg, model, params, eng, solo = _setup(max_new=10, slots=2)
    rng = np.random.RandomState(5)
    reqs = [(i, rng.randint(1, cfg.vocab_size, 4 + i % 3).astype(np.int32),
             2 + 2 * i)  # budgets 2, 4, 6, 8, 10
            for i in range(5)]
    out = eng.generate(reqs, jax.random.key(9), params=params)
    assert sorted(r.req_id for r in out) == list(range(5))
    for r in out:
        budget = 2 + 2 * r.req_id
        # no EOS configured -> exactly budget tokens, matching the
        # solo engine's first `budget` greedy tokens
        assert len(r.tokens) == budget
        ids = np.asarray([q[1] for q in reqs if q[0] == r.req_id][0])
        expect = _solo_completion(solo, ids, 10)[:budget]
        np.testing.assert_array_equal(r.tokens, expect)


def test_continuous_int8_kv_pools():
    """quantize_kv=True: int8 pools + scale pools; greedy completions
    agree with the bf16-pool engine on most tokens (per-vector int8 KV
    is ~0.4% RMS error — a few greedy flips are expected, wholesale
    divergence is not)."""
    cfg, model, params, eng, solo = _setup(max_new=10, slots=2)
    rcfg_q = RolloutConfig(max_prompt_len=12, max_new_tokens=10,
                           temperature=0.0, page_size=4, max_batch_size=2,
                           quantize_kv=True)
    eng_q = ContinuousBatchingEngine(model, cfg, rcfg_q, eos_token_id=None,
                                     segment_len=4)
    assert "k_scales" in eng_q._pools[0]
    assert eng_q._pools[0]["k_pages"].dtype == jnp.int8
    rng = np.random.RandomState(7)
    reqs = [(i, rng.randint(1, cfg.vocab_size, rng.randint(3, 12)))
            for i in range(5)]
    out_b = {r.req_id: r for r in eng.generate(reqs, jax.random.key(1),
                                               params)}
    out_q = {r.req_id: r for r in eng_q.generate(reqs, jax.random.key(1),
                                                 params)}
    assert sorted(out_q) == sorted(out_b)
    total = agree = 0
    for rid in out_b:
        a, b = out_b[rid].tokens, out_q[rid].tokens
        n = min(len(a), len(b))
        agree += (a[:n] == b[:n]).sum()
        total += n
        assert np.isfinite(out_q[rid].logprobs).all()
    assert agree / total >= 0.8, f"int8-kv greedy agreement {agree/total}"


# -- PR 8: serving-grade engine (chunked prefill, prefix cache,
#    on-demand pages + preemption) -------------------------------------

def _mk_engine(model, cfg, **kw):
    base = dict(max_prompt_len=32, max_new_tokens=8, temperature=0.0,
                page_size=4, max_batch_size=4)
    base.update(kw)
    return ContinuousBatchingEngine(model, cfg, RolloutConfig(**base),
                                    eos_token_id=None, segment_len=4)


def _serving_setup():
    cfg = ModelConfig.tiny(dtype="float32")
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(0), cfg)
    return cfg, model, params


def test_chunked_prefill_matches_oneshot():
    """chunked_prefill_tokens splits admission across decode segments;
    greedy tokens must equal the one-shot prefill's bit-for-bit (the
    chunk forward attends the gathered pool with the same mask) and
    the logprobs to 4 float32 ulps."""
    cfg, model, params = _serving_setup()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
               for n in (30, 17, 5, 26, 9, 31)]
    reqs = [(i, p) for i, p in enumerate(prompts)]
    one = _mk_engine(model, cfg, prefix_cache=False)
    base = {r.req_id: r for r in one.generate(reqs, jax.random.key(1),
                                              params)}
    chunked = _mk_engine(model, cfg, prefix_cache=False,
                         chunked_prefill_tokens=8)
    out = {r.req_id: r for r in chunked.generate(reqs, jax.random.key(1),
                                                 params)}
    assert sorted(out) == sorted(base)
    for i in base:
        np.testing.assert_array_equal(out[i].tokens, base[i].tokens,
                                      err_msg=f"req {i}")
        # the chunk program and the one-shot program differ in shape:
        # XLA:CPU may round a float32 logprob one ulp apart
        assert out[i].logprobs.shape == base[i].logprobs.shape
        np.testing.assert_array_max_ulp(out[i].logprobs, base[i].logprobs,
                                        maxulp=4)


def test_prefix_cache_bit_exact_trajectories():
    """prefix_cache on/off must produce IDENTICAL trajectories —
    tokens and logprobs bitwise, at temperature 1.0, including the
    second pass where the cache actually hits (mirroring the
    group_prefix_sharing guarantee: cached pages hold KV bit-identical
    to what a fresh prefill would write)."""
    cfg, model, params = _serving_setup()
    rng = np.random.RandomState(2)
    pref = rng.randint(1, cfg.vocab_size, 12).astype(np.int32)
    prompts = [np.concatenate(
        [pref, rng.randint(1, cfg.vocab_size, n).astype(np.int32)])
        for n in (4, 9, 2, 14)]
    reqs = [(i, p) for i, p in enumerate(prompts)]
    on = _mk_engine(model, cfg, prefix_cache=True, temperature=1.0)
    off = _mk_engine(model, cfg, prefix_cache=False, temperature=1.0)
    for key in (jax.random.key(5), jax.random.key(6)):
        o_on = {r.req_id: r for r in on.generate(reqs, key, params)}
        o_off = {r.req_id: r for r in off.generate(reqs, key, params)}
        for i in o_on:
            np.testing.assert_array_equal(o_on[i].tokens, o_off[i].tokens,
                                          err_msg=f"req {i}")
            np.testing.assert_array_equal(o_on[i].logprobs,
                                          o_off[i].logprobs)
    # pass 2 actually exercised the cache (retired pages graduated)
    assert on.sched.cached_total > 0
    assert off.sched.cached_total == 0


def test_prefix_cache_cleared_on_new_weights():
    """Cached KV is weight-dependent: installing new weights must drop
    the cache (a stale hit would decode against old-weights KV)."""
    cfg, model, params = _serving_setup()
    eng = _mk_engine(model, cfg, prefix_cache=True)
    rng = np.random.RandomState(3)
    reqs = [(0, rng.randint(1, cfg.vocab_size, 20).astype(np.int32))]
    eng.generate(reqs, jax.random.key(0), params)
    assert eng.sched.cached_total > 0
    params2 = init_params(model, jax.random.key(1), cfg)
    eng.load_weights(params2)
    assert eng.sched.cached_total == 0
    # and the post-reload trajectory equals a fresh engine's
    out = eng.generate(reqs, jax.random.key(2), params2)[0]
    fresh = _mk_engine(model, cfg, prefix_cache=True)
    expect = fresh.generate(reqs, jax.random.key(2), params2)[0]
    np.testing.assert_array_equal(out.tokens, expect.tokens)


def test_preemption_restart_recompute():
    """A pool too small for every admitted request's growth preempts
    the youngest decoding request (restart-by-recompute); greedy
    restarts reproduce the same completion, nothing is lost."""
    cfg, model, params = _serving_setup()
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, cfg.vocab_size, 9).astype(np.int32)
               for _ in range(4)]
    reqs = [(i, p) for i, p in enumerate(prompts)]
    tight = _mk_engine(model, cfg, prefix_cache=False, num_pages=12,
                       page_watermark=0, max_prompt_len=16)
    out = {r.req_id: r for r in tight.generate(reqs, jax.random.key(3),
                                               params)}
    assert tight.preemptions > 0
    ample = _mk_engine(model, cfg, prefix_cache=False, max_prompt_len=16)
    base = {r.req_id: r for r in ample.generate(reqs, jax.random.key(3),
                                                params)}
    assert sorted(out) == sorted(base)
    for i in base:
        np.testing.assert_array_equal(out[i].tokens, base[i].tokens,
                                      err_msg=f"req {i}")
    assert tight.sched.running == 0 and tight.sched.waiting == 0
    assert tight.sched.available_pages == 12


def test_pool_too_small_raises():
    cfg, model, params = _serving_setup()
    eng = _mk_engine(model, cfg, num_pages=2, max_prompt_len=16)
    import pytest

    with pytest.raises(RuntimeError, match="too small"):
        eng.generate([(0, np.ones(14, np.int32))], jax.random.key(0),
                     params)


def test_submit_step_service_surface():
    """The standing-service API: requests submitted over time complete
    across step() calls with the same outputs generate() produces."""
    cfg, model, params = _serving_setup()
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, cfg.vocab_size, 5 + i).astype(np.int32)
               for i in range(6)]
    base_eng = _mk_engine(model, cfg, prefix_cache=False)
    base = {r.req_id: r for r in base_eng.generate(
        [(i, p) for i, p in enumerate(prompts)], jax.random.key(7),
        params)}
    svc = _mk_engine(model, cfg, prefix_cache=False)
    svc.load_weights(params)
    svc.reset_rng(jax.random.key(7))
    done = {}
    # trickle the requests in: two per wave, finish order is free
    for i, p in enumerate(prompts[:2]):
        svc.submit(i, p)
    i_next = 2
    waves = 0
    while len(done) < len(prompts):
        for r in svc.step():
            done[r.req_id] = r
        if i_next < len(prompts):
            svc.submit(i_next, prompts[i_next])
            i_next += 1
        waves += 1
        assert waves < 100
    assert svc.pending == 0
    assert sorted(done) == sorted(base)
    # greedy: arrival timing cannot change any completion's content
    for i in base:
        np.testing.assert_array_equal(done[i].tokens, base[i].tokens,
                                      err_msg=f"req {i}")


def test_priority_admission_order():
    """admission_policy='priority': when slots free up, the
    higher-priority waiting request overtakes earlier arrivals."""
    cfg, model, params = _serving_setup()
    eng = _mk_engine(model, cfg, admission_policy="priority",
                     max_batch_size=1, max_new_tokens=4)
    rng = np.random.RandomState(6)
    p = [rng.randint(1, cfg.vocab_size, 6).astype(np.int32)
         for _ in range(3)]
    eng.load_weights(params)
    eng.reset_rng(jax.random.key(0))
    eng.submit(0, p[0], priority=0)
    eng.submit(1, p[1], priority=0)
    eng.submit(2, p[2], priority=9)   # must overtake requests 0 and 1
    order = []
    waves = 0
    while len(order) < 3:
        order.extend(r.req_id for r in eng.step())
        waves += 1
        assert waves < 100
    # highest priority first, then FIFO within the same class
    assert order == [2, 0, 1]


def test_pool_held_by_prefill_self_preempts_not_fatal():
    """Pool exhausted while the holder is MID-CHUNKED-PREFILL (not a
    preemptable decoding victim): the starved decoding request must
    restart-by-recompute (self-preempt + requeue), not kill the
    standing service with a fatal 'pool exhausted' raise."""
    cfg, model, params = _serving_setup()
    rng = np.random.RandomState(8)
    short = rng.randint(1, cfg.vocab_size, 4).astype(np.int32)
    long_p = rng.randint(1, cfg.vocab_size, 24).astype(np.int32)
    # 9 pages: short admits with 2, long with 7 -> free 0; the short
    # request's first growth fails while the long prompt is still
    # chunking (6 waves at chunk=4).
    tight = ContinuousBatchingEngine(
        model, cfg, RolloutConfig(
            max_prompt_len=24, max_new_tokens=16, temperature=0.0,
            page_size=4, max_batch_size=2, num_pages=9,
            page_watermark=0, prefix_cache=False,
            chunked_prefill_tokens=4),
        eos_token_id=None, segment_len=4)
    reqs = [(0, short, 16), (1, long_p, 4)]
    out = {r.req_id: r for r in tight.generate(reqs, jax.random.key(1),
                                               params)}
    assert sorted(out) == [0, 1]
    assert tight.preemptions > 0
    ample = _mk_engine(model, cfg, prefix_cache=False, max_prompt_len=24,
                       max_new_tokens=16, max_batch_size=2)
    base = {r.req_id: r for r in ample.generate(reqs, jax.random.key(1),
                                                params)}
    for i in base:
        np.testing.assert_array_equal(out[i].tokens, base[i].tokens,
                                      err_msg=f"req {i}")


def test_admit_max_out_contract_parity():
    """admit(max_out) is part of the shared contract: both impls cap a
    wave identically."""
    from orion_tpu.runtime import PyScheduler, Scheduler

    for s in (PyScheduler(32, 4, 4), Scheduler(32, 4, 4)):
        for i in range(4):
            s.add(i, 4, 4)
        first = s.admit(max_out=2)
        assert [a[0] for a in first] == [0, 1]
        rest = s.admit()
        assert [a[0] for a in rest] == [2, 3]


def test_generate_duplicate_ids_rejected_atomically():
    """A duplicate (or in-flight-colliding) request id must fail BEFORE
    anything is submitted — a mid-loop raise would leave earlier
    requests enqueued and poison every later generate() call."""
    import pytest

    cfg, model, params = _serving_setup()
    eng = _mk_engine(model, cfg)
    p = np.ones(4, np.int32)
    with pytest.raises(ValueError, match="already in flight"):
        eng.generate([(1, p), (1, p)], jax.random.key(0), params)
    # overlapping k-clone ranges collide too
    with pytest.raises(ValueError, match="already in flight"):
        eng.generate([(0, p, None, 3), (2, p)], jax.random.key(0), params)
    assert eng.sched.waiting == 0 and eng.pending == 0
    # the engine is NOT poisoned: a clean call returns exactly its ids
    out = eng.generate([(1, p)], jax.random.key(1), params)
    assert [r.req_id for r in out] == [1]
