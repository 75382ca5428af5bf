"""Speculative decoding v2 on the continuous engine (PR 10): per-slot
n-gram draft/verify over the paged pool, with k verify-slack positions
per reservation and FULL sampler composition.

Exactness contract (mirrors the dense engine's, now under the whole
control stack): at temperature 0 the speculative engine's tokens are
bit-identical to the non-speculative continuous engine at the same
seeds — drafts are verified against the same transformed argmax, with
the repetition-penalty seen-set and min_new EOS-forbid updated INSIDE
the verify chunk — and at temperature > 0 the delta-draft acceptance
keeps every emitted token's marginal exactly the tempered sampling
distribution.  Logprobs are compared with allclose, not bitwise: the
1-query decode step and the k+1-wide verify chunk take the paged
kernel twin vs the gather path, whose f32 results agree to ulps (the
same tolerance test_paged_engine grants the dense-vs-paged pair)."""

import jax
import numpy as np
import pytest

from orion_tpu.config import ModelConfig, RolloutConfig
from orion_tpu.models import Transformer, init_params
from orion_tpu.rollout.continuous import ContinuousBatchingEngine


def _setup():
    cfg = ModelConfig.tiny(dtype="float32")
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(0), cfg)
    return cfg, model, params


def _mk(model, cfg, k, eos=None, seg=4, **kw):
    base = dict(max_prompt_len=16, max_new_tokens=12, temperature=0.0,
                page_size=4, max_batch_size=3, speculative_k=k,
                spec_adaptive=False)
    base.update(kw)
    return ContinuousBatchingEngine(model, cfg, RolloutConfig(**base),
                                    eos_token_id=eos, segment_len=seg)


def _reqs(cfg, n=6, seed=0, lo=3, hi=16):
    rng = np.random.RandomState(seed)
    return [(i, rng.randint(1, cfg.vocab_size,
                            rng.randint(lo, hi)).astype(np.int32))
            for i in range(n)]


def _assert_same(out, base, lp_tol=1e-5):
    assert sorted(out) == sorted(base)
    for i in base:
        np.testing.assert_array_equal(out[i].tokens, base[i].tokens,
                                      err_msg=f"req {i}")
        np.testing.assert_allclose(out[i].logprobs, base[i].logprobs,
                                   rtol=lp_tol, atol=lp_tol)
        np.testing.assert_allclose(out[i].policy_logprobs,
                                   base[i].policy_logprobs,
                                   rtol=lp_tol, atol=lp_tol)


@pytest.mark.parametrize("eos,k", [(None, 4), (5, 1), (5, 4), (None, 1)])
def test_spec_continuous_matches_plain_greedy(eos, k):
    """Token-identical to the sequential continuous engine at temp 0,
    including EOS retirement mid-chunk, for more requests than slots
    (page recycling + admission churn under speculative extents)."""
    cfg, model, params = _setup()
    reqs = _reqs(cfg)
    base = {r.req_id: r for r in _mk(model, cfg, 0, eos=eos).generate(
        reqs, jax.random.key(1), params)}
    spec = _mk(model, cfg, k, eos=eos)
    out = {r.req_id: r for r in spec.generate(reqs, jax.random.key(1),
                                              params)}
    _assert_same(out, base)
    # the verify path actually ran and its pages all recycled
    assert spec.server_stats()["spec_drafted"] > 0
    assert spec.sched.available_pages == spec.num_pages


def test_spec_composes_with_repetition_penalty_and_min_new():
    """The satellite contract: repetition_penalty != 1 and
    min_new_tokens > 0 under speculative verify are BIT-EXACT with the
    sequential continuous path — the penalty seen-set and the EOS
    forbid mask are updated per candidate position inside the chunk,
    so speculative decoding COMPOSES instead of disabling itself."""
    cfg, model, params = _setup()
    reqs = _reqs(cfg, seed=3)
    for kw in (dict(min_new_tokens=8),
               dict(repetition_penalty=1.15, min_new_tokens=5)):
        base = {r.req_id: r for r in
                _mk(model, cfg, 0, eos=5, **kw).generate(
                    reqs, jax.random.key(2), params)}
        out = {r.req_id: r for r in
               _mk(model, cfg, 4, eos=5, **kw).generate(
                   reqs, jax.random.key(2), params)}
        _assert_same(out, base)
        if "min_new_tokens" in kw:
            for r in out.values():
                # every terminator really was suppressed under min_new
                head = r.tokens[:kw["min_new_tokens"] - 1]
                assert not (head == 5).any()


def test_spec_stop_token_in_chunk():
    """Stop ids terminate inside an accepted chunk exactly as in
    sequential decode — tokens after the stop are never emitted."""
    cfg, model, params = _setup()
    reqs = _reqs(cfg, n=8, seed=7)
    base = {r.req_id: r for r in
            _mk(model, cfg, 0, stop_token_ids=(9, 11)).generate(
                reqs, jax.random.key(1), params)}
    out = {r.req_id: r for r in
           _mk(model, cfg, 4, stop_token_ids=(9, 11)).generate(
               reqs, jax.random.key(1), params)}
    _assert_same(out, base)


def test_spec_composes_with_prefix_cache_and_chunked_prefill():
    """The PR 8 serving features stay bit-exact under speculative
    decode: the draft buffer is host-written from the FULL prompt, so
    a prefix-cache hit or a chunked prefill changes nothing the
    n-gram lookup sees."""
    cfg, model, params = _setup()
    rng = np.random.RandomState(2)
    pref = rng.randint(1, cfg.vocab_size, 8).astype(np.int32)
    prompts = [np.concatenate(
        [pref, rng.randint(1, cfg.vocab_size, n).astype(np.int32)])
        for n in (4, 7, 2, 6)]
    reqs = [(i, p) for i, p in enumerate(prompts)]
    base = {r.req_id: r for r in
            _mk(model, cfg, 0, prefix_cache=False).generate(
                reqs, jax.random.key(5), params)}
    featured = _mk(model, cfg, 4, prefix_cache=True,
                   chunked_prefill_tokens=8)
    for key in (jax.random.key(5), jax.random.key(5)):
        out = {r.req_id: r for r in featured.generate(reqs, key, params)}
        _assert_same(out, base)
    # second pass really hit the cache
    assert featured.sched.cached_total > 0


def test_spec_group_sampling_clones():
    """k-clone sampling groups (shared prompt pages) draft/verify per
    clone: greedy clones of one prompt all reproduce the solo
    completion."""
    cfg, model, params = _setup()
    rng = np.random.RandomState(11)
    p = rng.randint(1, cfg.vocab_size, 9).astype(np.int32)
    base = _mk(model, cfg, 0).generate([(0, p)], jax.random.key(3),
                                       params)[0]
    out = _mk(model, cfg, 3).generate([(0, p, None, 3)],
                                      jax.random.key(3), params)
    assert sorted(r.req_id for r in out) == [0, 1, 2]
    for r in out:
        np.testing.assert_array_equal(r.tokens, base.tokens)


def test_spec_counters_reconcile_with_emitted_tokens():
    """The satellite contract: spec_drafted / spec_accepted surface in
    server_stats() and reconcile with emitted tokens — every verify
    emission is either an accepted draft or a correction/bonus
    resample, and admission contributes exactly one token per request,
    so   sum(completion lens) == spec_accepted + spec_resampled + N
    when every decode wave is speculative (adaptive off, no eos)."""
    cfg, model, params = _setup()
    # budget 32: long enough for greedy cycles to form, so drafting
    # genuinely happens (drafted counts cover MATCHED rows only)
    eng = _mk(model, cfg, 4, max_new_tokens=32)
    reqs = _reqs(cfg, n=5, seed=9)
    out = eng.generate(reqs, jax.random.key(6), params)
    total = sum(len(r.tokens) for r in out)
    st = eng.server_stats()
    assert st["spec_accepted"] + st["spec_resampled"] + len(reqs) == total
    assert st["spec_drafted"] >= st["spec_accepted"] > 0
    # per-request acceptance histogram recorded at finish for every
    # request that drafted at least once
    assert 1 <= st["spec_acceptance_count"] <= len(reqs)
    assert 0.0 <= st["spec_acceptance_mean"] <= 1.0
    # counters reset with the other serving telemetry
    eng.reset_server_stats()
    st2 = eng.server_stats()
    assert st2["spec_drafted"] == 0.0 and st2["spec_accepted"] == 0.0


def test_spec_stochastic_second_token_distribution():
    """temperature > 0 delta-draft acceptance: the empirical marginal
    of the first drafted/verified position matches the sequential
    continuous sampler within TV sampling noise (the dense engine's
    TV test, re-run through the paged per-slot path)."""
    cfg = ModelConfig.tiny(vocab_size=16, hidden_size=32,
                           intermediate_size=64, num_layers=2,
                           num_heads=2, num_kv_heads=2, dtype="float32")
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(0), cfg)

    def hist(k, key0):
        eng = ContinuousBatchingEngine(
            model, cfg, RolloutConfig(
                max_prompt_len=8, max_new_tokens=3, temperature=1.0,
                page_size=4, max_batch_size=8, speculative_k=k,
                spec_adaptive=False),
            eos_token_id=None, segment_len=3)
        counts = np.zeros(16)
        prompt = np.asarray([3, 9, 4, 1], np.int32)
        for s in range(64):
            out = eng.generate([(i, prompt) for i in range(8)],
                               jax.random.key(key0 + s), params)
            for r in out:
                counts[r.tokens[1]] += 1
        return counts / counts.sum()

    tv = 0.5 * np.abs(hist(0, 100) - hist(3, 900)).sum()
    assert tv < 0.12, tv


def test_spec_stochastic_logprob_accounting():
    """At temperature 0.7 every emitted token's behavior logprob must
    be log p(token) under the TEMPERED distribution and its
    policy_logprob the raw model logprob, whichever branch emitted it
    (accepted draft, residual resample, bonus draw): recompute both
    from the training graph's full forward over prompt + completion.
    The lm_head is scaled 32x so the random model is peaky enough to
    repeat itself: drafts match, some are accepted, some rejected."""
    cfg, model, params = _setup()
    params = dict(params, lm_head=jax.tree.map(lambda x: x * 32.0,
                                               params["lm_head"]))
    temp = 0.7
    eng = _mk(model, cfg, 4, temperature=temp, max_new_tokens=32)
    reqs = _reqs(cfg, n=4, seed=11)
    out = {r.req_id: r for r in eng.generate(reqs, jax.random.key(5),
                                             params)}
    st = eng.server_stats()
    assert 0 < st["spec_accepted"] < st["spec_drafted"], st
    for rid, prompt in reqs:
        r = out[rid]
        n, p = len(r.tokens), len(prompt)
        assert n == 32
        seq = np.concatenate([prompt, r.tokens])[None]
        pos = np.arange(seq.shape[1], dtype=np.int32)[None]
        logits, _ = model.apply({"params": params}, seq, pos)
        # logits at position t predict token t + 1
        pred = np.asarray(logits[0, p - 1: p - 1 + n], np.float32)
        raw = np.asarray(jax.nn.log_softmax(pred, axis=-1))
        tempered = np.asarray(jax.nn.log_softmax(pred / temp, axis=-1))
        idx = np.arange(n)
        np.testing.assert_allclose(r.policy_logprobs, raw[idx, r.tokens],
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"req {rid}")
        np.testing.assert_allclose(r.logprobs, tempered[idx, r.tokens],
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"req {rid}")
        assert (r.logprobs != r.policy_logprobs).any()


def _cyclic_reqs(cfg, n=6, seed=4, period=2, length=14):
    """Prompts that repeat a short random pattern: the tiny model's
    greedy continuation settles into a cycle within a few tokens, so
    the trailing n-gram recurs and an always-on engine drafts (and
    accepts) on nearly every wave."""
    rng = np.random.RandomState(seed)
    return [(i, np.tile(rng.randint(1, cfg.vocab_size, period),
                        length // period + 1)[:length].astype(np.int32))
            for i in range(n)]


def test_spec_adaptive_goes_cold_and_probes():
    """Adaptive k with an unreachable breakeven (> k+1, so even a
    fully-accepting request can never qualify): every draftable
    request probes at most one wave to create its EMA, then every
    wave runs the plain segment — trajectories stay identical to
    spec-off (greedy: the wave mode never changes content), and the
    chunk tax collapses to the probes.  A shorter spec_probe_period
    forces extra probe waves on top.  The traffic is cyclic and long
    (64 tokens a request), so the always-on engine drafts on nearly
    every wave and the probes are a small part of that."""
    cfg, model, params = _setup()
    reqs = _cyclic_reqs(cfg)
    k, kw = 4, dict(max_new_tokens=64)
    base = {r.req_id: r for r in _mk(model, cfg, 0, **kw).generate(
        reqs, jax.random.key(8), params)}
    always = _mk(model, cfg, k, spec_adaptive=False, **kw)
    out = {r.req_id: r for r in always.generate(reqs, jax.random.key(8),
                                                params)}
    _assert_same(out, base)
    cold = _mk(model, cfg, k, spec_adaptive=True,
               spec_breakeven=6.0, spec_probe_period=0, **kw)
    out = {r.req_id: r for r in cold.generate(reqs, jax.random.key(8),
                                              params)}
    _assert_same(out, base)
    # proven-cold requests stop drafting: far fewer drafts than the
    # always-on engine (probes only; a probe wave drafts for every
    # matched slot, so at most slots * k a request)
    d_cold = cold.server_stats()["spec_drafted"]
    d_always = always.server_stats()["spec_drafted"]
    assert 0 < d_cold <= len(reqs) * cold.slots * k, d_cold
    assert d_cold < d_always / 2, (d_cold, d_always)

    probing = _mk(model, cfg, k, spec_adaptive=True,
                  spec_breakeven=6.0, spec_probe_period=2, **kw)
    out = {r.req_id: r for r in probing.generate(reqs, jax.random.key(8),
                                                 params)}
    _assert_same(out, base)  # greedy: probing never changes content
    assert d_cold < probing.server_stats()["spec_drafted"] < d_always


def test_spec_unstructured_text_never_drafts():
    """The draftability gate: when no trailing n-gram ever recurs
    (acyclic completions — forced here by a repetition penalty, which
    bars the sampler from re-entering any cycle), the match bit stays
    False and the adaptive engine never pays a single verify chunk —
    the mechanism behind the <=2% random-trace overhead bound."""
    cfg, model, params = _setup()
    eng = _mk(model, cfg, 4, spec_adaptive=True,
              repetition_penalty=1.5, spec_probe_period=0)
    reqs = _reqs(cfg, seed=6)
    out = eng.generate(reqs, jax.random.key(4), params)
    assert len(out) == len(reqs)
    st = eng.server_stats()
    assert st["spec_drafted"] == 0.0 and st["spec_resampled"] == 0.0


def test_spec_adaptive_stays_hot_on_cyclic_output():
    """Tiny random transformers fall into greedy cycles; once the
    output cycles the n-gram draft predicts it perfectly, the
    acceptance EMA stays above breakeven, and verify waves keep
    running — the structured-output case the feature exists for."""
    cfg, model, params = _setup()
    eng = _mk(model, cfg, 4, spec_adaptive=True, max_new_tokens=32,
              max_prompt_len=16)
    reqs = _reqs(cfg, n=4, seed=3)
    out = eng.generate(reqs, jax.random.key(2), params)
    assert all(len(r.tokens) == 32 for r in out)
    st = eng.server_stats()
    comp = np.stack([r.tokens for r in out])
    has_cycle = any(
        any(tuple(comp[i, t:t + 2]) == tuple(comp[i, t + 2:t + 4])
            for t in range(0, 24))
        for i in range(comp.shape[0]))
    if has_cycle:
        # cycling rows accept full chunks: strictly fewer verify
        # steps than tokens, visible as accepted > 0
        assert st["spec_accepted"] > 0


def test_spec_with_lagged_harvest():
    """harvest_lag=1 (the TPU auto setting): the spec counters and
    draftability bit ride the LAGGED flags snapshot one wave behind —
    pairing on the admission seq must keep the accounting and the
    completions correct across slot reuse."""
    cfg, model, params = _setup()
    reqs = _reqs(cfg, n=6, seed=0)
    base = {r.req_id: r for r in
            _mk(model, cfg, 0, eos=5, harvest_lag=0).generate(
                reqs, jax.random.key(1), params)}
    eng = _mk(model, cfg, 4, eos=5, harvest_lag=1)
    out = {r.req_id: r for r in eng.generate(reqs, jax.random.key(1),
                                             params)}
    _assert_same(out, base)
    st = eng.server_stats()
    total = sum(len(r.tokens) for r in out.values())
    assert st["spec_accepted"] + st["spec_resampled"] + len(reqs) == total


def test_spec_preemption_restart_under_slack_extents():
    """A pool too small for every request's speculative growth
    preempts (restart-by-recompute) — greedy restarts reproduce the
    ample-pool completions, nothing stranded, slack pages all
    recycled."""
    cfg, model, params = _setup()
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, cfg.vocab_size, 9).astype(np.int32)
               for _ in range(4)]
    reqs = [(i, p) for i, p in enumerate(prompts)]
    tight = ContinuousBatchingEngine(
        model, cfg, RolloutConfig(
            max_prompt_len=16, max_new_tokens=12, temperature=0.0,
            page_size=4, max_batch_size=3, speculative_k=4,
            spec_adaptive=False, num_pages=14, page_watermark=0,
            prefix_cache=False),
        eos_token_id=None, segment_len=4)
    out = {r.req_id: r for r in tight.generate(reqs, jax.random.key(3),
                                               params)}
    assert tight.preemptions > 0
    base = {r.req_id: r for r in _mk(model, cfg, 0,
                                     prefix_cache=False).generate(
        reqs, jax.random.key(3), params)}
    for i in base:
        np.testing.assert_array_equal(out[i].tokens, base[i].tokens,
                                      err_msg=f"req {i}")
    assert tight.sched.running == 0 and tight.sched.waiting == 0
    assert tight.sched.available_pages == 14
