"""Disaggregated prefill tier tests (ISSUE 17 tentpole, part b).

A PrefillWorker (its own engine, same weights) runs the prefill
forward and ships finished KV pages over the v6 ORTP frame family
(KV_OFFER / KV_PAGES / KV_ACK); the decode-side coordinator injects
them into the device prefix cache and admits in EDF order.  The bar:
tokens bit-exact vs a single-engine run and logprobs within 4 float32
ulps (bit-exact where both sides run the same prefill program), under
chaos (``kv.handoff`` faults, dead worker) included — every failure
mode degrades to the decode engine's own cold prefill, never to
different output."""

import threading
import time

import jax
import numpy as np
import pytest

from orion_tpu.config import ModelConfig, RolloutConfig
from orion_tpu.models import Transformer, init_params
from orion_tpu.orchestration.prefill_tier import (PrefillTierCoordinator,
                                                  PrefillWorker)
from orion_tpu.resilience.inject import FaultPlan, active_plan
from orion_tpu.rollout.continuous import ContinuousBatchingEngine


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig.tiny(dtype="float32")
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(0), cfg)
    return cfg, model, params


def _mk(model, cfg, params, **kw):
    base = dict(max_prompt_len=32, max_new_tokens=8, temperature=0.0,
                page_size=4, max_batch_size=4)
    base.update(kw)
    eng = ContinuousBatchingEngine(model, cfg, RolloutConfig(**base),
                                   eos_token_id=None, segment_len=4)
    eng.load_weights(params)
    eng.reset_rng(jax.random.key(1))
    return eng


def _tier_pair(model, cfg, params):
    """A serving PrefillWorker (background thread) + coordinator
    fronting a fresh decode engine."""
    decode = _mk(model, cfg, params)
    worker = PrefillWorker(_mk(model, cfg, params), port=0)
    thread = threading.Thread(target=worker.serve, daemon=True)
    thread.start()
    coord = PrefillTierCoordinator(decode, worker.port)
    return decode, worker, coord


def _drain(decode, coord, want, timeout=60.0):
    done = {}
    deadline = time.monotonic() + timeout
    while len(done) < want:
        assert time.monotonic() < deadline, "prefill tier drain hung"
        coord.pump()
        if decode.pending:
            for r in decode.step():
                done[r.req_id] = r
        else:
            time.sleep(0.002)
    return done


def _baseline(model, cfg, params, prompts):
    twin = _mk(model, cfg, params)
    return {r.req_id: r for r in twin.generate(
        [(i, p) for i, p in enumerate(prompts)], jax.random.key(1),
        params)}


def _assert_logprobs_4ulp(got, want, msg):
    """The tier prefills a prompt alone and the decode engine then
    prefix-hits it; the baseline prefills all prompts in one wave.  Two
    differently shaped prefill programs may round a float32 logprob one
    ulp apart on XLA:CPU — tokens stay exact, logprobs get 4 ulps."""
    assert got.shape == want.shape, msg
    np.testing.assert_array_max_ulp(got, want, maxulp=4)


def _prompts(cfg, seed=3, lens=(12, 7, 25)):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


def test_handoff_bit_exact_and_prefix_hits(setup):
    """KV prefilled remotely, injected locally: tokens bit-exact and
    logprobs to 4 ulps vs a single-engine run, and the decode engine
    actually prefix-HIT the injected pages (the prefill forward was
    skipped)."""
    cfg, model, params = setup
    prompts = _prompts(cfg)
    base = _baseline(model, cfg, params, prompts)
    decode, worker, coord = _tier_pair(model, cfg, params)
    try:
        for i, p in enumerate(prompts):
            coord.submit(i, p, budget=8)
        done = _drain(decode, coord, len(prompts))
        for i in base:
            np.testing.assert_array_equal(done[i].tokens, base[i].tokens,
                                          err_msg=f"req {i}")
            _assert_logprobs_4ulp(done[i].logprobs, base[i].logprobs,
                                  f"req {i}")
        assert coord.stats["handoffs"] == len(prompts)
        assert coord.stats["pages_injected"] > 0
        assert decode.prefix_cached_pages > 0   # prefill was skipped
        assert worker.stats["offers"] == len(prompts)
        assert worker.stats["pages_shipped"] >= \
            coord.stats["pages_injected"]
    finally:
        coord.close()
        worker.close()


def test_handoff_chaos_degrades_bit_identically(setup):
    """A seeded ``kv.handoff`` plan drops injections — those requests
    cold-prefill locally with IDENTICAL output, and the plan's event
    witness replays exactly across two identically-seeded runs."""
    cfg, model, params = setup
    prompts = _prompts(cfg, seed=5, lens=(14, 9, 21, 6))
    base = _baseline(model, cfg, params, prompts)
    witnesses = []
    for _ in range(2):
        decode, worker, coord = _tier_pair(model, cfg, params)
        plan = FaultPlan({"kv.handoff": {"at": (1, 3)}}, seed=7)
        try:
            with active_plan(plan):
                for i, p in enumerate(prompts):
                    coord.submit(i, p, budget=8)
                done = _drain(decode, coord, len(prompts))
            assert plan.events, "plan never fired — not a chaos run"
            witnesses.append(list(plan.events))
            for i in base:
                np.testing.assert_array_equal(done[i].tokens,
                                              base[i].tokens,
                                              err_msg=f"req {i}")
                _assert_logprobs_4ulp(done[i].logprobs, base[i].logprobs,
                                      f"req {i}")
            assert coord.stats["fallbacks"] == 2      # at=(1, 3)
            assert coord.stats["handoffs"] == len(prompts)
        finally:
            coord.close()
            worker.close()
    assert witnesses[0] == witnesses[1]


def test_dead_worker_falls_back_to_cold_prefill(setup):
    """Worker death mid-flight: every parked request cold-admits on
    the next pump — slower, bit-identical, nothing stranded."""
    cfg, model, params = setup
    prompts = _prompts(cfg, seed=9, lens=(10, 18))
    base = _baseline(model, cfg, params, prompts)
    decode, worker, coord = _tier_pair(model, cfg, params)
    try:
        worker.close()               # tier dies before any offer lands
        for i, p in enumerate(prompts):
            coord.submit(i, p, budget=8)
        done = _drain(decode, coord, len(prompts))
        for i in base:
            np.testing.assert_array_equal(done[i].tokens, base[i].tokens)
            np.testing.assert_array_equal(done[i].logprobs,
                                          base[i].logprobs)
        assert coord.pending == 0    # nothing stranded tier-side
    finally:
        coord.close()
        worker.close()


def test_edf_admission_order(setup):
    """When several prefilled requests are ready at one pump, they
    admit earliest-deadline-first (deadline-less last, then id
    order)."""
    cfg, model, params = setup
    decode, worker, coord = _tier_pair(model, cfg, params)
    order = []
    real_submit = decode.submit

    def spy(rid, ids, **kw):
        order.append(rid)
        return real_submit(rid, ids, **kw)

    decode.submit = spy
    try:
        prompts = _prompts(cfg, seed=11, lens=(8, 8, 8, 8))
        deadlines = [None, 30, 10, 20]
        for i, (p, dl) in enumerate(zip(prompts, deadlines)):
            coord.submit(i, p, budget=2, deadline=dl)
        # let every KV_PAGES frame arrive BEFORE the first pump
        deadline = time.monotonic() + 30.0
        while coord._arrived.qsize() < 4:
            assert time.monotonic() < deadline, "KV never arrived"
            time.sleep(0.01)
        coord.pump()
        assert order == [2, 3, 1, 0]     # EDF, deadline-less last
        _drain(decode, coord, 4)
    finally:
        decode.submit = real_submit
        coord.close()
        worker.close()


def test_cancel_while_parked_tier_side(setup):
    """Cancelling a request whose KV is still in flight forgets it at
    the coordinator — its later KV_PAGES frame is a no-op, the engine
    never sees it."""
    cfg, model, params = setup
    decode, worker, coord = _tier_pair(model, cfg, params)
    try:
        prompts = _prompts(cfg, seed=13, lens=(9, 16))
        for i, p in enumerate(prompts):
            coord.submit(i, p, budget=4)
        assert coord.cancel(0) is True
        assert coord.cancel(0) is False      # already forgotten
        done = _drain(decode, coord, 1)
        assert sorted(done) == [1]
        assert coord.stats["handoffs"] == 1
        assert coord.pending == 0
    finally:
        coord.close()
        worker.close()


def test_gateway_routes_through_prefill_tier(setup):
    """End-to-end over real TCP: GatewayClient -> ServingGateway ->
    prefill tier -> decode engine, streamed tokens bit-exact vs the
    in-process baseline, tier-labelled counters in gateway stats."""
    from orion_tpu.orchestration.gateway import (GatewayClient,
                                                 ServingGateway)

    cfg, model, params = setup
    prompts = _prompts(cfg, seed=15, lens=(12, 7, 22))
    base = _baseline(model, cfg, params, prompts)
    decode, worker, coord = _tier_pair(model, cfg, params)
    gw = ServingGateway(decode, prefill_tier=coord)
    gw.start()
    try:
        cl = GatewayClient(gw.port)
        rids = [cl.submit(p, budget=8) for p in prompts]
        finals = {}
        deadline = time.monotonic() + 60.0
        while len(finals) < len(rids):
            assert time.monotonic() < deadline, "gateway drain hung"
            ev = cl.next_event(timeout=1.0)
            if ev is not None and ev.done:
                assert ev.error is None
                finals[ev.req_id] = ev.completed
        for i, rid in enumerate(rids):
            np.testing.assert_array_equal(finals[rid].tokens,
                                          base[i].tokens,
                                          err_msg=f"req {i}")
            _assert_logprobs_4ulp(finals[rid].logprobs, base[i].logprobs,
                                  f"req {i}")
        cl.close()
        assert gw.stats["prefill_handoffs"] == len(prompts)
        assert gw.stats["prefill_pages_injected"] > 0
    finally:
        gw.close()
        coord.close()
        worker.close()
