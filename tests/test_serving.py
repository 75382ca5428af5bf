"""Arrivals-trace serving smoke (PR 8, tier-1): drive the
ContinuousBatchingEngine as a standing service through a seeded
arrivals trace (``benchmarks/traffic_gen.py``: Poisson arrivals, shared
prefixes, ragged heavy-tailed budgets) with deadlines, on a tiny model
in seconds, so the serving path is exercised by `-m 'not slow'`.

PR 12 added the network front door: ServingGateway/GatewayClient
end-to-end over real TCP (submit/stream/cancel, typed overload
backpressure across the wire) and the ``launch.py serve`` entrypoint
smoke through the in-process harness."""

import importlib.util
import os
import queue
import sys
import threading
import time

import jax
import numpy as np
import pytest

_N_REQ, _P, _T = 10, 32, 16
# ten requests over 0.2 s: one of 3 shared 16-token prefixes + a private
# part of 4-16 tokens (P = 32), budgets of 2-16 (T = 16)
_MIX = dict(name="serving-smoke", loop="open", rate_per_s=50.0,
            arrival_cv=1.0, warm_seconds=0.0, sizes_seed=3,
            prefix=dict(count=3, tokens=16, zipf_s=1.0),
            prompt=dict(median=10, sigma=0.5, min=4, max=_P - 16),
            budget=dict(median=6, sigma=0.8, min=2, max=_T))


def _traffic_gen():
    """``benchmarks/traffic_gen.py`` (no package: loaded by path)."""
    name = "orion_traffic_gen"
    if name not in sys.modules:
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "traffic_gen.py")
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def _trace(seed, vocab=200):
    """(prompts, budgets, arrivals) of the smoke mix for one seed."""
    reqs = _traffic_gen().open_schedule(_MIX, seed, _N_REQ / 50.0, vocab)
    assert len(reqs) == _N_REQ
    return ([r.prompt for r in reqs],
            np.asarray([r.budget for r in reqs], np.int32),
            np.asarray([r.due_s for r in reqs]))


def _engine():
    from orion_tpu.config import ModelConfig, RolloutConfig
    from orion_tpu.models import Transformer, init_params
    from orion_tpu.rollout.continuous import ContinuousBatchingEngine

    mc = ModelConfig.tiny(dtype="float32")
    model = Transformer(mc)
    cont = ContinuousBatchingEngine(
        model, mc, RolloutConfig(
            max_prompt_len=_P, max_new_tokens=_T, temperature=1.0,
            max_batch_size=4, page_size=8, segment_len=4,
            prefix_cache=True, chunked_prefill_tokens=16,
            admission_policy="deadline"),
        eos_token_id=None, pad_token_id=0)
    cont.load_weights(init_params(model, jax.random.key(0), mc))
    return cont


def _serve(cont, prompts, budgets, arrivals, deadlines):
    """The standing-service loop: submit each request when it is due,
    one engine wave per iteration.  Returns every CompletedRequest and
    the time each finished at (seconds from the start)."""
    n = len(prompts)
    cont.reset_rng(jax.random.key(17))
    t0 = time.monotonic()
    done, done_t, i_next = {}, np.zeros(n), 0
    while len(done) < n:
        now = time.monotonic() - t0
        while i_next < n and arrivals[i_next] <= now:
            cont.submit(i_next, prompts[i_next],
                        budget=int(budgets[i_next]),
                        deadline=int(deadlines[i_next] * 1e6))
            i_next += 1
        if cont.pending == 0:  # idle until the next arrival
            time.sleep(max(0.0, arrivals[i_next]
                           - (time.monotonic() - t0)))
            continue
        for r in cont.step():
            done[r.req_id] = r
            done_t[r.req_id] = time.monotonic() - t0
    return done, done_t


def test_arrivals_trace_end_to_end():
    """Everything due at once: the whole trace queues behind 4 slots,
    completes to its budgets, hits the shared prefixes, and leaves the
    scheduler and the page pool empty."""
    cont = _engine()
    prompts, budgets, _ = _trace(seed=3)
    done, done_t = _serve(cont, prompts, budgets, np.zeros(_N_REQ),
                          np.full(_N_REQ, 1e9))
    assert (done_t > 0).all()
    for i in range(_N_REQ):      # eos=None: every budget is used up
        assert len(done[i].tokens) == budgets[i]
    # the serving loop exercised the new machinery
    assert cont.prefix_cached_pages > 0          # shared templates hit
    assert cont.sched.running == 0 and cont.sched.waiting == 0
    assert cont.sched.available_pages == cont.num_pages


def test_arrivals_trace_with_real_arrivals_and_deadlines():
    """Timed arrivals (short span) through the submit/step service:
    every request completes, respecting budgets, with the deadline
    admission policy active."""
    cont = _engine()
    prompts, budgets, arrivals = _trace(seed=0)
    assert (np.diff(arrivals) >= 0).all() and arrivals[-1] <= 0.2
    done, done_t = _serve(cont, prompts, budgets, arrivals,
                          arrivals + 30.0)
    assert (done_t >= arrivals).all()
    assert all(len(done[i].tokens) == budgets[i] for i in range(_N_REQ))
    assert cont.pending == 0


def test_seeded_trace_is_deterministic():
    """One seed, one trace; another seed, the same sizes and arrival
    times in another order (what the serving tests above rely on)."""
    a, b, c = _trace(seed=5), _trace(seed=5), _trace(seed=6)
    for x, y in zip(a[0], b[0]):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(a[1], b[1])
    np.testing.assert_allclose(a[2], b[2])
    assert sorted(a[1]) == sorted(c[1])
    assert any(len(x) != len(y) or (x != y).any()
               for x, y in zip(a[0], c[0]))


# -- PR 12: streaming gateway over real TCP ---------------------------

def _gw_setup(**rollout_kw):
    from orion_tpu.config import ModelConfig, RolloutConfig
    from orion_tpu.models import Transformer, init_params
    from orion_tpu.rollout.continuous import ContinuousBatchingEngine

    cfg = ModelConfig.tiny(dtype="float32")
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(0), cfg)
    base = dict(max_prompt_len=32, max_new_tokens=8, temperature=0.0,
                page_size=4, max_batch_size=4)
    base.update(rollout_kw)
    eng = ContinuousBatchingEngine(model, cfg, RolloutConfig(**base),
                                   eos_token_id=None, segment_len=4)
    eng.load_weights(params)
    eng.reset_rng(jax.random.key(1))
    return cfg, model, params, eng


def _drain(client, want, timeout=60.0):
    """Collect StreamEvents until `want` requests are done (or error).
    Returns ({req: [chunk arrays]}, {req: final event})."""
    chunks, finals = {}, {}
    deadline = time.monotonic() + timeout
    while len(finals) < want:
        assert time.monotonic() < deadline, "gateway drain timed out"
        ev = client.next_event(timeout=1.0)
        if ev is None:
            continue
        chunks.setdefault(ev.req_id, [])
        if ev.restarted:
            chunks[ev.req_id] = []
        if ev.tokens.size:
            chunks[ev.req_id].append(ev.tokens)
        if ev.done:
            finals[ev.req_id] = ev
    return chunks, finals


def test_gateway_streams_bit_exact_tokens():
    """Remote clients stream over TCP: every request's concatenated
    chunks equal its final completion, which equals what the
    in-process generate() produces for the same seed (greedy — wave
    timing cannot change the content)."""
    from orion_tpu.orchestration.gateway import (GatewayClient,
                                                 ServingGateway)

    cfg, model, params, eng = _gw_setup()
    # in-process twin: same config/weights/seed, ids 0..N-1 in order
    _, _, _, twin = _gw_setup()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
               for n in (12, 7, 25, 4)]
    base = {r.req_id: r for r in twin.generate(
        [(i, p) for i, p in enumerate(prompts)], jax.random.key(1),
        params)}
    gw = ServingGateway(eng)
    gw.start()
    try:
        cl = GatewayClient(gw.port, tenant="paid")
        rids = [cl.submit(p) for p in prompts]
        chunks, finals = _drain(cl, len(rids))
        for i, rid in enumerate(rids):
            ev = finals[rid]
            assert ev.error is None
            got = np.concatenate(chunks[rid])
            np.testing.assert_array_equal(got, ev.completed.tokens)
            np.testing.assert_array_equal(ev.completed.tokens,
                                          base[i].tokens)
            np.testing.assert_array_equal(ev.completed.logprobs,
                                          base[i].logprobs)
        # more than one chunk per multi-wave request: streaming, not
        # finish-at-end delivery
        assert any(len(v) > 1 for v in chunks.values())
        cl.close()
    finally:
        gw.close()


def test_gateway_forwards_typed_backpressure():
    """Satellite 1, gateway path: an EngineOverloaded shed crosses the
    wire as a typed error event carrying queue depth and the
    retry-after hint — remote clients back off exactly like
    in-process callers."""
    from orion_tpu.orchestration.gateway import (GatewayClient,
                                                 ServingGateway)
    from orion_tpu.rollout.continuous import EngineOverloaded

    _, _, _, eng = _gw_setup(max_batch_size=1)
    gw = ServingGateway(
        eng, tenants={"free": {"weight": 1, "max_queued": 1}})
    gw.start()
    try:
        cl = GatewayClient(gw.port, tenant="free")
        rng = np.random.RandomState(5)
        # enough to exceed the 1-slot engine + 1-deep tenant queue
        rids = [cl.submit(rng.randint(1, 200, 8).astype(np.int32))
                for _ in range(4)]
        _, finals = _drain(cl, len(rids))
        errs = [e.error for e in finals.values() if e.error is not None]
        assert errs, "overload never shed"
        for e in errs:
            assert isinstance(e, EngineOverloaded)
            assert e.retry_after > 0
            assert e.tenant == "free"
        oks = [e for e in finals.values() if e.error is None]
        assert oks, "every request shed: QoS too aggressive"
        cl.close()
    finally:
        gw.close()


def test_gateway_cancel_and_client_drop():
    """CANCEL aborts an in-flight request (confirmed by a final
    'cancelled' event); a dropped client's requests are reaped and the
    engine drains clean."""
    from orion_tpu.orchestration.gateway import (GatewayClient,
                                                 ServingGateway)

    _, _, _, eng = _gw_setup(max_new_tokens=16)
    gw = ServingGateway(eng)
    gw.start()
    try:
        cl = GatewayClient(gw.port)
        rng = np.random.RandomState(6)
        rid = cl.submit(rng.randint(1, 200, 10).astype(np.int32),
                        budget=16)
        cl.cancel(rid)
        _, finals = _drain(cl, 1)
        assert finals[rid].error == "cancelled"
        # a second client that vanishes mid-request
        cl2 = GatewayClient(gw.port)
        cl2.submit(rng.randint(1, 200, 10).astype(np.int32), budget=16)
        cl2.chan.close()  # unceremonious drop, no GOODBYE
        deadline = time.monotonic() + 30
        while eng.pending and time.monotonic() < deadline:
            time.sleep(0.02)
        assert eng.pending == 0
        cl.close()
    finally:
        gw.close()
    assert eng.sched.available_pages == eng.num_pages


def test_launch_serve_entrypoint_smoke():
    """Tier-1 smoke for the ``launch.py serve`` path: run_serve on a
    thread (the in-process harness), drive a client round-trip with a
    tenant spec active, stop cleanly."""
    from orion_tpu.config import GRPOConfig, load_config
    from orion_tpu.launch import run_serve
    from orion_tpu.orchestration.gateway import GatewayClient

    cfg = load_config(GRPOConfig, cli_args=[
        "rollout.engine=continuous", "rollout.max_prompt_len=16",
        "rollout.max_new_tokens=8", "rollout.max_batch_size=4",
        "rollout.page_size=4", "rollout.segment_len=4",
        "rollout.temperature=0.0"])
    stop = threading.Event()
    ready: queue.Queue = queue.Queue()
    t = threading.Thread(
        target=run_serve,
        kwargs=dict(cfg=cfg, port=0,
                    tenant_spec="paid:weight=4;free:weight=1",
                    stop=stop, on_ready=ready.put),
        daemon=True)
    t.start()
    gw = ready.get(timeout=120)
    try:
        cl = GatewayClient(gw.port, tenant="paid")
        rid = cl.submit(np.arange(1, 10, dtype=np.int32), budget=6)
        chunks, finals = _drain(cl, 1)
        assert finals[rid].error is None
        assert finals[rid].completed.tokens.shape == (6,)
        cl.close()
    finally:
        stop.set()
        t.join(timeout=30)
    assert not t.is_alive()


def test_parse_tenant_spec():
    from orion_tpu.orchestration.gateway import parse_tenant_spec

    spec = parse_tenant_spec(
        "paid:weight=4,rate=100,burst=10;"
        "free:weight=1,max_queued=8,max_running=2")
    assert spec["paid"] == {"weight": 4, "rate_limit": 100.0,
                            "burst": 10.0}
    assert spec["free"] == {"weight": 1, "max_queued": 8,
                            "max_running": 2}
    with pytest.raises(ValueError):
        parse_tenant_spec("x:frobnicate=1")
    with pytest.raises(ValueError, match="missing ':'"):
        parse_tenant_spec("paid=4,rate=100")  # typo'd: no colon


def test_gateway_silent_stray_does_not_block_admission():
    """Review finding (mirrors the worker pool's acceptance): a silent
    peer parked mid-handshake must not serialize a healthy client
    behind it — admission is per-connection-threaded."""
    from orion_tpu.orchestration.gateway import (GatewayClient,
                                                 ServingGateway)
    from orion_tpu.orchestration.remote import PyTreeChannel

    _, _, _, eng = _gw_setup()
    gw = ServingGateway(eng)
    gw.start()
    stray = None
    try:
        # park a stray in the handshake: connects, never HELLOs
        stray = PyTreeChannel.connect(gw.port, timeout=10.0)
        t0 = time.monotonic()
        cl = GatewayClient(gw.port, connect_timeout=10.0)
        assert time.monotonic() - t0 < 5.0, \
            "healthy client serialized behind the silent stray"
        rid = cl.submit(np.arange(1, 8, dtype=np.int32), budget=4)
        _, finals = _drain(cl, 1)
        assert finals[rid].error is None
        cl.close()
    finally:
        if stray is not None:
            stray.close()
        gw.close()


def test_gateway_close_reaps_inflight_work():
    """Review finding: close() with clients still streaming must leave
    the caller-owned engine DRAINED of the gateway's work — the reap
    ops enqueued while dropping clients are applied even though the
    pump is already joined."""
    from orion_tpu.orchestration.gateway import (GatewayClient,
                                                 ServingGateway)

    _, _, _, eng = _gw_setup(max_new_tokens=64)
    gw = ServingGateway(eng)
    gw.start()
    cl = GatewayClient(gw.port)
    rng = np.random.RandomState(8)
    for _ in range(3):
        cl.submit(rng.randint(1, 200, 10).astype(np.int32), budget=64)
    deadline = time.monotonic() + 30
    while eng.pending < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert eng.pending == 3
    gw.close()   # client never said GOODBYE; requests were in flight
    assert eng.pending == 0, \
        "close() left the engine decoding cancelled clients' work"
    cl.close()
