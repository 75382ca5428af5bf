"""Zero-downtime fleet weight rollout chaos suite (ISSUE 18).

A WeightRolloutCoordinator rolls a version-tagged param snapshot
through a fleet of continuous engines blue/green — DRAINING → RELOAD
→ CANARY → READMIT per engine — while a ServingGateway routes around
the draining engine.  The bar: torn push, engine crash mid-reload,
canary rejection and coordinator death mid-fleet each converge the
fleet back to the OLD version automatically; a mid-trace roll drops
and duplicates ZERO client requests; and a seeded faulty roll replays
bit-identically (decisions + counters + fault-plan events).

Also here: the v7 ORTP staged/commit/abort WEIGHTS push (WEIGHTS_ACK
handshake — a torn push leaves workers on old weights), the
prefill-tier stale-KV-offer drop on weight-version bump, and the
typed GatewayClosed wake-up for clients blocked in ``next_event``
when the gateway drains away (the PR 18 satellite bugfixes)."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import ModelConfig, RolloutConfig, RolloutUpdateConfig
from orion_tpu.models import Transformer, init_params
from orion_tpu.orchestration.rollout_controller import (
    WeightRolloutCoordinator)
from orion_tpu.resilience import FaultPlan, InjectedFault, active_plan
from orion_tpu.rollout.continuous import ContinuousBatchingEngine


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig.tiny(dtype="float32")
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(0), cfg)
    return cfg, model, params


def _mk(model, cfg, params, seed=1, **kw):
    base = dict(max_prompt_len=32, max_new_tokens=8, temperature=0.0,
                page_size=4, max_batch_size=4)
    base.update(kw)
    eng = ContinuousBatchingEngine(model, cfg, RolloutConfig(**base),
                                   eos_token_id=None, segment_len=4)
    eng.load_weights(params)
    eng.reset_rng(jax.random.key(seed))
    return eng


@pytest.fixture(scope="module")
def fleet(setup):
    """Two engines shared across tests (compile once); the autouse
    cleaner below restores base params + un-drains after each test."""
    cfg, model, params = setup
    return [_mk(model, cfg, params, seed=1),
            _mk(model, cfg, params, seed=2)]


@pytest.fixture(autouse=True)
def _clean_fleet(request, setup):
    yield
    if "fleet" in request.fixturenames:
        cfg, model, params = setup
        for eng in request.getfixturevalue("fleet"):
            eng.drain(False)
            while eng.pending:
                eng.step()
            eng.reload_weights(params)


def _perturb(params, scale=1.001):
    return jax.tree_util.tree_map(lambda x: x * scale, params)


def _run(co, engines, max_ticks=500):
    """Drive coordinator + engines to convergence (direct mode)."""
    n = 0
    while co.active:
        assert n < max_ticks, "rollout did not converge"
        co.tick()
        for e in engines:
            if e.pending:
                e.step()
        n += 1
    return n


def _ladder(co, idx):
    """The state transitions engine ``idx`` walked, in order."""
    return [(frm, to) for (_t, what, d) in co.decisions
            if what == "state" and d[0] == idx
            for (frm, to) in [(d[1], d[2])]]


# -- the blue/green ladder ---------------------------------------------

def test_clean_fleet_roll_commits(fleet, setup):
    """Happy path: both engines walk DRAINING→RELOAD→CANARY→READMIT
    (flight-recorder ladder), an in-flight request finishes during
    the drain, and the fleet-wide commit lands the new snapshot."""
    cfg, model, params = setup
    new = _perturb(params)
    co = WeightRolloutCoordinator(engines=fleet)
    fleet[0].submit(5, np.arange(1, 9, dtype=np.int32), budget=4)
    co.begin(new, version=1)
    _run(co, fleet)
    assert co.version == 1
    counters = co.counters()
    assert counters["rollout_commits"] == 1
    assert counters["rollout_faults"] == 0
    assert counters["rollout_pushes"] == 1
    assert counters["rollout_version"] == 1.0
    assert counters["rollout_active"] == 0.0   # roll fully landed
    for idx, eng in enumerate(fleet):
        assert eng.params_snapshot() is new
        assert not eng.draining
        assert _ladder(co, idx) == [(None, "DRAINING"),
                                    ("DRAINING", "RELOAD"),
                                    ("RELOAD", "CANARY"),
                                    ("CANARY", "READMIT")]


def test_begin_while_active_is_refused(fleet, setup):
    cfg, model, params = setup
    co = WeightRolloutCoordinator(engines=fleet)
    co.begin(_perturb(params), version=1)
    with pytest.raises(RuntimeError, match="in progress"):
        co.begin(_perturb(params), version=2)
    _run(co, fleet)
    assert co.version == 1


# -- chaos: every fault converges back to OLD --------------------------

def test_torn_push_rolls_back(fleet, setup):
    """weights.push fault on the SECOND engine's reload (engine 0
    already upgraded): the fleet must converge back to the old
    snapshot — the torn state never commits."""
    cfg, model, params = setup
    plan = FaultPlan({"weights.push": {"at": 2}}, seed=0)
    with active_plan(plan):
        co = WeightRolloutCoordinator(engines=fleet)
        co.begin(_perturb(params), version=1)
        _run(co, fleet)
    assert plan.events == [("weights.push", 2)]
    assert co.version == 0
    c = co.counters()
    assert c["rollout_rollbacks"] == 1 and c["rollout_commits"] == 0
    assert c["rollout_engines_gated"] == 0
    for eng in fleet:
        assert eng.params_snapshot() is params
        assert not eng.draining
    # the fleet still serves after convergence
    fleet[0].submit(9, np.arange(1, 9, dtype=np.int32), budget=4)
    while fleet[0].pending:
        fleet[0].step()


def test_drain_fault_rolls_back(fleet, setup):
    """engine.drain fault on the very first gate: no engine has been
    touched yet, but the coordinator still walks the rollback ladder
    and the fleet converges on OLD."""
    cfg, model, params = setup
    plan = FaultPlan({"engine.drain": {"at": 1}}, seed=0)
    with active_plan(plan):
        co = WeightRolloutCoordinator(engines=fleet)
        co.begin(_perturb(params), version=1)
        _run(co, fleet)
    assert plan.events == [("engine.drain", 1)]
    assert co.version == 0
    c = co.counters()
    assert c["rollout_rollbacks"] == 1 and c["rollout_commits"] == 0
    assert c["rollout_faults"] == 1
    assert c["rollout_canary_failures"] == 0
    for eng in fleet:
        assert eng.params_snapshot() is params
        assert not eng.draining


def test_canary_fault_rolls_back(fleet, setup):
    """engine.canary fault on the first upgraded engine: it already
    holds the NEW snapshot, so the rollback must reload OLD before
    readmitting — the torn state never commits."""
    cfg, model, params = setup
    plan = FaultPlan({"engine.canary": {"at": 1}}, seed=0)
    with active_plan(plan):
        co = WeightRolloutCoordinator(engines=fleet)
        co.begin(_perturb(params), version=1)
        _run(co, fleet)
    assert plan.events == [("engine.canary", 1)]
    assert co.version == 0
    c = co.counters()
    assert c["rollout_rollbacks"] == 1 and c["rollout_commits"] == 0
    assert c["rollout_canary_failures"] == 1
    for eng in fleet:
        assert eng.params_snapshot() is params
        assert not eng.draining


def test_engine_crash_mid_reload_rolls_back(fleet, setup, monkeypatch):
    """A real exception (not an injected one) out of the param swap —
    the engine 'crashed' mid-reload — takes the same rollback path."""
    cfg, model, params = setup
    orig = fleet[1].reload_weights
    calls = {"n": 0}

    def boom(p):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("engine crashed mid-reload")
        return orig(p)

    monkeypatch.setattr(fleet[1], "reload_weights", boom)
    co = WeightRolloutCoordinator(engines=fleet)
    co.begin(_perturb(params), version=3)
    _run(co, fleet)
    assert co.version == 0
    assert co.counters()["rollout_rollbacks"] == 1
    assert fleet[0].params_snapshot() is params
    assert fleet[1].params_snapshot() is params
    assert calls["n"] == 2          # failed roll + successful rollback


def test_canary_rejects_nan_weights(fleet, setup):
    """NaN weights pass the push but MUST die at the canary gate
    (non-finite logprobs) before the engine readmits — and the old
    weights come back."""
    cfg, model, params = setup
    co = WeightRolloutCoordinator(engines=fleet)
    co.begin(_perturb(params), version=1)        # records fingerprint
    _run(co, fleet)
    bad = jax.tree_util.tree_map(lambda x: jnp.full_like(x, jnp.nan),
                                 params)
    co2 = WeightRolloutCoordinator(engines=fleet)
    co2.begin(bad, version=2)
    _run(co2, fleet)
    assert co2.version == 0
    c = co2.counters()
    assert c["rollout_canary_failures"] >= 1
    assert c["rollout_rollbacks"] == 1
    assert c["rollout_engines_gated"] == 0
    # engine 1 never saw the bad snapshot; engine 0 rolled back
    assert not any(d[1] == "reload" and d[2][0] == 1
                   for d in co2.decisions)


def test_rollback_failure_gates_engine_off(fleet, setup):
    """Faults at hits 2 AND 3: the roll's second reload dies, then
    the ROLLBACK reload on engine 0 dies too — that engine may hold
    half-loaded weights, so it is gated off permanently while the
    rest of the fleet converges to old."""
    cfg, model, params = setup
    plan = FaultPlan({"weights.push": {"at": (2, 3)}}, seed=0)
    with active_plan(plan):
        co = WeightRolloutCoordinator(engines=fleet)
        co.begin(_perturb(params), version=1)
        _run(co, fleet)
    assert co.version == 0
    c = co.counters()
    assert c["rollout_engines_gated"] == 1
    assert ("gate-off" in [d[1] for d in co.decisions])
    assert fleet[0].draining                 # gated off, admits nothing
    assert fleet[1].params_snapshot() is params
    assert not fleet[1].draining


def test_halt_policy_stops_without_rollback(fleet, setup):
    """rollback_policy='halt': the failing engine is gated off and
    the roll STOPS — no automatic rollback, already-upgraded engines
    keep the new weights (operator decides)."""
    cfg, model, params = setup
    new = _perturb(params)
    plan = FaultPlan({"weights.push": {"at": 2}}, seed=0)
    with active_plan(plan):
        co = WeightRolloutCoordinator(
            engines=fleet, cfg=RolloutUpdateConfig(rollback_policy="halt"))
        co.begin(new, version=1)
        _run(co, fleet)
    c = co.counters()
    assert c["rollout_rollbacks"] == 0
    assert c["rollout_engines_gated"] == 1
    assert "halted" in [d[1] for d in co.decisions]
    assert co.version == 0                   # never committed
    assert fleet[0].params_snapshot() is new  # upgraded, kept
    assert fleet[1].draining                  # gated off


def test_coordinator_death_mid_fleet_recovers(fleet, setup):
    """Kill the coordinator (stop ticking, drop it) right after
    engine 1 entered DRAINING — mixed fleet, one engine gated.  A
    fresh coordinator re-pushing the retained old snapshot converges
    every engine back to OLD."""
    cfg, model, params = setup
    co = WeightRolloutCoordinator(engines=fleet)
    # in-flight work keeps engine 1 in DRAINING for multiple ticks,
    # so the coordinator can die mid-drain
    fleet[1].submit(21, np.arange(1, 13, dtype=np.int32), budget=8)
    co.begin(_perturb(params), version=1)
    for _ in range(200):
        co.tick()
        if fleet[1].draining and fleet[1].pending:
            break                    # coordinator dies HERE, mid-drain
        for e in fleet:
            if e.pending:
                e.step()
    else:
        pytest.fail("engine 1 never entered DRAINING")
    assert fleet[1].draining
    del co
    co2 = WeightRolloutCoordinator(engines=fleet)
    co2.begin(params, version=0)     # recovery push of the old snapshot
    _run(co2, fleet)
    assert co2.counters()["rollout_commits"] == 1
    for eng in fleet:
        assert eng.params_snapshot() is params
        assert not eng.draining


def test_faulty_roll_replays_bit_identically(setup):
    """Two fresh single-engine fleets, same seeded FaultPlan: the
    decision log, counters and fault-plan events must be IDENTICAL —
    the debuggability bar for every rollout post-mortem."""
    cfg, model, params = setup

    def one_run():
        eng = _mk(model, cfg, params, seed=7)
        plan = FaultPlan({"weights.push": {"at": 1}}, seed=0)
        with active_plan(plan):
            co = WeightRolloutCoordinator(engines=[eng])
            co.begin(_perturb(params), version=5)
            _run(co, [eng])
        return co.decisions, co.counters(), plan.events

    d1, c1, e1 = one_run()
    d2, c2, e2 = one_run()
    assert d1 == d2
    assert c1 == c2
    assert e1 == e2
    assert c1["rollout_rollbacks"] == 1


# -- gateway end-to-end: zero drops mid-trace --------------------------

def _pump_drain(gw, client, want, co=None, timeout=120.0):
    """Manually pump the gateway (deterministic interleaving) while
    collecting client stream events.  Returns (chunks, finals,
    done_counts, restarted_rids)."""
    chunks, finals, done_counts, restarted = {}, {}, {}, set()
    deadline = time.monotonic() + timeout
    while len(finals) < want or (co is not None and co.active):
        assert time.monotonic() < deadline, "gateway drain timed out"
        gw.step()
        while True:
            ev = client.next_event(timeout=0.005)
            if ev is None:
                break
            chunks.setdefault(ev.req_id, [])
            if ev.restarted:
                restarted.add(ev.req_id)
                chunks[ev.req_id] = []
            if ev.tokens.size:
                chunks[ev.req_id].append(ev.tokens)
            if ev.done:
                done_counts[ev.req_id] = done_counts.get(ev.req_id, 0) + 1
                finals[ev.req_id] = ev
    return chunks, finals, done_counts, restarted


def test_fleet_roll_mid_traffic_zero_drops(fleet, setup):
    """The acceptance bar: a 2-engine fleet behind one gateway rolls
    weights mid-trace.  Every submitted request gets EXACTLY ONE
    final (zero dropped, zero duplicated), chunks reassemble to the
    final tokens, and the roll commits with the rollout_* counters
    surfaced in gateway stats."""
    from orion_tpu.orchestration.gateway import GatewayClient, ServingGateway

    cfg, model, params = setup
    new = _perturb(params)
    gw = ServingGateway(fleet)
    co = WeightRolloutCoordinator(gateway=gw)
    cl = GatewayClient(gw.port, tenant="paid")
    try:
        rng = np.random.RandomState(3)
        rids = [cl.submit(rng.randint(1, cfg.vocab_size, 10)
                          .astype(np.int32), budget=6)
                for _ in range(3)]
        for _ in range(4):       # admit the first batch
            gw.step()
        co.begin(new, version=1)
        rids += [cl.submit(rng.randint(1, cfg.vocab_size, 10)
                           .astype(np.int32), budget=6)
                 for _ in range(3)]
        chunks, finals, done_counts, _ = _pump_drain(
            gw, cl, want=len(rids), co=co)
        assert sorted(finals) == sorted(rids)            # zero dropped
        assert all(n == 1 for n in done_counts.values())  # zero duped
        for rid in rids:
            ev = finals[rid]
            assert ev.error is None, ev
            got = (np.concatenate(chunks[rid]) if chunks[rid]
                   else np.empty(0, np.int32))
            np.testing.assert_array_equal(got, ev.completed.tokens)
            assert ev.completed.tokens.size == 6         # full budget
        assert co.version == 1
        assert gw.stats["rollout_commits"] >= 1.0
        # one drain cycle per engine of the fleet, no more
        assert gw.stats["rollout_drains"] == float(len(fleet))
        for eng in fleet:
            assert eng.params_snapshot() is new
    finally:
        cl.close()
        gw.close()


def test_drain_deadline_migrates_streams(fleet, setup):
    """Requests pinned on the draining engine past the deadline are
    migrated: the client sees a RESTARTED marker, then the full
    stream from the sibling engine — nothing dropped."""
    from orion_tpu.orchestration.gateway import GatewayClient, ServingGateway

    cfg, model, params = setup
    gw = ServingGateway(fleet)
    co = WeightRolloutCoordinator(
        gateway=gw, cfg=RolloutUpdateConfig(drain_deadline_ticks=1))
    cl = GatewayClient(gw.port, tenant="paid")
    try:
        gw.set_engine_admit(1, False)        # pin submits onto engine 0
        rng = np.random.RandomState(5)
        # two batches deep (max_batch_size=4): the queued half cannot
        # finish within the drain deadline, forcing a migration
        rids = [cl.submit(rng.randint(1, cfg.vocab_size, 12)
                          .astype(np.int32), budget=8)
                for _ in range(8)]
        # Hold the pump until all eight submits are queued, then apply
        # them in ONE step: pumping while they trickle in lets early
        # requests finish, and a count of pending never reaches 8.
        deadline = time.monotonic() + 60.0
        while gw._ops.qsize() < len(rids):
            assert time.monotonic() < deadline, "submits never arrived"
            time.sleep(0.005)
        gw.step()
        assert fleet[0].pending == len(rids) and fleet[1].pending == 0
        gw.set_engine_admit(1, True)
        co.begin(_perturb(params), version=1)
        chunks, finals, done_counts, restarted = _pump_drain(
            gw, cl, want=len(rids), co=co)
        assert gw.stats["rollout_migrations"] >= 1.0
        assert restarted                          # marker reached client
        assert sorted(finals) == sorted(rids)
        assert all(n == 1 for n in done_counts.values())
        for rid in rids:
            assert finals[rid].error is None, finals[rid]
            np.testing.assert_array_equal(
                np.concatenate(chunks[rid]),
                finals[rid].completed.tokens)
            assert finals[rid].completed.tokens.size == 8
        assert co.version == 1
    finally:
        cl.close()
        gw.close()


def test_gateway_close_wakes_blocked_client(fleet):
    """Satellite bugfix: a client blocked in ``next_event(None)``
    must get a typed GatewayClosed when the gateway drains away —
    not hang until the channel recv deadline."""
    from orion_tpu.orchestration.gateway import (GatewayClient,
                                                 GatewayClosed,
                                                 ServingGateway)

    gw = ServingGateway([fleet[0]])
    gw.start()
    cl = GatewayClient(gw.port, tenant="paid")
    box = {}

    def blocked():
        try:
            cl.next_event(timeout=None)
        except BaseException as e:  # noqa: BLE001 - under test
            box["exc"] = e

    t = threading.Thread(target=blocked, daemon=True)
    t.start()
    time.sleep(0.2)
    gw.close()
    t.join(timeout=10.0)
    assert not t.is_alive(), "client stayed blocked after gateway close"
    assert isinstance(box.get("exc"), GatewayClosed)
    assert isinstance(box["exc"], ConnectionError)  # typed close
    cl.close()


# -- prefill tier: stale KV offers dropped on version bump -------------

def test_stale_kv_offer_dropped_on_weight_reload(setup):
    """Satellite bugfix: a KV offer prefilled under weight version v
    must NOT inject once the decode engine reloads (v+1) — the
    request cold-prefills under the new weights instead, bit-exact
    with a single-engine run."""
    from orion_tpu.orchestration.prefill_tier import (PrefillTierCoordinator,
                                                      PrefillWorker)

    cfg, model, params = setup
    decode = _mk(model, cfg, params, seed=1)
    worker = PrefillWorker(_mk(model, cfg, params, seed=1), port=0)
    wt = threading.Thread(target=worker.serve, daemon=True)
    wt.start()
    coord = PrefillTierCoordinator(decode, worker.port)
    try:
        rng = np.random.RandomState(3)
        prompt = rng.randint(1, cfg.vocab_size, 14).astype(np.int32)
        coord.submit(0, prompt, budget=8)
        # weights roll AFTER the offer was cut: same values, new
        # version — the offer is now stale.
        decode.reload_weights(params)
        done = {}
        deadline = time.monotonic() + 60.0
        while not done:
            assert time.monotonic() < deadline, "tier drain hung"
            coord.pump()
            if decode.pending:
                for r in decode.step():
                    done[r.req_id] = r
            else:
                time.sleep(0.002)
        assert coord.stats["stale_offers"] == 1
        assert coord.stats["pages_injected"] == 0
        twin = _mk(model, cfg, params, seed=1)
        base = {r.req_id: r for r in twin.generate(
            [(0, prompt)], jax.random.key(1), params)}
        np.testing.assert_array_equal(done[0].tokens, base[0].tokens)
    finally:
        worker.close()
        wt.join(timeout=10.0)


# -- v7 ORTP: staged / commit / abort weight push ----------------------

def _wait_until(cond, timeout=15.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting: {msg}"
        time.sleep(0.01)


def test_pool_staged_commit_and_torn_abort():
    """The two-phase WEIGHTS push: staged params stay INACTIVE on the
    worker until the learner's commit frame; a push that never
    commits (torn) leaves the worker on the old version; abort drops
    the staged snapshot; a later full push still lands."""
    from orion_tpu.orchestration.remote import (PoolWorkerClient,
                                                WorkerPool)

    pool = WorkerPool(0, heartbeat_timeout=30.0)
    client = None
    try:
        client = PoolWorkerClient(pool.port, name="w0",
                                  heartbeat_interval=0.05,
                                  connect_timeout=20)
        _wait_until(lambda: len(pool.live_members()) == 1, msg="join")
        member = pool.live_members()[0]

        assert pool.push_weights({"w": np.ones(2)}, version=1,
                                 timeout=15.0)
        _wait_until(lambda: client._version == 1, msg="commit applied")
        assert member.acked_version >= 1

        # torn push: staged but never committed → worker stays on v1
        assert pool.broadcast_staged({"w": np.full(2, 2.0)}, 2) == 1
        _wait_until(lambda: member.staged_version == 2, msg="staged ack")
        assert client._version == 1
        assert client._staged is not None and client._staged[0] == 2

        pool._send_weights_ctl("abort", 2)
        _wait_until(lambda: client._staged is None, msg="abort applied")
        assert client._version == 1

        # a fault at the push boundary never reaches the wire
        plan = FaultPlan({"weights.push": {"at": 1}}, seed=0)
        with active_plan(plan):
            with pytest.raises(InjectedFault):
                pool.push_weights({"w": np.zeros(2)}, version=3)
        assert client._version == 1

        assert pool.push_weights({"w": np.zeros(2)}, version=4,
                                 timeout=15.0)
        _wait_until(lambda: client._version == 4, msg="second commit")
    finally:
        pool.shutdown()
