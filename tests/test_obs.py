"""orion_tpu.obs (ISSUE 9): span nesting/ids, ring wraparound,
Perfetto-schema validity, cross-process trace stitching over a real
pool, flight-recorder dumps (worker death, degrade, injected fault,
SIGUSR1), histogram percentile math, MetricsWriter lifecycle, the
continuous engine's request telemetry, the disabled-tracing overhead
budget, and (ISSUE 25) the bridge to the profiler: every span is also a
``jax.profiler.TraceAnnotation`` while a profiler session records."""

import gc
import glob
import json
import os
import signal
import threading
import time

import jax
import numpy as np
import pytest

from orion_tpu import obs
from orion_tpu.config import GRPOConfig, ModelConfig, RolloutConfig
from orion_tpu.models import Transformer, init_params
from orion_tpu.obs import (FlightRecorder, RequestTelemetry, Tracer,
                           merge_chrome_traces)
from orion_tpu.orchestration import PoolOrchestrator, WorkerPool
from orion_tpu.resilience import FaultPlan, InjectedFault, active_plan, \
    clear_plan
from orion_tpu.rollout.continuous import ContinuousBatchingEngine
from orion_tpu.trainers import GRPOTrainer
from orion_tpu.utils.metrics import Counter, Histogram, MetricsWriter

from test_trainers import (lucky_token_reward, prompt_stream, _mk,
                           tiny_model_cfg)
from test_worker_pool import FakeWorker, P, _mk_trainer, _wait_until


# ---------------------------------------------------------------------------
# tracing core
# ---------------------------------------------------------------------------


def test_span_nesting_ids_and_adoption():
    t = Tracer(ring_size=64, enabled=True)
    with t.span("outer", phase="a") as outer:
        with t.span("inner") as inner:
            assert inner.parent_id == outer.span_id
        t.instant("tick", x=1)
    evs = t.events()
    names = [e["name"] for e in evs]
    assert names == ["inner", "tick", "outer"]
    by_name = {e["name"]: e for e in evs}
    assert by_name["inner"]["parent"] == by_name["outer"]["span"]
    assert by_name["tick"]["span"] == by_name["outer"]["span"]
    assert by_name["outer"]["parent"] == 0
    assert len({e["trace"] for e in evs}) == 1
    assert by_name["outer"]["dur"] >= by_name["inner"]["dur"] >= 0.0
    # cross-process adoption rewrites the trace id for later spans
    t.adopt_trace(12345)
    with t.span("adopted"):
        pass
    assert t.events()[-1]["trace"] == 12345
    assert (12345, 0) == t.context()


def test_ring_buffer_wraparound_keeps_last_n_in_order():
    t = Tracer(ring_size=8, enabled=True)
    for i in range(20):
        t.instant(f"e{i}", i=i)
    evs = t.events()
    assert [e["name"] for e in evs] == [f"e{i}" for i in range(12, 20)]


def test_chrome_export_is_valid_trace_event_json(tmp_path):
    t = Tracer(ring_size=32, enabled=True, pid=777, name="proc-a")
    with t.span("work", detail="x"):
        t.instant("mark")
    path = t.export_chrome(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    assert isinstance(evs, list) and len(evs) == 3  # meta + 2 events
    for e in evs:
        assert {"name", "ph", "pid"} <= set(e)
        assert e["ph"] in ("X", "i", "M")
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["ts"] > 0
            assert {"trace_id", "span_id", "parent_id"} <= set(e["args"])
        if e["ph"] == "M":
            assert e["args"]["name"] == "proc-a"
        assert e["pid"] == 777
    json.dumps(doc)  # round-trips


def test_disabled_span_is_a_shared_noop_but_timed_measures():
    t = Tracer(ring_size=16, enabled=False)
    # The off path: tracer disabled AND no profiler session recording
    # (under one, the same call opens a profiler annotation — see
    # test_disabled_span_reaches_the_profiler_trace).
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert t.span("a") is t.span("b")  # allocation-free singleton
    with t.span("a") as sp:
        pass
    assert sp.duration == 0.0
    with t.timed("b") as sp:
        time.sleep(0.01)
    assert sp.duration >= 0.005  # measured even with tracing off
    assert t.events() == []      # ...but nothing recorded
    assert t.context() == (0, 0)


# ---------------------------------------------------------------------------
# the bridge to the profiler (ISSUE 25): spans on the device trace's clock
# ---------------------------------------------------------------------------


def _profiled(tmp_path, body):
    """Run ``body()`` under a jax.profiler session (Python's own call
    tracer off, as the benchmark's traced run has it) and return the
    host events of the xplane: {name: [(thread, start, end, stats)]}."""
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                events.setdefault(e.name, []).append(
                    (i, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)))
    return events


def _program_spans(t):
    with t.span("outer", it=3, tag="abc"):
        with t.span("inner") as sp:
            time.sleep(0.002)
            sp.set(rows=2, bytes=1234)   # known only when the work is done
        with t.timed("lap") as lap:
            pass
    assert lap.duration >= 0.0


def test_disabled_span_reaches_the_profiler_trace(tmp_path):
    """obs.trace off: the ring stays empty, yet a span opened while a
    profiler session records lies in the /host:CPU plane with its name,
    nesting and attributes — the program finds out by itself."""
    t = Tracer(ring_size=16, enabled=False)
    before = t.span("not-yet")          # no session yet: the singleton
    events = _profiled(tmp_path, lambda: _program_spans(t))
    assert before is t.span("again")    # and the singleton again after
    assert t.events() == []
    (th_o, o0, o1, o_stats), = events["outer"]
    (th_i, i0, i1, i_stats), = events["inner"]
    (th_l, l0, l1, _), = events["lap"]
    assert th_o == th_i == th_l                     # one thread's line
    assert o0 <= i0 and i1 <= l0 and l1 <= o1       # nested, in order
    assert i1 - i0 >= 1e6                           # the 2 ms sleep, in ns
    # every span carries its thread's CPU time beside its attributes
    assert 0 <= o_stats.pop("cpu_us") <= (o1 - o0) / 1e3 + 100
    assert 0 <= i_stats.pop("cpu_us") < 1000        # it slept its 2 ms
    assert o_stats == {"it": 3, "tag": "abc"}
    assert i_stats == {"rows": 2, "bytes": 1234}


def test_enabled_span_is_in_the_ring_and_in_the_profiler_trace(tmp_path):
    t = Tracer(ring_size=16, enabled=True)
    events = _profiled(tmp_path, lambda: _program_spans(t))
    ring = {e["name"]: e for e in t.events()}
    assert set(ring) == {"outer", "inner", "lap"}
    assert ring["inner"]["parent"] == ring["outer"]["span"]
    assert ring["inner"]["attrs"] == {"rows": 2, "bytes": 1234}
    assert set(events) >= {"outer", "inner", "lap"}
    (_, _, _, stats), = events["inner"]
    assert stats.pop("cpu_us") == round(ring["inner"]["cpu"] * 1e6)
    assert stats == {"rows": 2, "bytes": 1234}
    # the two records agree on the duration (different clocks, one scope)
    (_, i0, i1, _), = events["inner"]
    assert abs((i1 - i0) / 1e9 - ring["inner"]["dur"]) < 1e-3


def test_no_session_no_ring_event_and_the_off_path_budget():
    """Tracer off and nothing recording: no event anywhere, and a span
    costs under the microsecond that obs/trace.py's docstring states.
    The budget is about instructions, so the loop is measured in the
    thread's CPU time (a neighbour's load is not the span's cost), in
    fifty windows of 10^4 (the 5 x 10^5 spans it always ran), and a
    window in which the kernel took the thread off the CPU (its caches
    come back cold) does not count while any other is there: the suite
    runs under six workers on a shared machine."""
    import resource

    def preempted():
        return resource.getrusage(resource.RUSAGE_THREAD).ru_nivcsw

    assert not jax.profiler.TraceAnnotation.is_enabled()
    prev = obs.set_tracer(None)     # the disabled default
    try:
        t = obs.get_tracer()
        assert not t.enabled
        n = 10_000
        quiet, every = [], []
        for _ in range(50):
            before = preempted()
            sp = obs.timed("budget-window")
            with sp:
                for _ in range(n):
                    with obs.span("x", a=1):
                        pass
            every.append(sp.cpu / n)
            if preempted() == before:
                quiet.append(sp.cpu / n)
        best = min(quiet or every)
        print(f"off-path span: {best * 1e6:.3f} us of thread CPU "
              f"({len(quiet)} of 50 windows undisturbed)")
        assert t.events() == []
        assert best < 1e-6, (best, len(quiet))
    finally:
        obs.set_tracer(prev)


def test_span_start_and_end_share_one_clock():
    """The trainer loop's stamps are starts and ends of spans."""
    t = Tracer(ring_size=4, enabled=False)
    with t.timed("a") as a:
        time.sleep(0.002)
    with t.timed("b") as b:
        pass
    assert a.end == pytest.approx(a.start + a.duration)
    assert a.start < a.end <= b.start <= b.end


# ---------------------------------------------------------------------------
# the thread's CPU clock on every measuring span (ISSUE 36)
# ---------------------------------------------------------------------------

_CPU_RESOLUTION = time.get_clock_info("thread_time").resolution + 1e-6


def _sleep():
    time.sleep(0.05)


def _spin():
    # until the thread has BURNT 50 ms: under six workers on a shared
    # machine a loop of 50 ms of wall got 25 ms of the CPU
    end = time.thread_time() + 0.05
    while time.thread_time() < end:
        pass


@pytest.mark.parametrize("enabled", [False, True], ids=["timed", "ring"])
@pytest.mark.parametrize("body, burns", [(_sleep, False), (_spin, True)],
                         ids=["sleep", "spin"])
def test_a_measuring_span_reads_the_threads_cpu_clock(body, burns, enabled):
    """Wall is not work: ~0 across a sleep, all that a busy loop burnt
    across it, never more than the wall."""
    t = Tracer(ring_size=4, enabled=enabled)
    with t.timed("x") as sp:
        body()
    assert 0.0 <= sp.cpu <= sp.duration + _CPU_RESOLUTION
    if burns:
        assert sp.cpu >= 0.05
    else:
        assert sp.cpu < 0.01
    if enabled:
        ev, = t.events()
        assert ev["cpu"] == sp.cpu and ev["dur"] == sp.duration
        chrome = [e for e in t.chrome_events() if e["ph"] == "X"]
        assert chrome[0]["tdur"] == pytest.approx(sp.cpu * 1e6)
    else:
        # off and nothing recording: still the shared no-op, no clock read
        assert t.span("x") is t.span("y")
        assert not hasattr(t.span("x"), "cpu")


# ---------------------------------------------------------------------------
# the collector's hook (ISSUE 36): counted always, gen 2 is a span
# ---------------------------------------------------------------------------


def _gc_hooks():
    return [cb for cb in gc.callbacks
            if getattr(cb, "__self__", None) is obs._GC]


def test_a_full_collection_is_a_span_in_the_ring_and_in_the_xplane(tmp_path):
    """A forced ``gc.collect()`` under a recording profiler session:
    one ``host.gc`` with ``gen`` 2, nested in the span it interrupted,
    in both records; young collections are counted and are no spans."""
    t = Tracer(ring_size=64, enabled=True)
    prev = obs.set_tracer(t)
    hold = obs.install_gc_watch()
    gc.disable()           # the two collections below and no other
    n0, s0 = obs.gc_totals()

    def body():
        with obs.span("outer"):
            gc.collect(0)
            gc.collect()
    try:
        events = _profiled(tmp_path, body)
        n1, s1 = obs.gc_totals()
    finally:
        gc.enable()
        hold.uninstall()
        obs.set_tracer(prev)
    assert n1 - n0 == 2 and s1 > s0      # both counted, one a span
    ring = {e["name"]: e for e in t.events()}
    assert [e["name"] for e in t.events()] == ["host.gc", "outer"]
    assert ring["host.gc"]["parent"] == ring["outer"]["span"]
    assert ring["host.gc"]["attrs"]["gen"] == 2
    assert ring["host.gc"]["attrs"]["collected"] >= 0
    # a collection burns the CPU for its whole length
    assert ring["host.gc"]["cpu"] <= ring["host.gc"]["dur"] + _CPU_RESOLUTION
    (th_g, g0, g1, g_stats), = events["host.gc"]
    (th_o, o0, o1, _), = events["outer"]
    assert th_g == th_o and o0 <= g0 and g1 <= o1
    assert g_stats["gen"] == 2 and {"collected", "cpu_us"} <= set(g_stats)
    by_gen = obs._GC.stats()
    assert by_gen[2][0] >= 1 and by_gen[2][2] <= by_gen[2][1]
    assert by_gen[0][0] >= 1


def test_the_gc_hook_is_one_and_goes_with_the_last_trainer():
    """Installed at construction whether or not ``obs.trace`` is on,
    not doubled by a second trainer, gone after the last ``close()``,
    which is idempotent; ``gc.callbacks`` is left as it was found."""
    before = list(gc.callbacks)
    assert _gc_hooks() == []
    cfg = _mk(GRPOConfig, group_size=2, kl_coef=0.0, num_epochs=1,
              minibatch_size=4)
    assert not cfg.obs.trace
    model = Transformer(cfg.model)
    params = init_params(model, jax.random.key(0), cfg.model)
    a = GRPOTrainer(cfg, model, params, reward_fn=lucky_token_reward,
                    eos_token_id=None)
    b = GRPOTrainer(cfg, model, params, reward_fn=lucky_token_reward,
                    eos_token_id=None)
    try:
        assert len(_gc_hooks()) == 1
        a.close()
        a.close()
        assert len(_gc_hooks()) == 1     # b still holds it
        n0, _ = obs.gc_totals()
        gc.collect()
        assert obs.gc_totals()[0] >= n0 + 1
    finally:
        a.close()
        b.close()
    assert _gc_hooks() == [] and list(gc.callbacks) == before
    n0, _ = obs.gc_totals()
    gc.collect()
    assert obs.gc_totals()[0] == n0      # the clock stands still
    b.close()


def test_session_end_writes_the_ring_once(tmp_path):
    """obs.trace on and a directory: the spans kept in memory are
    written as spans-<pid>.json when the session ends (trainer.close(),
    run_serve's exit), once."""
    cfg = _mk(GRPOConfig, log_dir=str(tmp_path / "m"))
    cfg.obs.trace = True
    session = obs.install_from_config(cfg)
    try:
        with obs.span("experience", it=0):
            obs.instant("tick")
    finally:
        session.uninstall()
    path = session.spans_path
    assert path == str(tmp_path / "m" / f"spans-{os.getpid()}.json")
    with open(path) as f:
        doc = json.load(f)
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] != "M"]
    assert names == ["tick", "experience"]
    os.remove(path)
    session.uninstall()              # idempotent: not written again
    assert not os.path.exists(path)
    assert not obs.get_tracer().enabled


# ---------------------------------------------------------------------------
# histogram / counter / MetricsWriter
# ---------------------------------------------------------------------------


def test_histogram_percentile_math_and_bounded_memory():
    h = Histogram()
    for v in range(1, 101):
        h.record(v)
    assert h.percentile(50) == 50
    assert h.percentile(95) == 95
    assert h.percentile(99) == 99
    assert h.mean == pytest.approx(50.5)
    s = h.summary("lat")
    assert s["lat_p95"] == 95 and s["lat_count"] == 100.0
    # bounded: the ring keeps the most recent window, count stays exact
    hb = Histogram(max_samples=10)
    for v in range(1000):
        hb.record(v)
    assert hb.count == 1000
    assert hb.percentile(50) >= 990  # recent window only
    assert len(hb._vals) == 10


def test_metrics_writer_expands_histograms_and_counters(tmp_path):
    h = Histogram()
    for v in (1.0, 2.0, 3.0):
        h.record(v)
    with MetricsWriter(str(tmp_path), tensorboard=False) as w:
        w.write(3, {"loss": 0.5, "wait": h, "deaths": Counter(2),
                    "profile_dir": "/tmp/prof"})
    rec = json.loads(
        open(os.path.join(str(tmp_path), "metrics.jsonl")).read())
    assert rec["step"] == 3 and rec["loss"] == 0.5
    assert rec["wait_p50"] == 2.0 and rec["wait_count"] == 3.0
    assert rec["deaths"] == 2.0
    assert rec["profile_dir"] == "/tmp/prof"  # jsonl-only annotation


def test_tenant_labelled_metrics_through_writer(tmp_path):
    """ISSUE 12 satellite: per-tenant Counter/Histogram state flows
    through RequestTelemetry as ``tenant_<name>_<metric>`` keys and
    expands into _p50/_p95/_p99 columns via MetricsWriter.write with
    NO writer plumbing — and reset() clears every tenant key."""
    from orion_tpu.obs import RequestTelemetry

    tel = RequestTelemetry()
    for rid, tenant in ((1, "paid"), (2, "free"), (3, "pa id!")):
        tel.mark(rid, "submit", tenant=tenant)
        tel.mark(rid, "admit")
        tel.mark(rid, "first_token")
        tel.finish(rid, 4)
    tel.record_shed("free")
    hists = tel.histograms()
    assert "tenant_paid_ttft_s" in hists
    assert "tenant_pa_id__queue_wait_s" in hists  # label sanitized
    with MetricsWriter(str(tmp_path), tensorboard=False) as w:
        w.write(1, {**hists, **tel.counters()})
    rec = json.loads(
        open(os.path.join(str(tmp_path), "metrics.jsonl")).read())
    for col in ("_p50", "_p95", "_p99", "_mean", "_count"):
        assert f"tenant_paid_ttft_s{col}" in rec
        assert f"tenant_free_queue_wait_s{col}" in rec
    assert rec["tenant_paid_ttft_s_count"] == 1.0
    assert rec["tenant_free_shed"] == 1.0
    assert rec["tenant_paid_finished"] == 1.0
    assert rec["requests_shed"] == 1.0
    # the flat summary() carries the same keys (bench JSON shape)
    summ = tel.summary()
    assert summ["tenant_paid_ttft_s_p95"] > 0.0
    tel.reset()
    assert not any(k.startswith("tenant_")
                   for k in {**tel.histograms(), **tel.counters()})
    assert tel.summary()["requests_shed"] == 0.0


def test_metrics_writer_lifecycle(tmp_path):
    w = MetricsWriter(str(tmp_path), tensorboard=False)
    w.write(0, {"a": 1})
    w.close()
    w.close()  # idempotent
    assert w.closed
    with pytest.raises(ValueError, match="closed"):
        w.write(1, {"a": 2})


# ---------------------------------------------------------------------------
# cross-process stitching + flight recorder over a real pool
# ---------------------------------------------------------------------------


def test_pool_chaos_merged_trace_and_flight_recorder(tmp_path):
    """ISSUE 9 acceptance: a seeded pool run (2 workers, 1 injected
    ``worker.traj`` fault) produces a single merged Perfetto-loadable
    trace whose spans cover learner + both workers under ONE trace id,
    and the fault's ladder transition (worker death) produces a
    flight-recorder dump naming it."""
    tL = Tracer(ring_size=4096, enabled=True, pid=1000, name="learner")
    prev_tracer = obs.set_tracer(tL)
    rec = FlightRecorder(str(tmp_path / "fr"), tracer=tL)
    prev_rec = obs.install_flight_recorder(rec)
    workers = []
    pool = WorkerPool(0, heartbeat_timeout=30.0)
    try:
        cfg, trainer = _mk_trainer(tmp_path, checkpoint_every=100)
        orch = PoolOrchestrator(trainer, pool)
        tws = [Tracer(ring_size=4096, enabled=True, pid=2001 + r,
                      name=f"worker-{r}") for r in range(2)]
        # staleness=0: each worker sends exactly one batch ahead of
        # consumption, so traj hits interleave with training.  The
        # plan arms only around train() — the workers' pre-train
        # staging sends must not burn its hit counter.
        workers.append(FakeWorker(pool.port, 0, staleness=0,
                                  tracer=tws[0]))
        pool.wait_for_workers(1, timeout=20)
        workers.append(FakeWorker(pool.port, 1, staleness=0,
                                  tracer=tws[1]))
        pool.wait_for_workers(2, timeout=20)
        _wait_until(lambda: all(m.produced >= 1
                                for m in pool.live_members()),
                    msg="both workers to stage their first batch")
        plan = FaultPlan({"worker.traj": {"at": 3}}, seed=0)
        with active_plan(plan):
            history = orch.train(prompt_stream(2, P), num_iterations=6)
        assert len(history) == 6
        assert plan.events == [("worker.traj", 3)]
        assert pool.recovery["worker_deaths"] == 1

        # every worker adopted the learner's trace id via the HELLO ack
        for tw in tws:
            assert tw.trace_id == tL.trace_id

        paths = [tL.export_chrome(str(tmp_path / "learner.json"))]
        paths += [tw.export_chrome(str(tmp_path / f"w{i}.json"))
                  for i, tw in enumerate(tws)]
        merged = merge_chrome_traces(paths, str(tmp_path / "merged.json"))
        doc = json.load(open(merged))
        evs = doc["traceEvents"]
        spans = [e for e in evs if e["ph"] == "X"]
        gen = [e for e in spans if e["name"] == "rollout.generate"]
        it = [e for e in spans if e["name"] == "learner.iter"]
        assert {e["pid"] for e in gen} == {2001, 2002}
        assert {e["pid"] for e in it} == {1000}
        # ONE trace id spans all three process tracks
        tids = {e["args"]["trace_id"] for e in gen + it}
        assert tids == {str(tL.trace_id)}
        # the learner linked consume events to worker generate spans
        consume = [e for e in evs if e["name"] == "learner.consume"]
        gen_ids = {e["args"]["span_id"] for e in gen}
        assert any(e["args"]["parent_id"] in gen_ids for e in consume)

        # the ladder transition hit the flight recorder
        assert rec.dumps, "worker death did not dump"
        dump = json.load(open(rec.dumps[-1]))
        assert dump["reason"] == "worker-death"
        assert "degradation-ladder" in dump["extra"]["transition"]
        assert dump["traceEvents"], "dump must be replayable in Perfetto"
    finally:
        pool.shutdown(goodbye=True)
        obs.install_flight_recorder(prev_rec)
        obs.set_tracer(prev_tracer)
    for w in workers:
        w.thread.join(timeout=20)


def test_flight_recorder_dumps_on_degrade(tmp_path):
    """The empty-pool → degrade-to-sync rung dumps a timeline naming
    the transition."""
    tL = Tracer(ring_size=2048, enabled=True)
    prev_tracer = obs.set_tracer(tL)
    rec = FlightRecorder(str(tmp_path / "fr"), tracer=tL)
    prev_rec = obs.install_flight_recorder(rec)
    pool = WorkerPool(0, heartbeat_timeout=30.0)
    try:
        cfg, trainer = _mk_trainer(tmp_path, checkpoint_every=100,
                                   degrade_to_sync=True, rejoin_grace=0.3)
        orch = PoolOrchestrator(trainer, pool)
        plan = FaultPlan({"worker.traj": {"at": 3}}, seed=0)
        with active_plan(plan):
            w = FakeWorker(pool.port, 0, staleness=0)
            pool.wait_for_workers(1, timeout=20)
            history = orch.train(prompt_stream(2, P, seed=9),
                                 num_iterations=6)
        w.thread.join(timeout=20)
        assert len(history) == 6
        reasons = [json.load(open(p))["reason"] for p in rec.dumps]
        assert "worker-death" in reasons and "degrade" in reasons
        degrade = json.load(open(rec.dumps[reasons.index("degrade")]))
        assert "degradation-ladder" in degrade["extra"]["transition"]
        # the injected fault left its marker on the dumped timeline of
        # at least one dump (the worker-death one fires right after)
        death = json.load(open(rec.dumps[reasons.index("worker-death")]))
        assert any(e["name"].startswith("pool.")
                   for e in death["traceEvents"])
    finally:
        pool.shutdown()
        obs.install_flight_recorder(prev_rec)
        obs.set_tracer(prev_tracer)


def test_flight_recorder_dump_on_injected_generate_fault(tmp_path):
    """Config-armed obs + a seeded ``rollout.generate`` fault: the
    exception escaping BaseTrainer.train dumps before re-raising, and
    the dump carries the fault's own timeline marker."""
    log_dir = str(tmp_path / "metrics")
    cfg = _mk(GRPOConfig, group_size=2, kl_coef=0.0, num_epochs=1,
              minibatch_size=4, log_dir=log_dir)
    cfg.obs.trace = True
    cfg.obs.ring_size = 512
    cfg.resilience.fault_plan = "rollout.generate:at=2"
    model = Transformer(cfg.model)
    params = init_params(model, jax.random.key(0), cfg.model)
    trainer = GRPOTrainer(cfg, model, params,
                          reward_fn=lucky_token_reward, eos_token_id=None)
    try:
        assert obs.get_tracer().enabled  # config armed the tracer
        with pytest.raises(InjectedFault):
            trainer.train(prompt_stream(2, 4), num_iterations=4)
        dumps = sorted(glob.glob(os.path.join(log_dir, "flightrec-*.json")))
        assert dumps, "no flight-recorder dump written"
        doc = json.load(open(dumps[-1]))
        assert doc["reason"] == "unhandled-exception"
        assert "InjectedFault" in doc["extra"]["error"]
        names = [e["name"] for e in doc["traceEvents"]]
        assert "fault.rollout.generate" in names
        assert "experience" in names  # the loop's spans made the ring
    finally:
        trainer.close()
        clear_plan()
    # close() restored the process globals
    assert not obs.get_tracer().enabled
    assert obs.current_flight_recorder() is None
    assert trainer.writer is None  # trainer exit routed through close


def test_sigusr1_triggers_dump(tmp_path):
    t = Tracer(ring_size=64, enabled=True)
    t.instant("before-signal")
    rec = FlightRecorder(str(tmp_path), tracer=t).install(
        excepthook=False, sigusr1=True)
    try:
        os.kill(os.getpid(), signal.SIGUSR1)
        deadline = time.monotonic() + 5.0
        while not rec.dumps and time.monotonic() < deadline:
            time.sleep(0.01)
        assert rec.dumps
        doc = json.load(open(rec.dumps[0]))
        assert doc["reason"] == "SIGUSR1"
        assert any(e["name"] == "before-signal"
                   for e in doc["traceEvents"])
    finally:
        rec.uninstall()


# ---------------------------------------------------------------------------
# config session wiring
# ---------------------------------------------------------------------------


def test_obs_session_install_and_close_restores(tmp_path):
    cfg = _mk(GRPOConfig, group_size=2, kl_coef=0.0, num_epochs=1,
              minibatch_size=4, log_dir=str(tmp_path / "m"))
    cfg.obs.trace = True
    prev = obs.get_tracer()
    model = Transformer(cfg.model)
    params = init_params(model, jax.random.key(0), cfg.model)
    trainer = GRPOTrainer(cfg, model, params,
                          reward_fn=lucky_token_reward, eos_token_id=None)
    try:
        assert obs.get_tracer() is trainer._obs.tracer
        assert obs.current_flight_recorder() is trainer._obs.recorder
        assert obs.get_tracer() is not prev
    finally:
        trainer.close()
    assert obs.get_tracer() is prev
    assert obs.current_flight_recorder() is None
    trainer.close()  # idempotent


# ---------------------------------------------------------------------------
# continuous-engine request telemetry + overhead budget
# ---------------------------------------------------------------------------


def _tiny_engine(max_new=10, slots=2):
    mc = ModelConfig.tiny(dtype="float32")
    model = Transformer(mc)
    params = init_params(model, jax.random.key(0), mc)
    rcfg = RolloutConfig(max_prompt_len=12, max_new_tokens=max_new,
                         temperature=0.0, page_size=4,
                         max_batch_size=slots)
    eng = ContinuousBatchingEngine(model, mc, rcfg, eos_token_id=None,
                                   segment_len=4)
    eng.load_weights(params)
    return mc, eng


def test_continuous_engine_request_telemetry():
    mc, eng = _tiny_engine()
    rng = np.random.RandomState(0)
    reqs = [(i, rng.randint(1, mc.vocab_size, rng.randint(3, 12)))
            for i in range(6)]
    eng.generate(reqs, jax.random.key(1))
    tel = eng.telemetry
    assert tel.queue_wait_s.count == 6
    assert tel.ttft_s.count == 6
    assert tel.tok_per_s.count >= 1
    assert tel.finished.value == 6
    occ = [tel.page_occupancy.percentile(50),
           tel.page_occupancy.percentile(99)]
    assert all(0.0 <= v <= 1.0 for v in occ)
    stats = eng.server_stats()
    for key in ("queue_wait_s_p95", "ttft_s_p99", "tok_per_s_p50",
                "page_occupancy_mean", "requests_finished",
                "requests_preempted", "preempted_requests",
                "prefix_cached_pages", "page_pool_size",
                "cancelled_requests", "spec_accept_ema"):
        assert key in stats, key
    assert stats["requests_finished"] == 6.0
    assert stats["page_pool_size"] == float(eng.num_pages)
    assert stats["cancelled_requests"] == 0.0
    assert stats["spec_accept_ema"] == 0.0   # spec decoding off here
    eng.reset_server_stats()
    assert eng.server_stats()["requests_finished"] == 0.0
    assert eng.telemetry.queue_wait_s.count == 0


def test_disabled_tracing_overhead_budget():
    """Tracing disabled ⇒ the instrumented serve loop pays effectively
    nothing: the no-op span path is so cheap that thousands of times
    the loop's actual obs touchpoints still fit inside 1% of its
    wall-clock.  The touchpoints' cost is the thread's CPU time over
    the loop (a neighbour's load is not the span's cost)."""
    t = obs.get_tracer()
    assert not t.enabled  # the default process tracer is off
    mc, eng = _tiny_engine(max_new=32, slots=4)
    rng = np.random.RandomState(3)
    n_req = 16

    def serve(seed, base):
        reqs = [(base + i,
                 rng.randint(1, mc.vocab_size, rng.randint(3, 12)))
                for i in range(n_req)]
        sp = obs.timed("serve")  # tests may time freely; use obs anyway
        with sp:
            eng.generate(reqs, jax.random.key(seed))
        return sp.duration

    serve(1, 0)            # warm: compiles out of the window
    wall = min(serve(2, 100), serve(3, 200))

    n = 20_000
    sp = obs.timed("noop-window")
    with sp:
        for _ in range(n):
            with obs.span("x", a=1):
                pass
            obs.instant("y", b=2)
    per_call = sp.cpu / (2 * n)
    # Upper bound on obs touchpoints inside one measured serve(): one
    # engine.step span per wave (~n_req*32/seg/slots ≈ 32 waves) +
    # ~5 lifecycle instants per request ≈ 112 — bound at 4x that.
    assert per_call * 450 < 0.01 * wall, (per_call, wall)
