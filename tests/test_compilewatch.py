"""The compile watch and the set-up account (ISSUE 51,
``orion_tpu/obs/compilewatch.py``): counts, names, sums and orderings
only, no wall-clock assertion; every test under a time limit of its
own."""

import contextlib
import glob
import io
import json
import logging
import re
import signal
import threading
import time

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring

from orion_tpu import obs
from orion_tpu.analysis.runtime_guards import RecompileSentinel
from orion_tpu.obs import compilewatch

LIMIT_S = 300


@pytest.fixture(autouse=True)
def time_limit():
    """A test that outlasts ``LIMIT_S`` fails there and then (the suite's
    workers run tests on their main thread)."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def expired(signum, frame):
        raise TimeoutError(f"over the test's limit of {LIMIT_S} s")

    prev = signal.signal(signal.SIGALRM, expired)
    signal.alarm(LIMIT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)


def _listeners():
    return (list(monitoring.get_event_duration_listeners()),
            list(monitoring.get_event_listeners()))


@contextlib.contextmanager
def _persistent_cache(directory):
    """jax's persistent cache at ``directory`` with both thresholds at
    zero, or off (``None``); as found afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    want = {"jax_enable_compilation_cache": directory is not None,
            "jax_compilation_cache_dir": directory,
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": 0}
    prev = {k: getattr(jax.config, k) for k in want}
    for k, v in want.items():
        jax.config.update(k, v)
    cc.reset_cache()
    try:
        yield
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
        cc.reset_cache()


def _fresh(name):
    """A jitted function nobody has compiled, under ``name``."""
    def fn(x):
        return jnp.sin(x) @ x + 1.0
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# (a) totals, programs, names
# ---------------------------------------------------------------------------


def test_a_compile_advances_the_clock_in_all_three_kinds():
    fn = _fresh("watched_program_a")
    x = jnp.ones((5, 5))
    sentinel = RecompileSentinel(budget=10).install()
    hold = obs.install_compile_watch()
    try:
        before = obs.compile_totals()
        fn(x)
        after = obs.compile_totals()
        fn(x)       # from jit's own cache: no event
        assert obs.compile_totals() == after
    finally:
        hold.uninstall()
        sentinel.uninstall()
    d = after.since(before)
    assert d.programs >= 1
    assert d.trace_s > 0 and d.lower_s > 0 and d.compile_s + d.load_s > 0
    assert d.seconds == pytest.approx(
        d.trace_s + d.lower_s + d.compile_s + d.load_s)
    p = obs.compile_programs()["watched_program_a"]
    assert p["count"] == p["traces"] == p["lowers"] == 1
    assert p["trace_s"] > 0 and p["lower_s"] > 0 and p["backend_s"] > 0
    assert p["hit"] + p["miss"] + p["off"] == 1
    # the name the sentinel gives it, and its two counts
    assert sentinel.counts["watched_program_a"] == 1
    assert sentinel.total_compiles == d.programs
    kinds = [e["kind"] for e in obs.compile_events()
             if e["fun"] == "watched_program_a"]
    assert kinds[-2:] == ["lower", "backend"]   # a short trace is not kept


def test_events_nest_and_the_clock_is_their_union():
    """A program's trace holds its callees': the thread's seconds are
    self times, so they never exceed the wall they lie in, where the
    durations jax reports sum to more."""
    inner = _fresh("watched_inner")

    @jax.jit
    def watched_outer(x):
        return inner(x) * 2.0

    with obs.install_compile_watch():
        before = obs.compile_totals()
        with obs.timed("wall") as sp:
            watched_outer(jnp.ones((6, 6)))
        d = obs.compile_totals().since(before)
    progs = obs.compile_programs()
    outer, inn = progs["watched_outer"], progs["watched_inner"]
    assert inn["traces"] == 1 and inn["count"] == 0    # traced inline
    assert outer["trace_s"] >= inn["trace_s"]
    assert outer["self_s"] <= (outer["trace_s"] + outer["lower_s"]
                               + outer["backend_s"]) - inn["trace_s"] + 1e-9
    assert 0 < d.seconds <= sp.duration


# ---------------------------------------------------------------------------
# (b) the cache outcome reaches its program
# ---------------------------------------------------------------------------


def test_cache_outcomes_hit_miss_and_off(tmp_path):
    x = jnp.ones((3, 3))
    with obs.install_compile_watch():
        with _persistent_cache(str(tmp_path)):
            fn = _fresh("watched_cached")
            t0 = obs.compile_totals()
            fn(x)
            t1 = obs.compile_totals()
            jax.clear_caches()
            fn(x)
            t2 = obs.compile_totals()
        with _persistent_cache(None):
            off = _fresh("watched_uncached")
            off(x)
    first, second = t1.since(t0), t2.since(t1)
    assert first.misses >= 1 and first.hits == 0 and first.compile_s > 0
    assert second.hits >= 1 and second.misses == 0
    assert second.load_s > 0 and second.compile_s == 0   # unmoved
    p = obs.compile_programs()
    assert (p["watched_cached"]["miss"], p["watched_cached"]["hit"]) == (1, 1)
    assert p["watched_uncached"]["off"] == 1
    assert p["watched_uncached"]["hit"] + p["watched_uncached"]["miss"] == 0
    outcomes = [e["cache"] for e in obs.compile_events()
                if e["fun"] == "watched_cached" and e["kind"] == "backend"]
    assert outcomes == ["miss", "hit"]


# ---------------------------------------------------------------------------
# (c) holds, (d) a failing listener
# ---------------------------------------------------------------------------


def test_holds_share_one_pair_of_listeners():
    found = _listeners()
    a = obs.install_compile_watch()
    grown = _listeners()
    assert [len(g) - len(f) for g, f in zip(grown, found)] == [1, 1]
    b = obs.install_compile_watch()
    assert _listeners() == grown
    a.uninstall()
    a.uninstall()       # idempotent: takes nothing from b
    assert _listeners() == grown
    before = obs.compile_totals()
    _fresh("watched_held")(jnp.ones((2, 2)))
    assert obs.compile_totals().programs > before.programs
    with b:             # a hold is a context manager too
        pass
    assert _listeners() == found
    still = obs.compile_totals()
    _fresh("watched_unheld")(jnp.ones((2, 2)))
    assert obs.compile_totals() == still    # the clock stands


def test_a_failing_listener_does_not_fail_the_compile(monkeypatch, caplog):
    watch = obs._COMPILE

    def broken(*a, **k):
        raise RuntimeError("planted")

    def bad_observer(kind, name):
        raise ValueError("planted too")

    errors = watch.errors
    with obs.install_compile_watch(bad_observer):
        out = _fresh("watched_observer_fails")(jnp.ones((2, 2)))
        assert float(out[0, 0]) == pytest.approx(2.0 * 0.8414709848 + 1.0)
        assert "watched_observer_fails" in obs.compile_programs()
        monkeypatch.setattr(watch, "_record", broken)
        with caplog.at_level(logging.ERROR):
            out = _fresh("watched_record_fails")(jnp.ones((2, 2)))
        assert float(out[0, 0]) == pytest.approx(2.0 * 0.8414709848 + 1.0)
    assert watch.errors > errors
    assert "watched_record_fails" not in obs.compile_programs()


# ---------------------------------------------------------------------------
# (g) the cap
# ---------------------------------------------------------------------------


def test_the_events_cap_holds_and_the_sums_stay_complete(monkeypatch):
    watch = obs.CompileWatch(obs.get_tracer)
    monkeypatch.setattr(compilewatch, "MAX_EVENTS", 5)
    now = time.monotonic()
    for i in range(9):
        watch._record("lower", "jit(capped)", now + i, 0.25)
        watch._record("trace", "capped", now + i + 0.5, 0.001)   # not kept
    assert len(watch.events()) == 5 and watch.dropped == 4
    p = watch.programs()["capped"]
    assert p["lowers"] == p["traces"] == 9
    assert p["lower_s"] == pytest.approx(9 * 0.25)
    assert p["trace_s"] == pytest.approx(9 * 0.001)
    t = watch.totals()
    assert t.lower_s == pytest.approx(9 * 0.25)
    assert t.seconds == pytest.approx(p["self_s"])


def test_self_times_by_planted_intervals():
    """trace [0, 10] holds a trace [1, 3] and an eager lowering and
    backend compile [4, 5], [5, 7]; a lowering [10, 12] follows."""
    watch = obs.CompileWatch(obs.get_tracer)
    for kind, fun, end, dur in (
            ("trace", "callee", 3.0, 2.0), ("lower", "jit(eager)", 5.0, 1.0),
            ("backend", "jit(eager)", 7.0, 2.0), ("trace", "f", 10.0, 10.0),
            ("lower", "jit(f)", 12.0, 2.0)):
        watch._record(kind, fun, end, dur)
    t = watch.totals()
    assert (t.programs, t.hits, t.misses) == (1, 0, 0)
    assert t.trace_s == pytest.approx(2.0 + 5.0)
    assert t.lower_s == pytest.approx(1.0 + 2.0)
    assert t.compile_s == pytest.approx(2.0) and t.load_s == 0.0
    assert t.seconds == pytest.approx(12.0)     # the union
    p = watch.programs()
    assert p["f"]["trace_s"] == 10.0 and p["f"]["self_s"] == pytest.approx(7.0)
    assert p["eager"]["off"] == 1


# ---------------------------------------------------------------------------
# (h) what the loop's one more read costs
# ---------------------------------------------------------------------------


def test_the_off_path_cost_of_the_loops_read():
    """A steady iteration reads the clock once more and no listener
    runs: the read stays under two microseconds of the thread's CPU
    time (steadied as tests/test_obs.py's budget is: windows in which
    the kernel took the thread off the CPU do not count while any other
    is there)."""
    import resource

    def preempted():
        return resource.getrusage(resource.RUSAGE_THREAD).ru_nivcsw

    n = 10_000
    quiet, every = [], []
    with obs.install_compile_watch():
        for _ in range(30):
            before = preempted()
            sp = obs.timed("budget-window")
            with sp:
                for _ in range(n):
                    obs.compile_totals()
            every.append(sp.cpu / n)
            if preempted() == before:
                quiet.append(sp.cpu / n)
    best = min(quiet or every)
    print(f"compile_totals(): {best * 1e6:.3f} us of thread CPU "
          f"({len(quiet)} of 30 windows undisturbed)")
    assert best < 2e-6, (best, len(quiet))


# ---------------------------------------------------------------------------
# (e) the tiny trainer's rows and its one setup row; the sentinel's
# counts against the log line they used to be parsed from; the export
# ---------------------------------------------------------------------------


class _LogLineCounts(logging.Handler):
    """``RecompileSentinel.counts`` as PR 50 took them: ``jax_log_compiles``
    on, "Compiling jit(<name>) with global shapes" parsed out of the
    records of the ``jax`` logger.  Kept here as the reference the
    watch's count of lowerings is held against."""

    _RE = re.compile(r"^Compiling (?:jit\()?([^\s()]+)\)? with global shapes")

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.counts = {}

    def emit(self, record):
        m = self._RE.match(record.getMessage())
        if m:
            self.counts[m.group(1)] = self.counts.get(m.group(1), 0) + 1

    def __enter__(self):
        self._prev = bool(jax.config.jax_log_compiles)
        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(self)
        return self

    def __exit__(self, *exc):
        logging.getLogger("jax").removeHandler(self)
        jax.config.update("jax_log_compiles", self._prev)


SYNC_PHASES = ("setup.config", "setup.mesh", "setup.build_trainer",
               "setup.resume", "setup.remat_probe")


@pytest.fixture(scope="module")
def tiny_job(tmp_path_factory):
    """Four iterations of a tiny PPO job through ``launch.main`` with
    the ring on: (rows, metrics.jsonl's records, stderr, the Chrome
    export's events, the old and the new sentinel's counts)."""
    from orion_tpu import launch

    log_dir = tmp_path_factory.mktemp("tiny_job")
    found = _listeners()
    stderr = io.StringIO()
    sentinel = RecompileSentinel(budget=10 ** 9).install()
    try:
        with _LogLineCounts() as old, contextlib.redirect_stderr(stderr):
            rows = launch.main([
                "ppo", "model_preset=tiny", "model.remat=true",
                "share_backbone=true", "total_iterations=4",
                "rollout_batch_size=4", "minibatch_size=4",
                "rollout.max_prompt_len=8", "rollout.max_new_tokens=4",
                "data.dataset=synthetic", "reward=length", "log_every=0",
                f"log_dir={log_dir}", "obs.trace=true",
                "obs.ring_size=16384"])
    finally:
        sentinel.uninstall()
    assert _listeners() == found     # main's hold and the trainer's: gone
    with open(log_dir / "metrics.jsonl") as f:
        records = [json.loads(line) for line in f]
    spans, = glob.glob(str(log_dir / "spans-*.json"))
    with open(spans) as f:
        chrome = json.load(f)["traceEvents"]
    return {"rows": rows, "records": records, "stderr": stderr.getvalue(),
            "chrome": chrome, "old": old.counts,
            "new": dict(sentinel.counts), "total": sentinel.total_compiles}


def _setup_rows(job):
    return [r for r in job["records"] if r.get("setup")]


def test_every_row_accounts_for_its_compiles(tiny_job):
    rows = tiny_job["rows"]
    assert [r["iteration"] for r in rows] == [0, 1, 2, 3]   # no setup entry
    assert all("loss" in r for r in rows)
    assert rows[0]["compiles"] > 0 and rows[0]["compile_s"] > 0
    assert 0 <= rows[0]["cache_misses"] <= rows[0]["compiles"]
    assert rows[0]["compile_s"] <= rows[0]["iter_s"]
    steady = next(i for i, r in enumerate(rows) if not r["compile_s"])
    for r in rows[steady:]:
        assert r["compile_s"] == 0 and r["compiles"] == 0
        assert r["cache_misses"] == 0
    # the watch saw no more backend compiles than the sentinel, which
    # was installed first
    assert sum(r["compiles"] for r in rows) <= tiny_job["total"]


def test_exactly_one_setup_row_and_it_closes(tiny_job):
    row, = _setup_rows(tiny_job)
    line, = [json.loads(x) for x in tiny_job["stderr"].splitlines()
             if x.startswith('{"setup"')]
    assert line["total_s"] == row["total_s"] and line["top"] == row["top"]
    rows = tiny_job["rows"]
    steady = next(i for i, r in enumerate(rows) if not r["compile_s"])
    assert row["iteration"] == steady
    # under the iteration's global step, beside that iteration's row
    assert row["step"] == steady + 1
    parts = (sum(p["s"] for p in row["phases"].values()) + row["trace_s"]
             + row["lower_s"] + row["compile_s"] + row["load_s"]
             + row["warm_run_s"] + row["unaccounted_s"])
    assert parts == pytest.approx(row["total_s"], rel=1e-9, abs=1e-9)
    assert set(SYNC_PHASES) <= set(row["phases"])
    assert all(p["s"] >= 0 and p["cpu_s"] >= 0
               for p in row["phases"].values())
    assert row["warm_run_s"] >= 0
    assert row["programs"] >= sum(r["compiles"] for r in rows) > 0
    assert row["hits"] + row["misses"] <= row["programs"]
    starts = row["iteration_starts_s"]
    assert len(starts) == steady + 2 and starts == sorted(starts)
    assert starts[steady] == pytest.approx(row["total_s"])
    assert 0 < len(row["top"]) <= 10
    names = [t["fun"] for t in row["top"]]
    assert {"_epochs_fn", "_generate"} <= set(names)
    secs = [t["trace_s"] + t["lower_s"] + t["backend_s"] for t in row["top"]]
    assert secs == sorted(secs, reverse=True)
    assert all(m["backend_s"] >= compilewatch.SLOW_MISS_S
               for m in row["missed"])


def test_the_setup_row_is_no_point_of_the_iterations_series(tmp_path):
    """It shares ``compile_s`` and a step with the steady iteration's
    row: ``metrics.jsonl`` gets both, the scalar series only the
    iteration's."""
    from orion_tpu.utils.metrics import MetricsWriter

    class Series:
        def __init__(self):
            self.points = []

        def write_scalars(self, step, scalars):
            self.points.append((step, dict(scalars)))

        def flush(self):
            pass

    with MetricsWriter(str(tmp_path), tensorboard=False) as w:
        w._tb = series = Series()
        w.write(2, {"iteration": 1, "compile_s": 0.0})
        w.write(2, {"setup": 1, "compile_s": 64.2, "top": [{"fun": "f"}],
                    "phases": {"setup.mesh": {"s": 0.1}}}, jsonl_only=True)
    assert series.points == [(2, {"iteration": 1.0, "compile_s": 0.0})]
    with open(tmp_path / "metrics.jsonl") as f:
        first, second = [json.loads(line) for line in f]
    assert first["compile_s"] == 0.0 and "setup" not in first
    assert second["setup"] == 1 and second["top"] == [{"fun": "f"}]
    assert second["phases"]["setup.mesh"]["s"] == 0.1 and second["step"] == 2


def test_the_sentinel_counts_what_the_log_line_counted(tiny_job):
    """``counts[name]`` is the watch's count of lowerings of
    ``jit(name)``: key for key what parsing "Compiling <name> with
    global shapes" gave, over the whole job."""
    assert tiny_job["new"] == tiny_job["old"]
    assert tiny_job["new"]["_epochs_fn"] >= 1
    assert tiny_job["total"] >= len(tiny_job["new"])


def test_the_chrome_export_shows_setup_as_it_happened(tiny_job):
    row, = _setup_rows(tiny_job)
    xs = [e for e in tiny_job["chrome"] if e["ph"] == "X"]
    by_name = {}
    for e in xs:
        by_name.setdefault(e["name"], []).append(e)

    def inside(e, outer, slack=2e3):    # microseconds
        return (outer["ts"] - slack <= e["ts"]
                and e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + slack)

    phases = {}
    for name in row["phases"]:
        spans = by_name[name]
        assert all(e["dur"] >= 0 for e in spans)
        phases[name] = spans[0]
    # (setup.import is the process's first job's alone: an earlier test
    # of this worker may have run one)
    order = [n for n in ("setup.import", "setup.config", "setup.mesh",
                         "setup.build_trainer", "setup.resume",
                         "setup.remat_probe") if n in phases]
    assert order[-5:] == list(SYNC_PHASES)
    stamps = [phases[n]["ts"] for n in order]
    assert stamps == sorted(stamps)
    it0 = min(by_name["train.iteration"], key=lambda e: e["ts"])
    assert phases["setup.resume"]["ts"] <= it0["ts"]
    assert inside(phases["setup.remat_probe"], it0)
    compiles = [e for e in xs if e["name"].startswith("compile.")]
    assert {e["name"] for e in compiles} == {
        "compile.trace", "compile.lower", "compile.backend"}
    assert all(e["args"]["fun"] for e in compiles)
    assert all(e["args"]["cache"] in ("hit", "miss", "off")
               for e in by_name["compile.backend"])
    # the update compiles inside iteration 0, the model's init inside
    # setup.build_trainer; nothing compiles after the steady iteration
    upd, = [e for e in by_name["compile.backend"]
            if e["args"]["fun"] == "_epochs_fn"]
    assert inside(upd, it0)
    built = phases["setup.build_trainer"]
    assert any(inside(e, built) for e in by_name["compile.backend"])
    steady = sorted(by_name["train.iteration"],
                    key=lambda e: e["ts"])[int(row["iteration"])]
    assert all(e["ts"] + e["dur"] <= steady["ts"] + 2e3 for e in compiles)
    # every kept event of the job is there once
    assert len(compiles) == len({(e["name"], e["ts"], e["args"]["fun"])
                                 for e in compiles})


def test_train_iteration_carries_its_compiles_on_the_ring(tiny_job):
    its = sorted((e for e in tiny_job["chrome"]
                  if e["name"] == "train.iteration"), key=lambda e: e["ts"])
    assert len(its) == 4
    for e, r in zip(its, tiny_job["rows"]):
        assert {"compile_us", "compiles", "gc_us", "gc_n"} <= set(e["args"])
        assert e["args"]["compiles"] == r["compiles"]
        assert e["args"]["compile_us"] == round(r["compile_s"] * 1e6)


# ---------------------------------------------------------------------------
# (f) under a profiler session, on the annotation
# ---------------------------------------------------------------------------


def test_train_iteration_carries_its_compiles_on_the_annotation(tmp_path):
    from test_obs import _profiled
    from test_trainers import (GRPOConfig, GRPOTrainer, _mk, _policy,
                               lucky_token_reward, prompt_stream)

    cfg = _mk(GRPOConfig, group_size=2, kl_coef=0.0, num_epochs=1,
              rollout_batch_size=4, minibatch_size=4)
    model, params = _policy()
    tr = GRPOTrainer(cfg, model, params, reward_fn=lucky_token_reward)
    try:
        events = _profiled(
            tmp_path, lambda: tr.train(prompt_stream(2, 5),
                                       num_iterations=2))
        rows = tr.metrics_history
    finally:
        tr.close()
    its = sorted(events["train.iteration"], key=lambda e: e[1])
    assert len(its) == len(rows) == 2
    for (_, _, _, stats), row in zip(its, rows):
        assert int(stats["compiles"]) == row["compiles"]
        assert int(stats["compile_us"]) == round(row["compile_s"] * 1e6)
    assert int(its[0][3]["compiles"]) > 0
