"""``benchmarks/host_spans.py`` and the ``program_span`` readers on a
recorded fixture: the span events of the ``/host:CPU`` plane of this
PR's first traced chip run of ``ppo1b-sync`` (TPU v5e, 2026-09-27; 54
events of one thread, three traced iterations; cut by
``host_spans.read_planes`` and nothing else), whose result line printed
``host_busy_ms.train`` 552.7359390000001 and ``host_wait_ms.train``
1742.456376 from the same file."""

import copy
import gzip
import json
import os
import types

import pytest

import bench_rehearsal as br

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "ppo1b_host_spans.json.gz")
MS = 1e6      # ns


@pytest.fixture(scope="module")
def hs():
    return br.lib("host_spans")


@pytest.fixture(scope="module")
def planes():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


def _ctx():
    return types.SimpleNamespace(lib=br.lib, out_dir="/nonexistent")


def _read(metric, spans, hs, monkeypatch):
    monkeypatch.setattr(hs, "of_run", lambda ctx: spans)
    return br.run_module().reader_of(metric).read({}, {}, _ctx())


def test_fixture_is_small():
    assert os.path.getsize(FIXTURE) < 4096


def test_window_whole_spans_and_the_chips_own_readings(planes, hs,
                                                       monkeypatch):
    spans = hs.from_planes(planes)
    assert len(spans.threads) == 1 and spans.n_spans == 54
    assert (spans.hi - spans.lo) / 1e9 == pytest.approx(6.838180684)
    its = spans.whole("train.iteration")
    assert [sp.stats["it"] for sp in its] == [4, 5, 6]
    # the profiler starts and stops inside next(prompt_iter): the batch
    # fetch is cut at both ends of the window, the iterations are whole
    assert len(spans.whole("data.next_batch")) == 2
    assert spans.before(its[0], "data.next_batch") == 0.0
    assert spans.before(its[1], "data.next_batch") == pytest.approx(
        0.27e-3, rel=0.05)
    waits = spans.per_parent("train.iteration", ("rollout.fetch",))
    assert [round(1e3 * w, 3) for w in waits] == [1694.651, 1742.456,
                                                  1742.782]
    # what the chip's result line printed from the whole 85 MB file
    assert _read("host_wait_ms.train", spans, hs, monkeypatch) == \
        pytest.approx(1742.456376, rel=1e-9)
    assert _read("host_busy_ms.train", spans, hs, monkeypatch) == \
        pytest.approx(552.7359390000001, rel=1e-9)
    # nothing to read: no number, no exception
    assert _read("sched_ms_per_wave.serve", spans, hs, monkeypatch) is None
    assert _read("harvest_wait_ms.serve", spans, hs, monkeypatch) is None


def test_self_time_is_duration_less_child_spans(planes, hs):
    spans = hs.from_planes(planes)
    thread = spans.threads[0][1]
    it = spans.whole("train.iteration")[1]
    kids = [sp for sp in thread if sp.parent >= 0
            and thread[sp.parent] is it]
    assert [k.name for k in kids] == ["experience", "update", "weight_sync"]
    assert it.self_ns == pytest.approx(it.dur - sum(k.dur for k in kids))
    # the one wait has no child span: all of it is its own
    fetch = [sp for sp in thread if sp.name == "rollout.fetch"][1]
    assert fetch.self_ns == fetch.dur
    rows = spans.by_name()
    assert rows["rollout.fetch"][spans.threads[0][0]]["count"] == 3
    total = rows["train.iteration"][spans.threads[0][0]]
    assert total["self_s"] < 1e-3 < total["total_s"]
    # the finding this trace brought: the experience "dispatch" takes as
    # long as the device needs for the experience programs
    exp = rows["experience.dispatch"][spans.threads[0][0]]
    assert exp["total_s"] / 3 == pytest.approx(0.5475, rel=0.01)


def test_clipping_to_a_narrower_window(planes, hs):
    full = hs.from_planes(planes)
    second = full.whole("train.iteration")[1]
    cut = copy.deepcopy(planes)
    for line in cut[1]["lines"]:
        for e in line["events"]:
            if e[0] == hs.WINDOW_SPAN:      # open 1 ms into iteration 5
                e[2] = e[1] + e[2] - (second.start + MS)
                e[1] = second.start + MS
    spans = hs.from_planes(cut)
    assert spans.lo == second.start + MS and spans.hi == full.hi
    # iteration 4 lies outside, 5 is cut, 6 is whole
    assert [sp.stats["it"] for sp in spans.whole("train.iteration")] == [6]
    label = spans.threads[0][0]
    row = spans.by_name()["train.iteration"][label]
    assert row["count"] == 2
    assert row["total_s"] == pytest.approx((spans.hi - spans.lo) / 1e9,
                                           rel=1e-3)
    assert spans.by_name()["rollout.fetch"][label]["count"] == 2


def test_threads_are_kept_apart(planes, hs):
    two = copy.deepcopy(planes)
    main = two[1]["lines"][0]
    # a second thread of the same name with one iteration of its own,
    # inside the window, whose wait is twice as long
    it = next(e for e in main["events"] if e[0] == "train.iteration"
              and e[3].get("it") == 5)
    two[1]["lines"].append({"name": main["name"], "events": [
        ["train.iteration", it[1] + MS, 1000 * MS, {"it": 99}],
        ["rollout.fetch", it[1] + 2 * MS, 600 * MS, {}]]})
    spans = hs.from_planes(two)
    labels = [label for label, _ in spans.threads]
    assert len(set(labels)) == 2 and all("python3" in x for x in labels)
    waits = spans.per_parent("train.iteration", ("rollout.fetch",))
    assert sorted(round(1e3 * w, 3) for w in waits) == [
        600.0, 1694.651, 1742.456, 1742.782]
    assert hs.median(waits) == pytest.approx(0.5 * (1.694651 + 1.742456),
                                             rel=1e-5)
    rows = spans.by_name()["rollout.fetch"]
    assert {r["count"] for r in rows.values()} == {1, 3}


def test_nest_inside_less_and_before_on_made_up_events(hs):
    ev = [["wave", 0, 100, {}], ["sched.extend", 10, 50, {}],
          ["wrapper", 12, 40, {}], ["engine.harvest", 20, 30, {}],
          ["sched.admit", 70, 10, {}], ["wave", 200, 10, {}]]
    assert hs.nest(ev) == [(40.0, -1), (10.0, 0), (10.0, 1), (30.0, 2),
                           (10.0, 0), (10.0, -1)]
    spans = hs.HostSpans([("t", ev + [["bench_window", 0, 1000, {}]])])
    first, second = spans.whole("wave")
    sched = ("sched.admit", "sched.extend")
    assert spans.inside(first, sched) == pytest.approx(60e-9)
    # by name: the wrapper between the span and its work takes nothing
    assert spans.inside(first, sched, less=("engine.harvest",)) == \
        pytest.approx(30e-9)
    assert spans.inside(second, sched) == 0.0
    assert spans.before(second, "sched.admit") == 0.0   # a wave between
    assert hs.median([3, 1, 2]) == 2 and hs.median([1, 2]) == 1.5


def test_what_is_a_span(hs):
    planes = [{"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ["train.iteration", 0, 10, {}], ["PjitFunction(f)", 1, 2, {}],
        ["np.asarray(jax.Array)", 3, 2, {}], ["$profiler.py:91 x", 5, 1, {}],
        ["fusion.3", 6, 1, {"hlo_module": "jit_f"}], ["update", 7, 1, {}]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["update", 0, 5, {}]]}]}]
    spans = hs.from_planes(planes)
    assert [sp.name for _, th in spans.threads for sp in th] == [
        "train.iteration", "update"]


def test_a_run_that_left_no_trace_reads_none(hs, tmp_path):
    ctx = types.SimpleNamespace(lib=br.lib, out_dir=str(tmp_path))
    assert hs.of_run(ctx) is None
    for metric in ("host_busy_ms.train", "host_wait_ms.train"):
        assert br.run_module().reader_of(metric).read({}, {}, ctx) is None
