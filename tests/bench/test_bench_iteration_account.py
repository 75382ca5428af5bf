"""The iteration's account of its wall in the benchmark (ISSUE 36): the
three ``program_span`` entries in the manifest, looked up by name, and
their readers through the ``train`` runner's traced CPU rehearsal of
``ppo1b-sync``.  What is printed here is never a measurement."""

import json
import math

import pytest

import bench_rehearsal as br

CELL = "ppo1b-sync"
NEW = ("host_cpu_ms.train", "fetch_copy_ms.train", "host_gc_ms.train")


def _entries():
    m = br.manifest()
    return m, {p["name"]: p for p in m["per_layer"]}


@pytest.mark.parametrize("name", NEW)
def test_the_entry_is_in_the_manifest_as_specified(name):
    m, per_layer = _entries()
    p = per_layer[name]
    loop = per_layer["host_busy_ms.train"]
    assert p == {"name": name, "unit": "ms", "better": "lower",
                 "source": "program_span", "layer": loop["layer"],
                 "moves": "train_samples_per_s",
                 "workloads": loop["workloads"]}
    # the train cells, all of them, each reporting the metric it moves
    moved = next(e for e in m["end_to_end"] if e["name"] == p["moves"])
    assert set(p["workloads"]) == set(moved.get(
        "workloads", [w["name"] for w in m["workloads"]]))
    # appended: after every entry the accepted manifest had
    names = [q["name"] for q in m["per_layer"]]
    assert names.index(name) > names.index("gdn_chunk_roofline_pct.train")
    # found by the stem, as host_busy_ms is
    reader = br.run_module().reader_of(name)
    assert reader.__name__.endswith(name.split(".", 1)[0])


def test_a_program_without_the_spans_gives_nothing_to_read(monkeypatch):
    """The parent's traced run: the readers return None, no error.  The
    recorded fixture of PR 32 is such a program's trace."""
    hs = br.lib("host_spans")
    fixture = br.read_json("..", "tests", "bench", "fixtures",
                           "kimi_linear_spans.json")
    spans = hs.from_planes(fixture["planes"])
    assert len(spans.whole("train.iteration")) == 3
    monkeypatch.setattr(hs, "of_run", lambda ctx: spans)

    class Ctx:
        lib = staticmethod(br.lib)

    for name in NEW:
        assert br.run_module().reader_of(name).read({}, {}, Ctx()) is None
    # the accepted readers do read it
    assert br.run_module().reader_of("host_wait_ms.train").read(
        {}, {}, Ctx()) == pytest.approx(3300.0)


def test_the_traced_rehearsal_reads_all_three(capsys, monkeypatch, tmp_path):
    monkeypatch.setattr(br.run_module(), "REPO", str(tmp_path))
    br.rehearse(CELL, seconds=2.0, trace=1)
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    printed = json.loads(lines[-1])
    assert printed["correct"] is True
    got = {k: v["value"] for k, v in printed["metrics"].items()}
    for name in NEW:
        assert math.isfinite(got[name]) and got[name] >= 0.0, (name, got)
    wall = got["host_busy_ms.train"] + got["host_wait_ms.train"]
    assert 0.0 < got["host_cpu_ms.train"] <= wall
    # the copy is a part of the fetch
    assert got["fetch_copy_ms.train"] <= got["host_wait_ms.train"]
    assert got["host_gc_ms.train"] <= wall

    hs, h = br.lib("host_spans"), br.lib("harness")
    out_dir = br.os.path.join(str(tmp_path), "chiprun_out", "bench", CELL)
    spans = hs.load(h.Tracer(True, out_dir + "/trace").xplane_path())
    its = spans.whole("train.iteration")
    assert its
    thread = spans.threads[its[0].thread][1]
    # every span of the program carries the thread's CPU time, at most
    # its wall (the profiler's clock and the program's differ by reads)
    program = [sp for sp in thread if sp.name != hs.WINDOW_SPAN
               and any(it.start <= sp.start and sp.end <= it.end
                       for it in its)
               and sp.name in ("train.iteration", "experience",
                               "rollout.dispatch", "rollout.fetch",
                               "fetch.wait", "fetch.copy", "stats.finalize",
                               "reward.score", "experience.dispatch",
                               "update", "weight_sync")]
    assert len(program) >= 11 * len(its)
    for sp in program:
        assert 0 <= float(sp.stats["cpu_us"]) <= sp.dur / 1e3 + 200, sp
    for it in its:
        assert {"gc_us", "gc_n", "nivcsw", "majflt"} <= set(it.stats)
        fetch = spans.inside(it, ("rollout.fetch",))
        parts = spans.inside(it, ("fetch.wait", "fetch.copy"))
        assert parts <= fetch <= parts * 1.01 + 2e-4
        wait, = [sp for sp in thread if sp.name == "fetch.wait"
                 and it.start <= sp.start and sp.end <= it.end]
        assert 0 <= float(wait.stats["update_ready_us"]) <= wait.dur / 1e3
