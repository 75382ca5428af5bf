"""The ``program_span`` readers through the runners' CPU rehearsals: the
program's own spans (``orion_tpu.obs`` -> ``TraceAnnotation``) reach the
xplane of a traced run with nothing passed to the program, nest as the
trainer loop and the engine wave nest them, and the readers turn them
into numbers.  What is printed here is never a measurement."""

import json

import pytest

import bench_rehearsal as br


def _traced(cell, capsys, monkeypatch, tmp_path):
    # The runner writes its trace under <REPO>/chiprun_out/bench/<cell>;
    # other test files rehearse the same cells in other xdist workers,
    # so this one keeps its traces in a directory of its own.
    monkeypatch.setattr(br.run_module(), "REPO", str(tmp_path))
    br.rehearse(cell, seconds=2.0, trace=1)
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    printed = json.loads(lines[-1])
    why = [json.loads(ln).get("why_incorrect") for ln in lines
           if ln.startswith('{"phase": "result_detail"')]
    assert printed["correct"] is True, why
    hs = br.lib("host_spans")
    h = br.lib("harness")
    out_dir = br.os.path.join(str(tmp_path), "chiprun_out", "bench", cell)
    spans = hs.load(h.Tracer(True, out_dir + "/trace").xplane_path())
    return printed["metrics"], spans, hs


def _children(spans, parent):
    """Names of the spans nested (at any depth) in ``parent``."""
    return [sp.name for sp in spans.threads[parent.thread][1]
            if sp is not parent and sp.start >= parent.start
            and sp.end <= parent.end]


def test_manifest_lists_the_two_trainer_span_metrics():
    per_layer = {m["name"]: m for m in br.manifest()["per_layer"]}
    for name in ("host_busy_ms.train", "host_wait_ms.train"):
        m = per_layer[name]
        assert m["source"] == "program_span" and m["unit"] == "ms"
        assert m["moves"] == "train_samples_per_s"
        assert m["layer"] == per_layer["update_ms.train"]["layer"]
    # appended: the entries PR 24 listed come first, in their order
    assert list(per_layer)[-2:] == ["host_busy_ms.train",
                                    "host_wait_ms.train"]


def test_train_rehearsal_reports_host_busy_and_wait(capsys, monkeypatch,
                                                    tmp_path):
    metrics, spans, hs = _traced("ppo1b-sync", capsys, monkeypatch,
                                 tmp_path)
    busy = metrics["host_busy_ms.train"]["value"]
    wait = metrics["host_wait_ms.train"]["value"]
    assert busy > 0 and wait > 0
    its = spans.whole("train.iteration")
    assert len(its) == 2          # trace_iterations of the tiny job, whole
    # the two readings add up to the iteration's wall: the spans cover
    # the loop (median of sums against the sum of medians: two samples)
    walls = [it.dur / 1e9 + spans.before(it, "data.next_batch")
             for it in its]
    assert busy + wait == pytest.approx(1e3 * hs.median(walls), rel=1e-6)
    # every row of the trainer part of the span table, once an iteration
    for it in its:
        inside = _children(spans, it)
        for name in ("experience", "rollout.dispatch", "rollout.fetch",
                     "stats.finalize", "reward.score",
                     "experience.dispatch", "update", "weight_sync"):
            assert inside.count(name) == 1, (name, inside)
        exp, = [sp for sp in spans.threads[it.thread][1]
                if sp.name == "experience" and sp.start >= it.start
                and sp.end <= it.end]
        assert {"rollout.dispatch", "rollout.fetch", "stats.finalize",
                "reward.score", "experience.dispatch"} <= set(
                    _children(spans, exp))
        assert "update" not in _children(spans, exp)
    # the batch fetch is a sibling before the iteration, never inside it
    assert all("data.next_batch" not in _children(spans, it) for it in its)
    assert any(spans.before(it, "data.next_batch") > 0 for it in its)


def test_serve_rehearsal_nests_the_wave_and_both_readers_read(
        capsys, monkeypatch, tmp_path):
    metrics, spans, hs = _traced("serve1b-arrivals", capsys, monkeypatch,
                                 tmp_path)
    assert metrics["sched_ms_per_wave.serve"]["value"] > 0
    assert metrics["harvest_wait_ms.serve"]["value"] >= 0
    waves = spans.whole("engine.step")
    assert waves
    seen = set()
    for wave in waves:
        inside = _children(spans, wave)
        assert inside.count("sched.admit") == 1
        assert inside.count("sched.extend") == 1
        seen |= set(inside)
        # a wave is pumped by the gateway: gw.step is its ancestor
        thread = spans.threads[wave.thread][1]
        assert any(sp.name == "gw.step" and sp.start <= wave.start
                   and wave.end <= sp.end for sp in thread)
    assert {"sched.admit", "engine.prefill_wave", "sched.extend",
            "engine.segment", "engine.harvest"} <= seen
    admits = [sp for sp in spans.threads[waves[0].thread][1]
              if sp.name == "sched.admit"]
    assert admits[0].stats["impl"] in ("native", "python")
    assert {"admitted", "waiting"} <= set(admits[0].stats)
    # the scheduler's share is its spans less a harvest nested in them
    one = waves[0]
    assert spans.inside(one, ("sched.admit", "sched.extend"),
                        less=("engine.harvest",)) <= spans.inside(
                            one, ("sched.admit", "sched.extend"))
