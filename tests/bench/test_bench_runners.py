"""CPU rehearsal of each runner at a tiny shape handed over by the test
(never selectable from the command line), so the benchmark cannot rot
between chip runs: the last line's keys, the traced path through the
reduction and the per-layer readers, and the one way it must fail — no
TPU, no result line.  Kernel presence cannot hold on the CPU
(``Rehearsal.require_kernels=False``); tests/test_chip_compile.py
covers the kernels through the TPU compiler."""

import json

import pytest

import bench_rehearsal as br

LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _last_line(capsys):
    """The last line printed; with the reasons a run gave for not being
    correct (from the detail line before it) under ``_why``."""
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    last = json.loads(lines[-1])
    why = [json.loads(ln).get("why_incorrect") for ln in lines
           if ln.startswith('{"phase": "result_detail"')]
    return last, why


def _names(group, cell):
    run = br.run_module()
    return {m["name"] for m in run.metrics_of(br.manifest_with_unproven(),
                                              group, cell)}


def _cell_of(runner, loop=None):
    """A cell, listed or not yet proven, driven by ``runner`` (and
    loop)."""
    for w in br.manifest_with_unproven()["workloads"]:
        cell = br.read_json("cells", w["name"] + ".json")
        mix = br.read_json("traffic", cell["traffic"] + ".json")
        if cell["runner"] == runner and cell["chips"] == 1 \
                and (loop is None or mix.get("loop") == loop):
            return w["name"]
    pytest.skip(f"no one-chip cell uses runner {runner!r} loop {loop!r}")


@pytest.mark.parametrize("runner,loop", [
    ("train", None), ("serve", "open"), ("serve", "closed")])
def test_untraced_rehearsal_prints_the_contract_line(runner, loop, capsys):
    cell = _cell_of(runner, loop)
    line = br.rehearse(cell, seconds=2.0, trace=0)
    printed, why = _last_line(capsys)
    assert printed == json.loads(json.dumps(line))
    assert set(printed) == LAST_LINE_KEYS
    assert printed["correct"] is True, why
    assert printed["attempted"] > 0 and printed["failed"] == 0
    assert set(printed["metrics"]) == _names("end_to_end", cell)
    for m in printed["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert printed["device"]["platform"] == "tpu"     # steered
    assert "busy_s" not in printed["device"]


@pytest.mark.parametrize("runner,loop", [("train", None),
                                         ("serve", "closed")])
def test_traced_rehearsal_reports_layer_metrics(runner, loop, capsys):
    cell = _cell_of(runner, loop)
    br.rehearse(cell, seconds=2.0, trace=1)
    printed, why = _last_line(capsys)
    assert set(printed) == LAST_LINE_KEYS | {"breakdown"}
    assert printed["correct"] is True, why
    allowed = _names("per_layer", cell)
    assert printed["metrics"] and set(printed["metrics"]) <= allowed
    assert not set(printed["metrics"]) & _names("end_to_end", cell)
    dev = printed["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert set(printed["breakdown"]) == {"device_ops", "idle_gaps"}
    for rows in printed["breakdown"].values():
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in rows)


def test_sweep_rehearsal_finds_a_knee(capsys):
    """``sweep.py`` takes its context from ``run.py`` and its server
    from the serve runner: two rates at the tiny shape, one table row
    each, and the knee as the last line."""
    run = br.run_module()
    sweep = br.load(br.os.path.join(br.BENCH, "sweep.py"), "orionbench_sweep")
    cell = _cell_of("serve", "open")
    sweep.main(["--workload", cell, "--start", "4", "--steps", "2",
                "--seconds", "1.5", "--seed", "7"],
               rehearsal=run.Rehearsal(config=br.tiny_config(cell),
                                       traffic=br.tiny_traffic(cell),
                                       device=dict(br.FAKE_DEVICE)))
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    rows = [ln for ln in lines if "offered_per_s" in ln]
    assert [r["rate_per_s"] for r in rows] == [4.0, 5.0]
    assert all(r["completed_per_s"] > 0 and not r["compiled_in_window"]
               for r in rows)
    assert set(lines[-1]) == {"knee_per_s", "cell_rate_per_s"}


def test_no_tpu_exits_nonzero_without_a_result_line(capsys):
    """What the command does on a machine without the chip: jax is held
    to the CPU here, and there is no fallback."""
    run = br.run_module()
    cell = br.manifest()["workloads"][0]["name"]
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    assert e.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out


def test_wrong_chip_count_exits_nonzero(monkeypatch, capsys):
    h = br.lib("harness")
    with pytest.raises(SystemExit) as e:
        h.require_device(3)
    assert e.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out


def test_unknown_cell_exits_nonzero(capsys):
    with pytest.raises(SystemExit) as e:
        br.run_module().main(["--workload", "no-such-cell", "--seed", "1",
                              "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out


def test_a_device_kind_without_a_peak_is_an_error():
    """peaks.json is keyed by device_kind; an unknown kind raises, it
    does not fall back to some default peak."""
    run = br.run_module()
    reader = run.load_module(
        br.os.path.join(br.BENCH, "layer_metrics", "mfu_pct.train.py"),
        "orionbench_metric_mfu_probe")
    trace = {"by_program": {"jit__epochs_fn": {
        "s": 3.0, "runs": 3, "median_s": 1.0, "period_s": 2.0}}}
    counters = {"model": br.read_json("configs", "pythia-1b.json"),
                "samples_per_iteration": 48, "prompt_len": 256,
                "new_tokens": 128, "num_epochs": 1, "chips": 1,
                "device_kind": "TPU v5 lite"}

    class Ctx:
        lib = staticmethod(br.lib)

    mfu = reader.read(trace, counters, Ctx())
    # 48 samples x 384 tokens x (1+2+3) forwards x ~1.83 GFLOP in 2 s
    assert 45 < mfu < 60
    with pytest.raises(KeyError, match="no peak"):
        reader.read(trace, dict(counters, device_kind="TPU v9"), Ctx())
