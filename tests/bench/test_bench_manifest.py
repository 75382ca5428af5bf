"""``BENCHMARK.json`` against its contract, and the data-driven layout:
every name within the allowed characters and lengths; every cell's
configuration, traffic, runner and per-layer readers found by name;
every cell that reports a per-layer metric reports the end-to-end metric
it ``moves``; at most one cell on four chips; ``run.py`` names no cell,
configuration, mix, runner or metric in code; the generators are
deterministic in ``--seed`` and never read the program's config."""

import os
import re

import numpy as np
import pytest

import bench_rehearsal as br

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def m():
    return br.manifest()


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 \
        and "\n" not in text and "\t" not in text


def test_top_level_shape(m):
    assert set(m) == TOP_KEYS
    assert m["command"][-1] == "benchmarks/run.py" and len(m["command"]) <= 32
    assert all(_line(w) for w in m["command"])
    assert 1 <= len(m["paths"]) <= 16
    for p in m["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(br.REPO, p))
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    # a full check of 24 cells must fit into 43200 s
    runs = 2 + 14 * 24
    assert runs * (m["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    size = os.path.getsize(os.path.join(br.REPO, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_names_units_and_keys(m):
    names = []
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k)
                                               for k in c["reduced"])
        names.append(c["name"])
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and _line(w["why"])
        names.append(w["name"])
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0 < e["bound"] <= 0.1
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert p["source"] in SOURCES and _line(p["layer"])
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        names.append(x["name"])
    metric_names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for group in ("configs", "workloads"):
        ns = [x["name"] for x in m[group]]
        assert len(ns) == len(set(ns))
    assert "setup_s" in [e["name"] for e in m["end_to_end"]]
    assert 1 <= len(m["end_to_end"]) <= 16 and 1 <= len(m["per_layer"]) <= 128
    assert 1 <= len(m["workloads"]) <= 24 and 1 <= len(m["configs"]) <= 24


def test_files_under_paths_have_contract_names(m):
    for p in m["paths"]:
        for root, dirs, files in os.walk(os.path.join(br.REPO, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(root, f), br.REPO)
                assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


def test_every_cell_is_found_by_name(m):
    configs = {c["name"]: c for c in m["configs"]}
    pairs = set()
    for w in m["workloads"]:
        cell = br.read_json("cells", w["name"] + ".json")
        # the cell's file is the source; the manifest repeats it
        for key in ("name", "config", "traffic", "chips", "why"):
            assert cell[key] == w[key], (w["name"], key)
        assert w["config"] in configs
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cfg = br.read_json("configs", w["config"] + ".json")
        assert configs[w["config"]]["file"] == \
            f"benchmarks/configs/{w['config']}.json"
        assert cfg["source"] == configs[w["config"]]["source"]
        assert cfg["reduced"] == configs[w["config"]]["reduced"]
        traffic = br.read_json("traffic", w["traffic"] + ".json")
        assert traffic["kind"] in ("train_job", "requests")
        assert os.path.isfile(os.path.join(br.BENCH, "runners",
                                           cell["runner"] + ".py"))
    used = {w["config"] for w in m["workloads"]}
    assert used == set(configs), "a configuration no cell uses"
    files = [c["file"] for c in m["configs"]]
    assert len(files) == len(set(files))


def test_cells_report_what_their_layer_metrics_move(m):
    run = br.run_module()
    cells = {w["name"] for w in m["workloads"]}
    e2e = {e["name"] for e in m["end_to_end"]}
    for x in m["end_to_end"] + m["per_layer"]:
        assert set(x.get("workloads", cells)) <= cells, x["name"]
    for w in m["workloads"]:
        mine = {e["name"] for e in run.metrics_of(m, "end_to_end", w["name"])}
        assert "setup_s" in mine and len(mine) >= 2
        layers = run.metrics_of(m, "per_layer", w["name"])
        assert layers, f"{w['name']} reports no per-layer metric"
        for p in layers:
            assert p["moves"] in e2e and p["moves"] != "setup_s"
            assert p["moves"] in mine, (w["name"], p["name"], p["moves"])
            assert hasattr(run.reader_of(p["name"]), "read"), p["name"]
    by_layer = {}
    for p in m["per_layer"]:
        by_layer.setdefault(p["layer"].lower(), set()).add(p["layer"])
    assert all(len(v) == 1 for v in by_layer.values())


def test_every_reader_is_found_by_name_or_by_stem():
    """One file reads every cell's ``<stem>.<suffix>`` where the reading
    is the same; a file with the full name goes first.  No reader file
    is left that no metric, listed or planned, would find."""
    run = br.run_module()
    names = [p["name"] for p in br.manifest_with_unproven()["per_layer"]]
    names.append("staleness_mean.async")          # async1b-2x2, README.md
    found = {os.path.basename(run.reader_of(n).__file__) for n in names}
    assert found == {f for f in os.listdir(
        os.path.join(br.BENCH, "layer_metrics")) if f.endswith(".py")}
    assert os.path.basename(run.reader_of("device_idle_pct.train").__file__) \
        == "device_idle_pct.py"
    assert os.path.basename(run.reader_of("update_ms.train").__file__) \
        == "update_ms.train.py"
    with pytest.raises(SystemExit):
        run.reader_of("no_such_metric.train")


def test_unproven_cells_keep_their_files():
    """Cells left out of ``workloads`` until a chip run proves them keep
    their files, as ISSUE 24 specified them.  Nothing in a file says
    whether it is a cell yet (``BENCHMARK.json`` alone does), so that
    listing one later edits no file."""
    for f in os.listdir(os.path.join(br.BENCH, "cells")):
        cell = br.read_json("cells", f)
        assert cell["name"] + ".json" == f
        for part in ("configs", "traffic"):
            key = "config" if part == "configs" else "traffic"
            assert os.path.isfile(os.path.join(
                br.BENCH, part, cell[key] + ".json")), (f, part)
    mix = br.read_json("traffic", "sessions-steady.json")
    assert mix["rate_per_s"] is None        # no knee at this geometry yet
    with pytest.raises(ValueError, match="no rate_per_s"):
        br.lib("traffic_gen").open_schedule(mix, 1, 10.0, 50304)
    assert mix["prompt"] == {"median": 128, "sigma": 0.8, "min": 16,
                             "max": 1024}
    closed = br.read_json("traffic", "rl-clients-g8.json")
    assert closed["callers"] == 48 and closed["budget"]["max"] == 1792


def test_four_chip_cells_are_few(m):
    four = [w["name"] for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)


def test_widths_are_never_reduced(m):
    width = re.compile(r"(hidden|intermediate|latent|state|proj).*size|"
                       r"_dim$|_rank$|head_dim|expansion|per_tok")
    for c in m["configs"]:
        assert not [k for k in c["reduced"] if width.search(k)]
        cfg = br.read_json("configs", c["name"] + ".json")
        for k in c["reduced"]:
            assert k in cfg and k in cfg["source_values"], (c["name"], k)


def test_run_py_names_nothing(m):
    with open(os.path.join(br.BENCH, "run.py")) as f:
        code = f.read()
    body = code.split('"""', 2)[2]          # past the module docstring
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[g]]
    names += [w["traffic"] for w in m["workloads"]]
    names += [os.path.splitext(f)[0] for f in
              os.listdir(os.path.join(br.BENCH, "runners"))
              if f.endswith(".py")]
    for n in set(names) - {"setup_s"}:
        assert not re.search(rf"\b{re.escape(n)}\b", body), n


def test_data_only_cell_needs_only_new_files(m):
    """The worked example of benchmarks/README.md: the async 2+2 cell is
    a cell file, a job file and (if listed) manifest entries — it reuses
    the train runner and a configuration that is there."""
    cell = br.read_json("cells", "async1b-2x2.json")
    assert cell["runner"] == "train" and cell["chips"] == 4
    job = br.read_json("traffic", cell["traffic"] + ".json")
    base = br.read_json("traffic", "ppo-sync-b48.json")
    extra = set(job["launch"]) - set(base["launch"])
    assert extra == {"async_mode=true", "rollout_devices=2",
                     "async_staleness=1"}
    assert os.path.isfile(os.path.join(br.BENCH, "configs",
                                       cell["config"] + ".json"))


# -- the generators ---------------------------------------------------------

def _open_mix():
    return dict(br.read_json("traffic", "sessions-steady.json"),
                rate_per_s=4.0)


def _closed_mix():
    return br.read_json("traffic", "rl-clients-g8.json")


def test_generator_never_reads_the_programs_config():
    with open(os.path.join(br.BENCH, "traffic_gen.py")) as f:
        code = f.read()
    assert not re.search(r"^\s*(import|from)\s+orion_tpu", code, re.M)
    assert "load_config" not in code


def test_open_schedule_is_deterministic_and_seed_keeps_the_work():
    gen = br.lib("traffic_gen")
    mix = _open_mix()
    a = gen.open_schedule(mix, 2 ** 31 + 7, 30.0, 50304)
    b = gen.open_schedule(mix, 2 ** 31 + 7, 30.0, 50304)
    c = gen.open_schedule(mix, 5, 30.0, 50304)
    assert len(a) == len(b) == len(c)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.budget == y.budget
        assert np.array_equal(x.prompt, y.prompt)

    def work(reqs):
        return sorted((len(r.prompt), r.budget, r.prefix_id)
                      for r in reqs if r.measured)

    assert work(a) == work(c)               # same sizes, another order
    assert [r.budget for r in a] != [r.budget for r in c]
    n = sum(r.measured for r in a)
    assert n == round(mix["rate_per_s"] * 30.0)
    warm = mix["warm_seconds"]
    assert all((r.due_s >= warm) == r.measured for r in a)
    assert max(r.due_s for r in a) <= warm + 30.0
    assert all(a[i].due_s <= a[i + 1].due_s for i in range(len(a) - 1))
    p, bdg, pre = mix["prompt"], mix["budget"], mix["prefix"]
    for r in a:
        assert pre["tokens"] + p["min"] <= len(r.prompt) \
            <= pre["tokens"] + p["max"]
        assert bdg["min"] <= r.budget <= bdg["max"]
        assert r.prompt.min() >= 2 and r.prompt.max() < 50304
    # a shared prefix really is shared
    by_prefix = {}
    for r in a:
        by_prefix.setdefault(r.prefix_id, []).append(
            r.prompt[:pre["tokens"]].tobytes())
    assert all(len(set(v)) == 1 for v in by_prefix.values())
    assert len(by_prefix) > 1


@pytest.mark.parametrize("cv", [1.0, 3.0])
def test_arrival_gaps_have_the_mix_s_burstiness(cv):
    """One arrival process: gamma gaps with the mix's ``arrival_cv``
    (1, the default, is Poisson); the gaps of a stretch always sum to
    its length, so the number of requests due never changes."""
    gen = br.lib("traffic_gen")
    mix = dict(_open_mix(), rate_per_s=50.0, arrival_cv=cv,
               warm_seconds=0.0)
    reqs = gen.open_schedule(mix, 7, 100.0, 50304)
    assert len(reqs) == 5000
    gaps = np.diff([r.due_s for r in reqs])
    assert np.mean(gaps) == pytest.approx(0.02, rel=0.01)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(cv, rel=0.1)


def test_closed_stream_groups_and_determinism():
    gen = br.lib("traffic_gen")
    mix = _closed_mix()
    g = mix["group"]

    def take(seed, n):
        s = gen.closed_stream(mix, seed, 50432)
        return [next(s) for _ in range(n)]

    a, b, c = take(11, 4 * g), take(11, 4 * g), take(12, 4 * g)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt) and x.budget == y.budget
    for i in range(0, len(a), g):          # g times in a row
        assert all(np.array_equal(a[i].prompt, a[i + j].prompt)
                   for j in range(g))
    assert not np.array_equal(a[0].prompt, a[g].prompt)
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    full = lambda seed: sorted(                      # noqa: E731
        (len(r.prompt), r.budget)
        for r in take(seed, mix["pool_groups"] * g))
    assert full(1) == full(2)               # one multiset of sizes
    for r in a:
        assert len(r.prompt) + r.budget <= 2048
