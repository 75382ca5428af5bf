"""The ``ouro`` configuration's part of the benchmark on the CPU: the
configuration file against the catalog row key by key; the cell, the job
and the manifest, every entry looked up BY NAME and the cell's metrics
asked to CONTAIN what ISSUE 56 names (a later PR appends behind them);
the ``train`` runner rehearsed with the configuration's tiny sibling and
``reference_check_ouro``'s parts; the three readers the cell adds on a
planted trace at the cell's sizes; ``flops_ouro`` against a brute-force
count and an initialised model's parameters.  Nothing printed here is a
measurement."""

import json
import os
import time
import types

import numpy as np
import pytest

import bench_rehearsal as br

CELL = "ppo-ouro-d8-sync"
CONFIG = "ouro-2.6b-d8"
JOB = "ppo-sync-b16-p256-t512"
HERE = os.path.dirname(os.path.abspath(__file__))
CATALOG_ROW = os.path.join(HERE, "fixtures", "ouro_catalog_row.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers"]
NEW = {"mfu_pct.ouro", "loop_decode_hbm_roofline_pct.train",
       "ut_passes_per_token.train"}
EXPECTED = NEW | {
    "update_ms.train", "rollout_ms.train", "experience_ms.train",
    "custom_call_pct.train", "device_idle_pct.train", "host_busy_ms.train",
    "host_wait_ms.train", "host_cpu_ms.train", "fetch_copy_ms.train",
    "host_gc_ms.train"}


def tiny_shape(cfg, **more):
    """The configuration file's keys at a ModelConfig's sizes."""
    return dict(
        layer_types=list(cfg.layer_types),
        num_hidden_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
        rms_norm_eps=cfg.rms_norm_eps, vocab_size=cfg.vocab_size,
        head_dim=cfg.head_dim, intermediate_size=cfg.intermediate_size,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, rope_theta=cfg.rope_theta,
        total_ut_steps=cfg.total_ut_steps,
        early_exit_threshold=cfg.early_exit_threshold, **more)


def tiny_config():
    """The configuration file with the tiny sibling's sizes and the
    preset that builds it."""
    from orion_tpu.config import ModelConfig

    shape = tiny_shape(
        ModelConfig.tiny_ouro(),
        launch=["model_preset=tiny_ouro", "model.max_seq_len=128",
                "model.dtype=float32"])
    return dict(br.read_json("configs", CONFIG + ".json"), **shape)


def tiny_job():
    job = br.tiny_traffic(CELL)
    job["launch"] = [k for k in job["launch"]
                     if not k.startswith("data.synthetic_")] + [
        "data.synthetic_min_len=10", "data.synthetic_max_len=16",
        "data.synthetic_vocab=256"]
    # the cell's own clockwork: one warm-up iteration, two traced
    return dict(job, warmup_iterations=1, trace_after_iterations=2,
                trace_iterations=2)


def _rehearse(trace, capsys, monkeypatch, tmp_path):
    run = br.run_module()
    monkeypatch.setattr(run, "REPO", str(tmp_path))
    run.main(["--workload", CELL, "--seed", "3000000019", "--seconds", "8.0",
              "--trace", str(trace)],
             rehearsal=run.Rehearsal(config=tiny_config(), traffic=tiny_job(),
                                     device=dict(br.FAKE_DEVICE),
                                     manifest=br.manifest(),
                                     reduce_trace=br.reduce_cpu_trace),
             t_process_start=time.perf_counter())
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    detail = [json.loads(ln) for ln in lines
              if ln.startswith('{"phase": "result_detail"')]
    return json.loads(lines[-1]), detail[-1]


def test_the_configuration_file_is_the_catalog_row_but_for_the_cut():
    with open(CATALOG_ROW) as f:
        row = json.load(f)
    if os.path.isfile(CATALOG):       # the fixture is the catalog's row
        with open(CATALOG) as f:
            rows = [json.loads(ln) for ln in f if ln.strip()]
        assert row == next(r for r in rows if r["name"] == row["name"])
    file = br.read_json("configs", CONFIG + ".json")
    assert file["source"] == row["source_url"]
    assert file["reduced"] == REDUCED
    for key, value in row["config"].items():
        if key in file["reduced"]:
            assert file["source_values"][key] == value, key
        else:
            assert file[key] == value, key
    assert set(file["source_values"]) == set(REDUCED)
    # the cut: depth alone, 8 of 48 layers; the passes, every width and
    # the vocabulary whole
    assert file["num_hidden_layers"] == 8
    assert (file["hidden_size"], file["head_dim"], file["intermediate_size"],
            file["num_attention_heads"], file["num_key_value_heads"],
            file["vocab_size"], file["total_ut_steps"],
            file["early_exit_threshold"]) == (2048, 128, 5632, 16, 16,
                                              49152, 4, 1)
    assert len(file["layer_types"]) == 48
    assert "6 pipeline stages of 8" in file["deployment"]
    assert "612.4 M parameters" in file["deployment"]
    for key in ("the embedding", "the loop", "attention",
                "the sandwich order", "the final norm", "the exit gate",
                "the exit masses", "the head", "the objective", "weights"):
        assert key in file["assumed"], key
    for key in ("launch", "reference_check", "weights"):
        assert file[key]
    assert file["launch"] == ["model_preset=ouro_2_6b", "model.num_layers=8"]
    # the reference stands alone
    with open(os.path.join(br.BENCH, "reference_ouro.py")) as f:
        text = f.read()
    assert "import orion_tpu" not in text and "from orion_tpu" not in text
    assert "lax.scan" not in text.split('"""')[2]


def test_the_launch_list_builds_the_cut_the_file_states():
    from orion_tpu.config import PPOConfig, load_config

    file = br.read_json("configs", CONFIG + ".json")
    mc = load_config(PPOConfig, cli_args=file["launch"]).model
    assert mc.arch == "ouro" and mc.num_layers == 8
    assert list(mc.layer_types) == file["layer_types"]
    same = tiny_shape(mc)
    same.pop("layer_types")
    assert same == {k: file[k] for k in same}
    assert not mc.tie_word_embeddings and mc.layer_visits() == 32
    assert mc.layer_kinds() == (("attention", "dense"),) * 8
    flops = br.lib("flops_ouro")
    assert flops.matmul_params(file) == pytest.approx(612.4e6, rel=1e-3)


def test_the_cell_the_job_and_the_manifest_by_name():
    m = br.manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == JOB
    assert cell == {k: br.read_json("cells", CELL + ".json")[k]
                    for k in ("name", "config", "traffic", "chips", "why")}
    assert len(cell["why"]) <= 200 and cell["config"] == CONFIG
    assert br.read_json("cells", CELL + ".json")["runner"] == "train"
    cfg = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert cfg["reduced"] == REDUCED
    assert cfg["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert len(cfg["why"]) <= 200 and len(cfg["source"]) <= 200
    # the job is ppo-sync-b32-s1280's but for its sizes
    base = br.read_json("traffic", "ppo-sync-b32-s1280.json")
    job = br.read_json("traffic", JOB + ".json")
    # (one warm-up iteration and two traced: the retreats of PERF.md
    # section 6, PR 56, for the run's wall-clock)
    assert {k for k in base if base[k] != job[k]} == {
        "name", "what", "launch", "samples_per_iteration", "new_tokens",
        "warmup_iterations", "trace_iterations"}
    assert (job["warmup_iterations"], job["trace_after_iterations"],
            job["trace_iterations"]) == (1, 2, 2)
    changed = ("model.max_seq_len=", "rollout.max_new_tokens=",
               "rollout_batch_size=", "minibatch_size=",
               "data.synthetic_vocab=")
    assert [k for k in job["launch"] if not k.startswith(changed)] == [
        k for k in base["launch"] if not k.startswith(changed)]
    for key in ("model.max_seq_len=768", "rollout.max_prompt_len=256",
                "rollout.max_new_tokens=512", "rollout_batch_size=16",
                "minibatch_size=4", "data.synthetic_min_len=128",
                "data.synthetic_max_len=256", "data.synthetic_vocab=49152"):
        assert key in job["launch"], key
    assert (job["samples_per_iteration"], job["prompt_len"],
            job["new_tokens"]) == (16, 256, 512)
    e2e = next(e for e in m["end_to_end"]
               if e["name"] == "train_samples_per_s")
    assert CELL in e2e["workloads"]
    mine = {p["name"] for p in br.run_module().metrics_of(m, "per_layer",
                                                          CELL)}
    assert EXPECTED <= mine             # contains: later PRs append more
    # GPT-NeoX's count, the experts', a step's weights read once: not
    # this cell's
    assert not {"mfu_pct.train", "moe_load_max_over_mean.train",
                "decode_hbm_roofline_pct.train"} & mine
    layers = {"mfu_pct.ouro": ("model (models/transformer.py)", "%"),
              "loop_decode_hbm_roofline_pct.train":
              ("rollout, fixed batch (rollout/engine.py)", "%"),
              "ut_passes_per_token.train":
              ("model (models/transformer.py)", "passes")}
    for name, (layer, unit) in layers.items():
        p = next(p for p in m["per_layer"] if p["name"] == name)
        assert p["workloads"] == [CELL] and p["unit"] == unit
        assert p["moves"] == "train_samples_per_s" and p["layer"] == layer
        assert os.path.isfile(os.path.join(br.BENCH, "layer_metrics",
                                           name + ".py"))


def test_untraced_rehearsal_is_correct_by_the_checks_parts(capsys,
                                                           monkeypatch,
                                                           tmp_path):
    line, detail = _rehearse(0, capsys, monkeypatch, tmp_path)
    assert line["correct"] is True, detail["why_incorrect"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    ref = detail["info"]["reference"]
    assert ref["ok"] and all(ref["parts"].values()) and ref["tokens"] == 16
    # float32 against float32
    assert ref["max_abs_diff"] < 1e-4 and ref["value_max_abs_diff"] < 1e-4
    assert ref["decode_tokens"] == 16 and ref["decode_max_abs_diff"] < 1e-4
    assert ref["exit_mass_max_abs_diff"] < 1e-5
    assert len(ref["exit_masses"]) == 4 and ref["passes_per_token"] == 4.0
    assert sum(ref["exit_masses"]) == pytest.approx(1.0, abs=1e-5)


def test_traced_rehearsal_reads_the_new_metrics(capsys, monkeypatch,
                                                tmp_path):
    line, detail = _rehearse(1, capsys, monkeypatch, tmp_path)
    assert line["correct"] is True, detail["why_incorrect"]
    got = line["metrics"]
    assert 0 < got["mfu_pct.ouro"]["value"]
    assert got["loop_decode_hbm_roofline_pct.train"]["value"] > 0
    assert got["ut_passes_per_token.train"]["value"] == 4.0
    assert EXPECTED - {"host_gc_ms.train"} <= set(got)
    hs = br.lib("host_spans")
    out_dir = os.path.join(str(tmp_path), "chiprun_out", "bench", CELL)
    spans = hs.load(br.lib("harness").Tracer(
        True, out_dir + "/trace").xplane_path())
    dispatch = spans.whole("rollout.dispatch")
    # 4 passes x 3 layers of 24 slots: 4 rows, 4 key-value heads of 16,
    # float32, keys and values
    entry = 4 * 24 * 2 * 4 * 16 * 4
    assert {(int(sp.stats["ut_steps"]), int(sp.stats["layer_visits"]))
            for sp in dispatch} == {(4, 12)}
    assert {int(sp.stats["cache_bytes"]) for sp in dispatch} == {12 * entry}
    assert {int(sp.stats["cache_bytes_a_pass"]) for sp in dispatch} \
        == {3 * entry}
    block = 4 * (4 * 64 * 64 + 3 * 64 * 96 + 4 * 64)
    assert {int(sp.stats["stack_weight_bytes"]) for sp in dispatch} \
        == {3 * block}
    # the head (the launcher widens a vocabulary of 256 to its byte
    # tokenizer's 260 ids), the final norm and the value head
    assert {int(sp.stats["once_weight_bytes"]) for sp in dispatch} \
        == {4 * (64 * 260 + 64 + 64)}
    update = spans.whole("update")
    assert update and all(
        (int(sp.stats["ut_steps"]), int(sp.stats["layer_visits"]),
         int(sp.stats["shared_grad_uses"])) == (4, 12, 4) for sp in update)
    assert all(0 < int(sp.stats["seq_tokens"]) <= 4 * 24
               and int(sp.stats["causal_keys"]) > 0 for sp in update)
    final = [sp.stats for sp in spans.whole("stats.finalize")]
    assert final and all(
        sum(float(s[f"ut_exit_mass_{t}"]) for t in (1, 2, 3, 4))
        == pytest.approx(1.0, abs=1e-4) for s in final)


class _Span:
    def __init__(self, **stats):
        self.stats = stats


def _planted_ctx(monkeypatch, spans):
    """A context whose run left the spans given: {name: [attributes]}."""
    hs = br.lib("host_spans")
    found = types.SimpleNamespace(whole=lambda name: [
        _Span(**s) for s in spans.get(name, [])])
    monkeypatch.setattr(hs, "of_run", lambda ctx: found)
    return types.SimpleNamespace(
        lib=br.lib, out_dir="/nonexistent",
        traffic=br.read_json("traffic", JOB + ".json"))


def _counters(model=None):
    return {"samples_per_iteration": 16, "prompt_len": 256,
            "new_tokens": 512, "num_epochs": 1, "chips": 1,
            "device_kind": br.FAKE_DEVICE["kind"],
            "model": model or br.read_json("configs", CONFIG + ".json")}


def test_the_readers_on_a_planted_trace_at_the_cells_sizes(monkeypatch):
    """The three readers on what ISSUE 56 expects of the cell: an
    iteration of 7.5 s, a rollout of 4.3 s, the program's own counts."""
    import dataclasses

    import jax

    from orion_tpu.config import ModelConfig, RolloutConfig
    from orion_tpu.models.heads import ActorCriticModel
    from orion_tpu.models.transformer import update_attrs
    from orion_tpu.rollout import RolloutEngine

    mc = dataclasses.replace(ModelConfig.ouro_2_6b(), num_layers=8,
                             max_seq_len=768, scan_layers=True)
    model = ActorCriticModel(mc)
    eng = RolloutEngine(model, mc, RolloutConfig(
        max_prompt_len=256, max_new_tokens=512))
    lens = np.random.RandomState(0).randint(128, 257, 16)
    ids = jax.ShapeDtypeStruct((1, 2), np.int32)
    shapes = jax.eval_shape(model.init, jax.random.key(0), ids, ids)
    dispatch = eng.dispatch_attrs((16, 256), lens, shapes["params"])
    # the issue's arithmetic: 8192 bytes a token a (pass, layer), 3.22 GB
    # over 32 visits; 822 MB of blocks read once a pass, the head's 201
    slot = 2 * 16 * 128 * 2
    assert dispatch["cache_bytes"] == 32 * 16 * 768 * slot
    assert dispatch["cache_bytes"] == pytest.approx(3.22e9, rel=2e-3)
    assert dispatch["cache_bytes_a_pass"] * 4 == dispatch["cache_bytes"]
    assert dispatch["stack_weight_bytes"] == pytest.approx(822e6, rel=2e-3)
    assert dispatch["once_weight_bytes"] == pytest.approx(201.3e6, rel=1e-3)
    assert (dispatch["ut_steps"], dispatch["layer_visits"]) == (4, 32)
    # one query head a key head: prefix_step, blocks of 128 slots
    assert dispatch["kv_step_form"] == "prefix"
    assert 256 < dispatch["kv_step_slots"] <= 768
    assert dispatch["kv_cache_lane_fill"] == 1.0
    update = update_attrs(mc, lens + 512)
    assert (update["ut_steps"], update["layer_visits"],
            update["shared_grad_uses"]) == (4, 32, 4)
    flops = br.lib("flops_ouro")
    counted = flops.causal_keys(lens + 512)
    assert {k: float(update[k]) for k in counted} == counted
    spans = {"rollout.dispatch": [dict(dispatch, batch=16)] * 3,
             "update": [dict(update, remat_kept="")] * 3,
             "stats.finalize": [{"ut_passes_per_token": 4.0,
                                 "ut_exit_mass_1": 0.5}] * 3}
    ctx = _planted_ctx(monkeypatch, spans)
    # two traced iterations: two rollouts, one whole update
    trace = {"window_s": 15.0, "by_program": {
        "jit__epochs_fn": {"s": 1.8, "runs": 1, "median_s": 1.8,
                           "period_s": None},
        "jit__generate": {"s": 8.6, "runs": 2, "median_s": 4.3,
                          "period_s": 7.5}}}
    run = br.run_module()
    mfu = run.reader_of("mfu_pct.ouro").read(trace, _counters(), ctx)
    want = flops.ppo_iteration_flops(
        _counters()["model"], 16, 512, 1,
        {k: float(update[k]) for k in flops.KEYS})
    assert mfu == pytest.approx(100 * want / 7.5 / 197e12)
    assert 5 < mfu < 100
    hbm = run.reader_of("loop_decode_hbm_roofline_pct.train").read(
        trace, _counters(), ctx)
    assert hbm == pytest.approx(100 * 512 * (
        4 * dispatch["stack_weight_bytes"] + dispatch["once_weight_bytes"]
        + dispatch["kv_step_slots"] * slot * 32 * 16) / 819e9 / 4.3)
    assert 30 < hbm < 100
    assert run.reader_of("ut_passes_per_token.train").read(
        trace, _counters(), ctx) == 4.0
    # a program without the counters (the parent), or another model's
    # configuration, is not these readers' to count
    bare = _planted_ctx(monkeypatch, {"stats.finalize": [{"moe_load": 1.0}],
                                      "update": [{"kda_chunk": ""}],
                                      "rollout.dispatch": [
                                          {"weight_bytes": 1.0}]})
    for name in NEW:
        assert run.reader_of(name).read(trace, _counters(), bare) is None
    other = _counters(br.read_json("configs", "lfm2-8b-a1b-ep4.json"))
    assert run.reader_of("mfu_pct.ouro").read(trace, other, ctx) is None


def test_flops_equal_a_brute_force_count_and_the_models_parameters():
    import jax

    from orion_tpu.config import ModelConfig
    from orion_tpu.models.transformer import Transformer, init_params

    flops = br.lib("flops_ouro")
    cfg = ModelConfig.tiny("ouro")
    params = init_params(Transformer(cfg), jax.random.key(0), cfg)
    counted = sum(
        x.size for path, x in
        jax.tree_util.tree_flatten_with_path(params)[0]
        if "norm" not in jax.tree_util.keystr(path)
        and "exit_gate" not in jax.tree_util.keystr(path))
    shape = tiny_shape(cfg)
    assert flops.matmul_params(shape) == counted
    # one forward, pair by pair and product by product, visit by visit
    lens = [5, 8, 9, 23]
    E, D, H, F = (cfg.hidden_size, cfg.head_dim, cfg.num_heads,
                  cfg.intermediate_size)
    brute = 0
    for n in lens:
        for t in range(n):
            for _ in range(cfg.total_ut_steps):
                for _ in cfg.layer_kinds():
                    brute += (t + 1) * 2 * 2 * H * D        # q.k and p v
                    brute += 2 * E * D * 4 * H              # q, k, v, o
                    brute += 2 * 3 * E * F                  # the SwiGLU
    brute += 7 * 2 * E * cfg.vocab_size                     # 7 logit rows
    counts = dict(flops.causal_keys(lens), ut_steps=4.0,
                  layer_visits=float(cfg.layer_visits()))
    assert flops.forward_flops(shape, counts, 7) == brute
    assert flops.ppo_iteration_flops(shape, 1, 7, 2, counts) \
        == brute * (1 + 2 + 3 * 2)
    # the published cut: ISSUE 56's own count of its parameters
    full = br.read_json("configs", CONFIG + ".json")
    assert flops.attention_params(full) == pytest.approx(16.78e6, rel=1e-3)
    assert flops.mlp_params(full) == pytest.approx(34.60e6, rel=1e-3)
    assert flops.matmul_params(full) == pytest.approx(612.4e6, rel=1e-3)
    assert flops.whole_model_params(full) == pytest.approx(2.668e9, rel=1e-3)
    assert flops.slot_bytes(full) == 8192
