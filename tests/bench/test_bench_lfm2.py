"""The ``lfm2_moe`` configuration's part of the benchmark on the CPU: the
configuration file against the catalog row key by key; the cell, the job
and the manifest, every entry looked up BY NAME and the cell's metrics
asked to CONTAIN what ISSUE 49 names (a later PR appends behind them);
the ``train`` runner rehearsed with the configuration's tiny sibling on
experts 4-7 of 8 and ``reference_check_lfm2``'s four parts; the three
readers the cell adds or joins on a planted trace at the cell's sizes;
``flops_lfm2`` against a count of an initialised model's parameters.
Nothing printed here is a measurement."""

import json
import os
import time
import types

import numpy as np
import pytest

import bench_rehearsal as br

CELL = "ppo-lfm2-ep4-sync"
CONFIG = "lfm2-8b-a1b-ep4"
JOB = "ppo-sync-b64-s1280"
HERE = os.path.dirname(os.path.abspath(__file__))
CATALOG_ROW = os.path.join(HERE, "fixtures", "lfm2_catalog_row.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
EXPECTED = {"update_ms.train", "rollout_ms.train", "experience_ms.train",
            "custom_call_pct.train", "device_idle_pct.train",
            "host_busy_ms.train", "host_wait_ms.train", "host_cpu_ms.train",
            "fetch_copy_ms.train", "host_gc_ms.train",
            "moe_load_max_over_mean.train", "decode_hbm_roofline_pct.train",
            "mfu_pct.lfm2"}


def tiny_shape(cfg, **more):
    """The configuration file's keys at a ModelConfig's sizes:
    ``num_experts`` counts the experts HELD."""
    names = {"conv": "conv", "attention": "full_attention"}
    return dict(
        layer_types=[names[m] for m, _ in cfg.layer_kinds()],
        num_hidden_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
        norm_eps=cfg.rms_norm_eps, vocab_size=cfg.vocab_size,
        conv_L_cache=cfg.conv_L_cache,
        intermediate_size=cfg.intermediate_size,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, rope_theta=cfg.rope_theta,
        num_dense_layers=cfg.first_k_dense_replace,
        num_experts=cfg.experts_held, expert_offset=cfg.expert_offset,
        num_experts_per_tok=cfg.num_experts_per_tok,
        moe_intermediate_size=cfg.moe_intermediate_size,
        routed_scaling_factor=cfg.routed_scaling_factor,
        source_values={"num_experts": cfg.n_routed_experts}, **more)


SHARE = ["model.experts_held=4", "model.expert_offset=4"]


def tiny_config():
    """The configuration file with the tiny sibling's sizes (experts 4-7
    of 8) and the preset that builds it."""
    import dataclasses

    from orion_tpu.config import ModelConfig

    cfg = dataclasses.replace(ModelConfig.tiny_lfm2_moe(), experts_held=4,
                              expert_offset=4)
    shape = tiny_shape(
        cfg, launch=["model_preset=tiny_lfm2_moe", *SHARE,
                     "model.max_seq_len=128", "model.dtype=float32"])
    return dict(br.read_json("configs", CONFIG + ".json"), **shape)


def tiny_job():
    """The cell's job at the tiny shape: prompts of 10-16 real tokens
    padded to 16, 8 new."""
    job = br.tiny_traffic(CELL)
    job["launch"] = [k for k in job["launch"]
                     if not k.startswith("data.synthetic_")] + [
        "data.synthetic_min_len=10", "data.synthetic_max_len=16",
        "data.synthetic_vocab=256"]
    return dict(job, trace_iterations=3)


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
    # the cut: the published layers 0-7, 8 of 32 experts, a quarter of
    # the vocabulary; no width is cut
    assert [file[k] for k in REDUCED] == [8, 8, 16384]
    assert file["expert_offset"] == 0
    assert file["num_experts"] * 4 == row["config"]["num_experts"]
    assert file["vocab_size"] * 4 == row["config"]["vocab_size"]
    assert file["layer_types"][:8] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv"]
    assert (file["hidden_size"], file["intermediate_size"],
            file["moe_intermediate_size"], file["num_experts_per_tok"],
            file["num_attention_heads"], file["num_key_value_heads"],
            file["conv_L_cache"], file["num_dense_layers"]) == (
        2048, 7168, 1792, 4, 32, 8, 3, 2)
    assert "3 pipeline stages of 8 layers" in file["deployment"]
    assert "4 chips sharing each layer" in file["deployment"]
    for key in ("tie_word_embeddings", "head_dim", "q/k norm", "rotary",
                "intermediate_size", "in-projection", "expert bias",
                "gate denominator"):
        assert key in file["assumed"], key
    assert "1e-20" in file["assumed"]["gate denominator"]
    assert {"the absent experts", "the absent layers"} <= set(
        file["left_out"])
    for key in ("launch", "reference_check", "weights"):
        assert file[key]
    assert "model.experts_held=8" in file["launch"]
    # the reference stands alone
    with open(os.path.join(br.BENCH, "reference_lfm2.py")) as f:
        text = f.read()
    assert "import orion_tpu" not in text and "from orion_tpu" not in text


def test_the_launch_list_builds_the_cut_the_file_states():
    """The file's counts are what the launch list makes the program
    hold: nothing states the share twice."""
    from orion_tpu.config import PPOConfig, load_config

    file = br.read_json("configs", CONFIG + ".json")
    mc = load_config(PPOConfig, cli_args=file["launch"]).model
    assert (mc.experts_held, mc.expert_offset, mc.vocab_size,
            mc.num_layers) == (file["num_experts"], file["expert_offset"],
                               file["vocab_size"],
                               file["num_hidden_layers"])
    assert mc.n_routed_experts == file["source_values"]["num_experts"]
    assert list(mc.layer_types) == file["layer_types"]
    same = tiny_shape(mc)
    for key in ("layer_types", "source_values"):    # whole in the file
        same.pop(key)
    assert same == {k: file[k] for k in same}
    assert mc.tie_word_embeddings and mc.head_dim == 64
    assert mc.attn_heads_a_step() == 4


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
    # the job is ppo-sync-b32-s1280's but for the batch and the minibatch
    base = br.read_json("traffic", "ppo-sync-b32-s1280.json")
    job = br.read_json("traffic", JOB + ".json")
    assert {k for k in base if base[k] != job[k]} == {
        "name", "what", "launch", "samples_per_iteration"}
    changed = ("rollout_batch_size=", "minibatch_size=")
    assert [k for k in job["launch"] if not k.startswith(changed)] == [
        k for k in base["launch"] if not k.startswith(changed)]
    for key in ("model.max_seq_len=1280", "rollout.max_prompt_len=256",
                "rollout.max_new_tokens=1024", "rollout_batch_size=64",
                "minibatch_size=16", "kl_coef=0.05",
                "data.synthetic_min_len=128", "data.synthetic_max_len=256",
                "data.synthetic_vocab=16384"):
        assert key in job["launch"], key
    assert (job["samples_per_iteration"], job["prompt_len"],
            job["new_tokens"]) == (64, 256, 1024)
    e2e = next(e for e in m["end_to_end"]
               if e["name"] == "train_samples_per_s")
    assert CELL in e2e["workloads"]
    mine = {p["name"] for p in br.run_module().metrics_of(m, "per_layer",
                                                          CELL)}
    assert EXPECTED <= mine             # contains: later PRs append more
    p = next(p for p in m["per_layer"] if p["name"] == "mfu_pct.lfm2")
    assert p["workloads"][0] == CELL and p["unit"] == "%"
    assert p["moves"] == "train_samples_per_s"
    assert p["layer"] == "model (models/transformer.py)"
    # no reader for a kernel this PR does not add: the convolution is XLA's
    assert not any("conv" in p["name"] and "roofline" in p["name"]
                   for p in m["per_layer"])


def test_untraced_rehearsal_is_correct_by_the_four_parts(capsys, monkeypatch,
                                                         tmp_path):
    line, detail = _rehearse(0, capsys, monkeypatch, tmp_path)
    assert line["correct"] is True, detail["why_incorrect"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    ref = detail["info"]["reference"]
    assert ref["ok"] and ref["tokens"] == 16
    # float32 against float32: the same selection, the same numbers
    assert ref["exchanged_tokens"] == 0 and ref["unfollowed_share"] == 0
    assert ref["max_abs_diff"] < 1e-4
    assert ref["decode_tokens"] > 0 and ref["decode_max_abs_diff"] < 1e-4
    # (c): two tokens a row read what prefill handed on
    assert ref["handover_tokens"] == 4
    assert ref["handover_max_abs_diff"] < 1e-4
    assert ref["conv_float32_share"] == 1.0
    # (d): each of the other models lies far further off than the
    # reference itself, on the same tokens
    assert ref["bias_selection_share"] == 1.0
    assert ref["bias_gates_published_diff"] \
        < 0.01 * ref["bias_gates_biased_diff"]
    for name in ("taps_reversed", "no_c_gate", "no_rotary", "no_qk_norm"):
        assert ref[name + "_mean_abs_diff"] > 100 * ref[
            "first_sequence_mean_abs_diff"], name


def test_traced_rehearsal_reads_the_new_metrics(capsys, monkeypatch,
                                                tmp_path):
    line, detail = _rehearse(1, capsys, monkeypatch, tmp_path)
    assert line["correct"] is True, detail["why_incorrect"]
    got = line["metrics"]
    assert 0 < got["mfu_pct.lfm2"]["value"]
    assert got["moe_load_max_over_mean.train"]["value"] >= 1
    assert got["decode_hbm_roofline_pct.train"]["value"] > 0
    assert EXPECTED - {"host_gc_ms.train"} <= set(got)
    hs = br.lib("host_spans")
    out_dir = os.path.join(str(tmp_path), "chiprun_out", "bench", CELL)
    spans = hs.load(br.lib("harness").Tracer(
        True, out_dir + "/trace").xplane_path())
    dispatch = spans.whole("rollout.dispatch")
    # 5 convolution layers' two rows of 64 and 2 attention layers' 2
    # key-value heads of 16; 4 rows, 24 slots, float32
    assert {int(sp.stats["state_bytes"]) for sp in dispatch} == \
        {5 * 4 * 2 * 64 * 4}
    assert {int(sp.stats["cache_bytes"]) for sp in dispatch} == \
        {2 * 4 * 24 * 2 * 2 * 16 * 4}
    assert all(int(sp.stats["weight_bytes"]) > 0 for sp in dispatch)
    assert {sp.stats["kv_step_form"] for sp in dispatch} == {"whole"}
    assert {int(sp.stats["attn_heads_a_step"]) for sp in dispatch} == {2}
    update = spans.whole("update")
    assert update and all(
        (int(sp.stats["conv_layers"]), int(sp.stats["conv_taps"]),
         int(sp.stats["experts_held"])) == (5, 3, 4) for sp in update)


class _Span:
    def __init__(self, **stats):
        self.stats = stats


def _planted_ctx(monkeypatch, spans):
    """A context whose run left the spans given: {name: [attributes]}."""
    hs = br.lib("host_spans")
    found = types.SimpleNamespace(whole=lambda name: [
        _Span(**s) for s in spans.get(name, [])])
    monkeypatch.setattr(hs, "of_run", lambda ctx: found)
    return types.SimpleNamespace(lib=br.lib, out_dir="/nonexistent",
                                 traffic=None)


def _counters(model=None):
    return {"samples_per_iteration": 64, "prompt_len": 256,
            "new_tokens": 1024, "num_epochs": 1, "chips": 1,
            "device_kind": br.FAKE_DEVICE["kind"],
            "model": model or br.read_json("configs", CONFIG + ".json")}


def test_the_shares_read_under_100_on_a_planted_trace_at_the_cells_sizes(
        monkeypatch):
    """The three readers on what ISSUE 49 expects of the cell: an
    iteration of 7.5 s, a rollout of 2.3 s, the program's own byte
    counts."""
    import dataclasses

    import jax

    from orion_tpu.config import ModelConfig, RolloutConfig
    from orion_tpu.models.transformer import Transformer, update_attrs
    from orion_tpu.rollout import RolloutEngine

    mc = dataclasses.replace(ModelConfig.lfm2_8b_a1b(), num_layers=8,
                             experts_held=8, vocab_size=16384,
                             max_seq_len=1280)
    model = Transformer(mc)
    eng = RolloutEngine(model, mc, RolloutConfig(
        max_prompt_len=256, max_new_tokens=1024))
    lens = np.random.RandomState(0).randint(128, 257, 64)
    ids = jax.ShapeDtypeStruct((1, 2), np.int32)
    shapes = jax.eval_shape(model.init, jax.random.key(0), ids, ids)
    dispatch = eng.dispatch_attrs((64, 256), lens, shapes["params"])
    # the issue's arithmetic: 335 MB of keys and values on two layers,
    # 3.1 MB of convolution inputs on six, 1.54 GB of bf16 weights
    assert dispatch["cache_bytes"] == 2 * 64 * 1280 * 2 * 8 * 64 * 2
    assert dispatch["state_bytes"] == 6 * 64 * 2 * 2048 * 2
    assert dispatch["weight_bytes"] == pytest.approx(2 * 772.2e6, rel=5e-3)
    assert dispatch["attn_heads_a_step"] == 4
    assert dispatch["kv_step_form"] == "prefix"
    update = update_attrs(mc, lens + 1024)
    assert (update["conv_layers"], update["conv_taps"],
            update["experts_held"]) == (6, 3, 8)
    spans = {"rollout.dispatch": [dispatch] * 3, "update": [update] * 3,
             "stats.finalize": [{"moe_pairs_here": 1.1e5,
                                 "moe_pairs_total": 4.0e5,
                                 "moe_load_max": 4100.0,
                                 "moe_load_mean": 2290.0}] * 3}
    ctx = _planted_ctx(monkeypatch, spans)
    trace = {"window_s": 30.0, "by_program": {
        "jit__epochs_fn": {"s": 9.0, "runs": 3, "median_s": 3.0,
                           "period_s": 7.5},
        "jit__generate": {"s": 6.9, "runs": 3, "median_s": 2.3,
                          "period_s": 7.5}}}
    run = br.run_module()
    mfu = run.reader_of("mfu_pct.lfm2").read(trace, _counters(), ctx)
    flops = br.lib("flops_lfm2")
    want = flops.ppo_iteration_flops(
        _counters()["model"], 64, 256, 1024, 1, 1.1e5 / 4.0e5)
    assert mfu == pytest.approx(100 * want / 7.5 / 197e12)
    assert 5 < mfu < 100
    hbm = run.reader_of("decode_hbm_roofline_pct.train").read(
        trace, _counters(), ctx)
    assert hbm == pytest.approx(100 * 1024 * (
        dispatch["weight_bytes"] + 2 * dispatch["state_bytes"]
        + dispatch["cache_bytes"] * 512 / 1280) / 819e9 / 2.3)
    assert 30 < hbm < 100
    load = run.reader_of("moe_load_max_over_mean.train").read(
        trace, _counters(), ctx)
    assert load == pytest.approx(4100 / 2290) and load > 1
    # a program without the counters, or another model's configuration,
    # is not this reader's to count
    bare = _planted_ctx(monkeypatch, {k: v for k, v in spans.items()
                                      if k != "update"})
    assert run.reader_of("mfu_pct.lfm2").read(trace, _counters(), bare) \
        is None
    other = _counters(br.read_json(
        "configs", "nemotron-3-super-120b-a12b-tp4-ep64.json"))
    assert run.reader_of("mfu_pct.lfm2").read(trace, other, ctx) is None


def test_flops_count_the_parameters_of_an_initialised_model():
    import dataclasses

    import jax

    from orion_tpu.config import ModelConfig
    from orion_tpu.models.transformer import Transformer, init_params

    flops = br.lib("flops_lfm2")
    for held in (8, 4):
        cfg = dataclasses.replace(ModelConfig.tiny("lfm2_moe"),
                                  experts_held=held)
        params = init_params(Transformer(cfg), jax.random.key(0), cfg)
        counted = 0
        for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
            name = jax.tree_util.keystr(path)
            if any(w in name for w in ("norm", "conv_weight",
                                       "correction_bias")):
                continue
            counted += x.size           # the embedding is the head
        assert flops.matmul_params(tiny_shape(cfg)) == counted
    # the published cut: ISSUE 49's own count of its parameters
    full = br.read_json("configs", CONFIG + ".json")
    assert flops.conv_params(full) == pytest.approx(16.78e6, rel=1e-3)
    assert flops.attention_params(full) == pytest.approx(10.49e6, rel=1e-3)
    assert flops.dense_mlp_params(full) == pytest.approx(44.04e6, rel=1e-3)
    assert 8 * flops.expert_params(full) == pytest.approx(88.08e6, rel=1e-3)
    assert flops.router_params(full) == 2048 * 32
    assert flops.matmul_params(full) == pytest.approx(772.2e6, rel=1e-3)
    assert flops.whole_model_params(full) == pytest.approx(8.34e9, rel=1e-3)
    # the program's share overrides the file's count, and nothing else
    assert flops.matmul_params(full, {"experts_held": 4}) == pytest.approx(
        772.2e6 - 6 * 44.04e6, rel=1e-3)
    it = flops.ppo_iteration_flops(
        full, samples=64, prompt_len=256, new_tokens=1024, num_epochs=1,
        held_share=8 / 32)
    # products a token: 6 conv and 2 attention mixers, 2 dense MLPs, 6
    # expert layers with 4 x 8 / 32 of an expert and the router, the
    # head; the convolutions' own 8 x 2048 and attention over 640 keys
    products = (6 * 16.777e6 + 2 * 10.486e6 + 2 * 44.04e6
                + 6 * (11.01e6 + 65536) + 2048 * 16384)
    own = 6 * 8 * 2048 + 2 * 2 * 2 * 32 * 64 * 640
    assert it == pytest.approx(6 * 64 * 1280 * (2 * products + own),
                               rel=2e-3)
