"""The ``keye_dsa`` configuration's part of the benchmark on the CPU: the
configuration file against the catalog row key by key; the cell, the job
and the manifest, every entry looked up BY NAME (a later PR appends
behind them); the ``train`` runner rehearsed with the configuration's
tiny sibling and ``reference_check_keye_dsa``'s four parts;
``flops_keye_dsa`` against a count of an initialised model's parameters;
``roofline_keye_dsa``'s work against a hand count, and its reader on
executions that lack a kernel.  Nothing printed here is a measurement."""

import json
import os
import time

import pytest

import bench_rehearsal as br

CELL = "ppo-keye-dsa-ep8-sync"
CONFIG = "keye-vl-2.0-30b-a3b-ep8"
JOB = "ppo-sync-b8-s8192"
HERE = os.path.dirname(os.path.abspath(__file__))
CATALOG_ROW = os.path.join(HERE, "fixtures", "keye_vl2_catalog_row.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "num_local_experts",
           "vocab_size"]
GENERIC = {"update_ms.train", "rollout_ms.train", "experience_ms.train",
           "custom_call_pct.train", "device_idle_pct.train",
           "host_busy_ms.train", "host_wait_ms.train", "host_cpu_ms.train",
           "fetch_copy_ms.train", "host_gc_ms.train"}
NEW = {"mfu_pct.dsa", "sparse_attn_roofline_pct.train",
       "sa_keys_selected_pct.train", "dsa_select_roofline_pct.train"}


def tiny_shape(cfg, **more):
    """The configuration file's keys at a ModelConfig's sizes."""
    return dict(
        num_hidden_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        vocab_size=cfg.vocab_size, rms_norm_eps=cfg.rms_norm_eps,
        rope_theta=cfg.rope_theta, num_experts=cfg.experts_held,
        num_local_experts=cfg.experts_held, expert_offset=cfg.expert_offset,
        num_experts_per_tok=cfg.num_experts_per_tok,
        moe_intermediate_size=cfg.moe_intermediate_size,
        sa_config=dict(indexer_num_heads=cfg.sa_index_heads,
                       indexer_head_dim=cfg.sa_index_head_dim,
                       indexer_num_kv_heads=1, topk=cfg.sa_topk,
                       q_chunk_size=cfg.sa_q_chunk,
                       kv_chunk_size=cfg.sa_kv_chunk),
        source_values={"num_experts": cfg.n_routed_experts}, **more)


def tiny_config():
    """The configuration file with the tiny sibling's sizes and the
    preset that builds it."""
    from orion_tpu.config import ModelConfig

    cfg = ModelConfig.tiny_keye_dsa()
    shape = tiny_shape(
        cfg, launch=["model_preset=tiny_keye_dsa", "model.max_seq_len=128",
                     "model.dtype=float32"])
    return dict(br.read_json("configs", CONFIG + ".json"),
                **dict(shape, vocab_size=260))


def tiny_job():
    """The cell's job at the tiny shape: prompts of 10-16 real tokens
    padded to 16, 8 new: queries past the 8th have more keys than the
    tiny ``topk`` of 8."""
    job = br.tiny_traffic(CELL)
    job["launch"] = [k for k in job["launch"]
                     if not k.startswith("data.synthetic_")] + [
        "data.synthetic_min_len=10", "data.synthetic_max_len=16",
        "data.synthetic_vocab=260"]
    return dict(job, trace_iterations=3)


def _rehearse(trace, capsys, monkeypatch, tmp_path):
    run = br.run_module()
    monkeypatch.setattr(run, "REPO", str(tmp_path))
    run.main(["--workload", CELL, "--seed", "3000000019", "--seconds", "6.0",
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
    # the floors: >= 4 layers, >= 16 experts... of 128, an eighth of the
    # vocabulary; no width is cut
    assert (file["num_hidden_layers"], file["num_experts"],
            file["num_local_experts"], file["vocab_size"],
            file["expert_offset"]) == (6, 16, 16, 18992, 0)
    assert file["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert (file["hidden_size"], file["num_attention_heads"],
            file["num_key_value_heads"], file["head_dim"],
            file["moe_intermediate_size"], file["num_experts_per_tok"],
            file["norm_topk_prob"], file["rope_theta"]) == (
        2048, 32, 4, 128, 768, 8, True, 10000000)
    assert file["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert "8 chips share each layer" in file["deployment"]
    assumed = file["assumed"]
    for n in "12345":              # the five the config is silent on
        assert any(k.startswith(n + " ") for k in assumed), n
    assert sum(k.startswith("left out") for k in assumed) >= 2
    for key in ("launch", "reference_check", "weights"):
        assert file[key]
    # the reference stands alone
    with open(os.path.join(br.BENCH, "reference_keye_dsa.py")) as f:
        text = f.read()
    assert "import orion_tpu" not in text and "from orion_tpu" not in text
    assert "approx_max_k" not in text.replace("no approximate", "")


def test_the_cell_the_job_and_the_manifest_by_name():
    m = br.manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == JOB
    assert cell == {k: br.read_json("cells", CELL + ".json")[k]
                    for k in ("name", "config", "traffic", "chips", "why")}
    assert len(cell["why"]) <= 200 and cell["config"] == CONFIG
    cfg = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert cfg["reduced"] == REDUCED
    assert cfg["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert len(cfg["why"]) <= 200 and len(cfg["source"]) <= 200
    # the job is ppo-sync-b32-s1024's but for the shapes and the prompts
    base = br.read_json("traffic", "ppo-sync-b32-s1024.json")
    job = br.read_json("traffic", JOB + ".json")
    assert {k for k in base if base[k] != job[k]} == {
        "name", "what", "launch", "samples_per_iteration", "prompt_len"}
    changed = ("model.max_seq_len=", "rollout.max_prompt_len=",
               "rollout_batch_size=", "minibatch_size=",
               "data.synthetic_")
    assert [k for k in job["launch"] if not k.startswith(changed)] == [
        k for k in base["launch"] if not k.startswith(changed)]
    for key in ("model.max_seq_len=8192", "rollout.max_prompt_len=7680",
                "rollout.max_new_tokens=512", "rollout_batch_size=8",
                "minibatch_size=2", "data.synthetic_min_len=6144",
                "data.synthetic_max_len=7680", "data.synthetic_vocab=18992"):
        assert key in job["launch"], key
    assert (job["samples_per_iteration"], job["prompt_len"],
            job["new_tokens"]) == (8, 7680, 512)
    e2e = next(e for e in m["end_to_end"]
               if e["name"] == "train_samples_per_s")
    assert CELL in e2e["workloads"]
    mine = {p["name"] for p in br.run_module().metrics_of(m, "per_layer",
                                                          CELL)}
    assert mine == GENERIC | NEW | {"moe_load_max_over_mean.train"}
    for name in NEW:
        p = next(p for p in m["per_layer"] if p["name"] == name)
        assert p["workloads"] == [CELL] and p["unit"] == "%"
        assert p["moves"] == "train_samples_per_s"
    layers = {p["name"]: p["layer"] for p in m["per_layer"]}
    assert layers["sparse_attn_roofline_pct.train"] == "kernels (ops/pallas)"
    assert layers["mfu_pct.dsa"] == "model (models/transformer.py)"
    # only appended: no other cell reports what this PR adds
    for w in m["workloads"]:
        if w["name"] != CELL:
            assert not NEW & {p["name"] for p in br.run_module().metrics_of(
                m, "per_layer", w["name"])}


def test_untraced_rehearsal_is_correct_by_the_four_parts(capsys, monkeypatch,
                                                         tmp_path):
    line, detail = _rehearse(0, capsys, monkeypatch, tmp_path)
    assert line["correct"] is True, detail["why_incorrect"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    ref = detail["info"]["reference"]
    assert ref["ok"] and ref["tokens"] == 16
    assert ref["parts"] == {"a_given_selections": True,
                            "b_own_selections": True, "c_decode": True,
                            "d_wrong_selection_fails": True}
    # float32 against float32: the same keys, the same numbers
    assert ref["selection_overlap"] == 1.0
    assert ref["max_abs_diff"] < 1e-4
    assert ref["own_selection_mean_abs_diff"] < 1e-4
    assert ref["decode_tokens"] > 0 and ref["decode_max_abs_diff"] < 1e-4
    assert ref["window_selection_mean_abs_diff"] > ref["mean_tolerance"]


def test_traced_rehearsal_reads_the_new_metrics(capsys, monkeypatch,
                                                tmp_path):
    line, detail = _rehearse(1, capsys, monkeypatch, tmp_path)
    assert line["correct"] is True, detail["why_incorrect"]
    got = line["metrics"]
    assert 0 < got["mfu_pct.dsa"]["value"]
    # 4 sequences of 18-24 real tokens, topk 8: between 8 / 24 and 1
    assert 40 < got["sa_keys_selected_pct.train"]["value"] < 75
    assert got["moe_load_max_over_mean.train"]["value"] >= 1
    # the CPU's trace has no device plane and the program no kernel here
    assert "sparse_attn_roofline_pct.train" not in got
    assert "dsa_select_roofline_pct.train" not in got
    assert GENERIC - {"host_gc_ms.train"} <= set(got)
    hs = br.lib("host_spans")
    out_dir = os.path.join(str(tmp_path), "chiprun_out", "bench", CELL)
    spans = hs.load(br.lib("harness").Tracer(
        True, out_dir + "/trace").xplane_path())
    dispatch = spans.whole("rollout.dispatch")
    # 2 layers x 4 sequences x 24 slots: k and v of 2 heads of 16 and the
    # indexer's key of 8, float32
    assert {int(sp.stats["index_cache_bytes"]) for sp in dispatch} == \
        {2 * 4 * 24 * 8 * 4}
    assert {int(sp.stats["cache_bytes"]) for sp in dispatch} == \
        {2 * 4 * 24 * (2 * 2 * 16 + 8) * 4}
    assert {int(sp.stats["sa_topk"]) for sp in dispatch} == {8}
    update = spans.whole("update")
    assert update and all(
        0 < int(sp.stats["sa_keys_selected"]) < int(sp.stats["sa_keys_valid"])
        for sp in update)


def test_flops_count_the_parameters_of_an_initialised_model():
    import jax

    from orion_tpu.config import ModelConfig
    from orion_tpu.models.transformer import Transformer, init_params

    cfg = ModelConfig.tiny("keye_dsa", experts_held=4)
    params = init_params(Transformer(cfg), jax.random.key(0), cfg)
    counted = 0
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = jax.tree_util.keystr(path)
        if "embedding" in name or "norm" in name:
            continue
        counted += x.size
    flops = br.lib("flops_keye_dsa")
    assert flops.matmul_params(tiny_shape(cfg)) == counted
    # the published cut: ISSUE 40's own count of its parameters
    full = br.read_json("configs", CONFIG + ".json")
    assert flops.attention_params(full) == pytest.approx(18.87e6, rel=1e-3)
    assert flops.indexer_params(full) == pytest.approx(2.26e6, rel=2e-3)
    assert flops.expert_params(full) == pytest.approx(4.72e6, rel=1e-3)
    assert flops.router_width(full) == 128
    embed = full["hidden_size"] * full["vocab_size"]
    assert flops.matmul_params(full) + embed == pytest.approx(659e6,
                                                              rel=2e-3)
    assert flops.whole_model_params(full) == pytest.approx(30.6e9, rel=2e-3)
    # a kept pair: q.k and p.v of 32 heads of 128; a scored pair: 16
    # heads of 64 and the weighted sum
    assert flops.pair_flops(full) == (2 * 32 * 256, 2 * 16 * 65)
    # the issue's arithmetic: at S = 8192 a row keeps 1792 keys on average
    per_token = flops.pair_flops(full)[0] * 1792
    assert per_token == pytest.approx(29.4e6, rel=1e-2)
    it = flops.ppo_iteration_flops(
        full, samples=8, prompt_len=7680, new_tokens=512, num_epochs=1,
        held_share=0.125, keys_valid=8 * 8192 * 8193 / 2,
        keys_selected=8 * 8192 * 1792)
    # products a token: 6 layers of attention, router and one expert's
    # worth of the eight selected, and the head; the indexer in the four
    # forwards alone
    products = 6 * (18.87e6 + 0.262e6 + 4.72e6) + 2048 * 18992
    assert it == pytest.approx(
        6 * (2 * products * 65536 + 6 * 16384 * 8 * 8192 * 1792)
        + 4 * 6 * (2 * 2.26e6 * 65536 + 2080 * 8 * 8192 * 8193 / 2),
        rel=2e-3)


def test_roofline_work_is_a_hand_count_at_one_small_shape():
    roof = br.lib("roofline_keye_dsa")
    model = {"num_hidden_layers": 3, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 8,
             "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 4}}
    counters = {"prompt_len": 12, "new_tokens": 4, "num_epochs": 1,
                "samples_per_iteration": 6}
    counts = {"prefill": (300.0, 200.0), "whole": (500.0, 260.0),
              "remat_kept": ("attn_resid",)}
    # forwards: the prefill, 2 experience + update's + remat's whole
    ops, byts = roof.work("sparse_fwd", model, counters, counts)
    assert ops == 3 * 4 * (2 * 2 * 8) * (200 + 4 * 260)
    tokens = 6 * 12 + 4 * 6 * 16
    assert byts == 3 * (tokens * (2 * 4 + 2 * 2) * 8 * 2 + 300 + 4 * 500)
    ops, byts = roof.work("sparse_bwd_dkv", model, counters, counts)
    assert ops == 3 * 4 * (2 * 4 * 8) * 260
    assert byts == 3 * (6 * 16 * (2 * 4 + 2 * 2 + 2 * 4) * 8 * 2 + 500)
    ops, byts = roof.work("dsa_select", model, counters, counts)
    assert ops == 3 * 2 * 2 * 4 * (300 + 4 * 500)
    assert byts == 3 * (tokens * (2 * 4 * 2 + 2 * 4 + 4 * 2)
                        + 300 + 4 * 500)
    with pytest.raises(KeyError):
        roof.work("sideways", model, counters, counts)
    # executions an iteration: 3 layers x (prefill + 2 experience + 2 x 3
    # minibatches) forward, 3 x 3 backward
    assert roof.calls_per_iteration("sparse_fwd", model, counters, 2,
                                    counts) == 27
    assert roof.calls_per_iteration("sparse_bwd_dq", model, counters, 2,
                                    counts) == 9
    # the update's checkpoints keep the attention's output: its forward
    # runs once a minibatch, the selection's still twice
    kept = dict(counts, remat_kept=("attn_resid", "attn_out"))
    assert roof.calls_per_iteration("sparse_fwd", model, counters, 2,
                                    kept) == 18
    assert roof.calls_per_iteration("dsa_select", model, counters, 2,
                                    kept) == 27
    assert roof.work("sparse_fwd", model, counters, kept)[0] == \
        3 * 4 * (2 * 2 * 8) * (200 + 3 * 260)


def test_roofline_reader_needs_the_counts_and_every_execution(monkeypatch):
    roof = br.lib("roofline_keye_dsa")
    run = br.run_module()
    model = br.read_json("configs", CONFIG + ".json")
    job = br.read_json("traffic", JOB + ".json")
    counters = {"model": model, "samples_per_iteration": 8,
                "prompt_len": 7680, "new_tokens": 512, "num_epochs": 1,
                "device_kind": "TPU v5 lite", "chips": 1}
    trace = {"window_s": 30.0, "by_program": {
        "jit__epochs_fn": {"s": 12.0, "runs": 3, "median_s": 4.0,
                           "period_s": 10.0}}}

    class Ctx:
        lib = staticmethod(br.lib)
        traffic = job
        out_dir = "/nonexistent"

    reader = run.reader_of("sparse_attn_roofline_pct.train")
    # no xplane, no spans: nothing to read, nothing raises (the parent)
    assert reader.read(trace, counters, Ctx) is None
    assert run.reader_of("mfu_pct.dsa").read(trace, counters, Ctx) is None
    assert run.reader_of("sa_keys_selected_pct.train").read(
        trace, counters, Ctx) is None
    n = 7000.0
    whole = (8 * n * (n + 1) / 2, 8 * (2048 * 2049 / 2 + (n - 2048) * 2048))
    counts = {"prefill": (0.8 * whole[0], 0.8 * whole[1]), "whole": whole,
              "topk": 2048.0, "index_cache_bytes": 0.0, "remat_kept": ()}
    monkeypatch.setattr(roof, "span_counts", lambda ctx: counts)
    # three iterations: 6 layers x (1 + 2 + 2 x 4) forward executions
    full = {"sparse_fwd": (3 * 66, 6.0), "sparse_bwd_dq": (3 * 24, 3.0),
            "sparse_bwd_dkv": (3 * 24, 3.0), "dsa_select": (3 * 66, 0.5)}
    monkeypatch.setattr(roof, "kernel_executions", lambda ctx: full)
    got = reader.read(trace, counters, Ctx)
    ops, byts = roof.work("sparse_fwd", model, counters, counts)
    assert got == pytest.approx(
        100 * max(ops / 197e12, byts / 819e9) * 3 / 6.0)
    assert 0 < got < 100
    select = run.reader_of("dsa_select_roofline_pct.train").read(
        trace, counters, Ctx)
    assert 0 < select < 100
    # attn_out kept: 6 x (1 + 2 + 4) executions an iteration are all
    keeping = dict(counts, remat_kept=("attn_out",))
    monkeypatch.setattr(roof, "span_counts", lambda ctx: keeping)
    monkeypatch.setattr(roof, "kernel_executions",
                        lambda ctx: dict(full, sparse_fwd=(3 * 42, 4.0)))
    assert 0 < reader.read(trace, counters, Ctx) < 100
    monkeypatch.setattr(roof, "span_counts", lambda ctx: counts)
    lacking = dict(full, sparse_fwd=(60, 2.0))
    monkeypatch.setattr(roof, "kernel_executions", lambda ctx: lacking)
    assert reader.read(trace, counters, Ctx) is None
    # and another model's configuration is not these readers' to count
    monkeypatch.setattr(roof, "kernel_executions", lambda ctx: full)
    other = dict(counters, model=br.read_json(
        "configs", "kanana-2-30b-a3b-ep8.json"))
    assert reader.read(trace, other, Ctx) is None
    assert run.reader_of("mfu_pct.dsa").read(trace, other, Ctx) is None
