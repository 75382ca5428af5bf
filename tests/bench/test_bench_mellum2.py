"""The ``mellum`` configuration's part of the benchmark on the CPU: the
configuration file against the catalog row key by key; the cell, the job
and the manifest, every entry looked up BY NAME and the cell's metrics
asked to CONTAIN what ISSUE 53 names (a later PR appends behind them);
the ``train`` runner rehearsed with the configuration's tiny sibling on
experts 4-7 of 8 and ``reference_check_mellum2``'s five parts; the four
readers the cell adds on a planted trace at the cell's sizes;
``flops_mellum2`` against a brute-force count and an initialised model's
parameters.  Nothing printed here is a measurement."""

import json
import os
import time
import types

import numpy as np
import pytest

import bench_rehearsal as br

CELL = "ppo-mellum2-ep8-sync"
CONFIG = "mellum2-12b-a2.5b-ep8"
JOB = "ppo-sync-b8-p7168-t1024"
HERE = os.path.dirname(os.path.abspath(__file__))
CATALOG_ROW = os.path.join(HERE, "fixtures", "mellum2_catalog_row.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW = {"mfu_pct.mellum2", "window_flash_roofline_pct.train",
       "window_decode_hbm_roofline_pct.train", "window_keys_seen_pct.train"}
EXPECTED = NEW | {
    "update_ms.train", "rollout_ms.train", "experience_ms.train",
    "custom_call_pct.train", "device_idle_pct.train", "host_busy_ms.train",
    "host_wait_ms.train", "host_cpu_ms.train", "fetch_copy_ms.train",
    "host_gc_ms.train", "moe_load_max_over_mean.train"}
SLIDING, FULL = "sliding_attention", "full_attention"


def tiny_shape(cfg, **more):
    """The configuration file's keys at a ModelConfig's sizes:
    ``num_experts`` counts the experts HELD."""
    names = {"window": SLIDING, "attention": FULL}
    return dict(
        layer_types=[names[m] for m, _ in cfg.layer_kinds()],
        num_hidden_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
        rms_norm_eps=cfg.rms_norm_eps, vocab_size=cfg.vocab_size,
        head_dim=cfg.head_dim, intermediate_size=cfg.intermediate_size,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        sliding_window=cfg.sliding_window,
        rope_parameters=cfg.rope_parameters,
        num_experts=cfg.experts_held, expert_offset=cfg.expert_offset,
        num_experts_per_tok=cfg.num_experts_per_tok,
        moe_intermediate_size=cfg.moe_intermediate_size,
        source_values={"num_experts": cfg.n_routed_experts}, **more)


SHARE = ["model.experts_held=4", "model.expert_offset=4"]


def tiny_config():
    """The configuration file with the tiny sibling's sizes (experts 4-7
    of 8) and the preset that builds it."""
    import dataclasses

    from orion_tpu.config import ModelConfig

    cfg = dataclasses.replace(ModelConfig.tiny_mellum(), experts_held=4,
                              expert_offset=4)
    shape = tiny_shape(
        cfg, launch=["model_preset=tiny_mellum", *SHARE,
                     "model.max_seq_len=128", "model.dtype=float32"])
    return dict(br.read_json("configs", CONFIG + ".json"), **shape)


def tiny_job():
    """The cell's job at the tiny shape: prompts of 10-16 real tokens
    padded to 16, 8 new: every sequence passes the window of 8."""
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
    # the cut: the published layers 0-7 (two whole periods), 8 of 64
    # experts, an eighth of the vocabulary; no width is cut
    assert [file[k] for k in REDUCED] == [8, 8, 12288]
    assert file["expert_offset"] == 0
    assert file["num_experts"] * 8 == row["config"]["num_experts"]
    assert file["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert file["layer_types"][:8] == ([SLIDING] * 3 + [FULL]) * 2
    assert (file["hidden_size"], file["head_dim"],
            file["moe_intermediate_size"], file["num_experts_per_tok"],
            file["num_attention_heads"], file["num_key_value_heads"],
            file["sliding_window"]) == (2304, 128, 896, 8, 32, 4, 1024)
    assert file["rope_parameters"][FULL]["rope_type"] == "yarn"
    assert "8 chips share each layer" in file["deployment"]
    assert "624 M parameters" in file["deployment"]
    for key in ("q/k norm", "the window's edge", "rotary",
                "intermediate_size", "the MTP head", "weights"):
        assert key in file["assumed"], key
    assert {"the absent experts", "the absent layers"} <= set(
        file["left_out"])
    for key in ("launch", "reference_check", "weights"):
        assert file[key]
    assert "model.experts_held=8" in file["launch"]
    # the reference stands alone
    with open(os.path.join(br.BENCH, "reference_mellum2.py")) as f:
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
    assert not mc.tie_word_embeddings and mc.attn_heads_a_step() == 8
    assert [m for m, _ in mc.layer_kinds()] == (
        ["window"] * 3 + ["attention"]) * 2


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
    # the job is ppo-sync-b8-s8192's but for the split of the 8192
    base = br.read_json("traffic", "ppo-sync-b8-s8192.json")
    job = br.read_json("traffic", JOB + ".json")
    assert {k for k in base if base[k] != job[k]} == {
        "name", "what", "launch", "prompt_len", "new_tokens"}
    changed = ("rollout.max_prompt_len=", "rollout.max_new_tokens=",
               "data.synthetic_")
    assert [k for k in job["launch"] if not k.startswith(changed)] == [
        k for k in base["launch"] if not k.startswith(changed)]
    for key in ("model.max_seq_len=8192", "rollout.max_prompt_len=7168",
                "rollout.max_new_tokens=1024", "rollout_batch_size=8",
                "minibatch_size=2", "data.synthetic_min_len=5120",
                "data.synthetic_max_len=7168",
                "data.synthetic_vocab=12288"):
        assert key in job["launch"], key
    assert (job["samples_per_iteration"], job["prompt_len"],
            job["new_tokens"]) == (8, 7168, 1024)
    e2e = next(e for e in m["end_to_end"]
               if e["name"] == "train_samples_per_s")
    assert CELL in e2e["workloads"]
    mine = {p["name"] for p in br.run_module().metrics_of(m, "per_layer",
                                                          CELL)}
    assert EXPECTED <= mine             # contains: later PRs append more
    # its reader spreads the cache over prompt + new slots: not this cell's
    assert "decode_hbm_roofline_pct.train" not in mine
    layers = {"mfu_pct.mellum2": "model (models/transformer.py)",
              "window_flash_roofline_pct.train": "kernels (ops/pallas)",
              "window_decode_hbm_roofline_pct.train":
              "rollout, fixed batch (rollout/engine.py)",
              "window_keys_seen_pct.train": "model (models/transformer.py)"}
    for name, layer in layers.items():
        p = next(p for p in m["per_layer"] if p["name"] == name)
        assert p["workloads"] == [CELL] and p["unit"] == "%"
        assert p["moves"] == "train_samples_per_s" and p["layer"] == layer
        assert os.path.isfile(os.path.join(br.BENCH, "layer_metrics",
                                           name + ".py"))


def test_untraced_rehearsal_is_correct_by_the_five_parts(capsys, monkeypatch,
                                                         tmp_path):
    line, detail = _rehearse(0, capsys, monkeypatch, tmp_path)
    assert line["correct"] is True, detail["why_incorrect"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    ref = detail["info"]["reference"]
    assert ref["ok"] and all(ref["parts"].values()) and ref["tokens"] == 16
    # float32 against float32: the same selection, the same numbers
    assert ref["unfollowed_share"] == 0 and ref["max_abs_diff"] < 1e-4
    assert ref["decode_tokens"] > 0 and ref["decode_max_abs_diff"] < 1e-4
    for kind in ("sliding", "full"):
        for path in ("forward", "decode"):
            assert ref[f"{kind}_{path}_edge_keys"] < 1e-3
    assert ref["router_float32_share"] == 1.0
    # (c): each of the other models lies further off than the reference
    for name in br.lib("reference_check_mellum2").VARIANTS:
        assert ref[name + "_mean_abs_diff"] > 10 * ref[
            "first_sequence_mean_abs_diff"], name


def test_traced_rehearsal_reads_the_new_metrics(capsys, monkeypatch,
                                                tmp_path):
    line, detail = _rehearse(1, capsys, monkeypatch, tmp_path)
    assert line["correct"] is True, detail["why_incorrect"]
    got = line["metrics"]
    assert 0 < got["mfu_pct.mellum2"]["value"]
    assert got["moe_load_max_over_mean.train"]["value"] >= 1
    assert got["window_decode_hbm_roofline_pct.train"]["value"] > 0
    # 16-24 real tokens under a window of 8
    assert 40 < got["window_keys_seen_pct.train"]["value"] < 75
    # the CPU runs no Mosaic kernel: nothing to read, and no failure
    assert "window_flash_roofline_pct.train" not in got
    assert EXPECTED - {"host_gc_ms.train",
                       "window_flash_roofline_pct.train"} <= set(got)
    hs = br.lib("host_spans")
    out_dir = os.path.join(str(tmp_path), "chiprun_out", "bench", CELL)
    spans = hs.load(br.lib("harness").Tracer(
        True, out_dir + "/trace").xplane_path())
    dispatch = spans.whole("rollout.dispatch")
    # 6 rings of 8 slots and 2 caches of 24: 4 rows, 2 key-value heads
    # of 16, float32
    row = 4 * 2 * 2 * 16 * 4
    assert {(int(sp.stats["window_layers"]), int(sp.stats["full_layers"]),
             int(sp.stats["window_slots"])) for sp in dispatch} == {(6, 2, 8)}
    assert {int(sp.stats["ring_cache_bytes"]) for sp in dispatch} \
        == {6 * 8 * row}
    assert {int(sp.stats["full_cache_bytes"]) for sp in dispatch} \
        == {2 * 24 * row}
    assert {int(sp.stats["cache_bytes"]) for sp in dispatch} \
        == {(6 * 8 + 2 * 24) * row}
    assert all(float(sp.stats["kv_slots_read_window"]) == 8.0
               and float(sp.stats["kv_slots_read_full"]) == 24.0
               for sp in dispatch)
    update = spans.whole("update")
    assert update and all(
        (int(sp.stats["window_layers"]), int(sp.stats["sliding_window"]),
         int(sp.stats["experts_held"])) == (6, 8, 4) for sp in update)
    assert all(0 < int(sp.stats["window_keys_seen"])
               < int(sp.stats["causal_keys"]) for sp in update)


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
    return {"samples_per_iteration": 8, "prompt_len": 7168,
            "new_tokens": 1024, "num_epochs": 1, "chips": 1,
            "device_kind": br.FAKE_DEVICE["kind"],
            "model": model or br.read_json("configs", CONFIG + ".json")}


def test_the_shares_read_under_100_on_a_planted_trace_at_the_cells_sizes(
        monkeypatch):
    """The four readers on what ISSUE 53 expects of the cell: an
    iteration of 5 s, a rollout of 2.2 s, the program's own counts."""
    import dataclasses

    import jax

    from orion_tpu.config import ModelConfig, RolloutConfig
    from orion_tpu.models.transformer import Transformer, update_attrs
    from orion_tpu.rollout import RolloutEngine

    mc = dataclasses.replace(ModelConfig.mellum2_12b_a2_5b(), num_layers=8,
                             experts_held=8, vocab_size=12288,
                             max_seq_len=8192)
    model = Transformer(mc)
    eng = RolloutEngine(model, mc, RolloutConfig(
        max_prompt_len=7168, max_new_tokens=1024))
    lens = np.random.RandomState(0).randint(5120, 7169, 8)
    ids = jax.ShapeDtypeStruct((1, 2), np.int32)
    shapes = jax.eval_shape(model.init, jax.random.key(0), ids, ids)
    dispatch = eng.dispatch_attrs((8, 7168), lens, shapes["params"])
    # the issue's arithmetic: 2 x 134 MB full + 6 x 16.8 MB ring, 1.25 GB
    # of bf16 weights
    slot = 8 * 2 * 4 * 128 * 2
    assert dispatch["ring_cache_bytes"] == 6 * 1024 * slot
    assert dispatch["full_cache_bytes"] == 2 * 8192 * slot
    assert dispatch["cache_bytes"] == (6 * 1024 + 2 * 8192) * slot
    assert dispatch["cache_bytes"] < 0.37e9 < 8 * 8192 * slot
    assert dispatch["weight_bytes"] == pytest.approx(2 * 624e6, rel=5e-3)
    assert (dispatch["window_layers"], dispatch["full_layers"],
            dispatch["window_slots"], dispatch["attn_heads_a_step"]) \
        == (6, 2, 1024, 8)
    # on the CPU the step goes over prefixes of the batch's furthest
    # position: the ring whole, the full cache up to 6-8 k
    assert dispatch["kv_slots_read_window"] == 1024
    assert 5120 < dispatch["kv_slots_read_full"] <= 8192
    update = update_attrs(mc, lens + 1024)
    assert (update["window_layers"], update["full_layers"],
            update["sliding_window"], update["experts_held"]) \
        == (6, 2, 1024, 8)
    assert update["seq_tokens"] == int(np.sum(lens + 1024))
    flops = br.lib("flops_mellum2")
    counted = flops.keys_seen(lens + 1024, 1024)
    assert {k: update[k] for k in counted} == counted
    spans = {"rollout.dispatch": [dispatch] * 3,
             "update": [dict(update, remat_kept="")] * 3,
             "stats.finalize": [{"moe_pairs_here": 5.9e4,
                                 "moe_pairs_total": 4.6e5,
                                 "moe_load_max": 2400.0,
                                 "moe_load_mean": 1850.0}] * 3}
    ctx = _planted_ctx(monkeypatch, spans)
    trace = {"window_s": 20.0, "by_program": {
        "jit__epochs_fn": {"s": 6.6, "runs": 3, "median_s": 2.2,
                           "period_s": 5.0},
        "jit__generate": {"s": 6.6, "runs": 3, "median_s": 2.2,
                          "period_s": 5.0}}}
    run = br.run_module()
    mfu = run.reader_of("mfu_pct.mellum2").read(trace, _counters(), ctx)
    want = flops.ppo_iteration_flops(
        _counters()["model"], 8, 1024, 1, 5.9e4 / 4.6e5,
        {k: float(update[k]) for k in flops.KEYS})
    assert mfu == pytest.approx(100 * want / 5.0 / 197e12)
    assert 5 < mfu < 100
    seen = run.reader_of("window_keys_seen_pct.train").read(
        trace, _counters(), ctx)
    assert seen == pytest.approx(
        100 * update["window_keys_seen"] / update["causal_keys"])
    assert 23 < seen < 31
    hbm = run.reader_of("window_decode_hbm_roofline_pct.train").read(
        trace, _counters(), ctx)
    assert hbm == pytest.approx(100 * 1024 * (
        dispatch["weight_bytes"] + slot * (
            6 * dispatch["kv_slots_read_window"]
            + 2 * dispatch["kv_slots_read_full"])) / 819e9 / 2.2)
    assert 30 < hbm < 100
    # the three windowed kernels: the executions an iteration's shapes
    # say (prefill, two experience forwards, the update's four
    # minibatches forward, again under remat, and backward: 6 layers
    # each), at half their roofline
    roof = br.lib("roofline_mellum2")
    upd = roof.span_medians(ctx, "update", ("window_layers",))
    roll = roof.span_medians(ctx, "rollout.dispatch", ("window_layers",))
    assert upd["remat_kept"] == ()
    calls = {k: roof.calls_per_iteration(k, _counters(), 2, upd)
             for k in roof.KERNELS}
    assert calls == {"flash_fwd_window": 6 * (3 + 2 * 4),
                     "flash_dq_window": 6 * 4, "flash_dkv_window": 6 * 4}
    least = {k: max(ops / 197e12, byts / 819e9) for k, (ops, byts) in (
        (k, roof.work(k, _counters()["model"], _counters(), roll, upd))
        for k in roof.KERNELS)}
    found = {k: (4 * calls[k], 4 * 2.0 * least[k]) for k in roof.KERNELS}
    share = roof.roofline_pct(roof.KERNELS, trace, _counters(), ctx, found)
    assert share == pytest.approx(50.0)
    # the algorithm's pairs are those inside the window: a forward over
    # the update's batch is 32 heads x 4 x 128 operations a pair
    ops, _ = roof.work("flash_dq_window", _counters()["model"], _counters(),
                       roll, upd)
    assert ops == 6 * 32 * 6 * 128 * update["window_keys_seen"]
    # too few executions for the window (a kernel taken off the path):
    # nothing to read
    few = dict(found, flash_dq_window=(3, 1.0))
    assert roof.roofline_pct(roof.KERNELS, trace, _counters(), ctx, few) \
        is None
    # a program without the counters, or another model's configuration,
    # is not these readers' to count
    bare = _planted_ctx(monkeypatch, {k: v for k, v in spans.items()
                                      if k == "stats.finalize"})
    other = _counters(br.read_json("configs", "lfm2-8b-a1b-ep4.json"))
    for name in NEW:
        assert run.reader_of(name).read(trace, _counters(), bare) is None
    assert run.reader_of("mfu_pct.mellum2").read(trace, other, ctx) is None
    assert roof.roofline_pct(roof.KERNELS, trace, other, ctx, found) is None


def test_flops_equal_a_brute_force_count_and_the_models_parameters():
    import dataclasses

    import jax

    from orion_tpu.config import ModelConfig
    from orion_tpu.models.transformer import Transformer, init_params

    flops = br.lib("flops_mellum2")
    for held in (8, 4):
        cfg = dataclasses.replace(ModelConfig.tiny("mellum"),
                                  experts_held=held)
        params = init_params(Transformer(cfg), jax.random.key(0), cfg)
        counted = sum(
            x.size for path, x in
            jax.tree_util.tree_flatten_with_path(params)[0]
            if "norm" not in jax.tree_util.keystr(path))
        assert flops.matmul_params(tiny_shape(cfg)) == counted
    # one forward, pair by pair and product by product
    cfg = dataclasses.replace(ModelConfig.tiny("mellum"), experts_held=4)
    shape, lens, W = tiny_shape(cfg), [5, 8, 9, 23], cfg.sliding_window
    E, D, H, Hkv = (cfg.hidden_size, cfg.head_dim, cfg.num_heads,
                    cfg.num_kv_heads)
    brute = 0
    for n in lens:
        for t in range(n):
            for mixer, _ in cfg.layer_kinds():
                keys = sum(1 for s in range(t + 1)
                           if mixer == "attention" or t - s < W)
                brute += keys * 2 * 2 * H * D               # q.k and p v
                brute += 2 * E * D * (2 * H + 2 * Hkv)      # q, k, v, o
                brute += 2 * E * cfg.n_routed_experts       # the router
                brute += 2 * cfg.num_experts_per_tok * 0.5 \
                    * 3 * E * cfg.moe_intermediate_size     # half held
    brute += 7 * 2 * E * cfg.vocab_size                     # 7 logit rows
    counts = dict(flops.layer_counts(shape), **flops.keys_seen(lens, W))
    assert counts["window_layers"] == 6 and counts["full_layers"] == 2
    assert flops.forward_flops(shape, counts, 0.5, 7) == brute
    assert flops.ppo_iteration_flops(shape, 1, 7, 2, 0.5, counts) \
        == brute * (1 + 2 + 3 * 2)
    # the published cut: ISSUE 53's own count of its parameters
    full = br.read_json("configs", CONFIG + ".json")
    assert flops.attention_params(full) == pytest.approx(21.23e6, rel=1e-3)
    assert flops.expert_params(full) == pytest.approx(6.193e6, rel=1e-3)
    assert flops.router_params(full) == 2304 * 64
    assert flops.matmul_params(full) == pytest.approx(624e6, rel=2e-3)
    assert flops.whole_model_params(full) == pytest.approx(12.1e9, rel=1e-2)
    assert flops.matmul_params(full, 4) == pytest.approx(
        624e6 - 8 * 4 * 6.193e6, rel=2e-3)
    assert flops.slot_bytes(full) == 2048
    # the issue's arithmetic a sequence and layer: windowed attention is
    # 129 GF where the full layer's is 550 GF (dense 2 S^2 x 4096)
    one = flops.keys_seen([8192], 1024)
    assert one["window_keys_seen"] * flops.pair_flops(full) \
        == pytest.approx(129e9, rel=0.02)
    assert one["causal_keys"] * flops.pair_flops(full) \
        == pytest.approx(550e9, rel=0.01)
