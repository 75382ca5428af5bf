"""The ``kimi_linear`` configuration's part of the benchmark on the CPU:
the configuration file against the catalog row key by key; the ``train``
runner rehearsed with the configuration's tiny sibling and
``reference_check_kimi_linear``; ``flops_kimi_linear`` against a count
of an initialised model's parameters; the two new readers on a recorded
fixture; and the faults the check must catch, each shown failing.
Nothing printed here is a measurement."""

import copy
import json
import os
import time

import numpy as np
import pytest

import bench_rehearsal as br

CELL = "ppo-kimi-linear-ep32-sync"
CONFIG = "kimi-linear-48b-a3b-ep32"
HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "kimi_linear_spans.json")
CATALOG_ROW = os.path.join(HERE, "fixtures", "kimi_linear_catalog_row.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _tiny_shape(cfg, **more):
    """The configuration file's keys at a ModelConfig's sizes."""
    return dict(
        num_hidden_layers=cfg.num_layers, num_attention_heads=cfg.num_heads,
        hidden_size=cfg.hidden_size, intermediate_size=cfg.intermediate_size,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        num_experts_per_token=cfg.num_experts_per_tok,
        num_shared_experts=cfg.n_shared_experts,
        moe_intermediate_size=cfg.moe_intermediate_size,
        first_k_dense_replace=cfg.first_k_dense_replace,
        routed_scaling_factor=cfg.routed_scaling_factor,
        num_experts=cfg.experts_held, expert_offset=cfg.expert_offset,
        source_values={"num_experts": cfg.n_routed_experts},
        vocab_size=cfg.vocab_size,
        linear_attn_config={
            "kda_layers": list(cfg.kda_layers),
            "full_attn_layers": [i + 1 for i in range(cfg.num_layers)
                                 if i + 1 not in cfg.kda_layers],
            "num_heads": cfg.kda_num_heads, "head_dim": cfg.kda_head_dim,
            "short_conv_kernel_size": cfg.short_conv_kernel_size},
        **more)


def _tiny_config():
    """The configuration file with the tiny sibling's sizes (every
    expert held) and the preset that builds it."""
    from orion_tpu.config import ModelConfig

    cfg = ModelConfig.tiny_kimi_linear()
    shape = _tiny_shape(
        cfg, launch=["model_preset=tiny_kimi_linear",
                     "model.max_seq_len=128", "model.dtype=float32"])
    return dict(br.read_json("configs", CONFIG + ".json"),
                **dict(shape, vocab_size=260))


def _rehearse(trace, capsys, monkeypatch, tmp_path):
    run = br.run_module()
    monkeypatch.setattr(run, "REPO", str(tmp_path))
    # five layers and their scans: an iteration takes a second or two here
    run.main(["--workload", CELL, "--seed", "3000000019", "--seconds", "8.0",
              "--trace", str(trace)],
             rehearsal=run.Rehearsal(config=_tiny_config(),
                                     # three traced iterations: under a
                                     # loaded host the last update may
                                     # outlast the trace, and a period
                                     # needs two whole ones
                                     traffic=dict(br.tiny_traffic(CELL),
                                                  trace_iterations=3),
                                     device=dict(br.FAKE_DEVICE),
                                     manifest=br.manifest(),
                                     reduce_trace=br.reduce_cpu_trace),
             t_process_start=time.perf_counter())
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    detail = [json.loads(ln) for ln in lines
              if ln.startswith('{"phase": "result_detail"')]
    return json.loads(lines[-1]), detail[-1]


def test_the_configuration_file_is_the_catalog_row_but_for_the_cut():
    """Key by key: every key of the catalog row's ``config`` is in the
    file under the same name with the same value (nested groups whole),
    but for the three named in ``reduced``, whose published values are
    under ``source_values``."""
    with open(CATALOG_ROW) as f:
        row = json.load(f)
    if os.path.isfile(CATALOG):       # the fixture is the catalog's row
        with open(CATALOG) as f:
            rows = [json.loads(ln) for ln in f if ln.strip()]
        assert row == next(r for r in rows if r["name"] == row["name"])
    file = br.read_json("configs", CONFIG + ".json")
    assert file["source"] == row["source_url"]
    assert file["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    for key, value in row["config"].items():
        if key in file["reduced"]:
            assert file["source_values"][key] == value, key
        else:
            assert file[key] == value, key
    assert (file["num_hidden_layers"], file["num_experts"],
            file["vocab_size"]) == (5, 8, 20480)
    # the floors: a whole period behind the dense layer, 8 experts, an
    # eighth of the vocabulary
    assert file["vocab_size"] * 8 == row["config"]["vocab_size"]
    kinds = [br.lib("reference_kimi_linear").mixer_kind(file, i)
             for i in range(file["num_hidden_layers"])]
    assert kinds == ["kda", "kda", "kda", "latent", "kda"]
    for key in ("assumed", "deployment", "launch", "reference_check"):
        assert file[key]


def test_the_cell_is_in_the_manifest_as_specified():
    m = br.manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "ppo-sync-b32-s1024"
    assert cell == {k: br.read_json("cells", CELL + ".json")[k]
                    for k in ("name", "config", "traffic", "chips", "why")}
    assert len(cell["why"]) <= 200
    cfg = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    mine = {p["name"] for p in br.run_module().metrics_of(m, "per_layer",
                                                          CELL)}
    assert mine == {
        "update_ms.train", "rollout_ms.train", "experience_ms.train",
        "custom_call_pct.train", "device_idle_pct.train",
        "host_busy_ms.train", "host_wait_ms.train",
        "moe_load_max_over_mean.train", "mfu_pct.kda",
        "decode_hbm_roofline_pct.train"}
    # their readers count a deepseek_v3 model by its key names, and
    # every layer as one that runs the flash kernel
    assert not {"mfu_pct.moe", "flash_fwd_roofline_pct.train",
                "mfu_pct.train"} & mine
    new = [p for p in m["per_layer"] if p["name"] in (
        "mfu_pct.kda", "decode_hbm_roofline_pct.train")]
    assert [p["workloads"] for p in new] == [[CELL], [CELL]]
    assert m["per_layer"][-2:] == new        # appended, nothing moved
    # the preset and the cut give the program what the file states
    from orion_tpu.config import PPOConfig, load_config

    file = br.read_json("configs", CONFIG + ".json")
    mc = load_config(PPOConfig, cli_args=file["launch"]).model
    assert (mc.num_layers, mc.experts_held, mc.n_routed_experts,
            mc.vocab_size, mc.num_experts_per_tok, mc.kda_head_dim) == (
        5, 8, 256, 20480, 8, 128)
    assert [m_ for m_, _ in mc.layer_kinds()] == [
        "kda", "kda", "kda", "latent", "kda"]


def test_untraced_rehearsal_is_correct(capsys, monkeypatch, tmp_path):
    line, detail = _rehearse(0, capsys, monkeypatch, tmp_path)
    assert line["correct"] is True, detail["why_incorrect"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    ref = detail["info"]["reference"]
    assert ref["ok"] and ref["tokens"] == 16
    assert ref["unfollowed_share"] == 0.0
    assert ref["max_abs_diff"] < 1e-4      # float32 against float32
    assert ref["decode_tokens"] > 0 and ref["decode_max_abs_diff"] < 1e-4
    assert ref["state_float32_share"] > 0.9
    assert ref["mean_abs_diff"] < ref["rotated_mean_abs_diff"]


def test_traced_rehearsal_reads_the_new_metrics(capsys, monkeypatch,
                                                tmp_path):
    line, detail = _rehearse(1, capsys, monkeypatch, tmp_path)
    assert line["correct"] is True, detail["why_incorrect"]
    got = line["metrics"]
    assert got["mfu_pct.kda"]["value"] > 0
    assert got["decode_hbm_roofline_pct.train"]["value"] > 0
    assert got["moe_load_max_over_mean.train"]["value"] > 1.0
    assert {"update_ms.train", "rollout_ms.train", "experience_ms.train",
            "host_busy_ms.train", "host_wait_ms.train",
            "custom_call_pct.train", "device_idle_pct.train"} <= set(got)
    hs = br.lib("host_spans")
    out_dir = os.path.join(str(tmp_path), "chiprun_out", "bench", CELL)
    spans = hs.load(br.lib("harness").Tracer(
        True, out_dir + "/trace").xplane_path())
    final = spans.whole("stats.finalize")
    assert final and all(
        float(sp.stats["moe_pairs_here"]) == float(
            sp.stats["moe_pairs_total"]) > 0 for sp in final)
    dispatch = spans.whole("rollout.dispatch")
    # one latent layer: (16 + 8) x 4 bytes x 4 sequences x 24 slots; four
    # KDA layers: 4 heads x 16 x 16 float32 + 3 x 192 float32 a sequence
    assert {int(sp.stats["cache_bytes"]) for sp in dispatch} == \
        {24 * 4 * 4 * 24}
    assert {int(sp.stats["state_bytes"]) for sp in dispatch} == \
        {4 * 4 * (4 * 16 * 16 * 4 + 3 * 192 * 4)}
    assert all(int(sp.stats["weight_bytes"]) > 0 for sp in dispatch)


def test_flops_count_the_parameters_of_an_initialised_model():
    import jax

    from orion_tpu.config import ModelConfig
    from orion_tpu.models.transformer import Transformer, init_params

    cfg = ModelConfig.tiny("kimi_linear", experts_held=4, expert_offset=2)
    params = init_params(Transformer(cfg), jax.random.key(0), cfg)
    counted = 0
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = jax.tree_util.keystr(path)
        if any(k in name for k in ("embedding", "norm", "correction_bias",
                                   "_conv", "A_log", "dt_bias")):
            continue
        counted += x.size
    flops = br.lib("flops_kimi_linear")
    shape = _tiny_shape(cfg)
    assert flops.matmul_params(shape) == counted
    assert flops.layers_of(shape) == [
        ("kda", True), ("kda", False), ("kda", False), ("latent", False),
        ("kda", False)]
    # the published cut: ISSUE 32's own count of its parameters
    full = br.read_json("configs", CONFIG + ".json")
    assert flops.kda_params(full) == pytest.approx(39.4e6, rel=5e-3)
    assert flops.latent_params(full) == pytest.approx(29.1e6, rel=5e-3)
    embed = full["hidden_size"] * full["vocab_size"]
    assert flops.matmul_params(full) + embed == pytest.approx(602.4e6,
                                                              rel=1e-3)
    # the recurrence's own operations: 7 d^2 a head and token
    outside = flops.kda_flops_per_token_outside_products(full)
    assert outside == 2 * 4 * 3 * 4096 + 32 * 7 * 128 * 128
    fwd = flops.forward_flops_per_token(full, context=0.0, held_share=0.0)
    assert fwd == 2 * (flops.matmul_params(full)
                       - 4 * 8 * flops.expert_params(full)) + 4 * outside


def test_new_readers_on_a_recorded_fixture(monkeypatch):
    """Spans as a traced run records them (``rollout.dispatch`` with the
    three byte counts, ``stats.finalize`` with the ``moe_*`` attributes,
    numbers as text) and a device trace cut to what the readers use."""
    with open(FIXTURE) as f:
        fx = json.load(f)
    hs = br.lib("host_spans")
    spans = hs.from_planes(fx["planes"])
    monkeypatch.setattr(hs, "of_run", lambda ctx: spans)
    run = br.run_module()

    class Ctx:
        lib = staticmethod(br.lib)

    counters = dict(fx["counters"], model=br.read_json(
        "configs", CONFIG + ".json"))
    mfu = run.reader_of("mfu_pct.kda").read(fx["trace"], counters, Ctx)
    flops = br.lib("flops_kimi_linear").ppo_iteration_flops(
        counters["model"], 32, 512, 512, 1, held_share=16400.0 / 524288.0)
    assert mfu == pytest.approx(100 * flops / 5.0 / 197e12)
    assert 0 < mfu < 100
    roof = run.reader_of("decode_hbm_roofline_pct.train").read(
        fx["trace"], counters, Ctx)
    per_step = 1204873472 + 2 * 277872640 + 37748736 * 256 / 1024
    assert roof == pytest.approx(100 * 512 * per_step / 819e9 / 2.4)
    assert 0 < roof < 100
    # a program whose spans lack the byte counts or the counters (the
    # parent's) gives nothing to read, and nothing raises
    old = copy.deepcopy(fx["planes"])
    for ev in old[0]["lines"][0]["events"]:
        if ev[0] == "rollout.dispatch":
            ev[3] = {k: v for k, v in ev[3].items()
                     if k not in ("state_bytes", "weight_bytes")}
    old[0]["lines"][0]["events"] = [
        e for e in old[0]["lines"][0]["events"] if e[0] != "stats.finalize"]
    bare = hs.from_planes(old)
    monkeypatch.setattr(hs, "of_run", lambda ctx: bare)
    for name in ("mfu_pct.kda", "decode_hbm_roofline_pct.train"):
        assert run.reader_of(name).read(fx["trace"], counters, Ctx) is None
    # and a deepseek_v3 configuration is not this reader's to count
    monkeypatch.setattr(hs, "of_run", lambda ctx: spans)
    other = dict(counters, model=br.read_json(
        "configs", "kanana-2-30b-a3b-ep8.json"))
    assert run.reader_of("mfu_pct.kda").read(fx["trace"], other, Ctx) is None
