"""The ``nemotron_h`` configuration's part of the benchmark on the CPU:
the configuration file against the catalog row key by key; the cell, the
job and the manifest, every entry looked up BY NAME and the cell's
metrics asked to CONTAIN what ISSUE 42 names (a later PR appends behind
them); the ``train`` runner rehearsed with the configuration's tiny
sibling on a share of its heads and experts and
``reference_check_nemotron_h``'s four parts; ``flops_nemotron_h`` against
a count of an initialised model's parameters.  Nothing printed here is a
measurement."""

import json
import os
import time

import pytest

import bench_rehearsal as br

CELL = "ppo-nemotron-h-tp4-sync"
CONFIG = "nemotron-3-super-120b-a12b-tp4-ep64"
JOB = "ppo-sync-b32-s1280"
HERE = os.path.dirname(os.path.abspath(__file__))
CATALOG_ROW = os.path.join(HERE, "fixtures",
                           "nemotron3_super_catalog_row.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "mamba_num_heads", "n_groups",
           "num_attention_heads", "num_key_value_heads", "n_routed_experts",
           "vocab_size"]
EXPECTED = {"update_ms.train", "rollout_ms.train", "experience_ms.train",
            "custom_call_pct.train", "device_idle_pct.train",
            "host_busy_ms.train", "host_wait_ms.train", "host_cpu_ms.train",
            "fetch_copy_ms.train", "host_gc_ms.train",
            "moe_load_max_over_mean.train", "decode_hbm_roofline_pct.train",
            "mfu_pct.mamba2"}


def tiny_shape(cfg, **more):
    """The configuration file's keys at a ModelConfig's sizes: the counts
    of heads and experts are those HELD."""
    held = cfg.heads_held()
    return dict(
        hybrid_override_pattern=cfg.hybrid_override_pattern,
        num_hidden_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
        layer_norm_epsilon=cfg.rms_norm_eps, vocab_size=cfg.vocab_size,
        mamba_num_heads=held["mamba"], mamba_head_dim=cfg.mamba_head_dim,
        n_groups=held["groups"], ssm_state_size=cfg.ssm_state_size,
        conv_kernel=cfg.mamba_conv_kernel,
        num_attention_heads=held["q"], num_key_value_heads=held["kv"],
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        n_routed_experts=cfg.experts_held, expert_offset=cfg.expert_offset,
        num_experts_per_tok=cfg.num_experts_per_tok,
        n_shared_experts=cfg.n_shared_experts,
        moe_intermediate_size=cfg.moe_intermediate_size,
        moe_latent_size=cfg.moe_latent_size,
        moe_shared_expert_intermediate_size=(
            cfg.moe_shared_expert_intermediate_size),
        routed_scaling_factor=cfg.routed_scaling_factor,
        head_share=list(cfg.head_share),
        source_values={"n_routed_experts": cfg.n_routed_experts}, **more)


SHARE = ["model.head_share=1,2", "model.experts_held=4",
         "model.expert_offset=4"]


def tiny_config():
    """The configuration file with the tiny sibling's sizes (share 1 of
    2 of its heads, experts 4-7 of 8) and the preset that builds it."""
    import dataclasses

    from orion_tpu.config import ModelConfig

    cfg = dataclasses.replace(ModelConfig.tiny_nemotron_h(),
                              head_share=(1, 2), experts_held=4,
                              expert_offset=4)
    shape = tiny_shape(
        cfg, launch=["model_preset=tiny_nemotron_h", *SHARE,
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
    # the cut: one period, a quarter of the heads, 8 experts, an eighth
    # of the vocabulary (the floors); no width is cut
    assert [file[k] for k in REDUCED] == [11, 32, 2, 8, 1, 8, 16384]
    assert file["head_share"] == [0, 4] and file["expert_offset"] == 0
    for key in ("mamba_num_heads", "n_groups", "num_attention_heads"):
        assert file[key] * 4 == row["config"][key], key
    assert file["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert file["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert (file["hidden_size"], file["mamba_head_dim"], file["head_dim"],
            file["ssm_state_size"], file["moe_latent_size"],
            file["moe_intermediate_size"],
            file["moe_shared_expert_intermediate_size"],
            file["num_experts_per_tok"], file["mlp_hidden_act"]) == (
        4096, 64, 128, 128, 1024, 2688, 5376, 22, "relu2")
    assert "64-chip v5e slice" in file["deployment"]
    assert "share each mixer's heads four ways" in file["deployment"]
    for n in "123456":
        assert any(k.startswith(n + " ") for k in file["assumed"]), n
    assert "rotary" in "".join(file["assumed"])
    assert "latent" in "".join(file["assumed"])
    assert any("multi-token-prediction" in k for k in file["left_out"])
    for key in ("launch", "reference_check", "weights"):
        assert file[key]
    assert "model.head_share=0,4" in file["launch"]
    # the reference stands alone
    with open(os.path.join(br.BENCH, "reference_nemotron_h.py")) as f:
        text = f.read()
    assert "import orion_tpu" not in text and "from orion_tpu" not in text


def test_the_launch_list_builds_the_cut_the_file_states():
    """The file's counts are what the launch list makes the program
    hold: nothing states the share twice."""
    from orion_tpu.config import PPOConfig, load_config

    file = br.read_json("configs", CONFIG + ".json")
    mc = load_config(PPOConfig, cli_args=file["launch"]).model
    held = mc.heads_held()
    assert (held["mamba"], held["groups"], held["q"], held["kv"]) == (
        file["mamba_num_heads"], file["n_groups"],
        file["num_attention_heads"], file["num_key_value_heads"])
    assert list(mc.head_share) == file["head_share"]
    assert (mc.experts_held, mc.expert_offset, mc.vocab_size,
            mc.num_layers) == (file["n_routed_experts"],
                               file["expert_offset"], file["vocab_size"],
                               file["num_hidden_layers"])
    assert mc.n_routed_experts == file["source_values"]["n_routed_experts"]
    assert mc.hybrid_override_pattern == file["hybrid_override_pattern"]
    assert (mc.mamba_chunk_size, mc.mamba_conv_kernel) == (
        file["chunk_size"], file["conv_kernel"])


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
    # the job is ppo-sync-b32-s1024's but for the shapes and the prompts
    base = br.read_json("traffic", "ppo-sync-b32-s1024.json")
    job = br.read_json("traffic", JOB + ".json")
    assert {k for k in base if base[k] != job[k]} == {
        "name", "what", "launch", "prompt_len", "new_tokens"}
    changed = ("model.max_seq_len=", "rollout.max_prompt_len=",
               "rollout.max_new_tokens=", "minibatch_size=",
               "data.synthetic_")
    assert [k for k in job["launch"] if not k.startswith(changed)] == [
        k for k in base["launch"] if not k.startswith(changed)]
    for key in ("model.max_seq_len=1280", "rollout.max_prompt_len=256",
                "rollout.max_new_tokens=1024", "rollout_batch_size=32",
                "minibatch_size=8", "kl_coef=0.05",
                "data.synthetic_min_len=128", "data.synthetic_max_len=256",
                "data.synthetic_vocab=16384"):
        assert key in job["launch"], key
    assert (job["samples_per_iteration"], job["prompt_len"],
            job["new_tokens"]) == (32, 256, 1024)
    e2e = next(e for e in m["end_to_end"]
               if e["name"] == "train_samples_per_s")
    assert CELL in e2e["workloads"]
    mine = {p["name"] for p in br.run_module().metrics_of(m, "per_layer",
                                                          CELL)}
    assert EXPECTED <= mine             # contains: later PRs append more
    p = next(p for p in m["per_layer"] if p["name"] == "mfu_pct.mamba2")
    assert p["workloads"][0] == CELL and p["unit"] == "%"
    assert p["moves"] == "train_samples_per_s"
    assert p["layer"] == "model (models/transformer.py)"
    # no reader for a kernel this PR does not add: the recurrence is XLA's
    assert not any("mamba" in p["name"] and "roofline" in p["name"]
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
    assert ref["state_float32_share"] > 0.9
    # (d): each of the three other models lies far further off than the
    # reference itself, on the same tokens
    for name in ("relu", "silu_gate", "interleaved"):
        assert ref[name + "_mean_abs_diff"] > 100 * ref[
            "first_sequence_mean_abs_diff"], name


def test_traced_rehearsal_reads_the_new_metrics(capsys, monkeypatch,
                                                tmp_path):
    line, detail = _rehearse(1, capsys, monkeypatch, tmp_path)
    assert line["correct"] is True, detail["why_incorrect"]
    got = line["metrics"]
    assert 0 < got["mfu_pct.mamba2"]["value"]
    assert got["moe_load_max_over_mean.train"]["value"] >= 1
    assert got["decode_hbm_roofline_pct.train"]["value"] > 0
    assert EXPECTED - {"host_gc_ms.train"} <= set(got)
    hs = br.lib("host_spans")
    out_dir = os.path.join(str(tmp_path), "chiprun_out", "bench", CELL)
    spans = hs.load(br.lib("harness").Tracer(
        True, out_dir + "/trace").xplane_path())
    dispatch = spans.whole("rollout.dispatch")
    # share 1 of 2: 3 Mamba-2 blocks of 4 heads of 8 x 16 float32 and the
    # convolution's 3 last inputs of 4 x 8 + 2 x 2 x 16 channels; 1
    # attention block of 1 key-value head of 16; 4 rows, 24 slots
    assert {int(sp.stats["state_bytes"]) for sp in dispatch} == \
        {3 * 4 * (4 * 8 * 16 * 4 + 3 * (32 + 64) * 4)}
    assert {int(sp.stats["cache_bytes"]) for sp in dispatch} == \
        {4 * 24 * 2 * 16 * 4}
    assert all(int(sp.stats["weight_bytes"]) > 0 for sp in dispatch)
    update = spans.whole("update")
    assert update and all(
        (int(sp.stats["heads_held"]), int(sp.stats["groups_held"]),
         int(sp.stats["attn_heads_held"]), int(sp.stats["kv_heads_held"]),
         int(sp.stats["experts_held"])) == (4, 2, 2, 1, 4) for sp in update)


def test_flops_count_the_parameters_of_an_initialised_model():
    import dataclasses

    import jax

    from orion_tpu.config import ModelConfig
    from orion_tpu.models.transformer import Transformer, init_params

    flops = br.lib("flops_nemotron_h")
    for share, held in (((0, 1), 8), ((1, 2), 4)):
        cfg = dataclasses.replace(ModelConfig.tiny("nemotron_h"),
                                  head_share=share, experts_held=held)
        params = init_params(Transformer(cfg), jax.random.key(0), cfg)
        counted = 0
        for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
            name = jax.tree_util.keystr(path)
            if any(w in name for w in ("embedding", "norm", "conv_", "A_log",
                                       "'D'", "dt_bias", "correction_bias")):
                continue
            counted += x.size
        assert flops.matmul_params(tiny_shape(cfg)) == counted
    # the published cut: ISSUE 42's own count of its parameters
    full = br.read_json("configs", CONFIG + ".json")
    s = flops.share(full)
    assert flops.mamba_params(full, s) == pytest.approx(27.41e6, rel=1e-3)
    assert flops.attention_params(full, s) == pytest.approx(9.44e6, rel=1e-3)
    assert flops.expert_params(full) == pytest.approx(5.505e6, rel=1e-3)
    assert flops.expert_layer_params_outside_experts(full) == pytest.approx(
        (8.39 + 44.04 + 2.10) * 1e6, rel=1e-3)
    assert flops.router_width(full) == 512
    embed = full["hidden_size"] * full["vocab_size"]
    assert flops.matmul_params(full) + embed == pytest.approx(773.6e6,
                                                              rel=1e-3)
    # the program's share overrides the file's counts, and nothing else
    half = dict(heads_held=16, groups_held=1, attn_heads_held=4,
                kv_heads_held=1, experts_held=8)
    assert flops.mamba_params(full, flops.share(full, half)) \
        == pytest.approx(27.41e6 / 2, rel=1e-3)
    it = flops.ppo_iteration_flops(
        full, samples=32, prompt_len=256, new_tokens=1024, num_epochs=1,
        held_share=8 / 512)
    # products a token: 5 M layers, the attention layer, 5 E layers with
    # 22 x 8 / 512 of an expert, and the head; the recurrence's own
    # 32 x (5 x 64 x 128 + 128) + 8 x 2560 and attention over 640 keys
    products = (5 * 27.41e6 + 9.44e6
                + 5 * (54.53e6 + 22 / 64 * 5.505e6) + 4096 * 16384)
    own = 5 * (32 * (5 * 64 * 128 + 128) + 8 * 2560) + 2 * 2 * 8 * 128 * 640
    assert it == pytest.approx(6 * 32 * 1280 * (2 * products + own),
                               rel=2e-3)
