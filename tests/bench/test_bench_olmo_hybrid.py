"""The ``olmo_hybrid`` configuration's part of the benchmark on the CPU:
the configuration file against the catalog row key by key; the cell and
the manifest; the ``train`` runner rehearsed with the configuration's
tiny sibling and ``reference_check_olmo_hybrid``; ``flops_olmo_hybrid``
against a count of an initialised model's parameters;
``roofline_olmo_hybrid``'s work against a hand count, and its reader on
executions that lack a kernel.  Nothing printed here is a measurement."""

import json
import os
import time

import pytest

import bench_rehearsal as br

CELL = "ppo-olmo-hybrid-vp8-sync"
CONFIG = "olmo-hybrid-7b-d4-vp8"
HERE = os.path.dirname(os.path.abspath(__file__))
CATALOG_ROW = os.path.join(HERE, "fixtures", "olmo_hybrid_catalog_row.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
JOBS = ("ppo-sync-b32-s1024", "ppo-sync-b32-s1024-mb4")


def _tiny_shape(cfg, **more):
    """The configuration file's keys at a ModelConfig's sizes."""
    return dict(
        num_hidden_layers=cfg.num_layers, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_heads, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        rms_norm_eps=cfg.rms_norm_eps, vocab_size=cfg.vocab_size,
        layer_types=list(cfg.layer_types),
        linear_num_key_heads=cfg.linear_num_key_heads,
        linear_num_value_heads=cfg.linear_num_value_heads,
        linear_key_head_dim=cfg.linear_key_head_dim,
        linear_value_head_dim=cfg.linear_value_head_dim,
        linear_conv_kernel_dim=cfg.linear_conv_kernel_dim,
        linear_allow_neg_eigval=cfg.linear_allow_neg_eigval, **more)


def _tiny_config():
    """The configuration file with the tiny sibling's sizes and the
    preset that builds it."""
    from orion_tpu.config import ModelConfig

    cfg = ModelConfig.tiny_olmo_hybrid()
    shape = _tiny_shape(
        cfg, launch=["model_preset=tiny_olmo_hybrid",
                     "model.max_seq_len=128", "model.dtype=float32"])
    return dict(br.read_json("configs", CONFIG + ".json"),
                **dict(shape, vocab_size=260))


def _rehearse(trace, capsys, monkeypatch, tmp_path):
    run = br.run_module()
    monkeypatch.setattr(run, "REPO", str(tmp_path))
    run.main(["--workload", CELL, "--seed", "3000000019", "--seconds", "6.0",
              "--trace", str(trace)],
             rehearsal=run.Rehearsal(config=_tiny_config(),
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
    file under the same name with the same value (``layer_types`` and
    ``rope_parameters`` whole), but for the two named in ``reduced``,
    whose published values are under ``source_values``."""
    with open(CATALOG_ROW) as f:
        row = json.load(f)
    if os.path.isfile(CATALOG):       # the fixture is the catalog's row
        with open(CATALOG) as f:
            rows = [json.loads(ln) for ln in f if ln.strip()]
        assert row == next(r for r in rows if r["name"] == row["name"])
    file = br.read_json("configs", CONFIG + ".json")
    assert file["source"] == row["source_url"]
    assert file["reduced"] == ["num_hidden_layers", "vocab_size"]
    for key, value in row["config"].items():
        if key in file["reduced"]:
            assert file["source_values"][key] == value, key
        else:
            assert file[key] == value, key
    # the floors: one whole period, an eighth of the vocabulary; no width
    assert (file["num_hidden_layers"], file["vocab_size"]) == (4, 12544)
    assert file["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert (file["hidden_size"], file["intermediate_size"],
            file["num_attention_heads"], file["linear_key_head_dim"],
            file["linear_value_head_dim"], file["linear_num_key_heads"],
            file["linear_conv_kernel_dim"], file["rms_norm_eps"]) == (
        3840, 11008, 30, 96, 192, 30, 4, 1e-6)
    ref = br.lib("reference_olmo_hybrid")
    assert [ref.mixer_kind(file, i) for i in range(4)] == [
        "linear_attention"] * 3 + ["full_attention"]
    for key in ("assumed", "deployment", "launch", "reference_check"):
        assert file[key]
    # the reference stands alone
    with open(os.path.join(br.BENCH, "reference_olmo_hybrid.py")) as f:
        text = f.read()
    assert "import orion_tpu" not in text and "from orion_tpu" not in text


def test_the_cell_is_in_the_manifest_as_specified():
    m = br.manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == JOBS[1]
    assert cell == {k: br.read_json("cells", CELL + ".json")[k]
                    for k in ("name", "config", "traffic", "chips", "why")}
    assert len(cell["why"]) <= 200
    # the job is a copy of the one cells 2 and 3 run that differs in
    # the minibatch alone (16 and 8 do not fit: PERF.md section 4)
    base = br.read_json("traffic", JOBS[0] + ".json")
    job = br.read_json("traffic", cell["traffic"] + ".json")
    differ = {k for k in base if base[k] != job[k]}
    assert differ <= {"name", "what", "launch"}
    assert [k for k in job["launch"]
            if not k.startswith("minibatch_size=")] == [
        k for k in base["launch"] if not k.startswith("minibatch_size=")]
    assert "minibatch_size=4" in job["launch"]
    # (entries are looked up by name: a later PR appends behind them)
    assert cell["config"] == CONFIG
    cfg = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert len(cfg["why"]) <= 200 and len(cfg["source"]) <= 200
    mine = {p["name"] for p in br.run_module().metrics_of(m, "per_layer",
                                                          CELL)}
    assert mine == {
        "update_ms.train", "rollout_ms.train", "experience_ms.train",
        "custom_call_pct.train", "device_idle_pct.train",
        "host_busy_ms.train", "host_wait_ms.train",
        "decode_hbm_roofline_pct.train", "mfu_pct.gdn",
        "gdn_chunk_roofline_pct.train"}
    new = [next(p for p in m["per_layer"] if p["name"] == name)
           for name in ("mfu_pct.gdn", "gdn_chunk_roofline_pct.train")]
    assert all(CELL in p["workloads"] for p in new)
    assert [p["layer"] for p in new] == ["model (models/transformer.py)",
                                         "kernels (ops/pallas)"]
    # the preset and the cut give the program what the file states
    from orion_tpu.config import PPOConfig, load_config

    file = br.read_json("configs", CONFIG + ".json")
    mc = load_config(PPOConfig, cli_args=file["launch"]).model
    assert (mc.num_layers, mc.vocab_size, mc.hidden_size, mc.num_heads,
            mc.head_dim, mc.delta_head_dims(), mc.rope_theta) == (
        4, 12544, 3840, 30, 128, (96, 192), 0.0)
    assert [m_ for m_, _ in mc.layer_kinds()] == ["gdn"] * 3 + ["attention"]
    assert list(mc.layer_types) == file["layer_types"]


def test_untraced_rehearsal_is_correct(capsys, monkeypatch, tmp_path):
    line, detail = _rehearse(0, capsys, monkeypatch, tmp_path)
    assert line["correct"] is True, detail["why_incorrect"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    ref = detail["info"]["reference"]
    assert ref["ok"] and ref["tokens"] == 16
    assert ref["max_abs_diff"] < 1e-4      # float32 against float32
    assert ref["decode_tokens"] > 0 and ref["decode_max_abs_diff"] < 1e-4
    assert ref["state_float32_share"] > 0.9
    assert ref["mean_abs_diff"] < ref["rotated_mean_abs_diff"]


def test_traced_rehearsal_reads_the_new_metrics(capsys, monkeypatch,
                                                tmp_path):
    line, detail = _rehearse(1, capsys, monkeypatch, tmp_path)
    assert line["correct"] is True, detail["why_incorrect"]
    got = line["metrics"]
    assert got["mfu_pct.gdn"]["value"] > 0
    assert got["decode_hbm_roofline_pct.train"]["value"] > 0
    # the CPU's trace has no device plane and the program no kernel here
    assert "gdn_chunk_roofline_pct.train" not in got
    assert {"update_ms.train", "rollout_ms.train", "experience_ms.train",
            "host_busy_ms.train", "host_wait_ms.train",
            "custom_call_pct.train", "device_idle_pct.train"} <= set(got)
    hs = br.lib("host_spans")
    out_dir = os.path.join(str(tmp_path), "chiprun_out", "bench", CELL)
    spans = hs.load(br.lib("harness").Tracer(
        True, out_dir + "/trace").xplane_path())
    dispatch = spans.whole("rollout.dispatch")
    # one full-attention layer: keys and values of 4 heads of 16, 4
    # sequences x 24 slots, float32; three GDN layers: 3 heads x 12 x 24
    # float32 + 3 inputs of 3 x (2 x 12 + 24) float32 a sequence
    assert {int(sp.stats["cache_bytes"]) for sp in dispatch} == \
        {2 * 4 * 24 * 4 * 16 * 4}
    assert {int(sp.stats["state_bytes"]) for sp in dispatch} == \
        {3 * 4 * (3 * 12 * 24 * 4 + 3 * 3 * 48 * 4)}
    assert all(int(sp.stats["weight_bytes"]) > 0 for sp in dispatch)
    update = spans.whole("update")
    assert update and {sp.stats["kda_chunk"] for sp in update} == {"jnp"}


def test_flops_count_the_parameters_of_an_initialised_model():
    import jax

    from orion_tpu.config import ModelConfig
    from orion_tpu.models.transformer import Transformer, init_params

    cfg = ModelConfig.tiny("olmo_hybrid", num_layers=8)
    params = init_params(Transformer(cfg), jax.random.key(0), cfg)
    counted = 0
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = jax.tree_util.keystr(path)
        if any(k in name for k in ("embedding", "norm", "_conv", "A_log",
                                   "dt_bias")):
            continue
        counted += x.size
    flops = br.lib("flops_olmo_hybrid")
    shape = _tiny_shape(cfg)
    assert flops.matmul_params(shape) == counted
    assert flops.layers_of(shape) == (["gdn"] * 3 + ["attention"]) * 2
    # the published cut: ISSUE 34's own count of its parameters
    full = br.read_json("configs", CONFIG + ".json")
    assert flops.gdn_params(full) == pytest.approx(88.7e6, rel=1e-3)
    assert flops.attention_params(full) == 4 * 3840 ** 2
    assert flops.mlp_params(full) == pytest.approx(126.8e6, rel=1e-3)
    embed = full["hidden_size"] * full["vocab_size"]
    assert flops.matmul_params(full) + embed == pytest.approx(928.6e6,
                                                              rel=2e-4)
    # the recurrence's own operations at the TRUE head sizes: 7 dk dv
    outside = flops.gdn_flops_per_token_outside_products(full)
    assert outside == 2 * 4 * 11520 + 30 * 7 * 96 * 192
    fwd = flops.forward_flops_per_token(full, context=0.0)
    assert fwd == 2 * flops.matmul_params(full) + 3 * outside
    assert flops.forward_flops_per_token(full, context=512.0) - fwd == \
        2 * 2 * 3840 * 512.0


def test_roofline_work_is_a_hand_count_at_one_small_shape():
    roof = br.lib("roofline_olmo_hybrid")
    model = {"layer_types": ["linear_attention", "full_attention",
                             "linear_attention"],
             "num_hidden_layers": 3, "linear_num_key_heads": 2,
             "linear_key_head_dim": 8, "linear_value_head_dim": 16}
    counters = {"prompt_len": 4, "new_tokens": 4, "num_epochs": 1,
                "samples_per_iteration": 6}
    # forwards: the prefill over 4 tokens, then 2 experience + update's
    # + remat's over 8; one backward over 8
    assert roof.passes(counters) == [(4.0, 1.0, 0.0), (8.0, 4.0, 1.0)]
    head_tokens_fwd = 6 * (4 + 4 * 8) * 2 * 2
    head_tokens_bwd = 6 * 8 * 2 * 2
    ops, byts = roof.work("forward", model, counters)
    assert ops == head_tokens_fwd * 7 * 8 * 16
    assert byts == head_tokens_fwd * ((8 + 8 + 16) * 2 + 2 * 4 + 16 * 4)
    ops, byts = roof.work("backward", model, counters)
    assert ops == head_tokens_bwd * 2 * 7 * 8 * 16
    assert byts == head_tokens_bwd * (
        (8 + 8 + 16) * 2 + 2 * 4 + 16 * 4 + (8 + 8 + 16) * 2 + 2 * 4)
    with pytest.raises(KeyError):
        roof.work("sideways", model, counters)
    # executions an iteration: 2 GDN layers x (prefill + 2 experience +
    # 2 x 3 minibatches) forward, 2 x 3 backward
    assert roof.calls_per_iteration(model, counters, minibatch=2) == {
        "forward": 2 * (1 + 2 + 6), "backward": 2 * 3}
    assert roof.minibatch_of({"launch": ["a=1", "minibatch_size=8"]}) == 8


def test_roofline_reader_needs_every_execution_of_both_kernels(monkeypatch):
    roof = br.lib("roofline_olmo_hybrid")
    run = br.run_module()
    model = br.read_json("configs", CONFIG + ".json")
    job = br.read_json("traffic", JOBS[0] + ".json")
    counters = {"model": model, "samples_per_iteration": 32,
                "prompt_len": 512, "new_tokens": 512, "num_epochs": 1,
                "device_kind": "TPU v5 lite", "chips": 1}
    trace = {"window_s": 15.0, "by_program": {
        "jit__epochs_fn": {"s": 6.0, "runs": 3, "median_s": 2.0,
                           "period_s": 5.0}}}

    class Ctx:
        lib = staticmethod(br.lib)
        traffic = job
        out_dir = "/nonexistent"

    reader = run.reader_of("gdn_chunk_roofline_pct.train")
    # no xplane at all: nothing to read, nothing raises
    assert reader.read(trace, counters, Ctx) is None
    # three iterations' executions of both kernels: 3 GDN layers x (1 + 2
    # + 2 x 2) forward, 3 x 2 backward, an iteration
    full = {"kda_chunk_fwd": (3 * 21, 0.63), "kda_chunk_bwd": (3 * 6, 0.27)}
    monkeypatch.setattr(roof, "kernel_executions", lambda ctx: full)
    got = reader.read(trace, counters, Ctx)
    least = sum(max(o / 197e12, b / 819e9) for o, b in (
        roof.work(d, model, counters) for d in ("forward", "backward")))
    assert got == pytest.approx(100 * least * 3 / 0.9)
    assert 0 < got < 100
    # the backward kernel's instructions are not there (a forward-only
    # program, or a reduction that dropped them): no number
    for lacking in ({"kda_chunk_fwd": (63, 0.63), "kda_chunk_bwd": (0, 0.0)},
                    {"kda_chunk_fwd": (20, 0.2), "kda_chunk_bwd": (18, 0.27)}):
        monkeypatch.setattr(roof, "kernel_executions", lambda ctx: lacking)
        assert reader.read(trace, counters, Ctx) is None
    # and another model's configuration is not this reader's to count
    monkeypatch.setattr(roof, "kernel_executions", lambda ctx: full)
    other = dict(counters, model=br.read_json(
        "configs", "kimi-linear-48b-a3b-ep32.json"))
    assert reader.read(trace, other, Ctx) is None
    assert run.reader_of("mfu_pct.gdn").read(trace, other, Ctx) is None
