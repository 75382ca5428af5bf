"""The ``sdar_moe`` configuration's part of the benchmark on the CPU: the
configuration file against the catalog row key by key; the cell, the job
and the manifest, every entry looked up BY NAME (a later PR appends
behind them); the ``train`` runner rehearsed with the configuration's
tiny sibling and ``reference_check_sdar``'s three parts; the faults the
check must catch, planted; ``flops_sdar`` against a count of an
initialised model's parameters and a hand count; the new readers on a
program without the counters and on a planted trace at the cell's
sizes.  Nothing printed here is a measurement."""

import json
import os
import time
import types

import numpy as np
import pytest

import bench_rehearsal as br

CELL = "ppo-sdar-ep8-sync"
CONFIG = "sdar-30b-a3b-chat-ep8"
JOB = "ppo-bdiff-b32-p256-t512"
HERE = os.path.dirname(os.path.abspath(__file__))
CATALOG_ROW = os.path.join(HERE, "fixtures", "sdar_catalog_row.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
GENERIC = {"update_ms.train", "rollout_ms.train", "experience_ms.train",
           "custom_call_pct.train", "device_idle_pct.train",
           "host_busy_ms.train", "host_wait_ms.train", "host_cpu_ms.train",
           "fetch_copy_ms.train", "host_gc_ms.train"}
NEW = {"mfu_pct.bdiff", "denoise_hbm_roofline_pct.train",
       "tokens_per_denoise_forward.train"}


def tiny_shape(cfg, **more):
    """The configuration file's keys at a ModelConfig's sizes."""
    return dict(
        num_hidden_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        vocab_size=cfg.vocab_size, rms_norm_eps=cfg.rms_norm_eps,
        rope_theta=cfg.rope_theta, num_experts=cfg.experts_held,
        expert_offset=cfg.expert_offset,
        num_experts_per_tok=cfg.num_experts_per_tok,
        moe_intermediate_size=cfg.moe_intermediate_size,
        block_length=cfg.block_length, denoising_steps=cfg.denoising_steps,
        mask_token_id=cfg.mask_id,
        source_values={"num_experts": cfg.n_routed_experts}, **more)


def tiny_config():
    from orion_tpu.config import ModelConfig

    cfg = ModelConfig.tiny("sdar_moe", vocab_size=260)
    shape = tiny_shape(
        cfg, launch=["model_preset=tiny_sdar_moe", "model.max_seq_len=128",
                     "model.vocab_size=260", "model.dtype=float32"])
    return dict(br.read_json("configs", CONFIG + ".json"), **shape)


def tiny_job():
    """The cell's job at the tiny shape: prompts of 9-16 real tokens
    padded to 16, 8 new tokens: 2 or 3 blocks a row."""
    job = br.tiny_traffic(CELL)
    job["launch"] = [k for k in job["launch"]
                     if not k.startswith(("data.synthetic_",
                                          "rollout.quantize_"))] + [
        "data.synthetic_min_len=9", "data.synthetic_max_len=16",
        "data.synthetic_vocab=259"]
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
    # the floors: >= 4 layers, >= 8 experts, an eighth of the vocabulary;
    # no width is cut
    assert (file["num_hidden_layers"], file["num_experts"],
            file["vocab_size"], file["expert_offset"]) == (6, 16, 18992, 0)
    assert file["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert (file["hidden_size"], file["num_attention_heads"],
            file["num_key_value_heads"], file["head_dim"],
            file["moe_intermediate_size"], file["num_experts_per_tok"],
            file["norm_topk_prob"], file["rope_theta"]) == (
        2048, 32, 4, 128, 768, 8, True, 1000000)
    assert (file["block_length"], file["denoising_steps"],
            file["mask_token_id"]) == (4, 4, 18991)
    assert "8 chips share each layer" in file["deployment"]
    assumed = file["assumed"]
    for n in "12345678":     # block length, alignment, steps and rule, ...
        assert any(k.startswith(n + " ") for k in assumed), n
    for key in ("launch", "reference_check", "weights"):
        assert file[key]
    assert file["launch"][:5] == [
        "model_preset=sdar_30b_a3b", "model.num_layers=6",
        "model.experts_held=16", "model.expert_offset=0",
        "model.vocab_size=18992"]
    # the launch lines build what the file says
    from orion_tpu.config import PPOConfig, load_config

    mc = load_config(PPOConfig, cli_args=file["launch"]).model
    assert (mc.arch, mc.num_layers, mc.experts_held, mc.vocab_size,
            mc.block_length, mc.denoising_steps, mc.mask_id) == (
        "sdar_moe", 6, 16, 18992, 4, 4, 18991)
    # the reference stands alone
    with open(os.path.join(br.BENCH, "reference_sdar.py")) as f:
        text = f.read()
    assert "import orion_tpu" not in text and "from orion_tpu" not in text
    assert "pallas" not in text


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
    # the job is ppo-sync-b8-s8192's but for the shapes and the prompts
    base = br.read_json("traffic", "ppo-sync-b8-s8192.json")
    job = br.read_json("traffic", JOB + ".json")
    assert {k for k in base if base[k] != job[k]} == {
        "name", "what", "launch", "samples_per_iteration", "prompt_len"}
    changed = ("model.max_seq_len=", "rollout.max_prompt_len=",
               "rollout_batch_size=", "minibatch_size=", "data.synthetic_")
    assert [k for k in job["launch"] if not k.startswith(changed)] == [
        k for k in base["launch"] if not k.startswith(changed)]
    for key in ("model.max_seq_len=1024", "rollout.max_prompt_len=256",
                "rollout.max_new_tokens=512", "rollout_batch_size=32",
                "minibatch_size=4", "data.synthetic_min_len=128",
                "data.synthetic_max_len=256", "data.synthetic_vocab=18991",
                "rollout.temperature=1.0"):
        assert key in job["launch"], key
    assert (job["samples_per_iteration"], job["prompt_len"],
            job["new_tokens"], job["warmup_iterations"],
            job["trace_after_iterations"], job["trace_iterations"]) == (
        32, 256, 512, 2, 2, 3)
    e2e = next(e for e in m["end_to_end"]
               if e["name"] == "train_samples_per_s")
    assert CELL in e2e["workloads"]
    mine = {p["name"] for p in br.run_module().metrics_of(m, "per_layer",
                                                          CELL)}
    assert mine == GENERIC | NEW | {"moe_load_max_over_mean.train"}
    for name in NEW:
        p = next(p for p in m["per_layer"] if p["name"] == name)
        assert p["workloads"] == [CELL]
        assert p["moves"] == "train_samples_per_s"
    by = {p["name"]: p for p in m["per_layer"]}
    assert by["mfu_pct.bdiff"]["layer"] == "model (models/transformer.py)"
    assert by["mfu_pct.bdiff"]["source"] == "device_trace"
    assert by["denoise_hbm_roofline_pct.train"]["layer"] == \
        by["decode_hbm_roofline_pct.train"]["layer"]
    assert by["tokens_per_denoise_forward.train"]["source"] == "program_span"
    # one token a row a step is not this cell's count
    assert CELL not in by["decode_hbm_roofline_pct.train"]["workloads"]
    # only appended: no other cell reports what this PR adds
    for w in m["workloads"]:
        if w["name"] != CELL:
            assert not NEW & {p["name"] for p in br.run_module().metrics_of(
                m, "per_layer", w["name"])}


def test_untraced_rehearsal_is_correct_by_the_three_parts(capsys, monkeypatch,
                                                          tmp_path):
    line, detail = _rehearse(0, capsys, monkeypatch, tmp_path)
    assert line["correct"] is True, detail["why_incorrect"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    ref = detail["info"]["reference"]
    assert ref["ok"] and ref["tokens"] == 16
    assert ref["parts"] == {"a_trace_logprobs": True, "b_engine": True,
                            "c_controls_fail": True}
    # float32 against float32: the same numbers
    assert ref["max_abs_diff"] < 1e-4 and ref["unfollowed_share"] == 0.0
    assert ref["decode_tokens"] > 0 and ref["decode_max_abs_diff"] < 1e-4
    assert not ref["trace_faults"]
    assert all(v > ref["mean_abs_diff"]
               for v in ref["control_mean_abs_diff"].values())
    assert all(abs(v) < 0.01 for v in ref["control_share"].values())


def test_traced_rehearsal_reads_the_new_metrics(capsys, monkeypatch,
                                                tmp_path):
    line, detail = _rehearse(1, capsys, monkeypatch, tmp_path)
    assert line["correct"] is True, detail["why_incorrect"]
    got = line["metrics"]
    assert 0 < got["mfu_pct.bdiff"]["value"] < 100
    assert 0 < got["denoise_hbm_roofline_pct.train"]["value"]
    # 8 new tokens over 2 or 3 blocks of 5 forwards
    assert 8 / 15 - 1e-6 <= got["tokens_per_denoise_forward.train"][
        "value"] <= 0.8 + 1e-6
    assert got["moe_load_max_over_mean.train"]["value"] >= 1
    assert GENERIC - {"host_gc_ms.train"} <= set(got)
    hs = br.lib("host_spans")
    out_dir = os.path.join(str(tmp_path), "chiprun_out", "bench", CELL)
    spans = hs.load(br.lib("harness").Tracer(
        True, out_dir + "/trace").xplane_path())
    dispatch = spans.whole("rollout.dispatch")
    assert {int(sp.stats["block_length"]) for sp in dispatch} == {4}
    assert {int(sp.stats["denoising_steps"]) for sp in dispatch} == {4}
    assert all(int(sp.stats["denoise_forwards"])
               == 5 * int(sp.stats["blocks"]) for sp in dispatch)
    assert {int(sp.stats["blocks"]) for sp in dispatch} <= {2, 3}
    # 2 layers x 4 sequences x 32 slots (16 + 3 blocks, rounded up to
    # 8): k and v of 2 heads of 16, float32
    assert {int(sp.stats["cache_bytes"]) for sp in dispatch} == \
        {2 * 4 * 32 * 2 * 2 * 16 * 4}
    assert all(int(sp.stats["weight_bytes"]) > 0 for sp in dispatch)
    for name in ("update", "experience.dispatch"):
        found = spans.whole(name)
        assert found
        for sp in found:
            assert int(sp.stats["streams"]) == 4
            assert int(sp.stats["clean_tokens"]) == 4 * 24
            assert int(sp.stats["noisy_tokens"]) == 4 * 4 * 3 * 4
            assert int(sp.stats["trace_pairs"]) > 0


# -- the faults the check must catch -------------------------------------

P, T = 24, 16


class _Model:
    """A model whose forward reads planted parameters."""

    def __init__(self, model, params_fault=None):
        self.model, self.fault = model, params_fault or (lambda p: p)

    def apply(self, variables, *a, **k):
        return self.model.apply({"params": self.fault(variables["params"])},
                                *a, **k)


class _Trainer:
    """What ``check_trainer`` uses of a trainer."""

    def __init__(self, cfg, model, params, engine_model=None):
        import jax

        from orion_tpu.config import RolloutConfig
        from orion_tpu.rollout.engine import RolloutEngine
        from orion_tpu.trainers.base import BaseTrainer

        self.cfg = types.SimpleNamespace(model=cfg)
        self.model = model
        self.state = types.SimpleNamespace(params=params)
        for name in ("_policy_apply", "_windowed_forward", "_trace_forward"):
            setattr(self, name, types.MethodType(getattr(BaseTrainer, name),
                                                 self))
        self._jit_logprobs = jax.jit(
            types.MethodType(BaseTrainer._logprobs_fn, self),
            static_argnames=("max_new",))
        self.engine = RolloutEngine(engine_model or model, cfg, RolloutConfig(
            max_prompt_len=P, max_new_tokens=T, temperature=1.0))

    def generate(self, prompt_ids, prompt_lens, rng):
        return self.engine.generate(prompt_ids, prompt_lens, rng,
                                    params=self.state.params)


class _Ctx:
    def __init__(self, config, seed):
        self.config, self.seed = config, seed
        self.traffic = {"prompt_len": P, "new_tokens": T,
                        "samples_per_iteration": 4}
        self.cell = {"chips": 1}

    lib = staticmethod(br.lib)


def _fp8(x):
    import jax.numpy as jnp

    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


def _fp8_attention(params):
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, x: _fp8(x) if any(
            getattr(k, "key", "") in ("q_proj", "k_proj", "v_proj", "o_proj")
            for k in path) else x, params)


def _fp8_head(params):
    params = dict(params)
    params["lm_head"] = {"kernel": _fp8(params["lm_head"]["kernel"])}
    return params


def _plant(monkeypatch, fault):
    """The three controls of part (c), planted in the PROGRAM."""
    import jax.numpy as jnp

    from orion_tpu.ops import logprobs
    from orion_tpu.rollout import engine

    if fault == "causal_mask":
        # the trainer masks clean and noisy queries by position alone
        real = logprobs.trace_streams

        def causal(*a, **k):
            row = real(*a, **k)
            return dict(row, see=jnp.where(row["see"] >= 0, row["positions"],
                                           -1))

        monkeypatch.setattr(logprobs, "trace_streams", causal)
    elif fault == "scored_from_z0":
        # every token read from the all-masked stream
        real = logprobs.trace_streams

        def z0(sequences, prompt_lens, reveal_step, *a, **k):
            return real(sequences, prompt_lens,
                        jnp.zeros_like(reveal_step), *a, **k)

        monkeypatch.setattr(logprobs, "trace_streams", z0)
    elif fault == "commit_skipped":
        # the engine leaves a block's keys as its last denoising step
        # wrote them: the commit forward writes nothing
        class Engine(engine.RolloutEngine):
            def _generate_blocks(self, *a, **k):
                apply = self._decode_model.apply

                def skipping(variables, *args, **kw):
                    out = apply(variables, *args, **kw)
                    if kw.get("skip_lm_head") and len(args) > 2:
                        return (out[0], args[2]) + tuple(out[2:])
                    return out

                self._decode_model = types.SimpleNamespace(apply=skipping)
                try:
                    return super()._generate_blocks(*a, **k)
                finally:
                    self._decode_model = types.SimpleNamespace(apply=apply)

        monkeypatch.setattr(engine, "RolloutEngine", Engine)


FAULTS = ["none", "causal_mask", "scored_from_z0", "commit_skipped",
          "attention_in_fp8", "head_in_fp8"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_check_passes_the_program_and_catches_each_fault(fault,
                                                             monkeypatch):
    import jax
    from jax.sharding import Mesh

    from orion_tpu.config import ModelConfig
    from orion_tpu.models.transformer import Transformer, init_params

    cfg = ModelConfig.tiny("sdar_moe", experts_held=4, expert_offset=2,
                           vocab_size=260, max_seq_len=P + T + 8,
                           dtype="float32")
    params = init_params(Transformer(cfg), jax.random.key(21), cfg)
    kw = {}
    if fault in ("causal_mask", "scored_from_z0", "commit_skipped"):
        _plant(monkeypatch, fault)
    elif fault == "attention_in_fp8":
        kw["params_fault"] = _fp8_attention
    elif fault == "head_in_fp8":
        kw["params_fault"] = _fp8_head
    trainer = _Trainer(cfg, _Model(Transformer(cfg), **kw), params,
                       engine_model=Transformer(cfg))
    chk = br.lib("reference_check_sdar")
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    verdicts = [chk.check_trainer(_Ctx(tiny_shape(cfg), seed), trainer, mesh)
                for seed in ((1, 2) if fault == "none" else (1,))]
    assert all(v["tokens"] == 2 * T for v in verdicts)
    if fault == "none":
        assert all(v["ok"] and all(v["parts"].values())
                   for v in verdicts), verdicts
        assert all(v["unfollowed_share"] == 0.0 for v in verdicts)
        assert all(v["decode_tokens"] == 2 * T for v in verdicts)
        assert all(v["max_abs_diff"] < 1e-4 for v in verdicts)
        assert all(not v["trace_faults"] for v in verdicts)
        return
    assert not any(v["ok"] for v in verdicts), verdicts
    v = verdicts[0]
    if fault == "commit_skipped":
        # the trainer's own program is sound; the engine's tokens were
        # drawn against stale keys
        assert v["parts"]["a_trace_logprobs"] and not v["parts"]["b_engine"]
        assert v["decode_mean_abs_diff"] > v["decode_mean_tolerance"]
    else:
        assert not v["parts"]["a_trace_logprobs"]
        assert v["mean_abs_diff"] > v["mean_tolerance"]
    if fault == "scored_from_z0":
        # the program computes the control: all of it is in the program
        assert v["control_share"]["all_masked"] > 0.9
        assert not v["parts"]["c_controls_fail"]


def test_a_malformed_trace_is_told():
    chk = br.lib("reference_check_sdar")

    def host(steps, n_new, seqs=None):
        steps = np.asarray(steps, np.int32)
        return types.SimpleNamespace(
            reveal_step=steps, prompt_lens=np.asarray([6] * len(steps)),
            completion_lens=np.asarray(n_new),
            sequences=np.zeros((len(steps), 6 + steps.shape[1]), np.int32)
            if seqs is None else seqs)

    good = [[1, 0, 2, 0, 1, 3, 0, 1]]     # 6,7 | 8..11 | 12,13
    assert chk.trace_faults(host(good, [8]), 4, 4) == []
    twice = [[0, 0, 2, 0, 1, 3, 0, 1]]
    assert any("steps" in f for f in chk.trace_faults(host(twice, [8]), 4, 4))
    never = [[1, 0, 4, 0, 1, 3, 0, 1]]
    assert any("never" in f for f in chk.trace_faults(host(never, [8]), 4, 4))
    # a stop token inside block 2: nothing behind that block
    seqs = np.zeros((1, 14), np.int32)
    seqs[0, 9] = 77
    behind = [[1, 0, 2, 0, 1, 3, 0, 4]]
    assert any("behind" in f for f in chk.trace_faults(
        host(behind, [4], seqs), 4, 4, (77,)))
    ended = [[1, 0, 2, 0, 1, 3, 4, 4]]
    assert chk.trace_faults(host(ended, [4], seqs), 4, 4, (77,)) == []
    assert any("without a stop" in f for f in chk.trace_faults(
        host(ended, [4]), 4, 4, (77,)))


# -- the counts and the readers ------------------------------------------


def test_flops_count_the_parameters_of_an_initialised_model():
    import jax

    from orion_tpu.config import ModelConfig
    from orion_tpu.models.transformer import Transformer, init_params

    cfg = ModelConfig.tiny("sdar_moe", experts_held=4)
    params = init_params(Transformer(cfg), jax.random.key(0), cfg)
    counted = sum(
        x.size for path, x in jax.tree_util.tree_flatten_with_path(params)[0]
        if "embedding" not in jax.tree_util.keystr(path)
        and "norm" not in jax.tree_util.keystr(path))
    flops = br.lib("flops_sdar")
    assert flops.matmul_params(tiny_shape(cfg)) == counted
    # the published cut: ISSUE 46's own count of its parameters
    full = br.read_json("configs", CONFIG + ".json")
    assert flops.attention_params(full) == pytest.approx(18.87e6, rel=1e-3)
    assert flops.expert_params(full) == pytest.approx(4.72e6, rel=1e-3)
    assert flops.router_width(full) == 128
    embed = full["hidden_size"] * full["vocab_size"]
    assert flops.matmul_params(full) + embed == pytest.approx(645e6, rel=2e-3)
    assert flops.whole_model_params(full) == pytest.approx(30.5e9, rel=3e-3)
    assert flops.pair_flops(full) == 2 * 32 * 256


def test_iteration_flops_are_a_hand_count_at_one_small_shape():
    flops = br.lib("flops_sdar")
    model = {"num_hidden_layers": 3, "hidden_size": 8, "head_dim": 4,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "num_experts": 2, "source_values": {"num_experts": 16},
             "num_experts_per_tok": 2, "moe_intermediate_size": 6,
             "vocab_size": 50}
    attn = 8 * 16 + 2 * 8 * 8 + 16 * 8
    per_entry = 3 * (attn + 8 * 16 + 2 * 0.25 * 3 * 8 * 6)
    pair = 3 * 2 * 4 * 8
    rollout = {"denoise_forwards": 15, "blocks": 3, "block_length": 4,
               "denoising_steps": 4, "decode_pairs": 1000}
    forward = {"row_tokens": 400, "trace_pairs": 3000}
    got = flops.ppo_iteration_flops(
        model, samples=2, prompt_len=10, new_tokens=8, num_epochs=1,
        held_share=0.25, rollout=rollout, forward=forward)
    generate = (2 * per_entry * 2 * (10 + 15 * 4)
                + 2 * 8 * 50 * 2 * (1 + 3 * 4 * 4) + pair * 1000)
    trace = 2 * per_entry * 400 + 2 * 8 * 50 * 2 * 8 + pair * 3000
    assert got == pytest.approx(generate + 5 * trace)


def _counters():
    model = br.read_json("configs", CONFIG + ".json")
    return {"model": model, "samples_per_iteration": 32, "prompt_len": 256,
            "new_tokens": 512, "num_epochs": 1, "device_kind": "TPU v5 lite",
            "chips": 1}


class _NoSpans:
    lib = staticmethod(br.lib)
    traffic = None
    out_dir = "/nonexistent"


def test_the_readers_give_nothing_for_a_program_without_the_counters():
    run = br.run_module()
    trace = {"window_s": 30.0, "by_program": {
        "jit__epochs_fn": {"s": 12.0, "runs": 3, "median_s": 4.0,
                           "period_s": 10.0},
        "jit__generate": {"s": 6.0, "runs": 3, "median_s": 2.0,
                          "period_s": 10.0}}}
    assert br.lib("flops_sdar").span_counts(_NoSpans) is None
    for name in NEW:
        assert run.reader_of(name).read(trace, _counters(), _NoSpans) is None
    # and another model's configuration is not this reader's to count
    other = dict(_counters(), model=br.read_json(
        "configs", "keye-vl-2.0-30b-a3b-ep8.json"))
    assert run.reader_of("mfu_pct.bdiff").read(trace, other, _NoSpans) is None


def test_the_shares_read_under_100_on_a_planted_trace_at_the_cells_sizes(
        monkeypatch):
    from orion_tpu.config import ModelConfig
    from orion_tpu.models import transformer

    run = br.run_module()
    flops = br.lib("flops_sdar")
    roof = br.lib("roofline_dsv3")
    mc = ModelConfig.sdar_30b_a3b()
    lens = np.random.RandomState(0).randint(128, 257, 32)
    slots = transformer.cache_slots(256 + mc.blocks_spanned(512) * 4)
    rollout = transformer.block_decode_attrs(mc, lens, slots, 512)
    assert rollout["blocks"] == 129 and rollout["denoise_forwards"] == 645
    forward = transformer.stream_attrs(mc, lens, 768, 512)
    assert forward["noisy_tokens"] == 32 * 4 * 516
    assert forward["row_tokens"] == 32 * (768 + 2176)
    # the bf16 copy of this share's weights and its cache: 6 layers x 32
    # rows x 784 slots x k and v of 4 heads of 128
    counts = {"rollout": dict(rollout, weight_bytes=1.29e9,
                              cache_bytes=6 * 32 * slots * 2 * 4 * 128 * 2),
              "forward": forward}
    monkeypatch.setattr(flops, "span_counts", lambda ctx: counts)
    monkeypatch.setattr(roof, "moe_counters", lambda ctx: {
        "moe_pairs_here": 1.0, "moe_pairs_total": 8.0, "moe_load_max": 1.0,
        "moe_load_mean": 1.0})
    # an iteration of 7 s, a rollout of 2 s: what ISSUE 46 expects
    trace = {"window_s": 30.0, "by_program": {
        "jit__epochs_fn": {"s": 9.0, "runs": 3, "median_s": 3.0,
                           "period_s": 7.0},
        "jit__generate": {"s": 6.0, "runs": 3, "median_s": 2.0,
                          "period_s": 7.0}}}
    mfu = run.reader_of("mfu_pct.bdiff").read(trace, _counters(), _NoSpans)
    assert 1 < mfu < 100
    hbm = run.reader_of("denoise_hbm_roofline_pct.train").read(
        trace, _counters(), _NoSpans)
    # 645 forwards of 1.29 GB of weights and ~half a 308 MB cache
    assert hbm == pytest.approx(
        100 * 645 * (1.29e9 + counts["rollout"]["cache_bytes"]
                     * rollout["kv_step_slots"] / slots) / 819e9 / 2.0)
    assert 30 < hbm < 100
