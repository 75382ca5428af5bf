"""The ``deepseek_v3`` configuration's part of the benchmark on the CPU:
the ``train`` runner rehearsed with the configuration's tiny sibling and
``reference_check_dsv3``; ``flops_dsv3`` against a count of an
initialised model's parameters; the new readers on a recorded fixture;
and the faults the check must catch, each shown failing.  Nothing
printed here is a measurement."""

import copy
import json
import os
import time

import numpy as np
import pytest

import bench_rehearsal as br

CELL = "ppo-kanana-ep8-sync"
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "dsv3_moe_spans.json")


def _tiny_shape(cfg, **more):
    return dict(
        num_hidden_layers=cfg.num_layers, num_attention_heads=cfg.num_heads,
        hidden_size=cfg.hidden_size, intermediate_size=cfg.intermediate_size,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.rms_norm_eps,
        num_experts_per_tok=cfg.num_experts_per_tok,
        n_shared_experts=cfg.n_shared_experts,
        moe_intermediate_size=cfg.moe_intermediate_size,
        first_k_dense_replace=cfg.first_k_dense_replace,
        routed_scaling_factor=cfg.routed_scaling_factor,
        n_routed_experts=cfg.experts_held, expert_offset=cfg.expert_offset,
        source_values={"n_routed_experts": cfg.n_routed_experts},
        vocab_size=cfg.vocab_size, **more)


def _tiny_config():
    """The configuration file with the tiny sibling's sizes (every
    expert held) and the preset that builds it."""
    from orion_tpu.config import ModelConfig

    cfg = ModelConfig.tiny_deepseek_v3()
    file = br.read_json("configs", br.read_json("cells", CELL + ".json")[
        "config"] + ".json")
    shape = _tiny_shape(
        cfg, launch=["model_preset=tiny_deepseek_v3",
                     "model.max_seq_len=128", "model.dtype=float32"])
    return dict(file, **dict(shape, vocab_size=260))


def _rehearse(trace, capsys, monkeypatch, tmp_path):
    run = br.run_module()
    monkeypatch.setattr(run, "REPO", str(tmp_path))
    run.main(["--workload", CELL, "--seed", "3000000019", "--seconds", "2.0",
              "--trace", str(trace)],
             rehearsal=run.Rehearsal(config=_tiny_config(),
                                     traffic=br.tiny_traffic(CELL),
                                     device=dict(br.FAKE_DEVICE),
                                     manifest=br.manifest(),
                                     reduce_trace=br.reduce_cpu_trace),
             t_process_start=time.perf_counter())
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    detail = [json.loads(ln) for ln in lines
              if ln.startswith('{"phase": "result_detail"')]
    return json.loads(lines[-1]), detail[-1]


def test_the_cell_is_in_the_manifest_as_specified():
    m = br.manifest()
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "ppo-sync-b32-s1024"
    cfg = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    file = br.read_json("configs", cell["config"] + ".json")
    assert file["source_values"] == {"num_hidden_layers": 48,
                                     "n_routed_experts": 128,
                                     "vocab_size": 128256}
    assert (file["num_hidden_layers"], file["n_routed_experts"],
            file["vocab_size"]) == (6, 16, 16032)
    # widths as published
    assert (file["hidden_size"], file["kv_lora_rank"],
            file["qk_nope_head_dim"], file["qk_rope_head_dim"],
            file["v_head_dim"], file["moe_intermediate_size"],
            file["intermediate_size"], file["num_experts_per_tok"]) == (
        2048, 512, 128, 64, 128, 768, 6144, 6)
    mine = {p["name"] for p in br.run_module().metrics_of(m, "per_layer",
                                                          CELL)}
    assert {"mfu_pct.moe", "moe_load_max_over_mean.train",
            "update_ms.train", "rollout_ms.train"} <= mine
    assert "mfu_pct.train" not in mine     # flops.py counts GPT-NeoX
    # the preset and the cut give the program what the file states
    from orion_tpu.config import PPOConfig, load_config

    mc = load_config(PPOConfig, cli_args=file["launch"]).model
    assert (mc.num_layers, mc.experts_held, mc.n_routed_experts,
            mc.vocab_size, mc.kv_lora_rank) == (6, 16, 128, 16032, 512)


def test_untraced_rehearsal_is_correct(capsys, monkeypatch, tmp_path):
    line, detail = _rehearse(0, capsys, monkeypatch, tmp_path)
    assert line["correct"] is True, detail["why_incorrect"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    ref = detail["info"]["reference"]
    assert ref["ok"] and ref["tokens"] == 16
    assert ref["unfollowed_share"] == 0.0
    assert ref["max_abs_diff"] < 1e-4      # float32 against float32


def test_traced_rehearsal_reads_the_new_metrics(capsys, monkeypatch,
                                                tmp_path):
    line, detail = _rehearse(1, capsys, monkeypatch, tmp_path)
    assert line["correct"] is True, detail["why_incorrect"]
    got = line["metrics"]
    assert got["mfu_pct.moe"]["value"] > 0
    # every expert is held at the tiny size, and a few tokens cannot
    # load eight experts evenly
    assert got["moe_load_max_over_mean.train"]["value"] > 1.0
    assert {"update_ms.train", "rollout_ms.train", "experience_ms.train",
            "host_busy_ms.train", "host_wait_ms.train"} <= set(got)
    hs = br.lib("host_spans")
    out_dir = os.path.join(str(tmp_path), "chiprun_out", "bench", CELL)
    spans = hs.load(br.lib("harness").Tracer(
        True, out_dir + "/trace").xplane_path())
    final = spans.whole("stats.finalize")
    assert final and all(
        float(sp.stats["moe_pairs_here"]) == float(
            sp.stats["moe_pairs_total"]) > 0 for sp in final)
    dispatch = spans.whole("rollout.dispatch")
    # 3 layers x (16 + 8) latent-cache bytes x 2 x 4 sequences x 24 slots
    assert [int(sp.stats["cache_bytes"]) for sp in dispatch] == \
        [3 * 24 * 4 * 4 * 24] * len(dispatch)


def test_flops_count_the_parameters_of_an_initialised_model():
    import jax

    from orion_tpu.config import ModelConfig
    from orion_tpu.models.transformer import Transformer, init_params

    cfg = ModelConfig.tiny("deepseek_v3", experts_held=4, expert_offset=2)
    params = init_params(Transformer(cfg), jax.random.key(0), cfg)
    counted = 0
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = jax.tree_util.keystr(path)
        if any(k in name for k in ("embedding", "norm", "correction_bias")):
            continue
        counted += x.size
    flops = br.lib("flops_dsv3")
    shape = _tiny_shape(cfg)
    assert flops.matmul_params(shape) == counted
    assert flops.layers_of(shape) == (1, 2)
    # with every selected expert held the forward is 2 per parameter
    # that a token touches, plus attention
    fwd = flops.forward_flops_per_token(shape, context=0.0, held_share=1.0)
    touched = counted - (cfg.experts_held - cfg.num_experts_per_tok) * 2 \
        * flops.expert_params(shape)
    assert fwd == 2 * touched
    full = br.read_json("configs", "kanana-2-30b-a3b-ep8.json")
    assert flops.matmul_params(full) == pytest.approx(654.6e6, rel=1e-3)


def test_new_readers_on_a_recorded_fixture(monkeypatch):
    """Spans as a traced run records them (``stats.finalize`` with the
    ``moe_*`` attributes as the profiler stores them: text) and a device
    trace cut to what the readers use."""
    with open(FIXTURE) as f:
        fx = json.load(f)
    hs = br.lib("host_spans")
    spans = hs.from_planes(fx["planes"])
    monkeypatch.setattr(hs, "of_run", lambda ctx: spans)
    run = br.run_module()

    class Ctx:
        lib = staticmethod(br.lib)

    counters = dict(fx["counters"], model=br.read_json(
        "configs", "kanana-2-30b-a3b-ep8.json"))
    load = run.reader_of("moe_load_max_over_mean.train").read(
        fx["trace"], counters, Ctx)
    assert load == pytest.approx(1010.0 / 770.0)       # the median row
    mfu = run.reader_of("mfu_pct.moe").read(fx["trace"], counters, Ctx)
    flops = br.lib("flops_dsv3").ppo_iteration_flops(
        counters["model"], 32, 512, 512, 1, held_share=12320.0 / 98304.0)
    assert mfu == pytest.approx(100 * flops / 3.0 / 197e12)
    roof = br.lib("roofline_dsv3")
    assert roof.kernel_seconds(fx["trace"], "moe_gmm") == pytest.approx(0.3)
    assert roof.kernel_seconds(fx["trace"], "moe_tgmm") is None
    pct = roof.roofline_pct("moe_gmm", fx["trace"], counters, Ctx)
    ops, byts = roof.work(br.lib("flops_dsv3"), "moe_gmm",
                          counters["model"], counters, 12320.0 / 98304.0)
    assert pct == pytest.approx(
        100 * max(ops / 197e12, byts / 819e9) * (9.0 / 3.0) / 0.3)
    assert 0 < pct < 100
    # a program without the counters (the parent) gives nothing to read
    bare = hs.from_planes([{"name": "/host:CPU", "lines": [{
        "name": "python3", "events": [
            e for e in fx["planes"][0]["lines"][0]["events"]
            if e[0] != "stats.finalize"]}]}])
    monkeypatch.setattr(hs, "of_run", lambda ctx: bare)
    for name in ("mfu_pct.moe", "moe_load_max_over_mean.train"):
        assert run.reader_of(name).read(fx["trace"], counters, Ctx) is None


# -- the check and the faults it must catch ---------------------------------

class _Model:
    """The program's model with a fault between the parameters it is
    given and the ones it computes with."""

    def __init__(self, model, fault):
        self.model, self.fault = model, fault

    def apply(self, variables, *a, **k):
        return self.model.apply({"params": self.fault(variables["params"])},
                                *a, **k)


class _Trainer:
    """What ``check_trainer`` uses of a trainer."""

    def __init__(self, cfg, model, params):
        import types

        from orion_tpu.trainers.base import BaseTrainer

        self.cfg = types.SimpleNamespace(model=cfg)
        self.model = model
        self.state = types.SimpleNamespace(params=params)
        self._policy_apply = types.MethodType(BaseTrainer._policy_apply, self)
        self._windowed_forward = types.MethodType(
            BaseTrainer._windowed_forward, self)
        import jax

        self._jit_logprobs = jax.jit(
            types.MethodType(BaseTrainer._logprobs_fn, self),
            static_argnames=("max_new",))
        from orion_tpu.config import RolloutConfig
        from orion_tpu.rollout.engine import RolloutEngine

        self.engine = RolloutEngine(model, cfg, RolloutConfig(
            max_prompt_len=24, max_new_tokens=40, temperature=1.0))

    def generate(self, prompt_ids, prompt_lens, rng):
        return self.engine.generate(prompt_ids, prompt_lens, rng,
                                    params=self.state.params)


class _Ctx:
    def __init__(self, config, seed):
        self.config, self.seed = config, seed
        self.traffic = {"prompt_len": 24, "new_tokens": 40,
                        "samples_per_iteration": 4}
        self.cell = {"chips": 1}

    lib = staticmethod(br.lib)


def _fp8(params):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
        if x.ndim >= 2 else x, params)


def _drop_an_expert(params):
    params = copy.deepcopy(params)
    for name, layer in params.items():
        if "experts_down_proj" in layer.get("mlp", {}):
            w = layer["mlp"]["experts_down_proj"]
            layer["mlp"]["experts_down_proj"] = w.at[1].set(0.0)
    return params


def _leaky_route(z, router_kernel, bias, k, scale):
    """The fault of a bias that leaks into the gates: they are taken
    from the BIASED scores."""
    import jax
    import jax.numpy as jnp

    biased = jax.nn.sigmoid(z.astype(jnp.float32) @ router_kernel.astype(
        jnp.float32)) + bias[None, :]
    top, idx = jax.lax.top_k(biased, k)
    return idx.astype(jnp.int32), scale * top / jnp.sum(top, axis=-1,
                                                       keepdims=True)


def _route_without_bias(real):
    """The program's router with the bias left out of the selection."""
    import jax.numpy as jnp

    def route(z, router_kernel, bias, k, scale):
        return real(z, router_kernel, jnp.zeros_like(bias), k, scale)
    return route


def _writer_that_forgets_decode_steps(real):
    """The cache write with a decode step's own entry left out."""
    def cache_writer(positions, B, L):
        return (lambda cache, new: cache) if L == 1 \
            else real(positions, B, L)
    return cache_writer


# the faults that move the training forward's logprobs, those that the
# reference follows and only the selection shows, and one of the decode
# path alone
FAULTS = ["none", "lower_precision", "dropped_expert", "gate_without_scale",
          "bias_in_the_gates", "bias_out_of_the_selection",
          "one_expert_fewer", "decode_drops_its_cache_write"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_check_passes_the_program_and_catches_each_fault(fault,
                                                             monkeypatch):
    import dataclasses

    import jax
    from jax.sharding import Mesh

    from orion_tpu.config import ModelConfig
    from orion_tpu.models.transformer import Transformer, init_params
    from orion_tpu.ops import moe

    # bfloat16 as the cell computes; half the experts held
    cfg = ModelConfig.tiny("deepseek_v3", experts_held=4, expert_offset=2,
                           vocab_size=260)
    params = init_params(Transformer(cfg), jax.random.key(21), cfg)
    program_cfg, change = cfg, (lambda p: p)
    if fault == "lower_precision":
        change = _fp8            # the nearest precision below bfloat16
    elif fault == "dropped_expert":
        change = _drop_an_expert
    elif fault == "gate_without_scale":
        program_cfg = dataclasses.replace(cfg, routed_scaling_factor=1.0)
    elif fault == "bias_in_the_gates":
        monkeypatch.setattr(moe, "sigmoid_topk_route", _leaky_route)
        # a bias as wide as the scores themselves: at the tiny size the
        # routed experts are a small part of the stream, and the limits
        # are those of the published widths
        for i in (1, 2):
            mlp = params[f"layers_{i}"]["mlp"]
            mlp["e_score_correction_bias"] = jax.random.normal(
                jax.random.key(30 + i), mlp["e_score_correction_bias"].shape)
    elif fault == "bias_out_of_the_selection":
        # the bias as the program initialises it (0.02)
        monkeypatch.setattr(moe, "sigmoid_topk_route",
                            _route_without_bias(moe.sigmoid_topk_route))
    elif fault == "one_expert_fewer":
        program_cfg = dataclasses.replace(
            cfg, num_experts_per_tok=cfg.num_experts_per_tok - 1)
    elif fault == "decode_drops_its_cache_write":
        from orion_tpu.models import transformer

        monkeypatch.setattr(transformer, "_cache_writer",
                            _writer_that_forgets_decode_steps(
                                transformer._cache_writer))
    trainer = _Trainer(cfg, _Model(Transformer(program_cfg), change), params)
    chk = br.lib("reference_check_dsv3")
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    verdicts = [chk.check_trainer(_Ctx(_tiny_shape(cfg), seed), trainer, mesh)
                for seed in (1, 2)]
    if fault == "one_expert_fewer":
        assert not any(v["ok"] for v in verdicts)
        assert all("selects 1 experts" in v["why"] for v in verdicts)
        return
    assert all(v["tokens"] == 80 for v in verdicts)
    if fault == "bias_out_of_the_selection":
        # followed, so the logprobs agree; the selection does not
        assert not any(v["ok"] for v in verdicts), verdicts
        assert all(v["mean_abs_diff"] <= v["mean_tolerance"]
                   and v["unfollowed_share"] == 0.0 for v in verdicts)
        assert all(v["selection_excess_sigmas"] > v["selection_excess_limit"]
                   or v["exchanged_tokens"] > v["exchanges_allowed"]
                   for v in verdicts), verdicts
    elif fault == "decode_drops_its_cache_write":
        # the training forward never reads the cache
        assert not any(v["ok"] for v in verdicts), verdicts
        assert all(v["mean_abs_diff"] <= v["mean_tolerance"]
                   and v["max_abs_diff"] <= v["max_tolerance"]
                   and v["decode_mean_abs_diff"]
                   > 3 * v["decode_mean_tolerance"]
                   for v in verdicts), verdicts
    elif fault == "none":
        assert all(v["ok"] for v in verdicts), verdicts
        assert all(v["unfollowed_share"] == 0.0 for v in verdicts)
        assert all(v["decode_tokens"] > 40 for v in verdicts)
    else:
        assert not any(v["ok"] for v in verdicts), verdicts
        # by the comparison of the logprobs, not by a side condition
        assert all(v["mean_abs_diff"] > v["mean_tolerance"]
                   or v["max_abs_diff"] > v["max_tolerance"]
                   for v in verdicts), verdicts


def test_a_selection_outside_the_margin_fails_the_check():
    """A program whose selection the reference cannot have made within
    rounding (here: a bias left out of the selection) is refused by the
    selection bound even where its logprobs are followed exactly."""
    chk, base = br.lib("reference_check_dsv3"), br.lib("reference_check")
    probe = {"sigma_z": 0.9, "depth": np.array([1, 2]),
             "margin": np.full((2, 50), 40.0 * base.U_BF16 * 12),
             "excess": np.zeros((2, 50, 6)),
             "exchanged": np.zeros((2, 50), bool)}
    good = chk.verdict(base, [np.full(50, 0.004)], [probe], 6)
    assert good["ok"] and good["exchanges_predicted"] < 1e-3
    bad = dict(probe, excess=probe["excess"].copy())
    bad["excess"][1, 7, 2] = 30.0 * chk.input_error(base, 2)
    worse = chk.verdict(base, [np.full(50, 0.004)], [bad], 6)
    assert not worse["ok"] and worse["selection_excess_sigmas"] > 8
    # and too many tokens that the timed forward and the followed one
    # disagree on
    some = np.ones(50, bool)
    some[:3] = False
    assert not chk.verdict(base, [np.full(50, 0.004)], [probe], 6,
                           [some])["ok"]


@pytest.mark.parametrize("bias_selects", [True, False])
def test_the_selection_bound_at_the_published_router(bias_selects):
    """The router alone at the published widths (2048 -> 128, top-6,
    weights and bias as the program initialises them): the program's
    selection, made from an input with the error the model gives it
    after three layers, stays inside the bound; with the bias (0.02)
    left out of the selection it does not."""
    import jax
    import jax.numpy as jnp

    from orion_tpu.ops import moe

    chk, base = br.lib("reference_check_dsv3"), br.lib("reference_check")
    ref = br.lib("reference_dsv3")
    file = br.read_json("configs", "kanana-2-30b-a3b-ep8.json")
    D, E = file["hidden_size"], file["source_values"]["n_routed_experts"]
    k, depth, n = file["num_experts_per_tok"], 3, 256
    keys = jax.random.split(jax.random.key(7), 5)
    z = jax.random.normal(keys[0], (n, D))        # a norm's output
    router = 0.02 * jax.random.normal(keys[1], (D, E))
    bias = 0.02 * jax.random.normal(keys[2], (E,))
    seen = (z * (1.0 + chk.input_error(base, depth)
                 * jax.random.normal(keys[3], z.shape))).astype(jnp.bfloat16)
    selected, _ = moe.sigmoid_topk_route(
        seen, router, bias if bias_selects else jnp.zeros_like(bias), k,
        file["routed_scaling_factor"])
    small = 0.02 * jax.random.normal(keys[4], (D, 16))
    w = {"w_router": router, "router_bias": bias,
         "e_gate_up": small[None], "e_down": small[None, :, :8].mT,
         "s_gate_up": small, "s_down": small[:, :8].T}
    _, probe = ref.expert_ffn(z, w, file, (0, 1), selected, probe=True)
    probe = {name: np.asarray(x)[None] for name, x in probe.items()}
    v = chk.verdict(base, [np.full(n, 0.004)],
                    [dict(probe, sigma_z=0.9, depth=np.array([depth]))], 6)
    assert v["exchanged_tokens"] > 4      # the selection is discrete
    if bias_selects:
        assert v["ok"], v
        assert v["selection_excess_sigmas"] < 5
    else:
        assert not v["ok"]
        assert v["selection_excess_sigmas"] > 2 * v["selection_excess_limit"]
        assert v["exchanged_tokens"] > v["exchanges_allowed"]
