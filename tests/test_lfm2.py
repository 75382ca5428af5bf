"""The ``lfm2_moe`` model (gated short convolutions beside grouped-query
attention of narrow heads, sigmoid-routed experts behind two dense
layers, a tied head) at the tiny size against the plain reference
``benchmarks/reference_lfm2.py`` on seeded weights: the training forward,
its loss and gradients, prefill of unequal prompts and decode through
the cache and through the engine, the two layouts, the expert shares
tied to the model, the selection bias, the published pattern and its
parameter counts, one PPO iteration through the launcher, the
refusals."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import ModelConfig, RolloutConfig
from orion_tpu.models.transformer import (MIXERS, ShortConv, Transformer,
                                          cannot_run, init_cache,
                                          init_params, remat_tag_bytes,
                                          update_attrs)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG_ROW = os.path.join(REPO, "tests", "bench", "fixtures",
                           "lfm2_catalog_row.json")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "lfm2_test_" + name, os.path.join(REPO, "benchmarks", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference_lfm2")
chk = _load("reference_check_lfm2")
flops = _load("flops_lfm2")
kimi_chk = _load("reference_check_kimi_linear")    # the layout reader


def _shape(cfg):
    """The configuration file's keys at a ModelConfig's sizes:
    ``num_experts`` counts the experts HELD."""
    names = {"conv": "conv", "attention": "full_attention"}
    return dict(
        layer_types=[names[m] for m, _ in cfg.layer_kinds()],
        num_hidden_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
        norm_eps=cfg.rms_norm_eps, vocab_size=cfg.vocab_size,
        conv_L_cache=cfg.conv_L_cache, intermediate_size=cfg.intermediate_size,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, rope_theta=cfg.rope_theta,
        num_dense_layers=cfg.first_k_dense_replace,
        num_experts=cfg.experts_held, expert_offset=cfg.expert_offset,
        num_experts_per_tok=cfg.num_experts_per_tok,
        moe_intermediate_size=cfg.moe_intermediate_size,
        routed_scaling_factor=cfg.routed_scaling_factor,
        source_values={"num_experts": cfg.n_routed_experts})


def _weights(params, cfg):
    """The program's tree as the reference takes it: one dict a layer."""
    kinds = ref.layer_kinds(_shape(cfg))
    return {"embed": params["embed"]["embedding"],
            "layers": [chk.layer_weights(
                kimi_chk.layer_tree(params, i, len(kinds)), kind)
                for i, kind in enumerate(kinds)],
            "nf_g": params["final_norm"]["scale"]}


def _reference_logits(params, cfg, ids, n_real=None, **variant):
    mask = None if n_real is None else jnp.arange(ids.shape[0]) < n_real
    return ref.forward(_weights(params, cfg), ids, _shape(cfg),
                       (cfg.expert_offset, cfg.experts_held), mask,
                       **variant)


def _positions(ids):
    return jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig.tiny("lfm2_moe", dtype="float32")
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(0), cfg)
    ids = jax.random.randint(jax.random.key(1), (2, 80), 2, cfg.vocab_size)
    return cfg, model, params, ids


def _count(cfg):
    shapes = jax.eval_shape(
        lambda: init_params(Transformer(cfg), jax.random.key(0), cfg))
    return sum(x.size for x in jax.tree.leaves(shapes))


# ---------------------------------------------------------------------------
# the published model and its cut
# ---------------------------------------------------------------------------

def test_the_preset_is_the_catalog_row():
    row = json.load(open(CATALOG_ROW))
    pub, c = ModelConfig.lfm2_8b_a1b(), row["config"]
    assert row["name"] == "LFM2-8B-A1B" and c["model_type"] == pub.arch
    names = {"conv": "conv", "attention": "full_attention"}
    assert [names[m] for m, _ in pub.layer_kinds()] == c["layer_types"]
    assert [f for _, f in pub.layer_kinds()] == \
        ["dense"] * c["num_dense_layers"] + ["experts"] * (
            c["num_hidden_layers"] - c["num_dense_layers"])
    assert (pub.conv_L_cache, pub.hidden_size, pub.intermediate_size,
            pub.max_seq_len, pub.moe_intermediate_size, pub.rms_norm_eps,
            pub.num_heads, pub.n_routed_experts, pub.num_experts_per_tok,
            pub.num_layers, pub.num_kv_heads, pub.rope_theta,
            pub.routed_scaling_factor, pub.vocab_size) == tuple(
        c[k] for k in ("conv_L_cache", "hidden_size", "intermediate_size",
                       "max_position_embeddings", "moe_intermediate_size",
                       "norm_eps", "num_attention_heads", "num_experts",
                       "num_experts_per_tok", "num_hidden_layers",
                       "num_key_value_heads", "rope_theta",
                       "routed_scaling_factor", "vocab_size"))
    assert c["use_expert_bias"] and pub.moe_scoring == "sigmoid"
    assert c["norm_topk_prob"] and not c["conv_bias"]
    assert pub.head_dim == 64 and pub.attn_heads_a_step() == 4
    assert pub.tie_word_embeddings
    kinds = [m for m, _ in pub.layer_kinds()]
    assert (kinds.count("conv"), kinds.count("attention")) == (18, 6)


def test_the_parameter_counts_are_the_issues():
    pub = ModelConfig.lfm2_8b_a1b()
    whole = _count(pub)
    assert abs(whole - 8.34e9) / 8.34e9 < 0.005, whole
    cut = dataclasses.replace(pub, num_layers=8, experts_held=8,
                              expert_offset=0, vocab_size=16384)
    assert [m for m, _ in cut.layer_kinds()] == [
        "conv", "conv", "attention", "conv", "conv", "conv", "attention",
        "conv"]
    assert cut.layer_runs() == (
        (0, 2, "conv", "dense"), (2, 1, "attention", "experts"),
        (3, 3, "conv", "experts"), (6, 1, "attention", "experts"),
        (7, 1, "conv", "experts"))
    n = _count(cut)
    assert abs(n - 772.2e6) / 772.2e6 < 0.005, n
    # the benchmark's count of the same file: matrix products alone
    model = json.load(open(os.path.join(
        REPO, "benchmarks", "configs", "lfm2-8b-a1b-ep4.json")))
    assert abs(flops.matmul_params(model) - n) / n < 1e-3
    assert abs(flops.whole_model_params(model) - whole) / whole < 1e-3
    p = jax.eval_shape(lambda: init_params(Transformer(cut),
                                           jax.random.key(0), cut))
    assert "lm_head" not in p                                  # tied
    assert p["layers_0"]["attn"]["in_proj"]["kernel"].shape == (2048, 6144)
    assert p["layers_0"]["attn"]["conv_weight"].shape == (3, 2048)
    assert p["layers_0"]["mlp"]["gate_proj"]["kernel"].shape == (2048, 7168)
    assert p["layers_2"]["attn"]["q_norm"]["scale"].shape == (64,)
    assert p["layers_2"]["attn"]["k_proj"]["kernel"].shape == (2048, 512)
    assert p["layers_2"]["mlp"]["router"].shape == (2048, 32)
    assert p["layers_2"]["mlp"]["experts_gate_up_proj"].shape == (
        8, 2048, 2 * 1792)


# ---------------------------------------------------------------------------
# against the reference
# ---------------------------------------------------------------------------

def test_training_forward_matches_reference_float32(tiny):
    cfg, model, params, ids = tiny
    logits, _ = model.apply({"params": params}, ids, _positions(ids),
                            token_mask=_positions(ids) < 70)
    for b in range(2):
        want = _reference_logits(params, cfg, ids[b], n_real=70)
        np.testing.assert_allclose(logits[b, :70], want[:70], atol=5e-5,
                                   rtol=0)
    # and it is none of the models the reference check asks about
    for name, variant in chk.VARIANTS.items():
        other = _reference_logits(params, cfg, ids[0], n_real=70, **variant)
        assert float(jnp.max(jnp.abs(logits[0, :70] - other[:70]))) > 1e-4, \
            name


def test_loss_and_gradients_match_the_reference(tiny):
    cfg, model, params, ids = tiny
    ids = ids[:1, :40]

    def mean_logprob(logits):
        lp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        return jnp.mean(jnp.take_along_axis(lp, ids[:, 1:, None], axis=-1))

    loss, got = jax.value_and_grad(lambda p: mean_logprob(
        model.apply({"params": p}, ids, _positions(ids))[0]))(params)
    ref_loss, want = jax.value_and_grad(lambda p: mean_logprob(
        _reference_logits(p, cfg, ids[0])[None]))(params)
    np.testing.assert_allclose(loss, ref_loss, atol=1e-5, rtol=0)
    np.testing.assert_allclose(
        -loss, ref.loss(_weights(params, cfg), ids[0], _shape(cfg),
                        (0, cfg.experts_held)), atol=1e-5, rtol=0)
    seen = set()
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        leaf = name.split("'")[-2]
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        if leaf == "e_score_correction_bias":
            assert float(jnp.max(jnp.abs(g))) == 0.0   # selection only
            continue
        seen.add(leaf)
        assert scale > 1e-9, name       # a gradient does reach it
        np.testing.assert_allclose(g, w, atol=3e-4 * scale + 1e-9,
                                   rtol=0, err_msg=name)
    # the tied head: the embedding's gradient has both sources
    assert {"conv_weight", "embedding", "router", "experts_gate_up_proj",
            "scale"} <= seen


def test_scanned_and_unrolled_layouts_agree(tiny):
    cfg, model, params, ids = tiny
    scfg = dataclasses.replace(cfg, scan_layers=True, remat=True)
    smodel = Transformer(scfg)
    sparams = init_params(smodel, jax.random.key(0), scfg)
    # the two leading dense layers are a stack like the rest
    assert {"layers_0to1", "layers_2to2", "layers_3to4", "layers_5to5",
            "layers_6to6"} <= set(sparams)
    assert "gate_proj" in sparams["layers_0to1"]["mlp"]
    want = _reference_logits(sparams, scfg, ids[0])
    got, _ = smodel.apply({"params": sparams}, ids[:1], _positions(ids[:1]))
    np.testing.assert_allclose(got[0], want, atol=5e-5, rtol=0)
    cache = init_cache(scfg, 2, 16)
    assert cache["dense"] == [] and len(cache["runs"]) == 5
    # a convolution layer's whole cache: two rows a sequence
    assert set(cache["runs"][0]) == {"conv"}
    assert cache["runs"][0]["conv"].shape == (2, 2, 2, cfg.hidden_size)
    assert cache["runs"][1]["k"].shape == (1, 2, 16, 2, 16)


def test_prefill_then_steps_equal_the_reference_with_unequal_prompts(tiny):
    """A long and a short prompt in one right-padded batch: logits, not
    tokens, against the reference's whole forward of each row alone."""
    cfg, model, params, ids = tiny
    B, P, steps = 2, 40, 6
    lens = jnp.asarray([P, 11])
    pos = _positions(ids)
    cache = init_cache(cfg, B, P + steps)
    got, cache = model.apply({"params": params}, ids[:, :P], pos[:, :P],
                             cache, token_mask=pos[:, :P] < lens[:, None])
    rows = [jnp.concatenate([ids[b, :int(lens[b])], ids[b, P:P + steps]])
            for b in range(B)]
    want = [_reference_logits(params, cfg, row) for row in rows]
    for b in range(B):
        n = int(lens[b])
        np.testing.assert_allclose(got[b, :n], want[b][:n], atol=5e-5,
                                   rtol=0)
    for t in range(steps):
        at = (lens + t)[:, None]
        got, cache = model.apply({"params": params}, ids[:, P + t][:, None],
                                 at, cache)
        for b in range(B):
            np.testing.assert_allclose(
                got[b, 0], want[b][int(lens[b]) + t], atol=5e-5, rtol=0)


def test_the_engine_decodes_through_the_window_and_per_head_cache(tiny):
    from orion_tpu.rollout import RolloutEngine

    cfg, model, params, ids = tiny
    P, T = 32, 16
    eng = RolloutEngine(model, cfg, RolloutConfig(
        max_prompt_len=P, max_new_tokens=T, temperature=1.0))
    eng.load_weights(params)
    lens = np.asarray([P, 5], np.int32)
    prompts = np.where(np.arange(P)[None, :] < lens[:, None],
                       np.asarray(ids[:, :P]), 0).astype(np.int32)
    out = eng.generate(jnp.asarray(prompts), jnp.asarray(lens),
                       jax.random.key(0)).to_host()
    for b in range(2):
        n, new = int(lens[b]), int(out.completion_lens[b])
        row = jnp.asarray(out.sequences[b, :n + new])
        want = ref.next_token_logprobs(
            _reference_logits(params, cfg, row), row)
        np.testing.assert_allclose(out.policy_logprobs[b, :new],
                                   want[n - 1:n - 1 + new], atol=5e-5,
                                   rtol=0)
    # what a decode step touches, from shapes: five convolution layers'
    # two rows, two attention layers' keys and values
    sizes = eng.dispatch_attrs((2, P), lens)
    assert sizes["state_bytes"] == 5 * 2 * 2 * cfg.hidden_size * 4
    assert sizes["cache_bytes"] == 2 * 2 * (2 * (P + T) * 2 * 16 * 4)
    assert sizes["weight_bytes"] == 4 * sum(
        x.size for x in jax.tree.leaves(params))
    assert sizes["attn_heads_a_step"] == 2 and sizes["kda_step"] == ""
    assert sizes["kv_step_form"] == "whole"
    assert (sizes["conv_layers"], sizes["conv_taps"]) == (5, 3)


# ---------------------------------------------------------------------------
# the expert layer's share and its bias
# ---------------------------------------------------------------------------

def _expert_layer(cfg, seed=1):
    from orion_tpu.ops.moe import TopKMoE

    x = jax.random.normal(jax.random.key(0), (2, 24, cfg.hidden_size))
    layer = TopKMoE(cfg)
    params = jax.tree.map(
        lambda t: t.value if hasattr(t, "value") else t,
        layer.init(jax.random.key(seed), x)["params"],
        is_leaf=lambda t: hasattr(t, "value"))
    w = {"w_router": params["router"],
         "router_bias": params["e_score_correction_bias"],
         "e_gate_up": params["experts_gate_up_proj"],
         "e_down": params["experts_down_proj"]}
    return x, layer, params, w


def test_the_four_expert_shares_add_up_to_the_uncut_layer():
    """The routed parts of all four shares (there is no shared expert)
    = the uncut reference's layer."""
    from orion_tpu.ops.moe import TopKMoE

    cfg = ModelConfig.tiny("lfm2_moe", dtype="float32")
    x, whole, params, w = _expert_layer(cfg)
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([ref.expert_ffn(
            x[b], w, _shape(cfg), (0, cfg.n_routed_experts))
            for b in range(2)])
    np.testing.assert_allclose(whole.apply({"params": params}, x), uncut,
                               atol=2e-5, rtol=0)
    total, of = 0.0, 4
    for which in range(of):
        held = cfg.n_routed_experts // of
        scfg = dataclasses.replace(cfg, experts_held=held,
                                   expert_offset=which * held)
        own = slice(which * held, (which + 1) * held)
        part = TopKMoE(scfg).apply({"params": dict(
            params, experts_gate_up_proj=params["experts_gate_up_proj"][own],
            experts_down_proj=params["experts_down_proj"][own])}, x)
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(part, jnp.stack([ref.expert_ffn(
                x[b], dict(w, e_gate_up=w["e_gate_up"][own],
                           e_down=w["e_down"][own]), _shape(scfg),
                (which * held, held)) for b in range(2)]), atol=2e-5, rtol=0)
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=3e-5, rtol=0)


def test_the_bias_selects_and_does_not_gate():
    cfg = ModelConfig.tiny("lfm2_moe", dtype="float32")
    x, layer, params, w = _expert_layer(cfg)
    # a bias that decides every selection: experts 0 and 1, always
    bias = jnp.full((cfg.n_routed_experts,), -5.0).at[:2].set(
        jnp.asarray([5.0, 9.0]))
    out, inter = layer.apply(
        {"params": dict(params, e_score_correction_bias=bias)}, x,
        mutable=["intermediates"])
    sel = np.asarray(inter["intermediates"]["moe_selected"][0])
    assert set(np.unique(sel)) == {0, 1}
    w = dict(w, router_bias=bias)
    with jax.default_matmul_precision("highest"):
        want = ref.expert_ffn(x[0], w, _shape(cfg), (0, 8))
        leaked = ref.expert_ffn(x[0], w, _shape(cfg), (0, 8),
                                gates="biased")
    # the gates are the unbiased scores over the two selected
    np.testing.assert_allclose(out[0], want, atol=2e-5, rtol=0)
    assert float(jnp.max(jnp.abs(out[0] - leaked))) \
        > 0.05 * float(jnp.max(jnp.abs(want)))
    # and no gradient reaches it
    g = jax.grad(lambda b: jnp.sum(layer.apply(
        {"params": dict(params, e_score_correction_bias=b)}, x) ** 2))(bias)
    assert float(jnp.max(jnp.abs(g))) == 0.0


# ---------------------------------------------------------------------------
# the kind's facts, the trainer
# ---------------------------------------------------------------------------

def test_the_kind_states_its_cache_tags_and_attributes():
    cfg = ModelConfig.tiny("lfm2_moe")
    assert MIXERS["conv"] is ShortConv and ShortConv.cache_kind == "state"
    assert cfg.recurrent and cfg.takes_token_mask
    entry = ShortConv.cache_entry(cfg, 3, 40, jnp.bfloat16)
    assert {k: v.shape for k, v in entry.items()} == {"conv": (3, 2, 64)}
    with pytest.raises(ValueError, match=r"ShortConv caches \{conv\}"):
        ShortConv.check_entry({"k": 0, "v": 0})
    # a recurrent kind still says {S, conv}
    with pytest.raises(ValueError, match=r"Mamba2 caches \{S, conv\}"):
        MIXERS["mamba2"].check_entry({"k": 0})
    tags = dict(remat_tag_bytes(cfg, rows=2, seq_len=64))
    n, act = 2 * 64, 2
    # five in-projections of 3 x 64 and two attention layers' q, k, v
    assert tags["attn_qkv"] == n * act * (5 * 3 * 64 + 2 * (4 + 2 * 2) * 16)
    # the gated outputs in the compute dtype; flash's output and lse
    assert tags["attn_out"] == 5 * n * 64 * act + 2 * (
        n * 4 * 16 * act + 2 * 4 * 64 * 4)
    assert update_attrs(cfg, [16] * 4) == {
        "kda_chunk": "", "attn_heads_a_step": 2, "conv_layers": 5,
        "conv_taps": 3, "experts_held": 8}


def test_ppo_iteration_through_the_launcher(tmp_path):
    from orion_tpu import launch

    kept = {}
    real = launch.build_trainer

    def build(algo, cfg, mesh, tokenizer):
        kept["trainer"] = real(algo, cfg, mesh, tokenizer)
        kept["before"] = jax.tree.map(np.asarray,
                                      kept["trainer"].state.params)
        return kept["trainer"]

    launch.build_trainer = build
    try:
        hist = launch.main([
            "ppo", "model_preset=tiny_lfm2_moe", "model.experts_held=4",
            "model.expert_offset=4", "model.remat=true",
            "model.scan_layers=true", "share_backbone=true",
            "model.max_seq_len=24", "rollout.max_prompt_len=16",
            "rollout.max_new_tokens=8", "rollout_batch_size=4",
            "minibatch_size=2", "num_epochs=1", "data.dataset=synthetic",
            "reward=length", "total_iterations=2",
            "optimizer.learning_rate=1e-3", "ref_param_dtype=bfloat16",
            "optimizer.mu_dtype=bfloat16", "optimizer.nu_dtype=bfloat16",
            f"log_dir={tmp_path}"])
    finally:
        launch.build_trainer = real
    assert len(hist) == 2 and all(np.isfinite(r["loss"]) for r in hist)
    row = hist[-1]
    assert (row["conv_layers"], row["conv_taps"], row["experts_held"]) \
        == (5, 3, 4)
    assert row["kda_chunk"] == "" and row["moe_pairs_total"] > 0
    before = kept["before"]["backbone"]
    after = kept["trainer"].state.params["backbone"]
    assert "lm_head" not in after
    for stack, half, name in (("layers_0to1", "attn", "conv_weight"),
                              ("layers_3to4", "attn", "in_proj"),
                              ("layers_2to2", "attn", "q_norm"),
                              ("embed", None, "embedding")):
        a, b = after[stack], before[stack]
        if half:
            a, b = a[half][name], b[half][name]
        else:
            a, b = a[name], b[name]
        moved = max(float(np.max(np.abs(np.asarray(x) - y))) for x, y in zip(
            jax.tree.leaves(a), jax.tree.leaves(b)))
        assert moved > 0, name
    # the selection bias is held: no gradient reaches it
    np.testing.assert_array_equal(
        np.asarray(after["layers_3to4"]["mlp"]["e_score_correction_bias"]),
        before["layers_3to4"]["mlp"]["e_score_correction_bias"])
    assert after["layers_3to4"]["mlp"]["experts_gate_up_proj"].shape[:2] \
        == (2, 4)
    trainer = kept["trainer"]
    sizes = trainer.engine.dispatch_attrs((4, 16), [16] * 4,
                                          trainer.state.params)
    # bf16: five layers' two rows of 64 for four sequences
    assert sizes["state_bytes"] == 5 * 4 * 2 * 64 * 2
    assert sizes["cache_bytes"] > 0 and sizes["weight_bytes"] > 0


def _refusals():
    from orion_tpu.models.hf_export import hf_state_dict
    from orion_tpu.models.hf_loader import (config_from_hf,
                                            convert_hf_state_dict)
    from orion_tpu.rollout import RolloutEngine
    from orion_tpu.rollout.continuous import ContinuousBatchingEngine

    cfg = ModelConfig.tiny("lfm2_moe")
    model = Transformer(cfg)

    def engine(**kw):
        return lambda: RolloutEngine(model, cfg, RolloutConfig(**kw))

    def tiny(**kw):
        return lambda: ModelConfig.tiny("lfm2_moe", **kw)

    class HF:
        model_type = "lfm2_moe"

    return {
        "continuous": (lambda: ContinuousBatchingEngine(
            model, cfg, RolloutConfig()),
            r"no convolution window per slot .* convolution's \{conv\}"),
        "paged": (engine(paged=True),
                  "convolution window is not made of pages"),
        "quantize_kv": (engine(quantize_kv=True),
                        "int8 form of a convolution's last inputs"),
        "quantize_weights": (engine(quantize_weights=True),
                             "int8 Dense twins do not reach this block"),
        "speculative": (engine(speculative_k=2),
                        "rollout.engine=continuous"),
        "ring": (tiny(attention_impl="ring"),
                 "hand-over of a convolution window between sequence shards"),
        "ulysses": (tiny(attention_impl="ulysses"),
                    "hand-over of a convolution window between sequence "
                    "shards"),
        "seq_shard": (tiny(seq_shard_activations=True),
                      "a convolution takes whole sequences"),
        "hf_import": (lambda: convert_hf_state_dict({}, cfg),
                      "no lfm2_moe checkpoint loader"),
        "hf_config": (lambda: config_from_hf(HF()),
                      "no lfm2_moe checkpoint loader"),
        "hf_export": (lambda: hf_state_dict(
            init_params(model, jax.random.key(0), cfg), cfg),
            "no lfm2_moe checkpoint layout"),
        "int8_cache": (lambda: init_cache(cfg, 1, 8, quantized=True),
                       "convolution window has no int8 form"),
        "layer_type": (tiny(layer_types=("conv", "linear_attention") * 4),
                       r"names \['conv', 'full_attention'\]"),
        "short_pattern": (tiny(layer_types=("conv",) * 3),
                          "at least num_layers=7"),
        "taps": (tiny(conv_L_cache=1), "conv_L_cache >= 2"),
        "softmax_scores": (tiny(moe_scoring="softmax"),
                           "scores by a sigmoid"),
        "dense_layers": (tiny(first_k_dense_replace=9),
                         "num_dense_layers"),
        "quantize_dense": (tiny(quantize_dense=True), "no int8 Dense twin"),
        "tied_elsewhere": (lambda: ModelConfig.tiny(
            "sdar_moe", tie_word_embeddings=True), "an untied head"),
        "dense_elsewhere": (lambda: ModelConfig.tiny(
            "sdar_moe", first_k_dense_replace=1),
            "every layer is an expert layer"),
    }


@pytest.mark.parametrize("path", [
    "continuous", "paged", "quantize_kv", "quantize_weights", "speculative",
    "ring", "ulysses", "seq_shard", "hf_import", "hf_config", "hf_export",
    "int8_cache", "layer_type", "short_pattern", "taps", "softmax_scores",
    "dense_layers", "quantize_dense", "tied_elsewhere", "dense_elsewhere"])
def test_paths_that_cannot_run_it_name_the_missing_mechanism(path):
    call, words = _refusals()[path]
    with pytest.raises(ValueError, match=words):
        call()
    if path in ("paged", "continuous", "quantize_kv", "quantize_weights"):
        assert cannot_run(ModelConfig.tiny("lfm2_moe"), path)
