"""The ``olmo_hybrid`` block (three gated-delta-rule layers with one decay
a head and heads of unequal key and value size to one full-attention
layer with a norm over the whole query and key projections and no
rotation, a dense SwiGLU, post-norm) at the tiny size against the plain
reference ``benchmarks/reference_olmo_hybrid.py`` on seeded weights: the
delta rule's two forms at dk != dv, a step size above 1, the training
forward, gradients of the PPO loss's logprobs, prefill of unequal
prompts and decode through the engine, the published pattern and its
parameter counts, one PPO iteration through the launcher, the
refusals."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import ModelConfig, RolloutConfig
from orion_tpu.models.transformer import (Transformer, init_cache,
                                          init_params, remat_tag_bytes)
from orion_tpu.ops.kda import kda_chunked, kda_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "olmo_test_" + name, os.path.join(REPO, "benchmarks", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference_olmo_hybrid")
chk = _load("reference_check_olmo_hybrid")
kimi_chk = _load("reference_check_kimi_linear")    # the layout reader


def _shape(cfg):
    """The configuration file's keys at a ModelConfig's sizes."""
    return dict(
        layer_types=list(cfg.layer_types), num_hidden_layers=cfg.num_layers,
        hidden_size=cfg.hidden_size, intermediate_size=cfg.intermediate_size,
        num_attention_heads=cfg.num_heads, vocab_size=cfg.vocab_size,
        rms_norm_eps=cfg.rms_norm_eps,
        linear_num_key_heads=cfg.linear_num_key_heads,
        linear_num_value_heads=cfg.linear_num_value_heads,
        linear_key_head_dim=cfg.linear_key_head_dim,
        linear_value_head_dim=cfg.linear_value_head_dim,
        linear_conv_kernel_dim=cfg.linear_conv_kernel_dim,
        linear_allow_neg_eigval=cfg.linear_allow_neg_eigval)


def _reference_logits(params, cfg, ids, n_real=None, rotated=False):
    """The reference on one sequence ``ids`` [L], from the program's
    parameter tree."""
    layers = [chk.layer_weights(kimi_chk.layer_tree(params, i,
                                                    cfg.num_layers))
              for i in range(cfg.num_layers)]
    weights = {"embed": params["embed"]["embedding"], "layers": layers,
               "nf_g": params["final_norm"]["scale"],
               "w_head": params["lm_head"]["kernel"]}
    mask = None if n_real is None else jnp.arange(ids.shape[0]) < n_real
    return ref.forward(weights, ids, _shape(cfg), mask, rotated)


def _positions(ids):
    return jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)


@pytest.fixture(scope="module")
def tiny():
    """Two periods, so that two stretches of each kind scan; heads of
    (12, 24): neither side a tile, dk != dv."""
    cfg = ModelConfig.tiny("olmo_hybrid", dtype="float32", num_layers=8)
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(0), cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(2, 256, (2, 80)),
                      jnp.int32)
    return cfg, model, params, ids


# ---------------------------------------------------------------------------
# the delta rule with one decay a head, dk != dv, beta up to 2
# ---------------------------------------------------------------------------

def _gdn_inputs(L, H=3, dk=12, dv=24, seed=0):
    rs = np.random.RandomState(seed)
    q, k = (rs.normal(size=(1, L, H, dk)) for _ in range(2))
    v = rs.normal(size=(1, L, H, dv))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    g = -rs.uniform(1.0, 16.0, (1, 1, H, 1)) * np.exp(
        rs.uniform(np.log(1e-3), np.log(1e-1), (1, L, H, 1)))
    beta = 2.0 / (1.0 + np.exp(-rs.normal(size=(1, L, H))))
    return tuple(jnp.asarray(t, jnp.float32) for t in (q, k, v, g, beta))


def _token_by_token(q, k, v, g, beta):
    S = jnp.zeros((1, q.shape[2], q.shape[3], v.shape[3]), jnp.float32)
    out = []
    for t in range(q.shape[1]):
        o, S = kda_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], S)
        out.append(o)
    return jnp.stack(out, axis=1), S


@pytest.mark.parametrize("L", [64, 100])
def test_chunked_equals_stepwise_and_reference_at_unequal_heads(L):
    q, k, v, g, beta = _gdn_inputs(L)
    assert float(jnp.max(beta)) > 1.0       # the eigenvalue goes negative
    o, S = kda_chunked(q, k, v, g, beta)
    o_step, S_step = _token_by_token(q, k, v, g, beta)
    o_ref, S_ref = ref.delta_rule(q[0], k[0], v[0], g[0, ..., 0], beta[0],
                                  jnp.ones((L,), bool))
    assert o.shape == (1, L, 3, 24) and S.shape == (1, 3, 12, 24)
    for got, want in ((o, o_step), (S, S_step), (o[0], o_ref),
                      (S[0], S_ref)):
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # one decay a head is the per-channel rule with g equal over a
    # head's channels
    o_wide, S_wide = kda_chunked(q, k, v, jnp.broadcast_to(g, q.shape), beta)
    np.testing.assert_allclose(o, o_wide, atol=1e-6, rtol=0)
    np.testing.assert_allclose(S, S_wide, atol=1e-6, rtol=0)


def test_lane_padding_leaves_the_result_as_it_was():
    """What surrounds the kernels on a TPU (zero key channels to 128,
    zero value columns to 256, the decay broadcast), through the
    ``jax.numpy`` form: the real part of the output and of the state
    are unchanged, the padded part of the state stays zero."""
    from orion_tpu.ops.kda import _to_lane_tiles

    q, k, v, g, beta = _gdn_inputs(70, dk=12, dv=24)
    S0 = jnp.asarray(np.random.RandomState(1).normal(size=(1, 3, 12, 24)),
                     jnp.float32)
    o, S = kda_chunked(q, k, v, g, beta, S0)
    qp, kp, vp, gp, Sp = _to_lane_tiles(q, k, v, g, S0)
    assert (qp.shape[-1], vp.shape[-1], gp.shape, Sp.shape) == (
        128, 128, qp.shape, (1, 3, 128, 128))
    o_pad, S_pad = kda_chunked(qp, kp, vp, gp, beta, Sp)
    np.testing.assert_allclose(o_pad[..., :24], o, atol=1e-6, rtol=0)
    np.testing.assert_allclose(S_pad[:, :, :12, :24], S, atol=1e-6, rtol=0)
    assert not np.any(np.asarray(o_pad[..., 24:]))
    assert not np.any(np.asarray(S_pad[:, :, 12:])) \
        and not np.any(np.asarray(S_pad[..., 24:]))


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

def test_the_pattern_and_the_parameter_counts_are_the_published_ones():
    full = ModelConfig.olmo_hybrid_7b()
    kinds = [m for m, f in full.layer_kinds()]
    assert len(kinds) == 32 and kinds.count("gdn") == 24 \
        and kinds.count("attention") == 8
    assert kinds[:4] == ["gdn", "gdn", "gdn", "attention"]
    assert all(f == "dense" for _, f in full.layer_kinds())
    assert full.recurrent and full.takes_token_mask and full.pattern \
        and not full.latent_attention
    assert full.delta_head_dims() == (96, 192) and full.head_dim == 128

    def count(cfg):
        shapes = jax.eval_shape(lambda: init_params(
            Transformer(cfg), jax.random.key(0), cfg))
        return sum(x.size for x in jax.tree.leaves(shapes))

    assert count(full) == pytest.approx(7.43e9, rel=1e-3)
    cut = dataclasses.replace(full, num_layers=4, vocab_size=12544)
    assert cut.layer_runs() == ((0, 3, "gdn", "dense"),
                                (3, 1, "attention", "dense"))
    assert count(cut) == pytest.approx(928.6e6, rel=5e-4)
    # ISSUE 34's arithmetic, layer by layer
    gdn = (3840 * (2 * 2880 + 3 * 5760) + 2 * 3840 * 30 + 11520 * 4
           + 2 * 30 + 192)
    mlp = 3 * 3840 * 11008 + 2 * 3840
    attn = 4 * 3840 ** 2 + 2 * 3840
    assert count(cut) == 3 * (gdn + mlp) + attn + mlp \
        + 2 * 12544 * 3840 + 3840


def test_training_forward_matches_reference_float32(tiny):
    cfg, model, params, ids = tiny
    logits, _ = model.apply({"params": params}, ids, _positions(ids),
                            token_mask=_positions(ids) < 70)
    for b in range(2):
        want = _reference_logits(params, cfg, ids[b], n_real=70)
        np.testing.assert_allclose(logits[b, :70], want[:70], atol=5e-5,
                                   rtol=0)
    # and it is not the rotated model
    rot = _reference_logits(params, cfg, ids[0], n_real=70, rotated=True)
    assert float(jnp.max(jnp.abs(logits[0, :70] - rot[:70]))) > 1e-2


def test_loss_and_gradients_match_the_reference(tiny):
    _, _, _, ids = tiny
    cfg = ModelConfig.tiny("olmo_hybrid", dtype="float32")   # one period
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(1), cfg)
    ids = ids[:1, :40]

    def mean_logprob(logits):
        lp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        return jnp.mean(jnp.take_along_axis(lp, ids[:, 1:, None], axis=-1))

    loss, got = jax.value_and_grad(lambda p: mean_logprob(
        model.apply({"params": p}, ids, _positions(ids))[0]))(params)
    ref_loss, want = jax.value_and_grad(lambda p: mean_logprob(
        _reference_logits(p, cfg, ids[0])[None]))(params)
    np.testing.assert_allclose(loss, ref_loss, atol=1e-5, rtol=0)
    seen = set()
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        seen.add(name.split("'")[-2])
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        assert scale > 1e-9, name       # a gradient does reach it
        np.testing.assert_allclose(g, w, atol=3e-4 * scale + 1e-9,
                                   rtol=0, err_msg=name)
    assert {"A_log", "dt_bias", "q_conv", "v_conv", "o_norm"} <= seen


def test_scanned_and_unrolled_layouts_agree(tiny):
    cfg, model, params, ids = tiny
    scfg = dataclasses.replace(cfg, scan_layers=True, remat=True)
    smodel = Transformer(scfg)
    sparams = init_params(smodel, jax.random.key(0), scfg)
    assert {"layers_0to2", "layers_3to3", "layers_4to6", "layers_7to7"} \
        <= set(sparams)
    want = _reference_logits(sparams, scfg, ids[0])
    got, _ = smodel.apply({"params": sparams}, ids[:1], _positions(ids[:1]))
    np.testing.assert_allclose(got[0], want, atol=5e-5, rtol=0)
    cache = init_cache(scfg, 2, 16)
    assert cache["dense"] == [] and len(cache["runs"]) == 4
    assert cache["runs"][0]["S"].shape == (3, 2, 3, 12, 24)
    assert cache["runs"][0]["conv"].shape == (3, 2, 3, 3 * (2 * 12 + 24))
    assert cache["runs"][1]["k"].shape == (1, 2, 16, 4, 16)


@pytest.mark.parametrize("step", ["jnp", "kernel"])
def test_the_engine_decodes_through_state_and_per_head_cache(tiny, step,
                                                             monkeypatch):
    """``RolloutEngine``: a long and a short prompt in one right-padded
    batch; prefill hands decode the state, the convolutions' last inputs
    and a per-head cache with each row's real length; the policy
    logprobs it recorded are the teacher-forced ones of the reference
    on what it sampled.  ``step``: the form the one-token step takes
    inside the decode ``while_loop`` (``kernel``: ops/pallas/
    kda_step.py, interpreted here, one decay a head, heads of 12 x
    24)."""
    from orion_tpu.ops import kda
    from orion_tpu.rollout import RolloutEngine

    monkeypatch.setattr(kda, "step_form", lambda dk, dv: step)
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
    # what a decode step touches, from shapes: both kinds non-zero
    H, dk, dv = 3, 12, 24
    sizes = eng.dispatch_attrs((2, P), lens)
    assert sizes["cache_bytes"] == 2 * 2 * (2 * (P + T) * 4 * 16 * 4)
    assert sizes["state_bytes"] == 6 * 2 * (
        H * dk * dv * 4 + 3 * H * (2 * dk + dv) * 4)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    assert sizes["weight_bytes"] == 4 * n_params    # float32 at this size


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
            "ppo", "model_preset=tiny_olmo_hybrid", "model.remat=true",
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
    # the CPU: the chunked rule's jax.numpy form, and the row says so
    assert hist[-1]["kda_chunk"] == "jnp"
    before = kept["before"]["backbone"]
    after = kept["trainer"].state.params["backbone"]
    for name in ("A_log", "dt_bias", "q_conv"):
        moved = np.max(np.abs(np.asarray(
            after["layers_0to2"]["attn"][name])
            - before["layers_0to2"]["attn"][name]))
        assert moved > 0, name
    sizes = kept["trainer"].engine.dispatch_attrs(
        (4, 16), [16] * 4, kept["trainer"].state.params)
    assert sizes["state_bytes"] > 0 and sizes["cache_bytes"] > 0 \
        and sizes["weight_bytes"] > 0
    assert sizes["kda_step"] == "jnp"            # the CPU's form


def test_remat_tags_count_both_kinds_of_mixer():
    cfg = ModelConfig.tiny("olmo_hybrid")
    tags = dict(remat_tag_bytes(cfg, rows=2, seq_len=64))
    n, act = 2 * 64, 2
    assert tags["attn_qkv"] == n * act * (3 * 3 * (2 * 12 + 24)
                                          + 3 * 4 * 16)
    assert tags["attn_out"] == 3 * n * 3 * 24 * 4 + (
        n * 4 * 16 * act + 2 * 4 * 64 * 4)
    assert tags["attn_resid"] == n * 4 * cfg.hidden_size * act
    assert tags["mlp_pre"] == n * 4 * 2 * cfg.intermediate_size * act
    assert set(tags) == {"attn_resid", "mlp_pre", "attn_out", "attn_qkv"}


# ---------------------------------------------------------------------------
# refusals
# ---------------------------------------------------------------------------

def _refusals():
    from orion_tpu.models.hf_export import hf_state_dict
    from orion_tpu.models.hf_loader import (config_from_hf,
                                            convert_hf_state_dict)
    from orion_tpu.rollout import RolloutEngine
    from orion_tpu.rollout.continuous import ContinuousBatchingEngine

    cfg = ModelConfig.tiny("olmo_hybrid")
    model = Transformer(cfg)

    def engine(**kw):
        return lambda: RolloutEngine(model, cfg, RolloutConfig(**kw))

    def tiny(**kw):
        return lambda: ModelConfig.tiny("olmo_hybrid", **kw)

    class HF:
        model_type = "olmo_hybrid"

    return {
        "continuous": (lambda: ContinuousBatchingEngine(
            model, cfg, RolloutConfig()), "recurrent state per slot"),
        "paged": (engine(paged=True), "not made of pages"),
        "quantize_kv": (engine(quantize_kv=True),
                        "int8 form of a float32 recurrent state"),
        "quantize_weights": (engine(quantize_weights=True),
                             "int8 Dense twins do not reach this block"),
        "ring": (tiny(attention_impl="ring"),
                 "recurrent state between sequence shards"),
        "ulysses": (tiny(attention_impl="ulysses"),
                    "recurrent state between sequence shards"),
        "hf_import": (lambda: convert_hf_state_dict({}, cfg),
                      "no olmo_hybrid checkpoint loader"),
        "hf_config": (lambda: config_from_hf(HF()),
                      "no olmo_hybrid checkpoint loader"),
        "hf_export": (lambda: hf_state_dict(
            init_params(model, jax.random.key(0), cfg), cfg),
            "no olmo_hybrid checkpoint layout"),
        "int8_cache": (lambda: init_cache(cfg, 1, 8, quantized=True),
                       "recurrent state has no int8 form"),
        "shared_key_heads": (tiny(linear_num_value_heads=6),
                             "value heads share a key head"),
        "layer_types": (tiny(layer_types=("linear_attention", "sliding")),
                        "model.layer_types names"),
        "quantize_dense": (tiny(quantize_dense=True), "no int8 Dense twin"),
    }


@pytest.mark.parametrize("path", [
    "continuous", "paged", "quantize_kv", "quantize_weights", "ring",
    "ulysses", "hf_import", "hf_config", "hf_export", "int8_cache",
    "shared_key_heads", "layer_types", "quantize_dense"])
def test_paths_that_cannot_run_it_name_the_missing_mechanism(path):
    call, words = _refusals()[path]
    with pytest.raises(ValueError, match=words):
        call()
