"""The ``kimi_linear`` block (three delta-rule layers to one latent layer
without rotation, the dropless sigmoid-routed expert layer, one leading
dense layer) at the tiny size against the plain reference
``benchmarks/reference_kimi_linear.py`` on seeded weights: the two forms
of the delta rule against the reference's recurrence, the training
forward, prefill of unequal prompts and decode through the state,
gradients, the scanned layout, one PPO iteration through the launcher,
the shares, the refusals, and ``deepseek_v3`` as the pattern "latent
everywhere"."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import ModelConfig, RolloutConfig
from orion_tpu.models.transformer import (Transformer, init_cache,
                                          init_params,
                                          maybe_unstack_for_decode,
                                          remat_tag_bytes)
from orion_tpu.ops import moe
from orion_tpu.ops.kda import kda_chunked, kda_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "kimi_test_" + name, os.path.join(REPO, "benchmarks", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference_kimi_linear")
chk = _load("reference_check_kimi_linear")


# ---------------------------------------------------------------------------
# the delta rule: chunked == stepwise == the reference's recurrence
# ---------------------------------------------------------------------------

def _inputs(L, decay, H=3, d=16, dtype=jnp.float32, seed=0):
    """q, k, v, g, beta [1, L, ...] as a KDA layer would hand them over.
    ``decay``: "init" (A ~ U(1, 16), softplus in [1e-3, 1e-1], what the
    initialiser gives), "strongest_init" (A = 16, softplus = 0.1
    everywhere) or "overflow" (-80 a step: ``exp(-G)`` overflows float32
    after two tokens) or "repeated_token" (every position the same key
    and query, beta 0.9, decay 1e-3 a step: a run of one token, where
    the chunk's triangular system is at its worst)."""
    rs = np.random.RandomState(seed)
    q, k, v = (rs.normal(size=(1, L, H, d)) for _ in range(3))
    if decay == "repeated_token":
        q, k = (np.broadcast_to(t[:, :1], t.shape) for t in (q, k))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    if decay == "init":
        g = -rs.uniform(1, 16, size=(1, 1, H, 1)) * np.exp(rs.uniform(
            np.log(1e-3), np.log(1e-1), size=(1, L, H, d)))
    else:
        g = np.full((1, L, H, d), {"strongest_init": -1.6, "overflow": -80.0,
                                   "repeated_token": -1e-3}[decay])
    beta = 1.0 / (1.0 + np.exp(-rs.normal(size=(1, L, H))))
    if decay == "repeated_token":
        beta = np.full_like(beta, 0.9)
    return [jnp.asarray(x, dtype) for x in (q, k, v)] + [
        jnp.asarray(g, jnp.float32), jnp.asarray(beta, jnp.float32)]


def _reference(q, k, v, g, beta, mask=None):
    f32 = lambda t: t[0].astype(jnp.float32)  # noqa: E731
    mask = jnp.ones((q.shape[1],), bool) if mask is None else mask
    return ref.delta_rule(f32(q), f32(k), f32(v), g[0], beta[0], mask)


def _stepwise(q, k, v, g, beta):
    S = jnp.zeros((1,) + q.shape[2:] + v.shape[3:], jnp.float32)
    out = []
    for t in range(q.shape[1]):
        o, S = kda_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], S)
        out.append(o)
    return jnp.stack(out, axis=1), S


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("L,decay", [
    (64, "init"), (128, "init"), (100, "init"), (7, "init"),
    (130, "strongest_init"), (70, "overflow"), (128, "repeated_token")])
def test_chunked_stepwise_and_reference_agree(L, decay, dtype):
    """Lengths that are and are not multiples of the chunk; the inputs
    in float32 and in bfloat16 (both forms and the reference compute on
    the same rounded inputs in float32)."""
    args = _inputs(L, decay, dtype=dtype)
    o, S = kda_chunked(*args)
    o_step, S_step = _stepwise(*args)
    want, S_want = _reference(*args)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o[0], want, atol=2e-6, rtol=0)
    np.testing.assert_allclose(o_step[0], want, atol=2e-6, rtol=0)
    np.testing.assert_allclose(S[0], S_want, atol=5e-6, rtol=0)
    np.testing.assert_allclose(S_step[0], S_want, atol=5e-6, rtol=0)


@pytest.mark.parametrize("L,decay", [(64, "init"), (100, "init"),
                                     (130, "strongest_init"),
                                     (70, "overflow"),
                                     (128, "repeated_token")])
def test_chunked_gradients_are_the_references(L, decay):
    """Autodiff through the chunked form against autodiff through the
    reference's token-by-token recurrence, for every input."""
    args = _inputs(L, decay)

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(3.0 * fn(*a)))

    got = jax.grad(loss(lambda *a: kda_chunked(*a)[0][0]),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(lambda *a: _reference(*a)[0]),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("qkvgb", got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        # alike keys: the system's condition shows in float32
        tol = 1e-4 if decay == "repeated_token" else 1e-5
        np.testing.assert_allclose(a, b, atol=tol * scale, rtol=0,
                                   err_msg=name)


def test_an_initial_state_continues_the_sequence():
    """Chunked over the first part, then over the rest from the state it
    left, is chunked over the whole (chunked prefill)."""
    args = _inputs(150, "init")
    o, S = kda_chunked(*args)
    cut = 83
    o1, S1 = kda_chunked(*(a[:, :cut] for a in args))
    o2, S2 = kda_chunked(*(a[:, cut:] for a in args), state=S1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], axis=1), o,
                               atol=2e-6, rtol=0)
    np.testing.assert_allclose(S2, S, atol=5e-6, rtol=0)


def test_positions_without_a_token_are_inert():
    """``g = 0, beta = 0`` (what ``token_mask`` false makes of a
    position): the state after a padded sequence is the state after its
    last real token, in both forms and in the reference."""
    real, L = 45, 100
    q, k, v, g, beta = _inputs(L, "init")
    mask = jnp.arange(L) < real
    g_m = jnp.where(mask[None, :, None, None], g, 0.0)
    b_m = jnp.where(mask[None, :, None], beta, 0.0)
    o, S = kda_chunked(q, k, v, g_m, b_m)
    _, S_step = _stepwise(q, k, v, g_m, b_m)
    o_short, S_short = kda_chunked(*(a[:, :real] for a in
                                     (q, k, v, g, beta)))
    _, S_ref = _reference(q, k, v, g, beta, mask)
    np.testing.assert_allclose(o[:, :real], o_short, atol=2e-6, rtol=0)
    for got in (S, S_step, S_ref[None]):
        np.testing.assert_allclose(got, S_short, atol=5e-6, rtol=0)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

def _shape(cfg):
    """The configuration file's keys, from a ModelConfig."""
    return {"num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim,
            "rms_norm_eps": cfg.rms_norm_eps,
            "num_experts_per_token": cfg.num_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "num_experts": cfg.experts_held,
            "expert_offset": cfg.expert_offset,
            "first_k_dense_replace": cfg.first_k_dense_replace,
            "vocab_size": cfg.vocab_size,
            "linear_attn_config": {
                "kda_layers": list(cfg.kda_layers),
                "full_attn_layers": [
                    i + 1 for i in range(cfg.num_layers)
                    if i + 1 not in cfg.kda_layers],
                "num_heads": cfg.kda_num_heads,
                "head_dim": cfg.kda_head_dim,
                "short_conv_kernel_size": cfg.short_conv_kernel_size}}


def _weights(params, cfg):
    """The program's (unrolled) tree as the reference's ``forward``
    takes it."""
    return {"embed": params["embed"]["embedding"],
            "layers": [chk.layer_weights(params[f"layers_{i}"])
                       for i in range(cfg.num_layers)],
            "nf_g": params["final_norm"]["scale"],
            "w_head": params["lm_head"]["kernel"]}


def _held(cfg):
    return cfg.expert_offset, cfg.experts_held


def _positions(ids):
    return jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)


def _reference_logits(params, cfg, ids, lens=None):
    w = _weights(params, cfg)
    return jnp.stack([
        ref.forward(w, row, _shape(cfg), _held(cfg),
                    None if lens is None else jnp.arange(len(row)) < n)
        for row, n in zip(ids, lens if lens is not None else ids)])


@pytest.fixture(scope="module")
def tiny():
    # a share: 4 of 8 experts from the third on; sequences longer than a
    # chunk of the delta rule, and not a multiple of it
    cfg = ModelConfig.tiny("kimi_linear", dtype="float32", experts_held=4,
                           expert_offset=2)
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(3), cfg)
    ids = np.random.RandomState(0).randint(2, cfg.vocab_size, (2, 84))
    return cfg, model, params, jnp.asarray(ids, jnp.int32)


def test_the_pattern_is_the_published_one():
    cfg = ModelConfig.kimi_linear_48b_a3b()
    kinds = cfg.layer_kinds()
    assert len(kinds) == 27 and kinds[0] == ("kda", "dense")
    assert [i + 1 for i, (m, _) in enumerate(kinds) if m == "latent"] == \
        [4, 8, 12, 16, 20, 24, 27]
    assert all(f == "experts" for _, f in kinds[1:])
    # the benchmark's cut: the dense layer and one whole period
    cut = dataclasses.replace(cfg, num_layers=5)
    assert [m for m, _ in cut.layer_kinds()] == \
        ["kda", "kda", "kda", "latent", "kda"]
    assert cut.layer_runs() == (
        (0, 1, "kda", "dense"), (1, 2, "kda", "experts"),
        (3, 1, "latent", "experts"), (4, 1, "kda", "experts"))
    assert cut.takes_token_mask and cut.recurrent
    with pytest.raises(ValueError, match="from 1"):
        ModelConfig.tiny("kimi_linear", kda_layers=(0, 1, 2))


def test_training_forward_matches_reference_float32(tiny):
    cfg, model, params, ids = tiny
    logits, _ = model.apply({"params": params}, ids, _positions(ids))
    want = _reference_logits(params, cfg, ids)
    np.testing.assert_allclose(logits, want, atol=3e-5, rtol=0)
    # the mixers do move the result: without the KDA layers' output the
    # logits are others
    gutted = jax.tree.map(lambda x: x, params)
    gutted["layers_1"]["attn"]["o_proj"]["kernel"] = jnp.zeros_like(
        params["layers_1"]["attn"]["o_proj"]["kernel"])
    other, _ = model.apply({"params": gutted}, ids, _positions(ids))
    assert float(jnp.max(jnp.abs(other - want))) > 1e-2


@pytest.mark.parametrize("scan,step", [(False, "jnp"), (True, "jnp"),
                                       (False, "kernel")])
def test_prefill_of_unequal_prompts_then_decode_through_the_state(
        tiny, scan, step, monkeypatch):
    """A right-padded batch of a full-length and a short prompt, then
    one-token steps: the logits at every real position are the full
    forward's and the reference's (logits, not tokens).  ``step``: the
    form the one-token step takes (``kernel``: ops/pallas/kda_step.py,
    interpreted here, as a TPU trace takes it)."""
    from orion_tpu.ops import kda

    monkeypatch.setattr(kda, "step_form", lambda dk, dv: step)
    cfg, model, params, ids = tiny
    if scan:
        cfg = dataclasses.replace(cfg, scan_layers=True)
        model = Transformer(cfg)
        run_params = init_params(model, jax.random.key(3), cfg)
        params = maybe_unstack_for_decode(run_params, cfg)
    else:
        run_params = params
    flat = dataclasses.replace(cfg, scan_layers=False)
    want = _reference_logits(params, flat, ids)
    P, T = 70, 14
    lens = jnp.asarray([P, 9], jnp.int32)
    pos = _positions(ids)
    prompts = jnp.where(pos[:, :P] < lens[:, None], ids[:, :P], 0)
    cache = init_cache(cfg, 2, P + T)
    logits, cache = model.apply(
        {"params": run_params}, prompts, pos[:, :P], cache,
        token_mask=pos[:, :P] < lens[:, None])
    for b in range(2):
        n = int(lens[b])
        np.testing.assert_allclose(logits[b, :n], want[b, :n], atol=3e-5,
                                   rtol=0)
    got = []
    for t in range(T):
        cur = lens + t
        tok = jnp.take_along_axis(ids, cur[:, None], axis=1)
        step, cache = model.apply({"params": run_params}, tok,
                                  cur[:, None], cache)
        got.append(step[:, 0])
    got = jnp.stack(got, axis=1)
    for b in range(2):
        n = int(lens[b])
        np.testing.assert_allclose(got[b], want[b, n:n + T], atol=3e-5,
                                   rtol=0)
    entries = cache if not scan else (
        cache["dense"] + [jax.tree.map(lambda x: x[0], c)
                          for c in cache["runs"]])
    kinds = [sorted(e) for e in entries]
    assert kinds.count(["S", "conv"]) == (4 if not scan else 3)
    assert ["c", "k_rope"] in kinds
    assert entries[0]["S"].dtype == jnp.float32
    assert entries[0]["S"].shape == (2, cfg.kda_num_heads,
                                     cfg.kda_head_dim, cfg.kda_head_dim)
    assert entries[0]["conv"].shape == (2, 3, 3 * 64)


def test_padding_leaves_every_real_position_as_it_was(tiny):
    """Behind a row's tokens ``token_mask`` is false: the logits before
    are those of the unpadded sequence, and of the reference told the
    same mask."""
    cfg, model, params, ids = tiny
    real = 50
    padded = ids.at[:, real:].set(0)
    mask = _positions(ids) < real
    masked, _ = model.apply({"params": params}, padded, _positions(ids),
                            token_mask=mask)
    short, _ = model.apply({"params": params}, ids[:, :real],
                           _positions(ids)[:, :real])
    np.testing.assert_allclose(masked[:, :real], short, atol=2e-5, rtol=0)
    want = _reference_logits(params, cfg, padded, lens=[real, real])
    np.testing.assert_allclose(masked[:, :real], want[:, :real], atol=3e-5,
                               rtol=0)


@pytest.mark.parametrize("experts", ["dense", "grouped"])
def test_loss_and_gradients_match_the_reference(tiny, experts, monkeypatch):
    cfg, model, params, ids = tiny
    ids = ids[:1, :70]
    if experts == "grouped":    # the Pallas grouped product, interpreted
        monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 0)

    def mean_logprob(logits):
        lp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        return jnp.mean(jnp.take_along_axis(lp, ids[:, 1:, None], axis=-1))

    loss, got = jax.value_and_grad(lambda p: mean_logprob(
        model.apply({"params": p}, ids, _positions(ids))[0]))(params)
    ref_loss, want = jax.value_and_grad(lambda p: mean_logprob(
        _reference_logits(p, cfg, ids)))(params)
    np.testing.assert_allclose(loss, ref_loss, atol=1e-5, rtol=0)
    flat_g = jax.tree_util.tree_flatten_with_path(got)[0]
    seen = set()
    for (path, g), w in zip(flat_g, jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        seen.add(name.split("'")[-2])
        if "e_score_correction_bias" in name:
            assert not np.any(np.asarray(g)), name   # selection only
            continue
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        assert scale > 1e-9, name       # a gradient does reach it
        np.testing.assert_allclose(g, w, atol=3e-4 * scale + 1e-9,
                                   rtol=0, err_msg=name)
    assert {"A_log", "dt_bias", "q_conv", "o_norm", "kv_b_proj"} <= seen


def test_scanned_and_unrolled_layouts_agree(tiny):
    cfg, model, _, ids = tiny
    scfg = dataclasses.replace(cfg, scan_layers=True, remat=True)
    smodel = Transformer(scfg)
    stacked = init_params(smodel, jax.random.key(5), scfg)
    # each stretch of equal kinds is one stack; the dense layer alone
    assert set(stacked) == {"embed", "final_norm", "lm_head", "layers_0",
                            "layers_1to2", "layers_3to3", "layers_4to4"}
    assert "gate_proj" in stacked["layers_0"]["mlp"]
    assert stacked["layers_1to2"]["attn"]["A_log"].shape == (
        2, cfg.kda_num_heads)
    assert "kv_b_proj" in stacked["layers_3to3"]["attn"]
    unrolled = maybe_unstack_for_decode(stacked, scfg)
    assert set(unrolled) == {"embed", "final_norm", "lm_head"} | {
        f"layers_{i}" for i in range(cfg.num_layers)}
    a, _ = smodel.apply({"params": stacked}, ids, _positions(ids))
    b, _ = model.apply({"params": unrolled}, ids, _positions(ids))
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)
    from orion_tpu.models.transformer import logical_specs
    specs = logical_specs(smodel, scfg)
    assert tuple(specs["layers_1to2"]["attn"]["q_conv"]) == (
        "layers", "conv", "heads")
    assert tuple(specs["layers_1to2"]["attn"]["f_a_proj"]["kernel"]) == (
        "layers", "embed", "latent")
    assert tuple(specs["layers_0"]["attn"]["dt_bias"]) == ("heads",)


def test_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts of all 8 shares (offsets 0..7, one expert each),
    with the shared expert counted once, are the uncut reference's
    expert layer at this model's numbers."""
    full = ModelConfig.tiny("kimi_linear", dtype="float32")
    model = Transformer(full)
    params = init_params(model, jax.random.key(11), full)
    p = params["layers_1"]["mlp"]
    z = jax.random.normal(jax.random.key(12), (3, 10, full.hidden_size))
    w = chk.layer_weights(params["layers_1"])
    shape = ref.moe_shape(_shape(full))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.dsv3.expert_ffn(row, w, shape, (0, 8))
                          for row in z])
        shared = jnp.stack([ref.swiglu(row, w["s_gate_up"], w["s_down"])
                            for row in z])
    total = 0.0
    for offset in range(8):
        cfg = dataclasses.replace(full, experts_held=1, expert_offset=offset)
        share = dict(p, experts_gate_up_proj=p["experts_gate_up_proj"][
            offset:offset + 1], experts_down_proj=p["experts_down_proj"][
            offset:offset + 1])
        out = moe.TopKMoE(cfg).apply({"params": share}, z)
        total = total + (out - shared)        # this share's routed part
    np.testing.assert_allclose(total + shared, want, atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# through the engine and the trainer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("step", ["jnp", "kernel"])
def test_the_engine_decodes_through_the_state(tiny, step, monkeypatch):
    """``RolloutEngine``: prompts of unequal length in one batch; the
    policy logprobs it recorded are the teacher-forced ones of the
    reference on what it sampled.  ``step``: the form the one-token step
    takes inside the decode ``while_loop`` (``kernel``: interpreted
    here)."""
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
            _reference_logits(params, cfg, row[None])[0], row)
        np.testing.assert_allclose(out.policy_logprobs[b, :new],
                                   want[n - 1:n - 1 + new], atol=5e-5,
                                   rtol=0)
    # what a decode step touches, from shapes
    latent = 2 * (P + T) * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * 4
    H, d = cfg.kda_num_heads, cfg.kda_head_dim
    state = 4 * 2 * (H * d * d * 4 + 3 * 3 * H * d * 4)
    sizes = eng.dispatch_attrs((2, P), lens)
    assert sizes["cache_bytes"] == latent
    assert sizes["state_bytes"] == state
    n_params = sum(x.size for x in jax.tree.leaves(params))
    assert sizes["weight_bytes"] == 4 * n_params    # float32 at this size
    plain = ModelConfig.tiny()
    eng0 = RolloutEngine(Transformer(plain), plain, RolloutConfig(
        max_prompt_len=8, max_new_tokens=8))
    sizes0 = eng0.dispatch_attrs((2, 8), [8, 8])
    assert sizes0["state_bytes"] == 0 and sizes0["cache_bytes"] > 0


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
            "ppo", "model_preset=tiny_kimi_linear", "model.remat=true",
            "model.scan_layers=true", "share_backbone=true",
            "model.max_seq_len=24", "rollout.max_prompt_len=16",
            "rollout.max_new_tokens=8", "rollout_batch_size=4",
            "minibatch_size=2", "num_epochs=1", "data.dataset=synthetic",
            "reward=length", "total_iterations=2",
            "optimizer.learning_rate=1e-3", "ref_param_dtype=bfloat16",
            "optimizer.mu_dtype=bfloat16", "optimizer.nu_dtype=bfloat16",
            "obs.trace=true", f"log_dir={tmp_path}"])
    finally:
        launch.build_trainer = real
    assert len(hist) == 2 and all(np.isfinite(r["loss"]) for r in hist)
    # the span says which form the one-token step takes: the CPU's
    dispatch = _dispatch_spans(tmp_path)
    assert len(dispatch) == 2
    assert {sp["kda_step"] for sp in dispatch} == {"jnp"}
    assert all(sp["state_bytes"] > 0 for sp in dispatch)
    row = hist[-1]
    # every expert is held at the tiny size; 4 expert layers of 5
    assert row["moe_pairs_here"] == row["moe_pairs_total"] == 4 * 2 * 24 * 2
    assert row["moe_load_max"] >= row["moe_load_mean"] > 0
    # the CPU, heads of 16: the chunked rule's jax.numpy form, and the
    # row says so (the kernels need a TPU and heads of 128)
    assert row["kda_chunk"] == "jnp"
    before = kept["before"]["backbone"]
    after = kept["trainer"].state.params["backbone"]
    for name in ("A_log", "dt_bias", "q_conv"):
        moved = np.max(np.abs(np.asarray(
            after["layers_1to2"]["attn"][name])
            - before["layers_1to2"]["attn"][name]))
        assert moved > 0, name
    np.testing.assert_array_equal(        # the selection bias is held
        after["layers_1to2"]["mlp"]["e_score_correction_bias"],
        before["layers_1to2"]["mlp"]["e_score_correction_bias"])
    trainer = kept["trainer"]
    sizes = trainer.engine.dispatch_attrs((4, 16), [16] * 4,
                                          trainer.state.params)
    assert sizes["state_bytes"] > sizes["cache_bytes"] > 0
    assert sizes["weight_bytes"] > 0
    assert sizes["kda_step"] == "jnp"            # the CPU's form


def _dispatch_spans(log_dir) -> list:
    """The attributes of the ``rollout.dispatch`` spans a run with
    ``obs.trace=true`` wrote into ``log_dir``."""
    import json
    import os

    with open(log_dir / f"spans-{os.getpid()}.json") as f:
        events = json.load(f)["traceEvents"]
    return [e["args"] for e in events if e["name"] == "rollout.dispatch"]


def test_the_rollout_span_of_a_model_without_a_state_says_so(tmp_path):
    """``rollout.dispatch`` carries ``kda_step``: the form the delta
    rule's one-token step takes in this process's traces (``jnp`` on the
    CPU, ``kernel`` on a TPU: the launcher test above), and ``""`` for a
    model without such a layer."""
    from orion_tpu import launch

    launch.main([
        "ppo", "model_preset=tiny", "share_backbone=true",
        "model.max_seq_len=24", "rollout.max_prompt_len=16",
        "rollout.max_new_tokens=8", "rollout_batch_size=4",
        "minibatch_size=2", "num_epochs=1", "data.dataset=synthetic",
        "reward=length", "total_iterations=1", "obs.trace=true",
        f"log_dir={tmp_path}"])
    (span,) = _dispatch_spans(tmp_path)
    assert span["kda_step"] == "" and span["state_bytes"] == 0
    assert span["batch"] == 4 and span["cache_bytes"] > 0


def test_remat_tags_count_both_kinds_of_mixer():
    cfg = ModelConfig.tiny("kimi_linear")
    tags = dict(remat_tag_bytes(cfg, rows=2, seq_len=64))
    n, act = 2 * 64, 2
    kda, latent = 4, 1
    assert tags["attn_qkv"] == n * act * (
        kda * 3 * cfg.kda_num_heads * cfg.kda_head_dim
        + latent * cfg.num_heads * (2 * 16 + 8))
    assert tags["attn_out"] == kda * n * 64 * 4 + latent * (
        n * cfg.num_heads * 8 * act + 2 * cfg.num_heads * 64 * 4)
    assert tags["attn_resid"] == n * 5 * cfg.hidden_size * act
    assert set(tags) == {"moe_route", "attn_resid", "mlp_pre", "attn_out",
                         "attn_qkv"}


# ---------------------------------------------------------------------------
# refusals, and deepseek_v3 as a pattern
# ---------------------------------------------------------------------------

def _refusals():
    from orion_tpu.models.hf_export import hf_state_dict
    from orion_tpu.models.hf_loader import (config_from_hf,
                                            convert_hf_state_dict)
    from orion_tpu.rollout import RolloutEngine
    from orion_tpu.rollout.continuous import ContinuousBatchingEngine

    cfg = ModelConfig.tiny("kimi_linear")
    model = Transformer(cfg)

    def engine(**kw):
        return lambda: RolloutEngine(model, cfg, RolloutConfig(**kw))

    class HF:
        model_type = "kimi_linear"

    return {
        "continuous": (lambda: ContinuousBatchingEngine(
            model, cfg, RolloutConfig()), "recurrent state per slot"),
        "paged": (engine(paged=True), "not made of pages"),
        "quantize_kv": (engine(quantize_kv=True),
                        "int8 form of a float32 recurrent state"),
        "quantize_weights": (engine(quantize_weights=True),
                             "int8 expert stacks"),
        "ring": (lambda: ModelConfig.tiny("kimi_linear",
                                          attention_impl="ring"),
                 "recurrent state between sequence shards"),
        "ulysses": (lambda: ModelConfig.tiny("kimi_linear",
                                             attention_impl="ulysses"),
                    "recurrent state between sequence shards"),
        "hf_import": (lambda: convert_hf_state_dict({}, cfg),
                      "no kimi_linear checkpoint loader"),
        "hf_config": (lambda: config_from_hf(HF()),
                      "no kimi_linear checkpoint loader"),
        "hf_export": (lambda: hf_state_dict(
            init_params(model, jax.random.key(0), cfg), cfg),
            "no kimi_linear checkpoint layout"),
        "int8_cache": (lambda: init_cache(cfg, 1, 8, quantized=True),
                       "recurrent state has no int8 form"),
    }


@pytest.mark.parametrize("path", [
    "continuous", "paged", "quantize_kv", "quantize_weights", "ring",
    "ulysses", "hf_import", "hf_config", "hf_export", "int8_cache"])
def test_paths_that_cannot_run_it_name_the_missing_mechanism(path):
    call, words = _refusals()[path]
    with pytest.raises(ValueError, match=words):
        call()


#: the parameter paths of ``ModelConfig.tiny("deepseek_v3")`` under
#: ``scan_layers`` before there were patterns (PR 28's layout):
#: checkpoints written then still load
DSV3_SCANNED_PATHS = {
    "embed/embedding", "final_norm/scale", "lm_head/kernel",
    *(f"layers_0/{p}" for p in (
        "attn/kv_a_norm/scale", "attn/kv_a_proj_with_mqa/kernel",
        "attn/kv_b_proj", "attn/o_proj/kernel", "attn/q_proj/kernel",
        "input_norm/scale", "post_attn_norm/scale",
        "mlp/gate_proj/kernel", "mlp/up_proj/kernel",
        "mlp/down_proj/kernel")),
    *(f"layers/{p}" for p in (
        "attn/kv_a_norm/scale", "attn/kv_a_proj_with_mqa/kernel",
        "attn/kv_b_proj", "attn/o_proj/kernel", "attn/q_proj/kernel",
        "input_norm/scale", "post_attn_norm/scale", "mlp/router",
        "mlp/e_score_correction_bias", "mlp/experts_gate_up_proj",
        "mlp/experts_down_proj", "mlp/shared_gate_proj/kernel",
        "mlp/shared_up_proj/kernel", "mlp/shared_down_proj/kernel")),
}


def test_deepseek_v3_is_the_pattern_latent_everywhere():
    cfg = ModelConfig.tiny("deepseek_v3", scan_layers=True)
    assert cfg.layer_kinds() == (("latent", "dense"),) + (
        ("latent", "experts"),) * 2
    assert cfg.takes_token_mask and not cfg.recurrent
    params = init_params(Transformer(cfg), jax.random.key(0), cfg)
    paths = {"/".join(k.key for k in path) for path, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]}
    assert paths == DSV3_SCANNED_PATHS
    assert params["layers"]["mlp"]["router"].shape[0] == 2
    # and its caches keep their layouts
    cache = init_cache(cfg, 2, 16)
    assert set(cache) == {"dense", "layers"}
    assert cache["layers"]["c"].shape == (2, 2, 16, cfg.kv_lora_rank)
    flat = init_cache(dataclasses.replace(cfg, scan_layers=False), 2, 16)
    assert [sorted(c) for c in flat] == [["c", "k_rope"]] * 3
    # a model of one kind of block has one stack and no pattern
    for arch in ("llama", "neox"):
        plain = ModelConfig.tiny(arch, scan_layers=True)
        assert not plain.takes_token_mask and not plain.recurrent
        assert set(init_params(Transformer(plain), jax.random.key(0),
                               plain)) >= {"layers"}
        assert set(init_cache(plain, 2, 16)) == {"k", "v"}
