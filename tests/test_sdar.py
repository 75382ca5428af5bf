"""The ``sdar_moe`` model (SDAR: the Qwen3-MoE block generating by
diffusion over blocks of 4) at the tiny size, float32, against the plain
reference ``benchmarks/reference_sdar.py`` on seeded weights: the clean
forward, the one-forward trace log-probabilities, values and PPO loss
gradients against the reference's separate forwards, the engine's
generation against the reference's whole-sequence loop on the same
draws, the ratio of a fresh rollout, the eight expert shares, the other
trainers, the attention pieces (the block step, the merged two-part
attention through the kernels, interpreted), the refusals."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import (GRPOConfig, ModelConfig, OnlineDPOConfig,
                              PPOConfig, RLOOConfig, RolloutConfig)
from orion_tpu.models.heads import ActorCriticModel
from orion_tpu.models.transformer import (Transformer, Visible, cannot_run,
                                          init_params)
from orion_tpu.ops.attention import (reference_attention_gqa, step_attention,
                                     streams_attention)
from orion_tpu.ops.logprobs import trace_streams
from orion_tpu.rollout.engine import RolloutEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "sdar_test_" + name, os.path.join(REPO, "benchmarks", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference_sdar")
chk = _load("reference_check_sdar")


def _shape(cfg):
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
        mask_token_id=cfg.mask_id)


def _weights(params, cfg):
    params = params.get("backbone", params)
    layers = [chk.layer_weights(chk.layer_tree(params, i))
              for i in range(cfg.num_layers)]
    return {"embed": params["embed"]["embedding"], "layers": layers,
            "nf_g": params["final_norm"]["scale"],
            "w_head": params["lm_head"]["kernel"]}


def _held(cfg):
    return cfg.expert_offset, cfg.experts_held


def _model(**kw):
    cfg = ModelConfig.tiny("sdar_moe", dtype="float32", **kw)
    model = Transformer(cfg)
    return cfg, model, init_params(model, jax.random.key(7), cfg)


def _positions(ids):
    return jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)


# -- the clean forward ---------------------------------------------------


def test_clean_forward_matches_the_reference():
    cfg, model, params = _model()
    ids = jnp.asarray(np.random.RandomState(0).randint(4, 255, (2, 22)))
    logits, _ = model.apply({"params": params}, ids, _positions(ids))
    for b in range(2):
        want = ref.forward(_weights(params, cfg), ids[b], _shape(cfg),
                           _held(cfg))
        np.testing.assert_allclose(logits[b], want, atol=2e-5)


def test_a_block_sees_itself_in_both_directions_and_nothing_later():
    cfg, model, params = _model()
    ids = np.random.RandomState(1).randint(4, 255, (1, 16))
    base, _ = model.apply({"params": params}, jnp.asarray(ids),
                          _positions(ids))
    later = ids.copy()
    later[0, 7] = 9                     # the last position of block 1
    out, _ = model.apply({"params": params}, jnp.asarray(later),
                         _positions(ids))
    moved = np.max(np.abs(np.asarray(out - base)), axis=-1)[0]
    assert np.all(moved[:4] == 0.0)            # block 0 sees nothing of it
    assert np.all(moved[4:8] > 0.0)            # its own block does, behind it
    assert np.all(moved[8:] > 0.0)


def test_block_length_one_is_the_causal_forward():
    cfg, model, params = _model(block_length=1, denoising_steps=1)
    ids = jnp.asarray(np.random.RandomState(2).randint(4, 255, (1, 12)))
    logits, _ = model.apply({"params": params}, ids, _positions(ids))
    want = ref.forward(_weights(params, cfg), ids[0], _shape(cfg), _held(cfg),
                       block=0)
    np.testing.assert_allclose(logits[0], want, atol=2e-5)


# -- the trace forward ---------------------------------------------------


def _trace_batch(cfg, P=12, T=10, seed=3, lens=(12, 9, 10, 7)):
    rs = np.random.RandomState(seed)
    B = len(lens)
    seqs = rs.randint(4, cfg.mask_id, (B, P + T)).astype(np.int32)
    lens = np.asarray(lens, np.int32)
    steps = chk.seeded_trace(rs, lens, T, cfg.block_length,
                             cfg.denoising_steps)
    return seqs, lens, steps


def _ppo(cfg, **kw):
    from orion_tpu.trainers.ppo import PPOTrainer

    model = ActorCriticModel(cfg)
    ids = jnp.zeros((1, 2), jnp.int32)
    import flax.linen as nn

    params = nn.meta.unbox(model.init(jax.random.key(5), ids, ids)["params"])
    tc = PPOConfig(model=cfg, share_backbone=True, rollout_batch_size=4,
                   minibatch_size=4, kl_coef=0.0, **kw)
    return PPOTrainer(tc, model, params,
                      reward_fn=lambda r, b: np.asarray(
                          r.completion_lens, np.float32))


def test_trace_streams_lays_out_the_states():
    cfg = ModelConfig.tiny("sdar_moe")
    seqs, lens, steps = _trace_batch(cfg)
    T, Bd, S = steps.shape[1], 4, 4
    blocks = cfg.blocks_spanned(T)
    W = blocks * Bd
    row = jax.tree.map(np.asarray, trace_streams(
        jnp.asarray(seqs), jnp.asarray(lens), jnp.asarray(steps), Bd, S,
        cfg.mask_id, blocks, S * W))
    L = seqs.shape[1]
    for b, n in enumerate(lens):
        start = n // Bd * Bd
        for s in range(S):
            part = slice(L + s * W, L + (s + 1) * W)
            np.testing.assert_array_equal(row["positions"][b, part],
                                          start + np.arange(W))
            np.testing.assert_array_equal(
                row["see"][b, part], (start + np.arange(W)) // Bd * Bd - 1)
            for j, p in enumerate(start + np.arange(W)):
                t = p - n
                shown = t < 0 or (t < T and steps[b, t] < s)
                want = seqs[b, p] if shown else cfg.mask_id
                assert row["ids"][b, L + s * W + j] == want
        for t in range(T):
            at = row["read_at"][b, t]
            assert row["positions"][b, at] == n + t
            assert at == L + steps[b, t] * W + (n + t - start)
            assert row["ids"][b, at] == cfg.mask_id   # scored while masked


def test_one_forward_trace_equals_the_references_separate_forwards():
    cfg = ModelConfig.tiny("sdar_moe", dtype="float32")
    trainer = _ppo(cfg)
    try:
        seqs, lens, steps = _trace_batch(cfg)
        T = steps.shape[1]
        params = trainer.state.params
        lp, _, values, _, _ = trainer._jit_lp_values(
            params, seqs, lens, jnp.ones(steps.shape, jnp.float32),
            max_new=T, with_entropy=False, reveal_step=steps)
        w = _weights(params, cfg)
        for b in range(len(lens)):
            n = int(lens[b])
            want, want_v, _ = ref.trace_logprobs(
                w, jnp.asarray(seqs[b]), n, jnp.asarray(steps[b]),
                _shape(cfg), _held(cfg),
                token_mask=jnp.arange(seqs.shape[1]) < n + T,
                value_head=params["value_head"])
            np.testing.assert_allclose(lp[b], want, atol=3e-5)
            np.testing.assert_allclose(values[b], want_v, atol=3e-5)
    finally:
        trainer.close()


def test_ppo_loss_gradients_match_the_reference():
    cfg = ModelConfig.tiny("sdar_moe", dtype="float32")
    trainer = _ppo(cfg)
    try:
        seqs, lens, steps = _trace_batch(cfg, lens=(12, 9))
        B, T = steps.shape
        rs = np.random.RandomState(11)
        mb = {"sequences": jnp.asarray(seqs), "prompt_lens": jnp.asarray(lens),
              "mask": jnp.asarray((np.arange(T)[None] < [[T], [T - 3]])
                                  .astype(np.float32)),
              "old_logprobs": jnp.asarray(rs.normal(-5.5, 0.2, (B, T)),
                                          jnp.float32),
              "old_values": jnp.asarray(rs.normal(0, 0.3, (B, T)),
                                        jnp.float32),
              "advantages": jnp.asarray(rs.normal(0, 1, (B, T)), jnp.float32),
              "returns": jnp.asarray(rs.normal(0, 1, (B, T)), jnp.float32),
              "reveal_step": jnp.asarray(steps)}
        params = trainer.state.params
        (loss, _), grads = jax.value_and_grad(
            trainer.loss_fn, has_aux=True)(params, mb)

        def ref_loss(w, vh):
            lps, vals = [], []
            for b in range(B):
                n = int(lens[b])
                lp, v, _ = ref.trace_logprobs(
                    w, mb["sequences"][b], n, mb["reveal_step"][b],
                    _shape(cfg), _held(cfg),
                    token_mask=jnp.arange(seqs.shape[1]) < n + T,
                    value_head=vh)
                lps.append(lp)
                vals.append(v * mb["mask"][b])
            return ref.ppo_loss(
                jnp.stack(lps), jnp.stack(vals), mb["old_logprobs"],
                mb["old_values"], mb["advantages"], mb["returns"], mb["mask"],
                trainer.cfg.clip_ratio, trainer.cfg.value_clip,
                trainer.cfg.vf_coef)

        w = _weights(params, cfg)
        want, (gw, gv) = jax.value_and_grad(ref_loss, argnums=(0, 1))(
            w, params["value_head"])
        np.testing.assert_allclose(loss, want, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(grads["value_head"], gv, atol=2e-6)
        g = grads["backbone"]
        np.testing.assert_allclose(g["lm_head"]["kernel"], gw["w_head"],
                                   atol=2e-6)
        np.testing.assert_allclose(g["embed"]["embedding"], gw["embed"],
                                   atol=2e-6)
        for i in range(cfg.num_layers):
            got = chk.layer_weights(chk.layer_tree(g, i))
            for name, x in gw["layers"][i].items():
                np.testing.assert_allclose(got[name], x, atol=2e-6,
                                           err_msg=f"layer {i} {name}")
    finally:
        trainer.close()


# -- generation ----------------------------------------------------------


def _engine_noise(rng, blocks, B, Bd, S, V):
    """The Gumbel noise the engine's draws add, [blocks, S, B, Bd, V]:
    one key a denoising step, split off the carried one in order."""
    out = np.zeros((blocks, S, B, Bd, V), np.float32)
    for i in range(blocks):
        for s in range(S):
            rng, sub = jax.random.split(rng)
            out[i, s] = np.asarray(jax.random.gumbel(
                sub, (B * Bd, V), jnp.float32)).reshape(B, Bd, V)
    return out


@pytest.mark.parametrize("T,stop", [(10, None), (8, 77), (7, None)])
def test_generation_matches_the_references_whole_sequence_loop(T, stop):
    cfg, model, params = _model()
    P = 8
    lens = np.asarray([5, 6, 7, 8], np.int32)          # len % 4 = 1, 2, 3, 0
    rs = np.random.RandomState(T)
    prompts = np.where(np.arange(P)[None] < lens[:, None],
                       rs.randint(4, 255, (4, P)), 0).astype(np.int32)
    if stop is not None:
        # a head that favours one id: some row reveals it inside a block
        head = params["lm_head"]["kernel"]
        params = {**params, "lm_head": {"kernel": head.at[:, stop].set(
            head[:, stop] + 0.45)}}
    engine = RolloutEngine(
        model, cfg, RolloutConfig(max_prompt_len=P, max_new_tokens=T,
                                  temperature=1.0),
        eos_token_id=stop)
    key = jax.random.key(21)
    host = engine.generate(jnp.asarray(prompts), jnp.asarray(lens), key,
                           params=params).to_host()
    Bd, S = cfg.block_length, cfg.denoising_steps
    blocks = cfg.blocks_spanned(T)
    noise = _engine_noise(key, blocks, 4, Bd, S, cfg.vocab_size)
    assert not chk.trace_faults(host, Bd, S, () if stop is None else (stop,))
    w = _weights(params, cfg)
    stopped = 0
    for b in range(4):
        n = int(lens[b])
        got = ref.generate(w, jnp.asarray(prompts[b, :n]), T, _shape(cfg),
                           _held(cfg), jnp.asarray(noise[:, :, b]),
                           stop_ids=() if stop is None else (stop,))
        # every reveal was decided beyond rounding: then the same trace
        assert min(min(row) for row in got["gap"]) > 1e-6
        m = int(host.completion_lens[b])
        assert m == got["n"]
        stopped += m < T
        made = len(got["gap"]) * Bd          # positions of generated blocks
        k = min(T, made - (n - n // Bd * Bd))
        np.testing.assert_array_equal(host.reveal_step[b, :k],
                                      got["step"][:k])
        np.testing.assert_array_equal(host.sequences[b, n:n + k],
                                      got["tokens"][:k])
        np.testing.assert_array_equal(host.completions[b, :m],
                                      got["tokens"][:m])
        np.testing.assert_allclose(host.policy_logprobs[b, :m],
                                   got["plp"][:m], atol=3e-5)
        np.testing.assert_allclose(host.logprobs[b, :m], got["lp"][:m],
                                   atol=3e-5)
        assert np.all(host.completion_mask[b, m:] == 0)
        assert np.all(host.reveal_step[b, k:] == S)
    if stop is not None:
        assert stopped, "no row met the stop token: the case tests nothing"


def test_a_fresh_rollouts_ratio_is_one():
    cfg = ModelConfig.tiny("sdar_moe", dtype="float32")
    trainer = _ppo(cfg, rollout=RolloutConfig(
        max_prompt_len=8, max_new_tokens=10, temperature=1.0))
    try:
        lens = np.asarray([5, 6, 7, 8], np.int32)
        prompts = np.where(
            np.arange(8)[None] < lens[:, None],
            np.random.RandomState(4).randint(4, 255, (4, 8)), 0).astype(
                np.int32)
        result = trainer.generate(prompts, lens, jax.random.key(9))
        lp = trainer.behavior_logprobs(result)
        mask = np.asarray(result.completion_mask)
        np.testing.assert_allclose(
            np.asarray(lp) * mask, np.asarray(result.policy_logprobs) * mask,
            atol=3e-5)
        experience, _ = trainer.build_experience(
            result, np.ones((4,), np.float32))
        assert "reveal_step" in experience
        stats = trainer.update_epochs(experience)
        assert abs(stats["ratio_mean"] - 1.0) < 1e-4
        assert np.isfinite(stats["loss"])
    finally:
        trainer.close()


@pytest.mark.parametrize("algo", ["grpo", "rloo", "online_dpo"])
def test_the_other_trainers_take_a_step(algo):
    from orion_tpu.trainers.grpo import GRPOTrainer
    from orion_tpu.trainers.online_dpo import OnlineDPOTrainer
    from orion_tpu.trainers.rloo import RLOOTrainer

    cfg, model, params = _model()
    rollout = RolloutConfig(max_prompt_len=8, max_new_tokens=6,
                            temperature=1.0)
    cls, tc = {
        "grpo": (GRPOTrainer, GRPOConfig(group_size=2)),
        "rloo": (RLOOTrainer, RLOOConfig(group_size=2)),
        "online_dpo": (OnlineDPOTrainer, OnlineDPOConfig()),
    }[algo]
    tc = dataclasses.replace(tc, model=cfg, rollout=rollout,
                             rollout_batch_size=2, minibatch_size=4)
    if algo == "online_dpo":
        tc = dataclasses.replace(tc, minibatch_size=2)
    trainer = cls(tc, model, params, reward_fn=lambda r, b: np.asarray(
        r.sequences[:, -3:].sum(axis=1) % 7, np.float32))
    try:
        before = np.asarray(params["lm_head"]["kernel"]).copy()
        lens = np.asarray([6, 7], np.int32)
        prompts = np.where(
            np.arange(8)[None] < lens[:, None],
            np.random.RandomState(6).randint(4, 255, (2, 8)), 0).astype(
                np.int32)
        experience, _ = trainer.make_experience(
            {"prompt_ids": prompts, "prompt_lens": lens})
        assert any(k.endswith("reveal_step") for k in experience)
        stats = trainer.update_epochs(experience)
        assert np.isfinite(stats["loss"])
        after = np.asarray(trainer.state.params["lm_head"]["kernel"])
        assert np.max(np.abs(after - before)) > 0
    finally:
        trainer.close()


# -- the shares ----------------------------------------------------------


def test_eight_expert_shares_add_up_to_the_uncut_layer():
    cfg, _, params = _model()
    shape = _shape(cfg)
    w = chk.layer_weights(params["layers_0"])
    x = jnp.asarray(np.random.RandomState(8).normal(0, 1, (12, 64)),
                    jnp.float32)
    positions = jnp.arange(12)
    mask = ref.clean_mask(positions, 4)
    whole = ref.layer(x, w, positions, shape, (0, 8), mask)
    attn_only = ref.layer(x, {**w, "e_gate_up": w["e_gate_up"][:0],
                              "e_down": w["e_down"][:0]}, positions, shape,
                          (0, 0), mask)
    total = attn_only
    from orion_tpu.models.transformer import Block

    for e in range(8):
        scfg = dataclasses.replace(cfg, experts_held=1, expert_offset=e)
        p = {**params["layers_0"], "mlp": {
            "router": params["layers_0"]["mlp"]["router"],
            "experts_gate_up_proj":
                params["layers_0"]["mlp"]["experts_gate_up_proj"][e:e + 1],
            "experts_down_proj":
                params["layers_0"]["mlp"]["experts_down_proj"][e:e + 1]}}
        out, _ = Block(scfg, "attention", "experts").apply(
            {"params": p}, x[None], positions[None])
        total = total + (out[0] - attn_only)
    np.testing.assert_allclose(total, whole, atol=3e-5)


# -- the attention pieces ------------------------------------------------


def test_the_block_step_equals_the_einsum():
    rs = np.random.RandomState(12)
    B, Lq, H, Hkv, D, m = 2, 4, 4, 2, 16, 24
    q = jnp.asarray(rs.normal(0, 1, (B, Lq, H, D)), jnp.float32)
    k = jnp.asarray(rs.normal(0, 1, (B, m, Hkv, D)), jnp.float32)
    v = jnp.asarray(rs.normal(0, 1, (B, m, Hkv, D)), jnp.float32)
    see = jnp.asarray([[11] * 4, [19] * 4])
    mask = jnp.arange(m)[None, None, :] <= see[:, :, None]
    np.testing.assert_allclose(
        step_attention(q, k, v, mask, 0.25),
        reference_attention_gqa(q, k, v, mask, 0.25), atol=1e-5)


def _streams_case(rs, B=2, Lc=128, G=32, Bd=4, H=4, Hkv=2, D=16):
    Ln = G * Bd
    L = Lc + Ln
    q = jnp.asarray(rs.normal(0, 1, (B, L, H, D)), jnp.float32)
    k = jnp.asarray(rs.normal(0, 1, (B, L, Hkv, D)), jnp.float32)
    v = jnp.asarray(rs.normal(0, 1, (B, L, Hkv, D)), jnp.float32)
    cpos = np.arange(Lc)
    # two streams of 16 blocks each from position 60 on; row 1 from 0 on,
    # so that its first groups see no clean key at all
    starts = np.asarray([60, 0])
    wpos = starts[:, None] + np.tile(np.arange(Ln // 2), 2)[None]
    see = np.concatenate(
        [np.broadcast_to(cpos // Bd * Bd + Bd - 1, (B, Lc)),
         wpos // Bd * Bd - 1], axis=1)
    return q, k, v, jnp.asarray(see, jnp.int32), Lc, Bd


def test_the_merged_two_part_attention_equals_the_mask():
    q, k, v, see, Lc, Bd = _streams_case(np.random.RandomState(13))
    want = streams_attention(q, k, v, see, Lc, Bd, 0.25, "reference")
    got = streams_attention(q, k, v, see, Lc, Bd, 0.25, "flash")
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_merged_two_part_attentions_gradients_equal_the_masks():
    q, k, v, see, Lc, Bd = _streams_case(np.random.RandomState(14))
    t = jnp.asarray(np.random.RandomState(15).normal(0, 1, q.shape),
                    jnp.float32)

    def loss(impl):
        return lambda q, k, v: jnp.sum(streams_attention(
            q, k, v, see, Lc, Bd, 0.25, impl) * t)

    want = jax.grad(loss("reference"), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss("flash"), argnums=(0, 1, 2))(q, k, v)
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(g, w_, atol=5e-5)


def test_the_model_through_the_kernels_equals_the_einsum_path():
    cfg, model, params = _model()
    seqs, lens, steps = _trace_batch(cfg, P=12, T=10, lens=(12, 9))
    blocks = cfg.blocks_spanned(10)
    row = trace_streams(jnp.asarray(seqs), jnp.asarray(lens),
                        jnp.asarray(steps), 4, 4, cfg.mask_id, blocks,
                        4 * blocks * 4)
    kw = dict(logits_positions=row["read_at"], token_mask=row["token_mask"],
              visible=Visible(see=row["see"], clean=seqs.shape[1], block=4))
    want, _ = model.apply({"params": params}, row["ids"], row["positions"],
                          **kw)
    flash = Transformer(dataclasses.replace(cfg, attention_impl="flash"))
    got, _ = flash.apply({"params": params}, row["ids"], row["positions"],
                         **kw)
    np.testing.assert_allclose(got, want, atol=3e-5)


# -- the preset, the refusals -------------------------------------------


def test_the_preset_is_the_published_model():
    cfg = ModelConfig.sdar_30b_a3b()
    assert cfg.layer_kinds() == (("attention", "experts"),) * 48
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.vocab_size) == (
                2048, 32, 4, 128, 128, 8, 768, 151936)
    assert (cfg.block_length, cfg.denoising_steps) == (4, 4)
    cut = dataclasses.replace(cfg, vocab_size=18992)
    assert cut.mask_id == 18991 and cfg.mask_id == 151935
    assert cfg.blocks_spanned(512) == 129 and cfg.blocks_spanned(10) == 4


@pytest.mark.parametrize("form", ["paged", "quantize_kv", "quantize_weights",
                                  "speculative_k", "continuous",
                                  "sequence_parallel"])
def test_what_block_diffusion_cannot_run_is_refused_with_its_reason(form):
    cfg, model, _ = _model()
    if form == "sequence_parallel":
        with pytest.raises(ValueError, match="both directions"):
            ModelConfig.tiny("sdar_moe", attention_impl="ring")
        return
    if form == "continuous":
        from orion_tpu.rollout.continuous import ContinuousBatchingEngine

        with pytest.raises(ValueError, match="block boundaries"):
            ContinuousBatchingEngine(model, cfg, RolloutConfig(
                engine="continuous", max_prompt_len=8, max_new_tokens=8))
        return
    reason = {"paged": "one token a row a step",
              "quantize_kv": "rewritten at every denoising step",
              "quantize_weights": "another trace",
              "speculative_k": "out of order"}[form]
    kw = {"speculative_k": 2} if form == "speculative_k" else {form: True}
    with pytest.raises(ValueError, match=reason):
        RolloutEngine(model, cfg, RolloutConfig(
            max_prompt_len=8, max_new_tokens=8, **kw))


def test_the_rule_belongs_to_sdar_alone_and_ppo_needs_the_shared_trunk():
    with pytest.raises(ValueError, match="only arch='sdar_moe'"):
        ModelConfig.tiny("llama", block_length=4, denoising_steps=4)
    with pytest.raises(ValueError, match="dividing it"):
        ModelConfig.tiny("sdar_moe", denoising_steps=3)
    assert cannot_run(ModelConfig.tiny("llama"), "paged") is None
    from orion_tpu.trainers.ppo import PPOTrainer

    cfg, model, params = _model()
    with pytest.raises(ValueError, match="share_backbone=true"):
        PPOTrainer(PPOConfig(model=cfg, share_backbone=False), model, params)
