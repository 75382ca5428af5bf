"""The ``deepseek_v3`` block (latent attention, the dropless
sigmoid-routed expert layer, one leading dense layer) at the tiny size
against the plain reference ``benchmarks/reference_dsv3.py`` on seeded
weights: training forward, cached decode through the absorbed path,
gradients, the scanned layout, one PPO iteration through the launcher,
and the tests that tie the chip's share to the uncut model."""

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
                                          maybe_unstack_for_decode)
from orion_tpu.ops import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "dsv3_test_" + name, os.path.join(REPO, "benchmarks", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference_dsv3")
chk = _load("reference_check_dsv3")


def _shape(cfg):
    return {"num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.rms_norm_eps,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "n_routed_experts": cfg.experts_held,
            "expert_offset": cfg.expert_offset,
            "vocab_size": cfg.vocab_size}


def _weights(params, cfg):
    """The program's tree as the reference's ``forward`` takes it."""
    layers = [chk._layer_weights(params[f"layers_{i}"])
              for i in range(cfg.num_layers)]
    return {"embed": params["embed"]["embedding"], "layers": layers,
            "nf_g": params["final_norm"]["scale"],
            "w_head": params["lm_head"]["kernel"]}


def _held(cfg):
    return cfg.expert_offset, cfg.experts_held


@pytest.fixture(scope="module")
def tiny():
    # a share: 4 of 8 experts from the third on
    cfg = ModelConfig.tiny("deepseek_v3", dtype="float32", experts_held=4,
                           expert_offset=2)
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(3), cfg)
    ids = np.random.RandomState(0).randint(2, cfg.vocab_size, (2, 40))
    return cfg, model, params, jnp.asarray(ids, jnp.int32)


def _positions(ids):
    return jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)


def _reference_logits(params, cfg, ids):
    w = _weights(params, cfg)
    return jnp.stack([ref.forward(w, row, _shape(cfg), _held(cfg))
                      for row in ids])


@pytest.mark.parametrize("experts", ["dense", "grouped"])
def test_training_forward_matches_reference_float32(tiny, experts,
                                                    monkeypatch):
    cfg, model, params, ids = tiny
    if experts == "grouped":    # the Pallas grouped product, interpreted
        monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 0)
    logits, _ = model.apply({"params": params}, ids, _positions(ids))
    want = _reference_logits(params, cfg, ids)
    # float32 on both sides, another order of operations
    np.testing.assert_allclose(logits, want, atol=2e-5, rtol=0)


def test_training_forward_bfloat16_is_inside_the_error_model(tiny):
    cfg, _, params, ids = tiny
    bf16 = Transformer(dataclasses.replace(cfg, dtype="bfloat16"))
    logits, _ = bf16.apply({"params": params}, ids, _positions(ids))
    want = _reference_logits(params, cfg, ids)
    err = np.abs(np.asarray(logits - want))
    sigma_z = float(jnp.mean(jnp.std(want, axis=-1)))
    rms = sigma_z * np.sqrt(cfg.num_layers * chk.ROUNDINGS_DSV3 + 3) \
        * 2.0 ** -9 / np.sqrt(3.0)
    # a logit's error is the model's; tokens that exchanged an expert
    # (the tiny model has a few) are off by more, so: the median
    assert np.median(err) < rms and np.median(err) > rms / 30


@pytest.mark.parametrize("scan", [False, True])
def test_prefill_then_absorbed_decode_matches_reference(tiny, scan):
    cfg, model, params, ids = tiny
    if scan:
        cfg = dataclasses.replace(cfg, scan_layers=True)
        model = Transformer(cfg)
        stacked = init_params(model, jax.random.key(3), cfg)
        params = maybe_unstack_for_decode(stacked, cfg)
        run_params = stacked
    else:
        run_params = params
    want = _reference_logits(params, dataclasses.replace(
        cfg, scan_layers=False), ids)
    P = 24
    cache = init_cache(cfg, ids.shape[0], ids.shape[1])
    pos = _positions(ids)
    logits, cache = model.apply({"params": run_params}, ids[:, :P],
                                pos[:, :P], cache)
    got = [logits]
    for t in range(P, ids.shape[1]):
        step, cache = model.apply({"params": run_params}, ids[:, t:t + 1],
                                  pos[:, t:t + 1], cache)
        got.append(step)
    np.testing.assert_allclose(jnp.concatenate(got, axis=1), want,
                               atol=2e-5, rtol=0)
    leaf = (cache["layers"] if scan else cache[-1])
    assert set(leaf) == {"c", "k_rope"}
    assert leaf["c"].shape[-1] == cfg.kv_lora_rank
    assert leaf["k_rope"].shape[-1] == cfg.qk_rope_head_dim


@pytest.mark.parametrize("experts", ["dense", "grouped"])
def test_gradients_match_the_reference(tiny, experts, monkeypatch):
    cfg, model, params, ids = tiny
    ids = ids[:1, :24]
    if experts == "grouped":
        monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 0)

    def mean_logprob(logits):
        lp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        return jnp.mean(jnp.take_along_axis(lp, ids[:, 1:, None], axis=-1))

    got = jax.grad(lambda p: mean_logprob(
        model.apply({"params": p}, ids, _positions(ids))[0]))(params)
    want = jax.grad(lambda p: mean_logprob(
        _reference_logits(p, cfg, ids)))(params)
    flat_g = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_w = jax.tree.leaves(want)
    for (path, g), w in zip(flat_g, flat_w):
        name = jax.tree_util.keystr(path)
        if "e_score_correction_bias" in name:
            assert not np.any(np.asarray(g)), name   # selection only
            continue
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        np.testing.assert_allclose(g, w, atol=2e-4 * scale + 1e-9,
                                   rtol=0, err_msg=name)


def test_scanned_and_unrolled_layouts_agree(tiny):
    cfg, model, _, ids = tiny
    scfg = dataclasses.replace(cfg, scan_layers=True, remat=True)
    smodel = Transformer(scfg)
    stacked = init_params(smodel, jax.random.key(5), scfg)
    # the dense layer stands outside the stack, under its own name
    assert set(stacked) == {"embed", "final_norm", "layers", "layers_0",
                            "lm_head"}
    assert "gate_proj" in stacked["layers_0"]["mlp"]
    assert stacked["layers"]["mlp"]["router"].shape[0] == cfg.num_layers - 1
    unrolled = maybe_unstack_for_decode(stacked, scfg)
    assert set(unrolled) == {"embed", "final_norm", "lm_head"} | {
        f"layers_{i}" for i in range(cfg.num_layers)}
    a, _ = smodel.apply({"params": stacked}, ids, _positions(ids))
    b, _ = model.apply({"params": unrolled}, ids, _positions(ids))
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)
    # and back: stacking the unrolled expert layers gives the stack
    again = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[unrolled[f"layers_{i}"] for i in range(1, cfg.num_layers)])
    for x, y in zip(jax.tree.leaves(again), jax.tree.leaves(
            stacked["layers"])):
        np.testing.assert_array_equal(x, y)
    # logical axes know the stack and the dense layer beside it
    from orion_tpu.models.transformer import logical_specs
    specs = logical_specs(smodel, scfg)
    assert tuple(specs["layers"]["mlp"]["experts_down_proj"]) == (
        "layers", "expert", "mlp", "embed")
    assert tuple(specs["layers_0"]["attn"]["kv_b_proj"]) == (
        "latent", "heads")


def test_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts of all 8 shares (offsets 0..7, one expert
    each), with the shared expert counted once, are the uncut
    reference's layer output."""
    full = ModelConfig.tiny("deepseek_v3", dtype="float32")
    model = Transformer(full)
    params = init_params(model, jax.random.key(11), full)
    p = params["layers_1"]["mlp"]
    z = jax.random.normal(jax.random.key(12), (3, 10, full.hidden_size))
    w = chk._layer_weights(params["layers_1"])
    shape = _shape(full)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.expert_ffn(row, w, shape, (0, 8))
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


def test_router_bias_selects_and_does_not_gate():
    rs = np.random.RandomState(0)
    z = jnp.asarray(rs.normal(size=(64, 32)), jnp.float32)
    kernel = jnp.asarray(rs.normal(size=(32, 16)) * 0.2, jnp.float32)
    zero = jnp.zeros((16,))
    idx0, g0 = moe.sigmoid_topk_route(z, kernel, zero, 4, 2.448)
    np.testing.assert_allclose(np.sum(g0, axis=-1), 2.448, rtol=1e-5)
    # the group limit (n_group = topk_group = 1) is the identity: the
    # selection is the plain top-k of the scores
    scores = jax.nn.sigmoid(z @ kernel)
    np.testing.assert_array_equal(np.sort(idx0, axis=-1), np.sort(
        np.argsort(-np.asarray(scores), axis=-1)[:, :4], axis=-1))
    bias = jnp.asarray(rs.normal(size=(16,)) * 0.3, jnp.float32)
    idx1, g1 = moe.sigmoid_topk_route(z, kernel, bias, 4, 2.448)
    assert np.any(np.sort(idx0, axis=-1) != np.sort(idx1, axis=-1))
    np.testing.assert_allclose(np.sum(g1, axis=-1), 2.448, rtol=1e-5)
    chosen = jnp.take_along_axis(scores, idx1, axis=-1)
    np.testing.assert_allclose(
        g1, 2.448 * chosen / jnp.sum(chosen, axis=-1, keepdims=True),
        rtol=1e-5)       # gates from the scores alone, bias nowhere


# the grouped form works on BLOCK rows of the sorted pairs at a time: a
# block of one row tile, of a quarter of the pairs, and of all of them,
# against routings that leave it empty, fill it partly, fill it exactly,
# overflow it (more blocks run) and put every pair on one held expert
DROPLESS_T, DROPLESS_K = 1024, 4                     # 4096 pairs


def _dropless_blocks():
    from orion_tpu.ops.pallas.grouped_matmul import padded_rows, row_tile

    n_pairs = DROPLESS_T * DROPLESS_K
    return {"one_tile": row_tile(n_pairs), "quarter": n_pairs // 4,
            "all_pairs": padded_rows(n_pairs)}


DROPLESS_CASES = [
    (block, routing) for block in ("one_tile", "quarter", "all_pairs")
    for routing in ("none_held", "under", "exact", "over",
                    "all_on_one_held")
    # all pairs in one block: more held pairs than its rows cannot be
    if (block, routing) != ("all_pairs", "over")]


def _dropless_routing(rs, routing, block, H):
    n_pairs = DROPLESS_T * DROPLESS_K
    if routing == "all_on_one_held":
        return jnp.ones((DROPLESS_T, DROPLESS_K), jnp.int32)
    n_held = {"none_held": 0, "under": block // 2, "exact": block,
              "over": block + block // 2}[routing]
    local = rs.choice([-3, -1, H, H + 5], size=n_pairs)
    held = rs.permutation(n_pairs)[:n_held]
    local[held] = rs.randint(0, H, size=n_held)
    return jnp.asarray(local.reshape(DROPLESS_T, DROPLESS_K), jnp.int32)


@pytest.mark.parametrize("block,routing", DROPLESS_CASES,
                         ids=[f"{b}-{r}" for b, r in DROPLESS_CASES])
def test_dropless(block, routing):
    """Static shapes and no drops, whatever the block: the grouped form
    equals every held expert on every token in its output AND in its
    gradients with respect to x, both weight stacks and the gates; a
    batch whose every pair lands on one held expert is computed exactly
    (through ``T k / block`` blocks); one with no pair on a held expert
    gives exactly nothing."""
    rs = np.random.RandomState(1)
    T, k, D, I, H = DROPLESS_T, DROPLESS_K, 16, 8, 3
    block = _dropless_blocks()[block]
    x = jnp.asarray(rs.normal(size=(T, D)), jnp.float32)
    w_gu = jnp.asarray(rs.normal(size=(H, D, 2 * I)) * 0.3, jnp.float32)
    w_d = jnp.asarray(rs.normal(size=(H, I, D)) * 0.3, jnp.float32)
    gates = jnp.asarray(rs.uniform(0.1, 1.0, size=(T, k)), jnp.float32)
    cot = jnp.asarray(rs.normal(size=(T, D)), jnp.float32)
    local = _dropless_routing(rs, routing, block, H)

    def run(fn, *static):
        def out_and_loss(x, w_gu, w_d, gates):
            out = fn(x, w_gu, w_d, local, gates, *static)
            return jnp.sum(out * cot), out
        (_, out), grads = jax.value_and_grad(
            out_and_loss, argnums=(0, 1, 2, 3), has_aux=True)(
                x, w_gu, w_d, gates)
        return (out,) + grads

    want = run(moe.experts_dense)
    got = run(moe.experts_grouped, block)
    if routing == "all_on_one_held":
        h = x @ w_gu[1]
        np.testing.assert_allclose(
            want[0], (jax.nn.silu(h[:, :I]) * h[:, I:]) @ w_d[1]
            * jnp.sum(gates, axis=-1, keepdims=True), atol=1e-5, rtol=0)
    if routing == "none_held":
        assert not np.any(np.asarray(got[0]))
    for name, g, w in zip(("out", "d_x", "d_w_gate_up", "d_w_down",
                           "d_gates"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        # float32 on both sides, another order of summation: a weight's
        # gradient sums over up to 4096 rows, so relative to its size
        scale = max(1.0, float(jnp.max(jnp.abs(w))))
        np.testing.assert_allclose(g, w, atol=1e-5 * scale, rtol=0,
                                   err_msg=name)


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
            "ppo", "model_preset=tiny_deepseek_v3", "model.remat=true",
            "model.scan_layers=true", "share_backbone=true",
            "model.max_seq_len=24", "rollout.max_prompt_len=16",
            "rollout.max_new_tokens=8", "rollout_batch_size=4",
            "minibatch_size=2", "num_epochs=1", "data.dataset=synthetic",
            "reward=length", "total_iterations=1",
            "optimizer.learning_rate=1e-3", "ref_param_dtype=bfloat16",
            "optimizer.mu_dtype=bfloat16", "optimizer.nu_dtype=bfloat16",
            f"log_dir={tmp_path}"])
    finally:
        launch.build_trainer = real
    assert len(hist) == 1 and all(np.isfinite(r["loss"]) for r in hist)
    row = hist[-1]
    # every expert is held at the tiny size: all pairs are computed here
    assert row["moe_pairs_here"] == row["moe_pairs_total"] == 2 * 24 * 2 * 2
    assert row["moe_load_max"] >= row["moe_load_mean"] > 0
    # 48 tokens a minibatch: the dense form, which has no blocks
    assert row["moe_block_rows"] == row["moe_blocks_max"] == 0
    assert row["moe_combine_items"] == row["moe_combine_fill"] == 0
    before = kept["before"]["backbone"]
    after = kept["trainer"].state.params["backbone"]
    moved = np.max(np.abs(np.asarray(after["layers"]["attn"]["q_proj"][
        "kernel"]) - before["layers"]["attn"]["q_proj"]["kernel"]))
    assert moved > 0
    np.testing.assert_array_equal(        # the selection bias is held
        after["layers"]["mlp"]["e_score_correction_bias"],
        before["layers"]["mlp"]["e_score_correction_bias"])


@pytest.mark.parametrize("bound", ["from_shapes", "one_tile"])
def test_block_counters_through_the_launcher(bound, tmp_path, monkeypatch):
    """The grouped form's counters reach the metrics row: the rows of a
    block over the layers of a minibatch's forward, and the most blocks
    a layer ran.  All 8 experts are held, so from the shapes a block is
    all 96 pairs of a minibatch and one block runs; with the bound
    forced to one row tile (of 16 rows) the same pairs take six, and
    are all computed as before."""
    from orion_tpu import launch
    from orion_tpu.ops.pallas import grouped_matmul

    # one device, as on the chip (the suite's 8 would take the dense
    # form): the update, the experience forwards and the prefill take
    # the grouped form (interpreted); decode steps of 4 tokens stay dense
    real = launch.make_mesh
    monkeypatch.setattr(launch, "make_mesh", lambda cfg, devices=None: real(
        cfg, jax.devices()[:1]))
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 8)
    if bound == "one_tile":
        monkeypatch.setattr(moe, "BLOCK_ROWS_OVER_EVEN", 0)
        monkeypatch.setattr(grouped_matmul, "TILE_ROWS", 16)
    row = launch.main([
        "ppo", "model_preset=tiny_deepseek_v3", "model.remat=true",
        "model.scan_layers=true", "share_backbone=true",
        "model.max_seq_len=24", "rollout.max_prompt_len=16",
        "rollout.max_new_tokens=8", "rollout_batch_size=4",
        "minibatch_size=2", "num_epochs=1", "data.dataset=synthetic",
        "reward=length", "total_iterations=1", f"log_dir={tmp_path}"])[-1]
    assert np.isfinite(row["loss"])
    assert row["moe_pairs_here"] == row["moe_pairs_total"] == 2 * 24 * 2 * 2
    rows, blocks = (16, 6) if bound == "one_tile" else (96, 1)
    assert row["moe_block_rows"] == 2 * rows          # two expert layers
    assert row["moe_blocks_max"] == blocks
    # the combine kernel's work items place every held row, in products
    # that the rows of their ranges fill in part
    assert row["moe_combine_items"] >= blocks
    assert 0 < row["moe_combine_fill"] <= 1


@pytest.mark.parametrize("algo", ["grpo", "rloo", "online_dpo"])
def test_every_loss_reports_the_expert_counters(algo, tmp_path):
    """The counters are the forward's return value and each loss puts
    them into its stats: the other algorithms' rows carry them too."""
    from orion_tpu import launch

    row = launch.main([
        algo, "model_preset=tiny_deepseek_v3", "model.max_seq_len=24",
        "rollout.max_prompt_len=16", "rollout.max_new_tokens=8",
        "rollout_batch_size=4", "minibatch_size=2", "num_epochs=1",
        "data.dataset=synthetic", "reward=length", "total_iterations=1",
        f"log_dir={tmp_path}"])[-1]
    assert np.isfinite(row["loss"])
    assert row["moe_pairs_here"] == row["moe_pairs_total"] > 0
    assert row["moe_load_max"] >= row["moe_load_mean"] > 0


def test_paths_that_cannot_run_it_say_so():
    from orion_tpu.models.hf_export import hf_state_dict
    from orion_tpu.models.hf_loader import convert_hf_state_dict
    from orion_tpu.ops.quant import quantize_params_int8
    from orion_tpu.rollout import RolloutEngine
    from orion_tpu.rollout.continuous import ContinuousBatchingEngine

    cfg = ModelConfig.tiny("deepseek_v3")
    model = Transformer(cfg)
    for key, word in (("paged", "latent paged"), ("quantize_kv", "int8"),
                      ("quantize_weights", "int8")):
        with pytest.raises(ValueError, match=word):
            RolloutEngine(model, cfg, RolloutConfig(**{key: True}))
    with pytest.raises(ValueError, match="continuous engine"):
        ContinuousBatchingEngine(model, cfg, RolloutConfig())
    for impl in ("ring", "ulysses"):
        with pytest.raises(ValueError, match="sequence-parallel"):
            ModelConfig.tiny("deepseek_v3", attention_impl=impl)
    params = init_params(model, jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="deepseek_v3"):
        quantize_params_int8(params)
    with pytest.raises(ValueError, match="deepseek_v3"):
        hf_state_dict(params, cfg)
    with pytest.raises(ValueError, match="deepseek_v3"):
        convert_hf_state_dict({}, cfg)
    with pytest.raises(ValueError, match="int8 latent"):
        init_cache(cfg, 1, 8, quantized=True)
    # the fixed-batch engine does run it, and knows its cache's size
    eng = RolloutEngine(model, cfg, RolloutConfig(max_prompt_len=8,
                                                  max_new_tokens=8))
    per_token = cfg.num_layers * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
    assert eng.dispatch_attrs((2, 8), [8, 8])["cache_bytes"] \
        == 2 * 16 * per_token * 2


@pytest.mark.parametrize("scan", [False, True])
def test_padding_is_routed_nowhere(tiny, scan):
    """Positions behind a right-padded sequence all hold the pad id and
    would all select the same experts; with ``token_mask`` they get the
    shared expert alone, count in no expert's load, and leave every
    token's logits as they were (nothing attends to them)."""
    cfg, model, params, ids = tiny
    if scan:
        cfg = dataclasses.replace(cfg, scan_layers=True)
        model = Transformer(cfg)
        params = init_params(model, jax.random.key(3), cfg)
    real = 25
    ids = ids.at[:, real:].set(0)
    mask = _positions(ids) < real
    (plain, _), seen = model.apply({"params": params}, ids, _positions(ids),
                                   mutable=["intermediates"])
    (masked, _), kept = model.apply({"params": params}, ids,
                                    _positions(ids), token_mask=mask,
                                    mutable=["intermediates"])
    np.testing.assert_allclose(masked[:, :real], plain[:, :real], atol=1e-6)
    assert np.max(np.abs(masked[:, real:] - plain[:, real:])) > 1e-4

    def loads(inter):
        return sum(int(jnp.sum(x)) for path, x in
                   jax.tree_util.tree_flatten_with_path(inter)[0]
                   if "moe_load" in jax.tree_util.keystr(path))

    per_layer_pairs = ids.shape[0] * cfg.num_experts_per_tok
    assert loads(seen) > loads(kept)
    assert loads(kept) <= 2 * per_layer_pairs * real


def test_a_mesh_of_several_devices_takes_the_dense_form(monkeypatch):
    """Under a mesh of more than one device the expert layer takes the
    dense form whatever the step's size (a Mosaic kernel cannot be
    partitioned automatically), with the held experts' stacks sharded
    on the ``expert`` axis, and computes what one device computes."""
    from orion_tpu.config import MeshConfig
    from orion_tpu.models.sharded import make_sharded_model
    from orion_tpu.parallel.mesh import make_mesh

    cfg = ModelConfig.tiny("deepseek_v3", dtype="float32")
    model = Transformer(cfg)
    ids = jnp.asarray(np.random.RandomState(5).randint(
        2, cfg.vocab_size, (4, 16)), jnp.int32)
    pos = _positions(ids)
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 0)   # grouped, if it may
    mesh = make_mesh(MeshConfig(data=1, fsdp=2, seq=1, expert=4, tensor=1),
                     jax.devices()[:8])
    with mesh:
        params, _ = make_sharded_model(model, mesh, jax.random.key(0),
                                       (ids[:1, :2], pos[:1, :2]))
        spec = params["layers_1"]["mlp"]["experts_down_proj"].sharding.spec
        assert "expert" in str(spec)

        def no_kernel(*a, **k):
            raise AssertionError("the grouped product under a mesh")

        with monkeypatch.context() as m:
            m.setattr(moe, "experts_grouped", no_kernel)
            sharded, _ = jax.jit(lambda p: model.apply(
                {"params": p}, ids, pos))(params)
        host = jax.device_get(params)
    one, _ = model.apply({"params": host}, ids, pos)      # grouped
    np.testing.assert_allclose(sharded, one, atol=2e-5, rtol=0)
