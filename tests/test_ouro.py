"""The ``ouro`` model (one stack of sandwich-norm blocks run four times
over with the same weights, a cache entry for every (pass, layer), an
exit gate read after every pass) at the tiny size against the plain
reference ``benchmarks/reference_ouro.py`` on seeded weights: the
training forward, its exit masses and all gradients in both layouts; the
tie to a plain model (an untied stack of ``4 x L`` blocks, a shared
weight's gradient the sum of its copies'); prefill of ragged prompts and
decode through the (pass, layer) entries across a ``prefix_lengths``
boundary; ``total_ut_steps = 1`` as the parent's programs; one block
body in the update whatever the passes; the counts against brute force;
one PPO iteration through the launcher; the refusals."""

import dataclasses
import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import ModelConfig, RolloutConfig
from orion_tpu.models.transformer import (MIXERS, MLP, Block, Transformer,
                                          _norm, cannot_run, decode_attrs,
                                          exit_masses, init_cache,
                                          init_params, make_decode_twin,
                                          maybe_unstack_for_decode,
                                          prefix_lengths, remat_tag_bytes,
                                          update_attrs)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "one_pass_jaxprs.json")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "ouro_test_" + name, os.path.join(REPO, "benchmarks", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference_ouro")
chk = _load("reference_check_ouro")


def _shape(cfg):
    return dict(
        num_hidden_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
        rms_norm_eps=cfg.rms_norm_eps, vocab_size=cfg.vocab_size,
        head_dim=cfg.head_dim, intermediate_size=cfg.intermediate_size,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, rope_theta=cfg.rope_theta,
        total_ut_steps=cfg.total_ut_steps)


def _cfg(**kw):
    return ModelConfig.tiny("ouro", dtype="float32", **kw)


def _sharpen(params):
    """Queries and keys sixfold and every norm's scale a seeded draw: at
    this width the scores are near zero and the norms all ones, which
    hides what a pass or a norm out of place would change."""
    rs = np.random.RandomState(5)
    params = chk.probe_params(params, rs)

    def scale(path, x):
        names = [str(getattr(k, "key", "")) for k in path]
        return 6.0 * x if {"q_proj", "k_proj"} & set(names) else x

    return jax.tree_util.tree_map_with_path(scale, params)


@pytest.fixture(scope="module")
def seeded():
    """(scanned configuration, its model, sharpened parameters)."""
    cfg = _cfg(scan_layers=True, remat=True)
    model = Transformer(cfg)
    return cfg, model, _sharpen(init_params(model, jax.random.key(3), cfg))


def _weights(params, cfg):
    """The program's (unstacked or stacked) tree as the reference's."""
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    return {"embed": f32(params["embed"]["embedding"]),
            "layers": [chk.layer_weights(chk.layer_tree(params, i))
                       for i in range(cfg.num_layers)],
            "nf": f32(params["final_norm"]["scale"]),
            "w_g": f32(params["exit_gate"]["kernel"])[:, 0],
            "b_g": f32(params["exit_gate"]["bias"])[0],
            "w_head": f32(params["lm_head"]["kernel"])}


def _program(model, params, ids):
    """(logits, hidden, masses [passes, B, L]) of the program."""
    pos = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    (logits, _, hidden), inter = model.apply(
        {"params": params}, ids, pos, return_hidden=True,
        mutable=["intermediates"])
    return logits, hidden, inter["intermediates"]["ut_exit_mass"][0]


@pytest.mark.parametrize("layout", ["scanned", "unrolled"])
def test_forward_masses_and_every_gradient_equal_the_references(seeded,
                                                                layout):
    cfg, model, params = seeded
    if layout == "unrolled":
        model, cfg = make_decode_twin(model, cfg)
        params = maybe_unstack_for_decode(params, seeded[0])
    shape = _shape(cfg)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, 256, (2, 40)), jnp.int32)
    # seeded cotangents, so that every output's gradient is compared
    cot = [jnp.asarray(rs.standard_normal(s), jnp.float32)
           for s in ((2, 40, 256), (2, 40, 64), (4, 2, 40))]

    def program_loss(p):
        return sum(jnp.sum(o * c) for o, c in zip(_program(model, p, ids),
                                                  cot))

    def reference_loss(w):
        total = 0.0
        for b in range(2):
            logits, hidden, masses = ref.forward(w, ids[b], shape)
            total += (jnp.sum(logits * cot[0][b])
                      + jnp.sum(hidden * cot[1][b])
                      + jnp.sum(masses * cot[2][:, b]))
        return total

    got = _program(model, params, ids)
    w = _weights(params, cfg)
    for b in range(2):
        want = ref.forward(w, ids[b], shape)
        np.testing.assert_allclose(got[0][b], want[0], atol=2e-4)
        np.testing.assert_allclose(got[1][b], want[1], atol=2e-5)
        np.testing.assert_allclose(got[2][:, b], want[2], atol=1e-5)
    np.testing.assert_allclose(jnp.sum(got[2], axis=0), 1.0, atol=1e-6)
    # ALL gradients: the reference's tree carried into the program's
    g_prog = jax.grad(program_loss)(params)
    g_ref = jax.grad(reference_loss)(w)
    g_want = _weights(g_prog, cfg)
    flat_w, _ = jax.tree_util.tree_flatten_with_path(g_want)
    flat_r = dict(jax.tree_util.tree_flatten_with_path(g_ref)[0])
    assert len(flat_w) == len(flat_r) == 7 + 11 * cfg.num_layers - 2
    for path, x in flat_w:
        scale = float(jnp.max(jnp.abs(flat_r[path]))) + 1e-6
        np.testing.assert_allclose(x / scale, flat_r[path] / scale,
                                   atol=2e-4, err_msg=str(path))
        assert scale > 1e-6, path
    # and nothing in the program's tree is left over
    assert sum(x.size for x in jax.tree.leaves(g_prog)) == sum(
        x.size for x in jax.tree.leaves(g_ref))


def test_the_looped_stack_is_an_untied_stack_of_four_times_the_blocks(seeded):
    """Layer l's weights at every (t, l) of a plain stack of 4 x L
    blocks with the final norm between groups of L: the same outputs,
    and a shared weight's gradient is the SUM of its four copies'."""
    cfg, model, params = seeded
    flat = maybe_unstack_for_decode(params, cfg)
    L, T = cfg.num_layers, cfg.total_ut_steps
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 256, (2, 24)))
    pos = jnp.broadcast_to(jnp.arange(24), ids.shape)
    cot = jnp.asarray(np.random.RandomState(2).standard_normal(
        (2, 24, 256)), jnp.float32)
    block = Block(dataclasses.replace(cfg, scan_layers=False, remat=False),
                  "attention", "dense")
    norm = _norm(cfg, None)

    def untied(copies, rest):
        x = rest["embed"]["embedding"][ids]
        for t in range(T):
            for j in range(L):
                x, _ = block.apply({"params": copies[t * L + j]}, x, pos)
            x = norm.apply({"params": rest["final_norm"]}, x)
        return x @ rest["lm_head"]["kernel"]

    copies = [flat[f"layers_{j}"] for _ in range(T) for j in range(L)]
    with jax.default_matmul_precision("highest"):
        plain = untied(copies, flat)
        looped = model.apply({"params": params}, ids, pos)[0]
        np.testing.assert_allclose(looped, plain, atol=1e-5)
        g_copies = jax.grad(lambda c: jnp.sum(untied(c, flat) * cot))(copies)
        g_loop = jax.grad(lambda p: jnp.sum(
            model.apply({"params": p}, ids, pos)[0] * cot))(params)
    g_loop = maybe_unstack_for_decode(g_loop, cfg)
    for j in range(L):
        summed = jax.tree.map(lambda *g: sum(g),
                              *[g_copies[t * L + j] for t in range(T)])
        one = g_copies[j]
        for (path, a), b, c in zip(
                jax.tree_util.tree_flatten_with_path(summed)[0],
                jax.tree.leaves(g_loop[f"layers_{j}"]), jax.tree.leaves(one)):
            scale = float(jnp.max(jnp.abs(a)))
            np.testing.assert_allclose(b / scale, a / scale, atol=1e-5,
                                       err_msg=f"{j} {path}")
            # not any one copy's
            assert float(jnp.max(jnp.abs(b - c))) > 1e-3 * scale


def test_prefill_and_decode_through_every_pass_layer_entry(seeded):
    """Ragged right-padded prompts, then one-token steps across a
    ``prefix_lengths`` boundary (128 of 256 slots), through the decode
    twin's 12 entries (three layers' leaves, four passes each, carried
    through the scan over passes): the logits are the reference's whole forward's."""
    scanned_cfg, model, params = seeded
    scanned_cfg = dataclasses.replace(scanned_cfg, max_seq_len=256)
    twin, cfg = make_decode_twin(Transformer(scanned_cfg), scanned_cfg)
    flat = maybe_unstack_for_decode(params, scanned_cfg)
    assert prefix_lengths(256) == [128, 256]
    lens = np.array([118, 96, 124])
    P, steps = 124, 12                  # the longest row passes slot 128
    rs = np.random.RandomState(4)
    seqs = rs.randint(0, 256, (3, P + steps))
    prompts = np.where(np.arange(P)[None] < lens[:, None], seqs[:, :P], 0)
    cache = init_cache(cfg, 3, 256)
    assert len(cache) == 3 and cache[0]["k"].shape == (4, 3, 256, 4, 16)
    pos = jnp.broadcast_to(jnp.arange(P), (3, P))
    step = jax.jit(lambda ids, pos, cache: twin.apply(
        {"params": flat}, ids, pos, cache))
    logits, cache = twin.apply(
        {"params": flat}, jnp.asarray(prompts), pos, cache,
        logits_positions=jnp.asarray(lens - 1)[:, None])
    got = [logits[:, 0]]
    # teacher-forced: row b's token at position lens[b] + t
    rows = np.arange(3)
    for t in range(steps):
        at = lens + t
        tok = jnp.asarray(seqs[rows, at])[:, None]
        logits, cache = step(tok, jnp.asarray(at)[:, None], cache)
        got.append(logits[:, 0])
    got = np.stack([np.asarray(g) for g in got], axis=1)   # [3, steps+1, V]
    w, shape = _weights(flat, cfg), _shape(cfg)
    for b in range(3):
        # the row as the cache saw it: its prompt, then its own tokens
        n = int(lens[b])
        row = np.concatenate([seqs[b, :n], seqs[b, n:n + steps]])
        want = ref.forward(w, jnp.asarray(row), shape)[0]
        np.testing.assert_allclose(got[b], want[n - 1:n + steps], atol=3e-4)
    # every (pass, layer) entry was written, each by its own pass: no
    # two of a layer's four entries hold the same keys
    for j in range(cfg.num_layers):
        ks = [np.asarray(cache[j]["k"][t, 0, :100]) for t in range(4)]
        assert all(np.abs(k).max() > 0 for k in ks)
        assert all(np.abs(ks[a] - ks[b]).max() > 1e-3
                   for a in range(4) for b in range(a))
    # the scanned cache: a leading pass axis
    stacked = init_cache(scanned_cfg, 3, 256)
    assert stacked["k"].shape == (4, 3, 3, 256, 4, 16)


# -- total_ut_steps = 1 is the parent's program -----------------------------

def _callers(arch: str) -> dict:
    """{caller: jaxpr text} of one tiny model with one pass: the
    training forward, the gradient under remat in the scanned layout,
    prefill and one step of the decode twin."""
    cfg = ModelConfig.tiny(arch, dtype="float32", scan_layers=True,
                           remat=True)
    model = Transformer(cfg)
    ids = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    params = jax.eval_shape(lambda k: init_params(model, k, cfg),
                            jax.random.key(0))
    twin, tcfg = make_decode_twin(model, cfg)
    cache = jax.eval_shape(lambda: init_cache(tcfg, 2, 24))
    one = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    kw = {"token_mask": jnp.ones((2, 16), bool)} \
        if cfg.takes_token_mask else {}
    kw1 = {"token_mask": jnp.ones((2, 1), bool)} \
        if cfg.takes_token_mask else {}

    def forward(p, ids):
        return model.apply({"params": p}, ids, ids, **kw)[0]

    def grad(p, ids):
        return jax.grad(lambda p: jnp.sum(forward(p, ids)))(p)

    def prefill(p, ids, cache):
        return twin.apply({"params": maybe_unstack_for_decode(p, cfg)},
                          ids, ids, cache, **kw)

    def step(p, ids, cache):
        return twin.apply({"params": maybe_unstack_for_decode(p, cfg)},
                          ids, ids, cache, **kw1)

    return {"forward": str(jax.make_jaxpr(forward)(params, ids)),
            "gradient": str(jax.make_jaxpr(grad)(params, ids)),
            "prefill": str(jax.make_jaxpr(prefill)(params, ids, cache)),
            "step": str(jax.make_jaxpr(step)(params, one, cache))}


def _digests(arch: str) -> dict:
    return {k: hashlib.sha256(v.encode()).hexdigest()
            for k, v in _callers(arch).items()}


@pytest.mark.parametrize("arch", ["llama", "olmo_hybrid", "neox"])
def test_one_pass_lowers_to_the_parents_programs(arch):
    """A pre-norm, a post-norm and a parallel-residual model: the jaxprs
    of four callers digest as they did on the PARENT tree (PR 55's
    re-anchor, before the pass loop and the sandwich order went into
    ``Transformer`` and ``Block``; the fixture holds digests, not
    text)."""
    with open(FIXTURE) as f:
        recorded = json.load(f)
    assert _digests(arch) == recorded[arch]


def _dots(text: str) -> int:
    return text.count("dot_general")


def test_the_update_holds_one_block_body_whatever_the_passes():
    """The gradient's program at 1 and at 4 passes: the same number of
    matrix products in the text (one scanned block's, forward, remat's
    and backward), and four passes hold one scan more around them."""
    texts = {}
    for passes in (1, 4):
        cfg = _cfg(scan_layers=True, remat=True, total_ut_steps=passes)
        model = Transformer(cfg)
        params = jax.eval_shape(lambda k: init_params(model, k, cfg),
                                jax.random.key(0))
        ids = jax.ShapeDtypeStruct((2, 16), jnp.int32)
        texts[passes] = str(jax.make_jaxpr(jax.grad(lambda p, ids: jnp.sum(
            model.apply({"params": p}, ids, ids)[0])))(params, ids))
    assert _dots(texts[1]) == _dots(texts[4]) > 0
    # one pass: the stack's scan forward and backward; four: the scan
    # over passes around each, and the backward's holds the pass's
    # forward again beside its backward
    assert (texts[1].count("scan["), texts[4].count("scan[")) == (2, 5)
    # unrolled it is L blocks a pass
    cfg = _cfg(total_ut_steps=4)
    model = Transformer(cfg)
    params = jax.eval_shape(lambda k: init_params(model, k, cfg),
                            jax.random.key(0))
    one = str(jax.make_jaxpr(lambda p, ids: model.apply(
        {"params": p}, ids, ids)[0])(params, ids))
    cfg1 = _cfg(total_ut_steps=1)
    plain = str(jax.make_jaxpr(lambda p, ids: Transformer(cfg1).apply(
        {"params": p}, ids, ids)[0])(params, ids))
    per_pass = _dots(plain) - 1                      # the head once
    assert _dots(one) == 4 * per_pass + 1


def test_the_counts_are_the_brute_force_ones():
    cfg = _cfg(scan_layers=True)
    one = dataclasses.replace(cfg, total_ut_steps=1)
    assert cfg.layer_visits() == 12 and one.layer_visits() == 3
    # what a kept tag holds: every visit's
    per_layer = {}
    for cls in (MIXERS["attention"], MLP):
        for tag, size in cls.tag_bytes(cfg, 4, 40, lambda d: d).items():
            per_layer[tag] = per_layer.get(tag, 0) + size
    per_layer["attn_resid"] = 4 * 40 * 64 * 4
    # scanned: the newest pass's stack is alive twice (the scan over
    # layers', and its copy in the scan over passes')
    assert dict(remat_tag_bytes(cfg, 4, 40)) == {
        t: 15 * b for t, b in per_layer.items()}
    assert dict(remat_tag_bytes(dataclasses.replace(
        cfg, scan_layers=False), 4, 40)) == {
        t: 12 * b for t, b in per_layer.items()}
    assert dict(remat_tag_bytes(one, 4, 40)) == {
        t: 3 * b for t, b in per_layer.items()}
    lens = [9, 16, 12, 16]
    d = decode_attrs(cfg, lens, 24, 8)
    assert (d["ut_steps"], d["layer_visits"], d["kv_step_form"],
            d["kv_step_slots"]) == (4, 12, "whole", 24.0)
    assert "ut_steps" not in decode_attrs(one, lens, 24, 8)
    u = update_attrs(cfg, [17, 24, 20, 24])
    assert (u["ut_steps"], u["layer_visits"], u["shared_grad_uses"]) \
        == (4, 12, 4)
    assert u["seq_tokens"] == 85
    assert u["causal_keys"] == sum(t + 1 for n in (17, 24, 20, 24)
                                   for t in range(n))
    assert not {"ut_steps", "seq_tokens"} & set(update_attrs(one, [17]))
    # the engine's reckoning: an entry a (pass, layer), the stack's bytes
    from orion_tpu.rollout import RolloutEngine

    model = Transformer(cfg)
    eng = RolloutEngine(model, cfg, RolloutConfig(max_prompt_len=16,
                                                  max_new_tokens=8))
    params = init_params(model, jax.random.key(0), cfg)
    a = eng.dispatch_attrs((4, 16), lens, params)
    entry = 4 * 24 * 2 * 4 * 16 * 4
    assert (a["cache_bytes"], a["cache_bytes_a_pass"]) == (12 * entry,
                                                           3 * entry)
    stack = sum(x.size * 4 for x in jax.tree.leaves(params["layers"]))
    assert a["stack_weight_bytes"] == stack
    assert a["once_weight_bytes"] == 4 * (64 * 256 + 64)
    assert a["weight_bytes"] == stack + 4 * (2 * 64 * 256 + 64 + 65)
    # the masses
    lam = jnp.asarray([[0.5, 1.0], [0.5, 0.3], [0.5, 0.2], [0.9, 0.9]])
    m = exit_masses(lam)
    np.testing.assert_allclose(m[:, 0], [0.5, 0.25, 0.125, 0.125])
    np.testing.assert_allclose(m[:, 1], [1.0, 0.0, 0.0, 0.0])


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
            "ppo", "model_preset=tiny_ouro", "model.remat=true",
            "model.scan_layers=true", "share_backbone=true",
            "model.max_seq_len=24", "rollout.max_prompt_len=16",
            "rollout.max_new_tokens=8", "rollout_batch_size=4",
            "minibatch_size=2", "num_epochs=1", "data.dataset=synthetic",
            "reward=length", "total_iterations=2",
            "data.synthetic_min_len=10", "data.synthetic_max_len=16",
            "optimizer.learning_rate=1e-3", "optimizer.weight_decay=0.1",
            "ref_param_dtype=bfloat16",
            "optimizer.mu_dtype=bfloat16", "optimizer.nu_dtype=bfloat16",
            f"log_dir={tmp_path}"])
    finally:
        launch.build_trainer = real
    assert len(hist) == 2 and all(np.isfinite(r["loss"]) for r in hist)
    row = hist[-1]
    assert (row["ut_steps"], row["layer_visits"], row["shared_grad_uses"]) \
        == (4, 12, 4)
    assert row["ut_passes_per_token"] == 4.0
    assert sum(row[f"ut_exit_mass_{t}"] for t in (1, 2, 3, 4)) \
        == pytest.approx(1.0, abs=1e-3)
    before = kept["before"]["backbone"]
    after = kept["trainer"].state.params["backbone"]
    assert after["layers"]["attn"]["q_proj"]["kernel"].shape == (3, 64, 64)
    for name in ("layers", "final_norm", "lm_head", "embed"):
        moved = max(float(np.max(np.abs(np.asarray(x) - y))) for x, y in zip(
            jax.tree.leaves(after[name]), jax.tree.leaves(before[name])))
        assert moved > 0, name
    # nothing in PPO's loss reads the gate: it stays as initialised,
    # weight decay too held off it
    for x, y in zip(jax.tree.leaves(after["exit_gate"]),
                    jax.tree.leaves(before["exit_gate"])):
        np.testing.assert_array_equal(np.asarray(x), y)


# -- the refusals ------------------------------------------------------------

@pytest.mark.parametrize("form,needle", [
    ("paged", "no pages for every (pass, layer)"),
    ("continuous", "no pass axis"),
    ("speculative", "continuous engine"),
    ("quantize_kv", "int8 entries of every (pass, layer)"),
    ("quantize_weights", "once a pass"),
    ("sequence_parallel", "scan over passes"),
])
def test_cannot_run_names_each_refused_form(form, needle):
    cfg = ModelConfig.tiny("ouro")
    assert needle in cannot_run(cfg, form)
    # one pass of the same block is a plain model and runs them all
    assert cannot_run(dataclasses.replace(cfg, total_ut_steps=1),
                      form) is None
    model = Transformer(cfg)
    if form in ("paged", "quantize_kv", "quantize_weights"):
        from orion_tpu.rollout import RolloutEngine

        with pytest.raises(ValueError, match="cannot run with rollout."
                           + form):
            RolloutEngine(model, cfg, RolloutConfig(**{form: True}))
    elif form == "continuous":
        from orion_tpu.rollout.continuous import ContinuousBatchingEngine

        with pytest.raises(ValueError, match="pass axis"):
            ContinuousBatchingEngine(model, cfg, RolloutConfig())
    elif form == "sequence_parallel":
        for impl in ("ring", "ulysses"):
            with pytest.raises(ValueError, match="scan over passes"):
                ModelConfig.tiny("ouro", attention_impl=impl)


def test_the_configuration_refuses_what_was_not_run():
    with pytest.raises(ValueError, match="only the published 1 runs"):
        ModelConfig.tiny("ouro", early_exit_threshold=0.9)
    with pytest.raises(ValueError, match="total_ut_steps >= 1"):
        ModelConfig.tiny("ouro", total_ut_steps=0)
    with pytest.raises(ValueError, match="only arch='ouro'"):
        ModelConfig.tiny("llama", total_ut_steps=2)
    with pytest.raises(ValueError, match="layer_types"):
        ModelConfig.tiny("ouro", layer_types=("sliding_attention",) * 3)
    with pytest.raises(ValueError, match="int8 Dense twin"):
        ModelConfig.tiny("ouro", quantize_dense=True)
    # a pipeline's stages would form a ring
    from jax.sharding import Mesh

    from orion_tpu.parallel.pipeline import PipelinedTransformer

    mesh = Mesh(np.array(jax.devices()[:1]), ("stage",))
    with pytest.raises(ValueError, match="stages form a ring"):
        PipelinedTransformer(ModelConfig.tiny("ouro", scan_layers=True),
                             mesh)
    # no checkpoint has been seen: import and export are refused by name
    from orion_tpu.models.hf_export import hf_state_dict
    from orion_tpu.models.hf_loader import (config_from_hf,
                                            convert_hf_state_dict)

    cfg = ModelConfig.tiny("ouro")
    with pytest.raises(ValueError, match="no ouro checkpoint loader"):
        convert_hf_state_dict({}, cfg)
    with pytest.raises(ValueError, match="no ouro checkpoint loader"):
        import types

        config_from_hf(types.SimpleNamespace(model_type="ouro"))
    with pytest.raises(ValueError, match="arch='ouro' is not written"):
        hf_state_dict({}, cfg)
    # the published preset is the catalog row's
    with open(os.path.join(REPO, "tests", "bench", "fixtures",
                           "ouro_catalog_row.json")) as f:
        row = json.load(f)["config"]
    mc = ModelConfig.ouro_2_6b()
    assert (mc.hidden_size, mc.num_layers, mc.num_heads, mc.num_kv_heads,
            mc.head_dim, mc.intermediate_size, mc.vocab_size,
            mc.rms_norm_eps, mc.rope_theta, mc.total_ut_steps,
            mc.early_exit_threshold, mc.max_seq_len,
            mc.tie_word_embeddings) == tuple(row[k] for k in (
                "hidden_size", "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "head_dim", "intermediate_size",
                "vocab_size", "rms_norm_eps", "rope_theta", "total_ut_steps",
                "early_exit_threshold", "max_position_embeddings",
                "tie_word_embeddings"))
    assert list(mc.layer_types) == row["layer_types"]
    assert mc.sandwich_norm and not mc.post_norm and mc.rms_norm
