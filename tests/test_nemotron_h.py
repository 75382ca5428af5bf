"""The ``nemotron_h`` model (Mamba-2 state-space layers beside relu^2
experts that work in a latent, one grouped-query layer in a period,
blocks with one half alone, a share of every mixer's heads) at the tiny
size against the plain reference ``benchmarks/reference_nemotron_h.py``
on seeded weights: the recurrence's three forms, the training forward
and its gradients, prefill of unequal prompts and decode through the
engine, the block with an absent half, the shares tied to the model, the
published pattern and its parameter counts, one PPO iteration through
the launcher, the refusals."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import ModelConfig, RolloutConfig
from orion_tpu.models.transformer import (Block, Mamba2, Attention,
                                          Transformer, init_cache,
                                          init_params, remat_tag_bytes)
from orion_tpu.ops.mamba2 import mamba2_chunked, mamba2_scan, mamba2_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "nemotron_test_" + name,
        os.path.join(REPO, "benchmarks", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference_nemotron_h")
chk = _load("reference_check_nemotron_h")
kimi_chk = _load("reference_check_kimi_linear")    # the layout reader


def _shape(cfg):
    """The configuration file's keys at a ModelConfig's sizes: the
    counts of heads are those HELD under ``cfg.head_share``."""
    held = cfg.heads_held()
    return dict(
        hybrid_override_pattern=cfg.hybrid_override_pattern,
        num_hidden_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
        layer_norm_epsilon=cfg.rms_norm_eps, vocab_size=cfg.vocab_size,
        mamba_num_heads=held["mamba"], mamba_head_dim=cfg.mamba_head_dim,
        n_groups=held["groups"], ssm_state_size=cfg.ssm_state_size,
        num_attention_heads=held["q"], num_key_value_heads=held["kv"],
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        num_experts_per_tok=cfg.num_experts_per_tok,
        routed_scaling_factor=cfg.routed_scaling_factor)


def _weights(params, cfg):
    """The program's tree as the reference takes it: one dict a
    PUBLISHED layer."""
    blocks = chk.blocks_of(cfg.hybrid_override_pattern[:cfg.num_layers])
    layers = []
    for i, (mixer, experts) in enumerate(blocks):
        p = kimi_chk.layer_tree(params, i, len(blocks))
        layers += [chk.layer_weights(p, c) for c in
                   ([mixer] if mixer else []) + (["E"] if experts else [])]
    return {"embed": params["embed"]["embedding"], "layers": layers,
            "nf_g": params["final_norm"]["scale"],
            "w_head": params["lm_head"]["kernel"]}


def _reference_logits(params, cfg, ids, n_real=None, **variant):
    mask = None if n_real is None else jnp.arange(ids.shape[0]) < n_real
    return ref.forward(_weights(params, cfg), ids, _shape(cfg),
                       (cfg.expert_offset, cfg.experts_held), mask,
                       **variant)


def _positions(ids):
    return jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)


@pytest.fixture(scope="module")
def tiny():
    """Two periods ("MEMEM*E" twice: ME, ME, M alone, *E, and again), so
    that stretches of each kind scan."""
    cfg = ModelConfig.tiny(
        "nemotron_h", dtype="float32", num_layers=14,
        hybrid_override_pattern="MEMEM*E" * 2)
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(0), cfg)
    ids = jax.random.randint(jax.random.key(1), (2, 80), 2, cfg.vocab_size)
    return cfg, model, params, ids


# ---------------------------------------------------------------------------
# the recurrence
# ---------------------------------------------------------------------------

def _ssm_inputs(L, seed=0, Bt=2, H=8, P=8, G=4, N=16):
    k = jax.random.split(jax.random.key(seed), 7)
    return dict(
        x=jax.random.normal(k[0], (Bt, L, H, P)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (Bt, L, H))),
        A=-jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.7)),
        B=jax.random.normal(k[3], (Bt, L, G, N)),
        C=jax.random.normal(k[4], (Bt, L, G, N)),
        D=jax.random.normal(k[5], (H,)),
        state=jax.random.normal(k[6], (Bt, H, P, N)))


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("L", [32, 37, 5])
def test_chunked_equals_token_scan_equals_reference(L, masked):
    """Values and gradients, for lengths that are and are not whole
    chunks (16), with and without positions that hold no token (dt = 0
    behind a row's real tokens)."""
    a = _ssm_inputs(L)
    if masked:
        real = jnp.arange(L)[None, :] < jnp.asarray([L, L // 2])[:, None]
        a["dt"] = jnp.where(real[..., None], a["dt"], 0.0)
    names = ("x", "dt", "A", "B", "C", "D", "state")

    def chunked(*t):
        return mamba2_chunked(*t, chunk=16)

    def reference(x, dt, A, B, C, D, state):
        return jax.vmap(lambda x, dt, B, C, S: ref.ssm_scan(
            x, dt, A, B, C, D, S))(x, dt, B, C, state)

    def loss(f):
        def of(*t):
            y, S = f(*t)
            return jnp.sum(y * jnp.cos(y)) + jnp.sum(jnp.square(S))
        return of

    args = tuple(a[n] for n in names)
    want_y, want_S = reference(*args)
    want_g = jax.grad(loss(reference), argnums=range(7))(*args)
    for f in (chunked, mamba2_scan):
        y, S = f(*args)
        top = float(jnp.max(jnp.abs(want_y)))
        np.testing.assert_allclose(y, want_y, atol=2e-5 * top, rtol=0)
        np.testing.assert_allclose(S, want_S, atol=2e-5 * top, rtol=0)
        got_g = jax.grad(loss(f), argnums=range(7))(*args)
        for name, g, w in zip(names, got_g, want_g):
            scale = float(jnp.max(jnp.abs(w)))
            np.testing.assert_allclose(g, w, atol=3e-5 * scale, rtol=0,
                                       err_msg=name)
    if masked:
        # the short row's state is the state after its last real token
        short = {n: (a[n][1:, :L // 2] if a[n].ndim > 1 and n != "state"
                     else a[n][1:] if n == "state" else a[n])
                 for n in names}
        _, S_short = mamba2_scan(*(short[n] for n in names))
        np.testing.assert_allclose(want_S[1], S_short[0], atol=1e-5, rtol=0)


def test_a_step_is_the_scan_of_one_token_and_groups_are_shared():
    a = _ssm_inputs(1)
    y, S = mamba2_step(a["x"][:, 0], a["dt"][:, 0], a["A"], a["B"][:, 0],
                       a["C"][:, 0], a["D"], a["state"])
    want_y, want_S = mamba2_scan(*(a[n] for n in
                                   ("x", "dt", "A", "B", "C", "D", "state")))
    np.testing.assert_allclose(y, want_y[:, 0], atol=1e-5, rtol=0)
    np.testing.assert_allclose(S, want_S, atol=1e-5, rtol=0)
    # head h reads group h // (H / G): heads 0 and 1 share B and C, and
    # taking them a head at a time (h % G) is another model
    other, _ = jax.vmap(lambda x, dt, B, C, S: ref.ssm_scan(
        x, dt, a["A"], B, C, a["D"], S, group_map="interleaved"))(
            a["x"], a["dt"], a["B"], a["C"], a["state"])
    assert float(jnp.max(jnp.abs(other - want_y))) > 0.1


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def test_the_pattern_and_the_parameter_counts_are_the_published_ones():
    pub = ModelConfig.nemotron_3_super_120b_a12b()
    assert len(pub.hybrid_override_pattern) == 88 == pub.num_layers
    chars = pub.hybrid_override_pattern
    assert (chars.count("M"), chars.count("E"), chars.count("*")) \
        == (40, 40, 8)
    kinds = pub.layer_kinds()
    # a mixer and the E behind it are one block; M before * stands alone
    assert len(kinds) == 48 and kinds.count(("mamba2", None)) == 8
    assert kinds.count(("attention", "experts")) == 8
    assert kinds.count(("mamba2", "experts")) == 32

    def count(cfg):
        shapes = jax.eval_shape(
            lambda: init_params(Transformer(cfg), jax.random.key(0), cfg))
        return sum(x.size for x in jax.tree.leaves(shapes))

    whole = count(pub)
    assert 118e9 < whole < 124e9, whole          # "120B"
    cut = dataclasses.replace(pub, num_layers=11, head_share=(0, 4),
                              experts_held=8, vocab_size=16384)
    assert cut.layer_kinds() == (("mamba2", "experts"),) * 3 + (
        ("mamba2", None), ("attention", "experts"), ("mamba2", "experts"))
    assert cut.heads_held() == {"q": 8, "kv": 1, "mamba": 32, "groups": 2}
    n = count(cut)
    assert abs(n - 773.6e6) / 773.6e6 < 0.005, n
    m = jax.eval_shape(lambda: init_params(
        Transformer(cut), jax.random.key(0), cut))["layers_0"]
    assert m["attn"]["in_proj"]["kernel"].shape == (4096, 4640)
    assert m["attn"]["out_proj"]["kernel"].shape == (2048, 4096)
    assert m["mlp"]["experts_up_proj"].shape == (8, 1024, 2688)
    assert m["mlp"]["router"].shape == (4096, 512)
    assert m["mlp"]["shared_up_proj"]["kernel"].shape == (4096, 5376)


def test_training_forward_matches_reference_float32(tiny):
    cfg, model, params, ids = tiny
    logits, _ = model.apply({"params": params}, ids, _positions(ids),
                            token_mask=_positions(ids) < 70)
    for b in range(2):
        want = _reference_logits(params, cfg, ids[b], n_real=70)
        np.testing.assert_allclose(logits[b, :70], want[:70], atol=5e-5,
                                   rtol=0)
    # and it is none of the models the reference check asks about
    for variant in chk.VARIANTS.values():
        other = _reference_logits(params, cfg, ids[0], n_real=70, **variant)
        assert float(jnp.max(jnp.abs(logits[0, :70] - other[:70]))) > 1e-3, \
            variant
    no_rope = _reference_logits(params, cfg, ids[0], n_real=70, rotary=False)
    assert float(jnp.max(jnp.abs(logits[0, :70] - no_rope[:70]))) > 1e-4


def test_loss_and_gradients_match_the_reference(tiny):
    _, _, _, ids = tiny
    cfg = ModelConfig.tiny("nemotron_h", dtype="float32")   # one period
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
        leaf = name.split("'")[-2]
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        if leaf == "e_score_correction_bias":
            assert float(jnp.max(jnp.abs(g))) == 0.0   # selection only
            continue
        seen.add(leaf)
        assert scale > 1e-9, name       # a gradient does reach it
        np.testing.assert_allclose(g, w, atol=3e-4 * scale + 1e-9,
                                   rtol=0, err_msg=name)
    assert {"A_log", "dt_bias", "D", "conv_weight", "conv_bias", "norm",
            "experts_up_proj", "router"} <= seen


def test_scanned_and_unrolled_layouts_agree(tiny):
    cfg, model, params, ids = tiny
    scfg = dataclasses.replace(cfg, scan_layers=True, remat=True)
    smodel = Transformer(scfg)
    sparams = init_params(smodel, jax.random.key(0), scfg)
    assert {"layers_0to1", "layers_2to2", "layers_3to3", "layers_4to5",
            "layers_6to6", "layers_7to7"} <= set(sparams)
    assert "mlp" not in sparams["layers_2to2"]        # M alone
    assert "post_attn_norm" not in sparams["layers_2to2"]
    want = _reference_logits(sparams, scfg, ids[0])
    got, _ = smodel.apply({"params": sparams}, ids[:1], _positions(ids[:1]))
    np.testing.assert_allclose(got[0], want, atol=5e-5, rtol=0)
    cache = init_cache(scfg, 2, 16)
    assert cache["dense"] == [] and len(cache["runs"]) == 6
    assert cache["runs"][0]["S"].shape == (2, 2, 8, 8, 16)
    assert cache["runs"][0]["S"].dtype == jnp.float32
    assert cache["runs"][0]["conv"].shape == (2, 2, 3, 8 * 8 + 2 * 4 * 16)
    assert cache["runs"][2]["k"].shape == (1, 2, 16, 2, 16)


def test_prefill_then_steps_equal_the_full_forward(tiny):
    cfg, model, params, ids = tiny
    B, P, steps = 2, 40, 6
    lens = jnp.asarray([P, 11])
    pos = _positions(ids)
    full, _ = model.apply({"params": params}, ids, pos,
                          token_mask=pos < (lens + steps)[:, None])
    cache = init_cache(cfg, B, P + steps)
    _, cache = model.apply({"params": params}, ids[:, :P], pos[:, :P], cache,
                           token_mask=pos[:, :P] < lens[:, None])
    for t in range(steps):
        at = (lens + t)[:, None]
        tok = jnp.take_along_axis(ids, at, axis=1)
        got, cache = model.apply({"params": params}, tok, at, cache)
        want = jnp.take_along_axis(full, at[..., None], axis=1)
        np.testing.assert_allclose(got, want, atol=3e-5, rtol=0)


def test_the_engine_decodes_through_state_and_per_head_cache(tiny):
    """``RolloutEngine``: a long and a short prompt in one right-padded
    batch; prefill hands decode the state, the convolution's last inputs
    and a per-head cache with each row's real length; the policy
    logprobs it recorded are the teacher-forced ones of the reference on
    what it sampled."""
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
    # what a decode step touches, from shapes: all three non-zero.  Two
    # periods: 6 Mamba-2 blocks, 2 attention blocks
    H, Pd, G, N = 8, 8, 4, 16
    sizes = eng.dispatch_attrs((2, P), lens)
    assert sizes["state_bytes"] == 6 * 2 * (
        H * Pd * N * 4 + 3 * (H * Pd + 2 * G * N) * 4)
    assert sizes["cache_bytes"] == 2 * 2 * (2 * (P + T) * 2 * 16 * 4)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    assert sizes["weight_bytes"] == 4 * n_params    # float32 at this size


@pytest.mark.parametrize("mixer,ffn", [("mamba2", None), (None, "experts"),
                                       ("attention", None)])
def test_a_block_with_an_absent_half(mixer, ffn):
    """``h + part(norm(h))`` once, not twice: the absent half adds
    nothing, has no parameters and (a mixer) caches nothing."""
    cfg = ModelConfig.tiny("nemotron_h", dtype="float32")
    block = Block(cfg, mixer=mixer, ffn=ffn)
    x = jax.random.normal(jax.random.key(0), (2, 12, cfg.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(12), (2, 12))
    params = block.init(jax.random.key(1), x, pos)["params"]
    assert set(params) == ({"input_norm", "attn"} if mixer
                           else {"post_attn_norm", "mlp"})
    y, cache = block.apply({"params": params}, x, pos)
    assert cache is None
    p = jax.tree.map(lambda t: t.value if hasattr(t, "value") else t, params,
                     is_leaf=lambda t: hasattr(t, "value"))
    shape = _shape(cfg)
    char = {"mamba2": "M", "attention": "*", None: "E"}[mixer]
    for b in range(2):
        want = ref.layer(x[b], chk.layer_weights(p, char), shape, char,
                         (0, cfg.experts_held))
        np.testing.assert_allclose(y[b], want, atol=2e-5, rtol=0)
    if mixer is None:
        _, cache = block.apply({"params": params}, x, pos, {})
        assert cache == {}


def test_an_expert_layer_alone_in_the_pattern():
    """"EE": the second E is a block without a mixer, whose cache entry
    is empty, through the model and its cache."""
    cfg = ModelConfig.tiny("nemotron_h", dtype="float32", num_layers=4,
                           hybrid_override_pattern="MEE*")
    assert cfg.layer_kinds() == (("mamba2", "experts"), (None, "experts"),
                                 ("attention", None))
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(0), cfg)
    ids = jax.random.randint(jax.random.key(1), (1, 20), 2, 256)
    got, _ = model.apply({"params": params}, ids, _positions(ids))
    np.testing.assert_allclose(got[0], _reference_logits(params, cfg, ids[0]),
                               atol=5e-5, rtol=0)
    cache = init_cache(cfg, 1, 24)
    assert cache[1] == {}
    _, cache = model.apply({"params": params}, ids[:, :16],
                           _positions(ids[:, :16]), cache)
    step, cache = model.apply({"params": params}, ids[:, 16:17],
                              jnp.asarray([[16]]), cache)
    np.testing.assert_allclose(step[0, 0], got[0, 16], atol=3e-5, rtol=0)


# ---------------------------------------------------------------------------
# the share, tied to the model
# ---------------------------------------------------------------------------

def _cols(w, parts, which, of):
    """Share ``which`` of ``of`` of each of the consecutive column
    ``parts`` (widths) of ``w``'s last axis."""
    out, start = [], 0
    for width in parts:
        step = width // of
        out.append(w[..., start + which * step:start + (which + 1) * step])
        start += width
    return jnp.concatenate(out, axis=-1)


def _mamba_share(p, cfg, which, of):
    H, P, G, N = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                  cfg.mamba_n_groups, cfg.ssm_state_size)
    d_in = H * P
    rows = slice(which * d_in // of, (which + 1) * d_in // of)
    heads = slice(which * H // of, (which + 1) * H // of)
    return {
        "in_proj": {"kernel": _cols(p["in_proj"]["kernel"],
                                    (d_in, d_in, G * N, G * N, H), which, of)},
        "conv_weight": _cols(p["conv_weight"], (d_in, G * N, G * N), which,
                             of),
        "conv_bias": _cols(p["conv_bias"], (d_in, G * N, G * N), which, of),
        "A_log": p["A_log"][heads], "D": p["D"][heads],
        "dt_bias": p["dt_bias"][heads], "norm": p["norm"][rows],
        "out_proj": {"kernel": p["out_proj"]["kernel"][rows]}}


def _attention_share(p, cfg, which, of):
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = slice(which * Hq * D // of, (which + 1) * Hq * D // of)
    # a share's query heads read key-value head (first query head) //
    # (Hq / Hkv): one repeated where the shares outnumber them
    n_kv = max(1, Hkv // of)
    first = (which * Hq // of) // (Hq // Hkv)
    kv = slice(first * D, (first + n_kv) * D)
    return {"q_proj": {"kernel": p["q_proj"]["kernel"][:, q]},
            "k_proj": {"kernel": p["k_proj"]["kernel"][:, kv]},
            "v_proj": {"kernel": p["v_proj"]["kernel"][:, kv]},
            "o_proj": {"kernel": p["o_proj"]["kernel"][q]}}


@pytest.mark.parametrize("of", [2, 4])
@pytest.mark.parametrize("mixer", ["mamba2", "attention"])
def test_the_head_shares_mixer_outputs_add_up_to_the_uncut_mixers(mixer, of):
    """Mamba-2: 8 heads in 4 groups, the groups follow the heads;
    attention: 4 query heads against 2 key-value heads, one repeated
    under 4 shares.  The uncut mixer is the program's own and the
    reference's."""
    cfg = ModelConfig.tiny("nemotron_h", dtype="float32")
    cls, cut = (Mamba2, _mamba_share) if mixer == "mamba2" \
        else (Attention, _attention_share)
    x = jax.random.normal(jax.random.key(0), (2, 24, cfg.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    whole = cls(cfg)
    params = jax.tree.map(
        lambda t: t.value if hasattr(t, "value") else t,
        whole.init(jax.random.key(1), x, pos)["params"],
        is_leaf=lambda t: hasattr(t, "value"))
    want, _ = whole.apply({"params": params}, x, pos)
    w = chk.layer_weights({"input_norm": {"scale": jnp.ones(())},
                           "attn": params},
                          "M" if mixer == "mamba2" else "*")
    for b in range(2):
        with jax.default_matmul_precision("highest"):
            uncut = ref.mamba2(x[b], w, _shape(cfg), jnp.ones((24,), bool)) \
                if mixer == "mamba2" else ref.attention(x[b], w, _shape(cfg))
        np.testing.assert_allclose(want[b], uncut, atol=2e-5, rtol=0)
    total = 0.0
    for which in range(of):
        scfg = dataclasses.replace(cfg, head_share=(which, of))
        part, _ = cls(scfg).apply(
            {"params": cut(params, cfg, which, of)}, x, pos)
        total = total + part
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=0)


def test_the_expert_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """The routed parts of all expert shares + the shared expert counted
    once + nothing else = the uncut reference's layer."""
    from orion_tpu.ops.moe import TopKMoE

    cfg = ModelConfig.tiny("nemotron_h", dtype="float32")
    x = jax.random.normal(jax.random.key(0), (2, 24, cfg.hidden_size))
    whole = TopKMoE(cfg)
    params = jax.tree.map(
        lambda t: t.value if hasattr(t, "value") else t,
        whole.init(jax.random.key(1), x)["params"],
        is_leaf=lambda t: hasattr(t, "value"))
    w = chk.layer_weights({"post_attn_norm": {"scale": jnp.ones(())},
                           "mlp": params}, "E")
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([ref.latent_moe(
            x[b], w, _shape(cfg), (0, cfg.n_routed_experts))
            for b in range(2)])
        shared = jnp.stack([ref.ACTS["relu2"](x[b] @ w["s_up"]) @ w["s_down"]
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
            params, experts_up_proj=params["experts_up_proj"][own],
            experts_down_proj=params["experts_down_proj"][own])}, x)
        total = total + (part - shared)       # a share's routed part
    np.testing.assert_allclose(total + shared, uncut, atol=3e-5, rtol=0)


# ---------------------------------------------------------------------------
# the trainer
# ---------------------------------------------------------------------------

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
            "ppo", "model_preset=tiny_nemotron_h", "model.head_share=1,2",
            "model.experts_held=4", "model.expert_offset=4",
            "model.remat=true", "model.scan_layers=true",
            "share_backbone=true", "model.max_seq_len=24",
            "rollout.max_prompt_len=16", "rollout.max_new_tokens=8",
            "rollout_batch_size=4", "minibatch_size=2", "num_epochs=1",
            "data.dataset=synthetic", "reward=length", "total_iterations=2",
            "optimizer.learning_rate=1e-3", "ref_param_dtype=bfloat16",
            "optimizer.mu_dtype=bfloat16", "optimizer.nu_dtype=bfloat16",
            f"log_dir={tmp_path}"])
    finally:
        launch.build_trainer = real
    assert len(hist) == 2 and all(np.isfinite(r["loss"]) for r in hist)
    # no delta-rule layer: the row names no form of one
    assert hist[-1]["kda_chunk"] == ""
    assert hist[-1]["moe_pairs_total"] > 0
    before = kept["before"]["backbone"]
    after = kept["trainer"].state.params["backbone"]
    for name in ("A_log", "dt_bias", "D", "conv_weight", "norm"):
        moved = np.max(np.abs(np.asarray(
            after["layers_0to1"]["attn"][name])
            - before["layers_0to1"]["attn"][name]))
        assert moved > 0, name
    # half the heads, half the experts
    assert after["layers_0to1"]["attn"]["A_log"].shape == (2, 4)
    assert after["layers_0to1"]["mlp"]["experts_up_proj"].shape[:2] == (2, 4)
    trainer = kept["trainer"]
    sizes = trainer.engine.dispatch_attrs((4, 16), [16] * 4,
                                          trainer.state.params)
    assert sizes["state_bytes"] > 0 and sizes["cache_bytes"] > 0 \
        and sizes["weight_bytes"] > 0
    assert sizes["kda_step"] == ""
    from orion_tpu.models.transformer import update_attrs

    def share_counters(cfg_model):
        return {k: v for k, v in update_attrs(cfg_model, [16] * 4).items()
                if k.endswith("_held")}

    assert share_counters(trainer.cfg.model) == {
        "heads_held": 4, "groups_held": 2, "attn_heads_held": 2,
        "kv_heads_held": 1, "experts_held": 4}
    assert share_counters(ModelConfig.tiny("deepseek_v3")) == {}


def test_remat_tags_count_the_new_mixer_and_the_absent_halves():
    cfg = ModelConfig.tiny("nemotron_h")
    tags = dict(remat_tag_bytes(cfg, rows=2, seq_len=64))
    n, act = 2 * 64, 2
    wide = 2 * 64 + 2 * 4 * 16 + 8            # z | x | B | C | dt
    assert tags["attn_qkv"] == n * act * (3 * wide + (4 + 2 * 2) * 16)
    assert tags["attn_out"] == 3 * n * 64 * 4 + (
        n * 4 * 16 * act + 2 * 4 * 64 * 4)
    # M alone has no second half to rebuild an input for; relu^2 has no
    # gate: one product of the shared expert's own width a layer
    assert tags["attn_resid"] == n * 3 * cfg.hidden_size * act
    assert tags["mlp_pre"] == n * 3 * 80 * act
    half = dict(remat_tag_bytes(dataclasses.replace(cfg, head_share=(0, 2)),
                                rows=2, seq_len=64))
    assert half["attn_qkv"] == n * act * (3 * wide // 2 + (2 + 2 * 1) * 16)


# ---------------------------------------------------------------------------
# refusals
# ---------------------------------------------------------------------------

def _refusals():
    from orion_tpu.models.hf_export import hf_state_dict
    from orion_tpu.models.hf_loader import (config_from_hf,
                                            convert_hf_state_dict)
    from orion_tpu.rollout import RolloutEngine
    from orion_tpu.rollout.continuous import ContinuousBatchingEngine

    cfg = ModelConfig.tiny("nemotron_h")
    model = Transformer(cfg)

    def engine(**kw):
        return lambda: RolloutEngine(model, cfg, RolloutConfig(**kw))

    def tiny(**kw):
        return lambda: ModelConfig.tiny("nemotron_h", **kw)

    class HF:
        model_type = "nemotron_h"

    return {
        "continuous": (lambda: ContinuousBatchingEngine(
            model, cfg, RolloutConfig()), "state-space layer's {S, conv}"),
        "paged": (engine(paged=True), "not made of pages"),
        "quantize_kv": (engine(quantize_kv=True),
                        "int8 form of a float32 recurrent state"),
        "quantize_weights": (engine(quantize_weights=True),
                             "experts without a gate"),
        "speculative": (engine(speculative_k=2),
                        "rollout.engine=continuous"),
        "ring": (tiny(attention_impl="ring"),
                 "state-space layer's state between sequence shards"),
        "ulysses": (tiny(attention_impl="ulysses"),
                    "state-space layer's state between sequence shards"),
        "hf_import": (lambda: convert_hf_state_dict({}, cfg),
                      "no nemotron_h checkpoint loader"),
        "hf_config": (lambda: config_from_hf(HF()),
                      "no nemotron_h checkpoint loader"),
        "hf_export": (lambda: hf_state_dict(
            init_params(model, jax.random.key(0), cfg), cfg),
            "no nemotron_h checkpoint layout"),
        "int8_cache": (lambda: init_cache(cfg, 1, 8, quantized=True),
                       "recurrent state has no int8 form"),
        "dense_mlp": (tiny(hybrid_override_pattern="ME-ME*E"),
                      "a dense MLP alone, is not written"),
        "short_pattern": (tiny(hybrid_override_pattern="ME"),
                          "at least num_layers=7"),
        "part_of_a_group": (tiny(head_share=(0, 8)),
                            "whole Mamba-2 groups"),
        "which_share": (tiny(head_share=(2, 2)), "which share, of how many"),
        "share_elsewhere": (lambda: ModelConfig.tiny(
            "llama", head_share=(0, 2)), "only arch='nemotron_h'"),
        "activation": (tiny(moe_activation="gelu"), "'swiglu' or 'relu2'"),
        "quantize_dense": (tiny(quantize_dense=True), "no int8 Dense twin"),
    }


@pytest.mark.parametrize("path", [
    "continuous", "paged", "quantize_kv", "quantize_weights", "speculative",
    "ring", "ulysses", "hf_import", "hf_config", "hf_export", "int8_cache",
    "dense_mlp", "short_pattern", "part_of_a_group", "which_share",
    "share_elsewhere", "activation", "quantize_dense"])
def test_paths_that_cannot_run_it_name_the_missing_mechanism(path):
    call, words = _refusals()[path]
    with pytest.raises(ValueError, match=words):
        call()
