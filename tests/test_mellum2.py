"""The ``mellum`` model (three sliding-window layers of 8 keys and plain
rotary to one full layer under YaRN, softmax-routed experts) at the tiny
size against the plain reference ``benchmarks/reference_mellum2.py`` on
seeded weights: the training forward, its loss and gradients, the two
layouts, prefill of ragged prompts and decode through the ring and the
full cache (and through the engine), the ring against a full-size cache
under the window mask, the rotary tables, the windowed flash kernels
against the XLA mask with the tiles they visit, ``window=None`` as the
parent's program, the expert shares tied to the model, one PPO iteration
through the launcher, the refusals."""

import dataclasses
import hashlib
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import ModelConfig, RolloutConfig
from orion_tpu.models import transformer
from orion_tpu.models.transformer import (MIXERS, Attention, Transformer,
                                          WindowAttention, cannot_run,
                                          decode_attrs, init_cache,
                                          init_params, remat_tag_bytes,
                                          update_attrs)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG_ROW = os.path.join(REPO, "tests", "bench", "fixtures",
                           "mellum2_catalog_row.json")
FLASH_FIXTURE = os.path.join(REPO, "tests", "fixtures",
                             "flash_no_window_jaxprs.json")
SLIDING, FULL = "sliding_attention", "full_attention"


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "mellum2_test_" + name,
        os.path.join(REPO, "benchmarks", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference_mellum2")
chk = _load("reference_check_mellum2")
kimi_chk = _load("reference_check_kimi_linear")    # the layout reader


def _shape(cfg):
    """The configuration file's keys at a ModelConfig's sizes:
    ``num_experts`` counts the experts HELD."""
    names = {"window": SLIDING, "attention": FULL}
    return dict(
        layer_types=[names[m] for m, _ in cfg.layer_kinds()],
        num_hidden_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
        rms_norm_eps=cfg.rms_norm_eps, vocab_size=cfg.vocab_size,
        head_dim=cfg.head_dim, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads,
        sliding_window=cfg.sliding_window,
        rope_parameters=cfg.rope_parameters,
        num_experts=cfg.experts_held, expert_offset=cfg.expert_offset,
        num_experts_per_tok=cfg.num_experts_per_tok,
        moe_intermediate_size=cfg.moe_intermediate_size,
        source_values={"num_experts": cfg.n_routed_experts})


def _weights(params, cfg):
    """The program's tree as the reference takes it: one dict a layer."""
    return {"embed": params["embed"]["embedding"],
            "layers": [chk.layer_weights(
                kimi_chk.layer_tree(params, i, cfg.num_layers))
                for i in range(cfg.num_layers)],
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
    """Two periods S S S F, a window of 8, sequences of 40: several
    windows pass, and YaRN's ramp (16 original positions) is crossed."""
    cfg = ModelConfig.tiny("mellum", dtype="float32")
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(0), cfg)
    ids = jax.random.randint(jax.random.key(1), (2, 40), 2, cfg.vocab_size)
    return cfg, model, params, ids


def _count(cfg):
    shapes = jax.eval_shape(
        lambda: init_params(Transformer(cfg), jax.random.key(0), cfg))
    return sum(x.size for x in jax.tree.leaves(shapes))


# ---------------------------------------------------------------------------
# the published model and its cut
# ---------------------------------------------------------------------------

def test_the_preset_is_the_catalog_row_and_the_cut_is_the_issues():
    row = json.load(open(CATALOG_ROW))
    pub, c = ModelConfig.mellum2_12b_a2_5b(), row["config"]
    assert row["name"] == "Mellum2-12B-A2.5B-Instruct"
    assert c["model_type"] == pub.arch == "mellum"
    names = {"window": SLIDING, "attention": FULL}
    assert [names[m] for m, _ in pub.layer_kinds()] == c["layer_types"]
    assert {f for _, f in pub.layer_kinds()} == {"experts"}
    assert set(c["mlp_layer_types"]) == {"sparse"}
    assert (pub.head_dim, pub.hidden_size, pub.intermediate_size,
            pub.max_seq_len, pub.moe_intermediate_size, pub.rms_norm_eps,
            pub.num_heads, pub.n_routed_experts, pub.num_experts_per_tok,
            pub.num_layers, pub.num_kv_heads, pub.sliding_window,
            pub.vocab_size, pub.tie_word_embeddings, pub.attn_bias) == tuple(
        c[k] for k in ("head_dim", "hidden_size", "intermediate_size",
                       "max_position_embeddings", "moe_intermediate_size",
                       "rms_norm_eps", "num_attention_heads", "num_experts",
                       "num_experts_per_tok", "num_hidden_layers",
                       "num_key_value_heads", "sliding_window", "vocab_size",
                       "tie_word_embeddings", "attention_bias"))
    assert pub.rope_parameters == c["rope_parameters"]
    assert c["norm_topk_prob"] and pub.moe_scoring == "softmax"
    assert pub.n_shared_experts == 0 and pub.routed_scaling_factor == 1.0
    assert pub.attn_heads_a_step() == 8
    kinds = [m for m, _ in pub.layer_kinds()]
    assert (kinds.count("window"), kinds.count("attention")) == (21, 7)
    # 12.1 B whole; the cut: layers 0-7, 8 of 64 experts, an eighth of
    # the vocabulary: 624 M, 10.0 GB at 16 bytes a parameter
    whole = _count(pub)
    assert abs(whole - 12.1e9) / 12.1e9 < 0.01, whole
    cut = dataclasses.replace(pub, num_layers=8, experts_held=8,
                              expert_offset=0, vocab_size=12288)
    assert [m for m, _ in cut.layer_kinds()] == (
        ["window"] * 3 + ["attention"]) * 2
    assert cut.layer_runs() == (
        (0, 3, "window", "experts"), (3, 1, "attention", "experts"),
        (4, 3, "window", "experts"), (7, 1, "attention", "experts"))
    held = _count(cut)
    assert abs(held - 624e6) / 624e6 < 0.002, held
    assert 9.9e9 < 16 * held < 10.1e9


# ---------------------------------------------------------------------------
# against the reference
# ---------------------------------------------------------------------------

def test_training_forward_matches_reference_float32(tiny):
    cfg, model, params, ids = tiny
    logits, _ = model.apply({"params": params}, ids, _positions(ids),
                            token_mask=_positions(ids) < 36)
    for b in range(2):
        want = _reference_logits(params, cfg, ids[b], n_real=36)
        np.testing.assert_allclose(logits[b, :36], want[:36], atol=5e-5,
                                   rtol=0)
    # and it is none of the models the reference check asks about, nor
    # the one whose window is a key longer or shorter
    edge = {"one_key_more": {"window_keys": cfg.sliding_window + 1},
            "one_key_fewer": {"window_keys": cfg.sliding_window - 1}}
    for name, variant in {**chk.VARIANTS, **edge}.items():
        other = _reference_logits(params, cfg, ids[0], n_real=36, **variant)
        assert float(jnp.max(jnp.abs(logits[0, :36] - other[:36]))) > 1e-4, \
            name


def test_loss_and_gradients_match_the_reference(tiny):
    cfg, model, params, ids = tiny
    ids = ids[:1]

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
        seen.add(name.split("'")[-2])
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        assert scale > 1e-9, name       # a gradient does reach it
        np.testing.assert_allclose(g, w, atol=3e-4 * scale + 1e-9,
                                   rtol=0, err_msg=name)
    assert {"embedding", "router", "experts_gate_up_proj", "scale",
            "kernel"} <= seen


def test_scanned_and_unrolled_layouts_agree(tiny):
    cfg, model, params, ids = tiny
    scfg = dataclasses.replace(cfg, scan_layers=True, remat=True)
    smodel = Transformer(scfg)
    sparams = init_params(smodel, jax.random.key(0), scfg)
    # runs of 3 and of 1, a stack each
    assert {"layers_0to2", "layers_3to3", "layers_4to6", "layers_7to7"} \
        <= set(sparams)
    want = _reference_logits(sparams, scfg, ids[0])
    got, _ = smodel.apply({"params": sparams}, ids[:1], _positions(ids[:1]))
    np.testing.assert_allclose(got[0], want, atol=5e-5, rtol=0)
    # caches of two shapes side by side: rings of 8 slots, full of 24
    cache = init_cache(scfg, 2, 24)
    assert cache["dense"] == [] and len(cache["runs"]) == 4
    assert [r["k"].shape for r in cache["runs"]] == [
        (3, 2, 8, 2, 16), (1, 2, 24, 2, 16)] * 2
    # a cache shorter than the window is the cache itself
    short = init_cache(cfg, 2, 5)
    assert {c["k"].shape[1] for c in short} == {8}


def _prefill_and_steps(model, cfg, params, seqs, lens, P, T, cache):
    """Logits of the prompts' last tokens and of ``T - 1`` one-token
    steps, teacher-forced on ``seqs`` [B, P + T]: [B, T, V]."""
    B = seqs.shape[0]
    p0 = jnp.broadcast_to(jnp.arange(P), (B, P))
    real = p0 < lens[:, None]
    lg, cache = model.apply(
        {"params": params}, jnp.where(real, seqs[:, :P], 0), p0, cache,
        token_mask=real, logits_positions=(lens - 1)[:, None])
    out = [lg[:, 0]]
    step = jax.jit(lambda tok, at, cache: model.apply(
        {"params": params}, tok[:, None], at[:, None], cache))
    for t in range(T - 1):
        at = lens + t
        lg, cache = step(seqs[jnp.arange(B), at], at, cache)
        out.append(lg[:, 0])
    return jnp.stack(out, axis=1)


def _ragged(cfg, P, T):
    """Rows whose real prompts are shorter than the window, a little
    longer and much longer, each followed by its own T tokens."""
    lens = jnp.asarray([5, 11, P])
    ids = jax.random.randint(jax.random.key(3), (3, P + T), 2,
                             cfg.vocab_size)
    return ids, lens


def test_prefill_then_steps_through_both_caches_equal_the_reference(tiny):
    """Ragged real lengths below AND above the window of 8 in one
    right-padded batch, then 20 steps: the ring wraps more than twice.
    Logits, not tokens.  (The scanned layout's caches of two shapes go
    through the launcher's test and the engine's decode twin.)"""
    cfg, model, params, _ = tiny
    P, T = 24, 20
    ids, lens = _ragged(cfg, P, T)
    got = _prefill_and_steps(model, cfg, params, ids, lens, P, T,
                             init_cache(cfg, 3, P + T))
    for b in range(3):
        n = int(lens[b])
        # the row's own sequence: its real prompt, then its T tokens
        want = _reference_logits(params, cfg, ids[b, :n + T])
        np.testing.assert_allclose(got[b], want[n - 1:n - 1 + T], atol=5e-5,
                                   rtol=0, err_msg=str(b))


def test_the_ring_equals_a_full_size_cache_under_the_window_mask(
        tiny, monkeypatch):
    """The same model keeping every position of a sliding layer and
    masking by the window gives the same logits within float rounding:
    what the ring leaves out no query sees."""
    cfg, model, params, _ = tiny
    P, T = 24, 20
    ids, lens = _ragged(cfg, P, T)
    ids = ids.at[jnp.arange(3), lens + T - 1].set(3)    # any token
    ring = _prefill_and_steps(model, cfg, params, ids, lens, P, T,
                              init_cache(cfg, 3, P + T))

    class Kept(WindowAttention):
        """Slot == position in a cache of every slot, the step masked
        by the window: no ring."""

        @classmethod
        def ring_slots(cls, cfg, slots):
            return slots

        def _attend(self, x, positions, layer_cache=None, visible=None,
                    token_mask=None):
            if layer_cache is None or x.shape[1] > 1:
                return super()._attend(x, positions, layer_cache, visible,
                                       token_mask)
            q, k, v = self.qkv(x, positions)
            B = x.shape[0]
            new = {n: c.at[jnp.arange(B), positions[:, 0]].set(t[:, 0])
                   for n, c, t in (("k", layer_cache["k"], k),
                                   ("v", layer_cache["v"], v))}
            from orion_tpu.ops.attention import (positional_mask,
                                                 reference_attention_gqa)
            out = reference_attention_gqa(
                q, new["k"], new["v"], positional_mask(
                    positions, new["k"].shape[1], self.window(self.cfg)),
                self.cfg.head_dim ** -0.5)
            from orion_tpu.models.transformer import _dense
            return _dense(self.cfg.hidden_size, ("heads", "embed"), False,
                          self.cfg, "o_proj")(out.reshape(B, 1, -1)), new

    monkeypatch.setitem(MIXERS, "window", Kept)
    cache = init_cache(cfg, 3, P + T)
    assert {c["k"].shape[1] for c in cache} == {48}      # 44 in whole 8s
    kept = _prefill_and_steps(model, cfg, params, ids, lens, P, T, cache)
    np.testing.assert_allclose(ring, kept, atol=2e-5, rtol=0)


def test_the_engine_decodes_through_the_ring_and_the_full_cache(tiny):
    from orion_tpu.rollout import RolloutEngine

    cfg, model, params, _ = tiny
    P, T = 24, 20
    eng = RolloutEngine(model, cfg, RolloutConfig(
        max_prompt_len=P, max_new_tokens=T, temperature=1.0))
    ids, lens = _ragged(cfg, P, T)
    prompts = jnp.where(jnp.arange(P)[None] < lens[:, None], ids[:, :P], 0)
    out = eng.generate(prompts, lens, jax.random.key(5), params=params)
    assert int(jnp.min(out.completion_lens)) == T
    for b in range(3):
        n = int(lens[b])
        want = jax.nn.log_softmax(_reference_logits(
            params, cfg, out.sequences[b, :n + T]), axis=-1)
        picked = jnp.take_along_axis(
            want[n - 1:n - 1 + T], out.completions[b][:, None], axis=-1)
        np.testing.assert_allclose(out.policy_logprobs[b], picked[:, 0],
                                   atol=5e-5, rtol=0)
    attrs = eng.dispatch_attrs((3, P), np.asarray(lens), params)
    row = 3 * 2 * 2 * 16 * 4
    assert (attrs["window_layers"], attrs["full_layers"],
            attrs["window_slots"]) == (6, 2, 8)
    assert attrs["ring_cache_bytes"] == 6 * 8 * row
    assert attrs["full_cache_bytes"] == 2 * 48 * row
    assert attrs["cache_bytes"] == attrs["ring_cache_bytes"] \
        + attrs["full_cache_bytes"]


# ---------------------------------------------------------------------------
# the rotary tables
# ---------------------------------------------------------------------------

def test_the_yarn_table_is_the_formulas_and_the_default_is_as_it_was():
    from orion_tpu.ops.rotary import apply_rotary, rope_cos_sin, rope_table

    pub = ModelConfig.mellum2_12b_a2_5b()
    full = pub.rope_parameters[FULL]
    inv_freq, factor = rope_table(128, pub.rope_theta, full)
    assert factor == 1.2772588722239782 == 0.1 * math.log(16) + 1

    def corr(n):
        return 128 * math.log(8192 / (2 * math.pi * n)) / (
            2 * math.log(500000))

    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (18, 35)
    # the formula's numbers written out: unchanged up to low, a sixteenth
    # from high on, the ramp between
    for i, ramp in ((0, 0.0), (low, 0.0), (27, 9 / 17), (high, 1.0),
                    (63, 1.0)):
        pos = 500000.0 ** (2 * i / 128)
        want = ramp / (16 * pos) + (1 - ramp) / pos
        assert float(inv_freq[i]) == pytest.approx(want, rel=2e-6), i
    assert float(inv_freq[0]) == 1.0
    assert float(inv_freq[63]) == pytest.approx(
        500000.0 ** (-126 / 128) / 16, rel=2e-6)
    ref_freq, ref_factor = ref.rope_table(full, 128)
    np.testing.assert_allclose(inv_freq, ref_freq, rtol=1e-6)
    assert ref_factor == factor
    # the default table, bit for bit what rope_cos_sin gave before: with
    # no entry, with a default entry, and through apply_rotary
    pos = jnp.arange(0, 8192, 37, dtype=jnp.int32)[None]
    inv = 1.0 / (500000.0 ** (jnp.arange(0, 128, 2, dtype=jnp.float32)
                              / 128))
    ang = pos.astype(jnp.float32)[..., None] * inv
    emb = jnp.concatenate([ang, ang], axis=-1)
    for params in (None, pub.rope_parameters[SLIDING]):
        assert rope_table(128, 500000.0, params) == (None, 1.0)
    cos, sin = rope_cos_sin(pos, 128, 500000.0)
    assert bool(jnp.all(cos == jnp.cos(emb)) & jnp.all(sin == jnp.sin(emb)))
    q = jax.random.normal(jax.random.key(0), (1, pos.shape[1], 2, 128))
    plain = apply_rotary(q, q, pos, 128, 500000.0)
    entry = apply_rotary(q, q, pos, 128, 1e4, pub.rope_parameters[SLIDING])
    assert bool(jnp.all(plain[0] == entry[0]))
    # under YaRN cos and sin carry the factor
    cos_y, _ = rope_cos_sin(pos, 128, 500000.0, inv_freq, factor)
    np.testing.assert_allclose(cos_y[0, :, 0], factor * jnp.cos(
        pos[0].astype(jnp.float32)), rtol=1e-6)
    # the layers take their own entries
    assert (WindowAttention.layer_type, Attention.layer_type) \
        == (SLIDING, FULL)


# ---------------------------------------------------------------------------
# the windowed flash kernels
# ---------------------------------------------------------------------------

def _tiles_left(L, tile, window):
    """(q tile, kv tile) pairs that hold a query and a key it sees."""
    n = L // tile
    return sum(1 for i in range(n) for j in range(n)
               if j * tile <= i * tile + tile - 1
               and i * tile - (j * tile + tile - 1) < window)


@pytest.mark.parametrize("shape,window", [
    ((2, 512, 4, 2, 16), 200),      # a group, a window across tiles
    ((1, 512, 2, 2, 16), 128),      # one head a key head, a tile wide
    ((1, 512, 4, 1, 16), 1000),     # never binds: every causal tile
])
def test_windowed_flash_kernels_equal_the_mask_and_visit_its_tiles(
        shape, window, monkeypatch):
    from orion_tpu.ops.attention import (positional_mask,
                                         reference_attention_gqa)
    from orion_tpu.ops.pallas import flash_attention as fa

    B, L, H, Hkv, D = shape
    monkeypatch.setattr(fa, "_MAJOR", 256)      # several major blocks
    visited = {"fwd": 0, "bwd": 0}
    init = fa._TileMask.__init__

    def counting(self, seen, bias_ref, fill, at):
        init(self, seen, bias_ref, fill, at)
        rows, cols = at
        # forward and dq: a run of kv tiles (rows); dkv: of q tiles
        wide = rows if rows.stop is not None else cols
        which = "fwd" if fill == fa.NEG_INF else "bwd"
        jax.debug.callback(
            lambda: visited.__setitem__(
                which, visited[which] + (wide.stop - wide.start) // 128))

    monkeypatch.setattr(fa._TileMask, "__init__", counting)
    ks = jax.random.split(jax.random.key(0), 4)
    q, do = (jax.random.normal(k, (B, L, H, D)) for k in ks[:2])
    k, v = (jax.random.normal(k_, (B, L, Hkv, D)) for k_ in ks[2:])
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    mask = positional_mask(pos, L, window)
    assert int(mask[0, 300].sum()) == min(301, window)

    def kernel(q, k, v):
        return fa.flash_attention_gqa(q, k, v, pos, 0.25, 128, 128, window)

    def plain(q, k, v):
        return reference_attention_gqa(q, k, v, mask, 0.25)

    out = jax.block_until_ready(kernel(q, k, v))
    jax.effects_barrier()
    np.testing.assert_allclose(out, plain(q, k, v), atol=2e-6, rtol=0)
    left = _tiles_left(L, 128, window)
    assert visited == {"fwd": B * Hkv * left, "bwd": 0}
    causal = _tiles_left(L, 128, L)
    assert left < causal or window >= L
    visited["fwd"] = 0
    got = jax.block_until_ready(jax.grad(
        lambda *a: (kernel(*a) * do).sum(), (0, 1, 2))(q, k, v))
    jax.effects_barrier()
    want = jax.grad(lambda *a: (plain(*a) * do).sum(), (0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=0, err_msg=name)
    # the forward again, then dq and dkv once each over the same tiles
    assert visited == {"fwd": B * Hkv * left, "bwd": 2 * B * Hkv * left}


def _flash_jaxprs():
    """The jaxprs of three existing callers' forward and gradient
    (grouped heads, one head a key head, one tile), printed."""
    from orion_tpu.ops.pallas.flash_attention import flash_attention_gqa

    out = {}
    for name, (B, L, H, Hkv, D) in {"gqa": (2, 256, 4, 2, 16),
                                    "mha": (1, 256, 2, 2, 16),
                                    "one": (2, 64, 4, 2, 16)}.items():
        q = jnp.zeros((B, L, H, D), jnp.float32)
        k = jnp.zeros((B, L, Hkv, D), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))

        def f(q, k, v):
            return flash_attention_gqa(q, k, v, pos, 0.25, 128, 128).sum()

        out[name + "_fwd"] = str(jax.make_jaxpr(f)(q, k, k))
        out[name + "_grad"] = str(jax.make_jaxpr(
            jax.grad(f, argnums=(0, 1, 2)))(q, k, k))
    return out


def test_without_a_window_the_kernels_are_the_parents_program():
    """``tests/fixtures/flash_no_window_jaxprs.json`` holds the digests
    of :func:`_flash_jaxprs` at the commit before the window (PR 52's
    tree: grids, index maps and kernel bodies, operation by operation);
    a windowed call differs, and names itself in the trace."""
    recorded = json.load(open(FLASH_FIXTURE))
    texts = _flash_jaxprs()
    got = {k: [len(v), hashlib.sha256(v.encode()).hexdigest()]
           for k, v in texts.items()}
    assert got == recorded["digests"], {
        k for k in got if got[k] != recorded["digests"][k]}
    assert "flash_fwd" in texts["gqa_fwd"] \
        and "window" not in texts["gqa_grad"]
    from orion_tpu.ops.pallas.flash_attention import flash_attention_gqa

    q = jnp.zeros((2, 256, 4, 16)), jnp.zeros((2, 256, 2, 16))
    pos = jnp.broadcast_to(jnp.arange(256, dtype=jnp.int32), (2, 256))
    windowed = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention_gqa(
            q, k, v, pos, 0.25, 128, 128, 100).sum(), (0, 1, 2)))(
        q[0], q[1], q[1]))
    for name in ("flash_fwd_window", "flash_dq_window", "flash_dkv_window"):
        assert name in windowed
    assert "flash_bwd_dq" not in windowed


# ---------------------------------------------------------------------------
# the expert layer's shares, tied to the model
# ---------------------------------------------------------------------------

def test_the_eight_expert_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts of all eight shares
    (there is no shared expert) = the uncut reference's layer."""
    from orion_tpu.ops.moe import TopKMoE

    cfg = ModelConfig.tiny("mellum", dtype="float32")
    whole = TopKMoE(cfg)
    x = jax.random.normal(jax.random.key(2), (2, 12, cfg.hidden_size))
    params = whole.init(jax.random.key(3), x)["params"]
    params = jax.tree.map(lambda t: t, params)
    from flax.core import meta

    params = meta.unbox(params)
    w = {"w_router": params["router"],
         "e_gate_up": params["experts_gate_up_proj"],
         "e_down": params["experts_down_proj"]}
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([ref.expert_ffn(
            x[b], w, _shape(cfg), (0, cfg.n_routed_experts))[0]
            for b in range(2)])
    np.testing.assert_allclose(whole.apply({"params": params}, x), uncut,
                               atol=2e-5, rtol=0)
    total, of = 0.0, 8
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
                (which * held, held))[0] for b in range(2)]),
                atol=2e-5, rtol=0)
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=3e-5, rtol=0)


def test_the_kind_states_its_cache_tags_and_attributes():
    cfg = ModelConfig.tiny("mellum")
    assert MIXERS["window"] is WindowAttention
    assert WindowAttention.cache_kind == "cache"
    assert WindowAttention.takes_token_mask and not Attention.takes_token_mask
    assert WindowAttention.window(cfg) == 8 and Attention.window(cfg) is None
    assert (WindowAttention.trace_scope(cfg), Attention.trace_scope(cfg)) \
        == ("attn.window", "attn.full")
    assert Attention.trace_scope(ModelConfig.tiny("llama")) is None
    assert cfg.takes_token_mask and not cfg.recurrent
    entry = WindowAttention.cache_entry(cfg, 2, 40, jnp.bfloat16)
    assert entry["k"].shape == (2, 8, 2, 16)
    assert Attention.cache_entry(cfg, 2, 40, jnp.bfloat16)["k"].shape \
        == (2, 40, 2, 16)
    # the flash kernel's tags, the same for both kinds
    tags = dict(remat_tag_bytes(cfg, 2, 40))
    assert set(tags) == {"moe_route", "attn_resid", "attn_out", "attn_qkv"}
    assert WindowAttention.tag_bytes(cfg, 2, 40, lambda d: d) \
        == Attention.tag_bytes(cfg, 2, 40, lambda d: d)
    lens = [5, 13, 24]
    dec = decode_attrs(cfg, lens, 40, 16)
    assert (dec["window_layers"], dec["full_layers"], dec["window_slots"]) \
        == (6, 2, 8)
    assert dec["kv_slots_read_window"] == 8 \
        and dec["kv_slots_read_full"] == 40
    assert dec["kv_step_form"] == "whole" and dec["seq_tokens"] == 42
    upd = update_attrs(cfg, [21, 29, 40])
    # sum over t of min(t + 1, 8), and of t + 1
    assert upd["window_keys_seen"] == sum(
        min(t + 1, 8) for n in (21, 29, 40) for t in range(n)) == 636
    assert upd["causal_keys"] == sum(n * (n + 1) // 2 for n in (21, 29, 40))
    assert (upd["sliding_window"], upd["seq_tokens"], upd["experts_held"]) \
        == (8, 90, 8)
    # on one TPU device the ring is laid for the kernel as the full
    # cache is: [B, slots, Hkv * D]
    from orion_tpu.ops import indexer

    pub = dataclasses.replace(ModelConfig.mellum2_12b_a2_5b(), num_layers=8)
    real = indexer.select_form
    indexer.select_form = lambda: "kernel"
    try:
        ring = jax.eval_shape(lambda: WindowAttention.cache_entry(
            pub, 8, 8192, jnp.bfloat16))
        whole = jax.eval_shape(lambda: Attention.cache_entry(
            pub, 8, 8192, jnp.bfloat16))
        dec = decode_attrs(pub, np.full((8,), 6000), 8192, 1024)
    finally:
        indexer.select_form = real
    assert ring["k"].shape == (8, 1024, 512)
    assert whole["k"].shape == (8, 8192, 512)
    assert dec["kv_step_form"] == "kernel" and dec["kv_cache_lane_fill"] == 1
    # the ring is read whole (two blocks of 512); the full cache up to
    # each row's position, in blocks of 512
    assert dec["kv_slots_read_window"] == 1024
    assert 6144 <= dec["kv_slots_read_full"] <= 7168
    assert dec["ring_cache_bytes"] == 6 * 8 * 1024 * 2048
    assert dec["full_cache_bytes"] == 2 * 8 * 8192 * 2048


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
            "ppo", "model_preset=tiny_mellum", "model.experts_held=4",
            "model.expert_offset=4", "model.remat=true",
            "model.scan_layers=true", "share_backbone=true",
            "model.max_seq_len=24", "rollout.max_prompt_len=16",
            "rollout.max_new_tokens=8", "rollout_batch_size=4",
            "minibatch_size=2", "num_epochs=1", "data.dataset=synthetic",
            "reward=length", "total_iterations=2",
            "data.synthetic_min_len=10", "data.synthetic_max_len=16",
            "optimizer.learning_rate=1e-3", "ref_param_dtype=bfloat16",
            "optimizer.mu_dtype=bfloat16", "optimizer.nu_dtype=bfloat16",
            f"log_dir={tmp_path}"])
    finally:
        launch.build_trainer = real
    assert len(hist) == 2 and all(np.isfinite(r["loss"]) for r in hist)
    row = hist[-1]
    assert (row["window_layers"], row["full_layers"], row["sliding_window"],
            row["experts_held"]) == (6, 2, 8, 4)
    assert 0 < row["window_keys_seen"] < row["causal_keys"]
    assert row["moe_pairs_total"] > 0
    before = kept["before"]["backbone"]
    after = kept["trainer"].state.params["backbone"]
    for stack, half, name in (("layers_0to2", "attn", "q_proj"),
                              ("layers_3to3", "attn", "k_norm"),
                              ("layers_4to6", "mlp", "router"),
                              ("lm_head", None, "kernel")):
        a, b = after[stack], before[stack]
        a, b = (a[half][name], b[half][name]) if half else (a[name], b[name])
        moved = max(float(np.max(np.abs(np.asarray(x) - y))) for x, y in zip(
            jax.tree.leaves(a), jax.tree.leaves(b)))
        assert moved > 0, name
    assert after["layers_4to6"]["mlp"]["experts_gate_up_proj"].shape[:2] \
        == (3, 4)
    trainer = kept["trainer"]
    sizes = trainer.engine.dispatch_attrs((4, 16), [16] * 4,
                                          trainer.state.params)
    assert sizes["cache_bytes"] == sizes["ring_cache_bytes"] \
        + sizes["full_cache_bytes"] > 0


# ---------------------------------------------------------------------------
# what it cannot run
# ---------------------------------------------------------------------------

def _refusals():
    from orion_tpu.models.hf_export import hf_state_dict
    from orion_tpu.models.hf_loader import (config_from_hf,
                                            convert_hf_state_dict)
    from orion_tpu.rollout import RolloutEngine
    from orion_tpu.rollout.continuous import ContinuousBatchingEngine

    cfg = ModelConfig.tiny("mellum")
    model = Transformer(cfg)

    def engine(**kw):
        return lambda: RolloutEngine(model, cfg, RolloutConfig(**kw))

    def tiny(**kw):
        return lambda: ModelConfig.tiny("mellum", **kw)

    class HF:
        model_type = "mellum"

    return {
        "continuous": (lambda: ContinuousBatchingEngine(
            model, cfg, RolloutConfig()),
            "cache is the page pool .* ring entry is not made of pages"),
        "paged": (engine(paged=True), "ring entry is not made of pages"),
        "quantize_kv": (engine(quantize_kv=True), "there is no int8 ring"),
        "int8_cache": (lambda: init_cache(cfg, 1, 8, quantized=True),
                       "there is no int8 ring"),
        "ring": (tiny(attention_impl="ring"),
                 "ring and ulysses forms are not"),
        "ulysses": (tiny(attention_impl="ulysses"),
                    "apply the causal rule alone"),
        "seq_shard": (tiny(seq_shard_activations=True),
                      "a window is not cut across sequence shards"),
        "hf_import": (lambda: convert_hf_state_dict({}, cfg),
                      "no mellum checkpoint loader"),
        "hf_config": (lambda: config_from_hf(HF()),
                      "no mellum checkpoint loader"),
        "hf_export": (lambda: hf_state_dict(
            init_params(model, jax.random.key(0), cfg), cfg),
            "no mellum checkpoint layout"),
        "layer_type": (tiny(layer_types=(SLIDING, "conv") * 4),
                       r"names \['full_attention', 'sliding_attention'\]"),
        "no_window": (tiny(sliding_window=0), "sliding_window >= 1"),
        "rope_type": (tiny(rope_parameters={FULL: {"rope_type": "llama3"}}),
                      "rope_type 'default' or 'yarn'"),
        "sigmoid_scores": (tiny(moe_scoring="sigmoid"),
                           "scores by a softmax"),
        "window_elsewhere": (lambda: ModelConfig.tiny(
            "sdar_moe", sliding_window=8), "only arch='mellum'"),
    }


@pytest.mark.parametrize("path", [
    "continuous", "paged", "quantize_kv", "int8_cache", "ring", "ulysses",
    "seq_shard", "hf_import", "hf_config", "hf_export", "layer_type",
    "no_window", "rope_type", "sigmoid_scores", "window_elsewhere"])
def test_paths_that_cannot_run_it_name_the_missing_mechanism(path):
    call, words = _refusals()[path]
    with pytest.raises(ValueError, match=words):
        call()
    if path in ("paged", "continuous", "quantize_kv"):
        assert cannot_run(ModelConfig.tiny("mellum"), path)
        # a model of full layers alone is not refused for the window's sake
        assert "ring" not in (cannot_run(ModelConfig.tiny("sdar_moe"), path)
                              or "")
