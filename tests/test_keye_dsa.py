"""The ``keye_dsa`` block (Keye-VL-2.0's language model: grouped-query
attention with a norm over each head of q and k, cut to ``sa_topk`` keys
a query by a learned indexer, over softmax-routed experts) at the tiny
size (``topk`` 8, S = 32-64, float32) against the plain reference
``benchmarks/reference_keye_dsa.py`` on seeded weights: forward and
gradients with ragged padding, the exact selection in both of its forms,
the selection-taking kernels interpreted, prefill and decode through the
cache, the indexer held fixed by an update, the softmax router in the
shared expert layer, the eight shares, the preset, the long synthetic
prompts, the refusals."""

import dataclasses
import importlib.util
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import ModelConfig, RolloutConfig
from orion_tpu.models.transformer import (Attention, Transformer, init_cache,
                                          init_params, make_decode_twin,
                                          prep_decode_params)
from orion_tpu.ops import indexer, moe
from orion_tpu.ops.attention import reference_attention_gqa
from orion_tpu.ops.pallas import flash_attention as fa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "keye_test_" + name, os.path.join(REPO, "benchmarks", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference_keye_dsa")
chk = _load("reference_check_keye_dsa")


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
        sa_config=dict(indexer_num_heads=cfg.sa_index_heads,
                       indexer_head_dim=cfg.sa_index_head_dim,
                       indexer_num_kv_heads=1, topk=cfg.sa_topk))


def _weights(params, cfg):
    layers = [chk.layer_weights(chk.layer_tree(params, i))
              for i in range(cfg.num_layers)]
    return {"embed": params["embed"]["embedding"], "layers": layers,
            "nf_g": params["final_norm"]["scale"],
            "w_head": params["lm_head"]["kernel"]}


def _held(cfg):
    return cfg.expert_offset, cfg.experts_held


def _positions(ids):
    return jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)


def _model(**kw):
    cfg = ModelConfig.tiny("keye_dsa", dtype="float32", **kw)
    model = Transformer(cfg)
    return cfg, model, init_params(model, jax.random.key(0), cfg)


@pytest.fixture(scope="module")
def tiny():
    cfg, model, params = _model()
    ids = jnp.asarray(np.random.RandomState(0).randint(2, 256, (2, 48)),
                      jnp.int32)
    return cfg, model, params, ids


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scan", [False, True])
def test_forward_equals_reference_with_ragged_padding(scan):
    """Rows of 48 and 29 real tokens (right-padded): the first 8 queries
    of each have fewer valid keys than ``topk`` and keep them all, the
    others keep 8 of up to 48; padding is routed nowhere."""
    cfg, model, params = _model(scan_layers=scan, remat=scan)
    ids = jnp.asarray(np.random.RandomState(1).randint(2, 256, (2, 48)),
                      jnp.int32)
    lens = np.array([48, 29])
    mask = _positions(ids) < lens[:, None]
    got, _ = model.apply({"params": params}, ids, _positions(ids),
                         token_mask=mask)
    for b in range(2):
        want = ref.forward(_weights(params, cfg), ids[b], _shape(cfg),
                           _held(cfg), mask[b])
        n = int(lens[b])
        np.testing.assert_allclose(got[b, :n], want[:n], atol=2e-5)


def test_gradients_equal_reference_and_the_indexer_gets_none(tiny):
    cfg, model, params, ids = tiny
    lens = np.array([48, 31])
    mask = _positions(ids) < lens[:, None]

    def loss(p):
        logits, _ = model.apply({"params": p}, ids, _positions(ids),
                                token_mask=mask)
        return sum(
            -jnp.sum(ref.next_token_logprobs(logits[b], ids[b])
                     * mask[b, 1:]) / jnp.sum(mask[b, 1:])
            for b in range(2))

    got = jax.grad(loss)(params)
    want = jax.grad(lambda w: sum(
        ref.loss(w, ids[b], _shape(cfg), _held(cfg), mask[b])
        for b in range(2)))(_weights(params, cfg))
    for i in range(cfg.num_layers):
        g, w = got[f"layers_{i}"], want["layers"][i]
        mine = chk.layer_weights(g)
        for key in ("wq", "wk", "wv", "wo", "q_g", "k_g", "n1_g", "n2_g",
                    "w_router", "e_gate_up", "e_down"):
            np.testing.assert_allclose(mine[key], w[key], atol=2e-5,
                                       err_msg=f"layer {i} {key}")
        for key in ("wiq", "wik", "wiw", "ik_g", "ik_b"):
            assert float(jnp.max(jnp.abs(mine[key]))) == 0.0
            assert float(jnp.max(jnp.abs(w[key]))) == 0.0
    np.testing.assert_allclose(got["lm_head"]["kernel"], want["w_head"],
                               atol=2e-5)


def test_no_more_keys_than_topk_is_dense_grouped_query_attention():
    """S <= topk: nothing is selected, and the mixer is ``Attention``
    given a norm over each head (the path the other cells run),
    exactly: same parameters, same output, bit for bit."""
    from orion_tpu.models.transformer import SparseAttention

    cfg = ModelConfig.tiny("keye_dsa", dtype="float32", sa_topk=64)
    x = jax.random.normal(jax.random.key(2), (2, 48, cfg.hidden_size))
    pos = _positions(x[..., 0])
    sparse = SparseAttention(cfg, qk_norm="head")
    params = nn.meta.unbox(sparse.init(jax.random.key(3), x, pos)["params"])
    dense_params = {k: v for k, v in params.items()
                    if not k.startswith("index_")}
    got, _ = sparse.apply({"params": params}, x, pos)
    want, _ = Attention(cfg, qk_norm="head").apply(
        {"params": dense_params}, x, pos)
    assert params["q_norm"]["scale"].shape == (cfg.head_dim,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and with fewer keys kept than there are, it is not
    cut = dataclasses.replace(cfg, sa_topk=8)
    other, _ = SparseAttention(cut, qk_norm="head").apply(
        {"params": params}, x, pos)
    assert float(jnp.max(jnp.abs(other - want))) > 1e-3


def _step_form(monkeypatch, form):
    """The one-token step through one of its forms whatever the device
    (the CPU's own is ``masked``)."""
    from orion_tpu.ops.pallas import sparse_step

    monkeypatch.setattr(sparse_step, "step_form", lambda cache_len: form)


@pytest.mark.parametrize("scan, form", [
    (False, "masked"), (True, "masked"), (False, "kernel"),
    (True, "kernel")])
def test_prefill_then_decode_through_the_cache_equals_the_full_forward(
        scan, form, monkeypatch):
    """Prefill of unequal prompts (24 and 17 of 24 slots) through the
    cache, then one-token steps that select and attend in place under
    the mask (XLA's einsum; the kernel, interpreted, over blocks of 16
    slots that no filled length is a multiple of): the logits of every
    real position equal the full forward's."""
    from orion_tpu.ops.pallas import sparse_step

    P, T = 24, 24
    Lmax = P + T
    _step_form(monkeypatch, form)
    monkeypatch.setattr(sparse_step, "BLOCK_SLOTS", 16)
    cfg, model, params = _model(scan_layers=scan)
    rs = np.random.RandomState(4)
    lens = np.array([24, 17])
    full_ids = rs.randint(2, 256, (2, P + T)).astype(np.int32)
    dmodel, dcfg = make_decode_twin(model, cfg)
    dparams = prep_decode_params(params, cfg)
    cache = init_cache(dcfg, 2, Lmax)
    assert set(cache[0]) == {"k", "v", "ki"}
    assert cache[0]["ki"].shape == (2, Lmax, cfg.sa_index_head_dim)
    prompt = np.where(np.arange(P)[None] < lens[:, None], full_ids[:, :P], 0)
    pos = _positions(jnp.asarray(prompt))
    logits, cache = dmodel.apply(
        {"params": dparams}, jnp.asarray(prompt), pos, cache,
        token_mask=pos < lens[:, None])
    # each row's own sequence: its prompt, then its continuation
    seqs = [np.concatenate([full_ids[b, :lens[b]], full_ids[b, P:]])
            for b in range(2)]
    want = [model.apply({"params": params}, jnp.asarray(s)[None],
                        _positions(jnp.asarray(s)[None]))[0][0]
            for s in seqs]
    for b in range(2):
        np.testing.assert_allclose(logits[b, :lens[b]],
                                   want[b][:lens[b]], atol=2e-5)
    one_step = jax.jit(lambda tok, cur, cache: dmodel.apply(
        {"params": dparams}, tok[:, None], cur[:, None], cache))
    cur = jnp.asarray(lens, jnp.int32)
    for t in range(T):
        tok = jnp.asarray([seqs[b][lens[b] + t] for b in range(2)])
        step, cache = one_step(tok, cur, cache)
        for b in range(2):
            np.testing.assert_allclose(step[b, 0], want[b][lens[b] + t],
                                       atol=2e-5)
        cur = cur + 1


# ---------------------------------------------------------------------------
# the selection: exact, in both forms, whole sequences and one step
# ---------------------------------------------------------------------------

def _index_inputs(B=2, Lq=64, Lk=64, Hi=4, Di=8, seed=0, ties=False):
    k = jax.random.split(jax.random.key(seed), 3)
    qi = jax.random.normal(k[0], (B, Lq, Hi, Di))
    ki = jax.random.normal(k[1], (B, Lk, Di))
    if ties:                      # equal keys: equal scores, slot decides
        ki = ki.at[:, 10:20].set(ki[:, 0:10]).at[:, 30:34].set(ki[:, 5:9])
    w = jax.random.normal(k[2], (B, Lq, Hi))
    pos = jnp.broadcast_to(jnp.arange(Lq) + (Lk - Lq), (B, Lq))
    return qi, ki, w, pos


def _brute_force(qi, ki, w, pos, topk):
    """[B, Lk, Lq] by a full sort a query (numpy, stable)."""
    scores = np.asarray(indexer.index_scores(qi, ki, w, pos, ki.shape[1]))
    B, Lq, Lk = scores.shape
    out = np.zeros((B, Lk, Lq), np.int8)
    for b in range(B):
        for q in range(Lq):
            valid = int(pos[b, q]) + 1
            order = np.argsort(-scores[b, q, :valid], kind="stable")
            out[b, order[:topk], q] = 1
    return out


@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("form", ["jnp", "kernel"])
def test_selection_is_exact_lower_slot_first_on_a_tie(form, ties,
                                                      monkeypatch):
    qi, ki, w, pos = _index_inputs(ties=ties)
    if form == "kernel":
        monkeypatch.setattr(indexer, "_TQ", 32)
        monkeypatch.setattr(indexer, "_KC", 16)
        got = indexer.select_kernel(qi, ki, w, pos, 8)
    else:
        got = indexer._select_jnp(qi, ki, w, pos, 8, 16, 16)
    want = _brute_force(qi, ki, w, pos, 8)
    np.testing.assert_array_equal(np.asarray(got), want)
    kept = np.asarray(got).sum(axis=1)
    np.testing.assert_array_equal(kept[0], np.minimum(np.arange(64) + 1, 8))


def test_selection_kernel_with_every_score_equal_keeps_the_first_slots(
        monkeypatch):
    """w = 0: every valid score is 0, a 64-way tie a query."""
    qi, ki, w, pos = _index_inputs()
    monkeypatch.setattr(indexer, "_TQ", 32)
    monkeypatch.setattr(indexer, "_KC", 16)
    got = np.asarray(indexer.select_kernel(qi, ki, w * 0, pos, 8))
    assert (got[:, :8, 7:] == 1).all() and got[:, 8:].sum() == 0


def test_selection_over_a_longer_cache_than_the_queries(monkeypatch):
    """Prefill against the whole cache: 32 queries at slots 0..31 of 64;
    slots past a query's position are never kept."""
    qi, ki, w, _ = _index_inputs(Lq=32, Lk=64)
    pos = jnp.broadcast_to(jnp.arange(32), (2, 32))
    monkeypatch.setattr(indexer, "_TQ", 32)
    monkeypatch.setattr(indexer, "_KC", 16)
    got = indexer.select_kernel(qi, ki, w, pos, 8)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(indexer._select_jnp(qi, ki, w, pos, 8,
                                                        16, 16)))
    assert np.asarray(got)[:, 32:].sum() == 0


def test_one_step_selects_what_the_whole_sequence_selects():
    """The decode step's mask of the slots for the query at position t
    is row t of the whole-sequence selection: identical, ties
    included."""
    qi, ki, w, pos = _index_inputs(ties=True)
    whole = np.asarray(indexer._select_jnp(qi, ki, w, pos, 8, 16, 16))
    for t in (3, 7, 8, 40, 63):
        mask = indexer.select_step(qi[:, t], ki, w[:, t], pos[:, t], 8)
        np.testing.assert_array_equal(np.asarray(mask, np.int8),
                                      whole[:, :, t])


@pytest.mark.parametrize("case", [
    "ties_at_the_kth_score", "every_score_equal", "fewer_valid_than_topk",
    "rows_of_different_filled_lengths"])
def test_the_steps_mask_is_top_ks_set(case):
    """``select_step``'s mask against a stable sort a row (numpy),
    where the k-th score is shared."""
    qi, ki, w, _ = _index_inputs()
    qi, w = qi[:, 50], w[:, 50]
    pos = jnp.asarray([63, 63])
    if case == "ties_at_the_kth_score":
        # three distinct keys: every score is one of three values, so
        # the 8th largest sits inside a run of ~21 equal ones
        ki = jnp.tile(ki[:, :3], (1, 22, 1))[:, :64]
    elif case == "every_score_equal":
        w = w * 0
    elif case == "fewer_valid_than_topk":
        pos = jnp.asarray([4, 6])
    else:
        pos = jnp.asarray([5, 40])
        ki = ki.at[:, 10:20].set(ki[:, 0:10])
    mask = np.asarray(indexer.select_step(qi, ki, w, pos, 8), np.int8)
    want = _brute_force(qi[:, None], ki, w[:, None], pos[:, None], 8)[..., 0]
    np.testing.assert_array_equal(mask, want)
    np.testing.assert_array_equal(mask.sum(-1),
                                  np.minimum(np.asarray(pos) + 1, 8))
    if case == "ties_at_the_kth_score":
        scores = np.asarray(indexer.index_scores(
            qi[:, None], ki, w[:, None], pos[:, None], 64))[:, 0]
        kth = np.sort(scores, axis=-1)[:, -8]
        assert ((scores == kth[:, None]).sum(-1) > 8).all()
    if case == "every_score_equal":
        assert (mask[:, :8] == 1).all()


# ---------------------------------------------------------------------------
# the selection-taking kernels, interpreted, against jax.numpy
# ---------------------------------------------------------------------------

def _sparse_inputs(H=4, Hkv=2, Lq=256, Lk=256):
    k = jax.random.split(jax.random.key(5), 5)
    q = jax.random.normal(k[0], (2, Lq, H, 16))
    kk = jax.random.normal(k[1], (2, Lk, Hkv, 16))
    v = jax.random.normal(k[2], (2, Lk, Hkv, 16))
    pos = jnp.broadcast_to(jnp.arange(Lq) + (Lk - Lq), (2, Lq))
    slots = jnp.arange(Lk)
    sel = (jax.random.uniform(k[3], (2, Lq, Lk)) < 0.3) \
        | (slots[None, None, :] == pos[:, :, None])
    ct = jax.random.normal(k[4], q.shape)
    mask = sel & (slots[None, None, :] <= pos[:, :, None])
    return q, kk, v, pos, sel.swapaxes(1, 2).astype(jnp.int8), mask, ct


@pytest.mark.parametrize("which", ["fwd", "dq", "dk", "dv"])
def test_selection_taking_kernels_equal_the_masked_einsum(which,
                                                          monkeypatch):
    monkeypatch.setattr(fa, "_MAJOR", 128)      # several major blocks
    q, k, v, pos, sel_t, mask, ct = _sparse_inputs()

    def kernel(q, k, v):
        return fa.sparse_attention_gqa(q, k, v, pos, sel_t, 0.25, 128, 128)

    def plain(q, k, v):
        return reference_attention_gqa(q, k, v, mask, 0.25)

    if which == "fwd":
        got, want = kernel(q, k, v), plain(q, k, v)
    else:
        arg = {"dq": 0, "dk": 1, "dv": 2}[which]
        got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * ct), argnums=arg)(
            q, k, v) for f in (kernel, plain))
    np.testing.assert_allclose(got, want, atol=5e-6)


def test_sparse_forward_over_a_cache_longer_than_the_queries(monkeypatch):
    monkeypatch.setattr(fa, "_MAJOR", 128)
    q, k, v, pos, sel_t, mask, _ = _sparse_inputs(Lq=128, Lk=256)
    got = fa.sparse_attention_gqa(q, k, v, pos, sel_t, 0.25, 128, 128)
    np.testing.assert_allclose(got, reference_attention_gqa(
        q, k, v, mask, 0.25), atol=5e-6)


@pytest.mark.parametrize("block", [16, 64])
def test_the_step_kernel_equals_the_masked_einsum(block, monkeypatch):
    """``sparse_step`` interpreted, 8 query heads on 2 key heads over a
    cache of 64 slots in blocks of 16 (and one block of 64): filled
    lengths that are no multiple of the block, a row that keeps nothing
    in its first block, a row that keeps one slot."""
    from orion_tpu.ops.pallas import sparse_step

    monkeypatch.setattr(sparse_step, "BLOCK_SLOTS", block)
    k = jax.random.split(jax.random.key(6), 4)
    q = jax.random.normal(k[0], (3, 1, 8, 16))
    kk = jax.random.normal(k[1], (3, 64, 2, 16))
    v = jax.random.normal(k[2], (3, 64, 2, 16))
    pos = jnp.asarray([63, 37, 21])
    slots = jnp.arange(64)
    keep = (jax.random.uniform(k[3], (3, 64)) < 0.4) \
        & (slots[None, :] <= pos[:, None])
    keep = keep.at[1, :20].set(False).at[1, 37].set(True)
    keep = keep.at[2].set(slots == 9)
    got = sparse_step.sparse_step(q, kk, v, keep, pos, 0.25)
    want = reference_attention_gqa(q, kk, v, keep[:, None, :], 0.25)
    np.testing.assert_allclose(got, want, atol=5e-6)
    np.testing.assert_allclose(got[2, 0], jnp.repeat(v[2, 9], 4, axis=0),
                               atol=1e-6)


@pytest.mark.parametrize("form", ["masked", "kernel"])
def test_the_masked_step_gathers_no_row_of_the_cache(form, monkeypatch):
    """The decode program of the tiny model: no ``gather`` takes an
    operand of the cache's shape (k and v are read where they lie), and
    the kernel is in it once a layer where the form is the kernel's."""
    from orion_tpu.ops.pallas import sparse_step

    _step_form(monkeypatch, form)
    monkeypatch.setattr(sparse_step, "BLOCK_SLOTS", 16)
    cfg, model, params = _model()
    dmodel, dcfg = make_decode_twin(model, cfg)
    dparams = prep_decode_params(params, cfg)
    cache = init_cache(dcfg, 2, 48)

    def walk(jaxpr):
        for e in jaxpr.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from walk(sub)

    jaxpr = jax.make_jaxpr(lambda tok, cur, cache: dmodel.apply(
        {"params": dparams}, tok, cur, cache))(
            jnp.zeros((2, 1), jnp.int32), jnp.full((2, 1), 30), cache)
    eqns = list(walk(jaxpr.jaxpr))
    of_the_cache = [e for e in eqns if e.primitive.name == "gather"
                    and e.invars[0].aval.shape == cache[0]["k"].shape]
    kernels = [e.params["name"] for e in eqns
               if e.primitive.name == "pallas_call"]
    assert not of_the_cache
    assert kernels.count("sparse_step") == (cfg.num_layers
                                            if form == "kernel" else 0)


@pytest.mark.parametrize("cache_len, device, want", [
    (8192, "kernel", "kernel"), (512, "kernel", "kernel"),
    # 7680 + 500 new tokens, rounded up to 8: 8 x 1023 slots
    (8184, "kernel", "masked"), (48, "kernel", "masked"),
    (8192, "jnp", "masked")])
def test_the_step_is_the_kernels_over_whole_blocks_only(
        cache_len, device, want, monkeypatch):
    """The rule that picks the step's form: the kernel where the trace
    is for one TPU device AND the cache is whole blocks of 512 slots
    (no smaller block is tried: a cache of 8 x 1023 slots would run in
    blocks of 8); XLA's einsum under the mask elsewhere."""
    from orion_tpu.ops.pallas import sparse_step

    monkeypatch.setattr(indexer, "select_form", lambda: device)
    assert sparse_step.BLOCK_SLOTS == 512
    assert sparse_step.step_form(cache_len) == want


def test_the_dense_kernels_take_no_selection_operand():
    """Other archs' programs do not change: the selection is an operand
    that is absent for them, and the kernels keep their names."""
    q, k, v, pos, sel_t, _, _ = _sparse_inputs(Lq=128, Lk=128)
    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from walk(sub)

    def calls(f):
        return list(walk(jax.make_jaxpr(f)(q, k, v).jaxpr))

    (dense,) = calls(lambda q, k, v: fa.flash_attention_gqa(
        q, k, v, pos, 0.25))
    (sparse,) = calls(lambda q, k, v: fa.sparse_attention_gqa(
        q, k, v, pos, sel_t, 0.25))
    # three tables, the positions, q, k, v; and the selection
    assert len(dense.invars) == 7 and len(sparse.invars) == 8
    assert not any(v.aval.dtype == jnp.int8 for v in dense.invars)
    assert dense.params["name"] == "flash_fwd"
    assert sparse.params["name"] == "sparse_fwd"


def test_the_model_through_the_kernels_equals_the_einsum_path(tiny,
                                                              monkeypatch):
    """attention_impl=flash and the selection kernel (both interpreted)
    against the default CPU path."""
    cfg, model, params, ids = tiny
    want, _ = model.apply({"params": params}, ids, _positions(ids))
    monkeypatch.setattr(indexer, "select_form", lambda: "kernel")
    monkeypatch.setattr(indexer, "_TQ", 16)
    monkeypatch.setattr(indexer, "_KC", 16)
    flash = Transformer(dataclasses.replace(cfg, attention_impl="flash"))
    got, _ = flash.apply({"params": params}, ids, _positions(ids))
    np.testing.assert_allclose(got, want, atol=2e-5)


# ---------------------------------------------------------------------------
# the expert layer: a softmax router as data, the shares
# ---------------------------------------------------------------------------

def test_softmax_router_gates_sum_to_one_and_padding_is_routed_nowhere():
    cfg = ModelConfig.tiny("keye_dsa", dtype="float32")
    z = jax.random.normal(jax.random.key(6), (2, 16, cfg.hidden_size))
    layer = moe.TopKMoE(cfg)
    params = nn.meta.unbox(layer.init(jax.random.key(7), z)["params"])
    assert "e_score_correction_bias" not in params      # no bias, no shared
    assert not any(k.startswith("shared") for k in params)
    idx, gates = moe.softmax_topk_route(
        z.reshape(32, -1), params["router"], cfg.num_experts_per_tok, 1.0)
    np.testing.assert_allclose(jnp.sum(gates, axis=-1), 1.0, atol=1e-6)
    probs = jax.nn.softmax(z.reshape(32, -1) @ params["router"], axis=-1)
    np.testing.assert_array_equal(
        np.sort(np.asarray(idx)), np.sort(np.asarray(jax.lax.top_k(
            probs, cfg.num_experts_per_tok)[1])))
    mask = jnp.arange(16)[None, :] < jnp.array([16, 9])[:, None]
    out, kept = layer.apply({"params": params}, z, mask,
                            mutable=["intermediates"])
    assert float(jnp.max(jnp.abs(out[1, 9:]))) == 0.0
    assert int(kept["intermediates"]["moe_load"][0].sum()) == 25 * 2
    w = {"w_router": params["router"],
         "e_gate_up": params["experts_gate_up_proj"],
         "e_down": params["experts_down_proj"]}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_ffn(z[0], w, _shape(cfg), (0, 8))
    np.testing.assert_allclose(out[0], want, atol=1e-5)


def test_eight_shares_add_up_to_the_uncut_layer():
    """The share test: the routed parts that eight chips holding 2 of 16
    experts each compute add up to the uncut reference's layer sum (no
    shared expert here: nothing is counted twice)."""
    cfg = ModelConfig.tiny("keye_dsa", dtype="float32", n_routed_experts=16,
                           num_experts_per_tok=4)
    z = jax.random.normal(jax.random.key(8), (1, 24, cfg.hidden_size))
    whole = nn.meta.unbox(
        moe.TopKMoE(cfg).init(jax.random.key(9), z)["params"])
    w = {"w_router": whole["router"],
         "e_gate_up": whole["experts_gate_up_proj"],
         "e_down": whole["experts_down_proj"]}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_ffn(z[0], w, _shape(cfg), (0, 16))
    total = 0.0
    for chip in range(8):
        share_cfg = dataclasses.replace(cfg, experts_held=2,
                                        expert_offset=2 * chip)
        share = dict(
            whole,
            experts_gate_up_proj=whole["experts_gate_up_proj"][
                2 * chip:2 * chip + 2],
            experts_down_proj=whole["experts_down_proj"][
                2 * chip:2 * chip + 2])
        part = moe.TopKMoE(share_cfg).apply({"params": share}, z)
        assert float(jnp.max(jnp.abs(part))) > 0
        total = total + part
    np.testing.assert_allclose(total[0], want, atol=1e-5)


def test_the_sigmoid_cells_keep_their_layer_and_its_bias():
    cfg = ModelConfig.tiny("deepseek_v3", dtype="float32")
    assert cfg.moe_scoring == "sigmoid"
    z = jax.random.normal(jax.random.key(10), (1, 8, cfg.hidden_size))
    layer = moe.TopKMoE(cfg)
    params = layer.init(jax.random.key(11), z)["params"]
    assert "e_score_correction_bias" in params
    assert isinstance(layer, moe.TopKMoE)


# ---------------------------------------------------------------------------
# an update holds the indexer fixed
# ---------------------------------------------------------------------------

def test_an_update_moves_every_parameter_but_the_indexers():
    """With weight decay on, so that only ``hold_fixed`` keeps it off."""
    from orion_tpu.config import OptimizerConfig
    from orion_tpu.trainers.base import hold_fixed, make_optimizer

    cfg, model, params = _model()
    ids = jnp.asarray(np.random.RandomState(12).randint(2, 256, (2, 40)))
    tx = make_optimizer(OptimizerConfig(learning_rate=1e-2,
                                        weight_decay=0.1))
    grads = jax.grad(lambda p: jnp.mean(model.apply(
        {"params": p}, ids, _positions(ids))[0] ** 2))(params)
    updates, _ = tx.update(grads, tx.init(params), params)
    after = jax.tree.map(lambda p, u: p + u, params,
                         hold_fixed(updates, cfg))
    moved = jax.tree_util.tree_map_with_path(
        lambda path, a, b: (jax.tree_util.keystr(path),
                            float(jnp.max(jnp.abs(a - b)))), after, params)
    for name, delta in jax.tree.leaves(
            moved, is_leaf=lambda x: isinstance(x, tuple)):
        if "index_" in name:
            assert delta == 0.0, name
        elif "index_k_norm" not in name and "bias" not in name:
            assert delta > 0.0, name
    # every other model's updates pass through untouched
    other = ModelConfig.tiny("llama")
    assert hold_fixed(updates, other) is updates


# ---------------------------------------------------------------------------
# the preset, the cut, the counters, the data
# ---------------------------------------------------------------------------

def _count(cfg):
    model = Transformer(cfg)
    shapes = jax.eval_shape(lambda: init_params(model, jax.random.key(0),
                                                cfg))
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


def test_the_preset_is_the_published_model_and_counts_30_6_billion():
    cfg = ModelConfig.keye_vl2_30b_a3b()
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim) == (2048, 32, 4, 128)
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.moe_scoring) == (
                128, 8, 768, "softmax")
    assert (cfg.sa_topk, cfg.sa_index_heads, cfg.sa_index_head_dim) == (
        2048, 16, 64)
    assert cfg.rope_theta == 1e7 and cfg.num_layers == 48
    assert cfg.layer_runs() == ((0, 48, "sparse", "experts"),)
    # one layer and the embeddings by shapes; 48 layers by arithmetic
    one = _count(dataclasses.replace(cfg, num_layers=1))
    emb = 2 * 151936 * 2048 + 2048
    per_layer = one - emb
    assert abs((48 * per_layer + emb) / 1e9 - 30.6) < 0.1


def test_the_cut_gives_the_program_what_the_file_states():
    from orion_tpu.config import PPOConfig, load_config

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "keye-vl-2.0-30b-a3b-ep8.json")) as f:
        conf = json.load(f)
    cfg = load_config(PPOConfig, cli_args=conf["launch"]).model
    sa = conf["sa_config"]
    assert (cfg.num_layers, cfg.experts_held, cfg.expert_offset,
            cfg.vocab_size) == (conf["num_hidden_layers"],
                                conf["num_experts"], conf["expert_offset"],
                                conf["vocab_size"])
    assert cfg.n_routed_experts == conf["source_values"]["num_experts"]
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.moe_intermediate_size, cfg.num_experts_per_tok) == (
        conf["hidden_size"], conf["num_attention_heads"],
        conf["num_key_value_heads"], conf["head_dim"],
        conf["moe_intermediate_size"], conf["num_experts_per_tok"])
    assert (cfg.sa_topk, cfg.sa_index_heads, cfg.sa_index_head_dim,
            cfg.sa_q_chunk, cfg.sa_kv_chunk) == (
        sa["topk"], sa["indexer_num_heads"], sa["indexer_head_dim"],
        sa["q_chunk_size"], sa["kv_chunk_size"])
    assert cfg.rope_theta == conf["rope_theta"]
    assert cfg.rms_norm_eps == conf["rms_norm_eps"]
    assert abs(_count(cfg) / 1e6 - 659) < 1.0      # ISSUE's 659 M


def test_key_counts_from_lengths():
    from orion_tpu.models.transformer import SparseAttention

    sa_key_counts = SparseAttention.key_counts
    got = sa_key_counts([5, 12], topk=8)
    assert got["sa_keys_valid"] == 15 + 78
    assert got["sa_keys_selected"] == 15 + (36 + 4 * 8)
    # the cell's lengths: 44-52 % of the valid keys are kept
    for n in (6656, 8192):
        c = sa_key_counts([n], topk=2048)
        assert 0.43 < c["sa_keys_selected"] / c["sa_keys_valid"] < 0.53


@pytest.mark.parametrize("form, slots", [
    # blocks of 16 up to each filled slot, the mean over 16 steps: 20..35
    # reads 32 slots twelve times and 48 four, 30..45 reads 32 twice
    ("kernel", 36 + 46),
    ("masked", 2 * 48)])
def test_step_read_from_lengths(form, slots, monkeypatch):
    """What the ``rollout.dispatch`` span says of the one-token step: its
    form, and the bytes of k and v a step reads a layer (2 key heads of
    16, float32)."""
    from orion_tpu.ops.pallas import sparse_step
    from orion_tpu.models.transformer import SparseAttention

    _step_form(monkeypatch, form)
    monkeypatch.setattr(sparse_step, "BLOCK_SLOTS", 16)
    cfg = ModelConfig.tiny("keye_dsa", dtype="float32")
    got = SparseAttention.step_read(cfg, [20, 30], 48, 16)
    assert got == {"sparse_step": form,
                   "sa_step_bytes": 2 * slots * 2 * 16 * 4}


def test_the_rollout_span_says_how_the_step_reads_k_and_v(tmp_path):
    """One iteration through the launcher with ``obs.trace=true``: the
    ``rollout.dispatch`` span carries the step's form (the CPU's: XLA's
    einsum under the mask, over all 64 slots of 4 sequences) and the
    bytes of k and v it reads a layer, beside ``sa_topk``; every
    ``sa_*`` attribute is a number (the benchmark's reader takes them
    for such)."""
    import json

    from orion_tpu import launch

    hist = launch.main([
        "ppo", "model_preset=tiny_keye_dsa", "model.remat=true",
        "model.scan_layers=true", "share_backbone=true",
        "model.max_seq_len=64", "rollout.max_prompt_len=48",
        "rollout.max_new_tokens=16", "data.synthetic_min_len=30",
        "data.synthetic_max_len=48", "rollout_batch_size=4",
        "minibatch_size=2", "num_epochs=1", "data.dataset=synthetic",
        "reward=length", "total_iterations=1", "obs.trace=true",
        f"log_dir={tmp_path}"])
    assert len(hist) == 1 and np.isfinite(hist[0]["loss"])
    with open(tmp_path / f"spans-{os.getpid()}.json") as f:
        events = json.load(f)["traceEvents"]
    (span,) = [e["args"] for e in events if e["name"] == "rollout.dispatch"]
    cfg = ModelConfig.tiny("keye_dsa")
    row = cfg.num_kv_heads * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize
    assert span["sparse_step"] == "masked"
    assert span["sa_step_bytes"] == 2 * 4 * 64 * row
    assert span["sa_topk"] == cfg.sa_topk == 8
    assert all(isinstance(v, (int, float)) for k, v in span.items()
               if k.startswith(("sa_", "index_")))


def test_long_synthetic_prompts_and_the_short_ones_unchanged():
    from orion_tpu.data.prompts import (ByteTokenizer, _records_synthetic,
                                        build_prompt_iterator)

    short = _records_synthetic(16, seed=3)
    assert short[0]["prompt"].startswith("Compute ")
    assert all(len(r["prompt"]) < 30 for r in short)
    long = _records_synthetic(16, seed=3, len_range=(100, 140))
    for a, b in zip(short, long):
        assert b["prompt"].endswith(a["prompt"]) and a["answer"] == b["answer"]
    it = build_prompt_iterator("synthetic", ByteTokenizer(), 8, 140, seed=3,
                               synthetic_len_range=(100, 140))
    batch = next(it)
    lens = batch["prompt_lens"]
    assert lens.min() >= 100 and lens.max() <= 140 and len(set(lens)) > 1
    assert batch["prompt_ids"].max() < 260
    again = next(build_prompt_iterator(
        "synthetic", ByteTokenizer(), 8, 140, seed=3,
        synthetic_len_range=(100, 140)))
    np.testing.assert_array_equal(batch["prompt_ids"], again["prompt_ids"])
    # filler as ids from a vocabulary, behind the bos, before the question
    wide = next(build_prompt_iterator(
        "synthetic", ByteTokenizer(), 8, 140, seed=3,
        synthetic_len_range=(100, 140), synthetic_vocab=5000))
    wlens = wide["prompt_lens"]
    assert wlens.min() >= 100 and wlens.max() <= 140 and len(set(wlens)) > 1
    ids = wide["prompt_ids"]
    assert (ids[:, 0] == 1).all() and 260 < ids.max() < 5000
    assert len(np.unique(ids[0, 1:60])) > 50 and ids[:, 1:60].min() >= 4
    n, m = int(wlens[0]), int(lens[0])       # the question's last bytes
    np.testing.assert_array_equal(ids[0, n - 9:n],
                                  batch["prompt_ids"][0, m - 9:m])
    assert set(wide) == set(batch)


# ---------------------------------------------------------------------------
# the refusals, by name
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("key, value, words", [
    ("paged", True, "no selection inside paged attention"),
    ("quantize_kv", True, "int8 cache under a selection"),
    ("quantize_weights", True, "int8 expert stacks"),
])
def test_the_fixed_batch_engine_refuses_by_name(key, value, words):
    from orion_tpu.rollout.engine import RolloutEngine

    cfg = ModelConfig.tiny("keye_dsa")
    with pytest.raises(ValueError, match=words):
        RolloutEngine(Transformer(cfg), cfg, RolloutConfig(**{key: value}))


def test_the_continuous_engine_refuses_by_name():
    from orion_tpu.rollout.continuous import ContinuousBatchingEngine

    cfg = ModelConfig.tiny("keye_dsa")
    with pytest.raises(ValueError, match="page pool for the indexer"):
        ContinuousBatchingEngine(Transformer(cfg), cfg, RolloutConfig())


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_sequence_parallel_attention_is_refused_by_name(impl):
    with pytest.raises(ValueError, match="selection across sequence shards"):
        ModelConfig.tiny("keye_dsa", attention_impl=impl)


def test_hf_import_and_export_are_refused_by_name():
    from orion_tpu.models.hf_export import hf_state_dict
    from orion_tpu.models.hf_loader import (config_from_hf,
                                            convert_hf_state_dict)

    cfg = ModelConfig.tiny("keye_dsa")
    with pytest.raises(ValueError, match="KeyeVL2 checkpoint"):
        hf_state_dict({}, cfg)
    with pytest.raises(ValueError, match="KeyeVL2 checkpoint loader"):
        convert_hf_state_dict({}, cfg)

    class Hf:
        model_type = "KeyeVL2"

    with pytest.raises(ValueError, match="KeyeVL2 checkpoint loader"):
        config_from_hf(Hf())


def test_a_cache_of_another_kind_is_refused():
    cfg, model, params = _model()
    ids = jnp.zeros((1, 4), jnp.int32)
    llama = init_cache(ModelConfig.tiny("llama"), 1, 8)
    with pytest.raises(ValueError, match="'k', 'v', 'ki'"):
        model.apply({"params": params}, ids, _positions(ids), llama)
    with pytest.raises(ValueError, match="int8"):
        init_cache(cfg, 1, 8, quantized=True)
