"""The one-token attention step over the filled prefix of its slot
cache (``models/transformer.py::prefix_step``): one ``lax.switch`` a
layer over static prefixes (whole blocks, the last one ragged), against
the same step over all ``Lmax`` slots and against the reference
attention."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.config import ModelConfig, RolloutConfig
from orion_tpu.models import Transformer, init_params
from orion_tpu.models import transformer as tr
from orion_tpu.rollout import RolloutEngine

LMAX, BLOCK = 384, 128          # three blocks, as ppo1b-sync's cache
KINDS = ("mha", "gqa", "int8", "latent")
TOL = {"mha": 2e-2, "gqa": 2e-6, "int8": 2e-6, "latent": 2e-6}  # bf16 | f32


def _layer(kind):
    """(module, cache of LMAX slots filled with noise, params, x) of one
    attention layer at a tiny width: ``mha`` 30 heads of 128 in bf16 with
    the norm over the whole projections and no rotation (olmo_hybrid's
    full-attention layer), ``gqa`` 4 query heads on 2 key heads, ``int8``
    the quantized cache of 4 heads on 4 (pythia's), ``latent``
    deepseek_v3's {c, k_rope}."""
    rs = np.random.RandomState(len(kind))
    B = 3

    def noise(shape, dtype):
        return jnp.asarray(rs.standard_normal(shape), dtype)

    if kind == "latent":
        cfg = ModelConfig.tiny("deepseek_v3", dtype="float32")
        mod = tr.LatentAttention(cfg)
        cache = {"c": noise((B, LMAX, cfg.kv_lora_rank), jnp.float32),
                 "k_rope": noise((B, LMAX, cfg.qk_rope_head_dim),
                                 jnp.float32)}
    elif kind == "mha":
        cfg = ModelConfig.tiny("olmo_hybrid", num_heads=30, head_dim=128)
        mod = tr.Attention(cfg, qk_norm="whole", rotary=False)
        shape = (B, LMAX, 30, 128)
        cache = {"k": noise(shape, jnp.bfloat16),
                 "v": noise(shape, jnp.bfloat16)}
    else:
        cfg = ModelConfig.tiny("neox" if kind == "int8" else "llama",
                               dtype="float32")
        mod = tr.Attention(cfg)
        shape = (B, LMAX, cfg.num_kv_heads, cfg.head_dim)
        if kind == "int8":
            cache = {
                "k": jnp.asarray(rs.randint(-127, 128, shape), jnp.int8),
                "v": jnp.asarray(rs.randint(-127, 128, shape), jnp.int8),
                "k_scale": jnp.abs(noise(shape[:-1], jnp.float32)) / 127,
                "v_scale": jnp.abs(noise(shape[:-1], jnp.float32)) / 127}
        else:
            cache = {"k": noise(shape, jnp.float32),
                     "v": noise(shape, jnp.float32)}
    x = noise((B, 1, cfg.hidden_size), jnp.dtype(cfg.dtype))
    params = mod.init(jax.random.key(1), x, jnp.zeros((B, 1), jnp.int32),
                      cache)["params"]
    return mod, cache, params, x


def _step(mod, params, x, positions, cache, monkeypatch=None):
    """The layer's output for one new token a row at ``positions``; with
    ``monkeypatch`` the step over the whole cache (one block holds it)."""
    if monkeypatch is not None:
        monkeypatch.setattr(tr, "_PREFIX_SLOTS", 10 ** 9)
    pos = jnp.asarray(positions, jnp.int32)[:, None]
    out, new = mod.apply({"params": params}, x, pos, cache)
    return np.asarray(out, np.float32), new


POSITIONS = {"last_of_a_block": [BLOCK - 1] * 3,
             "first_of_the_next": [BLOCK] * 3,
             "last_slot": [LMAX - 1] * 3,
             # the batch's furthest row decides: two blocks here
             "mixed_rows": [5, BLOCK + 70, BLOCK - 1]}


@pytest.mark.parametrize("where", sorted(POSITIONS))
@pytest.mark.parametrize("kind", KINDS)
def test_the_prefix_step_equals_the_whole_cache_step(kind, where,
                                                     monkeypatch):
    mod, cache, params, x = _layer(kind)
    got, new = _step(mod, params, x, POSITIONS[where], cache)
    want, new_whole = _step(mod, params, x, POSITIONS[where], cache,
                            monkeypatch)
    np.testing.assert_allclose(got, want, atol=TOL[kind], rtol=TOL[kind])
    for name in new:                             # the write is the same
        np.testing.assert_array_equal(np.asarray(new[name], np.float32),
                                      np.asarray(new_whole[name],
                                                 np.float32))


@pytest.mark.parametrize("where", ["last_of_a_block", "mixed_rows"])
@pytest.mark.parametrize("kind", KINDS)
def test_slots_past_the_chosen_prefix_are_never_read(kind, where,
                                                     monkeypatch):
    """NaN in every slot past the prefix the batch's furthest position
    chooses (an int8 cache: in the scales) leaves the output finite and
    equal to the clean cache's whole-cache step, which would read them
    (probability 0 times NaN)."""
    mod, cache, params, x = _layer(kind)
    positions = POSITIONS[where]
    m = (max(positions) // BLOCK + 1) * BLOCK
    poisoned = {name: (a.at[:, m:].set(jnp.nan)
                       if jnp.issubdtype(a.dtype, jnp.floating) else a)
                for name, a in cache.items()}
    got, _ = _step(mod, params, x, positions, poisoned)
    assert np.isfinite(got).all()
    want, _ = _step(mod, params, x, positions, cache, monkeypatch)
    np.testing.assert_allclose(got, want, atol=TOL[kind], rtol=TOL[kind])
    whole, _ = _step(mod, params, x, positions, poisoned, monkeypatch)
    assert not np.isfinite(whole).all()


def _eqns(jaxpr, name):
    found = []
    for e in jaxpr.eqns:
        if e.primitive.name == name:
            found.append(e)
        for sub in jax.core.jaxprs_in_params(e.params):
            found += _eqns(sub, name)
    return found


def _step_jaxpr(kind, Lmax):
    mod, cache, params, x = _layer(kind)
    cache = {n: a[:, :Lmax] for n, a in cache.items()}
    pos = jnp.zeros((3, 1), jnp.int32)
    return jax.make_jaxpr(lambda c: mod.apply({"params": params}, x, pos,
                                              c))(cache).jaxpr


@pytest.mark.parametrize("kind", KINDS)
def test_the_step_is_one_switch_over_static_prefixes(kind):
    """One ``cond`` of ``Lmax / block`` branches; branch ``i`` slices
    every cache operand (an array of ``Lmax`` slots) to its first ``(i +
    1) * block`` slots and reads nothing of it otherwise; the last
    branch is the whole-cache step."""
    (cond,) = _eqns(_step_jaxpr(kind, LMAX), "cond")
    branches = cond.params["branches"]
    assert len(branches) == LMAX // BLOCK
    n_cache = {"mha": 2, "gqa": 2, "int8": 4, "latent": 2}[kind]
    for i, br in enumerate(branches):
        cached = [v for v in br.jaxpr.invars
                  if v.aval.ndim >= 3 and v.aval.shape[1] == LMAX]
        assert len(cached) == n_cache
        for v in cached:
            uses = [e for e in br.jaxpr.eqns if v in e.invars]
            assert uses
            if i + 1 == len(branches):
                assert not any(e.primitive.name == "slice" for e in uses)
                continue
            for e in uses:
                assert e.primitive.name == "slice"
                assert e.outvars[0].aval.shape[1] == (i + 1) * BLOCK


@pytest.mark.parametrize("Lmax", [128, 200, 376],
                         ids=["one_block", "ragged_second", "ragged_third"])
@pytest.mark.parametrize("kind", KINDS)
def test_a_cache_of_no_whole_blocks_switches_too(kind, Lmax, monkeypatch):
    """A cache of one block: no ``cond``, the step over all of it.  A
    longer cache of no whole blocks: the last branch is ragged (all
    ``Lmax`` slots, unsliced), and a row in that tail reads what the
    whole-cache step reads."""
    ms = tr.prefix_lengths(Lmax)
    assert ms == {128: [128], 200: [128, 200], 376: [128, 256, 376]}[Lmax]
    conds = _eqns(_step_jaxpr(kind, Lmax), "cond")
    assert len(conds) == (len(ms) > 1)
    for cond in conds:
        branches = [br.jaxpr for br in cond.params["branches"]]
        assert len(branches) == len(ms)
        assert not [e for e in branches[-1].eqns
                    if e.primitive.name == "slice"
                    and e.invars[0].aval.shape[1:2] == (Lmax,)
                    and e.invars[0].aval.ndim >= 3]
    mod, cache, params, x = _layer(kind)
    cache = {n: a[:, :Lmax] for n, a in cache.items()}
    positions = [Lmax - 1, 3, Lmax - 40]
    got, _ = _step(mod, params, x, positions, cache)
    want, _ = _step(mod, params, x, positions, cache, monkeypatch)
    np.testing.assert_allclose(got, want, atol=TOL[kind], rtol=TOL[kind])


@pytest.mark.parametrize("Lmax, block, count", [
    (384, 128, 3), (1024, 128, 8), (1280, 160, 8), (2048, 256, 8),
    (8192, 1024, 8), (128, 128, 1), (256, 128, 2), (1000, 128, 8),
    (1032, 136, 8), (48, 48, 1)])
def test_prefixes_from_the_cache_length_alone(Lmax, block, count):
    """At most 8 prefixes, blocks of at least 128 slots and a multiple of
    8, the last prefix all of the cache (ragged where ``Lmax`` is no
    whole blocks); one prefix up to one block."""
    ms = tr.prefix_lengths(Lmax)
    assert len(ms) == count <= 8 and ms[-1] == Lmax
    assert ms[:-1] == [block * (i + 1) for i in range(count - 1)]
    assert 0 < Lmax - (count - 1) * block <= block


@pytest.mark.parametrize("g", [1, 4], ids=["one_query_a_key_head", "group"])
@pytest.mark.parametrize("int8", [False, True], ids=["float", "int8"])
def test_step_attention_has_the_reference_numbers(g, int8):
    """``step_attention`` against ``reference_attention_gqa`` over the
    (dequantized) cache, float32, a mask of ragged lengths."""
    from orion_tpu.ops.attention import (reference_attention_gqa,
                                         step_attention)
    from orion_tpu.ops.quant import dequant_kv, quantize_kv

    rs = np.random.RandomState(g + 2 * int8)
    B, m, Hkv, D = 3, 40, 2, 16
    q = jnp.asarray(rs.standard_normal((B, 1, Hkv * g, D)), jnp.float32)
    k, v = (jnp.asarray(rs.standard_normal((B, m, Hkv, D)), jnp.float32)
            for _ in range(2))
    mask = jnp.arange(m)[None, None, :] <= jnp.asarray([39, 0, 17])[:, None,
                                                                   None]
    scales = ()
    if int8:
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
        scales = (ks, vs)
        want = reference_attention_gqa(
            q, dequant_kv(k, ks, jnp.float32), dequant_kv(v, vs, jnp.float32),
            mask, 0.25)
    else:
        want = reference_attention_gqa(q, k, v, mask, 0.25)
    got = step_attention(q, k, v, mask, 0.25, *scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)


# -- a group of query heads on several key heads: the kernel, interpreted ------

def _kernel_form(monkeypatch, block=None):
    """The trace believes it is for one TPU device (the kernel runs
    interpreted here); ``block``: the most slots a grid step holds."""
    from orion_tpu.ops import indexer
    from orion_tpu.ops.pallas import dense_step

    monkeypatch.setattr(indexer, "select_form", lambda: "kernel")
    if block:
        monkeypatch.setattr(dense_step, "BLOCK_SLOTS", block)
    return dense_step


def _grouped(dtype, B=4, Lmax=320, H=32, Hkv=8, D=64, seed=0):
    """q [B, 1, H, D] and the cache as the kernel takes it, k, v [B,
    Lmax, Hkv * D]; :func:`_heads` gives the reference its view."""
    rs = np.random.RandomState(seed)
    return [jnp.asarray(rs.standard_normal(shape), dtype) for shape in (
        (B, 1, H, D), (B, Lmax, Hkv * D), (B, Lmax, Hkv * D))]


def _heads(a, D):
    return a.reshape(a.shape[0], a.shape[1], -1, D)


# (H, Hkv, D, slots, the most slots a grid step holds, those it holds)
GROUPED = {
    # LFM2's heads over a cache of no whole blocks of 256: two of 160
    "lfm2_heads": (32, 8, 64, 320, 256, 160),
    # heads of whole lanes
    "heads_of_128": (8, 2, 128, 320, 256, 160),
    # the cell's cache at the block sizes measured, the one kept last
    "1280_slots_by_256": (32, 8, 64, 1280, 256, 256),
    "1280_slots_by_512": (32, 8, 64, 1280, 512, 320),
    "1280_slots": (32, 8, 64, 1280, None, 640),
}


@pytest.mark.parametrize("dtype, tol", [("float32", 2e-6),
                                        ("bfloat16", 2e-2)])
@pytest.mark.parametrize("shape", sorted(GROUPED))
def test_dense_step_has_the_reference_numbers(shape, dtype, tol,
                                              monkeypatch):
    """``dense_step`` interpreted over a cache laid ``[B, slots, Hkv *
    D]``, rows filled to unequal lengths, against
    ``reference_attention_gqa`` over the same numbers by head."""
    from orion_tpu.ops.attention import reference_attention_gqa
    from orion_tpu.ops.pallas import dense_step

    H, Hkv, D, Lmax, most, tk = GROUPED[shape]
    if most:
        monkeypatch.setattr(dense_step, "BLOCK_SLOTS", most)
    q, k, v = _grouped(dtype, Lmax=Lmax, H=H, Hkv=Hkv, D=D)
    assert dense_step.block_slots(Lmax) == tk
    pos = jnp.asarray([3, tk - 1, tk, Lmax - 1])
    mask = jnp.arange(Lmax)[None, None, :] <= pos[:, None, None]
    got = dense_step.dense_step(q, k, v, pos, D ** -0.5)
    want = reference_attention_gqa(q, _heads(k, D), _heads(v, D), mask,
                                   D ** -0.5)
    assert got.dtype == q.dtype and got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("positions", [[3, 159, 100], [160, 5, 319]],
                         ids=["first_block", "mixed_rows"])
def test_blocks_past_a_rows_position_are_never_read(positions, monkeypatch):
    """NaN in every slot past the block that holds a ROW's position
    (finer than the batch's furthest) leaves the kernel's output finite
    and equal to the clean cache's; the einsum over the whole cache
    would read them (probability 0 times NaN)."""
    from orion_tpu.ops.attention import reference_attention_gqa
    from orion_tpu.ops.pallas import dense_step

    monkeypatch.setattr(dense_step, "BLOCK_SLOTS", 256)    # two of 160
    q, k, v = _grouped("float32", B=3)
    pos = jnp.asarray(positions)
    past = jnp.arange(320)[None, :] >= ((pos // 160 + 1) * 160)[:, None]
    kp, vp = (jnp.where(past[:, :, None], jnp.nan, a) for a in (k, v))
    mask = jnp.arange(320)[None, None, :] <= pos[:, None, None]

    def reference(k, v):
        return reference_attention_gqa(q, _heads(k, 64), _heads(v, 64),
                                       mask, 0.125)

    got = dense_step.dense_step(q, kp, vp, pos, 0.125)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(reference(k, v)),
                               atol=2e-6, rtol=2e-6)
    read = ~np.isfinite(np.asarray(reference(kp, vp))).all(axis=(1, 2, 3))
    np.testing.assert_array_equal(read, np.asarray(past.any(axis=1)))


@pytest.mark.parametrize("where", sorted(POSITIONS))
def test_the_grouped_layer_steps_through_the_kernel(where, monkeypatch):
    """The ``gqa`` layer (4 query heads on 2 key heads) under the kernel
    form (blocks of 64 slots of its 384): its cache entry is laid ``[B,
    slots, Hkv * D]``, and over the same numbers so laid the step gives
    the whole-cache step's output and writes the same row."""
    mod, cache, params, x = _layer("gqa")
    cfg = mod.cfg
    want, new_whole = _step(mod, params, x, POSITIONS[where], cache,
                            monkeypatch)
    dense_step = _kernel_form(monkeypatch, block=64)
    assert dense_step.step_form(1, 4, 2, LMAX) == "kernel"
    entry = mod.cache_entry(cfg, 3, LMAX, jnp.float32)
    packed = (3, LMAX, cfg.num_kv_heads * cfg.head_dim)
    assert {n: a.shape for n, a in entry.items()} == {"k": packed,
                                                      "v": packed}
    calls = []
    kernel = dense_step.dense_step
    monkeypatch.setattr(dense_step, "dense_step",
                        lambda *a: calls.append(1) or kernel(*a))
    got, new = _step(mod, params, x, POSITIONS[where],
                     {n: a.reshape(packed) for n, a in cache.items()})
    assert calls == [1]
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
    for name in new:
        assert new[name].shape == packed
        np.testing.assert_array_equal(
            np.asarray(new[name]).reshape(new_whole[name].shape),
            np.asarray(new_whole[name]))


# what the one-token step of each cell's model sees: (preset, overrides,
# slots, int8 cache) -> ``kv_step_form`` on one TPU device
CELL_STEPS = {
    # 8 heads on 8, int8: the product-and-sum form under prefix_step
    "ppo1b-sync": ("pythia_1b", {}, 384, True, "prefix"),
    # latent attention: its own absorbed step
    "ppo-kanana-ep8-sync": ("kanana_2_30b_a3b", {}, 1024, False, "prefix"),
    "ppo-kimi-linear-ep32-sync": ("kimi_linear_48b_a3b", {}, 1024, False,
                                  "prefix"),
    # 30 heads on 30
    "ppo-olmo-hybrid-vp8-sync": ("olmo_hybrid_7b", {}, 1024, False,
                                 "prefix"),
    # a selection: sparse_step, not this step
    "ppo-keye-dsa-ep8-sync": ("keye_vl2_30b_a3b", {}, 8192, False, None),
    # 8 query heads on the ONE key head a quarter holds: the einsum has
    # nothing to re-lay
    "ppo-nemotron-h-tp4-sync": ("nemotron_3_super_120b_a12b",
                                {"head_share": (0, 4)}, 1280, False,
                                "prefix"),
    # a block's 4 or 8 rows a sequence
    "ppo-sdar-ep8-sync": ("sdar_30b_a3b", {}, 1024, False, "prefix"),
    # 32 heads on 8, one token: the kernel
    "ppo-lfm2-ep4-sync": ("lfm2_8b_a1b", {}, 1280, False, "kernel"),
    # ... but an int8 cache, and a cache of no whole blocks, are not its
    "lfm2, int8": ("lfm2_8b_a1b", {}, 1280, True, "prefix"),
    "lfm2, 1000 slots": ("lfm2_8b_a1b", {}, 1000, False, "prefix"),
}


@pytest.mark.parametrize("device", ["kernel", "jnp"], ids=["tpu", "cpu"])
@pytest.mark.parametrize("cell", sorted(CELL_STEPS))
def test_the_step_form_from_what_the_step_sees(cell, device, monkeypatch):
    """The form follows the queries a row, the heads held, the cache's
    dtype and length, and the trace's target: the kernel for LFM2's
    shapes on one TPU device alone; everything as it was on the CPU (and
    under a mesh: ``select_form``).  The cache's layout follows the
    form: rank 3 for ``ppo-lfm2-ep4-sync`` on one TPU device, 4 for
    every other cell and everywhere else."""
    import dataclasses

    from orion_tpu.ops import indexer

    preset, overrides, slots, int8, want = CELL_STEPS[cell]
    monkeypatch.setattr(indexer, "select_form", lambda: device)
    cfg = dataclasses.replace(getattr(ModelConfig, preset)(), **overrides)
    got = tr.decode_attrs(cfg, [200, 256], slots, slots - 256, int8)
    if device == "jnp" and want == "kernel":
        want = "prefix"
    assert got.get("kv_step_form") == want
    assert ("kv_step_slots" in got) == (want is not None)
    # the cache is laid out for the step: K and V [B, slots, Hkv * D]
    # under the kernel, whose rows fill their lanes, and per head
    # elsewhere (the latent cache has no K and V)
    mixers = dict.fromkeys(m for m, _ in cfg.layer_kinds()
                           if m and tr.MIXERS[m].per_head_kv)
    ranks = {a.ndim for m in mixers for n, a in jax.eval_shape(
        lambda: tr.cache_entry(cfg, m, 2, slots, jnp.bfloat16,
                               quantized=int8)).items() if n in "kv"}
    assert ranks <= {3 if want == "kernel" else 4}
    if tr.Attention in tr.kinds(cfg):
        assert ranks and got["kv_cache_lane_fill"] == (
            1.0 if want == "kernel" else min(cfg.head_dim, 128) / 128)
        assert (want == "kernel") == (
            tr.Attention.kv_step_form(cfg, slots, int8) == "kernel")
    else:
        assert "kv_cache_lane_fill" not in got


def _generate(arch, monkeypatch, whole, **rollout):
    """tokens, logprobs, policy logprobs of a right-padded batch whose
    rows cross from the first block of a 256-slot cache into the second
    during the 24 steps (prompts of 100-120 of 232 slots)."""
    monkeypatch.setattr(tr, "_PREFIX_SLOTS", 10 ** 9 if whole else 128)
    cfg = ModelConfig.tiny(arch, dtype="float32", max_seq_len=256)
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(2), cfg)
    P, T = 232, 24
    assert len(tr.prefix_lengths(tr.cache_slots(P + T))) == (1 if whole
                                                             else 2)
    eng = RolloutEngine(model, cfg, RolloutConfig(
        max_prompt_len=P, max_new_tokens=T, temperature=1.0, **rollout))
    eng.load_weights(params)
    lens = np.asarray([120, 100, 111], np.int32)
    ids = np.random.RandomState(5).randint(2, 256, (3, P))
    prompts = np.where(np.arange(P)[None, :] < lens[:, None], ids,
                       0).astype(np.int32)
    return eng.generate(jnp.asarray(prompts), jnp.asarray(lens),
                        jax.random.key(7)).to_host()


@pytest.mark.parametrize("arch, rollout", [
    ("olmo_hybrid", {}), ("neox", {"quantize_kv": True}),
    ("deepseek_v3", {}), ("nemotron_h", {}), ("kimi_linear", {})],
    ids=["olmo_hybrid", "int8_pythia", "deepseek_v3", "nemotron_h",
         "kimi_linear"])
def test_the_engine_generates_what_the_whole_cache_step_did(
        arch, rollout, monkeypatch):
    got = _generate(arch, monkeypatch, False, **rollout)
    want = _generate(arch, monkeypatch, True, **rollout)
    np.testing.assert_array_equal(got.completions, want.completions)
    np.testing.assert_array_equal(got.completion_lens, want.completion_lens)
    np.testing.assert_allclose(got.logprobs, want.logprobs, atol=5e-5,
                               rtol=0)
    np.testing.assert_allclose(got.policy_logprobs, want.policy_logprobs,
                               atol=5e-5, rtol=0)


def test_the_engine_generates_under_the_kernel_what_the_einsum_did(
        monkeypatch):
    """The tiny llama (4 query heads on 2 key heads): 24 steps through
    the interpreted kernel over blocks of 64 of 256 slots."""
    want = _generate("llama", monkeypatch, True)
    _kernel_form(monkeypatch, block=64)
    got = _generate("llama", monkeypatch, False)
    np.testing.assert_array_equal(got.completions, want.completions)
    np.testing.assert_allclose(got.logprobs, want.logprobs, atol=5e-5,
                               rtol=0)


def _engine(arch="llama", **rollout):
    cfg = ModelConfig.tiny(arch, dtype="float32")
    return RolloutEngine(Transformer(cfg), cfg, RolloutConfig(**rollout))


@pytest.mark.parametrize("lens, Lmax, T, form, slots", [
    # the Olmo cell: steps at 24 .. 534, blocks of 128:
    # 104 x 128 + 128 x (256 + 384 + 512) + 23 x 640 over 511 steps
    ([24] * 32, 1024, 512, "prefix", 175488 / 511),
    # the longest prompt decides
    ([21, 26, 24], 1024, 512, "prefix", 176512 / 511),
    # ppo1b-sync: 26 .. 152 of 384: 102 x 128 + 25 x 256 over 127
    ([26, 21], 384, 128, "prefix", 19456 / 127),
    # full-length prompts: 512 .. 1022
    ([512], 1024, 512, "prefix", (128 * (640 + 768 + 896) + 127 * 1024)
     / 511),
    # no whole blocks, 24 .. 510 of 1000: the blocks all the same
    ([24], 1000, 488, "prefix", (104 * 128 + 128 * (256 + 384) + 127 * 512)
     / 487),
    # the ragged last prefix: 900 .. 998 of 1000
    ([900], 1000, 100, "prefix", 1000.0),
    ([24], 128, 64, "whole", 128.0)])
def test_step_read_from_lengths(lens, Lmax, T, form, slots):
    """What the engine says of the one-token step for ``rollout.
    dispatch``: its form and the slots one row's step reads a layer, the
    mean over the steps (346-odd of 1024 in the Olmo cell)."""
    got = _engine(max_prompt_len=Lmax - T, max_new_tokens=T) \
        .dispatch_attrs((len(lens), Lmax - T), lens)
    assert got["kv_step_form"] == form
    assert got["kv_step_slots"] == pytest.approx(slots)
    assert isinstance(got["kv_step_slots"], float)


def test_the_kernels_read_from_lengths(monkeypatch):
    """Under the kernel ``kv_step_slots`` is the blocks (256 slots) up
    to each ROW's filled slot, the mean over rows and steps: row 0 at 24
    .. 534, row 1 at 300 .. 810 of 1024 slots; an int8 cache steps as it
    did."""
    _kernel_form(monkeypatch, block=256)
    got = _engine(max_prompt_len=512, max_new_tokens=512) \
        .dispatch_attrs((2, 512), [24, 300])
    assert got["kv_step_form"] == "kernel"
    assert got["kv_step_slots"] == pytest.approx(
        (232 * 256 + 256 * 512 + 23 * 768
         + 212 * 512 + 256 * 768 + 43 * 1024) / (2 * 511))
    got = _engine(max_prompt_len=512, max_new_tokens=512, quantize_kv=True) \
        .dispatch_attrs((2, 512), [24, 300])
    assert got["kv_step_form"] == "prefix"


@pytest.mark.parametrize("arch, rollout", [
    ("llama", {"paged": True}), ("keye_dsa", {}), ("deepseek_v3", {})],
    ids=["paged", "a_selection", "latent"])
def test_the_engine_says_nothing_where_no_step_reads_a_prefix(arch, rollout):
    """Nothing under ``paged`` and for a model none of whose mixers
    goes through ``prefix_step`` (``Kind.steps_over_prefix``)."""
    got = _engine(arch, max_prompt_len=232, max_new_tokens=24,
                  **rollout).dispatch_attrs((1, 232), [100])
    assert ("kv_step_form" in got) == ("kv_step_slots" in got) \
        == (arch == "deepseek_v3")


@pytest.mark.parametrize("preset, extra, want", [
    # 104 prompt slots + 24 new = one block of 128: the whole cache
    ("tiny", ["model.max_seq_len=128", "rollout.max_prompt_len=104"],
     ("whole", 128.0)),
    # 232 + 24 = two blocks; synthetic prompts end before slot 104, so
    # every step stands in the first
    ("tiny_deepseek_v3", ["model.max_seq_len=256",
                          "rollout.max_prompt_len=232"], ("prefix", 128.0)),
    # a selection everywhere: not this step's
    ("tiny_keye_dsa", ["model.max_seq_len=64", "rollout.max_prompt_len=40",
                       "data.synthetic_min_len=30",
                       "data.synthetic_max_len=40"], None),
    # the tiny llama's 4 query heads on 2 key heads under the kernel
    # form: blocks of 64 slots, every row's 23 steps cross from its
    # first into its second
    ("tiny", ["model.max_seq_len=256", "rollout.max_prompt_len=232",
              "data.synthetic_min_len=50", "data.synthetic_max_len=60"],
     ("kernel", (64.0, 128.0)))])
def test_the_rollout_span_says_how_the_step_reads_its_cache(
        preset, extra, want, tmp_path, monkeypatch):
    from orion_tpu import launch

    if want and want[0] == "kernel":
        _kernel_form(monkeypatch, block=64)

    launch.main([
        "ppo", f"model_preset={preset}", "share_backbone=true",
        "rollout.max_new_tokens=24", "rollout_batch_size=4",
        "minibatch_size=2", "num_epochs=1", "data.dataset=synthetic",
        "reward=length", "total_iterations=1", "obs.trace=true",
        f"log_dir={tmp_path}"] + extra)
    with open(tmp_path / f"spans-{os.getpid()}.json") as f:
        events = json.load(f)["traceEvents"]
    (span,) = [e["args"] for e in events if e["name"] == "rollout.dispatch"]
    # beside it, on this span and the update's: the query heads a grid
    # step of the flash kernels holds (ISSUE 44); the tiny llama and
    # keye_dsa have 4 on 2 key heads, latent attention one a key head
    (update,) = [e["args"] for e in events if e["name"] == "update"]
    heads = 1 if preset == "tiny_deepseek_v3" else 2
    assert span["attn_heads_a_step"] == update["attn_heads_a_step"] == heads
    if want is None:
        assert "kv_step_form" not in span and "kv_step_slots" not in span
        return
    if want[0] == "kernel":
        low, high = want[1]
        assert span["kv_step_form"] == "kernel"
        assert low < span["kv_step_slots"] < high
        return
    assert (span["kv_step_form"], span["kv_step_slots"]) == want
    assert "," not in span["kv_step_form"]


@pytest.mark.parametrize("preset, overrides, heads", [
    ("pythia_1b", {}, 1),
    ("keye_vl2_30b_a3b", {}, 8),
    # ppo-nemotron-h-tp4-sync: a quarter of 32 query heads on 2 key heads
    ("nemotron_3_super_120b_a12b", {"head_share": (0, 4)}, 8),
    ("nemotron_3_super_120b_a12b", {}, 16),
    ("tiny_nemotron_h", {}, 2),
    ("tiny_keye_dsa", {}, 2),
    ("tiny_olmo_hybrid", {}, 1),
    ("olmo_hybrid_7b", {}, 1),
    ("kanana_2_30b_a3b", {}, 1),
    ("kimi_linear_48b_a3b", {}, 1),
    # latent attention expands a key head a query head; no attention
    # layer groups in a model of KDA and latent layers
    ("tiny_deepseek_v3", {}, 1),
    ("tiny_kimi_linear", {}, 1),
])
def test_query_heads_a_grid_step_from_the_configuration(preset, overrides,
                                                        heads):
    """``attn_heads_a_step``, the spans' attribute: the query heads that
    share a key head in the layers that keep a per-head K/V cache, of
    what ``head_share`` leaves here (the ``n_rep`` the flash kernels
    read from their operands' shapes); 1 where no layer groups."""
    import dataclasses

    from orion_tpu.config import ModelConfig

    cfg = dataclasses.replace(getattr(ModelConfig, preset)(), **overrides)
    assert cfg.attn_heads_a_step() == heads
