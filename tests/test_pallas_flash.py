"""Pallas flash-attention kernel vs the jnp reference (SURVEY.md §4
"Numerics": kernels validated against reference attention in interpret
mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.ops.attention import (reference_attention,
                                     reference_attention_gqa, repeat_kv)
from orion_tpu.ops.pallas.flash_attention import flash_attention_gqa


def _make(B=2, Lq=32, Lk=32, H=4, Hkv=2, D=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, Lq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Lk, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Lk, Hkv, D), dtype)
    return q, k, v


def _ref(q, k, v, qpos, scale):
    n_rep = q.shape[2] // k.shape[2]
    Lk = k.shape[1]
    mask = jnp.arange(Lk)[None, None, :] <= qpos[:, :, None]
    return reference_attention(q, repeat_kv(k, n_rep), repeat_kv(v, n_rep),
                               mask, scale)


def test_forward_matches_reference_causal():
    q, k, v = _make()
    qpos = jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32), (2, 32))
    scale = 1.0 / 16 ** 0.5
    out = flash_attention_gqa(q, k, v, qpos, scale)
    ref = _ref(q, k, v, qpos, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_forward_ragged_positions():
    """Chunked-prefill style: positions offset per sequence, Lk > Lq."""
    q, k, v = _make(Lq=16, Lk=64)
    # sequence 0 continues from position 5, sequence 1 from 30
    starts = jnp.asarray([5, 30], jnp.int32)
    qpos = starts[:, None] + jnp.arange(16, dtype=jnp.int32)[None, :]
    scale = 0.25
    out = flash_attention_gqa(q, k, v, qpos, scale)
    ref = _ref(q, k, v, qpos, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_backward_matches_reference():
    q, k, v = _make(B=1, Lq=16, Lk=16, H=4, Hkv=2, D=8, seed=3)
    qpos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (1, 16))
    scale = 1.0 / 8 ** 0.5

    def loss_flash(q, k, v):
        o = flash_attention_gqa(q, k, v, qpos, scale)
        return jnp.sum(o * jnp.cos(o))  # nontrivial cotangent

    def loss_ref(q, k, v):
        o = _ref(q, k, v, qpos, scale)
        return jnp.sum(o * jnp.cos(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_model_forward_flash_matches_reference_impl():
    """End-to-end: Transformer with attention_impl='flash' equals the
    reference impl on a full forward."""
    from orion_tpu.config import ModelConfig
    from orion_tpu.models import Transformer, init_params

    cfg_ref = ModelConfig.tiny(dtype="float32")
    cfg_flash = ModelConfig.tiny(dtype="float32", attention_impl="flash")
    model_ref = Transformer(cfg_ref)
    model_flash = Transformer(cfg_flash)
    params = init_params(model_ref, jax.random.key(0), cfg_ref)

    ids = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg_ref.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    logits_ref, _ = model_ref.apply({"params": params}, ids, pos)
    logits_flash, _ = model_flash.apply({"params": params}, ids, pos)
    np.testing.assert_allclose(np.asarray(logits_flash),
                               np.asarray(logits_ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_grad_through_model():
    """Training-path check: grads flow through the flash kernel inside
    the full model and match the reference-impl grads."""
    from orion_tpu.config import ModelConfig
    from orion_tpu.models import Transformer, init_params

    cfg_ref = ModelConfig.tiny(dtype="float32")
    cfg_flash = ModelConfig.tiny(dtype="float32", attention_impl="flash")
    model_ref = Transformer(cfg_ref)
    model_flash = Transformer(cfg_flash)
    params = init_params(model_ref, jax.random.key(0), cfg_ref)
    ids = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg_ref.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8))

    def loss(model):
        def f(p):
            logits, _ = model.apply({"params": p}, ids, pos)
            return jnp.mean(jax.nn.logsumexp(logits, axis=-1))
        return f

    g_ref = jax.grad(loss(model_ref))(params)
    g_flash = jax.grad(loss(model_flash))(params)
    flat_ref = jax.tree.leaves(g_ref)
    flat_flash = jax.tree.leaves(g_flash)
    for a, b in zip(flat_flash, flat_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# "auto" dispatch (VERDICT r1 weak #3: kernels must be the default path)
# ---------------------------------------------------------------------------


def test_auto_resolves_to_reference_off_tpu():
    """On the CPU harness, impl="auto" must take the exact einsum path
    (bit-identical to reference_attention_gqa, i.e. no Pallas kernel)."""
    from orion_tpu.ops.attention import attention, reference_attention_gqa

    q, k, v = _make()
    qpos = jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32), (2, 32))
    scale = 1.0 / 16 ** 0.5
    mask = jnp.arange(32)[None, None, :] <= qpos[:, :, None]
    auto = attention(q, k, v, mask, scale, impl="auto", q_positions=qpos)
    ref = jax.jit(reference_attention_gqa, static_argnums=(4,))(
        q, k, v, mask, scale)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))
    # and the grouped einsum itself matches the repeat_kv formulation
    np.testing.assert_allclose(np.asarray(auto),
                               np.asarray(_ref(q, k, v, qpos, scale)),
                               rtol=2e-5, atol=2e-5)


def test_auto_routes_to_flash_on_tpu(monkeypatch):
    """Force target_platform()="tpu" (interpret kept on): auto must call
    the Pallas flash kernel and still match the reference numerics."""
    import orion_tpu.ops.pallas as pallas_pkg
    import orion_tpu.ops.pallas.flash_attention as flash_mod
    from orion_tpu.ops.attention import attention

    monkeypatch.setattr(pallas_pkg, "target_platform", lambda: "tpu")
    # flash_attention bound interpret_mode at import; keep it interpreted.
    monkeypatch.setattr(flash_mod, "interpret_mode", lambda: True)
    called = {}
    orig = flash_mod.flash_attention_gqa

    def spy(*a, **kw):
        called["flash"] = True
        return orig(*a, **kw)

    monkeypatch.setattr(
        "orion_tpu.ops.pallas.flash_attention.flash_attention_gqa", spy)
    q, k, v = _make()
    qpos = jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32), (2, 32))
    scale = 1.0 / 16 ** 0.5
    mask = jnp.arange(32)[None, None, :] <= qpos[:, :, None]
    auto = attention(q, k, v, mask, scale, impl="auto", q_positions=qpos)
    assert called.get("flash"), "auto on TPU did not route to flash"
    ref = _ref(q, k, v, qpos, scale)
    np.testing.assert_allclose(np.asarray(auto), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # Decode steps (Lq == 1) must stay on the reference path.
    called.clear()
    out1 = attention(q[:, :1], k, v, mask[:, :1], scale, impl="auto",
                     q_positions=qpos[:, :1])
    assert "flash" not in called
    np.testing.assert_allclose(
        np.asarray(out1), np.asarray(_ref(q, k, v, qpos, scale))[:, :1],
        rtol=2e-5, atol=2e-5)


def test_target_platform_respects_mesh_context():
    """A CPU fake-device mesh must win over the default backend (the
    driver-dryrun fallback scenario)."""
    from jax.sharding import Mesh

    from orion_tpu.ops.pallas import target_platform

    assert target_platform() == "cpu"
    with Mesh(np.array(jax.devices("cpu")[:4]).reshape(2, 2), ("a", "b")):
        assert target_platform() == "cpu"


def test_flash_on_mesh_matches_reference():
    """Under a multi-device ``with mesh:`` the flash kernel runs inside
    a fully-manual shard_map (jax refuses to auto-partition a Mosaic
    kernel): batch over (data, fsdp), heads over tensor.  Same numbers
    as the reference, values and grads, GQA included."""
    from orion_tpu.config import MeshConfig
    from orion_tpu.ops.attention import attention
    from orion_tpu.parallel.mesh import make_mesh

    B, L, H, Hkv, D = 4, 32, 4, 2, 16
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, L, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, L, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, L, Hkv, D), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    mask = pos[:, :, None] >= jnp.arange(L)[None, None, :]

    def loss(impl):
        def f(q, k, v):
            return jnp.sum(attention(q, k, v, mask, D ** -0.5, impl=impl,
                                     q_positions=pos) ** 2)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))

    mesh = make_mesh(MeshConfig(data=2, fsdp=2, tensor=2))
    with mesh:
        got, got_g = loss("flash")(q, k, v)
        ref, ref_g = loss("reference")(q, k, v)
    np.testing.assert_allclose(got, ref, rtol=1e-4)
    for a, b in zip(got_g, ref_g):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# bf16 inputs: the kernels hand the MXU the operands in the dtype they
# arrived in, accumulate in float32, and round p / ds for the second
# product as reference_attention rounds probs (module docstring,
# "Precision").  The float32 cases above keep their tolerances: float32
# callers compute what they did.
# ---------------------------------------------------------------------------

# bf16 keeps 8 significant bits: neighbouring values lie 2^-8 of their
# size apart.  Tolerances are that step at the reference's largest
# entry, times a stated factor — derived, not fitted:
# - forward, 4: flash and the reference each round their output (half a
#   step each) and their probabilities (each at most 2^-9 * max|v|, and
#   max|v| <= 3 * max|out| on these inputs);
# - gradients, 8: two chained rounded products on either side (p, then
#   ds), and the reference's own bf16 backward rounds its cotangents.
BF16_STEP = 2.0 ** -8
FWD_STEPS, GRAD_STEPS = 4, 8

# (H, Hkv, D, Dv): the head shapes of pythia-1b, a llama-3-8B-like GQA
# and latent attention's expanded keys / values.
BF16_HEADS = {"d256_h8": (8, 8, 256, 256),
              "d128_gqa32_8": (32, 8, 128, 128),
              "d192_dv128": (4, 4, 192, 128)}


def _make_bf16(heads, Lq, Lk, B=2, seed=5):
    H, Hkv, D, Dv = BF16_HEADS[heads]
    ks = jax.random.split(jax.random.key(seed), 4)
    bf = jnp.bfloat16
    return (jax.random.normal(ks[0], (B, Lq, H, D), bf),
            jax.random.normal(ks[1], (B, Lk, Hkv, D), bf),
            jax.random.normal(ks[2], (B, Lk, Hkv, Dv), bf),
            jax.random.normal(ks[3], (B, Lq, H, Dv), bf))


def _positions(layout):
    """(Lq, Lk, q_positions [2, Lq]) — causal, or the chunked-prefill
    layout of test_forward_ragged_positions (rows continue from 5 and
    30 over a 64-slot cache)."""
    if layout == "causal":
        return 64, 64, jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32),
                                        (2, 64))
    starts = jnp.asarray([5, 30], jnp.int32)
    return 32, 64, starts[:, None] + jnp.arange(32, dtype=jnp.int32)[None]


def _assert_within_bf16_steps(got, ref, steps, name=""):
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    tol = steps * BF16_STEP * np.max(np.abs(ref))
    worst = np.max(np.abs(got - ref))
    assert worst <= tol, (name, worst, tol)


@pytest.mark.parametrize("layout", ["causal", "ragged"])
@pytest.mark.parametrize("heads", sorted(BF16_HEADS))
def test_bf16_forward_matches_reference(heads, layout):
    Lq, Lk, qpos = _positions(layout)
    q, k, v, _ = _make_bf16(heads, Lq, Lk)
    scale = q.shape[-1] ** -0.5
    mask = jnp.arange(Lk)[None, None, :] <= qpos[:, :, None]
    out = flash_attention_gqa(q, k, v, qpos, scale, 32, 32)
    assert out.dtype == jnp.bfloat16 and out.shape == q.shape[:3] + (
        v.shape[-1],)
    _assert_within_bf16_steps(out, reference_attention_gqa(q, k, v, mask, scale),
                              FWD_STEPS)


@pytest.mark.parametrize("layout", ["causal", "ragged"])
@pytest.mark.parametrize("heads", sorted(BF16_HEADS))
def test_bf16_backward_matches_reference(heads, layout):
    Lq, Lk, qpos = _positions(layout)
    q, k, v, dout = _make_bf16(heads, Lq, Lk)
    scale = q.shape[-1] ** -0.5
    mask = jnp.arange(Lk)[None, None, :] <= qpos[:, :, None]
    _, vjp_flash = jax.vjp(
        lambda q, k, v: flash_attention_gqa(q, k, v, qpos, scale, 32, 32),
        q, k, v)
    _, vjp_ref = jax.vjp(lambda q, k, v: reference_attention_gqa(q, k, v, mask, scale),
                         q, k, v)
    for gf, gr, name in zip(vjp_flash(dout), vjp_ref(dout), "qkv"):
        assert gf.dtype == jnp.bfloat16 and gf.shape == gr.shape
        _assert_within_bf16_steps(gf, gr, GRAD_STEPS, name)


def test_bf16_chunk_rotated_kv_positions():
    """The ring path's per-chunk entries in bf16: a zigzag query chunk
    over a ROTATED kv chunk (piecewise-contiguous, not monotone: one
    block pair is wholly in the future and skipped).  The chunk holds
    every key a row may see, so its lse is the global one and
    flash_chunk_grads must give the reference's gradients."""
    from orion_tpu.ops.pallas.flash_attention import (flash_chunk_fwd,
                                                      flash_chunk_grads)

    B, L = 2, 32
    q, k, v, dout = _make_bf16("d192_dv128", L, L)
    scale = q.shape[-1] ** -0.5
    ar = jnp.arange(16, dtype=jnp.int32)
    qpos = jnp.broadcast_to(jnp.concatenate([ar, 48 + ar]), (B, L))
    kvpos = jnp.broadcast_to(jnp.concatenate([32 + ar, ar]), (B, L))
    mask = kvpos[:, None, :] <= qpos[:, :, None]
    out, lse = flash_chunk_fwd(q, k, v, qpos, kvpos, scale, 16, 16)
    ref, vjp_ref = jax.vjp(lambda q, k, v: reference_attention_gqa(q, k, v, mask, scale),
                           q, k, v)
    assert out.dtype == jnp.bfloat16 and lse.dtype == jnp.float32
    _assert_within_bf16_steps(out, ref, FWD_STEPS)
    grads = flash_chunk_grads(q, k, v, qpos, kvpos, out, lse, dout, scale,
                              16, 16)
    for gf, gr, name in zip(grads, vjp_ref(dout), "qkv"):
        assert gf.dtype == jnp.bfloat16
        _assert_within_bf16_steps(gf, gr, GRAD_STEPS, name)


def _walk_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its equations'
    parameters (the pallas_call's kernel, pl.when's branches, ...)."""
    from jax.extend import core as jcore

    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    yield from _walk_eqns(sub)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_kernel_products_take_the_input_dtype(dtype):
    """The mechanism's "does it engage" check, read from the jaxprs of
    the three kernels: every one of the five products (q k^T, p v,
    do v^T, ds k, p^T do / ds^T q — nine dot_generals over forward, dq
    and dkv) takes both operands in the INPUT dtype and gives
    float32, and with bf16 inputs nothing inside a kernel is converted
    from bf16 up to float32 (that would be a q / k / v / do block on
    its way to a float32 product: the upcast coming back)."""
    H, Hkv, D, Dv = BF16_HEADS["d192_dv128"]
    qpos = jnp.broadcast_to(jnp.arange(64, dtype=jnp.int32), (1, 64))
    q = jnp.zeros((1, 64, H, D), dtype)
    k = jnp.zeros((1, 64, Hkv, D), dtype)
    v = jnp.zeros((1, 64, Hkv, Dv), dtype)

    def fwd_bwd(q, k, v):
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention_gqa(q, k, v, qpos, 0.1, 64, 64),
            q, k, v)
        return vjp(out)

    kernels = [e for e in _walk_eqns(jax.make_jaxpr(fwd_bwd)(q, k, v).jaxpr)
               if e.primitive.name == "pallas_call"]
    assert len(kernels) == 3  # flash_fwd, flash_bwd_dq, flash_bwd_dkv
    dots, upcasts = [], []
    for call in kernels:
        for e in _walk_eqns(call.params["jaxpr"]):
            if e.primitive.name == "dot_general":
                dots.append(e)
            elif (e.primitive.name == "convert_element_type"
                  and e.invars[0].aval.dtype == jnp.bfloat16
                  and e.params["new_dtype"] == jnp.float32):
                upcasts.append(e)
    assert len(dots) == 2 + 3 + 4     # one 64 x 64 tile in each kernel
    for e in dots:
        assert [x.aval.dtype for x in e.invars] == [dtype, dtype], e
        assert e.outvars[0].aval.dtype == jnp.float32, e
    assert not upcasts, upcasts


# -- a grid step holds the query heads of one key head (ISSUE 44) ----------

def _group_case(n_rep, sel, Dv=16, ragged=False, dtype=jnp.float32,
                Hkv=2, L=256, D=16):
    """Operands of one grouped call at tiles of 128 (L = 256: the second
    q tile's extent is two kv tiles wide, the first's one), their
    selection (or None) and positions."""
    B, H = 2, Hkv * n_rep
    ks = jax.random.split(jax.random.key(7 * n_rep + Dv), 5)
    q = jax.random.normal(ks[0], (B, L, H, D), dtype)
    k = jax.random.normal(ks[1], (B, L, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, L, Hkv, Dv), dtype)
    dout = jax.random.normal(ks[3], (B, L, H, Dv), dtype)
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    if ragged:     # the second row's first 37 slots hold no token of its own
        pos = jnp.maximum(pos - jnp.array([[0], [37]], jnp.int32), 0)
    sel_t = None
    if sel:     # half the pairs, and slot 0 always: no query without a key
        sel_t = (jax.random.uniform(ks[4], (B, L, L)) < 0.5).at[:, 0].set(
            True).astype(jnp.int8)
    return q, k, v, dout, pos, sel_t


def _one_head_a_step(fn, q, k, v, dout, n_rep):
    """``fn(q, k, v)`` and its gradients with k and v repeated a query
    head (``n_rep`` = 1 inside: the kernels' one-head program, which is
    the parent's), the group's dk and dv summed in float32."""
    out, vjp = jax.vjp(fn, q, repeat_kv(k, n_rep), repeat_kv(v, n_rep))
    dq, dk, dv = vjp(dout)
    B, L, H, _ = dk.shape

    def group_sum(g):
        return g.astype(jnp.float32).reshape(
            B, L, H // n_rep, n_rep, -1).sum(axis=3)

    return out, dq, group_sum(dk), group_sum(dv)


def _assert_float32_close(got, want, name, steps=4):
    """Equal to the order of a few float32 sums: ``steps`` ulps of the
    largest entry (bf16 results: one step of theirs)."""
    eps = jnp.finfo(got.dtype).eps
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    tol = steps * float(eps) * max(1.0, float(np.abs(want).max()))
    assert np.abs(got - want).max() <= tol, (name, np.abs(got - want).max(),
                                             tol)


GROUP_CASES = {
    # name: (n_rep, selection, value width, ragged positions, dtype)
    "one_head": (1, False, 16, False, jnp.float32),
    "one_head_selected": (1, True, 16, False, jnp.float32),
    "pair": (2, False, 16, False, jnp.float32),
    "pair_selected_ragged": (2, True, 16, True, jnp.float32),
    "eight": (8, False, 16, False, jnp.float32),
    "eight_selected": (8, True, 16, False, jnp.float32),
    "pair_values_wider_than_keys": (2, False, 32, False, jnp.float32),
    "four_selected_bf16": (4, True, 16, True, jnp.bfloat16),
}


@pytest.mark.parametrize("name", sorted(GROUP_CASES))
def test_a_group_of_heads_a_step_equals_one_head_a_step(name):
    """The kernels with the ``n_rep`` query heads of a key head in one
    grid step against the same kernels with one head a step (k and v
    repeated: the parent's program).  One head a key head IS that
    program: bit for bit.  A group's forward sums its extent tile by
    tile and its dk / dv over the heads inside the kernel: equal to the
    order of float32 sums, and dk / dv are rounded once."""
    from orion_tpu.ops.pallas.flash_attention import sparse_attention_gqa

    n_rep, sel, Dv, ragged, dtype = GROUP_CASES[name]
    q, k, v, dout, pos, sel_t = _group_case(n_rep, sel, Dv, ragged, dtype,
                                            Hkv=1 if n_rep == 8 else 2)

    def fn(q, k, v):
        if sel_t is None:
            return flash_attention_gqa(q, k, v, pos, 0.25, 128, 128)
        return sparse_attention_gqa(q, k, v, pos, sel_t, 0.25, 128, 128)

    out, vjp = jax.vjp(fn, q, k, v)
    got = (out,) + vjp(dout)
    want = _one_head_a_step(fn, q, k, v, dout, n_rep)
    assert got[2].shape == k.shape and got[3].shape == v.shape
    assert got[2].dtype == got[3].dtype == dtype
    for g, w, what in zip(got, want, ("out", "dq", "dk", "dv")):
        if n_rep == 1:
            np.testing.assert_array_equal(
                np.asarray(g, np.float32), np.asarray(w, np.float32), what)
        else:
            _assert_float32_close(g, w, what,
                                  steps=1 if dtype == jnp.bfloat16 else 16)
    # and the einsum under the same mask, so that both are not wrong alike
    mask = jnp.arange(q.shape[1])[None, None, :] <= pos[:, :, None]
    if sel_t is not None:
        mask = mask & (sel_t.swapaxes(1, 2) != 0)
    ref = reference_attention_gqa(q, repeat_kv(k, n_rep), repeat_kv(v, n_rep),
                                  mask, 0.25)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 2e-5,
                               atol=2e-2 if dtype == jnp.bfloat16 else 2e-5)


CHUNKS = {
    # name: (q positions, kv positions) of the two tiles of 128 each
    # a zigzag query chunk over a ROTATED kv chunk: the first q tile's
    # rows under position 64 see nothing of the first kv tile of their
    # two-tile extent, all of them something of the second
    "rotated": ((0, 384), (64, 0)),
    # the first q tile sees no key at all (both kv tiles skipped: l = 0);
    # the second's rows under position 300 see none either, INSIDE a
    # tile that is computed (all of it masked for them: lse = NEG_INF)
    "future": ((0, 200), (300, 600)),
}


@pytest.mark.parametrize("n_rep", [2, 8])
@pytest.mark.parametrize("chunk", sorted(CHUNKS))
def test_a_group_over_a_ring_chunk_equals_one_head_a_step(chunk, n_rep):
    """The ring entries (``kv_positions`` an operand, no fetch clamp)
    group the same way, rows without a key included: out = 0 and lse at
    ``NEG_INF`` where every tile was skipped (the ``l = 0`` guard), the
    parent's mean of v where a computed tile was all masked, and a
    backward whose ``exp(s - lse)`` is still 0 on such rows (``_DEAD``:
    the forward's ``NEG_INF`` fill would make it ``exp(0)``)."""
    from orion_tpu.ops.pallas import NEG_INF
    from orion_tpu.ops.pallas.flash_attention import (flash_chunk_fwd,
                                                      flash_chunk_grads)

    q, k, v, dout, _, _ = _group_case(n_rep, False, Hkv=1, L=256)
    ar = jnp.arange(128, dtype=jnp.int32)
    (q0, q1), (k0, k1) = CHUNKS[chunk]
    qpos = jnp.broadcast_to(jnp.concatenate([q0 + ar, q1 + ar]), (2, 256))
    kvpos = jnp.broadcast_to(jnp.concatenate([k0 + ar, k1 + ar]), (2, 256))

    def run(q, k, v):
        out, lse = flash_chunk_fwd(q, k, v, qpos, kvpos, 0.25, 128, 128)
        return (out, lse) + tuple(flash_chunk_grads(
            q, k, v, qpos, kvpos, out, lse, dout, 0.25, 128, 128))

    got = run(q, k, v)
    out, lse, dq, dk, dv = run(q, repeat_kv(k, n_rep), repeat_kv(v, n_rep))
    want = (out, lse, dq, dk.reshape(2, 256, 1, n_rep, -1).sum(axis=3),
            dv.reshape(2, 256, 1, n_rep, -1).sum(axis=3))
    for g, w, what in zip(got, want, ("out", "lse", "dq", "dk", "dv")):
        assert np.isfinite(np.asarray(g)).all(), what
        if what == "lse":      # NEG_INF rows: steps of THAT size
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-6, err_msg=what)
        else:
            _assert_float32_close(g, w, what, steps=16)
    if chunk == "future":
        out, lse, dq = (np.asarray(x) for x in got[:3])
        assert not out[:, :128].any() and not dq[:, :228].any()
        assert lse[:, :, :228].max() <= 0.9 * NEG_INF
        assert lse[:, :, 228:].min() > -1e3


def test_one_head_a_key_head_keeps_the_parents_grid_and_blocks():
    """``n_rep`` = 1 is the program of before the grouping: the grids
    and every block shape of the three kernels at two of the benchmark's
    shapes (Pythia-1B's 8 heads of 256 over 384 tokens; the 32 expanded
    latent heads, keys 192 / values 128, over 1024), written down from
    the parent commit.  A group's grid is over the KEY heads, its q-side
    blocks ``n_rep`` heads thick, dk / dv one key head's."""
    def kernels(B, L, H, Hkv, D, Dv):
        q = jnp.zeros((B, L, H, D), jnp.bfloat16)
        k = jnp.zeros((B, L, Hkv, D), jnp.bfloat16)
        v = jnp.zeros((B, L, Hkv, Dv), jnp.bfloat16)
        pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))

        def fwd_bwd(q, k, v):
            out, vjp = jax.vjp(
                lambda q, k, v: flash_attention_gqa(q, k, v, pos, 0.1),
                q, k, v)
            return vjp(out)

        found = {}
        for e in _walk_eqns(jax.make_jaxpr(fwd_bwd)(q, k, v).jaxpr):
            if e.primitive.name == "pallas_call":
                gm = e.params["grid_mapping"]
                found[e.params["name"]] = (
                    tuple(gm.grid),
                    [tuple(getattr(d, "block_size", d)
                           for d in bm.block_shape)
                     for bm in gm.block_mappings])
        return found

    def parent(B, H, L, D, Dv):
        pos, q, k, v = (1, 1, L), (1, 1, L, D), (1, 1, L, D), (1, 1, L, Dv)
        row = (1, 1, 1, L)
        return {
            "flash_fwd": ((B, H, 1, 1), [pos, q, k, (1, 1, Dv, L),
                                         (1, 1, Dv, L), row]),
            "flash_bwd_dq": ((B, H, 1, 1), [pos, q, k, (1, 1, D, L), v, v,
                                            row, row, (1, 1, D, L)]),
            "flash_bwd_dkv": ((B, H, 1, 1), [pos, q, k, v, v, row, row,
                                             k, v])}

    assert kernels(16, 384, 8, 8, 256, 256) == parent(16, 8, 384, 256, 256)
    assert kernels(16, 1024, 32, 32, 192, 128) == parent(16, 32, 1024, 192,
                                                         128)
    grouped = kernels(2, 2048, 32, 8, 128, 128)
    assert {name: grid for name, (grid, _) in grouped.items()} == {
        "flash_fwd": (2, 8, 2, 2), "flash_bwd_dq": (2, 8, 2, 2),
        "flash_bwd_dkv": (2, 8, 2, 2)}
    assert grouped["flash_fwd"][1][1] == (1, 4, 1024, 128)        # q
    assert grouped["flash_bwd_dkv"][1][-2:] == [(1, 1, 1024, 128)] * 2
