"""The chunked delta rule's Pallas kernels (ops/pallas/kda_chunk.py)
against the ``jax.numpy`` form (ops/kda.py), interpreted on the CPU.

The kernels round their products' operands to bfloat16, as the MXU does
at default precision; the CPU computes the ``jax.numpy`` form's products
exactly.  So most cases ask the kernels for float32 operands, where the
two forms are the same formulas and agree to float32's own noise, and
one case runs the operands the chip runs, to bfloat16's.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.ops import kda
from orion_tpu.ops.pallas import kda_chunk

B, H, D = 2, 2, 128
NAMES = ("q", "k", "v", "g", "beta", "state")


def _inputs(L, decay, seed=0, state=True, H=H):
    """As ``tests/test_kimi_linear.py::_inputs`` at the kernels' head
    size: ``init`` (what the initialiser gives), ``overflow`` (-80 a
    step: ``exp(-G)`` overflows float32 after two tokens),
    ``repeated_token`` (PR 32's run of one token: alike keys, slow
    decay, where the triangular system is at its worst)."""
    rs = np.random.RandomState(seed)
    q, k, v = (rs.normal(size=(B, L, H, D)) for _ in range(3))
    if decay == "repeated_token":
        q, k = (np.broadcast_to(t[:, :1], t.shape) for t in (q, k))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(D)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    if decay == "init":
        g = -rs.uniform(1, 16, size=(B, 1, H, 1)) * np.exp(rs.uniform(
            np.log(1e-3), np.log(1e-1), size=(B, L, H, D)))
    else:
        g = np.full((B, L, H, D), {"overflow": -80.0,
                                   "repeated_token": -1e-3}[decay])
    beta = 1.0 / (1.0 + np.exp(-rs.normal(size=(B, L, H))))
    if decay == "repeated_token":
        beta = np.full_like(beta, 0.9)
    S = rs.normal(size=(B, H, D, D)) * (0.1 if state else 0.0)
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta, S)]


@functools.lru_cache(maxsize=None)
def _kernel(operands="float32"):
    return jax.jit(lambda *a: kda_chunk.kda_chunk_kernel(
        *a, kda.CHUNK, jnp.dtype(operands)))


def _loss(fn):
    def loss(*a):
        o, S = fn(*a)
        return jnp.sum(jnp.sin(3.0 * o)) + jnp.sum(jnp.cos(S))
    return loss


@functools.lru_cache(maxsize=None)
def _grads(form, operands="float32"):
    fn = kda.kda_chunked if form == "jnp" else _kernel(operands)
    return jax.jit(jax.grad(_loss(fn), argnums=tuple(range(6))))


def _close(got, want, tol, name=""):
    assert np.isfinite(np.asarray(got)).all(), name
    scale = float(jnp.max(jnp.abs(want))) + 1e-12
    np.testing.assert_allclose(got, want, atol=tol * scale, rtol=0,
                               err_msg=name)


@pytest.mark.parametrize("L,decay,state,heads", [
    (128, "init", False, 2), (150, "init", True, 4), (150, "init", True, 1),
    (192, "overflow", True, 2), (150, "repeated_token", True, 2)])
def test_output_and_final_state_are_the_jnp_forms(L, decay, state, heads):
    """Whole chunks and not (150 = 2 x 64 + 22), from zero and from a
    given state; a decay under which ``exp(-G)`` overflows inside a
    chunk; a run of one token; one head (a stack of one), two (one stack
    of two) and four (two stacks a grid step)."""
    args = _inputs(L, decay, state=state, H=heads)
    o, S = _kernel()(*args)
    want_o, want_S = kda.kda_chunked(*args)
    assert o.shape == want_o.shape and o.dtype == jnp.float32
    _close(o, want_o, 1e-5, "o")
    _close(S, want_S, 1e-5, "state")


@pytest.mark.parametrize("decay,tol,heads", [
    ("init", 1e-5, 2), ("init", 1e-5, 4), ("init", 1e-5, 1),
    ("overflow", 1e-5, 2), ("repeated_token", 1e-4, 2)])
def test_gradients_of_all_inputs_and_the_state(decay, tol, heads):
    """The hand-written backward against autodiff of the ``jax.numpy``
    form: q, k, v, g, beta and the initial state, under a loss that
    reads ``o`` and the final state."""
    args = _inputs(150, decay, seed=1, H=heads)
    got, want = _grads("kernel")(*args), _grads("jnp")(*args)
    for name, a, b in zip(NAMES, got, want):
        _close(a, b, tol, name)


def test_bfloat16_operands_as_on_the_chip():
    """The kernels as the chip runs them (bfloat16 inputs and operands)
    against the exact products of the CPU: bfloat16's noise, no more."""
    args = _inputs(150, "init", seed=2)
    args = [a.astype(jnp.bfloat16) for a in args[:3]] + args[3:]
    o, S = _kernel("bfloat16")(*args)
    want_o, want_S = kda.kda_chunked(*args)
    _close(o, want_o, 2e-2, "o")
    _close(S, want_S, 2e-2, "state")
    got, want = _grads("kernel", "bfloat16")(*args), _grads("jnp")(*args)
    for name, a, b in zip(NAMES, got, want):
        assert a.dtype == b.dtype, name
        _close(a.astype(jnp.float32), b.astype(jnp.float32), 3e-2, name)


def test_a_split_sequence_hands_its_state_over():
    """Over the first part, then over the rest from the state it left,
    is over the whole (chunked prefill), cut inside a chunk."""
    args = _inputs(192, "init", seed=3)
    o, S = _kernel()(*args)
    cut = 83
    o1, S1 = _kernel()(*(a[:, :cut] for a in args[:5]), args[5])
    o2, S2 = _kernel()(*(a[:, cut:] for a in args[:5]), S1)
    _close(jnp.concatenate([o1, o2], axis=1), o, 1e-5, "o")
    _close(S2, S, 1e-5, "state")


def test_positions_without_a_token_are_inert():
    """``g = 0, beta = 0`` (``token_mask`` false): the state after a
    padded sequence is the state after its last real token, and no
    gradient reaches v or beta's value through a padded position."""
    real, L = 45, 128
    q, k, v, g, beta, S0 = _inputs(L, "init", seed=4)
    mask = jnp.arange(L) < real
    g_m = jnp.where(mask[None, :, None, None], g, 0.0)
    b_m = jnp.where(mask[None, :, None], beta, 0.0)
    o, S = _kernel()(q, k, v, g_m, b_m, S0)
    o_short, S_short = _kernel()(*(a[:, :real] for a in (q, k, v, g, beta)),
                                 S0)
    _close(o[:, :real], o_short, 1e-5, "o")
    _close(S, S_short, 1e-5, "state")
    dv = _grads("kernel")(q, k, v, g_m, b_m, S0)[2]
    assert float(jnp.max(jnp.abs(dv[:, real:]))) == 0.0


@pytest.mark.parametrize("platform,dk,dv,want", [
    ("tpu", 128, 128, "kernel"), ("tpu", 256, 128, "kernel"),
    ("cpu", 128, 128, "jnp"), ("gpu", 128, 128, "jnp"),
    ("tpu", 64, 64, "kernel"), ("tpu", 128, 96, "kernel"),
    ("tpu", 16, 16, "kernel"), ("tpu", 96, 192, "kernel"),
    ("cpu", 96, 192, "jnp")])
def test_the_choice_follows_the_platform_and_the_head_sizes(
        monkeypatch, platform, dk, dv, want):
    """On a TPU the kernels, whatever the head sizes (since PR 34 heads
    that are no whole lane tiles are padded around the call); the
    ``jax.numpy`` form everywhere else."""
    import orion_tpu.ops.pallas as pallas

    monkeypatch.setattr(pallas, "target_platform", lambda: platform)
    assert kda.chunk_form(dk, dv) == want


@pytest.mark.parametrize("mesh_shape", [None, (2, 2)],
                         ids=["one_device", "fsdp2_tensor2"])
def test_kda_chunked_takes_the_kernels_where_the_choice_says(monkeypatch,
                                                             mesh_shape):
    """``kda_chunked`` with the choice steered to the kernels
    (interpreted here), alone and under a mesh of several devices, where
    they run in a ``shard_map`` over rows and heads: same results, and
    the kernels are in the program."""
    import contextlib

    from orion_tpu.config import MeshConfig
    from orion_tpu.parallel.mesh import make_mesh

    args = _inputs(128, "init", seed=5)
    want, want_none = kda.kda_chunked(*args), kda.kda_chunked(*args[:5])
    calls, kernel = [], kda_chunk.kda_chunk_kernel
    monkeypatch.setattr(kda, "chunk_form", lambda dk, dv: "kernel")
    monkeypatch.setattr(kda_chunk, "kda_chunk_kernel",
                        lambda *a: calls.append(a[0].shape) or kernel(*a))
    mesh = contextlib.nullcontext() if mesh_shape is None else make_mesh(
        MeshConfig(data=1, fsdp=mesh_shape[0], tensor=mesh_shape[1]),
        devices=jax.devices()[:4])
    with mesh:
        got = jax.jit(kda.kda_chunked)(*args)
        got_none = jax.jit(lambda *a: kda.kda_chunked(*a))(*args[:5])
    # each device's share: rows over fsdp, heads over tensor
    per = (B, 128, H, D) if mesh_shape is None else (
        B // mesh_shape[0], 128, H // mesh_shape[1], D)
    assert calls == [per, per]
    for g_, w_ in zip(got + got_none, want + want_none):
        _close(g_, w_, 2e-2)


# -- heads off the lane tile: Olmo-Hybrid's 30 heads of 96 x 192, one decay
# -- a head, a step size up to 2 ---------------------------------------------

def _gdn_inputs(L, seed=0, heads=30, dk=96, dv=192):
    rs = np.random.RandomState(seed)
    q, k = (rs.normal(size=(1, L, heads, dk)) for _ in range(2))
    v = rs.normal(size=(1, L, heads, dv))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    g = -rs.uniform(1, 16, size=(1, 1, heads, 1)) * np.exp(rs.uniform(
        np.log(1e-3), np.log(1e-1), size=(1, L, heads, 1)))
    beta = 2.0 / (1.0 + np.exp(-rs.normal(size=(1, L, heads))))
    S = rs.normal(size=(1, heads, dk, dv)) * 0.1
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta, S)]


@pytest.fixture
def padded_kernels(monkeypatch):
    """``kda_chunked`` as a TPU trace takes it at these heads (zero
    channels up to 128 x 256 around the kernels, the decay broadcast),
    the kernels interpreted with float32 operands."""
    kernel = kda_chunk.kda_chunk_kernel
    shapes = []

    def run(q, k, v, g, beta, state, chunk):
        shapes.append((q.shape, v.shape, g.shape, state.shape))
        return kernel(q, k, v, g, beta, state, chunk, jnp.float32)

    jnp_form = kda.kda_chunked
    monkeypatch.setattr(kda_chunk, "kda_chunk_kernel", run)

    def padded(*args):
        with monkeypatch.context() as m:
            m.setattr(kda, "chunk_form", lambda dk, dv: "kernel")
            return jnp_form(*args)

    return padded, shapes


@pytest.mark.parametrize("case", ["forward", "backward", "carried_state",
                                  "masked_tail"])
def test_heads_of_96_by_192_run_the_kernels_padded(padded_kernels, case):
    """30 heads (two a grid step), key size 96 and value size 192
    (neither a multiple of 128), ONE decay a head, beta up to 2, against
    the ``jax.numpy`` form at the true sizes."""
    padded, shapes = padded_kernels
    L = 100                                   # 64 + 36: a padded chunk
    args = _gdn_inputs(L, seed=6)
    assert float(jnp.max(args[4])) > 1.0
    if case == "forward":
        (o, S), (want_o, want_S) = padded(*args[:5]), kda.kda_chunked(
            *args[:5])
        assert o.shape == (1, L, 30, 192) and S.shape == (1, 30, 96, 192)
        assert shapes == [((1, L, 30, 128), (1, L, 30, 256),
                           (1, L, 30, 128), (1, 30, 128, 256))]
        _close(o, want_o, 1e-5, "o")
        _close(S, want_S, 1e-5, "state")
    elif case == "backward":
        got = jax.grad(_loss(padded), argnums=tuple(range(6)))(*args)
        want = _grads("jnp")(*args)
        for name, a, b in zip(NAMES, got, want):
            assert a.shape == b.shape, name   # g's gradient: one a head
            _close(a, b, 1e-5, name)
    elif case == "carried_state":
        o, S = padded(*args)
        cut = 41
        o1, S1 = padded(*(a[:, :cut] for a in args[:5]), args[5])
        o2, S2 = padded(*(a[:, cut:] for a in args[:5]), S1)
        _close(jnp.concatenate([o1, o2], axis=1), o, 1e-5, "o")
        _close(S2, S, 1e-5, "state")
        _close(S, kda.kda_chunked(*args)[1], 1e-5, "state vs jnp")
    else:
        real = 45
        q, k, v, g, beta, S0 = args
        mask = jnp.arange(L) < real
        g_m = jnp.where(mask[None, :, None, None], g, 0.0)
        b_m = jnp.where(mask[None, :, None], beta, 0.0)
        o, S = padded(q, k, v, g_m, b_m, S0)
        o_short, S_short = padded(*(a[:, :real] for a in (q, k, v, g, beta)),
                                  S0)
        _close(o[:, :real], o_short, 1e-5, "o")
        _close(S, S_short, 1e-5, "state")


# -- one token: the step kernel (ops/pallas/kda_step.py) ---------------------

def _step_inputs(B, heads, dk, dv, one_decay, seed=0):
    """One decode position as the mixers hand it over: q, k, v in
    bfloat16, g (a channel or a head) and beta float32, a float32
    state."""
    rs = np.random.RandomState(seed)
    q, k = (rs.normal(size=(B, heads, dk)) for _ in range(2))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rs.normal(size=(B, heads, dv))
    g = -rs.uniform(1, 16, size=(B, heads, 1)) * np.exp(rs.uniform(
        np.log(1e-3), np.log(1e-1), size=(B, heads, 1 if one_decay else dk)))
    beta = (2.0 if one_decay else 1.0) / (1.0 + np.exp(-rs.normal(
        size=(B, heads))))
    S = rs.normal(size=(B, heads, dk, dv)) * 0.1
    bf16, f32 = jnp.bfloat16, jnp.float32
    return [jnp.asarray(x, t) for x, t in zip(
        (q, k, v, g, beta, S), (bf16, bf16, bf16, f32, f32, f32))]


@functools.lru_cache(maxsize=None)
def _step_kernel():
    from orion_tpu.ops.pallas import kda_step

    return jax.jit(kda_step.kda_step_kernel)


STEP_HEADS = {"kimi": (32, 128, 128, False), "olmo": (30, 96, 192, True)}


@pytest.mark.parametrize("case", [
    "kimi_heads", "olmo_heads", "inert_kimi", "inert_olmo", "chained_kimi",
    "chained_olmo", "mantissa_kimi", "mantissa_olmo", "few_heads"])
def test_the_step_kernel_is_the_jnp_step(case):
    """The kernel interpreted here against the ``jax.numpy`` lines of
    ``kda.kda_step`` (the form the CPU takes), at both models' heads: 32
    of 128 x 128 with a decay a channel, 30 of 96 x 192 with one a
    head."""
    name = case.split("_")[1]
    heads, dk, dv, one = STEP_HEADS.get(name, (4, 16, 24, False))
    assert kda.step_form(dk, dv) == "jnp"          # the CPU's own form
    step = _step_kernel()
    if case.endswith("_heads"):
        args = _step_inputs(2, heads, dk, dv, one, seed=3)
        (o, S), (want_o, want_S) = step(*args), kda.kda_step(*args)
        assert o.dtype == S.dtype == jnp.float32
        assert o.shape == (2, heads, dv) and S.shape == (2, heads, dk, dv)
        _close(o, want_o, 1e-6, "o")
        _close(S, want_S, 1e-6, "state")
    elif case.startswith("inert"):
        # g = 0, beta = 0 on one row: its state comes back bit for bit
        q, k, v, g, beta, S = _step_inputs(2, heads, dk, dv, one, seed=4)
        g, beta = g.at[1].set(0.0), beta.at[1].set(0.0)
        _, S1 = step(q, k, v, g, beta, S)
        assert np.array_equal(np.asarray(S1[1]), np.asarray(S[1]))
        assert not np.array_equal(np.asarray(S1[0]), np.asarray(S[0]))
    elif case.startswith("chained"):
        # 64 steps, one after the other, are one chunk of the chunked rule
        L, B = 64, 1
        rows = [_step_inputs(B, heads, dk, dv, one, seed=10 + t)
                for t in range(L)]
        S = S0 = rows[0][5]
        outs = []
        for q, k, v, g, beta, _ in rows:
            o, S = step(q, k, v, g, beta, S)
            outs.append(o)
        seq = [jnp.stack([r[i] for r in rows], axis=1).astype(jnp.float32)
               for i in range(5)]
        want_o, want_S = kda.kda_chunked(*seq, S0)
        _close(jnp.stack(outs, axis=1), want_o, 1e-5, "o")
        _close(S, want_S, 1e-5, "state")
    else:
        # a state whose entries need 17 mantissa bits, read through a
        # one-hot q beside an update of another row: every entry comes
        # back exactly (a bfloat16 anywhere on the way keeps 8)
        B = 1
        S = 1.0 + jnp.arange(dk * dv, dtype=jnp.float32).reshape(
            dk, dv) * 2.0 ** -17
        S = jnp.broadcast_to(S, (B, heads, dk, dv))
        assert not np.array_equal(
            np.asarray(S), np.asarray(S.astype(jnp.bfloat16), np.float32))
        q = jnp.zeros((B, heads, dk), jnp.bfloat16).at[..., 3].set(1.0)
        k = jnp.zeros((B, heads, dk), jnp.bfloat16).at[..., 5].set(1.0)
        v = jnp.zeros((B, heads, dv), jnp.bfloat16)
        g = jnp.zeros((B, heads, 1 if one else dk), jnp.float32)
        beta = jnp.full((B, heads), 0.5, jnp.float32)
        o, S1 = step(q, k, v, g, beta, S)
        assert np.array_equal(np.asarray(o), np.asarray(S[:, :, 3]))
        assert np.array_equal(np.asarray(S1[:, :, 5]),
                              np.asarray(S[:, :, 5] * 0.5))
        keep = np.arange(dk) != 5
        assert np.array_equal(np.asarray(S1)[:, :, keep],
                              np.asarray(S)[:, :, keep])


@pytest.mark.parametrize("platform,dk,dv,want", [
    ("tpu", 128, 128, "kernel"), ("tpu", 96, 192, "kernel"),
    ("tpu", 16, 16, "kernel"), ("cpu", 128, 128, "jnp"),
    ("cpu", 96, 192, "jnp"), ("gpu", 128, 128, "jnp")])
def test_the_steps_form_follows_the_platform_and_the_head_sizes(
        monkeypatch, platform, dk, dv, want):
    """On a TPU the kernel, whatever the head sizes (full-dimension
    blocks: both models' heads ran faster than the ``jax.numpy`` step on
    the chip); the ``jax.numpy`` lines everywhere else."""
    import orion_tpu.ops.pallas as pallas

    monkeypatch.setattr(pallas, "target_platform", lambda: platform)
    assert kda.step_form(dk, dv) == want


@pytest.mark.parametrize("heads,want", [(32, 16), (8, 8), (24, 8), (30, 30),
                                        (2, 2)])
def test_a_grid_step_holds_whole_sublane_tiles_of_heads(heads, want):
    from orion_tpu.ops.pallas.kda_step import heads_per_step

    assert heads_per_step(heads) == want


@pytest.mark.parametrize("mesh_shape", [None, (2, 2)],
                         ids=["one_device", "fsdp2_tensor2"])
def test_kda_step_takes_the_kernel_where_the_choice_says(monkeypatch,
                                                         mesh_shape):
    """``kda.kda_step`` with the choice steered to the kernel
    (interpreted here), alone and under a mesh of several devices, where
    it runs inside the ``shard_map`` the chunk kernels run in (rows over
    fsdp, heads over tensor): same results, each device's share."""
    import contextlib

    from orion_tpu.config import MeshConfig
    from orion_tpu.ops.pallas import kda_step
    from orion_tpu.parallel.mesh import make_mesh

    args = _step_inputs(4, 4, 16, 24, False, seed=7)
    want = kda.kda_step(*args)
    calls, kernel = [], kda_step.kda_step_kernel
    monkeypatch.setattr(kda, "step_form", lambda dk, dv: "kernel")
    monkeypatch.setattr(
        kda_step, "kda_step_kernel",
        lambda *a: calls.append((a[0].shape, a[5].shape)) or kernel(*a))
    mesh = contextlib.nullcontext() if mesh_shape is None else make_mesh(
        MeshConfig(data=1, fsdp=mesh_shape[0], tensor=mesh_shape[1]),
        devices=jax.devices()[:4])
    with mesh:
        got = jax.jit(kda.kda_step)(*args)
    n_b, n_h = mesh_shape or (1, 1)
    assert calls == [((4 // n_b, 4 // n_h, 16), (4 // n_b, 4 // n_h, 16, 24))]
    for g_, w_ in zip(got, want):
        _close(g_, w_, 1e-6)
