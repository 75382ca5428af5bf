"""The delta rule with a per-channel decay (Kimi Delta Attention) or one
decay a head (Gated DeltaNet: ``g`` of width 1, the same over a head's
key channels), in the two forms a model needs of it.

Per head, with a state ``S`` [dk, dv] in float32, zero before the first
token, a log decay ``g_t`` [dk] or [1] (<= 0), a step size ``beta_t``
and ``q_t, k_t`` [dk], ``v_t`` [dv]::

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

- :func:`kda_step`: one token (decode); the three lines above.  Two
  forms, chosen at trace time by :func:`step_form`: where the trace is
  for a TPU the Pallas kernel of ``ops/pallas/kda_step.py``, which holds
  a head's state in VMEM, so that it is read from the HBM once and
  written once a step, into the buffer it came in (as ``jax.numpy`` the
  prediction, a reduction over the whole tile that the update needs
  before it can write any of it, makes two passes of it, and the decode
  loop a copy); everywhere else (the CPU) the ``jax.numpy`` lines of
  :func:`kda_step`, which the kernel is tested against.  In both every
  product with the state is an elementwise float32 product and every sum
  a float32 sum, never a matrix product: a product with one row would
  round the state to bfloat16 on its way into the MXU, and a rounded
  state stays rounded.  The kernel takes heads of any size and either
  width of ``g`` as they are (full-dimension blocks: no padding).
- :func:`kda_chunked`: a whole sequence (training, the experience
  forwards, prefill), chunk by chunk, differentiable.  One algorithm in
  two forms, chosen at trace time by :func:`chunk_form`: where the
  trace is for a TPU, the two Pallas kernels of
  ``ops/pallas/kda_chunk.py`` (a chunk's insides stay in VMEM, the
  inputs are read as ``[B, L, H d]`` without a transpose, the backward
  is written by hand behind ``jax.custom_vjp`` and keeps the inputs and
  the float32 states at the chunk boundaries, ``[B, H, n, dv, dk]``,
  recomputing the insides); everywhere else (the CPU) the ``jax.numpy``
  form below, differentiated by autodiff, which is also what the
  kernels are tested against.  The kernels take heads whose sizes are
  whole lane tiles of 128 and a decay a channel: other heads
  (Olmo-Hybrid's 96 x 192) are padded with zero key channels and zero
  value columns around the call, which leave every product and the real
  part of the state as they were, and one decay a head is broadcast
  (:func:`_to_lane_tiles`); the padding is the kernel's time, not work.

A position with ``g = 0`` and ``beta = 0`` leaves the state as it was:
that is how a caller makes padding inert, and how the chunked form pads
a sequence to whole chunks.

**The chunked form.**  Inside a chunk of C tokens with ``G_t`` the
running sum of ``g`` (so ``exp(G_t - G_i)`` is the decay from token i
to token t), the pseudo-values ``u_t = beta_t (v_t - S_{t-1}'^T k_t)``
solve the unit lower-triangular system::

    (I + Diag(beta) tril(A, -1)) U = Diag(beta) (V - (K * e^G) S_0)
    A[t, i] = sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])

whose inverse is built by doubling from the inverses of its diagonal
blocks (``log2 C`` levels of small matrix products; no triangular
solve, which the TPU does one row at a time, and no Neumann series,
whose powers overflow where a chunk's keys are alike).  Then
``O = (Q * e^G) S_0 + tril(B) U`` with ``B`` as ``A`` with ``q_t`` in
place of ``k_t``, and ``S_C = Diag(e^{G_C}) S_0 + (K * e^{G_C - G})^T
U``.  In the ``jax.numpy`` form one ``lax.scan`` over the chunks carries
``S`` and does all of a chunk's work in its body (:func:`_chunk`), which
is checkpointed: the backward keeps the states at the chunk boundaries
and recomputes inside.  The kernels do the same arithmetic with the same
roundings (matrix-product operands in bfloat16 as the MXU takes them at
default precision; state, decays, ``g`` and accumulation float32).

**Decays that overflow.**  ``exp(G_t - G_i)`` is at most 1, but it is a
``[C, C, dk]`` tensor; split into ``e^{G_t} e^{-G_i}`` for a matrix
product, the second factor overflows float32 once a channel has
decayed by ``e^-88`` inside the chunk, which a strong decay does in a
few tokens.  So ``A`` and ``B`` are built in ``log2 C`` levels: at the
level of half-size h every block of 2h tokens is cut in its middle,
the pairs (t in the upper half, i in the lower half) take the decay
from the middle, ``e^{G_t - G_mid} e^{G_mid - G_i}``, both factors at
most 1, and every pair t > i is split by exactly one level.  Underflow
is harmless (the pair has decayed to nothing).  Each level is one
matrix product ``[2C, dk] x [dk, C]``: ``log2 C`` times the operations
of the naive product, all of them on the MXU.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

CHUNK = 64


def kda_step(q, k, v, g, beta, state):
    """One token.  q, k [B, H, dk]; g [B, H, dk] or [B, H, 1]; v
    [B, H, dv]; beta [B, H]; state [B, H, dk, dv] float32 -> (o
    [B, H, dv] float32, new state).

    Where :func:`step_form` says ``"kernel"`` (a trace for a TPU) the
    step is ``ops/pallas/kda_step.py``: the state crosses the HBM twice,
    in place.  Else the lines below, which cross it three times and more
    and are what the kernel is tested against.  Elementwise products and
    sums in float32 in both: a matrix product with one row would round
    the state on its way into the MXU."""
    if step_form(q.shape[-1], v.shape[-1]) == "kernel":
        from orion_tpu.ops.pallas.kda_step import kda_step_kernel

        return _kernel_on_mesh(
            kda_step_kernel, (q, k, v, g, beta, state),
            heads_at=(1, 1, 1, 1, 1, 1), out_heads_at=(1, 1))
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    state = jnp.exp(g)[..., None] * state
    pred = jnp.sum(state * k[..., None], axis=-2)               # [B, H, dv]
    u = beta[..., None] * (v - pred)
    state = state + k[..., None] * u[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


def _pair_products(rows, k, G):
    """sum_c rows[t, c] k[i, c] exp(G[t, c] - G[i, c]) for t > i, zero
    elsewhere, without forming ``exp(-G)``: the levels of the module
    docstring.  rows [..., R, C, d] (R stacked sets of rows: k and q
    share every level's scaling), k, G [..., C, d] -> [..., R, C, C]."""
    C = k.shape[-2]
    pos = jnp.arange(C)
    out = 0.0
    h = 1
    while h < C:
        upper = (pos // h) % 2 == 1                              # [C]
        # G at the last position of the lower half of each 2h-block
        mid = (pos // (2 * h)) * (2 * h) + h - 1
        G_mid = jnp.take(G, mid, axis=-2)
        scale = jnp.exp(jnp.where(upper[:, None], G - G_mid, G_mid - G))
        t_side = jnp.where(upper[:, None], rows * scale[..., None, :, :],
                           0.0)
        i_side = jnp.where(upper[:, None], 0.0, k * scale)
        same = (pos[:, None] // (2 * h)) == (pos[None, :] // (2 * h))
        level = jnp.einsum("...rtc,...ic->...rti", t_side, i_side)
        out = out + jnp.where(same, level, 0.0)
        h *= 2
    return out


def _unit_lower_inverse(M):
    """(I + M)^-1 for strictly lower-triangular M [..., C, C], C a power
    of two, by doubling: the inverses of the diagonal blocks of size m
    give those of size 2m, ``[[T1, 0], [-T2 M21 T1, T2]]``.  Every
    intermediate is the inverse of a diagonal block of ``I + M`` and as
    well-behaved as the answer, which a Neumann series by squarings is
    not: where the keys of a chunk are alike and decay slowly (a run of
    one repeated token) ``M`` is near ``beta`` times the all-ones
    triangle, its powers reach 1e8 before they cancel, and on the chip
    the update came out NaN (PERF.md section 6, PR 32).  log2 C levels of
    two batched products of [m, m]: a thirtieth of the squarings'
    operations."""
    C = M.shape[-1]
    assert C & (C - 1) == 0, "the chunk length must be a power of two"
    lead = M.shape[:-2]
    D = jnp.ones(lead + (C, 1, 1), M.dtype)      # the blocks of size 1
    m = 1
    while m < C:
        nb = C // (2 * m)
        # M's block below the diagonal inside each block of 2m
        M21 = jnp.einsum(
            "...iaib->...iab",
            M.reshape(lead + (nb, 2, m, nb, 2, m))[..., :, 1, :, :, 0, :])
        pairs = D.reshape(lead + (nb, 2, m, m))
        T1, T2 = pairs[..., 0, :, :], pairs[..., 1, :, :]
        T21 = -jnp.matmul(jnp.matmul(T2, M21), T1)
        D = jnp.concatenate(
            [jnp.concatenate([T1, jnp.zeros_like(T1)], axis=-1),
             jnp.concatenate([T21, T2], axis=-1)], axis=-2)
        m *= 2
    return D[..., 0, :, :]


def _chunk(S, q, k, v, g, beta):
    """One chunk for every (batch, head): S [B, H, dk, dv]; q, k, g
    [B, H, C, dk]; v [B, H, C, dv]; beta [B, H, C].  Every matrix
    product at the backend's default precision (bf16 operands on a TPU);
    the state itself stays float32."""
    mm = jnp.matmul
    C = q.shape[-2]
    G = jnp.cumsum(g, axis=-2)
    pairs = _pair_products(jnp.stack([k, q], axis=-3), k, G)
    A, Bq = pairs[..., 0, :, :], pairs[..., 1, :, :]
    T = _unit_lower_inverse(beta[..., None] * A)
    decay = jnp.exp(G)                                           # <= 1
    rhs = beta[..., None] * jnp.concatenate([v, k * decay], axis=-1)
    sol = mm(T, rhs)
    U = sol[..., :v.shape[-1]] - mm(sol[..., v.shape[-1]:], S)   # [.., C, dv]
    diag = jnp.sum(q * k, axis=-1)                               # t == i
    o = mm(q * decay, S) + mm(Bq + diag[..., None] * jnp.eye(C), U)
    G_end = G[..., -1:, :]
    S = jnp.swapaxes(jnp.exp(G_end), -1, -2) * S + mm(
        jnp.swapaxes(k * jnp.exp(G_end - G), -1, -2), U)
    return S, o


LANES = 128


def chunk_form(dk: int, dv: int) -> str:
    """Which form of the chunked rule a trace takes here: ``"kernel"``
    (ops/pallas/kda_chunk.py) where the trace is for a TPU, whatever the
    head sizes (those that are no whole lane tiles are padded around the
    call), ``"jnp"`` (the scan below) everywhere else.  Asked at trace
    time, as ``ops.attention`` asks for flash; the trainer reports the
    answer on its ``update`` span."""
    from orion_tpu.ops.pallas import target_platform

    return "kernel" if target_platform() == "tpu" else "jnp"


def step_form(dk: int, dv: int) -> str:
    """Which form one token's step takes in a trace here: ``"kernel"``
    (ops/pallas/kda_step.py) where the trace is for a TPU, whatever the
    head sizes (the kernel's blocks span a head's full dimensions: 128 x
    128 and 96 x 192 both ran faster than the ``jax.numpy`` step on a
    v5e, PERF.md section 6, PR 37), ``"jnp"`` everywhere else.  Asked at
    trace time beside :func:`chunk_form`; the trainer reports the answer
    on its ``rollout.dispatch`` span."""
    from orion_tpu.ops.pallas import target_platform

    return "kernel" if target_platform() == "tpu" else "jnp"


def _to_lane_tiles(q, k, v, g, state):
    """The kernels' operands for heads of any size: zero key channels up
    to the next multiple of 128 and zero value columns likewise, one
    decay a head broadcast over the (padded) key channels.  Padded key
    channels of q and k are zero, so no pair product, prediction or
    output sees them and their rows of the state stay zero whatever they
    decay by; padded value columns of v and of the state are zero and
    stay so."""
    dk, dv = q.shape[-1], v.shape[-1]
    pk, pv = -dk % LANES, -dv % LANES

    def last(t, n):
        return jnp.pad(t, ((0, 0),) * (t.ndim - 1) + ((0, n),)) if n else t

    if g.shape[-1] == 1:
        g = jnp.broadcast_to(g, g.shape[:-1] + (dk + pk,))
    else:
        g = last(g, pk)
    if pk or pv:
        state = jnp.pad(state, ((0, 0), (0, 0), (0, pk), (0, pv)))
    return last(q, pk), last(k, pk), last(v, pv), g, state


def _kernel_on_mesh(run, operands, heads_at, out_heads_at):
    """A kernel of this rule under whatever mesh is ambient, as
    ``ops.attention._flash_on_mesh``: a Mosaic kernel cannot be
    partitioned automatically, so under a mesh of several devices it
    runs in a ``shard_map`` over the batch (data, fsdp; every operand's
    and result's first dimension) and the heads (tensor; the dimension
    ``heads_at`` / ``out_heads_at`` names for each), each where it
    divides.  Rows and heads are independent: no collective."""
    import math

    from orion_tpu.parallel.sharding import ambient_mesh

    mesh = ambient_mesh()
    if (mesh.empty or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return run(*operands)
    from jax.sharding import PartitionSpec as P

    from orion_tpu.utils.platform import shard_map

    shape = dict(mesh.shape)
    batch = tuple(a for a in ("data", "fsdp") if shape.get(a, 1) > 1)
    n_batch = math.prod(shape[a] for a in batch)
    b = batch if batch and operands[0].shape[0] % n_batch == 0 else None
    tp = shape.get("tensor", 1)
    h = ("tensor" if tp > 1 and operands[0].shape[heads_at[0]] % tp == 0
         else None)

    def spec(at):
        return P(b, *(h if i == at else None for i in range(1, at + 1)))

    return shard_map(
        run, mesh=mesh, in_specs=tuple(map(spec, heads_at)),
        out_specs=tuple(map(spec, out_heads_at)),
        check_vma=False)(*operands)


def _chunk_kernel_on_mesh(q, k, v, g, beta, state, chunk):
    """The chunked form's kernels (sequences [B, L, H, d]: heads third,
    the state's second) under the ambient mesh."""
    from orion_tpu.ops.pallas.kda_chunk import kda_chunk_kernel

    return _kernel_on_mesh(
        lambda *a: kda_chunk_kernel(*a, chunk), (q, k, v, g, beta, state),
        heads_at=(2, 2, 2, 2, 2, 1), out_heads_at=(2, 1))


def kda_chunked(q, k, v, g, beta, state: Optional[jax.Array] = None,
                chunk: int = CHUNK):
    """A whole sequence.  q, k [B, L, H, dk]; g [B, L, H, dk] or
    [B, L, H, 1] (one decay a head); v [B, L, H, dv]; beta [B, L, H];
    state [B, H, dk, dv] float32 or None (zero) -> (o [B, L, H, dv]
    float32, the state after the last position)."""
    f32 = jnp.float32
    B, L, H, dk = q.shape
    dv = v.shape[-1]
    if state is None:
        state = jnp.zeros((B, H, dk, dv), f32)
    if chunk_form(dk, dv) == "kernel":
        if dk % LANES or dv % LANES or g.shape[-1] == 1:
            q, k, v, g, padded = _to_lane_tiles(q, k, v, g, state)
            o, padded = _chunk_kernel_on_mesh(q, k, v, g, beta, padded,
                                              chunk)
            return o[..., :dv], padded[:, :, :dk, :dv]
        return _chunk_kernel_on_mesh(q, k, v, g, beta, state, chunk)
    n = -(-L // chunk)
    pad = n * chunk - L

    def chunks(t):
        """[B, L, H, ...] -> [n, B, H, chunk, ...], padded inertly; in
        the dtype given (a chunk is brought to float32 in the body, so
        that what the backward keeps of a sequence stays as narrow as
        the caller made it)."""
        if pad:
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        t = t.reshape((B, n, chunk) + t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 1, 0), 2, 3)

    @jax.checkpoint
    def body(S, xs):
        return _chunk(S, *(t.astype(f32) for t in xs))

    state, o = jax.lax.scan(
        body, state.astype(f32),
        (chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta)))
    # [n, B, H, chunk, dv] -> [B, L, H, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1).reshape(
        B, n * chunk, H, dv)
    return o[:, :L], state
