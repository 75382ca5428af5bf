"""Pallas flash attention, forward + backward (SURVEY.md §2 #13).

TPU-native equivalent of the reference stack's flash-attention CUDA
kernels.  Design:

- Public layout [B, L, H, D] (matching the model); internally the
  wrapper transposes to [B, H, L, D] so every block's trailing two dims
  are (seq-block, head-dim) — the shape Mosaic requires to tile onto
  the MXU.
- Both loop dimensions are *grid* dimensions: the forward/dq grid is
  (B, H, q-block, kv-block) and the dkv grid is (B, H, kv-block,
  q-block), with online-softmax / gradient accumulators carried in VMEM
  scratch across the innermost dimension (sequential on TPU).  VMEM
  footprint is therefore O(block), not O(L) — long-context safe.
- GQA via BlockSpec index maps (``h // n_rep``) — no materialized
  ``repeat_kv``.
- The value width ``Dv`` may differ from the query/key width ``D``
  (latent attention expands keys of 192 and values of 128): v, o, dO
  and dV blocks are ``Dv`` wide, q, k, dQ and dK blocks ``D`` wide.
- Masking is positional, matching the model's semantics exactly
  (models/transformer.py Attention): query at absolute position p
  attends to the KV at absolute position j iff ``j <= p``.  KV
  positions are an explicit array (``kv_positions``): the standard
  causal path passes ``arange(Lk)`` (slot == position), and the
  ring-attention path passes rotated chunk positions — zigzag chunks
  are piecewise-contiguous, so an offset would not do.
- Causal skipping: a (q-block, kv-block) pair is skipped when the
  kv-block's MIN position exceeds the q-block's MAX position
  (``pl.when``); block-extent scalars (per-q-block max position,
  per-kv-block min position, per-kv-block first relevant q-block) are
  scalar-prefetched.  On the standard contiguous path the *index maps*
  additionally clamp the fetched block index so skipped steps re-fetch
  the same block — Pallas elides consecutive identical fetches, so
  they also cost no HBM bandwidth.  (The clamp assumes position
  monotonicity, so the ring/kv_positions path disables it and relies
  on the compute skip alone.)
- Rows with NO valid key (possible per ring chunk) produce out = 0 and
  lse ≈ -inf — exactly the neutral element of the streaming-softmax
  merge in parallel.longctx.ring_attention.
- Backward is the standard two-kernel flash split: dQ over kv-blocks,
  dK/dV over q-blocks, recomputing P from the saved LSE.  For GQA the
  dK/dV kernel emits per-q-head gradients, group-summed outside.  The
  per-chunk entry points (``flash_chunk_*``) take a caller-supplied
  GLOBAL lse, which is what makes the ring-attention backward exact.

Interpret mode runs automatically off-TPU (CPU test harness).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import (NEG_INF, interpret_mode,
                                  named_pallas_call)


def _pick_block(n: int, preferred: int) -> int:
    # Mosaic requires the second-minor block dim to be a multiple of 8
    # OR equal to the full array dim.  A dim that fits in one block is
    # therefore always legal as-is — and any sub-8 divisor is NOT
    # (found on-chip r5: the speculative verify chunk runs Lq=k+1=5
    # over an Lk=388 cache; the old divisor scan chose bkv=4 and
    # Mosaic refused to lower — invisible to CPU interpret mode).
    if n <= preferred:
        return n
    for c in (preferred, 512, 256, 128, 64, 32, 16, 8):
        if c <= preferred and n % c == 0:
            return c
    return n  # no legal tile ≤ preferred: one full-dim block


def _block_extents(q_positions, kv_positions, bq, bkv, nkv=None):
    """Scalar-prefetch tables (all int32):

    qmax [B, nq]   — largest position in q-block i.
    kvmin [B, nkv] — smallest position in kv-block j; pair (i, j) is
                     fully masked iff kvmin[j] > qmax[i].
    imin [B, nkv]  — number of q-blocks with qmax < kvmin[j] (= first
                     relevant q-block when q positions are monotone).

    kv_positions=None means the standard causal layout (slot ==
    position): kvmin[b, j] = j * bkv; nkv must then be given.
    """
    B, Lq = q_positions.shape
    qmax = jnp.max(q_positions.reshape(B, Lq // bq, bq),
                   axis=-1).astype(jnp.int32)
    if kv_positions is None:
        kvmin = jnp.broadcast_to(
            (jnp.arange(nkv, dtype=jnp.int32) * bkv)[None, :], (B, nkv))
    else:
        kvmin = jnp.min(kv_positions.reshape(B, -1, bkv),
                        axis=-1).astype(jnp.int32)
    imin = jnp.sum(qmax[:, :, None] < kvmin[:, None, :],
                   axis=1).astype(jnp.int32)
    return qmax, imin, kvmin


# ---------------------------------------------------------------------------
# forward.  Internal layout: q/k/v/o [B, H, L, D]; qpos [B, Lq, 1];
# kvpos [B, 1, Lk] (lane-major: the kv-position vector broadcasts
# along lanes in the mask compare; a sublane-major [B, Lk, 1] layout
# forces a giant Mosaic relayout that blows scoped VMEM); lse [B, H, Lq, 1].  Grid (B, H, nq, nkv), kv innermost.
# ---------------------------------------------------------------------------


def _fwd_kernel(qmax_ref, imin_ref, kvmin_ref, qpos_ref, *rest,
                scale: float, use_kvpos: bool):
    if use_kvpos:
        (kvpos_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_sc, l_sc, acc_sc) = rest
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc = rest
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _():
        m_sc[:, :] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:, :] = jnp.zeros_like(l_sc)
        acc_sc[:, :] = jnp.zeros_like(acc_sc)

    @pl.when(kvmin_ref[b, j] <= qmax_ref[b, i])
    def _():
        blk_q = q_ref.shape[2]
        blk_kv = k_ref.shape[2]
        q = q_ref[0, 0, :, :].astype(jnp.float32) * scale        # [bq, D]
        qpos = qpos_ref[0, :, 0]
        if use_kvpos:
            kvmat = kvpos_ref[0, 0, :][None, :]
        else:
            # standard causal path: slot == position, pure iota — no
            # kvpos operand (whose lane-dim block would violate the
            # Mosaic divisibility rule at odd cache lengths).
            kvmat = j * blk_kv + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_kv), 1)
        k = k_ref[0, 0, :, :].astype(jnp.float32)                # [bkv, D]
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                  # [bq, bkv]
        s = jnp.where(kvmat <= qpos[:, None], s, NEG_INF)
        m_prev, l_prev = m_sc[:, :], l_sc[:, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_sc[:, :] = m_new
        l_sc[:, :] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[:, :] = acc_sc[:, :] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _():
        # Rows with no valid key at all (possible per ring chunk) keep
        # l = 0: guard the division -> o = 0, lse ≈ NEG_INF (the merge
        # neutral element).
        l_safe = jnp.maximum(l_sc[:, :], 1e-30)
        o_ref[0, 0, :, :] = (acc_sc[:, :] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = m_sc[:, :] + jnp.log(l_safe)


def _fwd(qt, kt, vt, qpos3, kvpos3, scale, blk_q, blk_kv,
         clamp: bool):
    """qt [B,H,Lq,D], kt/vt [B,Hkv,Lk,D], qpos3 [B,Lq,1], kvpos3
    [B,1,Lk].  clamp=True enables the contiguous-path fetch clamps."""
    B, H, Lq, D = qt.shape
    Hkv, Lk, Dv = kt.shape[1], kt.shape[2], vt.shape[3]
    n_rep = H // Hkv
    bq = _pick_block(Lq, blk_q)
    bkv = _pick_block(Lk, blk_kv)
    nq, nkv = Lq // bq, Lk // bkv
    use_kvpos = kvpos3 is not None
    qmax, imin, kvmin = _block_extents(
        qpos3[:, :, 0], kvpos3[:, 0, :] if use_kvpos else None,
        bq, bkv, nkv=nkv)

    if clamp:
        def kv_map(b, h, i, j, qmax, imin, kvmin, r=n_rep, bkv=bkv):
            # Steps beyond the causal frontier re-fetch the same block,
            # which Pallas elides.  (Contiguous kv positions only.)
            return (b, h // r, jnp.minimum(j, qmax[b, i] // bkv), 0)

        def kvpos_map(b, h, i, j, qmax, imin, kvmin, bkv=bkv):
            return (b, 0, jnp.minimum(j, qmax[b, i] // bkv))
    else:
        def kv_map(b, h, i, j, qmax, imin, kvmin, r=n_rep):
            return (b, h // r, j, 0)

        def kvpos_map(b, h, i, j, qmax, imin, kvmin):
            return (b, 0, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, H, nq, nkv),
        in_specs=(
            [pl.BlockSpec((1, bq, 1),
                          lambda b, h, i, j, qm, im, km: (b, i, 0))]
            + ([pl.BlockSpec((1, 1, bkv), kvpos_map)] if use_kvpos
               else [])
            + [pl.BlockSpec((1, 1, bq, D),
                            lambda b, h, i, j, qm, im, km: (b, h, i, 0)),
               pl.BlockSpec((1, 1, bkv, D), kv_map),
               pl.BlockSpec((1, 1, bkv, Dv), kv_map)]
        ),
        out_specs=[
            pl.BlockSpec((1, 1, bq, Dv),
                         lambda b, h, i, j, qm, im, km: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda b, h, i, j, qm, im, km: (b, h, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running sumexp
            pltpu.VMEM((bq, Dv), jnp.float32),  # running accumulator
        ],
    )
    operands = [qmax, imin, kvmin, qpos3]
    if use_kvpos:
        operands.append(kvpos3)
    operands += [qt, kt, vt]
    out, lse = named_pallas_call(
        "flash_fwd",
        functools.partial(_fwd_kernel, scale=scale,
                          use_kvpos=use_kvpos),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Lq, Dv), qt.dtype),
            jax.ShapeDtypeStruct((B, H, Lq, 1), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(*operands)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(qmax_ref, imin_ref, kvmin_ref, qpos_ref, *rest,
               scale: float, use_kvpos: bool):
    if use_kvpos:
        (kvpos_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_sc) = rest
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
         dq_sc) = rest
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _():
        dq_sc[:, :] = jnp.zeros_like(dq_sc)

    @pl.when(kvmin_ref[b, j] <= qmax_ref[b, i])
    def _():
        q = q_ref[0, 0, :, :].astype(jnp.float32) * scale
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        lse = lse_ref[0, 0, :, :]
        delta = delta_ref[0, 0, :, :]
        blk_q = q_ref.shape[2]
        blk_kv = k_ref.shape[2]
        qpos = qpos_ref[0, :, 0]
        if use_kvpos:
            kvmat = kvpos_ref[0, 0, :][None, :]
        else:
            kvmat = j * blk_kv + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_kv), 1)
        k = k_ref[0, 0, :, :].astype(jnp.float32)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        p = jnp.where(kvmat <= qpos[:, None], jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_sc[:, :] = dq_sc[:, :] + jnp.dot(
            ds, k, preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _():
        dq_ref[0, 0, :, :] = (dq_sc[:, :] * scale).astype(dq_ref.dtype)


def _dkv_kernel(qmax_ref, imin_ref, kvmin_ref, qpos_ref, *rest,
                scale: float, use_kvpos: bool):
    if use_kvpos:
        (kvpos_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_sc, dv_sc) = rest
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
         dv_ref, dk_sc, dv_sc) = rest
    b, j, i = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    ni = pl.num_programs(3)

    @pl.when(i == 0)
    def _():
        dk_sc[:, :] = jnp.zeros_like(dk_sc)
        dv_sc[:, :] = jnp.zeros_like(dv_sc)

    @pl.when(qmax_ref[b, i] >= kvmin_ref[b, j])
    def _():
        q = q_ref[0, 0, :, :].astype(jnp.float32) * scale
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        lse = lse_ref[0, 0, :, :]
        delta = delta_ref[0, 0, :, :]
        blk_q = q_ref.shape[2]
        blk_kv = k_ref.shape[2]
        qpos = qpos_ref[0, :, 0]
        if use_kvpos:
            kvmat = kvpos_ref[0, 0, :][None, :]
        else:
            kvmat = j * blk_kv + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_kv), 1)
        k = k_ref[0, 0, :, :].astype(jnp.float32)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bq, bkv]
        p = jnp.where(kvmat <= qpos[:, None], jnp.exp(s - lse), 0.0)
        dv_sc[:, :] = dv_sc[:, :] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bkv, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bq, bkv]
        ds = p * (dp - delta)
        dk_sc[:, :] = dk_sc[:, :] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bkv, D]

    @pl.when(i == ni - 1)
    def _():
        dk_ref[0, 0, :, :] = dk_sc[:, :].astype(dk_ref.dtype)  # carries scale
        dv_ref[0, 0, :, :] = dv_sc[:, :].astype(dv_ref.dtype)


def _dq_call(qt, kt, vt, qpos3, kvpos3, dout_t, lse, delta, scale,
             blk_q, blk_kv, clamp: bool):
    B, H, Lq, D = qt.shape
    Hkv, Lk = kt.shape[1], kt.shape[2]
    n_rep = H // Hkv
    bq = _pick_block(Lq, blk_q)
    bkv = _pick_block(Lk, blk_kv)
    nq, nkv = Lq // bq, Lk // bkv
    use_kvpos = kvpos3 is not None
    qmax, imin, kvmin = _block_extents(
        qpos3[:, :, 0], kvpos3[:, 0, :] if use_kvpos else None,
        bq, bkv, nkv=nkv)

    if clamp:
        def kv_map(b, h, i, j, qm, im, km, r=n_rep, bkv=bkv):
            return (b, h // r, jnp.minimum(j, qm[b, i] // bkv), 0)

        def kvpos_map(b, h, i, j, qm, im, km, bkv=bkv):
            return (b, 0, jnp.minimum(j, qm[b, i] // bkv))
    else:
        def kv_map(b, h, i, j, qm, im, km, r=n_rep):
            return (b, h // r, j, 0)

        def kvpos_map(b, h, i, j, qm, im, km):
            return (b, 0, j)

    Dv = vt.shape[3]
    q_spec = pl.BlockSpec((1, 1, bq, D),
                          lambda b, h, i, j, qm, im, km: (b, h, i, 0))
    do_spec = pl.BlockSpec((1, 1, bq, Dv),
                           lambda b, h, i, j, qm, im, km: (b, h, i, 0))
    row_spec = pl.BlockSpec((1, 1, bq, 1),
                            lambda b, h, i, j, qm, im, km: (b, h, i, 0))
    in_specs = (
        [pl.BlockSpec((1, bq, 1),
                      lambda b, h, i, j, qm, im, km: (b, i, 0))]
        + ([pl.BlockSpec((1, 1, bkv), kvpos_map)] if use_kvpos else [])
        + [q_spec,
           pl.BlockSpec((1, 1, bkv, D), kv_map),
           pl.BlockSpec((1, 1, bkv, Dv), kv_map),
           do_spec, row_spec, row_spec]
    )
    operands = [qmax, imin, kvmin, qpos3]
    if use_kvpos:
        operands.append(kvpos3)
    operands += [qt, kt, vt, dout_t, lse, delta]
    return named_pallas_call(
        "flash_bwd_dq",
        functools.partial(_dq_kernel, scale=scale,
                          use_kvpos=use_kvpos),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H, nq, nkv),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
        interpret=interpret_mode(),
    )(*operands)


def _dkv_call(qt, kt, vt, qpos3, kvpos3, dout_t, lse, delta, scale,
              blk_q, blk_kv, clamp: bool):
    """Per-q-head dK/dV [B, H, Lk, D] f32 (caller group-sums GQA)."""
    B, H, Lq, D = qt.shape
    Hkv, Lk = kt.shape[1], kt.shape[2]
    n_rep = H // Hkv
    bq = _pick_block(Lq, blk_q)
    bkv = _pick_block(Lk, blk_kv)
    nq, nkv = Lq // bq, Lk // bkv
    use_kvpos = kvpos3 is not None
    qmax, imin, kvmin = _block_extents(
        qpos3[:, :, 0], kvpos3[:, 0, :] if use_kvpos else None,
        bq, bkv, nkv=nkv)

    if clamp:
        def q_map(b, h, j, i, qm, im, km):
            # q-blocks before this kv-block's causal frontier re-fetch
            # the first relevant block (monotone positions only).
            return (b, h, jnp.maximum(i, im[b, j]), 0)

        def q_row_map(b, h, j, i, qm, im, km):
            return (b, h, jnp.maximum(i, im[b, j]), 0)

        def qpos_map(b, h, j, i, qm, im, km):
            return (b, jnp.maximum(i, im[b, j]), 0)
    else:
        def q_map(b, h, j, i, qm, im, km):
            return (b, h, i, 0)

        def q_row_map(b, h, j, i, qm, im, km):
            return (b, h, i, 0)

        def qpos_map(b, h, j, i, qm, im, km):
            return (b, i, 0)

    Dv = vt.shape[3]
    kv_out_spec = pl.BlockSpec((1, 1, bkv, D),
                               lambda b, h, j, i, qm, im, km: (b, h, j, 0))
    v_out_spec = pl.BlockSpec((1, 1, bkv, Dv),
                              lambda b, h, j, i, qm, im, km: (b, h, j, 0))
    in_specs = (
        [pl.BlockSpec((1, bq, 1), qpos_map)]
        + ([pl.BlockSpec((1, 1, bkv),
                         lambda b, h, j, i, qm, im, km: (b, 0, j))]
           if use_kvpos else [])
        + [pl.BlockSpec((1, 1, bq, D), q_map),
           pl.BlockSpec((1, 1, bkv, D),
                        lambda b, h, j, i, qm, im, km, r=n_rep:
                        (b, h // r, j, 0)),
           pl.BlockSpec((1, 1, bkv, Dv),
                        lambda b, h, j, i, qm, im, km, r=n_rep:
                        (b, h // r, j, 0)),
           pl.BlockSpec((1, 1, bq, Dv), q_map),
           pl.BlockSpec((1, 1, bq, 1), q_row_map),
           pl.BlockSpec((1, 1, bq, 1), q_row_map)]
    )
    operands = [qmax, imin, kvmin, qpos3]
    if use_kvpos:
        operands.append(kvpos3)
    operands += [qt, kt, vt, dout_t, lse, delta]
    dk_h, dv_h = named_pallas_call(
        "flash_bwd_dkv",
        functools.partial(_dkv_kernel, scale=scale,
                          use_kvpos=use_kvpos),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H, nkv, nq),
            in_specs=in_specs,
            out_specs=[kv_out_spec, v_out_spec],
            scratch_shapes=[
                pltpu.VMEM((bkv, D), jnp.float32),
                pltpu.VMEM((bkv, Dv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Lk, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Lk, Dv), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(*operands)
    return dk_h, dv_h


def _bwd_impl(qt, kt, vt, qpos3, kvpos3, scale, blk_q, blk_kv, out_t,
              lse, dout_t, clamp: bool):
    B, H, Lq, D = qt.shape
    Hkv, Lk = kt.shape[1], kt.shape[2]
    n_rep = H // Hkv
    # delta = rowsum(dO * O) — cheap elementwise, plain XLA.
    delta = jnp.sum(dout_t.astype(jnp.float32) * out_t.astype(jnp.float32),
                    axis=-1, keepdims=True)                   # [B, H, Lq, 1]
    dq = _dq_call(qt, kt, vt, qpos3, kvpos3, dout_t, lse, delta, scale,
                  blk_q, blk_kv, clamp)
    dk_h, dv_h = _dkv_call(qt, kt, vt, qpos3, kvpos3, dout_t, lse, delta,
                           scale, blk_q, blk_kv, clamp)
    if n_rep > 1:
        dk = dk_h.reshape(B, Hkv, n_rep, Lk, D).sum(axis=2)
        dv = dv_h.reshape(B, Hkv, n_rep, Lk, vt.shape[3]).sum(axis=2)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry (custom VJP), model layout [B, L, H, D]
# ---------------------------------------------------------------------------


def _check_chunk_alignment(Lq: int, Lk: int, blk_q: int,
                           blk_kv: int) -> None:
    """Ring chunks feed the explicit-kv-positions kernel variant; on
    real TPU its blocks must satisfy Mosaic's lane/sublane rules:
    the kv-position block's lane dim (bkv) must be a multiple of 128
    or equal the full Lk, and the q block's sublane dim (bq) a
    multiple of 8 or equal the full Lq.  The standard causal path has
    no kv-position operand and no such constraint."""
    if interpret_mode():
        return
    bkv = _pick_block(Lk, blk_kv)
    if bkv % 128 and bkv != Lk:
        raise ValueError(
            f"ring-chunk kv length {Lk} tiles into lane blocks of "
            f"{bkv} on TPU, violating the Mosaic 128-lane rule; use a "
            "chunk length that is a multiple of 128 (or a power of two "
            "<= 512)")
    bq = _pick_block(Lq, blk_q)
    if bq % 8 and bq != Lq:
        raise ValueError(
            f"ring-chunk query length {Lq} tiles into sublane blocks "
            f"of {bq} on TPU, violating the Mosaic 8-sublane rule; use "
            "a chunk length that is a multiple of 8")


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention_gqa(q, k, v, q_positions, scale,
                        blk_q: int = 256, blk_kv: int = 512):
    # Default blocks from an on-chip sweep at L=2048/D=128 (bf16, v5e):
    # (256, 512) ≈ 2.9x/2.3x the XLA reference fwd/bwd; small shapes
    # fall back via _pick_block.
    """Flash attention with positional causal masking.

    q: [B, Lq, H, D]; k: [B, Lk, Hkv, D]; v: [B, Lk, Hkv, Dv] (Hkv
    divides H; Dv may differ from D — the output is Dv wide);
    q_positions: [B, Lq] int32 absolute positions, monotonic per row —
    query at position p attends to KV slots j <= p (identical semantics
    to the reference attention mask built in models/transformer.py).
    Returns [B, Lq, H, Dv] in q.dtype.
    """
    out, _ = _fwd(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                  v.transpose(0, 2, 1, 3), q_positions[:, :, None],
                  None, scale, blk_q, blk_kv, clamp=True)
    return out.transpose(0, 2, 1, 3)


def _vjp_fwd(q, k, v, q_positions, scale, blk_q, blk_kv):
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    qpos3 = q_positions[:, :, None]
    out_t, lse = _fwd(qt, kt, vt, qpos3, None, scale, blk_q, blk_kv,
                      clamp=True)
    return out_t.transpose(0, 2, 1, 3), (qt, kt, vt, qpos3, out_t, lse)


def _vjp_bwd(scale, blk_q, blk_kv, residuals, dout):
    qt, kt, vt, qpos3, out_t, lse = residuals
    dq, dk, dv = _bwd_impl(qt, kt, vt, qpos3, None, scale, blk_q,
                           blk_kv, out_t, lse, dout.transpose(0, 2, 1, 3),
                           clamp=True)
    return (dq.transpose(0, 2, 1, 3),
            dk.transpose(0, 2, 1, 3).astype(kt.dtype),
            dv.transpose(0, 2, 1, 3).astype(vt.dtype),
            None)


flash_attention_gqa.defvjp(_vjp_fwd, _vjp_bwd)


# ---------------------------------------------------------------------------
# per-chunk entries for ring attention (parallel.longctx)
# ---------------------------------------------------------------------------


def flash_chunk_fwd(q, k, v, q_positions, kv_positions, scale,
                    blk_q: int = 256, blk_kv: int = 512):
    """One ring chunk, flash-blockwise: returns (out [B, Lq, H, D]
    normalized WITHIN the chunk, lse [B, H, Lq] f32).  kv_positions
    [B, Lk] are arbitrary absolute positions (rotated zigzag chunks);
    fully-masked rows give out = 0, lse ≈ -inf.  No VJP — the ring
    caller owns the backward (flash_chunk_grads with the global lse)."""
    _check_chunk_alignment(q.shape[1], k.shape[1], blk_q, blk_kv)
    out_t, lse = _fwd(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), q_positions[:, :, None],
                      kv_positions[:, None, :], scale, blk_q, blk_kv,
                      clamp=False)
    return out_t.transpose(0, 2, 1, 3), lse[..., 0]


def flash_chunk_grads(q, k, v, q_positions, kv_positions, out, lse,
                      dout, scale, blk_q: int = 256, blk_kv: int = 512):
    """Per-chunk flash backward against the GLOBAL softmax statistics:
    ``lse`` [B, H, Lq] is the all-chunks log-sum-exp and ``out``/
    ``dout`` the FINAL merged output/cotangent — p = exp(s - lse)
    reconstructs this chunk's exact global attention weights, so the
    returned (dq_partial, dk, dv) are exact per-chunk contributions
    (dq sums over chunks; dk/dv are complete for this chunk's KV)."""
    _check_chunk_alignment(q.shape[1], k.shape[1], blk_q, blk_kv)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dq, dk, dv = _bwd_impl(
        qt, kt, vt, q_positions[:, :, None], kv_positions[:, None, :],
        scale, blk_q, blk_kv, out.transpose(0, 2, 1, 3), lse[..., None],
        dout.transpose(0, 2, 1, 3), clamp=False)
    return (dq.transpose(0, 2, 1, 3),
            dk.transpose(0, 2, 1, 3).astype(k.dtype),
            dv.transpose(0, 2, 1, 3).astype(v.dtype))
