"""Pallas flash attention, forward + backward (SURVEY.md §2 #13).

TPU-native equivalent of the reference stack's flash-attention CUDA
kernels.  Design:

- Public layout [B, L, H, D] (matching the model); internally the
  wrapper transposes to [B, H, L, D] so every block's trailing two dims
  are (seq-block, head-dim) — the shape Mosaic requires to tile onto
  the MXU — or, for v, o and dq, (head-dim, seq-block): see the
  transposed tiles below.
- Both loop dimensions are *grid* dimensions: the forward/dq grid is
  (B, H, q-block, kv-block) and the dkv grid is (B, H, kv-block,
  q-block), with online-softmax / gradient accumulators carried in VMEM
  scratch across the innermost dimension (sequential on TPU).  VMEM
  footprint is therefore O(block), not O(L) — long-context safe.
- A grid step holds a MAJOR block of up to ``_MAJOR`` queries and one
  of up to ``_MAJOR`` keys, each several [bq, bkv] TILES (``_tiles``),
  and computes, for every q tile (kv tile in dkv), ONE wide tile over
  the run of kv (q) tiles that the causal rule leaves live
  (``_for_each_extent``): the skip keeps the tile's granularity, while
  what a step costs whatever it computes — pipeline set-up, the update
  of the running statistics — is paid once per major block, and the
  wide tile is straight-line code in which the scheduler overlaps the
  products with the element-wise work.
- The score tiles are TRANSPOSED, [keys, queries]: ``s^T = k q^T``.
  Everything per query (positions, running max and sum, ``lse``,
  ``delta``) is then a lane-dense row vector instead of a [bq, 1]
  column, the softmax reductions run down sublanes (element-wise
  across vregs, no cross-lane work), and all five products take their
  operands as they lie: ``o^T = v^T p^T`` (v enters, o leaves
  transposed: the wrappers' own layout transposes absorb it),
  ``dv = p^T dO``, ``dp^T = v dO^T``, ``dk = ds^T q`` and
  ``dq^T = k^T ds^T`` (k enters dq a second time, transposed).
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
- Causal skipping: a (q, kv) TILE is skipped when the kv tile's MIN
  position exceeds the q tile's MAX position; tile-extent scalars (per-q-tile max position,
  per-kv-tile min position, per-kv-tile first relevant q-tile) are
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
- Precision follows the INPUT dtype and nothing else.  All five
  products (``q k^T``, ``p v``, ``do v^T``, ``ds k``, ``p^T do`` /
  ``ds^T q``) take their operands in the dtype q / k / v / dO arrived
  in and accumulate in float32 (``_dot``): bf16 in -> single-pass bf16
  MXU products (a product of two bf16 numbers is exact in the float32
  accumulator), float32 in -> float32 products.  The scale is applied
  to the float32 scores, never to an operand.  The softmax statistics
  (running max and sum, ``lse``, ``delta``) and every accumulator
  (``acc``, ``dq``, ``dk``, ``dv``) are float32; ``p`` and ``ds`` are
  computed in float32 and rounded to the operand dtype for their
  product only — the rounding ``ops.attention.reference_attention``
  makes (``probs.astype(q.dtype)`` before its second einsum), so the
  kernel and the model's plain path state the same precision.

Interpret mode runs automatically off-TPU (CPU test harness).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import (NEG_INF, interpret_mode,
                                  named_pallas_call)


def _pick_block(n: int, preferred: int) -> int:
    # Every tile dim is a lane dim somewhere (the queries of the
    # transposed tiles, the keys of the v^T block and of dq's score
    # tile), so Mosaic wants it a multiple of 128 OR equal to the full
    # array dim.  A dim that fits in one block is therefore always
    # legal as-is — and no smaller divisor is (found on-chip r5: the
    # speculative verify chunk runs Lq=k+1=5 over an Lk=388 cache; the
    # old divisor scan chose bkv=4 and Mosaic refused to lower —
    # invisible to CPU interpret mode).
    if n <= preferred:
        return n
    for c in (preferred, 512, 256, 128):
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


# Contracting dims of the kernels' two product forms.
_NT = ((1,), (1,))   # a @ b.T
_NN = ((1,), (0,))   # a @ b


def _dot(a, b, contract):
    """One MXU product: operands as given, float32 accumulation.

    A product of two bf16 numbers is exact in the float32 accumulator,
    so for them an ambient ``jax_default_matmul_precision`` asks for
    nothing — and Mosaic refuses "highest" on bf16 operands ("Bad lhs
    type"), so it is pinned to DEFAULT.  float32 operands keep the
    ambient precision, as before."""
    precision = (None if a.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# The kernels.  Internal layout: q/k/v/dO [B, H, L, D]; the score tiles
# are TRANSPOSED, [keys, queries] (keys along sublanes, queries along
# lanes), so everything per query — qpos [B, 1, Lq], lse and delta
# [B, H, 1, Lq], the running max and sum — is a lane-dense row vector
# (a [bq, 1] column costs a whole vreg for every 8 queries in every
# operation that touches it, and its reductions cross lanes), the
# softmax reductions run down sublanes, element-wise, and every
# product takes its operands as they lie: s^T = k q^T, o^T = v^T p^T,
# dv = p^T dO, dp^T = v dO^T, dk = ds^T q.  kvpos is [B, Lk, 1].
# Forward and dq: grid (B, H, nq, nkv), kv innermost; dkv: grid
# (B, H, nkv, nq), q innermost; one grid step holds a MAJOR block of
# several tiles along each sequence dim (see _tiles).
# ---------------------------------------------------------------------------

# The most keys or queries one grid step holds.
_MAJOR = 1024


def _tiles(n: int, preferred: int):
    """(tile, tiles per major block) along one sequence dim: as many
    tiles as fit ``_MAJOR`` and divide the dim's tile count."""
    tile = _pick_block(n, preferred)
    n_sub = max(1, min(_MAJOR, n) // tile)
    while (n // tile) % n_sub:
        n_sub -= 1
    return tile, n_sub


class _Plan(NamedTuple):
    """How one call tiles its two sequence dims, with the scalar-prefetch
    tables (``_block_extents``, at TILE granularity)."""
    bq: int          # q tile
    nq_sub: int      # q tiles per q major block
    bkv: int         # kv tile
    n_sub: int       # kv tiles per kv major block
    qmajor: int
    major: int
    nq: int          # q major blocks
    nkv: int         # kv major blocks
    tables: tuple    # (qmax, imin, kvmin)


def _plan(Lq, Lk, blk_q, blk_kv, qpos3, kvpos3) -> _Plan:
    bq, nq_sub = _tiles(Lq, blk_q)
    bkv, n_sub = _tiles(Lk, blk_kv)
    qmajor, major = bq * nq_sub, bkv * n_sub
    tables = _block_extents(
        qpos3[:, 0, :], None if kvpos3 is None else kvpos3[:, :, 0],
        bq, bkv, nkv=Lk // bkv)
    return _Plan(bq, nq_sub, bkv, n_sub, qmajor, major, Lq // qmajor,
                 Lk // major, tables)


def _kv_fetch(p: _Plan, clamp: bool):
    """The kv major block that step (i, j) of a (.., nq, nkv) grid
    fetches.  Under the clamp, steps beyond the q major block's causal
    frontier (its last tile's max position: positions are monotone)
    re-fetch the same block, which Pallas elides.  (Contiguous kv
    positions only.)"""
    if not clamp:
        return lambda qmax, b, i, j: j
    return lambda qmax, b, i, j: jnp.minimum(
        j, qmax[b, i * p.nq_sub + p.nq_sub - 1] // p.major)


def _for_each_extent(live, body, leading: bool):
    """Call ``body(c)`` for the one c such that tiles ``[0, c]`` of the
    major block (``leading``) or ``[c, n)`` (trailing) reach from its
    edge to the farthest live tile — nothing if no tile is live.

    Under the causal rule the live tiles ARE that run (a prefix of the
    keys for a q tile, a suffix of the queries for a kv tile), so the
    body computes one wide tile of exactly the live work: straight-line
    code, in which the scheduler overlaps the products with the
    element-wise work, and one update of the running statistics.  With
    arbitrary positions (ring chunks) a dead tile inside the run is
    computed and masked: correct, only not skipped."""
    order = range(len(live)) if leading else reversed(range(len(live)))
    edge = jnp.int32(-1)
    for c in order:                     # the last live tile in order wins
        edge = jnp.where(live[c], c, edge)
    for c in range(len(live)):
        pl.when(edge == c)(functools.partial(body, c))


def _seen(qpos_ref, kvpos_ref, kv_start, kv_rows, q_cols, shape,
          sel_ref=None):
    """[keys, queries] bool (``shape``): key rows ``kv_rows`` of the kv
    block, which starts at slot ``kv_start``, against query columns
    ``q_cols`` of the q block.  ``sel_ref``: the block of a selection
    ([1, keys, queries] int8, ops/indexer.py), which a pair must also
    hold."""
    if sel_ref is not None:
        return _seen(qpos_ref, kvpos_ref, kv_start, kv_rows, q_cols,
                     shape) & (sel_ref[0, kv_rows, q_cols] != 0)
    if kvpos_ref is not None:
        kvcol = kvpos_ref[0, kv_rows, :]                         # [w, 1]
    else:
        # standard causal path: slot == position, pure iota — no
        # kvpos operand (whose block would violate the Mosaic
        # divisibility rule at odd cache lengths).
        kvcol = kv_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return kvcol <= qpos_ref[0, :, q_cols]


def _fwd_kernel(qmax_ref, imin_ref, kvmin_ref, qpos_ref, *rest,
                scale: float, use_kvpos: bool, nq_sub: int, n_sub: int,
                use_sel: bool = False):
    kvpos_ref = sel_ref = None
    if use_kvpos:
        kvpos_ref, *rest = rest
    if use_sel:
        sel_ref, *rest = rest
    q_ref, k_ref, vt_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc = rest
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nj = pl.num_programs(3)
    blk_q, major = q_ref.shape[2] // nq_sub, k_ref.shape[2]
    blk_kv = major // n_sub

    @pl.when(j == 0)
    def _():
        m_sc[:, :] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:, :] = jnp.zeros_like(l_sc)
        acc_sc[:, :] = jnp.zeros_like(acc_sc)

    def update(cols, c):
        width = (c + 1) * blk_kv
        vt = vt_ref[0, 0, :, :width]                             # [Dv, w]
        st = _dot(k_ref[0, 0, :width, :], q_ref[0, 0, cols, :],
                  _NT) * scale                                   # [w, bq]
        st = jnp.where(_seen(qpos_ref, kvpos_ref, j * major,
                             slice(0, width), cols, st.shape, sel_ref),
                       st, NEG_INF)
        m_prev, l_prev = m_sc[:, cols], l_sc[:, cols]            # [1, bq]
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        pt = jnp.exp(st - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_sc[:, cols] = m_new
        l_sc[:, cols] = l_prev * alpha + jnp.sum(pt, axis=0, keepdims=True)
        acc_sc[:, cols] = acc_sc[:, cols] * alpha + _dot(
            vt, pt.astype(vt.dtype), _NN)                        # [Dv, bq]

    for r in range(nq_sub):
        # kv tile c of this major block holds a key some row of q tile r sees
        _for_each_extent(
            [kvmin_ref[b, j * n_sub + c] <= qmax_ref[b, i * nq_sub + r]
             for c in range(n_sub)],
            functools.partial(update, slice(r * blk_q, (r + 1) * blk_q)),
            leading=True)

    @pl.when(j == nj - 1)
    def _():
        # Rows with no valid key at all (possible per ring chunk) keep
        # l = 0: guard the division -> o = 0, lse ≈ NEG_INF (the merge
        # neutral element).
        l_safe = jnp.maximum(l_sc[:, :], 1e-30)
        o_ref[0, 0, :, :] = (acc_sc[:, :] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = m_sc[:, :] + jnp.log(l_safe)


def _fwd(qt, kt, vt, qpos3, kvpos3, scale, blk_q, blk_kv,
         clamp: bool, sel_t=None):
    """qt [B,H,Lq,D], kt/vt [B,Hkv,Lk,D], qpos3 [B,1,Lq], kvpos3
    [B,Lk,1] -> out [B,H,Lq,Dv], lse [B,H,1,Lq].  clamp=True enables
    the contiguous-path fetch clamps.  ``sel_t`` [B, Lk, Lq] int8: a
    selection every head shares, an operand only where it is given (the
    kernel is then ``sparse_fwd``)."""
    B, H, Lq, D = qt.shape
    Hkv, Lk, Dv = kt.shape[1], kt.shape[2], vt.shape[3]
    n_rep = H // Hkv
    p = _plan(Lq, Lk, blk_q, blk_kv, qpos3, kvpos3)
    qmajor, major = p.qmajor, p.major
    use_kvpos = kvpos3 is not None
    fetch = _kv_fetch(p, clamp)

    def k_map(b, h, i, j, qm, im, km):
        return (b, h // n_rep, fetch(qm, b, i, j), 0)

    def vt_map(b, h, i, j, qm, im, km):
        return (b, h // n_rep, 0, fetch(qm, b, i, j))

    def q_lanes(b, h, i, j, qm, im, km):
        return (b, h, 0, i)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, H, p.nq, p.nkv),
        in_specs=(
            [pl.BlockSpec((1, 1, qmajor),
                          lambda b, h, i, j, qm, im, km: (b, 0, i))]
            + ([pl.BlockSpec((1, major, 1),
                             lambda b, h, i, j, qm, im, km: (b, j, 0))]
               if use_kvpos else [])
            + ([pl.BlockSpec((1, major, qmajor),
                             lambda b, h, i, j, qm, im, km:
                             (b, fetch(qm, b, i, j), i))]
               if sel_t is not None else [])
            + [pl.BlockSpec((1, 1, qmajor, D),
                            lambda b, h, i, j, qm, im, km: (b, h, i, 0)),
               pl.BlockSpec((1, 1, major, D), k_map),
               pl.BlockSpec((1, 1, Dv, major), vt_map)]
        ),
        out_specs=[pl.BlockSpec((1, 1, Dv, qmajor), q_lanes),
                   pl.BlockSpec((1, 1, 1, qmajor), q_lanes)],
        scratch_shapes=[
            pltpu.VMEM((1, qmajor), jnp.float32),    # running max
            pltpu.VMEM((1, qmajor), jnp.float32),    # running sumexp
            pltpu.VMEM((Dv, qmajor), jnp.float32),   # running accumulator
        ],
    )
    operands = [*p.tables, qpos3]
    if use_kvpos:
        operands.append(kvpos3)
    if sel_t is not None:
        operands.append(sel_t)
    # v and o cross the kernel's boundary transposed ([.., Dv, L]); the
    # callers' own [B, L, H, D] <-> [B, H, L, D] transposes absorb it
    operands += [qt, kt, vt.swapaxes(2, 3)]
    out_t, lse = named_pallas_call(
        "flash_fwd" if sel_t is None else "sparse_fwd",
        functools.partial(_fwd_kernel, scale=scale, use_kvpos=use_kvpos,
                          nq_sub=p.nq_sub, n_sub=p.n_sub,
                          use_sel=sel_t is not None),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Dv, Lq), qt.dtype),
            jax.ShapeDtypeStruct((B, H, 1, Lq), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(*operands)
    return out_t.swapaxes(2, 3), lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(qmax_ref, imin_ref, kvmin_ref, qpos_ref, *rest,
               scale: float, use_kvpos: bool, nq_sub: int, n_sub: int,
               use_sel: bool = False):
    kvpos_ref = sel_ref = None
    if use_kvpos:
        kvpos_ref, *rest = rest
    if use_sel:
        sel_ref, *rest = rest
    (q_ref, k_ref, kt_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
     dq_sc) = rest
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nj = pl.num_programs(3)
    blk_q, major = q_ref.shape[2] // nq_sub, k_ref.shape[2]
    blk_kv = major // n_sub

    @pl.when(j == 0)
    def _():
        dq_sc[:, :] = jnp.zeros_like(dq_sc)

    def update(cols, c):
        width = (c + 1) * blk_kv
        kt = kt_ref[0, 0, :, :width]                             # [D, w]
        do = do_ref[0, 0, cols, :]                               # [bq, Dv]
        st = _dot(k_ref[0, 0, :width, :], q_ref[0, 0, cols, :],
                  _NT) * scale                                   # [w, bq]
        pt = jnp.where(_seen(qpos_ref, kvpos_ref, j * major,
                             slice(0, width), cols, st.shape, sel_ref),
                       jnp.exp(st - lse_ref[0, 0, :, cols]), 0.0)
        dpt = _dot(v_ref[0, 0, :width, :], do, _NT)              # [w, bq]
        dst = pt * (dpt - delta_ref[0, 0, :, cols])
        dq_sc[:, cols] = dq_sc[:, cols] + _dot(
            kt, dst.astype(kt.dtype), _NN)                       # [D, bq]

    for r in range(nq_sub):
        _for_each_extent(
            [kvmin_ref[b, j * n_sub + c] <= qmax_ref[b, i * nq_sub + r]
             for c in range(n_sub)],
            functools.partial(update, slice(r * blk_q, (r + 1) * blk_q)),
            leading=True)

    @pl.when(j == nj - 1)
    def _():
        dq_ref[0, 0, :, :] = (dq_sc[:, :] * scale).astype(dq_ref.dtype)


def _dkv_kernel(qmax_ref, imin_ref, kvmin_ref, qpos_ref, *rest,
                scale: float, use_kvpos: bool, nq_sub: int, n_sub: int,
                use_sel: bool = False):
    kvpos_ref = sel_ref = None
    if use_kvpos:
        kvpos_ref, *rest = rest
    if use_sel:
        sel_ref, *rest = rest
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
     dv_ref, dk_sc, dv_sc) = rest
    b, j, i = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    ni = pl.num_programs(3)
    qmajor, major = q_ref.shape[2], k_ref.shape[2]
    blk_q, blk_kv = qmajor // nq_sub, major // n_sub

    @pl.when(i == 0)
    def _():
        dk_sc[:, :] = jnp.zeros_like(dk_sc)
        dv_sc[:, :] = jnp.zeros_like(dv_sc)

    def update(rows, c):
        cols = slice(c * blk_q, qmajor)     # from q tile c to the block's end
        q = q_ref[0, 0, cols, :]                                 # [w, D]
        do = do_ref[0, 0, cols, :]                               # [w, Dv]
        st = _dot(k_ref[0, 0, rows, :], q, _NT) * scale          # [bkv, w]
        pt = jnp.where(_seen(qpos_ref, kvpos_ref, j * major + rows.start,
                             rows, cols, st.shape, sel_ref),
                       jnp.exp(st - lse_ref[0, 0, :, cols]), 0.0)
        dv_sc[rows, :] = dv_sc[rows, :] + _dot(pt.astype(do.dtype), do, _NN)
        dpt = _dot(v_ref[0, 0, rows, :], do, _NT)                # [bkv, w]
        dst = pt * (dpt - delta_ref[0, 0, :, cols])
        dk_sc[rows, :] = dk_sc[rows, :] + _dot(dst.astype(q.dtype), q, _NN)

    for r in range(n_sub):
        # q tile c of this major block holds a query that sees a key of
        # kv tile r
        _for_each_extent(
            [qmax_ref[b, i * nq_sub + c] >= kvmin_ref[b, j * n_sub + r]
             for c in range(nq_sub)],
            functools.partial(update, slice(r * blk_kv, (r + 1) * blk_kv)),
            leading=False)

    @pl.when(i == ni - 1)
    def _():
        dk_ref[0, 0, :, :] = (dk_sc[:, :] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_sc[:, :].astype(dv_ref.dtype)


def _dq_call(qt, kt, vt, qpos3, kvpos3, dout_t, lse, delta, scale,
             blk_q, blk_kv, clamp: bool, sel_t=None):
    B, H, Lq, D = qt.shape
    Hkv, Lk, Dv = kt.shape[1], kt.shape[2], vt.shape[3]
    n_rep = H // Hkv
    p = _plan(Lq, Lk, blk_q, blk_kv, qpos3, kvpos3)
    qmajor, major = p.qmajor, p.major
    use_kvpos = kvpos3 is not None
    fetch = _kv_fetch(p, clamp)

    def kv_map(b, h, i, j, qm, im, km):
        return (b, h // n_rep, fetch(qm, b, i, j), 0)

    def kt_map(b, h, i, j, qm, im, km):
        return (b, h // n_rep, 0, fetch(qm, b, i, j))

    def q_rows(b, h, i, j, qm, im, km):
        return (b, h, i, 0)

    def q_lanes(b, h, i, j, qm, im, km):
        return (b, h, 0, i)

    in_specs = (
        [pl.BlockSpec((1, 1, qmajor),
                      lambda b, h, i, j, qm, im, km: (b, 0, i))]
        + ([pl.BlockSpec((1, major, 1),
                         lambda b, h, i, j, qm, im, km: (b, j, 0))]
           if use_kvpos else [])
        + ([pl.BlockSpec((1, major, qmajor),
                         lambda b, h, i, j, qm, im, km:
                         (b, fetch(qm, b, i, j), i))]
           if sel_t is not None else [])
        + [pl.BlockSpec((1, 1, qmajor, D), q_rows),
           pl.BlockSpec((1, 1, major, D), kv_map),
           pl.BlockSpec((1, 1, D, major), kt_map),
           pl.BlockSpec((1, 1, major, Dv), kv_map),
           pl.BlockSpec((1, 1, qmajor, Dv), q_rows),
           pl.BlockSpec((1, 1, 1, qmajor), q_lanes),
           pl.BlockSpec((1, 1, 1, qmajor), q_lanes)]
    )
    operands = [*p.tables, qpos3]
    if use_kvpos:
        operands.append(kvpos3)
    if sel_t is not None:
        operands.append(sel_t)
    # k enters twice: as it lies for s^T = k q^T, transposed for
    # dq^T = k^T ds^T; dq leaves transposed like the forward's output
    operands += [qt, kt, kt.swapaxes(2, 3), vt, dout_t, lse, delta]
    dq_t = named_pallas_call(
        "flash_bwd_dq" if sel_t is None else "sparse_bwd_dq",
        functools.partial(_dq_kernel, scale=scale, use_kvpos=use_kvpos,
                          nq_sub=p.nq_sub, n_sub=p.n_sub,
                          use_sel=sel_t is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H, p.nq, p.nkv),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, D, qmajor), q_lanes),
            scratch_shapes=[pltpu.VMEM((D, qmajor), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, D, Lq), qt.dtype),
        interpret=interpret_mode(),
    )(*operands)
    return dq_t.swapaxes(2, 3)


def _dkv_call(qt, kt, vt, qpos3, kvpos3, dout_t, lse, delta, scale,
              blk_q, blk_kv, clamp: bool, sel_t=None):
    """Per-q-head dK/dV [B, H, Lk, D]: float32 where the caller still
    has to group-sum them (GQA), else in the inputs' dtype."""
    B, H, Lq, D = qt.shape
    Hkv, Lk, Dv = kt.shape[1], kt.shape[2], vt.shape[3]
    n_rep = H // Hkv
    p = _plan(Lq, Lk, blk_q, blk_kv, qpos3, kvpos3)
    qmajor, major = p.qmajor, p.major
    use_kvpos = kvpos3 is not None

    def first(im, b, j, i):
        # q major blocks before this kv block's causal frontier (its
        # first tile's first relevant q tile) re-fetch the first
        # relevant one: monotone positions only
        return jnp.maximum(i, im[b, j * p.n_sub] // p.nq_sub) if clamp else i

    def q_rows(b, h, j, i, qm, im, km):
        return (b, h, first(im, b, j, i), 0)

    def q_lanes(b, h, j, i, qm, im, km):
        return (b, h, 0, first(im, b, j, i))

    def kv_in(b, h, j, i, qm, im, km):
        return (b, h // n_rep, j, 0)

    def kv_out(b, h, j, i, qm, im, km):
        return (b, h, j, 0)

    in_specs = (
        [pl.BlockSpec((1, 1, qmajor),
                      lambda b, h, j, i, qm, im, km:
                      (b, 0, first(im, b, j, i)))]
        + ([pl.BlockSpec((1, major, 1),
                         lambda b, h, j, i, qm, im, km: (b, j, 0))]
           if use_kvpos else [])
        + ([pl.BlockSpec((1, major, qmajor),
                         lambda b, h, j, i, qm, im, km:
                         (b, j, first(im, b, j, i)))]
           if sel_t is not None else [])
        + [pl.BlockSpec((1, 1, qmajor, D), q_rows),
           pl.BlockSpec((1, 1, major, D), kv_in),
           pl.BlockSpec((1, 1, major, Dv), kv_in),
           pl.BlockSpec((1, 1, qmajor, Dv), q_rows),
           pl.BlockSpec((1, 1, 1, qmajor), q_lanes),
           pl.BlockSpec((1, 1, 1, qmajor), q_lanes)]
    )
    operands = [*p.tables, qpos3]
    if use_kvpos:
        operands.append(kvpos3)
    if sel_t is not None:
        operands.append(sel_t)
    operands += [qt, kt, vt, dout_t, lse, delta]
    grad_dtype = jnp.float32 if n_rep > 1 else kt.dtype
    dk_h, dv_h = named_pallas_call(
        "flash_bwd_dkv" if sel_t is None else "sparse_bwd_dkv",
        functools.partial(_dkv_kernel, scale=scale, use_kvpos=use_kvpos,
                          nq_sub=p.nq_sub, n_sub=p.n_sub,
                          use_sel=sel_t is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H, p.nkv, p.nq),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, 1, major, D), kv_out),
                       pl.BlockSpec((1, 1, major, Dv), kv_out)],
            scratch_shapes=[
                pltpu.VMEM((major, D), jnp.float32),
                pltpu.VMEM((major, Dv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Lk, D), grad_dtype),
            jax.ShapeDtypeStruct((B, H, Lk, Dv), grad_dtype),
        ],
        interpret=interpret_mode(),
    )(*operands)
    return dk_h, dv_h


def _bwd_impl(qt, kt, vt, qpos3, kvpos3, scale, blk_q, blk_kv, out_t,
              lse, dout_t, clamp: bool, sel_t=None):
    B, H, Lq, D = qt.shape
    Hkv, Lk = kt.shape[1], kt.shape[2]
    n_rep = H // Hkv
    # delta = rowsum(dO * O) — cheap elementwise, plain XLA.
    delta = jnp.sum(dout_t.astype(jnp.float32) * out_t.astype(jnp.float32),
                    axis=-1)[:, :, None, :]                   # [B, H, 1, Lq]
    dq = _dq_call(qt, kt, vt, qpos3, kvpos3, dout_t, lse, delta, scale,
                  blk_q, blk_kv, clamp, sel_t)
    dk_h, dv_h = _dkv_call(qt, kt, vt, qpos3, kvpos3, dout_t, lse, delta,
                           scale, blk_q, blk_kv, clamp, sel_t)
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
    """Ring chunks meet real TPU tiling rules with whatever length the
    mesh leaves them: every tile dim is a lane dim somewhere, so a
    chunk must tile into multiples of 128 or be one full block (what
    ``_pick_block`` falls back to).  A full block is only refused where
    it cannot be meant: longer than the major block."""
    if interpret_mode():
        return
    for name, n, blk in (("query", Lq, blk_q), ("kv", Lk, blk_kv)):
        tile = _pick_block(n, blk)
        if tile % 128 and n > _MAJOR:
            raise ValueError(
                f"ring-chunk {name} length {n} has no tile that is a "
                "multiple of 128 (the Mosaic lane rule) and is too long "
                "for one block; use a chunk length that is a multiple "
                "of 128")


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention_gqa(q, k, v, q_positions, scale,
                        blk_q: int = 512, blk_kv: int = 512):
    # Default tiles from on-chip sweeps of 2026-09-28 (PR 29; bf16, one
    # v5e chip; PERF.md section 6) at the shapes the benchmark's cells
    # run — (16 | 48, 384, 8 heads of 256), (16 | 32, 1024, 32 heads, keys
    # 192 / values 128), the prefills (48, 256) and (32, 512) — and at
    # (4, 2048, GQA 32 / 8, 128): (512, 512) is the best or within 5% of
    # it for all three kernels at every one (a length that 512 does not
    # divide takes its largest 128-multiple divisor, 384 itself), with
    # ``_MAJOR`` 1024 (2048 overflows VMEM at 1024-wide tiles and runs
    # 128 / 256-wide ones slower).  Precision: see the module docstring —
    # operands in the input dtype, float32 accumulation and softmax
    # statistics, ``p`` / ``ds`` rounded as ``reference_attention``
    # rounds ``probs``.
    """Flash attention with positional causal masking.

    q: [B, Lq, H, D]; k: [B, Lk, Hkv, D]; v: [B, Lk, Hkv, Dv] (Hkv
    divides H; Dv may differ from D — the output is Dv wide);
    q_positions: [B, Lq] int32 absolute positions, monotonic per row —
    query at position p attends to KV slots j <= p (identical semantics
    to the reference attention mask built in models/transformer.py).
    Returns [B, Lq, H, Dv] in q.dtype.
    """
    out, _ = _fwd(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                  v.transpose(0, 2, 1, 3), q_positions[:, None, :],
                  None, scale, blk_q, blk_kv, clamp=True)
    return out.transpose(0, 2, 1, 3)


def _vjp_fwd(q, k, v, q_positions, scale, blk_q, blk_kv):
    # The residuals carry the names a block's checkpoint may keep
    # (models/transformer.py, REMAT_TAGS): kept, the backward kernels
    # read them as they lie and the forward is not run a second time.
    qt, kt, vt = (checkpoint_name(t.transpose(0, 2, 1, 3), "attn_qkv")
                  for t in (q, k, v))
    qpos3 = q_positions[:, None, :]
    out_t, lse = (checkpoint_name(t, "attn_out") for t in _fwd(
        qt, kt, vt, qpos3, None, scale, blk_q, blk_kv, clamp=True))
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
# the same kernels under a selection (learned sparse attention)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def sparse_attention_gqa(q, k, v, q_positions, sel_t, scale,
                         blk_q: int = 512, blk_kv: int = 512):
    """:func:`flash_attention_gqa` where a query attends to slot j iff
    ``j <= its position`` AND ``sel_t[b, j, query]`` is set: ``sel_t``
    [B, Lk, Lq] int8 is one selection for all heads (ops/indexer.py),
    laid out as the kernels' transposed score tiles are.  The kernels
    (``sparse_fwd``, ``sparse_bwd_dq``, ``sparse_bwd_dkv``) go over all
    causal blocks and mask inside them: a block none of whose keys is
    selected is computed like any other.  No gradient reaches the
    selection."""
    out, _ = _fwd(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                  v.transpose(0, 2, 1, 3), q_positions[:, None, :],
                  None, scale, blk_q, blk_kv, clamp=True, sel_t=sel_t)
    return out.transpose(0, 2, 1, 3)


def _sparse_vjp_fwd(q, k, v, q_positions, sel_t, scale, blk_q, blk_kv):
    qt, kt, vt = (checkpoint_name(t.transpose(0, 2, 1, 3), "attn_qkv")
                  for t in (q, k, v))
    qpos3 = q_positions[:, None, :]
    out_t, lse = (checkpoint_name(t, "attn_out") for t in _fwd(
        qt, kt, vt, qpos3, None, scale, blk_q, blk_kv, clamp=True,
        sel_t=sel_t))
    return out_t.transpose(0, 2, 1, 3), (qt, kt, vt, qpos3, sel_t, out_t,
                                         lse)


def _sparse_vjp_bwd(scale, blk_q, blk_kv, residuals, dout):
    qt, kt, vt, qpos3, sel_t, out_t, lse = residuals
    dq, dk, dv = _bwd_impl(qt, kt, vt, qpos3, None, scale, blk_q,
                           blk_kv, out_t, lse, dout.transpose(0, 2, 1, 3),
                           clamp=True, sel_t=sel_t)
    return (dq.transpose(0, 2, 1, 3),
            dk.transpose(0, 2, 1, 3).astype(kt.dtype),
            dv.transpose(0, 2, 1, 3).astype(vt.dtype),
            None, None)


sparse_attention_gqa.defvjp(_sparse_vjp_fwd, _sparse_vjp_bwd)


# ---------------------------------------------------------------------------
# per-chunk entries for ring attention (parallel.longctx)
# ---------------------------------------------------------------------------


def flash_chunk_fwd(q, k, v, q_positions, kv_positions, scale,
                    blk_q: int = 512, blk_kv: int = 512):
    """One ring chunk, flash-blockwise: returns (out [B, Lq, H, D]
    normalized WITHIN the chunk, lse [B, H, Lq] f32).  kv_positions
    [B, Lk] are arbitrary absolute positions (rotated zigzag chunks);
    fully-masked rows give out = 0, lse ≈ -inf.  No VJP — the ring
    caller owns the backward (flash_chunk_grads with the global lse)."""
    _check_chunk_alignment(q.shape[1], k.shape[1], blk_q, blk_kv)
    out_t, lse = _fwd(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), q_positions[:, None, :],
                      kv_positions[:, :, None], scale, blk_q, blk_kv,
                      clamp=False)
    return out_t.transpose(0, 2, 1, 3), lse[:, :, 0, :]


def flash_chunk_grads(q, k, v, q_positions, kv_positions, out, lse,
                      dout, scale, blk_q: int = 512, blk_kv: int = 512):
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
        qt, kt, vt, q_positions[:, None, :], kv_positions[:, :, None],
        scale, blk_q, blk_kv, out.transpose(0, 2, 1, 3), lse[:, :, None, :],
        dout.transpose(0, 2, 1, 3), clamp=False)
    return (dq.transpose(0, 2, 1, 3),
            dk.transpose(0, 2, 1, 3).astype(k.dtype),
            dv.transpose(0, 2, 1, 3).astype(v.dtype))
