"""Pallas flash attention, forward + backward (SURVEY.md §2 #13).

TPU-native equivalent of the reference stack's flash-attention CUDA
kernels.  Design:

- Public layout [B, L, H, D] (matching the model); internally the
  wrapper transposes to [B, H, L, D] so every block's trailing two dims
  are (seq-block, head-dim) — the shape Mosaic requires to tile onto
  the MXU — or, for v, o and dq, (head-dim, seq-block): see the
  transposed tiles below.
- Both loop dimensions are *grid* dimensions: the forward/dq grid is
  (B, Hkv, q-block, kv-block) and the dkv grid is (B, Hkv, kv-block,
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
- GQA: a grid step holds the GROUP of ``n_rep = H // Hkv`` query heads
  that share a key head (q, o^T, dO, dq^T, ``lse``, ``delta`` blocks
  are ``n_rep`` heads thick, the accumulators one slab a head), so K,
  V and the selection's block are fetched once a group, and the mask
  of a wide tile (causal rule, ``kv_positions``, the selection's int8
  block unpacked) is built once a group, as a float32 tile that every
  head adds to its scores (``_TileMask``), in a rolled loop over the
  heads (``_for_each_head``).  A group's forward goes over an extent
  of several kv tiles in two passes, tile by tile (``_fwd_kernel``).
  No materialized ``repeat_kv``.  ``n_rep`` = 1 (one key head a query
  head) is the program of a grid over query heads: the same grid
  extents and blocks, the select on the boolean mask, no loop.
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
- A WINDOW (``flash_attention_gqa(..., window=w)``, slot == position
  only): a query at position p also needs ``p - j < w``.  The rule
  enters the mask, the tile extents (a q tile's live kv tiles of a
  major block are a run ``[lo, hi]``, no longer a prefix:
  ``_for_each_run``) and the fetch clamps (from below too), so tiles
  wholly behind the window are neither fetched nor multiplied.  A
  Python branch: without a window grid, index maps and kernel bodies
  are the program they were.  The windowed calls carry names of their
  own (``flash_fwd_window``, ``flash_dq_window``, ``flash_dkv_window``).
- Rows with NO valid key (possible per ring chunk) produce out = 0 and
  lse ≈ -inf — exactly the neutral element of the streaming-softmax
  merge in parallel.longctx.ring_attention.
- Backward is the standard two-kernel flash split: dQ over kv-blocks,
  dK/dV over q-blocks, recomputing P from the saved LSE.  The query
  heads of a group add into ONE float32 dK / dV accumulator inside the
  dK/dV kernel, which leaves [B, Hkv, Lk, D] in the inputs' dtype: the
  group's sum is rounded once, and no per-q-head gradient exists.  The
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


def _window_extents(q_positions, kvmin, bq, bkv, window):
    """The two tables a window adds (slot == position: kv tile j ends at
    ``kvmin[j] + bkv - 1``):

    qmin [B, nq]   — smallest position in q-block i; pair (i, j) lies
                     wholly behind the window iff
                     ``qmin[i] - (kvmin[j] + bkv - 1) >= window``.
    imax [B, nkv]  — number of q-blocks that kv-block j is not wholly
                     behind (= one past the last relevant q-block when q
                     positions are monotone).
    """
    B, Lq = q_positions.shape
    qmin = jnp.min(q_positions.reshape(B, Lq // bq, bq),
                   axis=-1).astype(jnp.int32)
    imax = jnp.sum(qmin[:, :, None] - (kvmin[:, None, :] + (bkv - 1))
                   < window, axis=1).astype(jnp.int32)
    return qmin, imax


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
# Forward and dq: grid (B, Hkv, nq, nkv), kv innermost; dkv: grid
# (B, Hkv, nkv, nq), q innermost; one grid step holds a MAJOR block of
# several tiles along each sequence dim (see _tiles), for the H // Hkv
# query heads of one key head.
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
    tables: tuple    # (qmax, imin, kvmin); a window adds (qmin, imax)


def _plan(Lq, Lk, blk_q, blk_kv, qpos3, kvpos3, window=None) -> _Plan:
    bq, nq_sub = _tiles(Lq, blk_q)
    bkv, n_sub = _tiles(Lk, blk_kv)
    qmajor, major = bq * nq_sub, bkv * n_sub
    tables = _block_extents(
        qpos3[:, 0, :], None if kvpos3 is None else kvpos3[:, :, 0],
        bq, bkv, nkv=Lk // bkv)
    if window is not None:
        assert kvpos3 is None, "a window needs slot == position"
        tables += _window_extents(qpos3[:, 0, :], tables[2], bq, bkv, window)
    return _Plan(bq, nq_sub, bkv, n_sub, qmajor, major, Lq // qmajor,
                 Lk // major, tables)


def _kv_fetch(p: _Plan, clamp: bool, window=None):
    """The kv major block that step (i, j) of a (.., nq, nkv) grid
    fetches, from the scalar-prefetch tables ``t``.  Under the clamp,
    steps beyond the q major block's causal frontier (its last tile's
    max position: positions are monotone) re-fetch the same block, which
    Pallas elides; under a window so do the steps before the first block
    that holds a key of the q major block's first tile's window.
    (Contiguous kv positions only.)"""
    if not clamp:
        return lambda t, b, i, j: j
    if window is None:
        return lambda t, b, i, j: jnp.minimum(
            j, t[0][b, i * p.nq_sub + p.nq_sub - 1] // p.major)
    return lambda t, b, i, j: jnp.clip(
        j, jnp.maximum(t[3][b, i * p.nq_sub] - (window - 1), 0) // p.major,
        t[0][b, i * p.nq_sub + p.nq_sub - 1] // p.major)


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


def _for_each_run(live, body):
    """Call ``body(lo, hi)`` for the run ``[lo, hi]`` of tiles of the
    major block from its first live tile to its last — nothing if no
    tile is live.  Under the causal rule AND a window the live tiles are
    that run (a q tile's window is an interval of keys, a kv tile is
    seen by an interval of queries): ``_for_each_extent`` without the
    edge that a window moves."""
    n = len(live)
    first, last = jnp.int32(n), jnp.int32(-1)
    for c in reversed(range(n)):
        first = jnp.where(live[c], c, first)
    for c in range(n):
        last = jnp.where(live[c], c, last)
    for lo in range(n):
        for hi in range(lo, n):
            pl.when((first == lo) & (last == hi))(
                functools.partial(body, lo, hi))


def _for_each_live_kv(window, live, update):
    """``update(lo, hi)`` over a q tile's live kv tiles of a major
    block: the leading extent ``[0, c]`` without a window, the run under
    one."""
    if window is None:
        _for_each_extent(live, functools.partial(update, 0), leading=True)
    else:
        _for_each_run(live, update)


def _windowed(seen, window, tables, b, q_tile, kv_tile, blk_kv):
    """``seen`` (a q tile and a kv tile hold a causal pair: each
    kernel's own comparison, as it was) and, under a window, that the kv
    tile is not wholly behind the q tile's window.  ``q_tile``,
    ``kv_tile``: thunks of the tiles' indices, so that nothing is
    computed without a window."""
    if window is None:
        return seen
    return seen & (tables[3][b, q_tile()] - tables[2][b, kv_tile()]
                   - (blk_kv - 1) < window)


def _seen(qpos_ref, kvpos_ref, kv_start, kv_rows, q_cols, shape,
          sel_ref=None, window=None):
    """[keys, queries] bool (``shape``): key rows ``kv_rows`` of the kv
    block, which starts at slot ``kv_start``, against query columns
    ``q_cols`` of the q block.  ``sel_ref``: the block of a selection
    ([1, keys, queries] int8, ops/indexer.py), which a pair must also
    hold.  ``window``: a query sees no key ``window`` or more positions
    behind it (slot == position)."""
    if window is not None:
        kvcol = kv_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        qpos = qpos_ref[0, :, q_cols]
        return (kvcol <= qpos) & (qpos - kvcol < window)
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


class _TileMask:
    """The mask of one wide tile (``_seen``), built ONCE and applied by
    every query head of the grid step's group.

    One head (``bias_ref`` None): the select itself on the boolean
    tile, the program of a grid step that held one head (``seen`` is
    called where that step built it: after the head's scores).  A
    group: a float32 tile in VMEM scratch, 0 where the pair is seen and
    ``fill`` elsewhere, written here, which every head ADDS to its
    scores (one add a head instead of the causal compare, the
    selection's unpacking and the select).  The sums are exact:
    ``s + 0`` is ``s``; the forward's ``fill`` is ``NEG_INF``, whose
    ulp (7.6e22) no score comes near, so ``s + NEG_INF`` IS
    ``NEG_INF``; the backward's is ``_DEAD``, under which
    ``exp(s + _DEAD - lse)`` is 0 for every ``lse`` a forward can leave
    (``NEG_INF`` itself would give ``exp(0)`` on a row whose ``lse`` is
    ``NEG_INF``: a ring chunk's row with no key)."""

    def __init__(self, seen, bias_ref, fill, at):
        self._seen, self.bias_ref, self.at = seen, bias_ref, at
        if bias_ref is not None:
            bias_ref[at] = jnp.where(seen(), 0.0, fill)

    def scores(self, st, rows=None):
        """``st`` where the pair is seen, ``NEG_INF`` elsewhere; a
        group may pass the tile's ``rows`` that ``st`` holds."""
        if self.bias_ref is None:
            return jnp.where(self._seen(), st, NEG_INF)
        return st + self.bias_ref[self.at if rows is None
                                  else (rows, self.at[1])]

    def probs(self, st, lse):
        """``exp(st - lse)`` where the pair is seen, 0 elsewhere."""
        if self.bias_ref is None:
            return jnp.where(self._seen(), jnp.exp(st - lse), 0.0)
        return jnp.exp(st + self.bias_ref[self.at] - lse)


def _for_each_head(n_rep: int, body):
    """``body(h)`` for the ``n_rep`` query heads of the step's group:
    one head is the call itself; a group is a ROLLED loop.  (Unrolled,
    eight heads' wide tiles are 67 000 bundles of straight-line code in
    which the scheduler overlaps nothing across heads, and they ran
    1.5 x slower on the chip than one head a step at FEWER bundles a
    head: the code's size, not its schedule; PERF.md section 6,
    PR 44.)"""
    if n_rep == 1:
        body(0)
        return

    def turn(h, carry):
        body(h)
        return carry

    jax.lax.fori_loop(0, n_rep, turn, None)


# The backward's fill of a group's mask tile: see _TileMask.
_DEAD = -3e38


def _split_tables(refs, window):
    """(the prefetched tables, qpos_ref, the other refs) of a kernel's
    arguments: three tables, five under a window."""
    n = 3 if window is None else 5
    return refs[:n], refs[n], refs[n + 1:]


def _fwd_kernel(*refs, scale: float, use_kvpos: bool, nq_sub: int,
                n_sub: int, use_sel: bool = False, window=None):
    tables, qpos_ref, rest = _split_tables(refs, window)
    qmax_ref, _, kvmin_ref = tables[:3]
    kvpos_ref = sel_ref = None
    if use_kvpos:
        kvpos_ref, *rest = rest
    if use_sel:
        sel_ref, *rest = rest
    (q_ref, k_ref, vt_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc,
     *bias_sc) = rest
    bias_sc, st_sc = bias_sc or (None, None)
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nj = pl.num_programs(3)
    n_rep = q_ref.shape[1]
    blk_q, major = q_ref.shape[2] // nq_sub, k_ref.shape[2]
    blk_kv = major // n_sub

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def update(cols, lo, hi):
        # kv tiles lo .. hi of the major block (lo is 0 without a window)
        span = slice(lo * blk_kv, (hi + 1) * blk_kv)
        width = span.stop - span.start
        mask = _TileMask(
            functools.partial(_seen, qpos_ref, kvpos_ref,
                              j * major + span.start if lo else j * major,
                              span, cols, (width, blk_q), sel_ref, window),
            bias_sc, NEG_INF, (slice(0, width), slice(None)))

        def group_head(h):
            # An extent of several kv tiles, a head of a group: two
            # passes over the tiles, the scores between them in VMEM.
            # Tile by tile the scheduler overlaps one tile's product
            # with the element-wise work of its neighbour, which it does
            # not inside one wide tile (there the two products and the
            # softmax run one after the other: PERF.md section 6, PR 44).
            q = q_ref[0, h, cols, :]
            m_prev, l_prev = m_sc[h, :, cols], l_sc[h, :, cols]  # [1, bq]
            m_new = m_prev

            def tile(t):     # (its rows of the extent, of the major block)
                rows = slice(t * blk_kv, (t + 1) * blk_kv)
                return rows, slice(rows.start + span.start,
                                   rows.stop + span.start)

            for t in range(hi + 1 - lo):
                rows, krows = tile(t)
                st = mask.scores(_dot(k_ref[0, 0, krows, :], q, _NT) * scale,
                                 rows)                           # [bkv, bq]
                st_sc[rows, :] = st
                m_new = jnp.maximum(m_new,
                                    jnp.max(st, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            m_sc[h, :, cols] = m_new
            l_new = l_prev * alpha
            acc_sc[h, :, cols] = acc_sc[h, :, cols] * alpha
            for t in range(hi + 1 - lo):
                rows, krows = tile(t)
                pt = jnp.exp(st_sc[rows, :] - m_new)
                l_new = l_new + jnp.sum(pt, axis=0, keepdims=True)
                acc_sc[h, :, cols] = acc_sc[h, :, cols] + _dot(
                    vt_ref[0, 0, :, krows], pt.astype(vt_ref.dtype), _NN)
            l_sc[h, :, cols] = l_new

        def one_head(h):             # ONE wide tile: the step of one head
            vt = vt_ref[0, 0, :, span]                           # [Dv, w]
            st = mask.scores(_dot(k_ref[0, 0, span, :],
                                  q_ref[0, h, cols, :], _NT) * scale)
            m_prev, l_prev = m_sc[h, :, cols], l_sc[h, :, cols]  # [1, bq]
            m_new = jnp.maximum(m_prev,
                                jnp.max(st, axis=0, keepdims=True))
            pt = jnp.exp(st - m_new)
            alpha = jnp.exp(m_prev - m_new)
            m_sc[h, :, cols] = m_new
            l_sc[h, :, cols] = l_prev * alpha + jnp.sum(
                pt, axis=0, keepdims=True)
            acc_sc[h, :, cols] = acc_sc[h, :, cols] * alpha + _dot(
                vt, pt.astype(vt.dtype), _NN)                    # [Dv, bq]

        _for_each_head(n_rep,
                       group_head if n_rep > 1 and hi > lo else one_head)

    for r in range(nq_sub):
        # kv tile c of this major block holds a key some row of q tile r sees
        _for_each_live_kv(
            window,
            [_windowed(
                kvmin_ref[b, j * n_sub + c] <= qmax_ref[b, i * nq_sub + r],
                window, tables, b, lambda: i * nq_sub + r,
                lambda: j * n_sub + c, blk_kv) for c in range(n_sub)],
            functools.partial(update, slice(r * blk_q, (r + 1) * blk_q)))

    @pl.when(j == nj - 1)
    def _():
        # Rows with no valid key at all (possible per ring chunk) keep
        # l = 0: guard the division -> o = 0, lse ≈ NEG_INF (the merge
        # neutral element).
        def head(h):
            l_safe = jnp.maximum(l_sc[h], 1e-30)
            o_ref[0, h] = (acc_sc[h] / l_safe).astype(o_ref.dtype)
            lse_ref[0, h] = m_sc[h] + jnp.log(l_safe)

        _for_each_head(n_rep, head)


# Mosaic's default scoped VMEM limit on a v5e: what a grid step of ONE
# head's blocks compiles under, with its wide tile's temporaries.
_SCOPED_VMEM = 16 << 20


def _group_params(n_rep: int, head_bytes: int, tile: tuple, tiles: int = 1):
    """(compiler params, scratch tiles) of a call whose grid step holds
    ``n_rep`` heads.  One head: nothing, the default limit and no
    scratch tile.  A group asks for the default limit plus what it
    adds, from the blocks' sizes: ``head_bytes`` (a head's blocks,
    double buffered, and its accumulators) for every further head, and
    ``tiles`` float32 scratch tiles of shape ``tile`` (the mask's,
    ``_TileMask``; the forward's scores) with one more for a
    temporary."""
    if n_rep == 1:
        return None, []
    tile_bytes = 4 * tile[0] * tile[1]
    return (pltpu.CompilerParams(vmem_limit_bytes=_SCOPED_VMEM
                                 + (n_rep - 1) * head_bytes
                                 + (tiles + 1) * tile_bytes),
            [pltpu.VMEM(tile, jnp.float32)] * tiles)


def _kernel_name(which: str, sel_t, window) -> str:
    """The name a call reaches the device trace under."""
    if window is not None:
        return {"fwd": "flash_fwd_window", "bwd_dq": "flash_dq_window",
                "bwd_dkv": "flash_dkv_window"}[which]
    return ("flash_" if sel_t is None else "sparse_") + which


def _fwd(qt, kt, vt, qpos3, kvpos3, scale, blk_q, blk_kv,
         clamp: bool, sel_t=None, window=None):
    """qt [B,H,Lq,D], kt/vt [B,Hkv,Lk,D], qpos3 [B,1,Lq], kvpos3
    [B,Lk,1] -> out [B,H,Lq,Dv], lse [B,H,1,Lq].  clamp=True enables
    the contiguous-path fetch clamps.  ``sel_t`` [B, Lk, Lq] int8: a
    selection every head shares, an operand only where it is given (the
    kernel is then ``sparse_fwd``).  A grid step holds the ``H // Hkv``
    query heads of one key head."""
    B, H, Lq, D = qt.shape
    Hkv, Lk, Dv = kt.shape[1], kt.shape[2], vt.shape[3]
    n_rep = H // Hkv
    assert window is None or sel_t is None
    p = _plan(Lq, Lk, blk_q, blk_kv, qpos3, kvpos3, window)
    qmajor, major = p.qmajor, p.major
    use_kvpos = kvpos3 is not None
    fetch = _kv_fetch(p, clamp, window)
    params, bias = _group_params(
        n_rep, qmajor * (2 * qt.dtype.itemsize * (D + Dv) + 4 * (Dv + 32)),
        (major, p.bq), tiles=2)

    def k_map(b, g, i, j, *t):
        return (b, g, fetch(t, b, i, j), 0)

    def vt_map(b, g, i, j, *t):
        return (b, g, 0, fetch(t, b, i, j))

    def q_lanes(b, g, i, j, *t):
        return (b, g, 0, i)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(p.tables),
        grid=(B, Hkv, p.nq, p.nkv),
        in_specs=(
            [pl.BlockSpec((1, 1, qmajor),
                          lambda b, g, i, j, *t: (b, 0, i))]
            + ([pl.BlockSpec((1, major, 1),
                             lambda b, g, i, j, *t: (b, j, 0))]
               if use_kvpos else [])
            + ([pl.BlockSpec((1, major, qmajor),
                             lambda b, g, i, j, *t:
                             (b, fetch(t, b, i, j), i))]
               if sel_t is not None else [])
            + [pl.BlockSpec((1, n_rep, qmajor, D),
                            lambda b, g, i, j, *t: (b, g, i, 0)),
               pl.BlockSpec((1, 1, major, D), k_map),
               pl.BlockSpec((1, 1, Dv, major), vt_map)]
        ),
        out_specs=[pl.BlockSpec((1, n_rep, Dv, qmajor), q_lanes),
                   pl.BlockSpec((1, n_rep, 1, qmajor), q_lanes)],
        scratch_shapes=[          # one slab a head of the group
            pltpu.VMEM((n_rep, 1, qmajor), jnp.float32),   # running max
            pltpu.VMEM((n_rep, 1, qmajor), jnp.float32),   # running sumexp
            pltpu.VMEM((n_rep, Dv, qmajor), jnp.float32),  # accumulator
        ] + bias,
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
        _kernel_name("fwd", sel_t, window),
        functools.partial(_fwd_kernel, scale=scale, use_kvpos=use_kvpos,
                          nq_sub=p.nq_sub, n_sub=p.n_sub,
                          use_sel=sel_t is not None,
                          **({} if window is None else {"window": window})),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Dv, Lq), qt.dtype),
            jax.ShapeDtypeStruct((B, H, 1, Lq), jnp.float32),
        ],
        compiler_params=params,
        interpret=interpret_mode(),
    )(*operands)
    return out_t.swapaxes(2, 3), lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(*refs, scale: float, use_kvpos: bool, nq_sub: int,
               n_sub: int, use_sel: bool = False, window=None):
    tables, qpos_ref, rest = _split_tables(refs, window)
    qmax_ref, _, kvmin_ref = tables[:3]
    kvpos_ref = sel_ref = None
    if use_kvpos:
        kvpos_ref, *rest = rest
    if use_sel:
        sel_ref, *rest = rest
    (q_ref, k_ref, kt_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
     dq_sc, *bias_sc) = rest
    (bias_sc,) = bias_sc or (None,)
    b, i, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nj = pl.num_programs(3)
    n_rep = q_ref.shape[1]
    blk_q, major = q_ref.shape[2] // nq_sub, k_ref.shape[2]
    blk_kv = major // n_sub

    @pl.when(j == 0)
    def _():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def update(cols, lo, hi):
        # kv tiles lo .. hi of the major block (lo is 0 without a window)
        span = slice(lo * blk_kv, (hi + 1) * blk_kv)
        width = span.stop - span.start
        mask = _TileMask(
            functools.partial(_seen, qpos_ref, kvpos_ref,
                              j * major + span.start if lo else j * major,
                              span, cols, (width, blk_q), sel_ref, window),
            bias_sc, _DEAD, (slice(0, width), slice(None)))

        def head(h):                 # one of those that share k, v, the mask
            kt = kt_ref[0, 0, :, span]                           # [D, w]
            do = do_ref[0, h, cols, :]                           # [bq, Dv]
            st = _dot(k_ref[0, 0, span, :], q_ref[0, h, cols, :],
                      _NT) * scale                               # [w, bq]
            pt = mask.probs(st, lse_ref[0, h, :, cols])
            dpt = _dot(v_ref[0, 0, span, :], do, _NT)            # [w, bq]
            dst = pt * (dpt - delta_ref[0, h, :, cols])
            dq_sc[h, :, cols] = dq_sc[h, :, cols] + _dot(
                kt, dst.astype(kt.dtype), _NN)                   # [D, bq]

        _for_each_head(n_rep, head)

    for r in range(nq_sub):
        _for_each_live_kv(
            window,
            [_windowed(
                kvmin_ref[b, j * n_sub + c] <= qmax_ref[b, i * nq_sub + r],
                window, tables, b, lambda: i * nq_sub + r,
                lambda: j * n_sub + c, blk_kv) for c in range(n_sub)],
            functools.partial(update, slice(r * blk_q, (r + 1) * blk_q)))

    @pl.when(j == nj - 1)
    def _():
        def head(h):
            dq_ref[0, h] = (dq_sc[h] * scale).astype(dq_ref.dtype)

        _for_each_head(n_rep, head)


def _dkv_kernel(*refs, scale: float, use_kvpos: bool, nq_sub: int,
                n_sub: int, use_sel: bool = False, window=None):
    tables, qpos_ref, rest = _split_tables(refs, window)
    qmax_ref, _, kvmin_ref = tables[:3]
    kvpos_ref = sel_ref = None
    if use_kvpos:
        kvpos_ref, *rest = rest
    if use_sel:
        sel_ref, *rest = rest
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
     dv_ref, dk_sc, dv_sc, *bias_sc) = rest
    (bias_sc,) = bias_sc or (None,)
    b, j, i = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    ni = pl.num_programs(3)
    n_rep = q_ref.shape[1]
    qmajor, major = q_ref.shape[2], k_ref.shape[2]
    blk_q, blk_kv = qmajor // nq_sub, major // n_sub

    @pl.when(i == 0)
    def _():
        dk_sc[:, :] = jnp.zeros_like(dk_sc)
        dv_sc[:, :] = jnp.zeros_like(dv_sc)

    def update(rows, lo, hi):
        # q tiles lo .. hi of the major block (hi is its last without a
        # window: from q tile lo to the block's end)
        cols = slice(lo * blk_q, (hi + 1) * blk_q)
        width = cols.stop - cols.start
        mask = _TileMask(
            functools.partial(_seen, qpos_ref, kvpos_ref,
                              j * major + rows.start, rows, cols,
                              (blk_kv, width), sel_ref, window),
            bias_sc, _DEAD, (slice(None), slice(0, width)))

        def head(h):     # the group's heads add into ONE dk / dv, in float32
            q = q_ref[0, h, cols, :]                             # [w, D]
            do = do_ref[0, h, cols, :]                           # [w, Dv]
            st = _dot(k_ref[0, 0, rows, :], q, _NT) * scale      # [bkv, w]
            pt = mask.probs(st, lse_ref[0, h, :, cols])
            dv_sc[rows, :] = dv_sc[rows, :] + _dot(
                pt.astype(do.dtype), do, _NN)
            dpt = _dot(v_ref[0, 0, rows, :], do, _NT)            # [bkv, w]
            dst = pt * (dpt - delta_ref[0, h, :, cols])
            dk_sc[rows, :] = dk_sc[rows, :] + _dot(
                dst.astype(q.dtype), q, _NN)

        _for_each_head(n_rep, head)

    for r in range(n_sub):
        # q tile c of this major block holds a query that sees a key of
        # kv tile r
        live = [_windowed(
            qmax_ref[b, i * nq_sub + c] >= kvmin_ref[b, j * n_sub + r],
            window, tables, b, lambda: i * nq_sub + c, lambda: j * n_sub + r,
            blk_kv) for c in range(nq_sub)]
        update_r = functools.partial(update,
                                     slice(r * blk_kv, (r + 1) * blk_kv))
        if window is None:
            _for_each_extent(
                live, lambda c: update_r(c, nq_sub - 1), leading=False)
        else:
            _for_each_run(live, update_r)

    @pl.when(i == ni - 1)
    def _():
        dk_ref[0, 0, :, :] = (dk_sc[:, :] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_sc[:, :].astype(dv_ref.dtype)


def _dq_call(qt, kt, vt, qpos3, kvpos3, dout_t, lse, delta, scale,
             blk_q, blk_kv, clamp: bool, sel_t=None, window=None):
    B, H, Lq, D = qt.shape
    Hkv, Lk, Dv = kt.shape[1], kt.shape[2], vt.shape[3]
    n_rep = H // Hkv
    p = _plan(Lq, Lk, blk_q, blk_kv, qpos3, kvpos3, window)
    qmajor, major = p.qmajor, p.major
    use_kvpos = kvpos3 is not None
    fetch = _kv_fetch(p, clamp, window)
    params, bias = _group_params(
        n_rep, qmajor * (2 * qt.dtype.itemsize * (2 * D + Dv) + 4 * (D + 32)),
        (major, p.bq))

    def kv_map(b, g, i, j, *t):
        return (b, g, fetch(t, b, i, j), 0)

    def kt_map(b, g, i, j, *t):
        return (b, g, 0, fetch(t, b, i, j))

    def q_rows(b, g, i, j, *t):
        return (b, g, i, 0)

    def q_lanes(b, g, i, j, *t):
        return (b, g, 0, i)

    in_specs = (
        [pl.BlockSpec((1, 1, qmajor),
                      lambda b, g, i, j, *t: (b, 0, i))]
        + ([pl.BlockSpec((1, major, 1),
                         lambda b, g, i, j, *t: (b, j, 0))]
           if use_kvpos else [])
        + ([pl.BlockSpec((1, major, qmajor),
                         lambda b, g, i, j, *t:
                         (b, fetch(t, b, i, j), i))]
           if sel_t is not None else [])
        + [pl.BlockSpec((1, n_rep, qmajor, D), q_rows),
           pl.BlockSpec((1, 1, major, D), kv_map),
           pl.BlockSpec((1, 1, D, major), kt_map),
           pl.BlockSpec((1, 1, major, Dv), kv_map),
           pl.BlockSpec((1, n_rep, qmajor, Dv), q_rows),
           pl.BlockSpec((1, n_rep, 1, qmajor), q_lanes),
           pl.BlockSpec((1, n_rep, 1, qmajor), q_lanes)]
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
        _kernel_name("bwd_dq", sel_t, window),
        functools.partial(_dq_kernel, scale=scale, use_kvpos=use_kvpos,
                          nq_sub=p.nq_sub, n_sub=p.n_sub,
                          use_sel=sel_t is not None,
                          **({} if window is None else {"window": window})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(p.tables),
            grid=(B, Hkv, p.nq, p.nkv),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, n_rep, D, qmajor), q_lanes),
            scratch_shapes=[pltpu.VMEM((n_rep, D, qmajor), jnp.float32)]
            + bias,
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, D, Lq), qt.dtype),
        compiler_params=params,
        interpret=interpret_mode(),
    )(*operands)
    return dq_t.swapaxes(2, 3)


def _dkv_call(qt, kt, vt, qpos3, kvpos3, dout_t, lse, delta, scale,
              blk_q, blk_kv, clamp: bool, sel_t=None, window=None):
    """dK [B, Hkv, Lk, D] and dV [B, Hkv, Lk, Dv] in the inputs' dtype:
    the query heads of a key head add into one float32 accumulator
    inside the kernel, which is rounded once."""
    B, H, Lq, D = qt.shape
    Hkv, Lk, Dv = kt.shape[1], kt.shape[2], vt.shape[3]
    n_rep = H // Hkv
    p = _plan(Lq, Lk, blk_q, blk_kv, qpos3, kvpos3, window)
    qmajor, major = p.qmajor, p.major
    use_kvpos = kvpos3 is not None
    params, bias = _group_params(
        n_rep, qmajor * (2 * qt.dtype.itemsize * (D + Dv) + 128),
        (p.bkv, qmajor))

    def first(t, b, j, i):
        # q major blocks before this kv block's causal frontier (its
        # first tile's first relevant q tile) re-fetch the first
        # relevant one: monotone positions only; under a window those
        # past the last q tile that its last tile is not wholly behind
        # re-fetch that one
        if not clamp:
            return i
        i = jnp.maximum(i, t[1][b, j * p.n_sub] // p.nq_sub)
        if window is None:
            return i
        return jnp.minimum(i, jnp.maximum(
            t[4][b, j * p.n_sub + p.n_sub - 1] - 1, 0) // p.nq_sub)

    def q_rows(b, g, j, i, *t):
        return (b, g, first(t, b, j, i), 0)

    def q_lanes(b, g, j, i, *t):
        return (b, g, 0, first(t, b, j, i))

    def kv(b, g, j, i, *t):
        return (b, g, j, 0)

    in_specs = (
        [pl.BlockSpec((1, 1, qmajor),
                      lambda b, g, j, i, *t: (b, 0, first(t, b, j, i)))]
        + ([pl.BlockSpec((1, major, 1),
                         lambda b, g, j, i, *t: (b, j, 0))]
           if use_kvpos else [])
        + ([pl.BlockSpec((1, major, qmajor),
                         lambda b, g, j, i, *t:
                         (b, j, first(t, b, j, i)))]
           if sel_t is not None else [])
        + [pl.BlockSpec((1, n_rep, qmajor, D), q_rows),
           pl.BlockSpec((1, 1, major, D), kv),
           pl.BlockSpec((1, 1, major, Dv), kv),
           pl.BlockSpec((1, n_rep, qmajor, Dv), q_rows),
           pl.BlockSpec((1, n_rep, 1, qmajor), q_lanes),
           pl.BlockSpec((1, n_rep, 1, qmajor), q_lanes)]
    )
    operands = [*p.tables, qpos3]
    if use_kvpos:
        operands.append(kvpos3)
    if sel_t is not None:
        operands.append(sel_t)
    operands += [qt, kt, vt, dout_t, lse, delta]
    return named_pallas_call(
        _kernel_name("bwd_dkv", sel_t, window),
        functools.partial(_dkv_kernel, scale=scale, use_kvpos=use_kvpos,
                          nq_sub=p.nq_sub, n_sub=p.n_sub,
                          use_sel=sel_t is not None,
                          **({} if window is None else {"window": window})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(p.tables),
            grid=(B, Hkv, p.nkv, p.nq),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, 1, major, D), kv),
                       pl.BlockSpec((1, 1, major, Dv), kv)],
            scratch_shapes=[
                pltpu.VMEM((major, D), jnp.float32),
                pltpu.VMEM((major, Dv), jnp.float32),
            ] + bias,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, Lk, D), kt.dtype),
            jax.ShapeDtypeStruct((B, Hkv, Lk, Dv), vt.dtype),
        ],
        compiler_params=params,
        interpret=interpret_mode(),
    )(*operands)


def _bwd_impl(qt, kt, vt, qpos3, kvpos3, scale, blk_q, blk_kv, out_t,
              lse, dout_t, clamp: bool, sel_t=None, window=None):
    # delta = rowsum(dO * O) — cheap elementwise, plain XLA.
    delta = jnp.sum(dout_t.astype(jnp.float32) * out_t.astype(jnp.float32),
                    axis=-1)[:, :, None, :]                   # [B, H, 1, Lq]
    dq = _dq_call(qt, kt, vt, qpos3, kvpos3, dout_t, lse, delta, scale,
                  blk_q, blk_kv, clamp, sel_t, window)
    dk, dv = _dkv_call(qt, kt, vt, qpos3, kvpos3, dout_t, lse, delta,
                       scale, blk_q, blk_kv, clamp, sel_t, window)
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_attention_gqa(q, k, v, q_positions, scale,
                        blk_q: int = 512, blk_kv: int = 512, window=None):
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
    ``window`` (static): the query also needs ``p - j < window`` (a
    sliding-window layer: itself and the ``window - 1`` keys before it);
    tiles wholly behind it are neither fetched nor multiplied.
    Returns [B, Lq, H, Dv] in q.dtype.
    """
    out, _ = _fwd(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                  v.transpose(0, 2, 1, 3), q_positions[:, None, :],
                  None, scale, blk_q, blk_kv, clamp=True, window=window)
    return out.transpose(0, 2, 1, 3)


def _vjp_fwd(q, k, v, q_positions, scale, blk_q, blk_kv, window):
    # The residuals carry the names a block's checkpoint may keep
    # (models/transformer.py, REMAT_TAGS): kept, the backward kernels
    # read them as they lie and the forward is not run a second time.
    qt, kt, vt = (checkpoint_name(t.transpose(0, 2, 1, 3), "attn_qkv")
                  for t in (q, k, v))
    qpos3 = q_positions[:, None, :]
    out_t, lse = (checkpoint_name(t, "attn_out") for t in _fwd(
        qt, kt, vt, qpos3, None, scale, blk_q, blk_kv, clamp=True,
        window=window))
    return out_t.transpose(0, 2, 1, 3), (qt, kt, vt, qpos3, out_t, lse)


def _vjp_bwd(scale, blk_q, blk_kv, window, residuals, dout):
    qt, kt, vt, qpos3, out_t, lse = residuals
    dq, dk, dv = _bwd_impl(qt, kt, vt, qpos3, None, scale, blk_q,
                           blk_kv, out_t, lse, dout.transpose(0, 2, 1, 3),
                           clamp=True, window=window)
    return (dq.transpose(0, 2, 1, 3),
            dk.transpose(0, 2, 1, 3), dv.transpose(0, 2, 1, 3),
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
            dk.transpose(0, 2, 1, 3), dv.transpose(0, 2, 1, 3),
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
            dk.transpose(0, 2, 1, 3), dv.transpose(0, 2, 1, 3))
