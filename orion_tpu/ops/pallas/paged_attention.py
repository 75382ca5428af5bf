"""Pallas paged-KV decode attention (SURVEY.md §2 #5, #13).

TPU-native equivalent of vLLM's CUDA paged-attention decode kernel: one
query token per sequence attends to that sequence's KV scattered across
fixed-size pages of a global pool, addressed through a block table.

Design: the grid is (batch, q-head, page-slot) and the page lookup
happens in the *BlockSpec index map* from a scalar-prefetched block
table (``PrefetchScalarGridSpec``) — Pallas's pipeline machinery then
double-buffers the page DMAs automatically, which is the Mosaic-idiomatic
version of the hand-rolled MultiPageAsyncCopyDescriptor pattern.
Online softmax accumulates across page-slots in VMEM scratch (the grid's
innermost dimension is sequential on TPU, so scratch persists).  The
page index map clamps to the last in-use page, so the masked tail of the
block table costs no HBM bandwidth however it is padded.

ONE kernel serves the bf16 and int8 pools: with ``quantized=True`` the
K/V pages arrive int8 with per-(slot, head) f32 scale operands ([1, ps]
blocks — see ops.paged_kv.init_paged_cache).  The K scale lands on the
scores and the V scale folds into the probs — both [1, ps] — so the big
page operands enter the dots as bare int8→f32 converts that fuse into
the reads (same recipe as the dense int8 cache in ops/attention.py),
and HBM moves 1 byte per cache element.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import NEG_INF as _NEG_INF
from orion_tpu.ops.pallas import (interpret_mode as _interpret,
                                  named_pallas_call)


def _decode_kernel(bt_ref, len_ref, q_ref, *refs, scale: float,
                   page_size: int, quantized: bool):
    if quantized:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, m_sc, l_sc, acc_sc = refs
    else:
        k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    j = pl.program_id(2)
    last = pl.num_programs(2) - 1
    seq_len = len_ref[b]

    @pl.when(j == 0)
    def _():
        m_sc[:] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    @pl.when(j * page_size < seq_len)
    def _():
        q = q_ref[0, 0, :, :].astype(jnp.float32) * scale        # [1, D]
        k = k_ref[0, 0, :, :].astype(jnp.float32)                # [ps, D]
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                  # [1, ps]
        if ks_ref is not None:
            s = s * ks_ref[0, 0, :, :]                           # [1, ps]
        idx = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        s = jnp.where(idx < seq_len, s, _NEG_INF)
        # All (1, 1)-shaped vector ops: Mosaic VMEM cannot store scalars.
        m_prev, l_prev = m_sc[:, :], l_sc[:, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                                   # [1, ps]
        alpha = jnp.exp(m_prev - m_new)
        m_sc[:, :] = m_new
        l_sc[:, :] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if vs_ref is not None:
            p = p * vs_ref[0, 0, :, :]
        acc_sc[:, :] = acc_sc[:, :] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)            # [1, D]

    @pl.when(j == last)
    def _():
        o_ref[0, 0, :, :] = (acc_sc[:, :] /
                             jnp.maximum(l_sc[:, :], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_reference(q, k_pages, v_pages, block_tables,
                                     seq_lens, scale: float,
                                     k_scales=None, v_scales=None):
    """Pure-XLA twin of the decode kernel (gather + masked softmax).

    Same math as :func:`paged_decode_attention` — f32 accumulation,
    GQA head h reads kv-head h // n_rep, int8 K scales land on the
    scores and V scales on the probs with the normalizer taken BEFORE
    the V scale (matching the kernel's online-softmax order).  This is
    the execution path on interpret-mode platforms: the emulated Pallas
    kernel is ~7x slower than XLA on CPU, which made the CPU serving
    harness decode-bound on emulation overhead rather than on anything
    the benchmark was measuring.
    """
    B, H, D = q.shape
    _, Hkv, ps, _ = k_pages.shape
    mp = block_tables.shape[1]
    n_rep = H // Hkv

    def gather(pages):                      # [N, Hkv, ps, D] -> slot order
        g = jnp.take(pages, block_tables, axis=0)   # [B, mp, Hkv, ps, D]
        return (g.transpose(0, 2, 1, 3, 4)
                .reshape(B, Hkv, mp * ps, D).astype(jnp.float32))

    def gather_s(scales):                   # [N, Hkv, 1, ps] -> [B,Hkv,S]
        g = jnp.take(scales[:, :, 0, :], block_tables, axis=0)
        return g.transpose(0, 2, 1, 3).reshape(B, Hkv, mp * ps)

    k = gather(k_pages)
    v = gather(v_pages)
    qh = q.reshape(B, Hkv, n_rep, D).astype(jnp.float32) * scale
    s = jnp.einsum("bkrd,bksd->bkrs", qh, k)
    if k_scales is not None:
        s = s * gather_s(k_scales)[:, :, None, :]
    idx = jnp.arange(mp * ps, dtype=seq_lens.dtype)
    s = jnp.where(idx[None, None, None, :] < seq_lens[:, None, None, None],
                  s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    if v_scales is not None:
        p = p * gather_s(v_scales)[:, :, None, :]
    out = jnp.einsum("bkrs,bksd->bkrd", p, v) / denom
    return out.reshape(B, H, D).astype(q.dtype)


def paged_decode_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                           seq_lens: jnp.ndarray, scale: float,
                           k_scales=None, v_scales=None,
                           force_kernel: bool = False) -> jnp.ndarray:
    """One decode step of attention over a paged KV pool.

    q: [B, H, D] (current token per sequence);
    k_pages/v_pages: [num_pages, Hkv, page_size, D] global pool (heads
      before slots so page blocks tile as (slots, head_dim) on the MXU),
      bf16/f32 — or int8 when ``k_scales``/``v_scales`` (f32
      [num_pages, Hkv, 1, page_size]) are given;
    block_tables: [B, max_pages] int32, entry j = pool page holding
      tokens [j*page_size, (j+1)*page_size) of that sequence;
    seq_lens: [B] int32 — number of valid tokens (inclusive of the
      current one).  Returns [B, H, D] in q.dtype.

    Off-TPU this dispatches to the pure-XLA reference twin instead of
    the emulated kernel (same math, ~7x faster on CPU — the difference
    between the CPU serving harness measuring the engine and measuring
    Pallas emulation).  ``force_kernel=True`` pins the (interpreted)
    kernel — the kernel-logic tests use it.
    """
    if _interpret() and not force_kernel:
        return paged_decode_attention_reference(
            q, k_pages, v_pages, block_tables, seq_lens, scale,
            k_scales=k_scales, v_scales=v_scales)
    B, H, D = q.shape
    _, Hkv, page_size, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    n_rep = H // Hkv
    quantized = k_scales is not None
    q4 = q[:, :, None, :]                                     # [B, H, 1, D]

    def page_map(b, h, j, bt, ln, r=n_rep, ps=page_size):
        # Clamp to the last in-use page: steps beyond seq_len re-fetch
        # the same page, which Pallas elides — the masked tail costs no
        # HBM bandwidth regardless of how the table is padded.
        last = jnp.maximum(ln[b] - 1, 0) // ps
        return (bt[b, jnp.minimum(j, last)], h // r, 0, 0)

    page_spec = pl.BlockSpec((1, 1, page_size, D), page_map)
    scale_spec = pl.BlockSpec((1, 1, 1, page_size), page_map)
    in_specs = [
        pl.BlockSpec((1, 1, 1, D), lambda b, h, j, bt, ln: (b, h, 0, 0)),
        page_spec,
    ]
    operands = [q4, k_pages]
    if quantized:
        in_specs.append(scale_spec)
        operands.append(k_scales)
    in_specs.append(page_spec)
    operands.append(v_pages)
    if quantized:
        in_specs.append(scale_spec)
        operands.append(v_scales)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, 1, D),
                               lambda b, h, j, bt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),   # running max
            pltpu.VMEM((1, 1), jnp.float32),   # running sumexp
            pltpu.VMEM((1, D), jnp.float32),   # running accumulator
        ],
    )
    out = named_pallas_call(
        "paged_decode",
        functools.partial(_decode_kernel, scale=scale,
                          page_size=page_size, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        interpret=_interpret(),
    )(block_tables, seq_lens, *operands)
    return out[:, :, 0, :]


def paged_decode_attention_int8(q, k_pages, v_pages, k_scales, v_scales,
                                block_tables, seq_lens, scale: float,
                                force_kernel: bool = False):
    """int8-pool entry point (scales REQUIRED); thin delegation to
    :func:`paged_decode_attention`."""
    return paged_decode_attention(q, k_pages, v_pages, block_tables,
                                  seq_lens, scale, k_scales=k_scales,
                                  v_scales=v_scales,
                                  force_kernel=force_kernel)


def paged_decode_attention_sharded(q, k_pages, v_pages, block_tables,
                                   seq_lens, scale: float,
                                   k_scales=None, v_scales=None):
    """Tensor-parallel paged decode (VERDICT r3 missing #2).

    When the ambient mesh has a tensor axis that divides both head
    counts, the kernel runs inside a nested ``shard_map`` over that
    axis: each device holds its kv-head slice of the page pools (and
    scale pools, for int8) and its (contiguous, kv-head-major) q-head
    slice, block tables and lengths replicate, and NO pool gather ever
    happens — the pallas_call is opaque to GSPMD, which would otherwise
    all-gather the entire KV pool every decode step.  The local
    ``h // n_rep`` GQA mapping stays correct because both H and Hkv are
    sliced proportionally.  Falls back to the plain kernel outside a
    mesh (single-chip engines) or when the axis doesn't divide the
    heads.
    """
    from orion_tpu.parallel.sharding import ambient_mesh

    B, H, D = q.shape
    Hkv = k_pages.shape[1]
    quantized = k_scales is not None
    mesh = ambient_mesh()
    tp = 0 if mesh is None or mesh.empty else \
        dict(mesh.shape).get("tensor", 1)
    if tp <= 1 or H % tp or Hkv % tp:
        return paged_decode_attention(q, k_pages, v_pages, block_tables,
                                      seq_lens, scale, k_scales=k_scales,
                                      v_scales=v_scales)
    from jax.sharding import PartitionSpec as P

    from orion_tpu.utils.platform import shard_map

    pool_spec = P(None, "tensor", None, None)
    args = [q, k_pages, v_pages]
    specs = [P(None, "tensor", None), pool_spec, pool_spec]
    if quantized:
        args += [k_scales, v_scales]
        specs += [pool_spec, pool_spec]
    args += [block_tables, seq_lens]
    specs += [P(), P()]

    def body(q_, kp, vp, *rest):
        if quantized:
            ks, vs, bt, ln = rest
        else:
            (bt, ln), ks, vs = rest, None, None
        return paged_decode_attention(q_, kp, vp, bt, ln, scale,
                                      k_scales=ks, v_scales=vs)

    # Manual over EVERY mesh axis, not just 'tensor': jax refuses to
    # lower a Mosaic kernel under a partially-manual shard_map ("Mosaic
    # kernels cannot be automatically partitioned"), and the repo's
    # meshes always carry all six axis names.  The specs name only
    # 'tensor', so the operands replicate over the other axes (q, block
    # tables and lengths are tiny; the pools are sharded over 'tensor'
    # alone to begin with).
    mapped = shard_map(
        body, mesh=mesh, in_specs=tuple(specs),
        out_specs=P(None, "tensor", None), check_vma=False)
    return mapped(*args)
