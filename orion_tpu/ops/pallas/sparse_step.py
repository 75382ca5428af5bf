"""The selected attention's one-token step (``models/transformer.py``
``SparseAttention``, one new token against the cache) as a flash-decode
kernel: one query a sequence attends over k and v where they lie, under
a mask of the kept slots (``ops/indexer.py::select_step``).  No row of
the cache is copied.

K and V stay ``[B, Lmax, Hkv, D]`` in the HBM and are viewed as ``[B,
Lmax * Hkv, D]``: the same bytes under the TPU's tiling of a bf16 array
whose second-minor dimension is 4 (a bitcast in the compiled program;
``[B, Lmax, Hkv * D]`` would be a copy), a row is ``slot * Hkv + head``.
A grid step (sequence, key block) holds ``BLOCK_SLOTS`` slots = ``tk *
Hkv`` rows.  ALL ``H`` query heads meet all rows of the block in one
product ``q [H, D] @ k_blk^T`` -> ``[H, tk * Hkv]`` (heads down the
sublanes, rows along the lanes: whole vregs), and a constant bias keeps
only the columns of a query head's own key head: ``1 - 1 / Hkv`` of the
MXU's work is thrown away, which is cheaper than relaying the block by
head (two heads share a packed sublane).  The kept slots arrive as a
float32 bias, every slot ``Hkv`` times to lie over its rows; that repeat
is a product with a 0/1 matrix outside (exact; as ``jnp.repeat`` XLA
takes 25 us for it, as a product 1.3).  Running max, sum and accumulator
live in VMEM; the block index is clamped to the row's filled length
(``paged_attention.py::page_map``'s clamp), so the unfilled tail of the
cache costs no bandwidth.  float32 scores and sums, the probabilities
rounded to the cache's dtype for the second product, as
``reference_attention_gqa`` has them.

On a v5e at Keye-VL-2.0's shapes (8 sequences, 8192 slots filled to
6144-8192, 32 query heads on 4 key heads of 128, 2048 kept): 169.6 us a
layer and step for ~117 MB of k and v = 690 GB/s, 84% of the HBM's peak
(blocks of 256 / 1024 / 2048 slots: 199.7 / 173.6 / 179.7), against 392
for the gather of the kept rows and the attention over the copies and
213-278 for XLA's masked einsum over the whole cache; the error against
float32 attention at the highest precision is the einsum's (mean 6.4e-5
against 6.6e-5 where the outputs' mean is 0.029).  End to end in
``ppo-keye-dsa-ep8-sync`` the kernel gives 1.9% more samples a second
than that einsum (1.1091-1.1111 against 1.0882-1.0901), which is why it
is here.  PERF.md section 6, PR 41.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import NEG_INF, interpret_mode, named_pallas_call

F32 = jnp.float32
_DEFAULT = jax.lax.Precision.DEFAULT

#: slots a grid step (a multiple of 8)
BLOCK_SLOTS = 512


def flash_block(last_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc,
                masked):
    """One grid step (sequence b, key block j) of a one-token flash
    decode, shared by this kernel and ``dense_step.py``'s: last [B] in
    SMEM (the row's last filled block; later steps do nothing), q [1, H,
    D], k, v [1, rows, D] -> o [1, H, D] at the last step.  ``masked``
    takes the products ``q . k`` [H, rows] float32 to the scaled scores
    under the caller's bias (NEG_INF where a query head may not see a
    row)."""
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(j <= last_ref[b])
    def _():
        s = masked(jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=F32, precision=_DEFAULT))  # [H, rows]
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a row with nothing kept yet sums its masked columns at weight
        # 1; the first kept key's alpha = exp(NEG_INF - m) = 0 wipes that
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=F32, precision=_DEFAULT)
        m_sc[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)


def _kernel(last_ref, q_ref, k_ref, v_ref, keep_ref, head_ref, o_ref,
            m_sc, l_sc, acc_sc, *, scale):
    """:func:`flash_block` under keep [1, nseg, W] float32 bias (0 kept,
    NEG_INF not; segment i is columns i W .. of the block's rows) and
    head [H, W] float32 bias (0 where the row is of the query head's key
    head)."""

    def masked(s):
        nseg, W = keep_ref.shape[1:]
        head = head_ref[...]
        return jnp.concatenate(
            [s[:, i * W:(i + 1) * W] * scale + head + keep_ref[0, i:i + 1]
             for i in range(nseg)], axis=1)

    flash_block(last_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc,
                masked)


def step_form(cache_len: int) -> str:
    """The form the selected one-token step takes against a cache of
    ``cache_len`` slots, from that size and the trace's target:
    ``kernel`` (:func:`sparse_step`) where the trace is for one TPU
    device and the cache is whole blocks of ``BLOCK_SLOTS``; ``masked``
    elsewhere (the CPU, a mesh, any other length): XLA's einsum over the
    whole cache under the same mask."""
    from orion_tpu.ops.indexer import select_form

    whole = cache_len % BLOCK_SLOTS == 0
    return "kernel" if whole and select_form() == "kernel" else "masked"


def step_slots(form: str, lens, cache_len: int, steps: int) -> float:
    """Slots of k (and as many of v) one step reads a layer under
    ``form``, summed over sequences whose prompts have ``lens`` real
    tokens, the mean over their ``steps`` steps: the kernel's blocks up
    to each row's filled slot, the whole cache under the einsum."""
    if form != "kernel":
        return float(len(lens) * cache_len)
    filled = (np.asarray(lens, np.int64)[:, None]
              + np.arange(steps)[None, :])
    return float(((filled // BLOCK_SLOTS + 1) * BLOCK_SLOTS)
                 .mean(axis=1).sum())


def sparse_step(q, k, v, keep, positions, scale: float):
    """q [B, 1, H, D]; k, v [B, Lmax, Hkv, D]; keep [B, Lmax] bool (no
    slot past ``positions``, at least one a row); positions [B] -> [B,
    1, H, D] in q's dtype: softmax(q . k * scale) over the kept slots,
    float32 scores and sums."""
    B, _, H, D = q.shape
    Lmax, Hkv = k.shape[1], k.shape[2]
    tk = BLOCK_SLOTS
    assert Lmax % tk == 0, (Lmax, tk)           # step_form's rule
    nblk, rows = Lmax // tk, tk * Hkv
    # the kept slots as a bias a ROW of the blocks (every slot Hkv
    # times), a block's as nseg sublanes of W lanes
    nseg = 8
    seg, W = tk // nseg, rows // nseg
    # the repeat as a product with a 0/1 matrix: exact, and on the MXU
    spread = (jnp.arange(W)[None, :] // Hkv
              == jnp.arange(seg)[:, None]).astype(jnp.bfloat16)
    bias = jnp.dot(
        jnp.where(keep, 0.0, NEG_INF).astype(jnp.bfloat16).reshape(-1, seg),
        spread, preferred_element_type=F32)
    bias = bias.reshape(B * nblk, nseg, W)
    own = (jnp.arange(H)[:, None] // (H // Hkv)
           == jnp.arange(W)[None, :] % Hkv)
    head = jnp.where(own, 0.0, NEG_INF).astype(F32)                  # [H, W]
    last = (positions // tk).astype(jnp.int32)

    def blk(b, j, last):
        return (b, jnp.minimum(j, last[b]), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nblk),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, j, last: (b, 0, 0)),
            pl.BlockSpec((1, rows, D), blk),
            pl.BlockSpec((1, rows, D), blk),
            pl.BlockSpec(
                (1, nseg, W),
                lambda b, j, last: (b * nblk + jnp.minimum(j, last[b]), 0, 0)),
            pl.BlockSpec((H, W), lambda b, j, last: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, j, last: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((H, 1), F32), pltpu.VMEM((H, 1), F32),
                        pltpu.VMEM((H, D), F32)],
    )
    out = named_pallas_call(
        "sparse_step",
        functools.partial(_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(last, q[:, 0], k.reshape(B, Lmax * Hkv, D),
      v.reshape(B, Lmax * Hkv, D), bias, head)
    return out[:, None]
