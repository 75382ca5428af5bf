"""The grouped-query one-token step (``models/transformer.py``
``Attention``, one new token a row against its dense slot cache) as a
flash-decode kernel: ``sparse_step.py``'s, with the positional rule
``slot <= position`` standing where the selection stands.  Under this
step K and V lie ``[B, Lmax, Hkv * D]`` in the HBM (``Attention.
cache_entry`` asks :func:`step_form`; the step follows the cache's
rank): the key heads
of a slot side by side along the lanes, so that heads of 64 pad no lane
(as ``[B, Lmax, Hkv, 64]`` every row of 64 lay in 128 lanes and the
kernel streamed twice the cache's bytes: PERF.md section 6, PR 52).  A
grid step holds a block of ``[tk, Hkv * D]``.  The query is spread
to ``[H, Hkv * D]``, a head's ``D`` numbers in its own key head's lanes
and 0 elsewhere, so ONE product with the block gives the scores ``[H,
tk]`` of every head against its own key head (zeros add exact 0 in
float32) and the second product gives ``[H, Hkv * D]``, of which a
head's own ``D`` lanes are picked; spread and pick are 0/1 products
inside the kernel, at a row's first and last grid step (outside they
were three operations more a layer and step); the block index is
clamped to the ROW's filled length, finer than the batch's furthest
position, so the step does not go through ``prefix_step``'s
``lax.switch``; float32 scores and sums, the probabilities rounded to
the cache's dtype for the second product (``reference_attention_gqa``'s
numbers).

Why it is here: with a group of query heads over SEVERAL key heads the
step's einsum is a batch of matrix products over the key heads, and the
TPU compiler first re-lays the cache's filled prefix out for it, slots
minor-most: at LFM2's shapes (64 rows, 1280 slots, 32 query heads on 8
key heads of 64) ``copy bf16[64, <prefix>, 8, 64]`` of K and of V on
both attention layers at every one of 1023 steps, 1040 ms of a 3902 ms
rollout (PERF.md section 6, PR 50).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import NEG_INF, interpret_mode, named_pallas_call
from orion_tpu.ops.pallas.sparse_step import F32, flash_block

#: the most slots a grid step holds (in ``ppo-lfm2-ep4-sync``, 1280 slots
#: filled from 128-256 to the end: blocks of 256 / 320 / 640 took 418.1 /
#: 405.1 / 398.3 ms an iteration: fewer grid steps outweigh the unfilled
#: slots a coarser block reads; PERF.md section 6, PR 52)
BLOCK_SLOTS = 640
#: a block's slots lie down the sublanes, 16 of bfloat16 to a tile
_SUBLANES = 16


def block_slots(cache_len: int) -> int:
    """The slots a grid step holds of a cache of ``cache_len``: its
    largest divisor up to ``BLOCK_SLOTS`` that is whole sublane tiles
    (1280 slots: 640; 1024: 512); 0 where there is none."""
    return max((tk for tk in range(_SUBLANES, min(BLOCK_SLOTS, cache_len) + 1,
                                   _SUBLANES) if cache_len % tk == 0),
               default=0)


def step_form(queries: int, heads: int, kv_heads: int, cache_len: int,
              quantized: bool = False) -> str:
    """The form the one-token step takes against a dense slot cache,
    from what the step can see: ``kernel`` (:func:`dense_step`, over a
    cache laid ``[B, slots, Hkv * D]``) where one query a row
    (``queries``) stands for a group of query heads on each of SEVERAL
    key heads, the cache is not int8, has whole blocks
    (:func:`block_slots`) and the trace is for one TPU device; ``""``
    elsewhere (the CPU, a mesh, an int8 cache, a block's rows, one
    query head a key head, ONE key head held): ``prefix_step`` over
    ``[B, slots, Hkv, D]``."""
    from orion_tpu.ops.indexer import select_form

    grouped = kv_heads > 1 and heads > kv_heads
    return "kernel" if (
        queries == 1 and grouped and not quantized
        and block_slots(cache_len) and select_form() == "kernel") else ""


def step_slots(lens, cache_len: int, steps: int) -> float:
    """Slots of k (and as many of v) one row's step reads a layer under
    the kernel after prompts of ``lens`` real tokens, the mean over the
    rows and their ``steps`` steps (step ``t`` stands at position ``len
    + t``): the blocks up to each row's filled slot."""
    tk = block_slots(cache_len)
    at = (np.asarray(lens, np.int64)[:, None]
          + np.arange(max(steps, 1))[None, :])
    return float(((np.minimum(at, cache_len - 1) // tk + 1) * tk).mean())


def _kernel(last_ref, pos_ref, q_ref, k_ref, v_ref, lanes_ref, heads_ref,
            own_ref, o_ref, q_sc, o_sc, m_sc, l_sc, acc_sc, *, scale, tk):
    """:func:`flash_block` over rows of ``Hkv * D`` lanes under the
    positional rule: pos [B] in SMEM is the row's position, a column of
    the scores a slot of the block.  The query [H, D] is spread at the
    row's first step to its own key head's lanes of q_sc [1, H, Hkv *
    D] and the result's own lanes are picked at its last from o_sc, by
    products with the 0/1 matrices lanes [D, Hkv * D] and heads [Hkv *
    D, D] under own [H, Hkv * D] float32 (1 on a head's own lanes): one
    term a sum is not 0, so both are exact."""
    j = pl.program_id(1)
    reach = pos_ref[pl.program_id(0)] - j * tk
    exact = (jax.lax.Precision.HIGHEST if q_ref.dtype == F32
             else jax.lax.Precision.DEFAULT)

    @pl.when(j == 0)
    def _():
        q_sc[0] = (jax.lax.dot_general(
            q_ref[0], lanes_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=F32, precision=exact)
            * own_ref[...]).astype(q_sc.dtype)

    def masked(s):
        slot = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return jnp.where(slot <= reach, s * scale, NEG_INF)

    flash_block(last_ref, q_sc, k_ref, v_ref, o_sc, m_sc, l_sc, acc_sc,
                masked)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = jax.lax.dot_general(
            o_sc[0] * own_ref[...].astype(o_sc.dtype), heads_ref[...],
            (((1,), (0,)), ((), ())), preferred_element_type=F32,
            precision=exact).astype(o_ref.dtype)


def dense_step(q, k, v, positions, scale: float):
    """q [B, 1, H, D]; k, v [B, Lmax, Hkv * D]; positions [B] -> [B, 1,
    H, D] in q's dtype: softmax(q . k * scale) over the slots up to each
    row's position, float32 scores and sums."""
    B, _, H, D = q.shape
    Lmax, W = k.shape[1:]
    Hkv = W // D
    tk = block_slots(Lmax)
    assert tk and W == Hkv * D, (k.shape, D)    # step_form's rule
    nblk = Lmax // tk
    # constants of the program, not of the step: numpy
    lanes = np.tile(np.eye(D, dtype=np.float32), (1, Hkv))          # [D, W]
    own = (np.arange(H)[:, None] // (H // Hkv)
           == np.arange(W)[None, :] // D).astype(np.float32)        # [H, W]
    positions = positions.astype(jnp.int32)
    last = jnp.minimum(positions // tk, nblk - 1)

    def blk(b, j, last, pos):
        # past a row's last filled block: the NEXT row's first block, so
        # that its copy runs beside this row's last product and not
        # alone in front of its own (a block's copy a row otherwise)
        idle = j > last[b]
        return (jnp.where(idle, jnp.minimum(b + 1, B - 1), b),
                jnp.where(idle, 0, j), 0)

    def whole(b, j, last, pos):
        return (0, 0)

    def row(b, j, last, pos):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nblk),
        in_specs=[
            pl.BlockSpec((1, H, D), row),
            pl.BlockSpec((1, tk, W), blk),
            pl.BlockSpec((1, tk, W), blk),
            pl.BlockSpec((D, W), whole),
            pl.BlockSpec((W, D), whole),
            pl.BlockSpec((H, W), whole),
        ],
        out_specs=pl.BlockSpec((1, H, D), row),
        scratch_shapes=[pltpu.VMEM((1, H, W), q.dtype),
                        pltpu.VMEM((1, H, W), q.dtype),
                        pltpu.VMEM((H, 1), F32), pltpu.VMEM((H, 1), F32),
                        pltpu.VMEM((H, W), F32)],
    )
    out = named_pallas_call(
        "dense_step",
        functools.partial(_kernel, scale=scale, tk=tk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(last, positions, q[:, 0], k, v, jnp.asarray(lanes, q.dtype),
      jnp.asarray(lanes.T, q.dtype), own)
    return out[:, None]
