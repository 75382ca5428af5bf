"""The grouped-query one-token step (``models/transformer.py``
``Attention``, one new token a row against its dense slot cache) as a
flash-decode kernel: ``sparse_step.py``'s, with the positional rule
``slot <= position`` standing where the selection stands.  K and V stay
``[B, Lmax, Hkv, D]`` in the HBM, viewed ``[B, Lmax * Hkv, D]`` (a
bitcast in the compiled program), a row is ``slot * Hkv + head``; all
``H`` query heads meet a block's rows in one product and a constant
bias keeps a query head's own key head; the block index is clamped to
the ROW's filled length, finer than the batch's furthest position, so
the step does not go through ``prefix_step``'s ``lax.switch``; float32
scores and sums, the probabilities rounded to the cache's dtype for the
second product (``reference_attention_gqa``'s numbers).

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

#: the most slots a grid step holds
BLOCK_SLOTS = 256
#: the block's rows (slots x key heads) lie along the lanes of the scores
_LANES = 128


def block_slots(cache_len: int, kv_heads: int) -> int:
    """The slots a grid step holds of a cache of ``cache_len``: its
    largest divisor up to ``BLOCK_SLOTS`` whose rows fill whole lanes
    (1280 and 1024 slots: 256); 0 where there is none."""
    return max((tk for tk in range(8, min(BLOCK_SLOTS, cache_len) + 1, 8)
                if cache_len % tk == 0 and tk * kv_heads % _LANES == 0),
               default=0)


def step_form(queries: int, heads: int, kv_heads: int, cache_len: int,
              quantized: bool = False) -> str:
    """The form the one-token step takes against a dense slot cache,
    from what the step can see: ``kernel`` (:func:`dense_step`) where
    one query a row (``queries``) stands for a group of query heads on
    each of SEVERAL key heads, the cache is not int8, has whole blocks
    (:func:`block_slots`) and the trace is for one TPU device; ``""``
    elsewhere (the CPU, a mesh, an int8 cache, a block's rows, one
    query head a key head, ONE key head held): ``prefix_step``."""
    from orion_tpu.ops.indexer import select_form

    grouped = kv_heads > 1 and heads > kv_heads
    return "kernel" if (
        queries == 1 and grouped and not quantized
        and block_slots(cache_len, kv_heads)
        and select_form() == "kernel") else ""


def step_slots(lens, cache_len: int, kv_heads: int, steps: int) -> float:
    """Slots of k (and as many of v) one row's step reads a layer under
    the kernel after prompts of ``lens`` real tokens, the mean over the
    rows and their ``steps`` steps (step ``t`` stands at position ``len
    + t``): the blocks up to each row's filled slot."""
    tk = block_slots(cache_len, kv_heads)
    at = (np.asarray(lens, np.int64)[:, None]
          + np.arange(max(steps, 1))[None, :])
    return float(((np.minimum(at, cache_len - 1) // tk + 1) * tk).mean())


def _kernel(last_ref, pos_ref, q_ref, k_ref, v_ref, head_ref, slot_ref,
            o_ref, m_sc, l_sc, acc_sc, *, scale, tk):
    """:func:`flash_block` under head [H, rows] float32 bias (0 where
    the row is of the query head's key head, else NEG_INF) and the
    positional rule: slot [1, rows] int32 is a row's slot inside its
    block, pos [B] in SMEM the row's position."""
    reach = pos_ref[pl.program_id(0)] - pl.program_id(1) * tk

    def masked(s):
        return jnp.where(slot_ref[...] <= reach,
                         s * scale + head_ref[...], NEG_INF)

    flash_block(last_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc,
                masked)


def dense_step(q, k, v, positions, scale: float):
    """q [B, 1, H, D]; k, v [B, Lmax, Hkv, D]; positions [B] -> [B, 1,
    H, D] in q's dtype: softmax(q . k * scale) over the slots up to each
    row's position, float32 scores and sums."""
    B, _, H, D = q.shape
    Lmax, Hkv = k.shape[1], k.shape[2]
    tk = block_slots(Lmax, Hkv)
    assert tk, (Lmax, Hkv)                      # step_form's rule
    nblk, rows = Lmax // tk, tk * Hkv
    # constants of the program, not of the step: numpy
    own = np.arange(H)[:, None] // (H // Hkv) == np.arange(rows) % Hkv
    head = np.where(own, 0.0, NEG_INF).astype(np.float32)        # [H, rows]
    slot = (np.arange(rows, dtype=np.int32) // Hkv)[None, :]
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
            pl.BlockSpec((1, rows, D), blk),
            pl.BlockSpec((1, rows, D), blk),
            pl.BlockSpec((H, rows), whole),
            pl.BlockSpec((1, rows), whole),
        ],
        out_specs=pl.BlockSpec((1, H, D), row),
        scratch_shapes=[pltpu.VMEM((H, 1), F32), pltpu.VMEM((H, 1), F32),
                        pltpu.VMEM((H, D), F32)],
    )
    out = named_pallas_call(
        "dense_step",
        functools.partial(_kernel, scale=scale, tk=tk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(),
    )(last, positions, q[:, 0], k.reshape(B, Lmax * Hkv, D),
      v.reshape(B, Lmax * Hkv, D), head, slot)
    return out[:, None]
