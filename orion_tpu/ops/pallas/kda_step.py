"""The delta rule's one-token step (``ops/kda.py::kda_step``) as one
Pallas TPU kernel: a head's float32 state is read from the HBM once and
written once a decode step, in place.

As ``jax.numpy`` the step is two passes over the state (the prediction
``S'^T k`` is a reduction over the whole tile that the update needs
before it can write any of it, and an XLA fusion cannot hold a tile
between a reduction and its broadcast), with a copy around the decode
loop on top.  Here a grid step holds the tiles ``[hb, dk, dv]`` of
``hb`` heads of one row in VMEM, decays them, reduces ``pred``, adds the
outer product, reduces ``o`` from the UPDATED tile (the ``jax.numpy``
form's order of operations) and writes tile and ``o``; the state operand
is aliased to the state result, so a decode loop carries one buffer a
layer.

Every product with the state is an elementwise float32 product on the
VPU and every sum a float32 sum: nothing passes through the MXU, so the
state keeps its 24 bits.  The state keeps the cache's orientation
``[dk, dv]``: ``v``, ``pred``, ``u`` and ``o`` are rows (sums over
sublanes, broadcasts over sublanes: cheap), ``q``, ``k`` and a decay a
channel have to lie along the sublanes and are relaid in the kernel:
the block's ``[hb, dk]`` is transposed once for all its heads (XLU) and
a head's column broadcast over the lanes.  One decay a head and
``beta`` are scalars, read from SMEM.  The decay ``e^g`` is taken
outside (it fuses into ``g``'s producer, and is then the very
``jnp.exp`` of the ``jax.numpy`` form).

Head sizes that are no lane tiles (Olmo-Hybrid's 96 x 192) take
full-dimension blocks: nothing is padded in the HBM.  A block's heads
are 16 or 8 where that divides the heads, else all of them (the
``[hb, dk]`` blocks of ``q, k`` need whole sublane tiles or the whole
dimension).

On a v5e the schedule is 118 bundles a head of 128 x 128 and 81 a head
of 96 x 192, under the ~150 cycles the HBM needs for the head's two
crossings, and the kernel takes the time of one that only copies its
blocks: 0.205 ms a layer a step at 32 x 32 heads of 128 x 128 (650 GB/s,
79% of the HBM peak) against 0.35 as ``jax.numpy``, 0.288 at 32 x 30
heads of 96 x 192 (which lie as 96 x 256 in the HBM's tiles) against
0.43; results bit for bit the ``jax.numpy`` step's (PERF.md section 6,
PR 37).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import interpret_mode, named_pallas_call

F32 = jnp.float32


def _kernel(beta_ref, d1_ref, q_ref, k_ref, v_ref, d_ref, s_ref,
            o_ref, s_out_ref, *, hb, one_decay):
    """beta, d1 [B, H] in SMEM (d1: one decay a head, unused else); q,
    k [1, hb, dk]; v [1, hb, dv]; d [1, hb, dk] (a decay a channel; a
    dummy for one a head); s [1, hb, dk, dv] -> o [1, hb, dv], s."""
    b, h0 = pl.program_id(0), pl.program_id(1) * hb
    # [hb, dk] -> [dk, hb]: head j's vector is column j, along the
    # sublanes as the state's rows are
    qT = q_ref[0].astype(F32).T
    kT = k_ref[0].astype(F32).T
    dT = None if one_decay else d_ref[0].T
    v = v_ref[0].astype(F32)
    rows = []
    for j in range(hb):
        decay = d1_ref[b, h0 + j] if one_decay else dT[:, j:j + 1]
        k = kT[:, j:j + 1]
        S = decay * s_ref[0, j]
        pred = jnp.sum(S * k, axis=0, keepdims=True)            # [1, dv]
        u = beta_ref[b, h0 + j] * (v[j:j + 1, :] - pred)
        S = S + k * u
        s_out_ref[0, j] = S
        rows.append(jnp.sum(S * qT[:, j:j + 1], axis=0, keepdims=True))
    o_ref[0] = rows[0] if hb == 1 else jnp.concatenate(rows, axis=0)


def heads_per_step(H: int) -> int:
    """Heads a grid step: whole sublane tiles of the ``[hb, dk]``
    blocks, else every head.  8, 16 and 32 heads a step ran alike on a
    v5e (0.209 ms a layer a step at 32 x 32 heads of 128 x 128): the
    step is bound by its DMAs, not by their size."""
    return next((hb for hb in (16, 8) if H % hb == 0), H)


def _tile_bytes(rows: int, cols: int) -> int:
    """A float32 [rows, cols] as VMEM holds it: whole (8, 128) tiles."""
    return (-(-rows // 8) * 8) * (-(-cols // 128) * 128) * 4


def kda_step_kernel(q, k, v, g, beta, state):
    """``kda.kda_step`` through the kernel, same arguments and results:
    q, k [B, H, dk]; g [B, H, dk] or [B, H, 1]; v [B, H, dv]; beta
    [B, H]; state [B, H, dk, dv] float32 -> (o [B, H, dv] float32, the
    new state, in the buffer of the old one where the caller donates
    it)."""
    B, H, dk = q.shape
    dv = v.shape[-1]
    hb = heads_per_step(H)
    one_decay = g.shape[-1] == 1
    decay = jnp.exp(g.astype(F32))
    if one_decay:
        d1, d = decay[..., 0], jnp.zeros((B, H, 1), F32)
    else:
        d1, d = jnp.zeros((1, 1), F32), decay

    def vec(n):
        return pl.BlockSpec((1, hb, n), lambda b, h: (b, h, 0))

    tile = pl.BlockSpec((1, hb, dk, dv), lambda b, h: (b, h, 0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    # the state's block twice (in and out), each double-buffered, and
    # room for the vectors and the compiler's own
    vmem = 4 * hb * _tile_bytes(dk, dv) + 8 * 2**20
    return named_pallas_call(
        "kda_step",
        functools.partial(_kernel, hb=hb, one_decay=one_decay),
        grid=(B, H // hb),
        in_specs=[smem, smem, vec(dk), vec(dk), vec(dv), vec(d.shape[-1]),
                  tile],
        out_specs=[vec(dv), tile],
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), F32),
                   jax.ShapeDtypeStruct((B, H, dk, dv), F32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=vmem),
        interpret=interpret_mode(),
    )(beta.astype(F32), d1, q, k, v, d, state.astype(F32))
