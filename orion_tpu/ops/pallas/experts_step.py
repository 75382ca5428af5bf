"""The small-step expert layer (``ops/moe.py::TopKMoE`` where a step
carries few tokens: decode) as a kernel that reads only the held
experts SOME row selected.  Dense in rows, sparse in experts: every row
meets every hit expert and is weighted by its gate (0 where it did not
select it), as ``experts_dense`` has it, but the stacks of an expert no
row selected are never fetched: their term of the sum has a gate of 0
in every row.  No sort and no gather (PR 28's grouped form on decode
lost to its fixed costs: PERF.md section 6).

The hit experts' indices arrive in ascending order as scalar prefetch
(``read`` [H], the tail filled with the last hit; ``n_hit`` [1]).  The
grid is (held expert, tile of the experts' width): step ``(i, f)`` of a
hit expert holds tile ``f`` of ``w_up[read[i]]`` ([D, tile], the gate's
and the up product's columns as two blocks of the one array where the
activation has a gate) and of ``w_down[read[i]]`` ([tile, D]); a step
past ``n_hit`` names the block that is already resident in BOTH grid
axes (the last hit expert's last tile), so the pipeline issues no copy
for it, and does nothing.  ``x`` [T, D] and the gates [T, H] are
resident, the sum [T, D] is a float32 scratch written out once.  The
products are in the compute dtype with float32 accumulation and the
activation's result is rounded to the compute dtype before the second
product, as the einsum form's is; the gate multiplies in float32.

Why it is here: a held expert is selected by none of a step's ``T`` rows
with probability ``(1 - k / E) ** T`` and the einsum form reads its 9-22
MB all the same (``ppo-keye-dsa-ep8-sync``, 16 of 128 held, top 8, 8
rows: 0.91 GB of stacks a step, ~0.4 needed; PERF.md section 6, PR 54);
and up to 64 rows it moves what it reads faster than the einsum form,
every stack hit (``ppo-lfm2-ep4-sync``; PERF.md section 6, PR 57).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import interpret_mode, named_pallas_call

F32 = jnp.float32

#: the most columns of an expert's width a grid step holds (of the gate,
#: of the up product and of the down product's rows each)
TILE_COLUMNS = 512
#: the lanes of a vreg: a tile is whole lanes or the whole width
_LANES = 128


def width_tile(width: int) -> int:
    """The columns of an expert's width ``I`` a grid step holds: the
    largest divisor of ``width`` up to :data:`TILE_COLUMNS` that is
    whole lanes (768: 384; 896: 128; 1024: 512; 2688: 384); the whole
    width where there is none (interpreted only: ``ops/moe.py::
    step_form`` sends a width that is not whole lanes elsewhere)."""
    return max((t for t in range(_LANES, min(TILE_COLUMNS, width) + 1, _LANES)
                if width % t == 0), default=width)


def hits(local, n_held: int):
    """``local`` [T, k] (a row's selected experts among the ``n_held``
    held ones, anything outside 0..n_held-1 = not held) -> (read
    [n_held] int32: the held experts some row selected, ascending, the
    tail filled with the last of them, all 0 where there is none; n_hit
    [1] int32).  From the selection, not from the gates: a gate is a
    float."""
    held = jnp.arange(n_held, dtype=jnp.int32)
    hit = jnp.any(local.reshape(-1, 1) == held, axis=0)
    n_hit = jnp.sum(hit, dtype=jnp.int32)
    # a hit expert's rank among the hit ones, and the expert of each
    # rank: compares and sums over [H, H], which fuse (a sort or a
    # scatter of H numbers is a program of its own at every layer and
    # step)
    rank = jnp.sum((held[None, :] <= held[:, None]) & hit[None, :],
                   axis=1, dtype=jnp.int32) - 1
    want = jnp.minimum(held, jnp.maximum(n_hit - 1, 0))
    read = jnp.sum(jnp.where(hit[None, :] & (rank[None, :] == want[:, None]),
                             held[None, :], 0), axis=1, dtype=jnp.int32)
    return read, n_hit[None]


def tile_at(i, f, n_hit, n_tiles: int):
    """The tile of its expert's width that grid step ``(i, f)`` holds:
    ``f`` for a hit expert; past the last hit one (whose index the tail
    of ``read`` repeats) its LAST tile, the block the step before left
    resident, so that no copy is issued."""
    return jnp.where(i < n_hit, f, n_tiles - 1)


def _kernel(read_ref, n_ref, x_ref, gate_ref, *refs, act_fn, parts: int):
    """Grid step (i, f): tile ``f`` of held expert ``read[i]`` where ``i
    < n_hit``.  refs: ``parts`` blocks [1, D, tile] of w_up (gate | up
    where the activation has a gate), w_down [1, tile, D], o [T, D],
    acc [T, D] float32."""
    up_refs, (down_ref, o_ref, acc_ref) = refs[:parts], refs[parts:]
    i, f = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (f == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < n_ref[0])
    def _():
        x = x_ref[...]
        # (bfloat16 operands take no other precision on the MXU)
        dot = functools.partial(
            jnp.dot, preferred_element_type=F32,
            precision=jax.lax.Precision.HIGHEST if x.dtype == F32
            else jax.lax.Precision.DEFAULT)
        pre = jnp.concatenate([dot(x, up[0]) for up in up_refs], axis=-1)
        h = act_fn(pre).astype(x.dtype)
        y = dot(h, down_ref[0])
        # this expert's column of the gates [T, H], picked by a mask
        gates = gate_ref[...]
        column = jax.lax.broadcasted_iota(jnp.int32, gates.shape, 1)
        gate = jnp.sum(jnp.where(column == read_ref[i], gates, 0.0),
                       axis=1, keepdims=True)
        acc_ref[...] += gate * y

    @pl.when((i == pl.num_programs(0) - 1) & (f == pl.num_programs(1) - 1))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _forward(x, w_up, w_down, weight, read, n_hit, act: str):
    from orion_tpu.ops.moe import ACTIVATIONS

    T, D = x.shape
    H, I = w_down.shape[:2]
    act_fn, parts = ACTIVATIONS[act]
    assert w_up.shape == (H, D, parts * I), (w_up.shape, w_down.shape)
    tile = width_tile(I)
    n_tiles = I // tile

    def resident(i, f, read, n):
        return (0, 0)

    def up(part):
        return lambda i, f, read, n: (
            read[i], 0, part * n_tiles + tile_at(i, f, n[0], n_tiles))

    def down(i, f, read, n):
        return (read[i], tile_at(i, f, n[0], n_tiles), 0)

    itemsize = jnp.dtype(w_up.dtype).itemsize
    # both buffers of the three weight blocks, the rows and the sums
    vmem = 2 * (parts + 1) * D * tile * itemsize + 4 * T * D * 4 + (4 << 20)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(H, n_tiles),
        in_specs=[pl.BlockSpec((T, D), resident),
                  pl.BlockSpec((T, H), resident),
                  *(pl.BlockSpec((1, D, tile), up(p)) for p in range(parts)),
                  pl.BlockSpec((1, tile, D), down)],
        out_specs=pl.BlockSpec((T, D), resident),
        scratch_shapes=[pltpu.VMEM((T, D), F32)],
    )
    return named_pallas_call(
        "experts_step",
        functools.partial(_kernel, act_fn=act_fn, parts=parts),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(vmem, 16 << 20)),
        interpret=interpret_mode(),
    )(read, n_hit, x, weight, *[w_up] * parts, w_down)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def experts_step(x, w_up, w_down, local, gates, act: str = "swiglu"):
    """``ops/moe.py::experts_dense``'s result (x [T, D]; w_up [H, D,
    F]; w_down [H, I, D]; local, gates [T, k] -> [T, D] in x's dtype)
    without reading the stacks of a held expert that no row selected.
    Its gradients are ``experts_dense``'s own."""
    from orion_tpu.ops.moe import dense_weight

    H = w_up.shape[0]
    read, n_hit = hits(local, H)
    return _forward(x, w_up, w_down, dense_weight(local, gates, H), read,
                    n_hit, act)


def _fwd(x, w_up, w_down, local, gates, act):
    return (experts_step(x, w_up, w_down, local, gates, act),
            (x, w_up, w_down, local, gates))


def _bwd(act, res, g):
    from orion_tpu.ops.moe import experts_dense

    x, w_up, w_down, local, gates = res
    _, vjp = jax.vjp(
        lambda x, w_up, w_down, gates: experts_dense(
            x, w_up, w_down, local, gates, act), x, w_up, w_down, gates)
    d_x, d_up, d_down, d_gates = vjp(g)
    return d_x, d_up, d_down, None, d_gates


experts_step.defvjp(_fwd, _bwd)
