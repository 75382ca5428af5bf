"""The dropless expert layer's combine (ops/moe.py): the rows of one
block of the sorted (token, choice) pairs added into their tokens' rows
of a float32 sum.

``out[tok[r]] += y[r] * gate`` for the block's held rows ``r``.  XLA's
scatter-add leaves the rows where they are and fetches the sum's row for
each (97 ns a row on a v5e, PERF.md section 6, PR 48).  Here a tile of
``tm`` consecutive tokens of the SUM stays in VMEM and the rows come to
it.  The contract that makes that cheap, and that the callers keep:

* the pairs are sorted by expert with a STABLE sort, so inside one held
  expert's run the tokens ascend, and a token selects an expert at most
  once, so they are distinct: the rows of a (token tile, held expert)
  are ONE contiguous range of the sorted order (:func:`ranges`);
* placing a 128-row chunk of ``y`` is then a product with a 0/1 matrix
  ``P[tm, 128]`` (token id == the tile's row, inside the range): weights
  0 and 1, at most one non-zero term a row, float32 accumulation — the
  product COPIES ``y``'s rows to their tokens, exactly; the gate
  multiplies the copy in float32 and the sum is float32, as the
  scatter-add's were.

The work follows the routing through a list of work items (token tile,
chunk, expert, the range) of STATIC length, scalar-prefetched and read
by the index maps, the items past the real ones masked (megablox's own
pattern: never a trip count that follows the routing).  Off-TPU the
kernel runs interpreted.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import interpret_mode, named_pallas_call

#: Rows of ``y`` a work item places: the MXU's contraction.
CHUNK_ROWS = 128
#: Bytes of the sum's tile that stays in VMEM (float32 [tm, D]); the
#: pipeline holds it four times (the carry in and the sum out, each
#: double-buffered) beside the product's [tm, D] float32.
TILE_BYTES = 4 << 20
_VMEM_LIMIT = 64 << 20


def token_tile(n_tokens: int, width: int) -> int:
    """Tokens of the sum a tile holds, from the shapes: ``TILE_BYTES``
    of float32 rows (512 at a width of 2048, 1024 at 1024), between 128
    and 1024, and no more than the tokens there are (in whole
    sublanes)."""
    tm = max(128, min(1024, TILE_BYTES // (4 * width) // 128 * 128))
    return min(tm, -(-n_tokens // 8) * 8)


def chunk_rows(block: int) -> int:
    """Rows of a chunk for blocks of ``block`` rows: 128 wherever a
    block is whole row tiles of the grouped product."""
    return math.gcd(block, CHUNK_ROWS)


def ranges(local, n_held: int, tm: int):
    """Where each (token tile, held expert) lies in the sorted order.
    local [T, k] (the expert among the held ones, anything else: not
    held) -> (starts, ends) [T / tm, n_held] int32: positions of the
    pairs sorted by expert, held experts first (ops/moe.py::
    experts_grouped).  Dense compares and cumulative sums; no scatter."""
    T = local.shape[0]
    n_tiles = -(-T // tm)
    hits = jnp.sum(local[:, :, None] == jnp.arange(n_held, dtype=local.dtype),
                   axis=1, dtype=jnp.int32)                     # [T, H]
    counts = jnp.sum(jnp.pad(hits, ((0, n_tiles * tm - T), (0, 0)))
                     .reshape(n_tiles, tm, n_held), axis=1)     # [tiles, H]
    sizes = jnp.sum(counts, axis=0)
    starts = (jnp.cumsum(sizes) - sizes)[None, :] \
        + jnp.cumsum(counts, axis=0) - counts
    return starts, starts + counts


def n_items(n_tiles: int, n_held: int, block: int) -> int:
    """The static length of a block's work items: a range is one item
    and one more for every chunk boundary inside it; a tile without a
    range has an empty one to spend on the item that writes it."""
    return n_tiles * n_held + block // chunk_rows(block)


def range_chunks(starts, ends, b, block: int):
    """Block ``b``'s part of every range, in the block's own rows, and
    the chunks each touches: (lo, hi, chunks) [tiles, H]."""
    rows = chunk_rows(block)
    lo = jnp.clip(starts - b * block, 0, block)
    hi = jnp.clip(ends - b * block, 0, block)
    return lo, hi, jnp.where(hi > lo, (hi - 1) // rows - lo // rows + 1, 0)


def work_items(starts, ends, b, block: int):
    """Block ``b``'s work items, ordered by tile: ((tile, expert, chunk,
    lo, hi) each [:func:`n_items`] int32, the number of real ones).  An
    item places rows ``lo <= r < hi`` (of the block) of chunk ``chunk``
    for held expert ``expert`` into token tile ``tile``.  Every tile has
    at least one item, an empty one (``lo == hi``) where no held pair of
    the block is the tile's; the items past the real ones are empty, of
    the last tile and of the last real item's chunk, so that they move
    nothing."""
    n_tiles, n_held = starts.shape
    length = n_items(n_tiles, n_held, block)
    lo, hi, chunks = range_chunks(starts, ends, b, block)
    # a tile that nothing touches: one empty item, on its first expert
    first = jnp.arange(n_held) == 0
    chunks = jnp.where(first & (jnp.sum(chunks, axis=1, keepdims=True) == 0),
                       1, chunks)
    table = jnp.stack([t.reshape(-1) for t in (lo, hi, chunks)], axis=1)
    last = jnp.cumsum(table[:, 2])                 # items up to each range
    n_real = last[-1]
    item = jnp.arange(length, dtype=jnp.int32)
    # the range of item i: how many ranges end at or before it (a dense
    # compare [items, ranges]; ranges without an item are stepped over)
    of = jnp.sum(last[None, :] <= jnp.minimum(item, n_real - 1)[:, None],
                 axis=1, dtype=jnp.int32)
    lo_i, hi_i, chunks_i = jnp.take(table, of, axis=0).T
    ordinal = jnp.minimum(item, n_real - 1) - (jnp.take(last, of) - chunks_i)
    chunk = jnp.minimum(lo_i // chunk_rows(block) + ordinal,
                        block // chunk_rows(block) - 1)
    real = item < n_real
    zero = jnp.zeros_like(item)
    return (of // n_held, of % n_held, chunk, jnp.where(real, lo_i, zero),
            jnp.where(real, hi_i, zero)), n_real


def _kernel(tile_ref, expert_ref, chunk_ref, lo_ref, hi_ref, tok_ref, y_ref,
            g_ref, carry_ref, out_ref, *, tm: int, rows: int):
    i = pl.program_id(0)
    tile = tile_ref[i]

    @pl.when((i == 0) | (tile != tile_ref[jnp.maximum(i - 1, 0)]))
    def _():
        out_ref[...] = carry_ref[...]

    lo, hi = lo_ref[i], hi_ref[i]

    @pl.when(hi > lo)
    def _():
        row = chunk_ref[i] * rows + jax.lax.broadcasted_iota(
            jnp.int32, (1, rows), 1)
        at = jnp.where((row >= lo) & (row < hi), tok_ref[...] - tile * tm, -1)
        place = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (tm, rows), 0) == at,
            1.0, 0.0).astype(y_ref.dtype)
        gate = None
        if g_ref is not None:
            g = g_ref[...]
            held = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
            gate = jnp.sum(jnp.where(held == expert_ref[i], g, 0.0), axis=1,
                           keepdims=True)
        # 0/1 weights and one term a row: a copy, exact in one bf16 pass
        # (float32 rows, which only tests bring, need every pass)
        placed = jax.lax.dot_general(
            place, y_ref[...], (((1,), (0,)), ((), ())),
            precision=(jax.lax.Precision.DEFAULT
                       if y_ref.dtype == jnp.bfloat16
                       else jax.lax.Precision.HIGHEST),
            preferred_element_type=jnp.float32)
        out_ref[...] += placed if gate is None else placed * gate


def moe_combine(carry, y, tok, items, g=None):
    """``carry + zeros.at[tok].add(y * gate)`` over the rows the items
    name.  carry [T, D] float32 (given up: the result takes its place);
    y [block, D] one block's rows; tok [block] int32 their tokens;
    ``items`` from :func:`work_items` for this block, over tiles of
    :func:`token_tile` tokens; g [T, H] float32 the gate of each token
    for each held expert, or None: the rows carry their weight already
    (the backward's ``d_x``) -> [T, D] float32."""
    T, D = carry.shape
    block = y.shape[0]
    tm, rows = token_tile(T, D), chunk_rows(block)

    def tile_map(i, tile, *_):
        return tile[i], 0

    def chunk_map(i, tile, expert, chunk, *_):
        return chunk[i], 0

    sum_spec = pl.BlockSpec((tm, D), tile_map)
    in_specs = [
        pl.BlockSpec((None, 1, rows),
                     lambda i, tile, expert, chunk, *_: (chunk[i], 0, 0)),
        pl.BlockSpec((rows, D), chunk_map)]
    operands = [tok.reshape(block // rows, 1, rows), y]
    if g is not None:
        in_specs.append(pl.BlockSpec((tm, g.shape[1]), tile_map))
        operands.append(g)

    def kernel(*refs):
        if g is None:       # no gate: the slot stays empty
            refs = refs[:7] + (None,) + refs[7:]
        _kernel(*refs, tm=tm, rows=rows)

    return named_pallas_call(
        "moe_combine", kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(items), grid=(items[0].shape[0],),
            in_specs=in_specs + [sum_spec], out_specs=sum_spec),
        out_shape=jax.ShapeDtypeStruct((T, D), jnp.float32),
        input_output_aliases={len(items) + len(operands): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret_mode(),
    )(*items, *operands, carry)
