"""Grouped matrix product for the dropless expert layer (ops/moe.py).

``out[r] = lhs[r] @ rhs[g]`` for every row ``r`` of group ``g``, the
rows of a group lying side by side (the caller sorts them by expert and
hands over one block of the sorted rows at a time).  ``group_sizes`` has
one entry MORE than ``rhs`` has groups: the last group holds the rows of
a block that belong to no held expert (the pairs routed elsewhere, and
padding), which no kernel visits and whose output is zero — the kernels'
grids are as long as the held groups' row tiles, not as ``lhs``, so the
work follows the rows routed here.

The kernels are jax's own megablox Pallas kernels
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` for the product
and for the gradient of ``lhs``, ``tgmm`` for the gradient of ``rhs``),
called under names of this repo so that each reaches the device trace
(see ``named_pallas_call``): ``moe_gmm``, ``moe_gmm_dlhs``,
``moe_tgmm``.  The three are plain functions: the expert layer's VJP is
written in ops/moe.py over whole blocks (megablox's own ties all three
calls to one tiling).  Off-TPU they run interpreted.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from orion_tpu.ops.pallas import interpret_mode

# (rows, contraction, columns) tiles.  Rows: a group that starts inside
# a tile pays for the whole tile, so small row tiles waste less and big
# ones re-read the expert's weights less often; 512 keeps a v5e's MXU
# fed from HBM (197e12 / 819e9 = 240 operations a byte).
TILE_ROWS = 512
TILE_K = 1024
TILE_N = 1024


def row_tile(n_rows: int) -> int:
    """The row tile for ``n_rows`` rows: ``lhs`` is padded to a multiple
    of it by the caller."""
    return min(TILE_ROWS, -(-n_rows // 8) * 8)


def padded_rows(n_rows: int) -> int:
    """``n_rows`` rounded up to whole row tiles: the rows of ``lhs``."""
    return -(-n_rows // row_tile(n_rows)) * row_tile(n_rows)


def _fit(size: int, limit: int) -> int:
    """Largest multiple of 128 up to ``limit`` that divides ``size``,
    else the whole of it."""
    for tile in range(min(limit, size) // 128 * 128, 0, -128):
        if size % tile == 0:
            return tile
    return size


def _tiling(m: int, k: int, n: int):
    if m % row_tile(m):
        raise ValueError(f"{m} rows are no multiple of the row tile "
                         f"{row_tile(m)}: pad them (ops/moe.py does)")
    return row_tile(m), _fit(k, TILE_K), _fit(n, TILE_N)


def _call(name, fn, *args, **kw):
    # the jitted wrapper would put its own entry innermost on the name
    # stack; the instruction is named after the innermost entry
    fn = getattr(fn, "__wrapped__", fn)
    with jax.named_scope(name):
        return fn(*args, interpret=interpret_mode(), **kw)


def _backend():
    # the package re-exports its own custom-VJP ``gmm`` over the module
    # of that name; the kernels' module is reached through ops
    from jax.experimental.pallas.ops.tpu.megablox import ops

    return ops.backend


def _gmm(name, lhs, rhs, group_sizes, transpose_rhs=False):
    backend = _backend()
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return _call(name, backend.gmm, lhs, rhs, group_sizes,
                 preferred_element_type=lhs.dtype, tiling=_tiling(m, k, n),
                 group_offset=jnp.zeros((), jnp.int32),
                 transpose_rhs=transpose_rhs)


def gmm(lhs, rhs, group_sizes):
    """lhs [m, k], rhs [G, k, n], group_sizes [G + 1] int32 summing to
    m -> [m, n] in lhs.dtype; rows of the last group come back zero."""
    return _gmm("moe_gmm", lhs, rhs, group_sizes)


def gmm_dlhs(grad, rhs, group_sizes):
    """The gradient of ``gmm``'s ``lhs``: grad [m, n], rhs [G, k, n] ->
    [m, k], rows of the last group zero."""
    return _gmm("moe_gmm_dlhs", grad, rhs, group_sizes, transpose_rhs=True)


def tgmm(lhs, grad, group_sizes, out_dtype):
    """The gradient of ``gmm``'s ``rhs``: lhs [m, k], grad [m, n] ->
    [G, k, n] in ``out_dtype``, ``G = len(group_sizes) - 1``: group
    ``g``'s rows of ``lhs``, transposed, times its rows of ``grad``."""
    m, k = lhs.shape
    return _call("moe_tgmm", _backend().tgmm, lhs.swapaxes(0, 1), grad,
                 group_sizes, preferred_element_type=out_dtype,
                 tiling=_tiling(m, k, grad.shape[1]),
                 group_offset=jnp.zeros((), jnp.int32),
                 num_actual_groups=group_sizes.shape[0] - 1)
