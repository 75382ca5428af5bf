"""Grouped matrix product for the dropless expert layer (ops/moe.py).

``out[r] = lhs[r] @ rhs[g]`` for every row ``r`` of group ``g``, the
rows of a group lying side by side (the caller sorts them by expert).
``group_sizes`` has one entry MORE than ``rhs`` has groups: the last
group holds the rows of experts this chip does not hold (and padding),
which no kernel visits and whose output is zero — the kernels' grids are
as long as the held groups' row tiles, not as ``lhs``, so the work
follows the rows routed here.

The kernels are jax's own megablox Pallas kernels
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` for the product
and for the gradient of ``lhs``, ``tgmm`` for the gradient of ``rhs``),
called under names of this repo so that each reaches the device trace
(see ``named_pallas_call``): ``moe_gmm``, ``moe_gmm_dlhs``,
``moe_tgmm``.  The VJP is written here (megablox's own ties all three
calls to one tiling).  Off-TPU they run interpreted.
"""

from __future__ import annotations

import functools

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


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes):
    """lhs [m, k], rhs [G, k, n], group_sizes [G + 1] int32 summing to
    m -> [m, n] in lhs.dtype; rows of the last group come back zero."""
    return _gmm("moe_gmm", lhs, rhs, group_sizes)


def _fwd(lhs, rhs, group_sizes):
    return grouped_matmul(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _bwd(res, grad):
    backend = _backend()
    lhs, rhs, group_sizes = res
    grad = grad.astype(lhs.dtype)
    d_lhs = _gmm("moe_gmm_dlhs", grad, rhs, group_sizes, transpose_rhs=True)
    m, k = lhs.shape
    d_rhs = _call("moe_tgmm", backend.tgmm, lhs.swapaxes(0, 1), grad,
                  group_sizes, preferred_element_type=rhs.dtype,
                  tiling=_tiling(m, k, grad.shape[1]),
                  group_offset=jnp.zeros((), jnp.int32),
                  num_actual_groups=rhs.shape[0])
    return d_lhs, d_rhs, None


grouped_matmul.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def dispatch_rows(x, order, inverse, k: int):
    """x [T, D] -> the rows of the (token, choice) pairs in sorted
    order, [m, D]: pair ``p`` is token ``p // k``.  ``order`` [m] lists
    the pairs by expert (padding points anywhere); ``inverse`` [T * k]
    is where each pair went.  Both directions are gathers: the gradient
    of a token is the sum of its k pairs' rows."""
    return jnp.take(x, order // k, axis=0, mode="clip")


def _dispatch_fwd(x, order, inverse, k):
    return dispatch_rows(x, order, inverse, k), (inverse, x.shape[0])


def _dispatch_bwd(k, res, g):
    inverse, n_tokens = res
    pairs = jnp.take(g, inverse, axis=0, mode="clip")
    return pairs.reshape(n_tokens, k, -1).sum(axis=1), None, None


dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def collect_rows(y, order, inverse):
    """y [m, D] in sorted order -> [T * k, D] in pair order; the
    gradient goes back by ``order`` (rows past the pairs get a row that
    no kernel reads)."""
    return jnp.take(y, inverse, axis=0, mode="clip")


def _collect_fwd(y, order, inverse):
    return collect_rows(y, order, inverse), (order,)


def _collect_bwd(res, g):
    (order,) = res
    return jnp.take(g, order, axis=0, mode="clip"), None, None


collect_rows.defvjp(_collect_fwd, _collect_bwd)
