"""The chunked delta rule (``ops/kda.py``) as two Pallas TPU kernels: a
chunk's pair products, triangular inverse and state update stay in VMEM,
forward and backward.

One grid step is one chunk of ``C`` tokens for ``hb`` heads of one row;
the chunk axis is last and sequential, and the state rides it in VMEM
scratch.  Nothing is transposed on the way in or out: ``q, k, v, g`` and
``o`` are read and written as ``[B, L, H * d]`` with blocks ``(1, C,
hb * d)``, the layout the mixer has.  The state crosses the kernels'
boundary transposed, ``R = S^T`` [dv, dk]: its decay is then a lane
broadcast and every product with it contracts last dimensions.

Two heads make one stack (:class:`_Chunk`): their chunks lie one above
the other on the rows and their [C, C] matrices are the diagonal blocks
of one [2 C, 2 C] matrix, which fills the 128 lanes at C = 64 and walks
the inverse's chain of dependent products once for both.  A grid step
holds up to two stacks, independent chains for the scheduler.

The arithmetic is the ``jax.numpy`` chunk's (``kda._chunk``), with the
same roundings: matrix-product operands in bfloat16, accumulation, the
state, ``g``, its running sums and every decay in float32.

- ``G``, the running sum of ``g``, is a product of a table of zeros and
  ones with ``g`` cut into three bfloat16 pieces: exact products,
  float32 sums (:func:`_exact_rows`).
- Pair products ``sum_c r_t k_i exp(G_t - G_i)`` in ``log2 C`` levels,
  each splitting the decay in the middle of a block so that both factors
  are at most 1 (no ``exp(-G)``); the pair (t, i) belongs to the level of
  the highest bit in which t and i differ.  A level's exponent is ``G``
  less its row at the block's middle (``g`` itself at half-size 1, a
  table's rows where a block is smaller than a float32 tile).
- ``(I + M)^-1`` by doubling from the inverses of the diagonal blocks,
  ``D <- D - D M_m D`` with ``M_m`` the level's blocks of ``M``; never a
  series of powers.

The backward recomputes a chunk's insides from its inputs and the state
at its start, which the forward writes out when it is differentiated
(``[B, H, n, dv, dk]`` float32), and runs the chunks in reverse with the
state's gradient in scratch.  With ``X = beta (V - (K e^G) S0)``, ``U =
T X``: ``dU = P^T dO + K^ dS1``, ``Y = T^T dU`` is ``dX``, ``dM =
-strict_tril(Y U^T)`` (the doubling is not differentiated), and a pair
product's gradient is split by the same levels as the product.

On a v5e at 16 x 1024 tokens, 32 heads of 128 (PERF.md section 6, PR
33): forward 8.7 ms, backward 13.3 ms; half of the forward is the
inverse's ten dependent [128, 128] products, a quarter the pair
products.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import interpret_mode, named_pallas_call

F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))      # a b
_NT = (((1,), (1,)), ((), ()))      # a b^T
_TN = (((0,), (0,)), ((), ()))      # a^T b


def _levels(C: int) -> int:
    assert C & (C - 1) == 0, "the chunk length must be a power of two"
    return C.bit_length() - 1


def _table_levels(C: int) -> list:
    """The levels whose exponents come from a table: blocks of fewer
    rows than a float32 tile has (the level of half-size 1 needs none,
    its exponent is ``g`` itself)."""
    return [lvl for lvl in range(1, _levels(C)) if 2 << lvl < 8]


@functools.lru_cache(maxsize=None)
def _row_tables(C: int, P: int) -> np.ndarray:
    """Tables of zeros and ones for P chunks stacked on the rows, each
    [P C, P C] and block-diagonal; times ``g`` [P C, d]: ``G_t`` (the
    running sum inside each chunk); for each of :func:`_table_levels`
    the exponent of the level's scaling (``G_t - G_mid`` above the
    middle of t's block of 2h, ``G_mid - G_t`` below: both <= 0 for
    g <= 0); and last the reversed running sum, which turns ``dG`` into
    ``dg``."""
    t, j = np.arange(C)[:, None], np.arange(C)[None, :]
    tabs = [j <= t]
    for lvl in _table_levels(C):
        h = 1 << lvl
        mid = (t // (2 * h)) * (2 * h) + h - 1
        tabs.append(np.where((t // h) % 2 == 1,
                             (j > mid) & (j <= t), (j > t) & (j <= mid)))
    tabs.append(j >= t)
    return np.concatenate(
        [np.kron(np.eye(P), tab) for tab in tabs], axis=0).astype(np.float32)


def _exact_rows(table, x):
    """``table`` (zeros and ones, bfloat16) times float32 ``x``, to
    float32's accuracy on the MXU: x as three bfloat16 pieces, each the
    top 16 bits of what the pieces before left (cut with a mask, not by
    a convert and back, which a compiler may remove)."""
    out = None
    for _ in range(3):
        top = lax.bitcast_convert_type(
            lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(-65536), F32)
        part = lax.dot_general(table, top.astype(jnp.bfloat16), _NN,
                               preferred_element_type=F32)
        out = part if out is None else out + part
        x = x - top
    return out


def _rows(parts):
    """Blocks of rows one above the other."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


class _Chunk:
    """A chunk's insides for P heads at once, as both kernels need them.

    The heads' chunks are stacked on the rows: q, k, g [P C, dk], v
    [P C, dv] float32, beta [P C, 1]; the [C, C] matrices of the heads
    (pair products, ``M``, its inverse) are the diagonal blocks of one
    [P C, P C] matrix, so that two heads fill the 128 lanes and the
    inverse's chain of small products is walked once for both.  R: the
    P states at the chunk's start, each [dv, dk] float32 (transposed);
    w_ref the row tables; dt the matrix products' operand type."""

    def __init__(self, q, k, v, g, beta, R, w_ref, dt, C):
        n = q.shape[0]
        self.C, self.P, self.n, self.dt = C, n // C, n, dt
        self.n_lvl = _levels(C)
        self.q, self.k, self.g, self.R = q, k, g, R
        self.w_ref = w_ref
        self.row = lax.broadcasted_iota(jnp.int32, (n, n), 0)
        self.col = lax.broadcasted_iota(jnp.int32, (n, n), 1)
        # the highest bit in which t and i differ says which level
        # splits the pair, and whether they are of one head at all
        self.split = jnp.bitwise_xor(self.row, self.col)
        self.pos = lax.broadcasted_iota(jnp.int32, (n, 1), 0)
        self.G = _exact_rows(w_ref[:n, :], g)
        self.decay = jnp.exp(self.G)                        # e^G <= 1
        self.Kd = k * self.decay
        self.Qd = q * self.decay
        self.to_end = jnp.exp(self.at_end(self.G) - self.G)  # e^(G_C - G)
        self.Kh = k * self.to_end

        A = Bq = jnp.zeros((n, n), F32)
        for lvl in range(self.n_lvl):
            ks, qs, _ = self.scaled(lvl)
            m = self.level(lvl)
            A = jnp.where(m, self.mm(ks, ks, _NT), A)
            Bq = jnp.where(m, self.mm(qs, ks, _NT), Bq)
        self.A = A
        M = beta * A
        # (I + M)^-1: the blocks of 1 are 1, so those of 2 need no product
        T = (self.row == self.col).astype(F32) - jnp.where(
            self.level(0), M, 0.0)
        for lvl in range(1, self.n_lvl):
            Mm = jnp.where(self.level(lvl), M, 0.0)
            T = T - self.mm(self.mm(T, Mm), T)
        self.T = T
        # V - (K e^G) S0
        self.err = v - self.per_head(lambda p, s: self.mm(
            self.Kd[s], R[p], _NT))
        self.U = self.mm(T, beta * self.err)
        self.Pm = Bq + jnp.where(self.row == self.col, jnp.sum(
            q * k, axis=1, keepdims=True), 0.0)

    def mm(self, a, b, dims=_NN):
        return lax.dot_general(a.astype(self.dt), b.astype(self.dt), dims,
                               preferred_element_type=F32)

    def per_head(self, fn):
        """``fn(p, rows of head p)`` for each head, stacked on the rows."""
        return _rows([fn(p, slice(p * self.C, (p + 1) * self.C))
                      for p in range(self.P)])

    def last(self, x, p):
        """The last row of head p's chunk in x [P C, d]: [1, d]."""
        return x[(p + 1) * self.C - 1:(p + 1) * self.C, :]

    def at_end(self, x):
        """Each chunk's last row of x [P C, d], on all of its rows."""
        return self.per_head(lambda p, s: jnp.broadcast_to(
            self.last(x, p), (self.C, x.shape[1])))

    def level(self, lvl):
        """The pairs t > i that the level of half-size ``2^lvl`` splits:
        same block of 2h (so same head), t above its middle, i below."""
        h = 1 << lvl
        return ((self.row > self.col) & (self.split >= h)
                & (self.split < 2 * h))

    def scaled(self, lvl):
        """k and q under the level's scaling (operands), and the
        scaling ``exp(-|G_t - G_mid|)``, G_mid at the middle of t's
        block of 2h."""
        h, n = 1 << lvl, self.n
        upper = (self.pos // h) % 2 == 1
        if h == 1:
            e = jnp.where(upper, self.g, 0.0)
        elif lvl in _table_levels(self.C):
            i = 1 + _table_levels(self.C).index(lvl)
            e = _exact_rows(self.w_ref[i * n:(i + 1) * n, :], self.g)
        else:
            blocks = self.G.reshape(n // (2 * h), 2 * h, self.G.shape[1])
            e = (blocks - blocks[:, h - 1:h, :]).reshape(self.G.shape)
            e = jnp.where(upper, e, -e)
        scale = jnp.exp(e)
        return ((self.k * scale).astype(self.dt),
                (self.q * scale).astype(self.dt), scale)

    def rev_sum(self, x):
        """The reversed running sum inside each chunk (``dG -> dg``)."""
        return _exact_rows(self.w_ref[self.w_ref.shape[0] - self.n:, :], x)

    def out(self):
        """(o [P C, dv], the P states after the chunk, each [dv, dk])."""
        o = self.per_head(lambda p, s: self.mm(
            self.Qd[s], self.R[p], _NT)) + self.mm(self.Pm, self.U)
        R1 = [self.R[p] * self.last(self.decay, p)
              + self.mm(self.U[p * self.C:(p + 1) * self.C],
                        self.Kh[p * self.C:(p + 1) * self.C], _TN)
              for p in range(self.P)]
        return o, R1


def _stacks(hb: int) -> list:
    """The heads of a grid step in stacks of two (one, if hb is odd):
    each stack is one :class:`_Chunk`, and a step's stacks are
    independent chains for the scheduler to interleave."""
    P = 2 if hb % 2 == 0 else 1
    return [range(j, j + P) for j in range(0, hb, P)]


def _stacked(ref, heads, d):
    """The block's ``heads`` [C, d] each, stacked on the rows."""
    return _rows([ref[0, :, j * d:(j + 1) * d].astype(F32) for j in heads])


def _beta(ref, heads):
    """The step sizes of ``heads`` (columns of the block [C, hb]) as one
    column."""
    b = ref[0, 0]
    return _rows([b[:, j:j + 1] for j in heads])


def _unstack(ref, x, heads, d):
    C = ref.shape[1]
    for p, j in enumerate(heads):
        ref[0, :, j * d:(j + 1) * d] = x[p * C:(p + 1) * C].astype(ref.dtype)


def _fwd_kernel(w_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, r0_ref,
                o_ref, rfin_ref, *rest, hb, dk, dv, dt):
    """rest: the states at the chunks' starts (only when the backward
    will want them), then the scratch that carries the state."""
    r_scr = rest[-1]
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        r_scr[...] = r0_ref[0]

    for heads in _stacks(hb):
        R = [r_scr[j] for j in heads]
        if len(rest) == 2:
            for j, Rj in zip(heads, R):
                rest[0][0, j, 0] = Rj
        o, R1 = _Chunk(
            _stacked(q_ref, heads, dk), _stacked(k_ref, heads, dk),
            _stacked(v_ref, heads, dv), _stacked(g_ref, heads, dk),
            _beta(beta_ref, heads), R, w_ref, dt, q_ref.shape[1]).out()
        _unstack(o_ref, o, heads, dv)
        for j, Rj in zip(heads, R1):
            r_scr[j] = Rj

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        rfin_ref[0] = r_scr[...]


def _bwd_kernel(w_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, rb_ref, do_ref,
                drfin_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                dr0_ref, dr_scr, *, hb, dk, dv, dt):
    c = pl.program_id(2)            # the index maps reverse the chunks
    C = q_ref.shape[1]

    @pl.when(c == 0)
    def _():
        dr_scr[...] = drfin_ref[0]

    lane = lax.broadcasted_iota(jnp.int32, (C, hb), 1)
    dbeta_all = jnp.zeros((C, hb), F32)
    for heads in _stacks(hb):
        q, k, beta = (_stacked(q_ref, heads, dk), _stacked(k_ref, heads, dk),
                      _beta(beta_ref, heads))
        R = [rb_ref[0, j, 0] for j in heads]
        ch = _Chunk(q, k, _stacked(v_ref, heads, dv),
                    _stacked(g_ref, heads, dk), beta, R, w_ref, dt, C)
        mm, U, per_head = ch.mm, ch.U, ch.per_head
        do = _stacked(do_ref, heads, dv)
        dR1 = [dr_scr[j] for j in heads]
        one_head = ch.split < C
        lower = (ch.row > ch.col) & one_head

        dU = mm(ch.Pm, do, _TN) + per_head(
            lambda p, s: mm(ch.Kh[s], dR1[p], _NT))
        dP = jnp.where((ch.row >= ch.col) & one_head, mm(do, U, _NT), 0.0)
        dQd = per_head(lambda p, s: mm(do[s], R[p]))
        dKh = per_head(lambda p, s: mm(U[s], dR1[p]))
        Y = mm(ch.T, dU, _TN)                               # dX
        dM = -jnp.where(lower, mm(Y, U, _NT), 0.0)
        derr = beta * Y                                     # d(V - K~ S0)
        dKd = -per_head(lambda p, s: mm(derr[s], R[p]))
        dbeta = (jnp.sum(Y * ch.err, axis=1, keepdims=True)
                 + jnp.sum(dM * ch.A, axis=1, keepdims=True))
        for p, j in enumerate(heads):
            s = slice(p * C, (p + 1) * C)
            dr_scr[j] = (dR1[p] * ch.last(ch.decay, p)
                         + mm(do[s], ch.Qd[s], _TN)
                         - mm(derr[s], ch.Kd[s], _TN))
            dbeta_all = jnp.where(lane == j, dbeta[s], dbeta_all)

        # the pair products' gradient, level by level as the products
        dA, dB = beta * dM, jnp.where(lower, dP, 0.0)
        dq_p = dk_t = dk_i = jnp.zeros_like(q)
        for lvl in range(ch.n_lvl):
            ks, qs, scale = ch.scaled(lvl)
            m = ch.level(lvl)
            mA, mB = jnp.where(m, dA, 0.0), jnp.where(m, dB, 0.0)
            dk_t = dk_t + scale * mm(mA, ks)
            dq_p = dq_p + scale * mm(mB, ks)
            dk_i = dk_i + scale * (mm(mA, ks, _TN) + mm(mB, qs, _TN))
        ddiag = jnp.sum(jnp.where(ch.row == ch.col, dP, 0.0), axis=1,
                        keepdims=True)
        dKhKh = dKh * ch.Kh
        dG = (q * dq_p + k * (dk_t - dk_i) + dQd * ch.Qd + dKd * ch.Kd
              - dKhKh)
        dG_end = per_head(lambda p, s: jnp.broadcast_to(
            jnp.sum(dKhKh[s], axis=0, keepdims=True)
            + ch.last(ch.decay, p) * jnp.sum(
                R[p] * dR1[p], axis=0, keepdims=True), (C, dk)))
        _unstack(dq_ref, dq_p + ddiag * k + dQd * ch.decay, heads, dk)
        _unstack(dk_ref, dk_t + dk_i + ddiag * q + dKd * ch.decay
                 + dKh * ch.to_end, heads, dk)
        _unstack(dv_ref, derr, heads, dv)
        _unstack(dg_ref, ch.rev_sum(dG) + dG_end, heads, dk)
    dbeta_ref[0, 0] = dbeta_all

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        dr0_ref[0] = dr_scr[...]


def _heads_per_step(H: int) -> int:
    """Heads a grid step: their chains of small products are independent
    and share the step's fixed cost."""
    return next(hb for hb in (4, 2, 1) if H % hb == 0)


def _tables(C, hb):
    return jnp.asarray(_row_tables(C, len(_stacks(hb)[0])), jnp.bfloat16)


def _specs(B, H, n, C, dk, dv, hb, chunk_of):
    """Block specs by name; ``chunk_of`` maps the grid's chunk index to
    the chunk (the backward walks them in reverse)."""
    def seq(d):
        return pl.BlockSpec((1, C, hb * d),
                            lambda b, h, c: (b, chunk_of(c), h))

    state = pl.BlockSpec((1, hb, dv, dk), lambda b, h, c: (b, h, 0, 0))
    return {
        "w": pl.BlockSpec(_tables(C, hb).shape, lambda b, h, c: (0, 0)),
        "qk": seq(dk), "v": seq(dv), "state": state,
        "beta": pl.BlockSpec((1, 1, C, hb),
                             lambda b, h, c: (b, h, chunk_of(c), 0)),
        "starts": pl.BlockSpec((1, hb, 1, dv, dk),
                               lambda b, h, c: (b, h, chunk_of(c), 0, 0)),
    }


def _call(name, kernel, grid, in_specs, out_specs, out_shape, scratch):
    return named_pallas_call(
        name, kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode())


def _forward(H, C, dt, keep, q, k, v, g, beta, r0):
    """q, k, g [B, n C, H dk]; v [B, n C, H dv]; beta [B, H / hb, n C,
    hb]; r0 [B, H, dv, dk] -> o, the last state and, if ``keep``, the
    states at the chunks' starts."""
    B, Lp = q.shape[:2]
    dk, dv, n = q.shape[2] // H, v.shape[2] // H, Lp // C
    hb = _heads_per_step(H)
    s = _specs(B, H, n, C, dk, dv, hb, lambda c: c)
    out_specs = [s["v"], s["state"]] + [s["starts"]] * keep
    out_shape = [jax.ShapeDtypeStruct((B, Lp, H * dv), F32),
                 jax.ShapeDtypeStruct((B, H, dv, dk), F32)] + [
        jax.ShapeDtypeStruct((B, H, n, dv, dk), F32)] * keep
    return _call(
        "kda_chunk_fwd",
        functools.partial(_fwd_kernel, hb=hb, dk=dk, dv=dv, dt=dt),
        (B, H // hb, n),
        [s["w"], s["qk"], s["qk"], s["v"], s["qk"], s["beta"], s["state"]],
        out_specs, out_shape, [pltpu.VMEM((hb, dv, dk), F32)],
    )(_tables(C, hb), q, k, v, g, beta, r0)


def _backward(H, C, dt, q, k, v, g, beta, starts, do, dr_fin):
    B, Lp = q.shape[:2]
    dk, dv, n = q.shape[2] // H, v.shape[2] // H, Lp // C
    hb = _heads_per_step(H)
    s = _specs(B, H, n, C, dk, dv, hb, lambda c: n - 1 - c)
    return _call(
        "kda_chunk_bwd",
        functools.partial(_bwd_kernel, hb=hb, dk=dk, dv=dv, dt=dt),
        (B, H // hb, n),
        [s["w"], s["qk"], s["qk"], s["v"], s["qk"], s["beta"], s["starts"],
         s["v"], s["state"]],
        [s["qk"], s["qk"], s["v"], s["qk"], s["beta"], s["state"]],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype),
         jax.ShapeDtypeStruct(g.shape, F32),
         jax.ShapeDtypeStruct(beta.shape, F32),
         jax.ShapeDtypeStruct(dr_fin.shape, F32)],
        [pltpu.VMEM((hb, dv, dk), F32)],
    )(_tables(C, hb), q, k, v, g, beta, starts, do, dr_fin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _core(H, C, dt, q, k, v, g, beta, r0):
    return tuple(_forward(H, C, dt, False, q, k, v, g, beta, r0))


def _core_fwd(H, C, dt, q, k, v, g, beta, r0):
    o, r_fin, starts = _forward(H, C, dt, True, q, k, v, g, beta, r0)
    return (o, r_fin), (q, k, v, g, beta, starts)


def _core_bwd(H, C, dt, res, cts):
    return tuple(_backward(H, C, dt, *res, *cts))


_core.defvjp(_core_fwd, _core_bwd)


def kda_chunk_kernel(q, k, v, g, beta, state, chunk, operand_dtype=None):
    """``kda.kda_chunked`` through the kernels, same arguments and
    results; ``state`` [B, H, dk, dv] float32 (not None).  The products'
    operands are bfloat16, as the MXU takes them at default precision;
    a test may ask for float32 to see the formulas apart from the
    rounding."""
    B, L, H, dk = q.shape
    dv = v.shape[-1]
    n = -(-L // chunk)
    hb = _heads_per_step(H)

    def seq(t):
        t = jnp.pad(t, ((0, 0), (0, n * chunk - L)) + ((0, 0),) * (t.ndim - 2))
        return t.reshape(B, n * chunk, -1)

    beta_h = jnp.moveaxis(
        seq(beta.astype(F32)).reshape(B, n * chunk, H // hb, hb), 2, 1)
    o, r_fin = _core(
        H, chunk, operand_dtype or jnp.bfloat16, seq(q), seq(k), seq(v),
        seq(g.astype(F32)), beta_h, jnp.swapaxes(state.astype(F32), -1, -2))
    return (o.reshape(B, n * chunk, H, dv)[:, :L],
            jnp.swapaxes(r_fin, -1, -2))
