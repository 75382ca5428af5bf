"""The Mamba-2 recurrence (state-space duality), in the forms a model
needs of it.

Per head h of H, with a state ``S`` [P, N] in float32, zero before the
first token, a step ``dt_t > 0`` (after its softplus), ``A_h < 0`` one
scalar a head, ``x_t`` [P], and ``B_t, C_t`` [N] that the ``H / G``
heads of a group share (head h reads group ``h // (H / G)``)::

    S_t = exp(dt_t A_h) S_{t-1} + (dt_t x_t) B_t^T
    y_t = S_t C_t + D_h x_t

- :func:`mamba2_step`: one token (decode); the two lines above as
  elementwise float32 products and float32 sums, never a matrix product:
  a product with one row would round the state to bfloat16 on its way
  into the MXU (as ``ops/kda.py::kda_step``).
- :func:`mamba2_scan`: a whole sequence token by token (``lax.scan`` over
  :func:`mamba2_step`): what the chunked form is tested against.
- :func:`mamba2_chunked`: a whole sequence (training, the experience
  forwards, prefill), all chunks of ``chunk`` tokens at once,
  differentiable by autodiff.  Inside a chunk with ``cs_t`` the running
  sum of ``dt A`` (so ``exp(cs_t - cs_s)`` is the decay from token s to
  token t, at most 1; the pairs s > t are masked BEFORE the exponential,
  which would overflow there)::

      Y = ((C B^T) * exp(cs_t - cs_s) * [s <= t]) (dt x)     within
        + exp(cs_t) C S_in                                   from before
      S_out = exp(cs_Q) S_in + sum_s exp(cs_Q - cs_s) (dt_s x_s) B_s^T

  ``C B^T`` is one product a GROUP (its heads share it), the other two a
  head; the states at the chunk boundaries come from one ``lax.scan`` over
  the chunks that carries ``S`` in float32.  Matrix-product operands are
  in the inputs' dtype (bfloat16 as the MXU takes them at default
  precision), decays, states and accumulation float32.  XLA's: no Pallas
  kernel (PERF.md section 5 has the trace's share).

A position with ``dt = 0`` leaves the state as it was (decay 1, no
input): that is how a caller makes padding inert, and how the chunked
form pads a sequence to whole chunks.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def mamba2_step(x, dt, A, B, C, D, state):
    """One token.  x [Bt, H, P]; dt [Bt, H] float32; A, D [H]; B, C
    [Bt, G, N]; state [Bt, H, P, N] float32 -> (y [Bt, H, P] float32,
    new state)."""
    f32 = jnp.float32
    Bt, H, P = x.shape
    G, N = B.shape[1:]
    x, dt, B, C = (t.astype(f32) for t in (x, dt, B, C))
    S = state.reshape(Bt, G, H // G, P, N)
    decay = jnp.exp(dt * A.astype(f32)).reshape(Bt, G, H // G, 1, 1)
    dtx = (dt[..., None] * x).reshape(Bt, G, H // G, P, 1)
    S = decay * S + dtx * B[:, :, None, None, :]
    y = jnp.sum(S * C[:, :, None, None, :], axis=-1).reshape(Bt, H, P)
    return y + D.astype(f32)[:, None] * x, S.reshape(state.shape)


def mamba2_scan(x, dt, A, B, C, D, state=None):
    """A whole sequence, token by token.  x [Bt, L, H, P]; dt
    [Bt, L, H]; B, C [Bt, L, G, N] -> (y [Bt, L, H, P] float32, the
    state after the last position)."""
    Bt, _, H, P = x.shape
    if state is None:
        state = jnp.zeros((Bt, H, P, B.shape[-1]), jnp.float32)

    def step(S, inp):
        y, S = mamba2_step(*inp[:2], A, *inp[2:], D, S)
        return S, y

    state, y = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1), state


def mamba2_chunked(x, dt, A, B, C, D, state: Optional[jax.Array] = None,
                   chunk: int = 128):
    """A whole sequence, chunk by chunk: shapes and result as
    :func:`mamba2_scan`'s."""
    f32 = jnp.float32
    Bt, L, H, P = x.shape
    G, N = B.shape[2:]
    R, cdt = H // G, x.dtype
    Q = min(chunk, -(-L // 8) * 8)
    pad = -L % Q
    if pad:
        x, dt, B, C = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, B, C))
    n = (L + pad) // Q
    xc = x.reshape(Bt, n, Q, G, R, P)
    Bc, Cc = (t.reshape(Bt, n, Q, G, N) for t in (B, C))
    dtc = dt.astype(f32).reshape(Bt, n, Q, G, R)
    cs = jnp.cumsum(dtc * A.astype(f32).reshape(G, R), axis=2)
    dtx = dtc[..., None] * xc.astype(f32)                # [Bt,n,Q,G,R,P]

    # within a chunk: one C B^T a group, the decays a head
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", Cc, Bc,
                    preferred_element_type=f32)
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    by_group = jnp.moveaxis(cs, 2, 3)                    # [Bt,n,G,Q,R]
    diff = by_group[:, :, :, :, None] - by_group[:, :, :, None]
    m = cb[..., None] * jnp.exp(
        jnp.where(tri[:, :, None], diff, -jnp.inf))      # [Bt,n,G,Q,Q,R]
    y = jnp.einsum("bcgqsr,bcsgrp->bcqgrp", m.astype(cdt), dtx.astype(cdt),
                   preferred_element_type=f32)

    # a chunk's own contribution to the state at its end, and the
    # states at the chunk boundaries
    to_end = jnp.exp(cs[:, :, -1:] - cs)                 # [Bt,n,Q,G,R]
    own = jnp.einsum("bcsgrp,bcsgn->cbgrpn",
                     (dtx * to_end[..., None]).astype(cdt), Bc,
                     preferred_element_type=f32)
    whole = jnp.moveaxis(jnp.exp(cs[:, :, -1]), 1, 0)    # [n,Bt,G,R]
    S0 = jnp.zeros((Bt, G, R, P, N), f32) if state is None \
        else state.astype(f32).reshape(Bt, G, R, P, N)

    def boundary(S, inp):
        own_c, whole_c = inp
        return whole_c[..., None, None] * S + own_c, S

    S_last, S_in = jax.lax.scan(boundary, S0, (own, whole))
    y = y + jnp.exp(cs)[..., None] * jnp.einsum(
        "bcqgn,cbgrpn->bcqgrp", Cc, S_in.astype(cdt),
        preferred_element_type=f32)
    y = y.reshape(Bt, L + pad, H, P)[:, :L] \
        + D.astype(f32)[:, None] * x[:, :L].astype(f32)
    return y, S_last.reshape(Bt, H, P, N)
