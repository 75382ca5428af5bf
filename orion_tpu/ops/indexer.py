"""The lightning indexer of learned sparse attention (DeepSeek-V3.2-Exp's,
on grouped-query heads as Keye-VL-2.0 publishes it): which keys a query
attends to.

``I[t, s] = scale * sum_j w[t, j] * ReLU(qI[t, j] . kI[s])`` over the
``Hi`` indexer heads against ONE key head, float32, for key slots ``s <=
position of t`` (slot == position as everywhere in models/transformer.py:
a real query never reaches padding), minus infinity otherwise; a query
keeps the ``min(topk, its valid keys)`` largest, the LOWER slot first on
a tie.  Exact: there is no approximate top-k, no capacity and no dropped
key in either form.

The scores never exist whole (``Hi x S x S`` float32 is 4.3 GB a
sequence at S = 8192): both forms go over the queries a tile at a time
and over the keys in chunks (``sa_q_chunk`` / ``sa_kv_chunk``, which
change no equation).

- :func:`select` gives the selection of whole sequences as the operand
  the attention kernels take: ``[B, keys, queries]`` int8, TRANSPOSED as
  their score tiles are (ops/pallas/flash_attention.py).  On a TPU it is
  one kernel (``dsa_select``): a tile of queries holds its scores as
  order-preserving integers in VMEM, finds the ``topk``-th largest by a
  search over their 32 bits (a count a bit, no sort), then the slot of
  the last tie that fits by a search over the slots' bits.  Elsewhere
  ``jax.lax.top_k`` (a stable sort: the lower slot first).
- :func:`select_step` gives one new query's selection against a cache of
  indexer keys as a mask of the slots, for the one-token step that reads
  k and v in place (``ops/pallas/sparse_step.py``).

No gradient passes: the selection is discrete, the callers hand over
``stop_gradient`` operands (models/transformer.py SparseAttention).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from orion_tpu.ops.pallas import interpret_mode, named_pallas_call

_INT_MIN = -2 ** 31


def select_form() -> str:
    """``kernel`` where the trace is for one TPU device (a Mosaic kernel
    cannot be partitioned automatically), else ``jnp``."""
    from orion_tpu.parallel.sharding import ambient_mesh

    mesh = ambient_mesh()
    one_device = mesh is None or mesh.empty or mesh.size == 1
    return "kernel" if one_device and not interpret_mode() else "jnp"


def index_scores(qi, ki, w, q_positions, kv_chunk: int):
    """qi [B, Lq, Hi, Di], ki [B, Lk, Di], w [B, Lq, Hi] float32 (the
    scale folded in), q_positions [B, Lq] -> I [B, Lq, Lk] float32, minus
    infinity where the slot is past the query's position.  A chunk of
    keys at a time: the [B, Lq, Hi, chunk] products are the largest
    thing alive."""
    Lk = ki.shape[1]
    out = []
    for start in range(0, Lk, kv_chunk):
        s = jnp.einsum("bqhd,bkd->bqhk", qi, ki[:, start:start + kv_chunk],
                       preferred_element_type=jnp.float32)
        out.append(jnp.einsum("bqhk,bqh->bqk", jax.nn.relu(s), w))
    scores = jnp.concatenate(out, axis=-1)
    slots = jnp.arange(Lk, dtype=q_positions.dtype)
    return jnp.where(slots[None, None, :] <= q_positions[:, :, None],
                     scores, -jnp.inf)


def _select_jnp(qi, ki, w, q_positions, topk, q_chunk, kv_chunk):
    B, Lq = q_positions.shape
    Lk = ki.shape[1]
    k = min(topk, Lk)
    q_chunk = min(q_chunk, Lq)
    if Lq % q_chunk:
        q_chunk = Lq

    def tile(args):
        qi_c, w_c, pos_c = args
        scores = index_scores(qi_c, ki, w_c, pos_c, kv_chunk)
        vals, idx = jax.lax.top_k(scores, k)      # stable: lower slot first
        rows = jnp.arange(B)[:, None, None], jnp.arange(q_chunk)[None, :, None]
        sel = jnp.zeros(scores.shape, jnp.int8).at[rows + (idx,)].max(
            (vals > -jnp.inf).astype(jnp.int8))
        return sel.swapaxes(1, 2)                             # [B, Lk, qc]

    def chunks(t):
        return t.reshape((B, Lq // q_chunk, q_chunk) + t.shape[2:]
                         ).swapaxes(0, 1)

    sel = jax.lax.map(tile, (chunks(qi), chunks(w), chunks(q_positions)))
    return sel.transpose(1, 2, 0, 3).reshape(B, Lk, Lq)


# ---------------------------------------------------------------------------
# The kernel: grid (B, query tiles); a tile's scores live in VMEM as
# order-preserving int32 [keys, queries] (keys down sublanes, queries
# along lanes: the counts are element-wise adds of vregs and one sublane
# reduction a chunk).  Key chunks past the tile's last position are
# neither scored nor counted; their rows of the output are zeros.
# ---------------------------------------------------------------------------

#: queries a grid step (lanes) and keys a chunk (sublanes)
_TQ, _KC = 128, 512


def _ordered(x):
    """float32 -> int32 with the same order (no NaN among the scores)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _select_kernel(qmax_ref, qpos_ref, qi_ref, w_ref, ki_ref, sel_ref,
                   key_sc, *, topk: int, kc: int, pos_bits: int):
    b, i = pl.program_id(0), pl.program_id(1)
    Lk, tq = sel_ref.shape[1], sel_ref.shape[2]
    n_heads = qi_ref.shape[1]
    n_chunks = Lk // kc
    n_live = jnp.minimum(qmax_ref[b, i] // kc + 1, n_chunks)
    qpos = qpos_ref[0]                                        # [1, tq]
    want = jnp.minimum(qpos + 1, topk)           # keys to keep, a query

    def slots(c):
        return c * kc + jax.lax.broadcasted_iota(jnp.int32, (kc, tq), 0)

    def score(c, _):
        rows = pl.ds(pl.multiple_of(c * kc, kc), kc)
        ki = ki_ref[0, rows, :]                               # [kc, Di]
        acc = jnp.zeros((kc, tq), jnp.float32)
        for j in range(n_heads):
            # (bf16 operands: exact in the float32 accumulator, and
            # Mosaic refuses an ambient "highest" on them: flash's _dot)
            s = jax.lax.dot_general(
                ki, qi_ref[0, j], (((1,), (1,)), ((), ())),
                precision=(None if ki.dtype == jnp.float32
                           else jax.lax.Precision.DEFAULT),
                preferred_element_type=jnp.float32)           # [kc, tq]
            acc = acc + jnp.maximum(s, 0.0) * w_ref[0, j:j + 1, :]
        key_sc[rows, :] = jnp.where(slots(c) <= qpos, _ordered(acc),
                                    jnp.int32(_INT_MIN))
        return 0

    jax.lax.fori_loop(0, n_live, score, 0)

    def count(pred):
        """[1, tq]: over the live chunks, how many keys satisfy
        ``pred(ordered keys [kc, tq], chunk)``."""
        def add(c, n):
            rows = pl.ds(pl.multiple_of(c * kc, kc), kc)
            return n + jnp.sum(pred(key_sc[rows, :], c).astype(jnp.int32),
                               axis=0, keepdims=True)
        return jax.lax.fori_loop(0, n_live, add,
                                 jnp.zeros((1, tq), jnp.int32))

    # the want-th largest key: its bits from the top, in the unsigned
    # order (a signed key with its sign bit flipped)
    def bit(n, t):
        trial = t | (jnp.int32(1) << (31 - n))
        signed = trial ^ jnp.int32(_INT_MIN)
        enough = count(lambda keys, c: keys >= signed) >= want
        return jnp.where(enough, trial, t)

    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros((1, tq), jnp.int32)) \
        ^ jnp.int32(_INT_MIN)
    # the keys equal to it share what the larger ones leave: the lowest
    # slots first.  last = the smallest slot with `left` ties at or
    # below it.
    left = want - count(lambda keys, c: keys > thr)

    def slot_bit(n, p):
        trial = p + (jnp.int32(1) << (pos_bits - 1 - n))
        ties = count(lambda keys, c: (keys == thr) & (slots(c) < trial))
        return jnp.where(ties < left, trial, p)

    last = jax.lax.fori_loop(0, pos_bits, slot_bit,
                             jnp.zeros((1, tq), jnp.int32))

    def write(c, _):
        rows = pl.ds(pl.multiple_of(c * kc, kc), kc)
        keys = key_sc[rows, :]
        keep = (keys > thr) | ((keys == thr) & (slots(c) <= last))
        sel_ref[0, rows, :] = keep.astype(jnp.int32).astype(jnp.int8)
        return 0

    def blank(c, _):
        rows = pl.ds(pl.multiple_of(c * kc, kc), kc)
        sel_ref[0, rows, :] = jnp.zeros((kc, tq), jnp.int8)
        return 0

    jax.lax.fori_loop(0, n_live, write, 0)
    jax.lax.fori_loop(n_live, n_chunks, blank, 0)


def _tile(n: int, preferred: int) -> int:
    """A lane tile: ``preferred`` where it divides ``n``, else all."""
    return preferred if n % preferred == 0 else n


def select_kernel(qi, ki, w, q_positions, topk: int):
    """The kernel form of :func:`select` (interpreted off the TPU)."""
    B, Lq, Hi, Di = qi.shape
    Lk = ki.shape[1]
    tq = _tile(Lq, _TQ)
    kc = _tile(Lk, _KC)
    qpos3 = q_positions[:, None, :].astype(jnp.int32)
    qmax = jnp.max(qpos3.reshape(B, Lq // tq, tq), axis=-1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Lq // tq),
        in_specs=[
            pl.BlockSpec((1, 1, tq), lambda b, i, qm: (b, 0, i)),
            pl.BlockSpec((1, Hi, tq, Di), lambda b, i, qm: (b, 0, i, 0)),
            pl.BlockSpec((1, Hi, tq), lambda b, i, qm: (b, 0, i)),
            pl.BlockSpec((1, Lk, Di), lambda b, i, qm: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Lk, tq), lambda b, i, qm: (b, 0, i)),
        scratch_shapes=[pltpu.VMEM((Lk, tq), jnp.int32)],
    )
    return named_pallas_call(
        "dsa_select",
        functools.partial(_select_kernel, topk=topk, kc=kc,
                          pos_bits=max(1, (Lk - 1).bit_length())),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Lk, Lq), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 << 20,
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret_mode(),
    )(qmax, qpos3, qi.transpose(0, 2, 1, 3), w.transpose(0, 2, 1), ki)


def select(qi, ki, w, q_positions, topk: int, q_chunk: int = 512,
           kv_chunk: int = 512):
    """The selection of whole sequences: qi [B, Lq, Hi, Di] and ki [B,
    Lk, Di] in the compute dtype, w [B, Lq, Hi] float32 with the scale
    folded in, q_positions [B, Lq] (monotone a row; slot == position)
    -> [B, Lk, Lq] int8, 1 where query q keeps key slot k."""
    with jax.named_scope("attn.select"):
        if select_form() == "kernel":
            return select_kernel(qi, ki, w, q_positions, topk)
        return _select_jnp(qi, ki, w, q_positions, topk, q_chunk, kv_chunk)


def select_step(qi, ki_cache, w, positions, topk: int):
    """One new query a sequence against the cache: qi [B, Hi, Di],
    ki_cache [B, Lmax, Di], w [B, Hi] float32, positions [B] -> keep [B,
    Lmax] bool, the set ``jax.lax.top_k`` keeps (exact; the lower slot
    first on a tie; every valid slot where there are no more than
    ``topk``; never a slot past the position).  From the k-th largest
    score: the larger ones, and of the equal ones those up to the last
    slot ``top_k`` took (it takes the lowest first)."""
    with jax.named_scope("attn.select"):
        scores = index_scores(qi[:, None], ki_cache, w[:, None],
                              positions[:, None], ki_cache.shape[1])[:, 0]
        vals, idx = jax.lax.top_k(scores, min(topk, ki_cache.shape[1]))
        kth = vals[:, -1:]
        edge = jnp.max(jnp.where(vals == kth, idx, -1), axis=-1,
                       keepdims=True)
        slots = jnp.arange(scores.shape[1], dtype=idx.dtype)
        keep = (scores > kth) | ((scores == kth) & (slots[None, :] <= edge))
        return keep & (scores > -jnp.inf)
