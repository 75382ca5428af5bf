"""Mixture-of-Experts layer with expert parallelism (SURVEY.md §2
parallelism table, row EP: "mesh expert axis + ragged all-to-all;
lowest priority").

TPU-native design — the GShard/Switch formulation rather than a CUDA
grouped-GEMM: routing becomes dense one-hot dispatch/combine einsums
over a fixed per-expert capacity, which XLA tiles onto the MXU and,
with the expert-stacked parameters sharded over the mesh's ``expert``
axis, lowers the dispatch/combine contractions into the all-to-all /
reduce pattern over ICI.  Static shapes throughout (capacity bounds the
ragged assignment; overflow tokens fall through on the residual path) —
the same trade the rollout engine makes with paged KV.

No SPEC config uses MoE (BASELINE.json); this exists to make the EP row
of the parallelism table first-class, as the task demands.

Beside it, :class:`TopKMoE` is the expert layer of the ``deepseek_v3``
block as published (sigmoid scores, a selection bias, top-k of all
experts, normalised and scaled gates, shared experts, no capacity, no
drops, no auxiliary loss), with ``moe_scoring="softmax"`` of the
Qwen3-MoE family's (a float32 softmax over all experts, no bias, no
shared expert) and, with ``moe_activation="relu2"`` and
``moe_latent_size``, of ``nemotron_h``'s (experts without a gate that
work in a latent between two projections), told which experts it holds:
its rows, products and gradients follow the (token, choice) pairs
routed to those, a static block of them at a time.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from orion_tpu.config import ModelConfig
from orion_tpu.models.transformer import Kind, _dt


def top2_routing(router_logits: jnp.ndarray, n_experts: int,
                 capacity: int):
    """GShard top-2 routing with capacity.

    router_logits: [T, E] f32.  Returns (dispatch [T, E, C] bool-ish
    f32, combine [T, E, C] f32, aux_loss scalar).  Gates of the chosen
    two experts are renormalized to sum to 1; tokens overflowing an
    expert's capacity are dropped (their combine weights are 0 — the
    caller's residual connection carries them unchanged).
    """
    T, E = router_logits.shape
    probs = jax.nn.softmax(router_logits, axis=-1)           # [T, E]

    g1 = jnp.max(probs, axis=-1)
    e1 = jnp.argmax(probs, axis=-1)
    probs_wo1 = probs * (1.0 - jax.nn.one_hot(e1, E))
    g2 = jnp.max(probs_wo1, axis=-1)
    e2 = jnp.argmax(probs_wo1, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    oh1 = jax.nn.one_hot(e1, E)                              # [T, E]
    oh2 = jax.nn.one_hot(e2, E)
    # position of each token within its expert's queue (choice-1 tokens
    # first — they carry the larger gate, so they win capacity).
    pos1 = jnp.cumsum(oh1, axis=0) * oh1 - oh1               # [T, E]
    n1 = jnp.sum(oh1, axis=0, keepdims=True)                 # [1, E]
    pos2 = (jnp.cumsum(oh2, axis=0) - oh2 + n1) * oh2
    keep1 = oh1 * (pos1 < capacity)
    keep2 = oh2 * (pos2 < capacity)

    d1 = keep1[:, :, None] * jax.nn.one_hot(
        pos1.astype(jnp.int32), capacity)                    # [T, E, C]
    d2 = keep2[:, :, None] * jax.nn.one_hot(
        pos2.astype(jnp.int32), capacity)
    dispatch = d1 + d2
    combine = d1 * g1[:, None, None] + d2 * g2[:, None, None]

    # Load-balance auxiliary loss (Switch eq. 4): fraction of tokens
    # routed (top-1) x mean router prob, summed over experts, scaled E.
    frac = jnp.mean(oh1, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = jnp.sum(frac * mean_prob) * E
    return dispatch, combine, aux


class MoEMLP(nn.Module, Kind):
    """Expert-parallel SwiGLU MLP (drop-in for the dense MLP inside a
    Block when ``cfg.num_experts > 0``).

    Expert params are stacked [E, ...] with logical axis "expert" —
    LOGICAL_RULES maps it to the mesh's ``expert`` axis, so each device
    holds E/ep experts and the dispatch/combine einsums become the EP
    collectives.  The router stays replicated (tiny).
    """

    cfg: ModelConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, L, Dm = x.shape
        E = cfg.num_experts
        T = B * L
        cap = max(1, int(cfg.expert_capacity_factor * 2 * T / E))
        xt = x.reshape(T, Dm)

        router = nn.Dense(
            E, use_bias=False, dtype=jnp.float32,
            param_dtype=_dt(cfg.param_dtype),
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("embed", "norm")),
            name="router")
        logits = router(xt.astype(jnp.float32))               # [T, E]
        dispatch, combine, aux = top2_routing(logits, E, cap)
        self.sow("intermediates", "moe_aux_loss", aux)

        cdt = _dt(cfg.dtype)
        expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(cdt),
                               xt.astype(cdt))                # [E, C, Dm]

        def stacked(name, shape, axes):
            return self.param(
                name,
                nn.with_logical_partitioning(
                    nn.initializers.normal(stddev=0.02), axes),
                shape, _dt(cfg.param_dtype))

        I = cfg.intermediate_size
        wg = stacked("gate_proj", (E, Dm, I), ("expert", "embed", "mlp"))
        wu = stacked("up_proj", (E, Dm, I), ("expert", "embed", "mlp"))
        wd = stacked("down_proj", (E, I, Dm), ("expert", "mlp", "embed"))
        h = nn.silu(jnp.einsum("ecd,edf->ecf", expert_in,
                               wg.astype(cdt))) * \
            jnp.einsum("ecd,edf->ecf", expert_in, wu.astype(cdt))
        y = jnp.einsum("ecf,efd->ecd", h, wd.astype(cdt))     # [E, C, Dm]

        out = jnp.einsum("tec,ecd->td", combine.astype(cdt), y)
        return out.reshape(B, L, Dm)


# ---------------------------------------------------------------------------
# The deepseek_v3 expert layer: dropless, top-k of sigmoid scores
# ---------------------------------------------------------------------------

# Up to this many tokens a step (decode), every held expert is computed
# for every token and weighted by its gate (zero where not selected):
# the step reads each expert's weights once either way, and the sort
# and the kernels' fixed costs are then the larger part.  Read on a v5e
# at the two sizes ppo-kanana-ep8-sync runs (PERF.md section 6, PR 28):
# 32 tokens a decode step, 512 steps: rollout 1591 ms dense, 1694 ms
# grouped; 16 384 tokens a training step: the dense form would compute
# 262 144 rows a layer where the grouped product computes ~29 000.  The
# crossing between the two was not looked for.
DENSE_MAX_TOKENS = 256

# Such a small step takes the kernel ops/pallas/experts_step.py, which
# reads only the stacks some row selected, where (1) the share of them
# it expects to need, 1 - (1 - k / E) ** tokens for top k of E experts,
# is under STEP_MAX_READ_SHARE: what it skips is gained (a v5e, PERF.md
# section 6, PR 54: ppo-keye-dsa-ep8-sync at 0.40 +5.0% samples/s, and
# the cells nearest the bound still win, ppo-nemotron-h-tp4-sync at 0.75
# +7.5%, ppo-kanana-ep8-sync at 0.78 +2.9%; over 0.9 a tenth at most is
# left to skip), or (2) it has STEP_KERNEL_MAX_ROWS rows at most: up to
# there the kernel moves what it reads faster than the einsum form,
# every stack hit (the loop alone, us a layer, kernel | einsum: 8 stacks
# of 22 MB at 64 rows 240.1 | 259.9, at 128 rows 208.5 | 145.4), and in
# ppo-lfm2-ep4-sync (64 rows of top-4 of 32: 0.9998) 235.5 us a layer,
# rollout 2922 -> 2741 ms, +2.8% samples/s (PERF.md section 6, PR 57).
STEP_MAX_READ_SHARE = 0.9
STEP_KERNEL_MAX_ROWS = 64


def step_read_share(n_tokens: int, k: int, n_experts: int) -> float:
    """The share of a chip's held stacks that a step of ``n_tokens``
    rows needs if each row selects ``k`` of ``n_experts`` at random: a
    held expert is selected by none with ``(1 - k / E) ** n_tokens``."""
    return 1.0 - (1.0 - k / n_experts) ** n_tokens


def step_form(n_tokens: int, k: int, n_experts: int, width: int) -> str:
    """The form a step of ``n_tokens`` tokens takes through the held
    experts of ``width``, from what the step can see: ``kernel``
    (``experts_step``: the hit experts' stacks alone) where it is small
    (:data:`DENSE_MAX_TOKENS`) and either expects to need less than
    :data:`STEP_MAX_READ_SHARE` of the stacks or has at most
    :data:`STEP_KERNEL_MAX_ROWS` rows, the width is whole lanes (the
    kernel's tiles are) and the trace is for one TPU device; ``""``
    elsewhere (the CPU, a mesh of several devices, where GSPMD
    partitions the einsums, more rows than that which select about every
    held expert between them, a tiny model): ``experts_dense``, or the
    grouped form where :func:`block_rows` says so."""
    from orion_tpu.ops.indexer import select_form

    return "kernel" if (
        n_tokens <= DENSE_MAX_TOKENS and width % 128 == 0
        and (n_tokens <= STEP_KERNEL_MAX_ROWS
             or step_read_share(n_tokens, k, n_experts) < STEP_MAX_READ_SHARE)
        and select_form() == "kernel") else ""


def sigmoid_topk_route(z, router_kernel, bias, k: int, scale: float):
    """The published router.  z [T, D], router_kernel [D, E], bias [E]
    -> (idx [T, k] int32 over ALL E experts, gates [T, k] f32).

    Scores are sigmoids in float32 (the product at the highest matmul
    precision: the selection is discrete, so the only rounding left in
    it is that of ``z`` itself); the k largest of ``score + bias`` are
    selected; a gate is ``scale * score / (sum of the k selected scores
    + 1e-20)`` — the bias takes part in the selection only."""
    logits = jnp.dot(z.astype(jnp.float32),
                     router_kernel.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = checkpoint_name(jax.nn.sigmoid(logits), "moe_route")
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32)[None, :], k)
    idx = checkpoint_name(idx, "moe_route")
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gates = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                              + 1e-20)
    return idx.astype(jnp.int32), gates


def softmax_topk_route(z, router_kernel, k: int, scale: float):
    """The Qwen3-MoE router (``norm_topk_prob`` true).  z [T, D],
    router_kernel [D, E] -> (idx [T, k] int32 over ALL E experts, gates
    [T, k] f32): a float32 softmax over all experts (the product at the
    highest precision, as :func:`sigmoid_topk_route`'s), its k largest,
    a gate ``scale * p / (sum of the k selected)``.  No bias."""
    logits = jnp.dot(z.astype(jnp.float32),
                     router_kernel.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = checkpoint_name(jax.nn.softmax(logits, axis=-1), "moe_route")
    _, idx = jax.lax.top_k(probs, k)
    idx = checkpoint_name(idx, "moe_route")
    chosen = jnp.take_along_axis(probs, idx, axis=-1)
    gates = scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), gates


def _swiglu(h):
    gate, up = jnp.split(h, 2, axis=-1)
    return nn.silu(gate) * up


def _relu2(h):
    return jnp.square(nn.relu(h))


#: ``cfg.moe_activation`` -> (what an expert does to its first product
#: [.., F], the columns F of that product for a width of I): a SwiGLU
#: splits a fused gate|up product, relu(.)^2 has no gate.
ACTIVATIONS = {"swiglu": (_swiglu, 2), "relu2": (_relu2, 1)}


def shared_width(cfg: ModelConfig) -> int:
    """The shared expert's width: ``n_shared_experts`` times its own
    ``moe_shared_expert_intermediate_size`` where that is given, times
    the routed experts' ``moe_intermediate_size`` where not."""
    return cfg.n_shared_experts * (cfg.moe_shared_expert_intermediate_size
                                   or cfg.moe_intermediate_size)


def dense_weight(local, gates, n_held: int):
    """[T, H] float32: each token's gate for each held expert, 0 where
    it did not select it (a token that holds an expert more than once
    gets the sum)."""
    return jnp.sum(jax.nn.one_hot(local, n_held, dtype=jnp.float32)
                   * gates[..., None], axis=1)


def experts_dense(x, w_up, w_down, local, gates, act: str = "swiglu"):
    """Every held expert on every token, weighted.  x [T, D];
    w_up [H, D, F] (F: ``ACTIVATIONS[act]``); w_down [H, I, D]; local
    [T, k] (expert index among the held ones, anything outside 0..H-1 =
    not held); gates [T, k].  Exact for any routing; its work does not
    follow it."""
    weight = dense_weight(local, gates, w_up.shape[0])
    with jax.named_scope("moe.experts"):
        h = ACTIVATIONS[act][0](jnp.einsum("td,hdf->thf", x, w_up))
        y = jnp.einsum("thf,hfd->thd", h, w_down)
    with jax.named_scope("moe.combine"):
        return jnp.einsum("thd,th->td", y, weight.astype(y.dtype))


# The rows of one block of the grouped form, as a multiple of what an
# even routing of UNMASKED tokens sends to the held experts
# (T k held / all).  Sized for full-length prompts, not for the padded
# ones the cells send (padding is routed nowhere, so those fill a
# quarter of it): at 1 an unmasked batch runs a second block in every
# other layer call, and a further block costs 11 ms forward + backward
# where a block of twice the rows costs 1.6 ms more to combine.  Read on
# a v5e in ppo-kanana-ep8-sync (PERF.md section 6, PR 35): 9.53
# samples/s at 2, 9.95 at 1 (block 0 outside the loop then: 9.66 at 2),
# 8.65 with a row for every pair.
BLOCK_ROWS_OVER_EVEN = 2


def block_rows(cfg: ModelConfig, n_tokens: int) -> int:
    """The (token, choice) pair rows that one block of the grouped form
    moves and multiplies for a step of ``n_tokens`` tokens, from shapes
    alone: the even share of the held experts times
    :data:`BLOCK_ROWS_OVER_EVEN`, in whole row tiles, at least one and
    at most all pairs (a layer that holds every expert works on all
    pairs at once).  0 where the layer takes its dense form: a small
    step, or a mesh of several devices (a Mosaic kernel cannot be
    partitioned automatically)."""
    from orion_tpu.ops.pallas.grouped_matmul import padded_rows, row_tile
    from orion_tpu.parallel.sharding import ambient_mesh

    mesh = ambient_mesh()
    one_device = mesh is None or mesh.empty or mesh.size == 1
    if not one_device or n_tokens <= DENSE_MAX_TOKENS:
        return 0
    n_pairs = n_tokens * cfg.num_experts_per_tok
    tile = row_tile(n_pairs)
    even = n_pairs * cfg.experts_held * BLOCK_ROWS_OVER_EVEN
    tiles = max(1, -(-even // (cfg.n_routed_experts * tile)))
    return min(tiles * tile, padded_rows(n_pairs))


def experts_grouped(x, w_up, w_down, local, gates, block: int,
                    act: str = "swiglu"):
    """Same result by a grouped matrix product over the (token, choice)
    pairs sorted by expert, the held experts' pairs first.  Only those
    get rows: the sorted pairs are worked on ``block`` rows at a time
    (static: :func:`block_rows`), block 0 always and further
    blocks while held pairs are left (one loop of static length), so a batch whose every pair lands
    on one held expert takes ``T k / block`` blocks and is computed
    exactly (no capacity, no drops), and a batch routed like the even
    share takes one.  Rows are gathered, multiplied, activated and
    placed into their tokens' rows of a float32 sum, a tile of tokens at
    a time (ops/pallas/moe_combine.py); the kernels' work follows the
    rows of the held experts inside a block."""
    T, k = local.shape
    H = w_up.shape[0]
    n_pairs = T * k
    with jax.named_scope("moe.dispatch"):
        held = (local >= 0) & (local < H)
        key = jnp.where(held, local, H).reshape(n_pairs)
        # The combine kernel's contract (ops/pallas/moe_combine.py): this
        # sort is STABLE and a token selects an expert at most once, so
        # inside one held expert's run the tokens ascend and are distinct
        # and a tile of tokens owns ONE contiguous range of the run.  The
        # kernel places that range by a 0/1 product and sums in float32:
        # it is wrong the day this sort is not stable.
        _, order = jax.lax.sort_key_val(
            key, jnp.arange(n_pairs, dtype=jnp.int32))
        sizes = jnp.zeros((H + 1,), jnp.int32).at[key].add(1)
        # whole blocks: the padding lies behind every pair, in the group
        # nobody computes, and points at pair 0
        order = jnp.pad(order, (0, -n_pairs % block))
        order, sizes = (checkpoint_name(t, "moe_route")
                        for t in (order, sizes))
    return _routed(x, w_up, w_down, gates, local, order, sizes, block, act)


def _block(b, block: int, gates, order, sizes):
    """Block ``b`` of the sorted pairs: (pair [block], token [block],
    gate [block, 1], group sizes [H + 1] of the block's rows: what of
    each held expert's run lies inside it, the rest in the group nobody
    computes)."""
    pair = jax.lax.dynamic_slice_in_dim(order, b * block, block)
    ends = jnp.clip(jnp.cumsum(sizes[:-1]) - b * block, 0, block)
    sizes_b = jnp.concatenate([jnp.diff(ends, prepend=0), block - ends[-1:]])
    gate = jnp.take(gates.reshape(-1), pair)[:, None]
    return pair, pair // gates.shape[1], gate, sizes_b


def _over_blocks(body, carry, block: int, order, sizes):
    """``body(b, carry)`` for block 0, then for blocks 1, 2, ... while
    ``b * block`` is less than the held pairs.  ONE loop over all the
    blocks the sorted pairs make, of static length, whose trips past the
    held pairs do nothing: every block, the first too, is the same call
    site of the same kernels, and what follows the routing is a branch,
    not a trip count (on the chip a ``while_loop`` whose trip count
    followed the routing did not end in a small program of a process
    that had trained: PERF.md section 6, PR 35)."""
    n_blocks = order.shape[0] // block
    if n_blocks == 1:
        return body(0, carry)
    n_held = jnp.sum(sizes[:-1])

    def step(b, carry):
        return jax.lax.cond((b == 0) | (b * block < n_held),
                            lambda c: body(b, c), lambda c: c, carry)

    return jax.lax.fori_loop(0, n_blocks, step, carry)


def _gate_of(local, gates, n_held: int):
    """[T, H] float32: the gate of each token for each held expert, 0
    where it did not select it.  A token that holds an expert more than
    once (no router's top-k does; a test's routing may) gets the MEAN of
    those gates: its rows of that expert are equal and the combine
    places their sum."""
    hit = local[:, :, None] == jnp.arange(n_held, dtype=local.dtype)
    total = jnp.sum(jnp.where(hit, gates[:, :, None], 0.0), axis=1)
    return total / jnp.maximum(jnp.sum(hit, axis=1), 1)


def _token_ranges(local, n_held: int, width: int):
    """:func:`moe_combine.ranges` over the tiles of tokens that the
    combine of a sum ``width`` wide works on."""
    from orion_tpu.ops.pallas import moe_combine as mc

    return mc.ranges(local, n_held, mc.token_tile(local.shape[0], width))


def combine_work(local, n_held: int, width: int, block: int):
    """[2] int32: the work items that place rows, over all the blocks
    of one layer call, and the held rows they place (the layer's
    counters: an item is a product over 128 rows, filled rows / (items x
    128))."""
    from orion_tpu.ops.pallas import moe_combine as mc

    starts, ends = _token_ranges(local, n_held, width)
    blocks = jnp.arange(-(-local.size // block))[:, None, None]
    lo, hi, chunks = mc.range_chunks(starts, ends, blocks, block)
    return jnp.stack([jnp.sum(chunks), jnp.sum(hi - lo)]).astype(jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _routed(x, w_up, w_down, gates, local, order, sizes, block: int,
            act: str):
    """The held experts' part of the layer's sum.  x [T, D]; gates,
    local [T, k]; order [whole blocks] the pairs sorted by expert (pair
    ``p`` is token ``p // k``); sizes [H + 1] the pairs of each held
    expert and of all others -> [T, D] in x.dtype, summed in float32."""
    from orion_tpu.ops.pallas.grouped_matmul import gmm
    from orion_tpu.ops.pallas.moe_combine import moe_combine, work_items

    n_held = w_up.shape[0]
    with jax.named_scope("moe.dispatch"):
        spans = _token_ranges(local, n_held, x.shape[1])
        gate_of = _gate_of(local, gates, n_held)

    def add_block(b, out):
        _, tok, _, sizes_b = _block(b, block, gates, order, sizes)
        with jax.named_scope("moe.experts"):
            rows = jnp.take(x, tok, axis=0)
            h = ACTIVATIONS[act][0](gmm(rows, w_up, sizes_b))
            y = gmm(h, w_down, sizes_b)             # zero where not held
        with jax.named_scope("moe.combine"):
            items, _ = work_items(*spans, b, block)
            return moe_combine(out, y, tok, items, gate_of)

    out = _over_blocks(add_block, jnp.zeros(x.shape, jnp.float32), block,
                       order, sizes)
    return out.astype(x.dtype)


def _routed_fwd(x, w_up, w_down, gates, local, order, sizes, block, act):
    # what is kept is what came in: the backward rebuilds a block's rows
    # and its activation (one more product on the held rows)
    return (_routed(x, w_up, w_down, gates, local, order, sizes, block, act),
            (x, w_up, w_down, gates, local, order, sizes))


def _routed_bwd(block, act, res, g):
    from orion_tpu.ops.pallas.grouped_matmul import gmm, gmm_dlhs, tgmm
    from orion_tpu.ops.pallas.moe_combine import moe_combine, work_items

    x, w_up, w_down, gates, local, order, sizes = res
    f32 = jnp.float32
    with jax.named_scope("moe.dispatch"):
        spans = _token_ranges(local, w_up.shape[0], x.shape[1])

    def add_block(b, carry):
        d_x, d_up, d_down, d_gate_of = carry
        pair, tok, gate, sizes_b = _block(b, block, gates, order, sizes)
        with jax.named_scope("moe.experts"):
            rows = jnp.take(x, tok, axis=0)
            h, act_vjp = jax.vjp(ACTIVATIONS[act][0],
                                 gmm(rows, w_up, sizes_b))
            g_rows = jnp.take(g, tok, axis=0)
            # y enters the sum times its gate: the gate's gradient is
            # <g, y> = <g w_down^T, h>, and y itself is not rebuilt
            d_h = gmm_dlhs(g_rows, w_down, sizes_b).astype(f32)
            d_gate = jnp.sum(d_h * h.astype(f32), axis=-1)
            d_down = d_down + tgmm(
                (h.astype(f32) * gate).astype(h.dtype), g_rows, sizes_b, f32)
            (d_pre,) = act_vjp((d_h * gate).astype(h.dtype))
            d_up = d_up + tgmm(rows, d_pre, sizes_b, f32)
            d_rows = gmm_dlhs(d_pre, w_up, sizes_b)
        with jax.named_scope("moe.dispatch"):
            # the rows carry their gate already: placed as they are
            items, _ = work_items(*spans, b, block)
            return (moe_combine(d_x, d_rows, tok, items), d_up, d_down,
                    d_gate_of.at[pair].add(d_gate))

    primals = (x, w_up, w_down, gates.reshape(-1))
    grads = _over_blocks(add_block,
                         tuple(jnp.zeros(t.shape, f32) for t in primals),
                         block, order, sizes)
    d_x, d_up, d_down, d_gate_of = (
        d.astype(t.dtype) for d, t in zip(grads, primals))
    return (d_x, d_up, d_down, d_gate_of.reshape(gates.shape), None, None,
            None)


_routed.defvjp(_routed_fwd, _routed_bwd)


class TopKMoE(nn.Module, Kind):
    """The dropless expert layer and the chip's share of it
    (``deepseek_v3``'s as published; ``cfg.moe_scoring`` says how the
    router scores: :func:`sigmoid_topk_route` with its selection bias,
    or :func:`softmax_topk_route`, which has none).

    ``FFN(z) = W_2 (sum_{i in top-k} w_i E_i(W_1 z)) + S(z)``.  An
    expert E_i is what ``cfg.moe_activation`` says (:data:`ACTIVATIONS`):
    a SwiGLU of ``moe_intermediate_size`` (``swiglu``: ``W_down(silu(W_gate
    v) * W_up v)``, gate and up one fused product, deepseek_v3's and the
    Qwen3-MoE family's) or ``W_down relu(W_up v)^2`` without a gate
    (``relu2``, nemotron_h's).  With ``moe_latent_size`` the routed
    experts work in a latent of that width: ``fc1_latent_proj`` ``W_1``
    before the dispatch and ``fc2_latent_proj`` ``W_2`` behind the
    combine, which every chip holds whole (where it is 0 there is
    neither, and E_i reads ``z``); the router and the shared expert read
    ``z`` itself.  S is ONE shared expert of the same activation, of
    :func:`shared_width` (absent where that is 0).  The router scores,
    selects and normalises over ALL
    ``n_routed_experts``; this layer holds the ``experts_held``
    consecutive experts from ``expert_offset`` on and adds their part
    of the sum; what the absent ones would add is left out (on one chip
    there is no exchange, and nothing stands in for one).  The selection
    bias ``e_score_correction_bias`` is a parameter that no gradient
    reaches (it enters through ``top_k`` alone) and so stays as it was
    initialised: spread a little (0.02: the largest load of an expert is
    then some 1.7 times the mean), so that it does change selections.

    Sows ``moe_load`` [experts_held] int32, the pairs computed by each
    held expert, and in the grouped form ``moe_combine`` [2]
    (:func:`combine_work`), for the trainer's counters, and ``moe_selected``
    [B, L, k], the experts each token selected (the reference check
    reads it: a selection is discrete, see
    benchmarks/reference_check_dsv3.py), and where a small step takes
    the kernel (:func:`step_form`) ``moe_step_read``, the held experts
    whose stacks it read (the rollout's counter).

    Expert weights are stacked on the ``expert`` logical axis.  On one
    device the large-batch path is the grouped product (Pallas) over
    blocks of the held pairs (:func:`block_rows`: a share that holds an
    eighth of the experts moves a quarter of the pair rows, twice its
    even share, and more only when the routing sends it more), and a
    small step that leaves held experts unselected, or has 64 rows at
    most, reads the selected ones' stacks alone (:func:`step_form`); under a
    mesh of several devices a Mosaic kernel cannot be partitioned
    automatically, so the layer takes its dense form there, which GSPMD
    partitions over the ``expert`` axis like the GShard layer's einsums.
    """

    cfg: ModelConfig

    takes_token_mask = True

    @staticmethod
    def lacks(cfg):
        return {"quantize_weights":
                "there are no int8 expert stacks (ops/quant.py quantises "
                "Dense kernels" + (
                    ", and was not run on experts without a gate)"
                    if cfg.moe_activation == "relu2" else ")")}

    @staticmethod
    def tag_bytes(cfg, rows, seq_len, w):
        """``mlp_pre``: the shared expert's first product; ``moe_route``:
        scores [n, E] float32 and the selection [n, k] (the gather of the
        selected scores keeps its own indices); both forms read the
        selection again (the dense one for its weights, the grouped
        one's backward for the ranges its combine places), and the
        grouped form's backward reads order [n k, in whole blocks] and
        sizes [held + 1] besides."""
        n, k = rows * seq_len, cfg.num_experts_per_tok
        route = n * (w(cfg.n_routed_experts) + 2 * w(k))
        block = block_rows(cfg, n)
        if block:
            route += w(-(-n * k // block) * block) + w(cfg.experts_held + 1)
        return {"mlp_pre": n * ACTIVATIONS[cfg.moe_activation][1]
                * w(shared_width(cfg)) * jnp.dtype(cfg.dtype).itemsize,
                "moe_route": 4 * route}

    @nn.compact
    def __call__(self, x, token_mask=None):
        """``token_mask`` [B, L] bool: positions that hold a token.  The
        others (the padding behind a right-padded sequence, which no
        token attends to) are routed nowhere: they all carry the same
        pad id, would all select the same experts, and would make up
        those experts' whole load.  They get the shared expert alone."""
        from orion_tpu.models.transformer import _dense

        cfg = self.cfg
        B, L, Dm = x.shape
        E, H, k = cfg.n_routed_experts, cfg.experts_held, \
            cfg.num_experts_per_tok
        I, Dl = cfg.moe_intermediate_size, cfg.moe_latent_size or Dm
        act, act_fn = cfg.moe_activation, ACTIVATIONS[cfg.moe_activation][0]
        cdt, pdt = _dt(cfg.dtype), _dt(cfg.param_dtype)
        z = x.reshape(B * L, Dm)

        def param(name, init, shape, axes, dtype=pdt):
            return self.param(
                name, nn.with_logical_partitioning(init, axes), shape, dtype)

        normal = nn.initializers.normal(stddev=0.02)
        router = param("router", normal, (Dm, E), ("embed", "norm"))
        sigmoid = cfg.moe_scoring == "sigmoid"
        if sigmoid:
            bias = param("e_score_correction_bias",
                         nn.initializers.normal(stddev=0.02), (E,),
                         ("norm",), jnp.float32)
        # the first product's name says what it holds
        w_up = param(
            "experts_gate_up_proj" if act == "swiglu" else "experts_up_proj",
            normal, (H, Dl, ACTIVATIONS[act][1] * I),
            ("expert", "embed", "mlp"))
        w_down = param("experts_down_proj", normal, (H, I, Dl),
                       ("expert", "mlp", "embed"))

        with jax.named_scope("moe.route"):
            if sigmoid:
                idx, gates = sigmoid_topk_route(
                    z, router, jax.lax.stop_gradient(bias), k,
                    cfg.routed_scaling_factor)
            else:
                idx, gates = softmax_topk_route(
                    z, router, k, cfg.routed_scaling_factor)
            local = idx - cfg.expert_offset
            if token_mask is not None:
                local = jnp.where(token_mask.reshape(B * L, 1), local, H)
            held = (local >= 0) & (local < H)
            self.sow("intermediates", "moe_selected", idx.reshape(B, L, k))
            self.sow("intermediates", "moe_load",
                     jnp.zeros((H + 1,), jnp.int32).at[
                         jnp.where(held, local, H).reshape(-1)].add(1)[:H])

        z_in = z
        if cfg.moe_latent_size:
            with jax.named_scope("moe_latent"):
                z_in = _dense(Dl, ("embed", "latent"), False, cfg,
                              "fc1_latent_proj")(z)
        block = block_rows(cfg, B * L)
        if block:
            self.sow("intermediates", "moe_combine",
                     combine_work(local, H, Dl, block))
        operands = (z_in.astype(cdt), w_up.astype(cdt), w_down.astype(cdt),
                    local, gates)
        if block:
            routed = experts_grouped(*operands, block, act)
        elif step_form(B * L, k, E, I):
            from orion_tpu.ops.pallas.experts_step import experts_step, hits

            self.sow("intermediates", "moe_step_read", hits(local, H)[1][0])
            routed = experts_step(*operands, act)
        else:
            routed = experts_dense(*operands, act)
        if cfg.moe_latent_size:
            with jax.named_scope("moe_latent"):
                routed = _dense(Dm, ("latent", "embed"), False, cfg,
                                "fc2_latent_proj")(routed.astype(cdt))

        with jax.named_scope("moe.shared"):
            S = shared_width(cfg)
            shared = 0.0
            if S:
                def pre(name):
                    return checkpoint_name(_dense(
                        S, ("embed", "mlp"), False, cfg, name)(x), "mlp_pre")

                h = nn.silu(pre("shared_gate_proj")) * pre("shared_up_proj") \
                    if act == "swiglu" else act_fn(pre("shared_up_proj"))
                shared = _dense(Dm, ("mlp", "embed"), False, cfg,
                                "shared_down_proj")(h)
        return routed.reshape(B, L, Dm).astype(cdt) + shared
