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

Beside it, :class:`SigmoidTopKMoE` is the expert layer of the
``deepseek_v3`` block as published (sigmoid scores, a selection bias,
top-k of all experts, normalised and scaled gates, shared experts, no
capacity, no drops, no auxiliary loss), told which experts it holds.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from orion_tpu.config import ModelConfig
from orion_tpu.models.transformer import _dt


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


class MoEMLP(nn.Module):
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


def _swiglu(h):
    gate, up = jnp.split(h, 2, axis=-1)
    return nn.silu(gate) * up


def experts_dense(x, w_gate_up, w_down, local, gates):
    """Every held expert on every token, weighted.  x [T, D];
    w_gate_up [H, D, 2I]; w_down [H, I, D]; local [T, k] (expert index
    among the held ones, anything outside 0..H-1 = not held); gates
    [T, k].  Exact for any routing; its work does not follow it."""
    H = w_gate_up.shape[0]
    weight = jnp.sum(jax.nn.one_hot(local, H, dtype=jnp.float32)
                     * gates[..., None], axis=1)               # [T, H]
    with jax.named_scope("moe.experts"):
        h = _swiglu(jnp.einsum("td,hdf->thf", x, w_gate_up))
        y = jnp.einsum("thf,hfd->thd", h, w_down)
    with jax.named_scope("moe.combine"):
        return jnp.einsum("thd,th->td", y, weight.astype(y.dtype))


def experts_grouped(x, w_gate_up, w_down, local, gates):
    """Same result by a grouped matrix product over the (token, choice)
    pairs sorted by expert: static shapes (all T * k pairs have a row),
    no drops (a batch whose every pair lands on one held expert is that
    expert's group), and kernels whose work follows the rows of the
    held experts alone."""
    from orion_tpu.ops.pallas.grouped_matmul import (
        collect_rows, dispatch_rows, grouped_matmul, padded_rows)

    T, k = local.shape
    H = w_gate_up.shape[0]
    n_pairs = T * k
    with jax.named_scope("moe.dispatch"):
        held = (local >= 0) & (local < H)
        key = jnp.where(held, local, H).reshape(n_pairs)
        _, order = jax.lax.sort_key_val(
            key, jnp.arange(n_pairs, dtype=jnp.int32))
        inverse = jnp.zeros((n_pairs,), jnp.int32).at[order].set(
            jnp.arange(n_pairs, dtype=jnp.int32), unique_indices=True)
        sizes = jnp.zeros((H + 1,), jnp.int32).at[key].add(1)
        m = padded_rows(n_pairs)
        if m > n_pairs:   # padding rows join the group nobody computes
            order = jnp.pad(order, (0, m - n_pairs))
            sizes = sizes.at[H].add(m - n_pairs)
        order, inverse, sizes = (checkpoint_name(t, "moe_route")
                                 for t in (order, inverse, sizes))
        rows = dispatch_rows(x, order, inverse, k)              # [m, D]
    with jax.named_scope("moe.experts"):
        h = _swiglu(grouped_matmul(rows, w_gate_up, sizes))
        y = grouped_matmul(h, w_down, sizes)                    # [m, D]
    with jax.named_scope("moe.combine"):
        pairs = collect_rows(y, order, inverse).reshape(T, k, -1)
        weight = jnp.where(held, gates, 0.0).astype(y.dtype)
        return jnp.einsum("tkd,tk->td", pairs, weight)


class SigmoidTopKMoE(nn.Module):
    """The ``deepseek_v3`` expert layer and the chip's share of it.

    ``FFN(z) = sum_{i in top-k} w_i E_i(z) + S(z)``: E_i a SwiGLU of
    ``moe_intermediate_size``, S one SwiGLU of ``n_shared_experts``
    times that.  The router scores, selects and normalises over ALL
    ``n_routed_experts``; this layer holds the ``experts_held``
    consecutive experts from ``expert_offset`` on and adds their part
    of the sum; what the absent ones would add is left out (on one chip
    there is no exchange, and nothing stands in for one).  The selection
    bias ``e_score_correction_bias`` is a parameter that no gradient
    reaches (it enters through ``top_k`` alone) and so stays as it was
    initialised: spread a little (0.02: the largest load of an expert is
    then some 1.7 times the mean), so that it does change selections.

    Sows ``moe_load`` [experts_held] int32, the pairs computed by each
    held expert, for the trainer's counters, and ``moe_selected``
    [B, L, k], the experts each token selected (the reference check
    reads it: a selection is discrete, see
    benchmarks/reference_check_dsv3.py).

    Expert weights are stacked on the ``expert`` logical axis.  On one
    device the large-batch path is the grouped product (Pallas); under
    a mesh of several devices a Mosaic kernel cannot be partitioned
    automatically, so the layer takes its dense form there, which GSPMD
    partitions over the ``expert`` axis like the GShard layer's einsums.
    """

    cfg: ModelConfig

    @nn.compact
    def __call__(self, x, token_mask=None):
        """``token_mask`` [B, L] bool: positions that hold a token.  The
        others (the padding behind a right-padded sequence, which no
        token attends to) are routed nowhere: they all carry the same
        pad id, would all select the same experts, and would make up
        those experts' whole load.  They get the shared expert alone."""
        from orion_tpu.models.transformer import _dense
        from orion_tpu.parallel.sharding import ambient_mesh

        cfg = self.cfg
        B, L, Dm = x.shape
        E, H, k = cfg.n_routed_experts, cfg.experts_held, \
            cfg.num_experts_per_tok
        I = cfg.moe_intermediate_size
        cdt, pdt = _dt(cfg.dtype), _dt(cfg.param_dtype)
        z = x.reshape(B * L, Dm)

        def param(name, init, shape, axes, dtype=pdt):
            return self.param(
                name, nn.with_logical_partitioning(init, axes), shape, dtype)

        normal = nn.initializers.normal(stddev=0.02)
        router = param("router", normal, (Dm, E), ("embed", "norm"))
        bias = param("e_score_correction_bias",
                     nn.initializers.normal(stddev=0.02), (E,), ("norm",),
                     jnp.float32)
        w_gate_up = param("experts_gate_up_proj", normal, (H, Dm, 2 * I),
                          ("expert", "embed", "mlp"))
        w_down = param("experts_down_proj", normal, (H, I, Dm),
                       ("expert", "mlp", "embed"))

        with jax.named_scope("moe.route"):
            idx, gates = sigmoid_topk_route(
                z, router, jax.lax.stop_gradient(bias), k,
                cfg.routed_scaling_factor)
            local = idx - cfg.expert_offset
            if token_mask is not None:
                local = jnp.where(token_mask.reshape(B * L, 1), local, H)
            held = (local >= 0) & (local < H)
            self.sow("intermediates", "moe_selected", idx.reshape(B, L, k))
            self.sow("intermediates", "moe_load",
                     jnp.zeros((H + 1,), jnp.int32).at[
                         jnp.where(held, local, H).reshape(-1)].add(1)[:H])

        mesh = ambient_mesh()
        one_device = mesh is None or mesh.empty or mesh.size == 1
        experts = experts_grouped if (
            one_device and B * L > DENSE_MAX_TOKENS) else experts_dense
        routed = experts(z.astype(cdt), w_gate_up.astype(cdt),
                         w_down.astype(cdt), local, gates)

        with jax.named_scope("moe.shared"):
            S = cfg.n_shared_experts * I
            shared = 0.0
            if S:
                def pre(name):
                    return checkpoint_name(_dense(
                        S, ("embed", "mlp"), False, cfg, name)(x), "mlp_pre")

                h = nn.silu(pre("shared_gate_proj")) * pre("shared_up_proj")
                shared = _dense(Dm, ("mlp", "embed"), False, cfg,
                                "shared_down_proj")(h)
        return routed.reshape(B, L, Dm).astype(cdt) + shared
