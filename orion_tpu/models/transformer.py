"""The decoder-only transformer (policy / reference / critic / RM backbone).

A model is what ``ModelConfig.layer_kinds()`` says: one ``(mixer, ffn)``
pair per block, every block a :class:`Block`.  What follows from a kind
is stated ONCE, by the module that implements it (:class:`Kind`), and
read off :data:`MIXERS` and :func:`ffn_class` by everything else: a
mixer's cache entry and whether it is indexed by position, a kind's
share of :func:`remat_tag_bytes`, whether it takes ``token_mask``, what
it reports on the trainer's spans (:func:`decode_attrs`,
:func:`update_attrs`), which parameters RL holds fixed and the forms it
cannot run (:func:`cannot_run`).  The mixers: :class:`Attention`,
:class:`WindowAttention`, :class:`SparseAttention`, :class:`LatentAttention`,
:class:`KimiDeltaAttention`, :class:`GatedDeltaNet`, :class:`Mamba2`,
:class:`ShortConv`;
the feed-forward halves: :class:`MLP`, ``ops.moe.MoEMLP`` (GShard,
``num_experts``), ``ops.moe.TopKMoE`` (the dropless expert layer).
:func:`mixer_spec` is the one place where an arch picks anything; where
the norms sit follows from the configuration (:class:`Block`).  Under
``scan_layers`` each stretch of equal consecutive kinds is one scanned
stack (``ModelConfig.layer_runs``); a latent-attention model's leading
dense layers stay outside the stacks.

Design notes (TPU-first):
- Params are annotated with *logical* axes via flax logical
  partitioning; the mesh rules in ``orion_tpu.parallel.sharding`` turn
  them into NamedShardings (FSDP on ``embed``, tensor-parallel on
  ``heads``/``mlp``/``vocab``).  XLA emits all ICI collectives.
- The KV cache is a *functional* argument (list of per-layer {k, v}
  arrays) rather than a flax mutable collection, so the decode step
  nests cleanly inside ``lax.while_loop`` in the rollout engine.
- Compute dtype bf16, params f32, softmax/logits/logprobs f32.
- ``remat=True`` wraps each block in ``jax.checkpoint``: recompute in
  the backward what does not fit.  Kept is the block's input and, of
  the tensors a block tags (:data:`REMAT_TAGS`: the outputs of matrix
  products, of the attention kernel and of the expert layer's sort),
  those the caller names in ``remat_keep``, which a trainer chooses
  from the device's free memory (:func:`remat_keep`).  With none named,
  as for every caller that gives no budget, the block's input alone.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, List, Optional

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from orion_tpu.config import ModelConfig
from orion_tpu.ops.attention import (_NEG_INF, attention, positional_mask,
                                     step_attention, streams_attention)
from orion_tpu.ops.paged_kv import is_paged, write_paged_tokens
from orion_tpu.ops.pallas import dense_step
from orion_tpu.ops.rotary import apply_rotary

# Unrolled models: a per-layer list of cache entries, each what its
# layer's mixer states (``<Mixer>.cache_entry``; {} for a block without
# a mixer).  scan_layers models: ONE stacked entry scanned over axis 0
# where the model is one stack and nothing beside it (likewise for the
# paged-cache pytrees); {"dense": [per layer], "layers": stacked} where
# leading dense layers stand beside one stack; {"dense": [...], "runs":
# [stacked, one per ModelConfig.layer_runs stretch]} for several.  A
# stack run total_ut_steps times over: an entry for every (pass, layer),
# a leading pass axis on every leaf, of the list's entries and of the
# stacked pytrees alike (init_cache).
KVCache = Any

_dt = lambda s: jnp.dtype(s)  # noqa: E731

#: What a block tags (``jax.ad_checkpoint.checkpoint_name``) for its
#: checkpoint to keep, in the order it is kept: milliseconds of
#: recomputation saved per byte held, by the products' operations over
#: the tensors' bytes at the widths of the two models with a chip
#: record (PERF.md section 6, PR 31).  ``moe_route``: the router's
#: scores and selection and the sort of the pairs (ops/moe.py);
#: ``attn_resid``: ``x + attn`` of a sequential block, which the MLP's
#: input is rebuilt from (the output projection is not run again);
#: ``mlp_pre``: the MLP's up (gate / up) projection, the shared
#: experts' too; ``attn_out`` and ``attn_qkv``: what the flash kernel
#: gives and what it takes (ops/pallas/flash_attention.py: its backward
#: kernels read both as they lie).
REMAT_TAGS = ("moe_route", "attn_resid", "mlp_pre", "attn_out", "attn_qkv")


class Kind:
    """What follows from a layer's kind, stated by the module that
    implements it (a mixer of :data:`MIXERS`, a feed-forward class of
    :func:`ffn_class`) and read by everything else.  The defaults: a
    kind that keeps nothing, reports nothing and runs everywhere."""

    #: a mixer's cache entry is indexed by position ("cache") or is a
    #: per-sequence "state", read and written whole a step
    cache_kind = "cache"
    index_leaves = ()          # the entry's leaves that are an indexer's keys
    takes_token_mask = False   # __call__ takes it behind its other arguments
    per_head_kv = False        # a K/V cache per key head (attn_heads_a_step)
    steps_over_prefix = False  # the one-token step goes through prefix_step
    rl_fixed = ()              # prefixes of the parameter names RL holds fixed

    @staticmethod
    def lacks(cfg) -> dict:
        """{form: why} for the forms it has none of: RolloutConfig's
        ``paged``, ``quantize_kv``, ``quantize_weights``; ``continuous``
        (that engine); ``sequence_parallel`` (ring / ulysses)."""
        return {}

    @staticmethod
    def tag_bytes(cfg, rows: int, seq_len: int, w) -> dict:
        """{tag of :data:`REMAT_TAGS`: bytes one layer holds under it}
        for ``rows`` sequences of ``seq_len``; ``w`` pads a last
        dimension to where it is held."""
        return {}

    @staticmethod
    def decode_attrs(cfg, lens, slots: int, new_tokens: int) -> dict:
        """Its part of :func:`decode_attrs`: host numbers."""
        return {}

    @staticmethod
    def forward_attrs(cfg, total_lens) -> dict:
        """Its part of :func:`update_attrs`: host numbers."""
        return {}


def remat_tag_bytes(cfg: ModelConfig, rows: int, seq_len: int,
                    lane: int = 1):
    """((tag, bytes), ...) in :data:`REMAT_TAGS` order, for the tags
    this model's blocks have: what keeping a tag holds over all layer
    visits for one minibatch of ``rows`` sequences of ``seq_len``, from
    the shapes alone (each kind's ``tag_bytes``) and the loops that hold
    them: a stack run ``total_ut_steps`` times over holds a tag once a
    pass, and under ``scan_layers`` one pass more, because the scan over
    layers stacks a pass's kept tensors as its own output and the scan
    over passes then writes that stack into its accumulator of
    ``passes`` stacks, so the newest pass's stack exists twice
    (``tests/test_chip_compile.py`` holds the update compiled for the
    chip to this count).
    ``lane``: the multiple a tensor's last dimension is padded to where
    it is held (128 on a TPU; 1 counts the elements).  The attention tags are counted as the
    flash kernel leaves them: an implementation without tags keeps
    nothing under those names and is over-reckoned."""
    def w(d):
        return -(-d // lane) * lane

    total = dict.fromkeys(REMAT_TAGS, 0)
    passes = cfg.total_ut_steps
    held = passes + (1 if passes > 1 and cfg.scan_layers else 0)
    resid = rows * seq_len * w(cfg.hidden_size) * _dt(cfg.dtype).itemsize
    for mixer, ffn in cfg.layer_kinds():
        parts = [cls.tag_bytes(cfg, rows, seq_len, w) for cls in
                 (mixer and MIXERS[mixer], ffn and ffn_class(ffn)) if cls]
        if not cfg.use_parallel_residual and mixer and ffn:
            parts.append({"attn_resid": resid})
        for part in parts:
            for tag, size in part.items():
                total[tag] += size * held
    return tuple((t, b) for t, b in total.items() if b)


def remat_keep(names_with_bytes, budget_bytes: Optional[int]):
    """The ladder: the names, in the order given, whose bytes fit into
    ``budget_bytes`` together, up to the first that does not.  No
    budget (``None``) or none left keeps nothing."""
    kept, left = [], budget_bytes or 0
    for name, size in names_with_bytes:
        if size > left:
            break
        kept.append(name)
        left -= size
    return tuple(kept)


class QuantDense(nn.Module):
    """Weight-only int8 Dense (ops/quant.py layout): kernel stored int8
    with a per-output-channel f32 scale; the int8→bf16 convert fuses
    into the dot's operand read so HBM sees 1 byte/param (measured
    1.76x over bf16 on the 16-layer decode matmul stack).  Params come
    from ``quantize_params_int8``, never from init.  ``axes`` carries
    the SAME logical partitioning as the dense kernel (scale/bias get
    the output axis) so a tensor-sharded rollout mesh shards the int8
    kernels instead of replicating them per device (ADVICE r3)."""

    features: int
    use_bias: bool
    dtype: Any
    param_dtype: Any
    axes: tuple = (None, None)

    @nn.compact
    def __call__(self, x):
        kq = self.param(
            "kernel_q",
            nn.with_logical_partitioning(nn.initializers.zeros_init(),
                                         self.axes),
            (x.shape[-1], self.features), jnp.int8)
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones_init(),
                                         (self.axes[-1],)),
            (self.features,), jnp.float32)
        x = x.astype(self.dtype)
        y = (x @ kq.astype(self.dtype)) * scale.astype(self.dtype)
        if self.use_bias:
            bias = self.param(
                "bias",
                nn.with_logical_partitioning(nn.initializers.zeros_init(),
                                             (self.axes[-1],)),
                (self.features,), self.param_dtype)
            y = y + bias.astype(self.dtype)
        return y


def _dense(features, axes, use_bias, cfg, name):
    if cfg.quantize_dense:
        return QuantDense(features=features, use_bias=use_bias,
                          dtype=_dt(cfg.dtype),
                          param_dtype=_dt(cfg.param_dtype),
                          axes=axes, name=name)
    return nn.Dense(
        features=features,
        use_bias=use_bias,
        dtype=_dt(cfg.dtype),
        param_dtype=_dt(cfg.param_dtype),
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(stddev=0.02), axes),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), (axes[-1],)),
        name=name,
    )


def _norm(cfg, name):
    if cfg.rms_norm:
        return nn.RMSNorm(
            epsilon=cfg.rms_norm_eps, dtype=_dt(cfg.dtype),
            param_dtype=_dt(cfg.param_dtype),
            scale_init=nn.with_logical_partitioning(
                nn.initializers.ones_init(), ("norm",)),
            name=name)
    return nn.LayerNorm(
        epsilon=cfg.layernorm_eps, dtype=_dt(cfg.dtype),
        param_dtype=_dt(cfg.param_dtype),
        scale_init=nn.with_logical_partitioning(
            nn.initializers.ones_init(), ("norm",)),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), ("norm",)),
        name=name)


@flax.struct.dataclass
class Visible:
    """What a block-diffusion model's attention masks by, beside the
    positions it rotates by (``ModelConfig.block_length``).  A row is
    ``[clean stream ; noisy streams]``: its first ``clean`` entries hold
    tokens at slot == position and are the keys every query may see;
    the rest, none outside a trainer's trace forward, whole groups of
    ``block`` entries, each one block of one noisy stream at its true
    positions.  A query sees the clean key at slot j iff ``j <= see``
    (the last position of its block under the clean rule: causal across
    blocks, both directions inside one; the last position BEFORE its
    block for a noisy query), and a noisy query also its own group."""

    see: jnp.ndarray                                      # [B, L] int
    clean: int = flax.struct.field(pytree_node=False)
    block: int = flax.struct.field(pytree_node=False)


def clean_rule(positions, block: int) -> Visible:
    """The clean stream's rule on ``positions`` [B, L]: key j is visible
    to the query at p iff ``j // block <= p // block``."""
    return Visible(see=positions // block * block + (block - 1),
                   clean=positions.shape[1], block=block)


def trace_row_length(cfg: ModelConfig, seq_len: int, new_tokens: int) -> int:
    """The entries of one row of a block-diffusion model's trace forward
    (:func:`trace_inputs`): the clean stream's ``seq_len``, one noisy
    stream a denoising step of every block ``new_tokens`` completion
    positions can lie in, and what the kernels' tiling pads that by."""
    from orion_tpu.ops.attention import noisy_length

    noisy = (cfg.denoising_steps * cfg.blocks_spanned(new_tokens)
             * cfg.block_length)
    return seq_len + noisy_length(noisy, cfg.block_length)


def trace_inputs(cfg: ModelConfig, sequences, prompt_lens, reveal_step):
    """(ids, positions, keywords) of the ONE forward that scores a
    block-diffusion model's completions along their sampling trace
    (``ops.logprobs.trace_streams`` has the layout): the keywords are
    ``logits_positions`` (the T noisy entries (step, position) that
    logits and values are read at), ``token_mask`` and ``visible``."""
    from orion_tpu.ops.logprobs import trace_streams

    L, T = sequences.shape[1], reveal_step.shape[1]
    row = trace_streams(
        sequences, prompt_lens, reveal_step, cfg.block_length,
        cfg.denoising_steps, cfg.mask_id, cfg.blocks_spanned(T),
        trace_row_length(cfg, L, T) - L)
    return row["ids"], row["positions"], {
        "logits_positions": row["read_at"], "token_mask": row["token_mask"],
        "visible": Visible(see=row["see"], clean=L, block=cfg.block_length)}


def _cache_writer(positions, B: int, L: int, step: bool = False):
    """``write(cache, new)``: the L new entries of every sequence at
    slots starting at ``positions[:, 0]``.  ``step``: the L entries are
    one step of a decode loop (a block-diffusion model's block, or two:
    the one it commits in front of the one it denoises), each written
    at its own position: one past the cache's end is not written."""
    starts = positions[:, 0]
    if step and L > 1:
        # one batched scatter with unique indices, as the one-token step
        # (a scatter drops what lies out of bounds)
        rows = jnp.arange(B)[:, None]

        def write(cache, new):
            return cache.at[rows, positions].set(new, unique_indices=True)
    elif L == 1:
        # Decode: ONE batched scatter with unique indices.  The
        # vmap(dynamic_update_slice) form lowers to a serial
        # scatter-WHILE per array on TPU — profiled at 5.2 ms of
        # a 7.6 ms decode step (32 nested whiles + 1024 per-
        # element fusions per step) vs ~0 for this scatter.
        bidx = jnp.arange(B)

        def write(cache, new):
            return cache.at[bidx, starts].set(
                new[:, 0], unique_indices=True)
    else:
        # Prefill writes an L-token block per sequence; runs
        # once per generate, where the slice form is fine.
        def write(cache, new):
            # vmap strips the batch dim: per-sequence slices
            # index (start, 0, ...) over new.ndim-1 dims.
            zeros = (0,) * (new.ndim - 2)
            return jax.vmap(
                lambda c, t, i: jax.lax.dynamic_update_slice(
                    c, t, (i,) + zeros))(cache, new, starts)
    return write


def _pass_writer(write, at, positions, B: int, L: int):
    """``write`` for the entries of pass ``at`` (a traced index) of a
    cache whose leaves lead with a pass axis, [passes, B, slots, ...]
    (a stack run several times over, its passes a scan that carries the
    cache): the one-token step ONE scatter into the whole leaf, in
    place; a prefill takes its pass's entries out and puts them back,
    once a generate."""
    if L == 1:
        bidx, starts = jnp.arange(B), positions[:, 0]

        def step(cache, new):
            return cache.at[at, bidx, starts].set(
                new[:, 0], unique_indices=True)
        return step

    def prefill(cache, new):
        return jax.lax.dynamic_update_index_in_dim(
            cache, write(jax.lax.dynamic_index_in_dim(
                cache, at, 0, keepdims=False), new), at, 0)
    return prefill


def _pass_entry(leaf, at, slots: Optional[int] = None):
    """Pass ``at``'s [B, slots, ...] of such a leaf (its first ``slots``
    slots): one dynamic slice, for the consumer to fuse."""
    sizes = (1, leaf.shape[1], slots or leaf.shape[2]) + leaf.shape[3:]
    return jax.lax.dynamic_slice(
        leaf, (at,) + (0,) * (leaf.ndim - 1), sizes)[0]


# A one-token step's attention reads a static prefix of its slot cache:
# blocks of at least _PREFIX_SLOTS slots, at most _PREFIX_COUNT prefixes.
_PREFIX_SLOTS = 128
_PREFIX_COUNT = 8


def prefix_lengths(Lmax: int) -> list:
    """The static prefixes :func:`prefix_step` chooses among over a
    cache of ``Lmax`` slots: whole blocks of ``max(128, Lmax / 8)``
    slots (rounded up to 8: 128 at 384 and 1024, 160 at 1280), the last
    one ragged where ``Lmax`` is no whole blocks (128 ... 896, 1000 at
    1000); ``[Lmax]`` alone up to one block."""
    block = max(_PREFIX_SLOTS, -(-Lmax // (8 * _PREFIX_COUNT)) * 8)
    return [*range(block, Lmax, block), Lmax]


def prefix_step(positions, Lmax: int, fn):
    """``fn(m)`` for the least prefix ``m`` of :func:`prefix_lengths`
    that holds every row's ``positions`` (one new token a row, [B, 1];
    the last slots a block-diffusion model's step sees, [B, L]):
    ``fn(m)`` is the step's attention over slots ``[:m]`` of a dense
    cache of ``Lmax`` slots and of the mask ``slot <= position``.  Slots
    fill from 0 up (right-padded prompts, decode overwrites the tail
    slot by slot), so the slots past the batch's furthest position have
    probability exactly 0 and are not fetched: ONE ``lax.switch`` over
    static slices, each branch XLA's own fusions on fewer slots (a
    cache of one block: ``fn(Lmax)``, no switch).  The slots inside the
    last block that a row has not reached stay masked as before; a
    position past the cache's end reads it whole (``lax.switch`` clamps
    its index)."""
    ms = prefix_lengths(Lmax)
    return jax.lax.switch(jnp.max(positions) // ms[0],
                          [partial(fn, m) for m in ms])


def prefix_step_slots(lens, Lmax: int, new_tokens: int) -> float:
    """The slots one row's one-token step reads a layer under
    :func:`prefix_step`, the mean over the ``new_tokens - 1`` steps the
    fixed-batch engine makes after prompts of ``lens`` real tokens (step
    ``t`` stands at position ``len + t``; the batch's longest prompt
    decides).  Host arithmetic, from lengths."""
    ms = np.asarray(prefix_lengths(Lmax))
    at = int(np.max(lens)) + np.arange(max(new_tokens - 1, 1))
    return float(ms[np.minimum(at // ms[0], len(ms) - 1)].mean())


def _flash_tag_bytes(cfg, rows, seq_len, w, heads, per_tok, per_out) -> dict:
    """What the flash kernel takes (``per_tok`` elements a token) and
    gives (``per_out``, and lse [rows, heads, 1, seq_len] in float32)."""
    n, act = rows * seq_len, _dt(cfg.dtype).itemsize
    return {"attn_qkv": n * per_tok * act,
            "attn_out": n * per_out * act + rows * heads * w(seq_len) * 4}


class Attention(nn.Module, Kind):
    """``qk_norm``: true or ``"whole"``, one norm over the whole query
    and key projections, before the split into heads (olmo_hybrid's);
    ``"head"``, one norm over each head's ``head_dim`` of q and of k, a
    weight of ``head_dim`` each that all heads share (keye_dsa's, the
    Qwen3 family's); ``rotary`` false: nothing is rotated
    (olmo_hybrid's, whose recurrent layers carry position).  The heads
    are those ``cfg.heads_held()`` leaves here (all, but under
    ``head_share``: that share's query heads against the key-value heads
    they read, and their part of the output projection's sum).  The
    rotation's table is that of the layer type's entry in
    ``cfg.rope_parameters`` (``layer_type``; none: the default table at
    ``rope_theta``)."""

    cfg: ModelConfig
    qk_norm: Any = False
    rotary: bool = True

    per_head_kv = True
    steps_over_prefix = True
    layer_type = "full_attention"     # its key in cfg.rope_parameters

    @staticmethod
    def window(cfg) -> Optional[int]:
        """The keys a query sees, itself among them (None: every key up
        to itself)."""
        return None

    @classmethod
    def trace_scope(cls, cfg) -> Optional[str]:
        """The named scope a device trace groups this kind's operations
        under, where a model mixes it with windowed layers."""
        return "attn.full" if cfg.sliding_window else None

    @classmethod
    def kv_step_form(cls, cfg, slots, quantized=False) -> str:
        """``kernel`` where this kind's one-token step against a cache
        of ``slots`` slots is ``ops/pallas/dense_step.py``'s (its
        ``step_form``, of the heads held), else ``""``: asked by
        :meth:`cache_entry`, which lays the cache out for the step, and
        by :func:`decode_attrs`; the step follows the cache's rank."""
        held = cfg.heads_held()
        return dense_step.step_form(cfg.block_length or 1, held["q"],
                                    held["kv"], slots, quantized)

    @classmethod
    def cache_entry(cls, cfg, batch, slots, dtype, pre=(), quantized=False):
        """{"k", "v"} of the key-value heads held: [B, slots, Hkv * D]
        where the one-token step is the kernel (:meth:`kv_step_form`:
        the key heads of a slot side by side, no lane padded under
        heads of 64), [B, slots, Hkv, D] elsewhere; ``quantized``: int8
        values beside per-token-per-head float32 scales
        (RolloutConfig.quantize_kv, ops/quant.py)."""
        shape = pre + (batch, slots, cfg.heads_held()["kv"], cfg.head_dim)
        if cls.kv_step_form(cfg, slots, quantized):
            shape = shape[:-2] + (shape[-2] * shape[-1],)
        entry = {n: jnp.zeros(shape, jnp.int8 if quantized else dtype)
                 for n in "kv"}
        if quantized:
            entry.update({n + "_scale": jnp.zeros(shape[:-1], jnp.float32)
                          for n in "kv"})
        return entry

    @staticmethod
    def tag_bytes(cfg, rows, seq_len, w):
        held = cfg.heads_held()
        return _flash_tag_bytes(
            cfg, rows, seq_len, w, held["q"],
            (held["q"] + 2 * held["kv"]) * w(cfg.head_dim),
            held["q"] * w(cfg.head_dim))

    def qkv(self, x, positions):
        """The projections, normed and rotated as configured: q [B, L,
        H, D], k and v [B, L, Hkv, D].  (Called inside a compact
        ``__call__``: the submodules are this module's.)"""
        cfg = self.cfg
        B, L, _ = x.shape
        held = cfg.heads_held()
        H, Hkv, D = held["q"], held["kv"], cfg.head_dim

        q = _dense(H * D, ("embed", "heads"), cfg.attn_bias, cfg, "q_proj")(x)
        k = _dense(Hkv * D, ("embed", "kv_heads"), cfg.attn_bias, cfg, "k_proj")(x)
        v = _dense(Hkv * D, ("embed", "kv_heads"), cfg.attn_bias, cfg, "v_proj")(x)
        if self.qk_norm and self.qk_norm != "head":
            with jax.named_scope("attn.qk_norm"):
                q = _norm(cfg, "q_norm")(q)
                k = _norm(cfg, "k_norm")(k)
        q = q.reshape(B, L, H, D)
        k = k.reshape(B, L, Hkv, D)
        v = v.reshape(B, L, Hkv, D)
        if self.qk_norm == "head":
            with jax.named_scope("attn.qk_norm"):
                q = _norm(cfg, "q_norm")(q)
                k = _norm(cfg, "k_norm")(k)

        if self.rotary:
            rotary_dim = int(D * cfg.rotary_pct)
            q, k = apply_rotary(q, k, positions, rotary_dim, cfg.rope_theta,
                                cfg.rope_parameters.get(self.layer_type))
        return q, k, v

    @nn.compact
    def __call__(self, x, positions, layer_cache=None, visible=None):
        return self.attend(x, positions, layer_cache, visible)

    def attend(self, *args, **kw):
        """:meth:`_attend` under the kind's named scope, where it has
        one.  (Called inside a compact ``__call__``, as :meth:`qkv`.)"""
        scope = self.trace_scope(self.cfg)
        if not scope:
            return self._attend(*args, **kw)
        with jax.named_scope(scope):
            return self._attend(*args, **kw)

    def _attend(self, x, positions, layer_cache=None, visible=None,
                token_mask=None):
        """x: [B, L, E]; positions: [B, L] absolute positions.

        layer_cache: {"k","v"} [B, Lmax, Hkv, D], [B, Lmax, Hkv * D]
        where the one-token step is the kernel (:meth:`cache_entry`; the
        rank tells them apart), or None.  When a cache
        is given, the L new keys/values are written at per-sequence
        slots starting at ``positions[:, 0]`` — one formula covers
        prefill (positions 0..L-1), chunked prefill (P..P+L-1) and
        decode (positions = current lengths).  The cache is dense
        ([B, Lmax] slots a layer, int8 with scales under
        ``quantize_kv``); a one-token step reads its filled prefix in
        blocks (the kernel ``dense_step`` a row's, :func:`prefix_step`
        the batch's), and so does the step of a
        block-diffusion model: ``block_length`` tokens a row, or twice
        that, the block before riding in front to be committed (the L
        keys and values are written before any query reads, and the
        clean rule lets the later block see the earlier one's).
        ``visible`` (:class:`Visible`): what to mask by where that is
        not ``positions``; a block-diffusion model given none masks by
        the clean rule (:func:`clean_rule`).
        Under a window (:meth:`window`, :class:`WindowAttention`) the
        cache is a ring, written at ``position mod its slots``: prefill
        attends over its own fresh keys and values under the window rule
        and hands the ring each row's last real tokens
        (``token_mask`` [B, L] says which are real; none: all), and a
        one-token step reads the ring's filled slots, all of which the
        write has left inside the window.
        Returns (out [B, L, E], new_layer_cache).
        """
        cfg = self.cfg
        B, L, _ = x.shape
        H, D = cfg.heads_held()["q"], cfg.head_dim
        window = self.window(cfg)
        q, k, v = self.qkv(x, positions)
        if visible is None and cfg.block_length:
            visible = clean_rule(positions, cfg.block_length)
        # a query sees the slots up to ``see``
        see = positions if visible is None else visible.see
        # one step of a decode loop: one token; one block's, or two's
        step = layer_cache is not None and L in (
            1, cfg.block_length, 2 * cfg.block_length)
        # the pass whose entries these are, of leaves [passes, B, slots,
        # Hkv, D] (Transformer's scan over the passes of an unrolled
        # stack run several times over)
        at = None
        if layer_cache is not None and "pass" in layer_cache:
            layer_cache = dict(layer_cache)
            at = layer_cache.pop("pass")

        scale = 1.0 / D ** 0.5
        paged_decode_out = None
        if is_paged(layer_cache):
            # Paged-KV path (rollout engine with RolloutConfig.paged).
            new_cache = write_paged_tokens(layer_cache, k, v, positions)
            if L == 1:
                # Decode step: Pallas paged attention over the pool
                # (tensor-sharded over kv-heads under an ambient mesh —
                # the _sharded dispatch keeps GSPMD from all-gathering
                # the pool around the opaque pallas_call).
                from orion_tpu.ops.pallas.paged_attention import (
                    paged_decode_attention_sharded)
                paged_decode_out = paged_decode_attention_sharded(
                    q[:, 0], new_cache["k_pages"], new_cache["v_pages"],
                    new_cache["block_tables"], positions[:, 0] + 1, scale,
                    k_scales=new_cache.get("k_scales"),
                    v_scales=new_cache.get("v_scales"))
                keys = values = None
            else:
                # Prefill (full or chunked): gather the sequence's pages
                # into slot order so slot j holds absolute position j —
                # then the shared mask formula below covers history and
                # in-chunk keys alike.  (Gather cost ≈ the dense cache;
                # paged wins on the decode side, same trade vLLM makes.)
                from orion_tpu.ops.paged_kv import gather_paged_kv
                keys, values = gather_paged_kv(new_cache, _dt(cfg.dtype))
        elif layer_cache is not None:
            if window is None:
                write = _cache_writer(positions, B, L, step)
            else:
                write = _ring_writer(positions, token_mask, B, L,
                                     layer_cache["k"].shape[1])
            if at is not None:
                write = _pass_writer(write, at, positions, B, L)

            if "k_scale" in layer_cache:
                # int8 KV cache (RolloutConfig.quantize_kv): quantize
                # the new tokens' K/V per (token, head) over D and
                # write both values and scales (ops/quant.py).
                from orion_tpu.ops.quant import dequant_kv, quantize_kv
                kq_, ks_ = quantize_kv(k)
                vq_, vs_ = quantize_kv(v)
                new_cache = {
                    "k": write(layer_cache["k"], kq_),
                    "v": write(layer_cache["v"], vq_),
                    "k_scale": write(layer_cache["k_scale"], ks_),
                    "v_scale": write(layer_cache["v_scale"], vs_),
                }
                if L > 1:
                    # Prefill: the standard attention below consumes
                    # the dequantized cache (convert+mul fuse into its
                    # operand reads); a one-token step reads the int8
                    # cache itself (step_attention).
                    keys = dequant_kv(new_cache["k"], new_cache["k_scale"],
                                      _dt(cfg.dtype))
                    values = dequant_kv(new_cache["v"],
                                        new_cache["v_scale"],
                                        _dt(cfg.dtype))
            else:
                # a cache laid [B, Lmax, Hkv * D] takes its rows so, and
                # gives the prefill's attention one re-laid copy
                lay = (B, L) + layer_cache["k"].shape[2 + (at is not None):]
                new_cache = {"k": write(layer_cache["k"], k.reshape(lay)),
                             "v": write(layer_cache["v"], v.reshape(lay))}
                if window is None:
                    keys, values = (
                        (new_cache[n] if at is None
                         else _pass_entry(new_cache[n], at)
                         ).reshape(B, -1, *k.shape[2:]) for n in "kv")
                else:
                    # prefill sees its own rows; a ring is no place to
                    # look positions up in
                    keys, values = k, v
        else:
            new_cache = None
            keys, values = k, v

        # Mask (positional_mask): query at absolute position p attends
        # to cache slots j <= p.  Slots map 1:1 to absolute positions in
        # the train, prefill, decode and paged-gather paths (decode
        # overwrites the right-padded prompt tail slot by slot), so one
        # formula covers all of them.
        if paged_decode_out is not None:
            out = paged_decode_out[:, None, :, :]
        elif step and not is_paged(layer_cache):
            # one new token (one block's, two's) against the dense slot
            # cache, int8 or not
            Lmax = new_cache["k"].shape[1 + (at is not None)]
            if window is not None:
                # the ring's filled slots: after the write every one of
                # them holds a key inside the window (the new token took
                # the place of the one that left it), and a softmax does
                # not care in which order its slots lie
                see = jnp.minimum(see, Lmax - 1)
            if new_cache["k"].ndim == 3 + (at is not None):
                # laid out for the kernel (cache_entry: one token, a
                # group of query heads on each of several key heads):
                # over each row's filled blocks
                k_, v_ = (new_cache[n] if at is None
                          else _pass_entry(new_cache[n], at) for n in "kv")
                out = dense_step.dense_step(q, k_, v_, see[:, 0], scale)
            else:
                # over the filled prefix of its slots
                whole = positional_mask(see, Lmax)

                def attend(m):
                    c = {n: a[:, :m] if at is None else _pass_entry(a, at, m)
                         for n, a in new_cache.items()}
                    return step_attention(
                        q, c["k"], c["v"], whole[..., :m], scale,
                        c.get("k_scale"), c.get("v_scale"))

                out = prefix_step(see, Lmax, attend)
        elif visible is not None and visible.clean < L:
            # a trainer's trace forward: the clean stream and its noisy
            # streams in one row, no cache
            out = streams_attention(q, k, v, see, visible.clean,
                                    visible.block, scale,
                                    impl=cfg.attention_impl)
        else:
            out = attention(q, keys, values,
                            positional_mask(see, keys.shape[1], window),
                            scale=scale, impl=cfg.attention_impl,
                            q_positions=see, window=window)
        out = out.reshape(B, L, H * D)
        out = _dense(cfg.hidden_size, ("heads", "embed"),
                     cfg.attn_bias, cfg, "o_proj")(out)
        return out, new_cache


def _ring_writer(positions, token_mask, B: int, L: int, ring: int):
    """``write(cache, new)`` into a ring of ``ring`` slots, position p
    at slot ``p mod ring``.  One token a row: its slot.  A prefill of L
    rows from position 0: each ROW's last ``min(len, ring)`` real tokens
    (``token_mask`` [B, L], a row's prefix: rows are right-padded to
    different real lengths; none: all L), slot j taking position ``j +
    ring floor((len - 1 - j) / ring)`` in ONE gather along the sequence;
    a slot no real token falls on keeps what it held."""
    if L == 1:
        return _cache_writer(positions % ring, B, 1)
    lens = (jnp.full((B,), L, jnp.int32) if token_mask is None
            else jnp.sum(token_mask, axis=1, dtype=jnp.int32))
    slot = jnp.arange(ring, dtype=jnp.int32)[None, :]
    held = slot < lens[:, None]                                  # [B, ring]
    at = jnp.where(held, slot + (lens[:, None] - 1 - slot) // ring * ring, 0)

    def write(cache, new):
        wide = (...,) + (None,) * (new.ndim - 2)
        return jnp.where(held[wide],
                         jnp.take_along_axis(new, at[wide], axis=1), cache)
    return write


class WindowAttention(Attention):
    """Sliding-window attention (mellum's ``sliding_attention`` layers):
    :class:`Attention` whose query at position t sees the keys s with
    ``t - sliding_window < s <= t``, itself and the ``sliding_window -
    1`` before it, rotated by its own layer type's table.

    What follows from the window, stated here and nowhere else: the
    cache entry is a RING of ``min(sliding_window, slots)`` slots
    (:meth:`cache_entry`; laid ``[B, ring, Hkv * D]`` where the step is
    the kernel, as :class:`Attention`'s), position p written at slot ``p
    mod ring`` (:func:`_ring_writer`).  Whole sequences and prefill run
    the attention over their own keys under the window rule (the flash
    kernels' ``window``: tiles wholly behind it are neither fetched nor
    multiplied; the einsum over :func:`positional_mask` elsewhere).  The
    one-token step needs no new rule: after its write every filled slot
    of the ring is inside the window, so it is :class:`Attention`'s step
    (``dense_step``, :func:`prefix_step` on the CPU) under ``reach =
    min(t, ring - 1)``.  ``token_mask`` says which of a prefill's rows
    are real: the ring takes each row's LAST real tokens."""

    takes_token_mask = True
    layer_type = "sliding_attention"

    @staticmethod
    def window(cfg):
        return cfg.sliding_window

    @classmethod
    def trace_scope(cls, cfg):
        return "attn.window"

    @classmethod
    def ring_slots(cls, cfg, slots: int) -> int:
        """The ring's slots beside full caches of ``slots``."""
        return min(cls.window(cfg), slots)

    @classmethod
    def kv_step_form(cls, cfg, slots, quantized=False) -> str:
        """:class:`Attention`'s, of the ring beside caches of ``slots``."""
        return super().kv_step_form(cfg, cls.ring_slots(cfg, slots),
                                    quantized)

    @classmethod
    def cache_entry(cls, cfg, batch, slots, dtype, pre=(), quantized=False):
        """:class:`Attention`'s entry of :meth:`ring_slots` slots."""
        return super().cache_entry(cfg, batch, cls.ring_slots(cfg, slots),
                                   dtype, pre, quantized)

    @staticmethod
    def lacks(cfg):
        pages = ("a ring entry is not made of pages: the page pool, its "
                 "block tables and the paged-attention kernel hold every "
                 "position of a sequence and mask by position alone "
                 "(ops/paged_kv.py, ops/pallas/paged_attention.py); a "
                 "window over pages (pages freed as they leave it, a lower "
                 "bound in the kernel) is not written")
        return {
            "paged": pages,
            "continuous": "the continuous engine's cache is the page pool "
            "and its prefill comes in chunks at any position: " + pages,
            "quantize_kv": "there is no int8 ring (ops/quant.py's scales "
            "would ride the ring beside their values; the ring's write and "
            "the step over it were run against the reference in bf16 alone)",
            "sequence_parallel": "the sequence-parallel attentions exchange "
            "keys and values by position and apply the causal rule alone "
            "(ops/attention.py::_flash_on_mesh is handed the window, "
            "parallel/longctx.py's ring and ulysses forms are not: most of "
            "a ring's rotations would carry keys no query of the shard "
            "sees)"}

    @staticmethod
    def keys_seen(lens, window: int) -> dict:
        """The real tokens of sequences of ``lens`` (``seq_tokens``) and
        the keys their queries see on ONE layer, under the window and
        under the causal rule alone: query t (0-based) has ``min(t + 1,
        window)`` of its ``t + 1``."""
        n = np.asarray(lens, np.int64)
        m = np.minimum(n, window)
        return {"seq_tokens": int(n.sum()),
                "window_keys_seen": int((m * (m + 1) // 2
                                         + (n - m) * window).sum()),
                "causal_keys": int((n * (n + 1) // 2).sum())}

    @staticmethod
    def layer_counts(cfg) -> dict:
        mixers = [m for m, _ in cfg.layer_kinds()]
        return {"window_layers": mixers.count("window"),
                "full_layers": mixers.count("attention")}

    @classmethod
    def forward_attrs(cls, cfg, total_lens):
        """Of ONE whole-sequence forward of the batch: how many layers
        of either kind, the window, the real tokens (``seq_tokens``) and
        the keys their queries see on a layer (:meth:`keys_seen`); the
        experts held beside them (the benchmark's operation count reads
        all of it off the span: it hard-codes no count)."""
        return {**cls.layer_counts(cfg), "sliding_window": cfg.sliding_window,
                "experts_held": cfg.experts_held,
                **cls.keys_seen(total_lens, cfg.sliding_window)}

    @classmethod
    def decode_attrs(cls, cfg, lens, slots, new_tokens):
        """The two kinds of cache side by side: ``window_slots`` (the
        ring's), ``ring_cache_bytes`` and ``full_cache_bytes`` of the
        batch over all layers of either kind, and the slots one row's
        one-token step reads a layer of either kind, the mean over the
        steps after prompts of ``lens`` real tokens
        (``kv_slots_read_window`` under ``reach = min(t, ring - 1)``,
        ``kv_slots_read_full``): the blocks ``dense_step`` visits where
        the step is the kernel (its ``step_slots``), :func:`prefix_step`'s
        prefix elsewhere; and what the prefill goes over: the prompts'
        real tokens (``seq_tokens``) and the keys their queries see a
        layer (:meth:`keys_seen`)."""
        ring = cls.ring_slots(cfg, slots)
        counts = cls.layer_counts(cfg)
        row = (2 * cfg.heads_held()["kv"] * cfg.head_dim
               * _dt(cfg.dtype).itemsize * len(lens))

        def read(kind, cache_len):
            if kind.kv_step_form(cfg, slots):
                return dense_step.step_slots(lens, cache_len, new_tokens - 1)
            return prefix_step_slots(lens, cache_len, new_tokens)

        return {**counts, "window_slots": ring,
                **cls.keys_seen(lens, cfg.sliding_window),
                "ring_cache_bytes": counts["window_layers"] * ring * row,
                "full_cache_bytes": counts["full_layers"] * slots * row,
                "kv_slots_read_window": read(cls, ring),
                "kv_slots_read_full": read(Attention, slots)}

    @nn.compact
    def __call__(self, x, positions, layer_cache=None, token_mask=None,
                 visible=None):
        return self.attend(x, positions, layer_cache, visible, token_mask)


class SparseAttention(Attention):
    """Grouped-query attention over the keys a learned indexer selects
    (keye_dsa; the lightning indexer of DeepSeek-V3.2-Exp on grouped
    heads).  Projections, per-head q/k norm, rotary and cache writer are
    :class:`Attention`'s.

    Indexer: ``qI = h W_Iq`` as ``sa_index_heads`` heads of
    ``sa_index_head_dim``, ``kI = LayerNorm(h W_Ik)`` ONE head, the same
    rotary on the whole of both, ``w = h W_Iw`` a head; ``I[t, s] =
    (heads x dim)^-1/2 sum_j w[t, j] ReLU(qI[t, j] . kI[s])`` over the
    causal pairs, float32; a query keeps its ``sa_topk`` largest (all,
    where it has no more; the lower slot on a tie): ``ops/indexer.py``.
    ``h`` enters it under ``stop_gradient`` and the selection is
    discrete: its parameters take no gradient and no alignment loss is
    added (RL here holds the indexer fixed; the published recipe trains
    it by a separate KL term).  bf16 inputs, float32 accumulation; the
    published FP8 quantisation and Hadamard rotation of qI, kI are left
    out (an orthogonal map: no product changes).

    The cache holds ``ki`` [B, Lmax, dim] beside ``k`` and ``v``.
    Whole sequences and prefill run the flash kernels over all causal
    blocks with the selection as an operand (``sparse_fwd`` ...); one
    new token against the cache reads k and v where they lie, under a
    mask of the kept slots (``ops/pallas/sparse_step.py``: the kernel
    ``sparse_step`` over the filled slots on one TPU device, XLA's
    einsum over the whole cache elsewhere).
    Where a call has no more keys than ``sa_topk`` nothing is selected
    and the attention is :class:`Attention`'s, exactly.
    """

    index_leaves = ("ki",)
    steps_over_prefix = False
    rl_fixed = ("index_",)

    @classmethod
    def kv_step_form(cls, cfg, slots, quantized=False) -> str:
        """Never ``dense_step``: the selected step (:meth:`step_read`)."""
        return ""

    @classmethod
    def cache_entry(cls, cfg, batch, slots, dtype, pre=()):
        """:class:`Attention`'s [B, slots, Hkv, D] beside {"ki": [B,
        slots, sa_index_head_dim]}, the indexer's one key head."""
        return {**super().cache_entry(cfg, batch, slots, dtype, pre),
                "ki": jnp.zeros(
                    pre + (batch, slots, cfg.sa_index_head_dim), dtype)}

    @staticmethod
    def lacks(cfg):
        pages = ("there is no selection inside paged attention nor a page "
                 "pool for the indexer's keys (ops/paged_kv.py)")
        return {
            "paged": pages, "continuous": pages,
            "quantize_kv": "there is no int8 cache under a selection (the "
            "selected step reads bf16 keys and values where they lie, and "
            "none was run against the reference)",
            "quantize_weights": "an int8 indexer would select other keys "
            "than the update's",
            "sequence_parallel": "the sequence-parallel attentions exchange "
            "keys and values by position and apply the causal rule alone; "
            "there is no exchange of the indexer's keys nor a selection "
            "across sequence shards"}

    @staticmethod
    def key_counts(lens, topk: int) -> dict:
        """The valid and the selected keys summed over the real queries
        of sequences of ``lens`` real tokens: query t (0-based) has t +
        1 and keeps ``min(topk, t + 1)``."""
        n = np.asarray(lens, np.int64)
        m = np.minimum(n, topk)
        return {"sa_topk": topk,
                "sa_keys_valid": int((n * (n + 1) // 2).sum()),
                "sa_keys_selected": int(
                    (m * (m + 1) // 2 + (n - m) * topk).sum())}

    @staticmethod
    def step_read(cfg, lens, slots, new_tokens) -> dict:
        """{sparse_step, sa_step_bytes}: the form the selected one-token
        step takes against a cache of ``slots`` slots in this process's
        traces (``ops/pallas/sparse_step.py::step_form``: ``kernel`` /
        ``masked``; not under an ``sa_`` name: the benchmark's reader of
        the span, ``roofline_keye_dsa.py::span_counts``, takes every
        ``sa_*`` attribute for a number) and the bytes of k and v one
        step then reads a layer, the mean over the ``new_tokens`` steps
        of prompts of ``lens`` real tokens (``step_slots``)."""
        from orion_tpu.ops.pallas import sparse_step

        form = sparse_step.step_form(slots)
        read = sparse_step.step_slots(form, lens, slots, new_tokens)
        row = cfg.num_kv_heads * cfg.head_dim * _dt(cfg.dtype).itemsize
        return {"sparse_step": form, "sa_step_bytes": int(2 * read * row)}

    @staticmethod
    def decode_attrs(cfg, lens, slots, new_tokens):
        """The prefill's key counts and how the steps read k and v."""
        return {**SparseAttention.key_counts(lens, cfg.sa_topk),
                **SparseAttention.step_read(cfg, lens, slots, new_tokens)}

    @staticmethod
    def forward_attrs(cfg, total_lens):
        """Of ONE whole-sequence forward of the batch (the experience
        forwards and the update's each make it)."""
        return SparseAttention.key_counts(total_lens, cfg.sa_topk)

    @nn.compact
    def __call__(self, x, positions, layer_cache=None):
        from orion_tpu.ops import indexer
        from orion_tpu.ops.attention import sparse_attention
        from orion_tpu.ops.pallas import sparse_step

        cfg = self.cfg
        B, L, _ = x.shape
        H, D = cfg.num_heads, cfg.head_dim
        Hi, Di, topk = cfg.sa_index_heads, cfg.sa_index_head_dim, cfg.sa_topk
        if is_paged(layer_cache) or (layer_cache is not None
                                     and "ki" not in layer_cache):
            raise ValueError(
                "sparse attention caches {'k', 'v', 'ki'} (init_cache); "
                "there is no paged or int8 cache under a selection yet")
        q, k, v = self.qkv(x, positions)
        scale = 1.0 / D ** 0.5

        with jax.named_scope("attn.indexer"):
            h = jax.lax.stop_gradient(x)
            qi = _dense(Hi * Di, ("embed", "index"), False, cfg,
                        "index_q_proj")(h).reshape(B, L, Hi, Di)
            ki = nn.LayerNorm(
                epsilon=cfg.rms_norm_eps, dtype=_dt(cfg.dtype),
                param_dtype=_dt(cfg.param_dtype),
                scale_init=nn.with_logical_partitioning(
                    nn.initializers.ones_init(), ("norm",)),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), ("norm",)),
                name="index_k_norm")(
                    _dense(Di, ("embed", "index"), False, cfg,
                           "index_k_proj")(h))
            qi, ki = apply_rotary(qi, ki[:, :, None, :], positions, Di,
                                  cfg.rope_theta)
            ki = ki[:, :, 0, :]
            w = _dense(Hi, ("embed", "index"), False, cfg,
                       "index_w_proj")(h).astype(jnp.float32) \
                * (Hi * Di) ** -0.5
            qi, ki, w = (jax.lax.stop_gradient(t) for t in (qi, ki, w))

        new_cache = None
        if layer_cache is not None:
            write = _cache_writer(positions, B, L)
            new_cache = {"k": write(layer_cache["k"], k),
                         "v": write(layer_cache["v"], v),
                         "ki": write(layer_cache["ki"], ki)}
            k, v, ki = new_cache["k"], new_cache["v"], new_cache["ki"]
        Lk = k.shape[1]
        key_slots = jnp.arange(Lk, dtype=positions.dtype)
        mask = key_slots[None, None, :] <= positions[:, :, None]

        if Lk <= topk:
            # no query has more keys than it may keep
            out = attention(q, k, v, mask, scale=scale,
                            impl=cfg.attention_impl, q_positions=positions)
        elif layer_cache is not None and L == 1:
            keep = indexer.select_step(qi[:, 0], ki, w[:, 0],
                                       positions[:, 0], topk)
            with jax.named_scope("attn.sparse"):
                # k and v where they lie, the softmax over the kept
                # slots (which lie under the positional rule already)
                if sparse_step.step_form(Lk) == "kernel":
                    out = sparse_step.sparse_step(q, k, v, keep,
                                                  positions[:, 0], scale)
                else:
                    out = attention(q, k, v, keep[:, None, :], scale=scale,
                                    impl="reference")
        else:
            sel_t = indexer.select(qi, ki, w, positions, topk,
                                   cfg.sa_q_chunk, cfg.sa_kv_chunk)
            # read only by a caller that makes "selections" mutable (the
            # reference checks): [B, keys, queries] int8
            self.sow("selections", "sa_selected", sel_t)
            with jax.named_scope("attn.sparse"):
                out = sparse_attention(q, k, v, mask, sel_t, positions,
                                       scale=scale, impl=cfg.attention_impl)
        out = out.reshape(B, L, H * D)
        out = _dense(cfg.hidden_size, ("heads", "embed"),
                     cfg.attn_bias, cfg, "o_proj")(out)
        return out, new_cache


def _absorbed_step(q_lat, q_rope, c, k_rope, mask, scale: float):
    """The absorbed one-token step's softmax(scores) c over the latents
    handed in: q_lat [B, H, R], q_rope [B, H, dr], c [B, m, R], k_rope
    [B, m, dr], mask [B, 1, m] -> [B, H, R]."""
    scores = (jnp.einsum("bhr,blr->bhl", q_lat, c,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhd,bld->bhl", q_rope, k_rope,
                           preferred_element_type=jnp.float32)) * scale
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(c.dtype)
    return jnp.einsum("bhl,blr->bhr", probs, c)


class LatentAttention(nn.Module, Kind):
    """Multi-head latent attention (deepseek_v3, ``q_lora_rank: null``).

    ``q = h W_q`` per head is ``[q_nope ; q_rope]``; ``h W_kva`` is
    ``[c_raw ; k_rope_raw]``, ``c = RMSNorm(c_raw)``; ``c W_kvb`` per
    head is ``[k_nope ; v]``.  Rotary on ``q_rope`` of every head and on
    the one ``k_rope`` all heads share, the rotary features stored as
    adjacent pairs and brought to the half-split layout first
    (``rope_interleave``); under ``mla_use_nope`` nothing is rotated.
    Scores ``q . [k_nope ; k_rope] / sqrt(nope + rope)``.

    The cache holds ``c`` and the rotated ``k_rope`` of a token, nothing
    per head.  Two paths compute the same attention:

    - **expand** (training; prefill and any step longer than one token):
      keys and values of every head are formed from ``c`` (of the whole
      cache, when there is one: slot == position as everywhere) and go
      through ``ops.attention`` (flash on the TPU);
    - **absorb** (one new token against the cache): with ``W_kvb``
      split per head into ``W_uk`` and ``W_uv``, ``score = (q_nope
      W_uk^T) . c + q_rope . k_rope`` and ``o = (P c) W_uv``, so no
      per-head key or value of the context is ever formed; ``c`` and
      ``k_rope`` are read up to the filled prefix of the cache, in
      blocks (:func:`prefix_step`).
    """

    cfg: ModelConfig

    steps_over_prefix = True

    @staticmethod
    def cache_entry(cfg, batch, slots, dtype, pre=()):
        return {n: jnp.zeros(pre + (batch, slots, width), dtype)
                for n, width in (("c", cfg.kv_lora_rank),
                                 ("k_rope", cfg.qk_rope_head_dim))}

    @staticmethod
    def tag_bytes(cfg, rows, seq_len, w):
        H = cfg.heads_held()["q"]
        return _flash_tag_bytes(
            cfg, rows, seq_len, w, H,
            H * (2 * w(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
                 + w(cfg.v_head_dim)), H * w(cfg.v_head_dim))

    @staticmethod
    def lacks(cfg):
        return {
            "paged": "there is no latent paged cache (ops/paged_kv.py and "
            "the Pallas paged-decode kernel hold per-head K/V pages)",
            "continuous": "a latent paged cache (c, k_rope) and a kernel "
            "that attends over it are not written yet",
            "quantize_kv": "there is no int8 latent cache (ops/quant.py "
            "scales per head)",
            "quantize_weights": "there is no absorbed int8 kv_b_proj "
            "(ops/quant.py quantises Dense kernels)",
            "sequence_parallel": "the sequence-parallel attentions exchange "
            "per-head K/V of one head_dim, and there is no exchange of the "
            "latent (c, k_rope) yet"}

    @nn.compact
    def __call__(self, x, positions, layer_cache=None):
        cfg = self.cfg
        B, L, _ = x.shape
        H, R = cfg.num_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        if is_paged(layer_cache) or (layer_cache is not None
                                     and "c" not in layer_cache):
            raise ValueError(
                "latent attention caches {'c', 'k_rope'} (init_cache); "
                "there is no paged or int8 latent cache yet")
        scale = 1.0 / (dn + dr) ** 0.5

        q = _dense(H * (dn + dr), ("embed", "heads"), False, cfg,
                   "q_proj")(x).reshape(B, L, H, dn + dr)
        kva = _dense(R + dr, ("embed", "latent"), False, cfg,
                     "kv_a_proj_with_mqa")(x)
        c = _norm(cfg, "kv_a_norm")(kva[..., :R])
        # a bare kernel, not a Dense: the absorbed path applies it to
        # the query and the output, split per head
        w_kvb = self.param(
            "kv_b_proj", nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("latent", "heads")),
            (R, H * (dn + dv)), _dt(cfg.param_dtype)).astype(_dt(cfg.dtype))

        def halves(t):   # adjacent pairs -> half-split (rope_interleave)
            return jnp.concatenate([t[..., 0::2], t[..., 1::2]], axis=-1)

        if cfg.mla_use_nope:
            # no rotation: the shared features enter the scores as
            # projected (and are still called k_rope in the cache)
            q_nope, q_rope, k_rope = q[..., :dn], q[..., dn:], kva[..., R:]
        else:
            q_nope, q_rope = q[..., :dn], halves(q[..., dn:])
            k_rope = halves(kva[..., R:])[:, :, None, :]      # one "head"
            q_rope, k_rope = apply_rotary(q_rope, k_rope, positions, dr,
                                          cfg.rope_theta)
            k_rope = k_rope[:, :, 0, :]

        new_cache = None
        if layer_cache is not None:
            write = _cache_writer(positions, B, L)
            new_cache = {"c": write(layer_cache["c"], c),
                         "k_rope": write(layer_cache["k_rope"], k_rope)}
            c, k_rope = new_cache["c"], new_cache["k_rope"]
        key_slots = jnp.arange(c.shape[1], dtype=positions.dtype)
        mask = key_slots[None, None, :] <= positions[:, :, None]

        if layer_cache is not None and L == 1:
            with jax.named_scope("mla.absorb"):
                w = w_kvb.reshape(R, H, dn + dv)
                q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0],
                                   w[..., :dn])

                def attend(m):
                    return _absorbed_step(q_lat, q_rope[:, 0], c[:, :m],
                                          k_rope[:, :m], mask[..., :m],
                                          scale)

                o_lat = prefix_step(positions, c.shape[1], attend)
                out = jnp.einsum("bhr,rhd->bhd", o_lat,
                                 w[..., dn:])[:, None]
        else:
            with jax.named_scope("mla.expand"):
                kv = jnp.dot(c, w_kvb).reshape(B, c.shape[1], H, dn + dv)
                k = jnp.concatenate(
                    [kv[..., :dn],
                     jnp.broadcast_to(k_rope[:, :, None, :],
                                      kv.shape[:3] + (dr,))], axis=-1)
                qf = jnp.concatenate([q_nope, q_rope], axis=-1)
            out = attention(qf, k, kv[..., dn:], mask, scale=scale,
                            impl=cfg.attention_impl, q_positions=positions)
        out = out.reshape(B, L, H * dv)
        return _dense(cfg.hidden_size, ("heads", "embed"), False, cfg,
                      "o_proj")(out), new_cache


def _l2norm(t):
    return t * jax.lax.rsqrt(
        jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)


def _short_conv(ext, w_conv, L: int):
    """Depthwise causal convolution: ext [B, taps - 1 + L, C] (the last
    inputs before the block, then the block), w_conv [taps, C] float32,
    the current token's tap last -> [B, L, C] float32."""
    return sum(ext[:, j:j + L].astype(jnp.float32) * w_conv[j]
               for j in range(w_conv.shape[0]))


def _conv_handover(ext, token_mask, taps: int):
    """The convolutions' inputs of each row's last ``taps - 1`` real
    tokens, for the next call: position p is row p + taps - 1 of
    ``ext`` [B, taps - 1 + L, C]."""
    B, L = ext.shape[0], ext.shape[1] - (taps - 1)
    n_real = (jnp.full((B,), L, jnp.int32) if token_mask is None
              else jnp.sum(token_mask, axis=1, dtype=jnp.int32))
    rows = n_real[:, None] + jnp.arange(taps - 1)[None, :]
    return jnp.take_along_axis(ext, rows[:, :, None], axis=1)


def _delta_rule(scope, q, k, v, g, beta, layer_cache, ext, token_mask):
    """The delta rule in the form the call needs (``ops/kda.py``): one
    new token against a cache takes the step, everything else the
    chunked form.  ``ext``: the convolutions' inputs, ``taps - 1`` rows
    of the past first.  Returns (o [B, L, H, dv] float32, the layer's
    new cache or None)."""
    from orion_tpu.ops.kda import kda_chunked, kda_step

    L = q.shape[1]
    if layer_cache is not None and L == 1:
        with jax.named_scope(scope + ".step"):
            o, S = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                            layer_cache["S"])
        return o[:, None], {"S": S, "conv": ext[:, 1:]}
    with jax.named_scope(scope + ".chunk"):
        o, S = kda_chunked(q, k, v, g, beta,
                           None if layer_cache is None else layer_cache["S"])
    if layer_cache is None:
        return o, None
    return o, {"S": S, "conv": _conv_handover(ext, token_mask,
                                              ext.shape[1] - L + 1)}


class StateKind(Kind):
    """A mixer whose cache entry is a state: not indexed by position,
    read and written whole a step.  What every such mixer keeps is its
    convolution's window, {"conv": [B, taps - 1, conv]}: the last inputs
    after the last token a row holds (a recurrence's own state is
    :class:`RecurrentKind`'s).  ``sizes(cfg)`` -> (state, taps, conv,
    projs, out): a recurrence's state (None without one), the window's
    sizes, the widths of the input projections it tags ``attn_qkv`` and
    the width of the output it tags ``attn_out``, held in ``out_dtype``
    (None: the compute dtype)."""

    cache_kind = "state"
    takes_token_mask = True
    leaves = ("conv",)
    out_dtype = None
    # what differs in lacks()
    state = "a convolution window"
    int8_of = "a convolution's last inputs, two rows a sequence"
    whose, handed = "a gated short convolution's", None     # None: state
    no_int8 = ("the int8 Dense twins do not reach this block (no "
               "QuantDense decode twin was run against its reference)")

    @classmethod
    def cache_entry(cls, cfg, batch, slots, dtype, pre=()):
        _, taps, conv, _, _ = cls.sizes(cfg)
        return {"conv": jnp.zeros(pre + (batch, taps - 1, conv), dtype)}

    @classmethod
    def _leaves(cls) -> str:
        return "{" + ", ".join(cls.leaves) + "}"

    @classmethod
    def check_entry(cls, layer_cache):
        if layer_cache is not None and cls.leaves[0] not in layer_cache:
            raise ValueError(
                f"{cls.__name__} caches {cls._leaves()} (init_cache): a "
                "state, not keys and values by position")

    @classmethod
    def tag_bytes(cls, cfg, rows, seq_len, w):
        *_, projs, out = cls.sizes(cfg)
        n = rows * seq_len
        return {"attn_qkv": n * sum(map(w, projs)) * _dt(cfg.dtype).itemsize,
                "attn_out": n * w(out)
                * _dt(cls.out_dtype or cfg.dtype).itemsize}

    @classmethod
    def lacks(cls, cfg):
        return {
            "paged": f"{cls.state} is not made of pages "
            "(init_paged_cache gives every layer pages)",
            "continuous": f"its cache manager holds no {cls.state[2:]} per "
            "slot (admission, preemption and prefix reuse move pages, and "
            f"a state is not made of pages: {cls.whose} {cls._leaves()})",
            "quantize_kv": f"{cls.state} has no int8 form "
            "(ops/quant.py scales keys and values per head; an int8 form "
            f"of {cls.int8_of} is not written)",
            "quantize_weights": cls.no_int8,
            "sequence_parallel": "there is no hand-over of "
            f"{cls.handed or cls.state} between sequence shards"}


class RecurrentKind(StateKind):
    """A state-kind mixer with a recurrence: its entry holds the
    recurrence's float32 state {"S": [B, *state]} before the window, and
    its tagged output is the recurrence's, float32."""

    leaves = ("S", "conv")
    out_dtype = "float32"
    state, int8_of = "a recurrent state", "a float32 recurrent state"
    whose = "a delta-rule layer's"

    @classmethod
    def cache_entry(cls, cfg, batch, slots, dtype, pre=()):
        return {"S": jnp.zeros(pre + (batch,) + cls.sizes(cfg)[0],
                               jnp.float32),
                **super().cache_entry(cfg, batch, slots, dtype, pre)}


class DeltaKind(RecurrentKind):
    """A mixer that runs the delta rule (``ops/kda.py``): its span
    attributes are the forms the one-token step and the chunked rule
    take in this process's traces (``kernel`` / ``jnp``)."""

    @classmethod
    def head_dims(cls, cfg):
        """(dk, dv) of a head's state."""
        return cls.sizes(cfg)[0][1:]

    @classmethod
    def decode_attrs(cls, cfg, lens, slots, new_tokens):
        from orion_tpu.ops.kda import step_form

        return {"kda_step": step_form(*cls.head_dims(cfg))}

    @classmethod
    def forward_attrs(cls, cfg, total_lens):
        from orion_tpu.ops.kda import chunk_form

        return {"kda_chunk": chunk_form(*cls.head_dims(cfg))}


def _delta_conv_init(*a):
    """U(-0.5, 0.5): torch's Conv1d default for a depthwise kernel of 4
    taps."""
    return nn.initializers.uniform(scale=1.0)(*a) - 0.5


def _delta_A_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _delta_dt_bias_init(key, shape, dtype):
    """softplus(dt_bias) log-uniform in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class KimiDeltaAttention(nn.Module, DeltaKind):
    """Kimi Delta Attention: the delta rule with a per-channel decay
    (``ops/kda.py``), ``kda_num_heads`` heads of ``kda_head_dim``.

    ``q~, k~, v~ = x W_q, x W_k, x W_v``, each through its own depthwise
    causal convolution of ``short_conv_kernel_size`` taps and SiLU; per
    head ``q = l2norm(q~) / sqrt(d)``, ``k = l2norm(k~)``, ``v = v~``;
    log decay ``g = -exp(A_log) * softplus(x W_fa W_fb + dt_bias)`` per
    head and key channel, step size ``beta = sigmoid(x W_b)`` per head,
    both float32; output ``W_o concat_h(RMSNorm(o) * sigmoid(x W_ga
    W_gb))``.  No position enters it.

    One new token against a cache (:class:`RecurrentKind`) takes
    :func:`ops.kda.kda_step`; everything else the chunked form.

    ``token_mask`` [B, L]: a position that holds no token leaves the
    state untouched (decay 1, step 0).  The mask must be a row's
    prefix (right padding, as the engine's prompts and the trainer's
    packed sequences are): the convolution of a real token then never
    reaches over padding, and the inputs handed on to decode are those
    of each row's last real tokens.
    """

    cfg: ModelConfig

    @staticmethod
    def sizes(cfg):
        H, d = cfg.kda_num_heads, cfg.kda_head_dim
        return ((H, d, d), cfg.short_conv_kernel_size, 3 * H * d,
                (H * d,) * 3, H * d)

    @nn.compact
    def __call__(self, x, positions, layer_cache=None, token_mask=None):
        cfg = self.cfg
        B, L, _ = x.shape
        H, d, taps = (cfg.kda_num_heads, cfg.kda_head_dim,
                      cfg.short_conv_kernel_size)
        wide = H * d
        f32, pdt = jnp.float32, _dt(cfg.param_dtype)
        self.check_entry(layer_cache)

        def param(name, init, shape, axes, dtype=pdt):
            return self.param(
                name, nn.with_logical_partitioning(init, axes), shape, dtype)

        proj = checkpoint_name(jnp.concatenate(
            [_dense(wide, ("embed", "heads"), False, cfg, n + "_proj")(x)
             for n in "qkv"], axis=-1), "attn_qkv")
        w_conv = jnp.concatenate(
            [param(n + "_conv", _delta_conv_init, (taps, wide),
                   ("conv", "heads")) for n in "qkv"], axis=-1).astype(f32)

        # The elementwise stretches between the matrix products and the
        # recurrence are checkpointed each on its own: a backward keeps
        # their bf16 inputs and recomputes the float32 in between (3 x
        # [B, L, 3 H d] of it in the first alone).
        @jax.checkpoint
        def convolve(ext, w_conv):
            q, k, v = (t.reshape(B, L, H, d) for t in jnp.split(
                nn.silu(_short_conv(ext, w_conv, L)), 3, axis=-1))
            # the recurrence takes them in the compute dtype (they are
            # operands of matrix products), in both of its forms
            return tuple(t.astype(_dt(cfg.dtype)) for t in
                         (_l2norm(q) * d ** -0.5, _l2norm(k), v))

        with jax.named_scope("kda.conv"):
            prev = (layer_cache["conv"] if layer_cache is not None
                    else jnp.zeros((B, taps - 1, 3 * wide), proj.dtype))
            ext = jnp.concatenate([prev.astype(proj.dtype), proj], axis=1)
            q, k, v = convolve(ext, w_conv)

        with jax.named_scope("kda.gate"):
            A_log = param("A_log", _delta_A_log_init, (H,), ("norm",), f32)
            dt_bias = param("dt_bias", _delta_dt_bias_init, (wide,),
                            ("heads",), f32)
            f = _dense(wide, ("latent", "heads"), False, cfg, "f_b_proj")(
                _dense(d, ("embed", "latent"), False, cfg, "f_a_proj")(x))
            b = _dense(H, ("embed", "norm"), False, cfg, "b_proj")(x)

            @jax.checkpoint
            def decay_and_step(f, b, A_log, dt_bias):
                g = -jnp.exp(A_log)[:, None] * jax.nn.softplus(
                    (f.astype(f32) + dt_bias).reshape(B, L, H, d))
                beta = jax.nn.sigmoid(b.astype(f32))
                if token_mask is not None:
                    g = jnp.where(token_mask[:, :, None, None], g, 0.0)
                    beta = jnp.where(token_mask[:, :, None], beta, 0.0)
                return g, beta

            g, beta = decay_and_step(f, b, A_log, dt_bias)

        o, new_cache = _delta_rule("kda", q, k, v, g, beta, layer_cache,
                                   ext, token_mask)
        o = checkpoint_name(o, "attn_out")

        gate = _dense(wide, ("latent", "heads"), False, cfg, "g_b_proj")(
            _dense(d, ("embed", "latent"), False, cfg, "g_a_proj")(x))
        o_norm = param("o_norm", nn.initializers.ones_init(), (d,),
                       ("norm",))

        @jax.checkpoint
        def norm_and_gate(o, gate, o_norm):
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1,
                                           keepdims=True) + cfg.rms_norm_eps)
            o = o * o_norm.astype(f32) * jax.nn.sigmoid(
                gate.astype(f32)).reshape(B, L, H, d)
            return o.astype(_dt(cfg.dtype)).reshape(B, L, wide)

        return _dense(cfg.hidden_size, ("heads", "embed"), False, cfg,
                      "o_proj")(norm_and_gate(o, gate, o_norm)), new_cache


class GatedDeltaNet(nn.Module, DeltaKind):
    """Gated DeltaNet under Olmo-Hybrid's ``linear_*`` keys: the delta
    rule of ``ops/kda.py`` with ONE decay a head, ``linear_num_key_heads``
    heads whose state is ``linear_key_head_dim`` x
    ``linear_value_head_dim`` (96 x 192: neither side a lane tile).

    ``q~, k~, v~ = x W_q, x W_k, x W_v`` (H dk, H dk, H dv wide), each
    through its own depthwise causal convolution of
    ``linear_conv_kernel_dim`` taps and SiLU; per head ``q = l2norm(q~)
    / sqrt(dk)``, ``k = l2norm(k~)``, ``v = v~``; log decay ``g =
    -exp(A_log) * softplus(x W_a + dt_bias)`` and step size ``beta =
    2 sigmoid(x W_b)`` (``linear_allow_neg_eigval``; else ``sigmoid``),
    one number a head each, float32; output ``W_o concat_h(RMSNorm_dv(o)
    * silu(x W_z))``.  No position enters it.

    Cache, ``token_mask`` and the two forms of the rule as
    :class:`KimiDeltaAttention`.
    """

    cfg: ModelConfig

    @staticmethod
    def sizes(cfg):
        H, dk, dv = (cfg.linear_num_key_heads, cfg.linear_key_head_dim,
                     cfg.linear_value_head_dim)
        wide = H * (2 * dk + dv)
        return (H, dk, dv), cfg.linear_conv_kernel_dim, wide, (wide,), H * dv

    @nn.compact
    def __call__(self, x, positions, layer_cache=None, token_mask=None):
        cfg = self.cfg
        B, L, _ = x.shape
        H, dk, dv, taps = (cfg.linear_num_key_heads, cfg.linear_key_head_dim,
                           cfg.linear_value_head_dim,
                           cfg.linear_conv_kernel_dim)
        # (value heads that share a key head are refused by the config)
        widths = {"q": H * dk, "k": H * dk,
                  "v": cfg.linear_num_value_heads * dv}
        f32, pdt, cdt = jnp.float32, _dt(cfg.param_dtype), _dt(cfg.dtype)
        self.check_entry(layer_cache)

        def param(name, init, shape, axes, dtype=pdt):
            return self.param(
                name, nn.with_logical_partitioning(init, axes), shape, dtype)

        proj = checkpoint_name(jnp.concatenate(
            [_dense(w, ("embed", "heads"), False, cfg, n + "_proj")(x)
             for n, w in widths.items()], axis=-1), "attn_qkv")
        w_conv = jnp.concatenate(
            [param(n + "_conv", _delta_conv_init, (taps, w),
                   ("conv", "heads")) for n, w in widths.items()],
            axis=-1).astype(f32)

        # checkpointed stretch by stretch, as KimiDeltaAttention's
        @jax.checkpoint
        def convolve(ext, w_conv):
            y = nn.silu(_short_conv(ext, w_conv, L))
            q, k, v = (t.reshape(B, L, H, -1) for t in jnp.split(
                y, (H * dk, 2 * H * dk), axis=-1))
            return tuple(t.astype(cdt) for t in
                         (_l2norm(q) * dk ** -0.5, _l2norm(k), v))

        with jax.named_scope("gdn.conv"):
            prev = (layer_cache["conv"] if layer_cache is not None
                    else jnp.zeros((B, taps - 1, proj.shape[-1]),
                                   proj.dtype))
            ext = jnp.concatenate([prev.astype(proj.dtype), proj], axis=1)
            q, k, v = convolve(ext, w_conv)

        with jax.named_scope("gdn.gate"):
            A_log = param("A_log", _delta_A_log_init, (H,), ("norm",), f32)
            dt_bias = param("dt_bias", _delta_dt_bias_init, (H,),
                            ("norm",), f32)
            a = _dense(H, ("embed", "norm"), False, cfg, "a_proj")(x)
            b = _dense(H, ("embed", "norm"), False, cfg, "b_proj")(x)
            top = 2.0 if cfg.linear_allow_neg_eigval else 1.0

            @jax.checkpoint
            def decay_and_step(a, b, A_log, dt_bias):
                g = -jnp.exp(A_log) * jax.nn.softplus(
                    a.astype(f32) + dt_bias)
                beta = top * jax.nn.sigmoid(b.astype(f32))
                if token_mask is not None:
                    g = jnp.where(token_mask[:, :, None], g, 0.0)
                    beta = jnp.where(token_mask[:, :, None], beta, 0.0)
                return g[..., None], beta       # one decay a head

            g, beta = decay_and_step(a, b, A_log, dt_bias)

        o, new_cache = _delta_rule("gdn", q, k, v, g, beta, layer_cache,
                                   ext, token_mask)
        o = checkpoint_name(o, "attn_out")

        z = _dense(H * dv, ("embed", "heads"), False, cfg, "z_proj")(x)
        o_norm = param("o_norm", nn.initializers.ones_init(), (dv,),
                       ("norm",))

        @jax.checkpoint
        def norm_and_gate(o, z, o_norm):
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1,
                                           keepdims=True) + cfg.rms_norm_eps)
            o = o * o_norm.astype(f32) * nn.silu(
                z.astype(f32)).reshape(B, L, H, dv)
            return o.astype(cdt).reshape(B, L, H * dv)

        return _dense(cfg.hidden_size, ("heads", "embed"), False, cfg,
                      "o_proj")(norm_and_gate(o, z, o_norm)), new_cache


class Mamba2(nn.Module, RecurrentKind):
    """A Mamba-2 mixer (nemotron_h's ``M``): the state-space recurrence
    of ``ops/mamba2.py`` on the H heads of ``mamba_head_dim`` P and the G
    groups that ``cfg.heads_held()`` leaves here, state ``ssm_state_size``
    N.

    ``[z | xBC | dt] = u W_in`` (widths H P, H P + 2 G N, H: ONE
    projection); ``xBC <- silu(conv(xBC) + b)``, ONE depthwise causal
    convolution of ``mamba_conv_kernel`` taps over x, B and C together;
    ``[x | B | C] = xBC``, x as [H, P], B and C as [G, N], head h reads
    group ``h // (H / G)``; ``dt = softplus(dt + dt_bias)``, ``A =
    -exp(A_log)``, one scalar a head each, float32; the recurrence; then
    ``W_out RMSNorm_g(y * silu(z))``: the gate first, the norm over each
    group's ``(H / G) P`` channels with a learned weight.  No position
    enters it.

    One new token against a cache (:class:`RecurrentKind`) takes
    :func:`ops.mamba2.mamba2_step`; everything else the chunked form.
    ``token_mask`` as :class:`KimiDeltaAttention`'s: a position that
    holds no token has ``dt = 0`` (decay 1, no input) and the mask must
    be a row's prefix.
    """

    cfg: ModelConfig

    whose, handed = "a state-space layer's", "a state-space layer's state"
    no_int8 = ("ops/quant.py was not run on a state-space layer's one "
               "input projection")

    @staticmethod
    def sizes(cfg):
        held = cfg.heads_held()
        H, P, N = held["mamba"], cfg.mamba_head_dim, cfg.ssm_state_size
        wide = H * P + 2 * held["groups"] * N
        # the input projection whole: [z | xBC | dt]
        return ((H, P, N), cfg.mamba_conv_kernel, wide,
                (H * P + wide + H,), H * P)

    @nn.compact
    def __call__(self, x, positions, layer_cache=None, token_mask=None):
        from orion_tpu.ops.mamba2 import mamba2_chunked, mamba2_step

        cfg = self.cfg
        B, L, _ = x.shape
        held = cfg.heads_held()
        H, G = held["mamba"], held["groups"]
        P, N, taps = (cfg.mamba_head_dim, cfg.ssm_state_size,
                      cfg.mamba_conv_kernel)
        d_in, wide = H * P, H * P + 2 * G * N
        f32, pdt, cdt = jnp.float32, _dt(cfg.param_dtype), _dt(cfg.dtype)
        self.check_entry(layer_cache)

        def param(name, init, shape, axes, dtype=pdt):
            return self.param(
                name, nn.with_logical_partitioning(init, axes), shape, dtype)

        proj = checkpoint_name(
            _dense(d_in + wide + H, ("embed", "ssm"), False, cfg,
                   "in_proj")(x), "attn_qkv")
        z, xBC, dt = jnp.split(proj, (d_in, d_in + wide), axis=-1)
        w_conv = param("conv_weight", _delta_conv_init, (taps, wide),
                       ("conv", "ssm")).astype(f32)
        b_conv = param("conv_bias", _delta_conv_init, (wide,),
                       ("ssm",)).astype(f32)
        A_log = param("A_log", _delta_A_log_init, (H,), ("norm",), f32)
        dt_bias = param("dt_bias", _delta_dt_bias_init, (H,), ("norm",), f32)
        D = param("D", nn.initializers.ones_init(), (H,), ("norm",), f32)

        # checkpointed stretch by stretch, as KimiDeltaAttention's
        @jax.checkpoint
        def convolve(ext, w_conv, b_conv, dt, dt_bias):
            y = nn.silu(_short_conv(ext, w_conv, L) + b_conv).astype(cdt)
            xs, Bm, Cm = jnp.split(y, (d_in, d_in + G * N), axis=-1)
            dt = jax.nn.softplus(dt.astype(f32) + dt_bias)
            if token_mask is not None:
                dt = jnp.where(token_mask[:, :, None], dt, 0.0)
            return (xs.reshape(B, L, H, P), Bm.reshape(B, L, G, N),
                    Cm.reshape(B, L, G, N), dt)

        with jax.named_scope("mamba2.conv"):
            prev = (layer_cache["conv"] if layer_cache is not None
                    else jnp.zeros((B, taps - 1, wide), xBC.dtype))
            ext = jnp.concatenate([prev.astype(xBC.dtype), xBC], axis=1)
            xs, Bm, Cm, dt = convolve(ext, w_conv, b_conv, dt, dt_bias)

        A = -jnp.exp(A_log)
        new_cache = None
        if layer_cache is not None and L == 1:
            with jax.named_scope("mamba2_step"):
                y, S = mamba2_step(xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                                   D, layer_cache["S"])
            y, new_cache = y[:, None], {"S": S, "conv": ext[:, 1:]}
        else:
            with jax.named_scope("mamba2_chunk"):
                y, S = mamba2_chunked(
                    xs, dt, A, Bm, Cm, D,
                    None if layer_cache is None else layer_cache["S"],
                    cfg.mamba_chunk_size)
            if layer_cache is not None:
                new_cache = {"S": S,
                             "conv": _conv_handover(ext, token_mask, taps)}
        y = checkpoint_name(y, "attn_out")
        norm = param("norm", nn.initializers.ones_init(), (d_in,), ("ssm",))

        @jax.checkpoint
        def gate_and_norm(y, z, norm):
            y = (y.reshape(B, L, d_in) * nn.silu(z.astype(f32))).reshape(
                B, L, G, d_in // G)
            y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1,
                                           keepdims=True) + cfg.rms_norm_eps)
            return (y.reshape(B, L, d_in) * norm.astype(f32)).astype(cdt)

        return _dense(cfg.hidden_size, ("ssm", "embed"), False, cfg,
                      "out_proj")(gate_and_norm(y, z, norm)), new_cache


class ShortConv(nn.Module, StateKind):
    """LFM2's gated short convolution (``layer_types`` entry ``conv``).

    ``[b | c | z] = u W_in`` (hidden -> 3 hidden, no bias, the thirds in
    this order); ``s = b * z``; ``v_t = sum_j w_j * s_{t - taps + 1 +
    j}``, a depthwise causal convolution of ``conv_L_cache`` taps, no
    bias, no activation, zeros before the sequence; ``W_out (c * v)``.
    No position enters it.

    ``s`` is held in the compute dtype (it is what the cache keeps:
    :class:`StateKind`, the last ``taps - 1`` of it a sequence, and
    nothing else); the taps' multiply-adds and the gate run in float32.
    One new token against a cache reads the kept rows and its own
    (``short_conv.step``); everything else is one pass over the
    sequence (``short_conv.chunk``).  ``token_mask`` as
    :class:`KimiDeltaAttention`'s: the mask must be a row's prefix, and
    prefill hands on each row's last real inputs.
    """

    cfg: ModelConfig

    @staticmethod
    def sizes(cfg):
        E = cfg.hidden_size
        return None, cfg.conv_L_cache, E, (3 * E,), E

    @staticmethod
    def forward_attrs(cfg, total_lens):
        """How many layers run it and over how many taps, and the
        experts this chip holds beside them (the benchmark's operation
        count reads all three off the span: it hard-codes no count)."""
        return {"conv_layers": sum(m == "conv" for m, _ in cfg.layer_kinds()),
                "conv_taps": cfg.conv_L_cache,
                "experts_held": cfg.experts_held}

    @staticmethod
    def decode_attrs(cfg, lens, slots, new_tokens):
        return ShortConv.forward_attrs(cfg, lens)

    @nn.compact
    def __call__(self, x, positions, layer_cache=None, token_mask=None):
        cfg = self.cfg
        B, L, E = x.shape
        taps = cfg.conv_L_cache
        f32 = jnp.float32
        self.check_entry(layer_cache)
        step = layer_cache is not None and L == 1

        proj = checkpoint_name(
            _dense(3 * E, ("embed", "gates"), False, cfg, "in_proj")(x),
            "attn_qkv")
        w_conv = self.param(
            "conv_weight", nn.with_logical_partitioning(
                _delta_conv_init, ("conv", "gates")), (taps, E),
            _dt(cfg.param_dtype)).astype(f32)
        prev = (layer_cache["conv"].astype(proj.dtype)
                if layer_cache is not None
                else jnp.zeros((B, taps - 1, E), proj.dtype))

        # one elementwise stretch between the two products: a backward
        # keeps its bf16 inputs and recomputes the float32 in between
        @jax.checkpoint
        def gate_and_convolve(proj, prev, w_conv):
            b, c, z = jnp.split(proj, 3, axis=-1)
            ext = jnp.concatenate([prev, b * z], axis=1)
            y = c.astype(f32) * _short_conv(ext, w_conv, L)
            return y.astype(proj.dtype), ext

        with jax.named_scope("short_conv.step" if step
                             else "short_conv.chunk"):
            y, ext = gate_and_convolve(proj, prev, w_conv)
        new_cache = None
        if step:
            new_cache = {"conv": ext[:, 1:]}
        elif layer_cache is not None:
            new_cache = {"conv": _conv_handover(ext, token_mask, taps)}
        y = checkpoint_name(y, "attn_out")
        return _dense(E, ("gates", "embed"), False, cfg,
                      "out_proj")(y), new_cache


class MLP(nn.Module, Kind):
    cfg: ModelConfig

    @staticmethod
    def tag_bytes(cfg, rows, seq_len, w):
        return {"mlp_pre": rows * seq_len * (2 if cfg.gated_mlp else 1)
                * w(cfg.intermediate_size) * _dt(cfg.dtype).itemsize}

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        if cfg.gated_mlp:
            gate = checkpoint_name(
                _dense(cfg.intermediate_size, ("embed", "mlp"),
                       cfg.mlp_bias, cfg, "gate_proj")(x), "mlp_pre")
            up = checkpoint_name(
                _dense(cfg.intermediate_size, ("embed", "mlp"),
                       cfg.mlp_bias, cfg, "up_proj")(x), "mlp_pre")
            h = nn.silu(gate) * up
            return _dense(cfg.hidden_size, ("mlp", "embed"),
                          cfg.mlp_bias, cfg, "down_proj")(h)
        h = checkpoint_name(
            _dense(cfg.intermediate_size, ("embed", "mlp"),
                   cfg.mlp_bias, cfg, "up_proj")(x), "mlp_pre")
        h = nn.gelu(h, approximate=False)
        return _dense(cfg.hidden_size, ("mlp", "embed"),
                      cfg.mlp_bias, cfg, "down_proj")(h)


#: ``ModelConfig.layer_kinds``' mixers -> the modules that implement
#: them and state what follows from them (:class:`Kind`).
MIXERS = {"attention": Attention, "window": WindowAttention,
          "sparse": SparseAttention,
          "latent": LatentAttention, "kda": KimiDeltaAttention,
          "gdn": GatedDeltaNet, "mamba2": Mamba2, "conv": ShortConv}


def mixer_spec(cfg: ModelConfig, kind: str):
    """(module class, its keywords) of the mixer ``kind`` in ``cfg``'s
    blocks: the one place where an arch picks anything."""
    kw = {}
    if cfg.arch == "olmo_hybrid" and kind == "attention":
        # rope_theta is published null (0 here): no rotation
        kw = {"qk_norm": True, "rotary": cfg.rope_theta > 0}
    elif cfg.arch in ("keye_dsa", "sdar_moe", "lfm2_moe", "mellum") \
            and issubclass(MIXERS[kind], Attention):
        kw = {"qk_norm": "head"}
    return MIXERS[kind], kw


def ffn_class(kind: str):
    """The module class of the feed-forward half ``kind``."""
    if kind == "dense":
        return MLP
    from orion_tpu.ops import moe

    return {"gshard": moe.MoEMLP, "experts": moe.TopKMoE}[kind]


def kinds(cfg: ModelConfig) -> tuple:
    """The kind classes of ``cfg``'s layers, each once: the mixers,
    then the feed-forward halves."""
    pairs = cfg.layer_kinds()
    return tuple(dict.fromkeys([MIXERS[m] for m, _ in pairs if m]
                               + [ffn_class(f) for _, f in pairs if f]))


#: What generation by diffusion over blocks (``ModelConfig.block_length``)
#: has none of, whatever the layers' kinds: :func:`cannot_run`'s forms.
BLOCK_DIFFUSION_LACKS = {
    "continuous": "the continuous engine admits, steps and retires a slot "
    "one token at a time; a block-diffusion slot's step is a block "
    "(admission at block boundaries, a cache step of block_length rows) "
    "and that engine has none",
    "speculative": "a draft is verified against one next-token "
    "distribution a row; a block is revealed out of order over several "
    "forwards",
    "paged": "the paged cache writes and the paged-attention kernel reads "
    "one token a row a step; a block's step writes block_length slots and "
    "sees them in both directions",
    "quantize_kv": "a block's keys and values are rewritten at every "
    "denoising step; an int8 cache under a step of block_length rows was "
    "not run against the reference",
    "quantize_weights": "the confidences that order a block's reveals "
    "would come from int8 weights and the update's trace "
    "log-probabilities from the float ones: another trace",
    "sequence_parallel": "the sequence-parallel attentions apply the "
    "causal rule by position; a block's positions see each other in both "
    "directions and a noisy stream sees its own block beside the clean "
    "stream's earlier ones",
}


#: What a stack run several times over (``ModelConfig.total_ut_steps``
#: > 1) has none of, whatever the layers' kinds: :func:`cannot_run`'s
#: forms, and ``pipeline`` (parallel/pipeline.py).
LOOPED_LACKS = {
    "continuous": "the continuous engine keeps one cache entry a layer "
    "and a slot; a stack run several times over keeps one for every "
    "(pass, layer), and that engine's slots and pages have no pass axis",
    "speculative": "speculative decoding exists on the continuous engine "
    "alone, whose cache has no entry for every (pass, layer)",
    "paged": "the page pool holds one entry a layer (ops/paged_kv.py: "
    "[num_layers] pages and block tables); pass t of a token reads pass "
    "t's keys and there are no pages for every (pass, layer)",
    "quantize_kv": "four passes compound an int8 cache's rounding and the "
    "int8 entries of every (pass, layer) were not run against the "
    "reference",
    "quantize_weights": "the int8 weights would be read once a pass and "
    "their rounding compounds over the passes; not run against the "
    "reference",
    "sequence_parallel": "the sequence-parallel attentions were not run "
    "against the reference inside the scan over passes",
    "pipeline": "a looped stack's stages form a ring (the last stage "
    "hands pass t's output to the first for pass t + 1); "
    "parallel/pipeline.py walks its stages once",
}


def cannot_run(cfg: ModelConfig, form: str) -> Optional[str]:
    """Why ``cfg``'s model cannot run under ``form`` (the keys of
    :meth:`Kind.lacks`): every layer kind's reason, each once, the
    generation rule's (:data:`BLOCK_DIFFUSION_LACKS`) and the looped
    stack's (:data:`LOOPED_LACKS`); None where nothing stands in the
    way."""
    reasons = dict.fromkeys(kind.lacks(cfg).get(form) for kind in kinds(cfg))
    if cfg.block_length:
        reasons[BLOCK_DIFFUSION_LACKS.get(form)] = None
    if cfg.total_ut_steps > 1:
        reasons[LOOPED_LACKS.get(form)] = None
    return "; ".join(r for r in reasons if r) or None


def ut_attrs(cfg: ModelConfig) -> dict:
    """What both spans carry of a stack run several times over:
    ``ut_steps``, the passes, and ``layer_visits``, the blocks a token
    passes; {} for every other model."""
    if cfg.total_ut_steps == 1:
        return {}
    return {"ut_steps": cfg.total_ut_steps,
            "layer_visits": cfg.layer_visits()}


def rl_fixed(cfg: ModelConfig) -> tuple:
    """The prefixes of the parameter names RL holds fixed: what the
    model's kinds name (``Kind.rl_fixed``) and the exit gate of a stack
    run several times over, which nothing in an RL loss reads."""
    fixed = tuple(p for kind in kinds(cfg) for p in kind.rl_fixed)
    return fixed + (("exit_gate",) if cfg.total_ut_steps > 1 else ())


def ut_weight_reads(cfg: ModelConfig, tree: dict) -> dict:
    """What a one-token step of a stack run several times over reads of
    ``tree`` (a decode copy of the weights, or their shapes; the
    Transformer's own or under a wrapper's ``backbone`` beside its
    heads): ``stack_weight_bytes``, the blocks', once a pass, and
    ``once_weight_bytes``, everything else, once a step (the final norm
    and the heads), but for what no step reads: the exit gate, and an
    embedding that is not the head, which is gathered by row.  {} for
    every other model."""
    if cfg.total_ut_steps == 1:
        return {}

    def size(t):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))

    trunk = tree.get("backbone", tree)
    stack = sum(size(v) for k, v in trunk.items() if k.startswith("layers"))
    unread = size(trunk["exit_gate"]) + (
        0 if cfg.tie_word_embeddings else size(trunk["embed"]))
    return {"stack_weight_bytes": stack,
            "once_weight_bytes": size(tree) - stack - unread}


def decode_attrs(cfg: ModelConfig, lens=None, slots: int = 0,
                 new_tokens: int = 0, quantized: bool = False) -> dict:
    """What the ``rollout.dispatch`` span carries of the model:
    ``attn_heads_a_step`` and ``kda_step`` (``""`` without a delta-rule
    layer) always; where the decode loop steps over a dense cache of
    ``slots`` slots (int8 under ``quantized``) after prompts of ``lens``
    real tokens (None: a page pool), each kind's own and how the
    one-token steps over a slot cache read it: ``kv_step_form``,
    ``kernel`` (``ops/pallas/dense_step.py``: each row's filled blocks),
    under :func:`prefix_step` ``prefix`` (the batch's filled blocks) /
    ``whole`` (a cache of one block), and ``kv_step_slots``, the slots
    one row's step reads a layer (mean); with :class:`Attention` layers
    ``kv_cache_lane_fill``, their cache's minor dimension over that
    dimension rounded up to the TPU's 128 lanes: what the HBM holds of
    K and V is the data over this (1.0 under the kernel, whose cache is
    ``Hkv * D`` minor; 0.5 at heads of 64 laid ``[.., Hkv, 64]``).  A
    stack run several times over says so (:func:`ut_attrs`); its
    ``kv_step_slots`` are one visit's."""
    attrs = {"kda_step": "", "attn_heads_a_step": cfg.attn_heads_a_step(),
             **ut_attrs(cfg)}
    if lens is None:
        return attrs
    of = kinds(cfg)
    kernel = Attention in of and Attention.kv_step_form(cfg, slots, quantized)
    if Attention in of:
        width = cfg.head_dim * (cfg.heads_held()["kv"] if kernel else 1)
        attrs["kv_cache_lane_fill"] = width / (-(-width // 128) * 128)
    if kernel:
        attrs.update(
            kv_step_form="kernel",
            kv_step_slots=dense_step.step_slots(lens, slots, new_tokens - 1))
    elif any(kind.steps_over_prefix for kind in of):
        attrs.update(
            kv_step_form="prefix" if len(prefix_lengths(slots)) > 1
            else "whole",
            kv_step_slots=prefix_step_slots(lens, slots, new_tokens))
    for kind in of:
        attrs.update(kind.decode_attrs(cfg, lens, slots, new_tokens))
    if cfg.block_length:
        attrs.update(block_decode_attrs(cfg, lens, slots, new_tokens))
    return attrs


def block_decode_attrs(cfg: ModelConfig, lens, slots: int,
                       new_tokens: int) -> dict:
    """What ``rollout.dispatch`` carries of a block-diffusion rollout
    after prompts of ``lens`` real tokens, where no row stops early:
    ``blocks`` the loop runs (the row that spans most decides),
    ``denoise_forwards``, the forwards of the loop, each one read of the
    weights (``denoising_steps`` a block), ``commit_rows``, the rows a
    sequence that ride in them beside a block's own ``block_length``
    (every block but the last the loop runs is committed in the first
    forward of the next: its ``block_length`` final tokens),
    ``kv_step_slots``, the slots a forward reads a layer (mean): its
    prefix of :func:`prefix_lengths` holds the block's last position,
    and ``decode_pairs``, the (query, key) pairs of the prompts' real
    tokens in prefill, of the ``denoising_steps`` forwards of every
    row's own blocks and of the commit of all but its last (a query has
    the keys through its block's end)."""
    Bd, S = cfg.block_length, cfg.denoising_steps
    lens = np.asarray(lens, np.int64)
    need = (lens % Bd + new_tokens - 1) // Bd + 1        # blocks a row
    blocks = int(np.max(need))
    ms = np.asarray(prefix_lengths(slots))
    ends = (int(np.max(lens // Bd)) + np.arange(blocks)) * Bd + Bd - 1

    def own(n, k):
        """The pairs of one forward over k blocks from prompt n's last."""
        return int(_seen_keys(
            np.arange(n // Bd * Bd, (n // Bd + k) * Bd), Bd).sum())

    pairs = sum(int(_seen_keys(np.arange(n), Bd).sum())
                + S * own(n, k) + own(n, k - 1) for n, k in zip(lens, need))
    return {"block_length": Bd, "denoising_steps": S, "blocks": blocks,
            "denoise_forwards": blocks * S,
            "commit_rows": (blocks - 1) * Bd, "decode_pairs": pairs,
            "kv_step_slots": float(
                ms[np.minimum(ends // ms[0], len(ms) - 1)].mean())}


def _seen_keys(positions, block: int):
    """The clean keys a query at each of ``positions`` sees under the
    clean rule: those through its block's end."""
    return (np.asarray(positions, np.int64) // block + 1) * block


def stream_attrs(cfg: ModelConfig, prompt_lens, seq_len: int,
                 new_tokens: int) -> dict:
    """What ONE trace forward of a block-diffusion model goes over for
    sequences of ``seq_len`` entries after prompts of ``prompt_lens``
    real tokens, ``new_tokens`` of them a completion's
    (trainers/base.py::_trace_forward; the experience forwards and the
    update's each make it): ``streams`` noisy streams a row, the
    ``clean_tokens`` and ``noisy_tokens`` entries of all rows,
    ``row_tokens``, all of a row's entries with what the noisy part is
    padded by (``ops.attention.noisy_length``), and ``trace_pairs``, the
    (query, key) pairs the two-part mask leaves the real entries: a
    clean or a noisy query at position p has the ``(p // block + 1)
    block`` keys through its block's end, clean ones and its own
    block's.  {} for every other model."""
    if not cfg.block_length:
        return {}
    Bd, S = cfg.block_length, cfg.denoising_steps
    noisy = S * cfg.blocks_spanned(new_tokens) * Bd
    pairs = 0
    for n in np.asarray(prompt_lens, np.int64):
        end = n + new_tokens
        pairs += int(_seen_keys(np.arange(end), Bd).sum()
                     + S * _seen_keys(np.arange(
                         n // Bd * Bd, -(-end // Bd) * Bd), Bd).sum())
    batch = len(prompt_lens)
    return {"streams": S, "clean_tokens": batch * seq_len,
            "noisy_tokens": batch * noisy, "trace_pairs": pairs,
            "row_tokens": batch * trace_row_length(cfg, seq_len, new_tokens)}


def update_attrs(cfg: ModelConfig, total_lens, prompt_lens=(),
                 seq_len: int = 0, new_tokens: int = 0) -> dict:
    """What the ``update`` span carries of the model for a batch of
    sequences of ``total_lens`` real tokens (``prompt_lens`` of them the
    prompts', padded to ``seq_len``, ``new_tokens`` a completion's
    window): :func:`stream_attrs`, ``attn_heads_a_step``,
    ``kda_chunk`` (``""`` without a delta-rule layer), each mixer's own
    and, for a model held in part (``head_share``), what of every layer
    this chip holds (the benchmark's operation counts read it): the
    state-space layers' heads and groups, attention's query and
    key-value heads, the routed experts; for a stack run several times
    over :func:`ut_attrs`, ``shared_grad_uses``, the uses whose
    gradients a block's parameter sums, and ``seq_tokens`` /
    ``causal_keys``, the batch's real tokens and the keys their queries
    see on one visit."""
    attrs = {"kda_chunk": "", "attn_heads_a_step": cfg.attn_heads_a_step(),
             **stream_attrs(cfg, prompt_lens, seq_len, new_tokens),
             **ut_attrs(cfg)}
    if cfg.total_ut_steps > 1:
        # a shared parameter's gradient is the sum over its uses; the
        # real tokens and their causal keys on ONE visit (the
        # benchmark's operation counts read them)
        n = np.asarray(total_lens, np.int64)
        attrs.update(shared_grad_uses=cfg.total_ut_steps,
                     seq_tokens=int(n.sum()),
                     causal_keys=int((n * (n + 1) // 2).sum()))
    for kind in kinds(cfg):
        attrs.update(kind.forward_attrs(cfg, total_lens))
    if cfg.head_share != (0, 1):
        held = cfg.heads_held()
        attrs.update(heads_held=held["mamba"], groups_held=held["groups"],
                     attn_heads_held=held["q"], kv_heads_held=held["kv"],
                     experts_held=cfg.experts_held)
    return attrs


def exit_masses(lams):
    """The exit masses [passes, ...] of a looped stack from its gates
    ``lams`` [passes, ...] (float32, each in (0, 1)): ``p_t = lambda_t
    prod_{j<t} (1 - lambda_j)`` and the last pass takes what is left,
    ``prod_{j<last} (1 - lambda_j)`` (its own gate is not read), so that
    they sum to 1."""
    stay = jnp.cumprod(1.0 - lams[:-1], axis=0)
    before = jnp.concatenate([jnp.ones_like(lams[:1]), stay], axis=0)
    return jnp.concatenate([lams[:-1] * before[:-1], before[-1:]], axis=0)


class Block(nn.Module):
    """One block of ``mixer`` and ``ffn`` (``ModelConfig.layer_kinds``;
    None: that half is absent).  ``a = x + Mixer(N1(x))``, ``y = a +
    FFN(N2(a))`` (``input_norm``, ``post_attn_norm``); under
    ``use_parallel_residual`` (GPT-NeoX) ``y = x + Mixer(N1(x)) +
    FFN(N2(x))``; under ``post_norm`` (the OLMo 2 / 3 order) ``a = x +
    N_a(Mixer(x))``, ``y = a + N_f(FFN(a))`` (``post_attn_norm``,
    ``post_mlp_norm``); under ``sandwich_norm`` (Ouro's order) ``a = x
    + N2(Mixer(N1(x)))``, ``y = a + N4(FFN(N3(a)))`` (``input_norm``,
    ``attn_out_norm``, ``post_attn_norm``, ``post_mlp_norm``).  A block
    of both halves tags ``a`` (``attn_resid``) unless they run in
    parallel."""

    cfg: ModelConfig
    mixer: Optional[str]
    ffn: Optional[str]

    @nn.compact
    def __call__(self, x, positions, layer_cache=None, token_mask=None,
                 visible=None):
        cfg = self.cfg
        if cfg.seq_shard_activations:
            from orion_tpu.parallel.sharding import \
                constrain_seq_activation as sp
        else:
            def sp(t):
                return t

        def norm(name, t, after: bool = False):
            here = cfg.sandwich_norm or after == cfg.post_norm
            return _norm(cfg, name)(t) if here else t

        # the sandwich order norms the mixer's output AND the MLP's input
        # (post_attn_norm, as in the pre-norm order): a name of its own
        after_mixer = "attn_out_norm" if cfg.sandwich_norm \
            else "post_attn_norm"

        def run(kind, module, *args, **kw):
            more = (token_mask,) if kind.takes_token_mask else ()
            return module(*args, *more, **kw)

        x = sp(x)
        h, new_cache = x, (None if layer_cache is None else {})
        if self.mixer is not None:
            kind, kw = mixer_spec(cfg, self.mixer)
            attn_out, new_cache = run(
                kind, kind(cfg, name="attn", **kw), norm("input_norm", x),
                positions, layer_cache,
                **({} if visible is None else {"visible": visible}))
            attn_out = norm(after_mixer, attn_out, after=True)
        if self.ffn is None:
            return sp(x + attn_out), new_cache
        kind = ffn_class(self.ffn)
        ffn = kind(cfg, name="mlp")
        if cfg.use_parallel_residual:
            mlp_out = run(kind, ffn, norm("post_attn_norm", x))
            out = x + attn_out + norm("post_mlp_norm", mlp_out, after=True)
            return sp(out), new_cache
        if self.mixer is not None:
            h = sp(checkpoint_name(x + attn_out, "attn_resid"))
        mlp_out = run(kind, ffn, norm("post_attn_norm", h))
        return sp(h + norm("post_mlp_norm", mlp_out, after=True)), new_cache


def _stack_names(cfg: ModelConfig) -> dict:
    """{first layer: parameter name} of the stretches that
    ``scan_layers`` stacks: every ``cfg.layer_runs`` stretch but a
    pattern model's leading dense layers.  One stack is ``layers`` (the
    layout of every model before there were patterns); several are
    ``layers_<first>to<last>``."""
    stacked = [(first, length) for first, length, _, ffn in cfg.layer_runs()
               if not (cfg.latent_attention and ffn == "dense")]
    if len(stacked) == 1:
        return {stacked[0][0]: "layers"}
    return {first: f"layers_{first}to{first + length - 1}"
            for first, length in stacked}


def _split_cache(cfg: ModelConfig, cache):
    """The cache of each ``cfg.layer_runs`` stretch: a list of per-layer
    entries, or the stacked pytree of a stretch that is scanned."""
    runs = cfg.layer_runs()
    if cache is None:
        return [None] * len(runs)
    if not cfg.scan_layers:
        return [cache[first:first + length] for first, length, _, _ in runs]
    if not isinstance(cache, dict) or "dense" not in cache:
        return [cache]                      # one stack, nothing beside it
    stacks = _stack_names(cfg)
    dense = iter(cache["dense"])
    stacked = iter(cache["runs"] if "runs" in cache else [cache["layers"]])
    return [next(stacked) if first in stacks else [next(dense)]
            for first, _, _, _ in runs]


def _join_cache(cfg: ModelConfig, run_caches):
    """Inverse of :func:`_split_cache`."""
    runs = cfg.layer_runs()
    if not cfg.scan_layers:
        return [c for rc in run_caches for c in rc]
    stacks = _stack_names(cfg)
    dense = [rc[0] for (first, _, _, _), rc in zip(runs, run_caches)
             if first not in stacks]
    stacked = [rc for (first, _, _, _), rc in zip(runs, run_caches)
               if first in stacks]
    if not dense and len(stacked) == 1:
        return stacked[0]
    if len(stacked) == 1:
        return {"dense": dense, "layers": stacked[0]}
    return {"dense": dense, "runs": stacked}


class Transformer(nn.Module):
    """Backbone + LM head.

    __call__ returns (logits_f32 [B, L, V], new_cache | None).
    ``return_hidden=True`` additionally returns final-norm hidden states
    (used by the value/reward heads).
    """

    cfg: ModelConfig

    @nn.compact
    def __call__(self, input_ids, positions, cache: Optional[KVCache] = None,
                 return_hidden: bool = False, skip_lm_head: bool = False,
                 logits_positions: Optional[jnp.ndarray] = None,
                 token_mask: Optional[jnp.ndarray] = None,
                 remat_keep: tuple = (),
                 visible: Optional[Visible] = None):
        """``logits_positions`` [B, T]: compute the vocab projection only
        at these sequence positions (ops.logprobs.completion_window_
        positions) — logits come back [B, T, V].  ``return_hidden``
        always returns the FULL [B, L, E] hidden states.
        ``token_mask`` [B, L] bool (``cfg.takes_token_mask``): which
        positions hold a token; the expert layers route the others
        nowhere and the recurrent mixers leave their state untouched.
        ``remat_keep``: the :data:`REMAT_TAGS` a block's checkpoint
        keeps (``cfg.remat``; nothing to a forward alone).
        ``visible``: what a block-diffusion model's attention masks by
        (:class:`Visible`; none: the clean rule on ``positions``)."""
        cfg = self.cfg
        embed = nn.Embed(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            dtype=_dt(cfg.dtype), param_dtype=_dt(cfg.param_dtype),
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("vocab", "embed")),
            name="embed")
        x = embed(input_ids)

        # One stretch of equal kinds at a time (cfg.layer_runs).  A
        # pattern model's leading dense layers, layers_<i> in both
        # layouts, stand outside the scanned stacks.
        runs = cfg.layer_runs()
        stacks = _stack_names(cfg)

        def build():
            """The stack's modules, one entry a stretch: a scanned stack,
            or its blocks.  (Called where their parameters live: this
            module's compact call, or the body of the scan over
            passes.)"""
            built = []
            for first, length, mixer, ffn in runs:
                cls = Block
                if cfg.remat:
                    cls = nn.remat(
                        Block, static_argnums=(),
                        policy=jax.checkpoint_policies
                        .save_only_these_names(*remat_keep)
                        if remat_keep else None)
                if cfg.scan_layers and first in stacks:
                    # One Block traced once, lax.scan over a stacked param
                    # tree [length, ...] — compile time is O(1) in depth
                    # (the VERDICT r1 "compile-time win" flag, now real).
                    # The cache is likewise a stacked pytree (see
                    # init_cache / init_paged_cache with
                    # scan_layers=True); positions are broadcast.  Param
                    # metadata gains a leading "layers" logical axis
                    # (replicated by LOGICAL_RULES).
                    scan_block = nn.scan(
                        cls,
                        # "intermediates" must be listed or nn.scan
                        # silently DROPS everything sown inside the
                        # scanned block — the MoE router aux loss would
                        # read as zero under scan_layers with no error.
                        variable_axes={"params": 0, "intermediates": 0,
                                       "selections": 0},
                        split_rngs={"params": True},
                        in_axes=(nn.broadcast, 0, nn.broadcast,
                                 nn.broadcast),
                        out_axes=0,
                        length=length,
                        metadata_params={nn.meta.PARTITION_NAME: "layers"},
                    )
                    built.append(scan_block(cfg, mixer, ffn,
                                            name=stacks[first]))
                else:
                    built.append([
                        cls(cfg, mixer, ffn, name=f"layers_{first + j}")
                        for j in range(length)])
            return built

        def walk(built, x, pass_cache, at=None):
            """One pass over the stack: (x, the pass's new cache).
            ``at``: the pass, where the unrolled layers' entries lead
            with a pass axis (each mixer reads and writes its own)."""
            new_caches = []
            for (first, length, _, _), rc, mods in zip(
                    runs, _split_cache(cfg, pass_cache), built):
                if cfg.scan_layers and first in stacks:
                    x, c = mods(x, positions, rc, token_mask, visible)
                    new_caches.append(c)
                else:
                    out = []
                    for j in range(length):
                        x, c = mods[j](
                            x, positions,
                            None if rc is None else rc[j] if at is None
                            else {**rc[j], "pass": at},
                            token_mask, visible)
                        out.append(c)
                    new_caches.append(out)
            return x, (None if pass_cache is None
                       else _join_cache(cfg, new_caches))

        passes = cfg.total_ut_steps
        if passes == 1:
            x, new_cache = walk(build(), x, cache)
            x = _norm(cfg, "final_norm")(x)
        else:
            # The stack run ``passes`` times over with ONE set of
            # parameters, the final norm after every pass (its output the
            # next pass's input and the pass's hidden state), pass t of a
            # token against pass t's cache entries.  The exit gate is read
            # where someone reads what it sows.
            def finish(mdl, x, fnorm, gate):
                x = fnorm(x)
                if not (mdl.is_mutable_collection("intermediates")
                        or mdl.is_initializing()):
                    return x, None
                return x, jax.nn.sigmoid(
                    gate(x).astype(jnp.float32))[..., 0]

            def exit_gate():
                return _dense(1, ("embed", None), True, cfg, "exit_gate")

            if cfg.scan_layers:
                # a scan over passes with the parameters broadcast, around
                # the scan over layers: one traced block, a shared
                # weight's gradient summed over its uses inside the
                # program
                def one_pass(mdl, x, pass_cache):
                    x, c = walk(build(), x, pass_cache)
                    x, lam = finish(mdl, x, _norm(cfg, "final_norm"),
                                    exit_gate())
                    return x, (c, lam)

                x, (new_cache, lams) = nn.scan(
                    one_pass, variable_broadcast="params",
                    variable_axes={"intermediates": 0, "selections": 0},
                    split_rngs={"params": False},
                    length=passes)(self, x, cache)
            elif cache is not None:
                # the decode twin: the layers unrolled, the passes a scan
                # that CARRIES the cache (every leaf [passes, B, slots,
                # ...], a mixer writing its pass's rows in place), so the
                # rollout's program holds one pass's blocks
                def one_pass(mdl, carry, t):
                    with jax.named_scope("ut.pass"):
                        x, c = walk(build(), carry[0], carry[1], t)
                        x, lam = finish(mdl, x, _norm(cfg, "final_norm"),
                                        exit_gate())
                    return (x, c), lam

                (x, new_cache), lams = nn.scan(
                    one_pass, variable_broadcast="params",
                    variable_axes={"intermediates": 0, "selections": 0},
                    split_rngs={"params": False})(
                        self, (x, cache), jnp.arange(passes))
            else:
                # unrolled and no cache: every block built once, called
                # once a pass
                built, fnorm, gate = build(), _norm(cfg, "final_norm"), \
                    exit_gate()
                new_cache, lams = None, []
                for t in range(passes):
                    with jax.named_scope("ut.pass"):
                        x, _ = walk(built, x, None)
                        x, lam = finish(self, x, fnorm, gate)
                    lams.append(lam)
                lams = None if lams[0] is None else jnp.stack(lams)
            if self.is_mutable_collection("intermediates"):
                self.sow("intermediates", "ut_exit_mass", exit_masses(lams))
        hidden = x
        if skip_lm_head:
            # Heads-only callers (critic/RM) skip the vocab projection —
            # at Llama-3 scale that is the largest matmul in the model
            # and its f32 logits would be materialized only to be
            # discarded.  lm_head params are never created on this path.
            return None, new_cache, hidden
        if logits_positions is not None:
            x = jnp.take_along_axis(x, logits_positions[..., None], axis=1)
        if cfg.tie_word_embeddings:
            logits = embed.attend(x)
        else:
            logits = _dense(cfg.vocab_size, ("embed", "vocab"),
                            False, cfg, "lm_head")(x)
        logits = logits.astype(jnp.float32)
        if return_hidden:
            return logits, new_cache, hidden
        return logits, new_cache


# ---------------------------------------------------------------------------
# Init / cache helpers
# ---------------------------------------------------------------------------


def sown(intermediates, name: str) -> list:
    """The arrays sown under ``name`` anywhere in an intermediates
    tree (stacked over layers under scan_layers)."""
    return [x for path, x in
            jax.tree_util.tree_flatten_with_path(intermediates)[0]
            if any(getattr(k, "key", None) == name for k in path)]


def cache_slots(max_len: int) -> int:
    """The slots :func:`init_cache` allocates for ``max_len`` positions.
    Round the length up to a multiple of 8: Mosaic tiles the cache
    axis and needs multiple-of-8 blocks (an unlucky max_len like 350
    = 2·5²·7 would otherwise force one full-length block — VMEM
    pressure at long context, found on-chip r5 via the speculative
    verify chunk).  Slots carry the slot==position causal rule, so
    the padded tail is masked for every real query."""
    return -(-max_len // 8) * 8


def cache_entry(cfg: ModelConfig, mixer: Optional[str], batch: int,
                slots: int, dtype, pre: tuple = (), quantized: bool = False):
    """The cache entry of one layer of ``mixer`` (what the mixer states;
    {} for a block without one), ``pre`` the leading axes of a stack."""
    if mixer is None:
        return {}
    kw = {"quantized": True} if quantized else {}      # Attention's alone
    return MIXERS[mixer].cache_entry(cfg, batch, slots, dtype, pre, **kw)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype: Optional[Any] = None, quantized: bool = False):
    """Dense pre-allocated cache: per layer what its mixer states
    (``cache_entry``; {} for a block without a mixer).  ``scan_layers``
    models use a stacked [num_layers, ...] pytree (scanned over axis
    0); unrolled models a per-layer list.  A stack run several times
    over (``total_ut_steps``) has an entry for every (pass, layer): a
    leading pass axis on every leaf, of a layer's entry in the list
    ([passes, B, slots, ...]) and of the stacked pytree ([passes,
    layers, B, slots, ...]).  ``quantized`` stores int8
    values with per-token-per-head f32 scales (RolloutConfig.quantize_kv
    — see ops/quant.py)."""
    dtype = dtype or _dt(cfg.dtype)
    slots = cache_slots(max_len)
    why = quantized and cannot_run(cfg, "quantize_kv")
    if why:
        raise ValueError("there is no int8 cache (rollout.quantize_kv) for "
                         f"arch={cfg.arch!r} yet: {why}")

    # the passes of a stack run several times over lead every leaf
    lead = (cfg.total_ut_steps,) if cfg.total_ut_steps > 1 else ()

    def entry(mixer, pre=()):
        return cache_entry(cfg, mixer, batch, slots, dtype, lead + pre,
                           quantized)

    stacks = _stack_names(cfg) if cfg.scan_layers else {}
    return _join_cache(cfg, [
        entry(mixer, (length,)) if first in stacks
        else [entry(mixer) for _ in range(length)]
        for first, length, mixer, _ in cfg.layer_runs()])


def make_decode_twin(model: nn.Module, cfg: ModelConfig):
    """(decode_model, decode_cfg) for the rollout engines: scan_layers
    models decode through an UNROLLED twin — the stacked [L, ...] cache
    carried through nn.scan defeats in-place cache updates and costs
    ~2x decode wall-clock (measured 2.3s -> 1.2s, pythia-1b B=32 T=128
    on v5e).  Pair with :func:`maybe_unstack_for_decode` on the params
    inside the jitted program; scan keeps its compile-time win on the
    train/update graphs.  Identity for unrolled models."""
    if not cfg.scan_layers:
        return model, cfg
    import dataclasses

    dcfg = dataclasses.replace(cfg, scan_layers=False)
    return type(model)(dcfg), dcfg


def maybe_unstack_for_decode(params: Any, cfg: ModelConfig):
    """Unstack scan-layout params for the decode twin (jit-safe
    constant-index slices XLA fuses); identity for unrolled models."""
    if not cfg.scan_layers:
        return params
    stacks = _stack_names(cfg)
    return unstack_params_tree(params, {
        stacks[first]: (first, length)
        for first, length, _, _ in cfg.layer_runs() if first in stacks})


def prep_decode_params(params: Any, cfg: ModelConfig,
                       quantize_weights: bool = False):
    """THE decode param-prep pipeline, shared by every engine path:
    compute-dtype cast (so each decode step reads 2 bytes/param, not 4
    + a per-op cast) → scan-layout unstack → optional int8 weight
    quantization.  Each transform is idempotent, so pre-processed
    trees pass through unchanged.  A prep-order change edits exactly
    one place."""
    cdt = jnp.dtype(cfg.dtype)
    if cdt != jnp.dtype(cfg.param_dtype):
        params = jax.tree.map(
            lambda x: x.astype(cdt)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    params = maybe_unstack_for_decode(params, cfg)
    if quantize_weights:
        from orion_tpu.ops.quant import quantize_params_int8

        params = quantize_params_int8(params)
    return params


def unstack_params_tree(params: Any, stacks: dict):
    """jit-safe inverse of the scan_layers stacking: every subtree
    holding a stacked entry named in ``stacks`` ({name: (first layer,
    length)}, see :func:`_stack_names`) becomes layers_<first>..
    subtrees (recursing through wrappers like ActorCriticModel's
    "backbone"; layers that stand unstacked beside a stack stay as they
    are).  XLA lowers the constant-index slices to views/copies
    it can fuse — used by the rollout engine to decode with an
    unrolled model twin (the stacked cache carried through nn.scan
    costs ~2x decode time; see RolloutEngine)."""
    if not isinstance(params, dict):
        return params
    out = {}
    for k, v in params.items():
        if k in stacks:
            first, length = stacks[k]
            for i in range(length):
                out[f"layers_{first + i}"] = jax.tree.map(
                    lambda x: x[i], v)
        elif isinstance(v, dict):
            out[k] = unstack_params_tree(v, stacks)
        else:
            out[k] = v
    return out


def init_params(model: nn.Module, rng: jax.Array, cfg: ModelConfig,
                unbox: bool = True):
    """Initialize params (tiny dummy batch).  Returns unboxed param tree."""
    ids = jnp.zeros((1, 2), jnp.int32)
    pos = jnp.zeros((1, 2), jnp.int32)
    variables = model.init(rng, ids, pos)
    params = variables["params"]
    return nn.meta.unbox(params) if unbox else params


def logical_specs(model: nn.Module, cfg: ModelConfig):
    """Pytree of logical-axis PartitionSpecs matching the param tree."""
    ids = jax.ShapeDtypeStruct((1, 2), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.key(0), ids, ids)
    return nn.get_partition_spec(variables)["params"]
