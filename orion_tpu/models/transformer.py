"""The decoder-only transformer (policy / reference / critic / RM backbone).

One configurable implementation covers the two model families the spec
requires (SURVEY.md §2 #14):

- ``arch="llama"``: RMSNorm, SwiGLU MLP, full rotary, optional GQA
  (Llama-3 family).
- ``arch="neox"``: LayerNorm with bias, parallel attention+MLP residual,
  partial rotary (``rotary_pct``), biased projections (Pythia family).
- ``arch="deepseek_v3"``: pre-norm RMSNorm, latent attention
  (:class:`LatentAttention`), a SwiGLU MLP in the first
  ``first_k_dense_replace`` layers and the dropless expert layer
  (``ops.moe.SigmoidTopKMoE``) after them; no biases, untied head.
  Under ``scan_layers`` the leading dense layers stay outside the
  scanned stack of expert layers.

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

from typing import Any, List, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from orion_tpu.config import ModelConfig
from orion_tpu.ops.attention import _NEG_INF, attention
from orion_tpu.ops.paged_kv import is_paged, write_paged_tokens
from orion_tpu.ops.rotary import apply_rotary

# Unrolled models: per-layer list of {"k": [B,L,Hkv,D], "v": ...}.
# scan_layers models: ONE stacked dict {"k": [N,B,L,Hkv,D], "v": ...}
# scanned over axis 0 (likewise for the paged-cache pytrees).
# deepseek_v3: a layer caches {"c": [B,L,kv_lora_rank], "k_rope":
# [B,L,qk_rope_head_dim]}; scan_layers models {"dense": [per layer],
# "layers": stacked} (the leading dense layers are not in the stack).
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


def remat_tag_bytes(cfg: ModelConfig, rows: int, seq_len: int,
                    lane: int = 1):
    """((tag, bytes), ...) in :data:`REMAT_TAGS` order, for the tags
    this model's blocks have: what keeping a tag holds over all layers
    for one minibatch of ``rows`` sequences of ``seq_len``, from the
    shapes alone.  ``lane``: the multiple a tensor's last dimension is
    padded to where it is held (128 on a TPU; 1 counts the elements).
    The attention tags are counted as the flash kernel leaves them: an
    implementation without tags keeps nothing under those names and is
    over-reckoned."""
    def w(d):
        return -(-d // lane) * lane

    n = rows * seq_len
    act = _dt(cfg.dtype).itemsize
    L, H = cfg.num_layers, cfg.num_heads
    route = 0
    if cfg.latent_attention:
        from orion_tpu.ops import moe
        from orion_tpu.ops.pallas.grouped_matmul import padded_rows

        lead = cfg.first_k_dense_replace
        shared = cfg.n_shared_experts * cfg.moe_intermediate_size
        qkv = H * (2 * w(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
                   + w(cfg.v_head_dim))
        out = H * w(cfg.v_head_dim)
        mlp = 2 * (lead * w(cfg.intermediate_size) + (L - lead) * w(shared))
        # scores [n, E] float32 and the selection [n, k] (twice: the
        # gather of the selected scores keeps its own indices); the
        # grouped form adds order [m], inverse [n k], sizes [held + 1]
        k = cfg.num_experts_per_tok
        route = n * (w(cfg.n_routed_experts) + 2 * w(k))
        if n > moe.DENSE_MAX_TOKENS:
            route += w(padded_rows(n * k)) + w(n * k) \
                + w(cfg.experts_held + 1)
        route *= 4 * (L - lead)
    else:
        qkv = (H + 2 * cfg.num_kv_heads) * w(cfg.head_dim)
        out = H * w(cfg.head_dim)
        mlp = 0 if cfg.num_experts else \
            L * (2 if cfg.arch == "llama" else 1) * w(cfg.intermediate_size)
    resid = 0 if cfg.use_parallel_residual else L * w(cfg.hidden_size)
    sizes = (route, n * resid * act, n * mlp * act,
             # out_t, and lse [rows, H, 1, seq_len] in float32
             L * (n * out * act + rows * H * w(seq_len) * 4),
             L * n * qkv * act)
    return tuple((t, b) for t, b in zip(REMAT_TAGS, sizes) if b)


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
    if cfg.arch in ("llama", "deepseek_v3"):
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


def _cache_writer(positions, B: int, L: int):
    """``write(cache, new)``: the L new entries of every sequence at
    slots starting at ``positions[:, 0]``."""
    starts = positions[:, 0]
    if L == 1:
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


class Attention(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x, positions, layer_cache=None):
        """x: [B, L, E]; positions: [B, L] absolute positions.

        layer_cache: {"k","v"} [B, Lmax, Hkv, D] or None.  When a cache
        is given, the L new keys/values are written at per-sequence
        slots starting at ``positions[:, 0]`` — one formula covers
        prefill (positions 0..L-1), chunked prefill (P..P+L-1) and
        decode (positions = current lengths).
        Returns (out [B, L, E], new_layer_cache).
        """
        cfg = self.cfg
        B, L, _ = x.shape
        H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

        q = _dense(H * D, ("embed", "heads"), cfg.attn_bias, cfg, "q_proj")(x)
        k = _dense(Hkv * D, ("embed", "kv_heads"), cfg.attn_bias, cfg, "k_proj")(x)
        v = _dense(Hkv * D, ("embed", "kv_heads"), cfg.attn_bias, cfg, "v_proj")(x)
        q = q.reshape(B, L, H, D)
        k = k.reshape(B, L, Hkv, D)
        v = v.reshape(B, L, Hkv, D)

        rotary_dim = int(D * cfg.rotary_pct)
        q, k = apply_rotary(q, k, positions, rotary_dim, cfg.rope_theta)

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
            write = _cache_writer(positions, B, L)

            if "k_scale" in layer_cache:
                # int8 KV cache (RolloutConfig.quantize_kv): quantize
                # the new tokens' K/V per (token, head) over D and
                # write both values and scales (ops/quant.py).
                from orion_tpu.ops.attention import (
                    int8_decode_attention as _int8_decode_attention)
                from orion_tpu.ops.quant import dequant_kv, quantize_kv
                kq_, ks_ = quantize_kv(k)
                vq_, vs_ = quantize_kv(v)
                new_cache = {
                    "k": write(layer_cache["k"], kq_),
                    "v": write(layer_cache["v"], vq_),
                    "k_scale": write(layer_cache["k_scale"], ks_),
                    "v_scale": write(layer_cache["v_scale"], vs_),
                }
                if L == 1:
                    # Decode: int8-specialized attention — scales land
                    # on scores/probs, the int8 cache operands enter
                    # the einsums as bare fused converts, and no
                    # dequantized [B, Lmax, Hkv, D] copy ever exists.
                    key_slots = jnp.arange(new_cache["k"].shape[1],
                                           dtype=positions.dtype)
                    mask = key_slots[None, None, :] <= positions[:, :, None]
                    paged_decode_out = _int8_decode_attention(
                        q, new_cache["k"], new_cache["k_scale"],
                        new_cache["v"], new_cache["v_scale"], mask,
                        scale)[:, 0]
                    keys = values = None
                else:
                    # Prefill: the standard attention below consumes
                    # the dequantized cache (convert+mul fuse into its
                    # operand reads).
                    keys = dequant_kv(new_cache["k"], new_cache["k_scale"],
                                      _dt(cfg.dtype))
                    values = dequant_kv(new_cache["v"],
                                        new_cache["v_scale"],
                                        _dt(cfg.dtype))
            else:
                ck = write(layer_cache["k"], k)
                cv = write(layer_cache["v"], v)
                new_cache = {"k": ck, "v": cv}
                keys, values = ck, cv
        else:
            new_cache = None
            keys, values = k, v

        if paged_decode_out is not None:
            out = paged_decode_out[:, None, :, :]
        else:
            # Mask: query at absolute position p attends to cache slots
            # j <= p.  Slots map 1:1 to absolute positions in the train,
            # prefill, decode and paged-gather paths (decode overwrites
            # the right-padded prompt tail slot by slot), so one formula
            # covers all of them.
            key_slots = jnp.arange(keys.shape[1], dtype=positions.dtype)
            mask = key_slots[None, None, :] <= positions[:, :, None]
            out = attention(q, keys, values, mask, scale=scale,
                            impl=cfg.attention_impl, q_positions=positions)
        out = out.reshape(B, L, H * D)
        out = _dense(cfg.hidden_size, ("heads", "embed"),
                     cfg.attn_bias, cfg, "o_proj")(out)
        return out, new_cache


class LatentAttention(nn.Module):
    """Multi-head latent attention (deepseek_v3, ``q_lora_rank: null``).

    ``q = h W_q`` per head is ``[q_nope ; q_rope]``; ``h W_kva`` is
    ``[c_raw ; k_rope_raw]``, ``c = RMSNorm(c_raw)``; ``c W_kvb`` per
    head is ``[k_nope ; v]``.  Rotary on ``q_rope`` of every head and on
    the one ``k_rope`` all heads share, the rotary features stored as
    adjacent pairs and brought to the half-split layout first
    (``rope_interleave``).  Scores ``q . [k_nope ; k_rope] /
    sqrt(nope + rope)``.

    The cache holds ``c`` and the rotated ``k_rope`` of a token, nothing
    per head.  Two paths compute the same attention:

    - **expand** (training; prefill and any step longer than one token):
      keys and values of every head are formed from ``c`` (of the whole
      cache, when there is one: slot == position as everywhere) and go
      through ``ops.attention`` (flash on the TPU);
    - **absorb** (one new token against the cache): with ``W_kvb``
      split per head into ``W_uk`` and ``W_uv``, ``score = (q_nope
      W_uk^T) . c + q_rope . k_rope`` and ``o = (P c) W_uv``, so no
      per-head key or value of the context is ever formed.
    """

    cfg: ModelConfig

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
        c = nn.RMSNorm(
            epsilon=cfg.rms_norm_eps, dtype=_dt(cfg.dtype),
            param_dtype=_dt(cfg.param_dtype),
            scale_init=nn.with_logical_partitioning(
                nn.initializers.ones_init(), ("norm",)),
            name="kv_a_norm")(kva[..., :R])
        # a bare kernel, not a Dense: the absorbed path applies it to
        # the query and the output, split per head
        w_kvb = self.param(
            "kv_b_proj", nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("latent", "heads")),
            (R, H * (dn + dv)), _dt(cfg.param_dtype)).astype(_dt(cfg.dtype))

        def halves(t):   # adjacent pairs -> half-split (rope_interleave)
            return jnp.concatenate([t[..., 0::2], t[..., 1::2]], axis=-1)

        q_nope, q_rope = q[..., :dn], halves(q[..., dn:])
        k_rope = halves(kva[..., R:])[:, :, None, :]          # one "head"
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
                scores = (jnp.einsum("bhr,blr->bhl", q_lat, c,
                                     preferred_element_type=jnp.float32)
                          + jnp.einsum("bhd,bld->bhl", q_rope[:, 0], k_rope,
                                       preferred_element_type=jnp.float32)
                          ) * scale
                scores = jnp.where(mask, scores, _NEG_INF)
                probs = jax.nn.softmax(scores, axis=-1).astype(c.dtype)
                o_lat = jnp.einsum("bhl,blr->bhr", probs, c)
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


class MLP(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        if cfg.arch in ("llama", "deepseek_v3"):
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


class Block(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x, positions, layer_cache=None):
        cfg = self.cfg
        sp = None
        if cfg.seq_shard_activations:
            from orion_tpu.parallel.sharding import constrain_seq_activation
            sp = constrain_seq_activation
            x = sp(x)
        if cfg.num_experts > 0:
            from orion_tpu.ops.moe import MoEMLP
            mlp_cls = MoEMLP
        else:
            mlp_cls = MLP
        if cfg.use_parallel_residual:
            # GPT-NeoX: x + attn(ln1(x)) + mlp(ln2(x))
            attn_out, new_cache = Attention(cfg, name="attn")(
                _norm(cfg, "input_norm")(x), positions, layer_cache)
            mlp_out = mlp_cls(cfg, name="mlp")(
                _norm(cfg, "post_attn_norm")(x))
            out = x + attn_out + mlp_out
            return (sp(out) if sp else out), new_cache
        attn_out, new_cache = Attention(cfg, name="attn")(
            _norm(cfg, "input_norm")(x), positions, layer_cache)
        h = checkpoint_name(x + attn_out, "attn_resid")
        if sp:
            h = sp(h)
        mlp_out = mlp_cls(cfg, name="mlp")(_norm(cfg, "post_attn_norm")(h))
        return (sp(h + mlp_out) if sp else h + mlp_out), new_cache


class LatentBlock(nn.Module):
    """deepseek_v3 block: ``a = x + Attn(N1(x))``, ``y = a + FFN(N2(a))``,
    FFN the SwiGLU MLP (``dense``) or the expert layer."""

    cfg: ModelConfig
    dense: bool = False

    @nn.compact
    def __call__(self, x, positions, layer_cache=None, token_mask=None):
        cfg = self.cfg
        attn_out, new_cache = LatentAttention(cfg, name="attn")(
            _norm(cfg, "input_norm")(x), positions, layer_cache)
        h = checkpoint_name(x + attn_out, "attn_resid")
        z = _norm(cfg, "post_attn_norm")(h)
        if self.dense:
            return h + MLP(cfg, name="mlp")(z), new_cache
        from orion_tpu.ops.moe import SigmoidTopKMoE
        return h + SigmoidTopKMoE(cfg, name="mlp")(z, token_mask), new_cache


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
                 remat_keep: tuple = ()):
        """``logits_positions`` [B, T]: compute the vocab projection only
        at these sequence positions (ops.logprobs.completion_window_
        positions) — logits come back [B, T, V].  ``return_hidden``
        always returns the FULL [B, L, E] hidden states.
        ``token_mask`` [B, L] bool (deepseek_v3 only): which positions
        hold a token; the expert layers route the others nowhere.
        ``remat_keep``: the :data:`REMAT_TAGS` a block's checkpoint
        keeps (``cfg.remat``; nothing to a forward alone)."""
        cfg = self.cfg
        embed = nn.Embed(
            num_embeddings=cfg.vocab_size, features=cfg.hidden_size,
            dtype=_dt(cfg.dtype), param_dtype=_dt(cfg.param_dtype),
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=0.02), ("vocab", "embed")),
            name="embed")
        x = embed(input_ids)

        block_cls = LatentBlock if cfg.latent_attention else Block
        if cfg.remat:
            block_cls = nn.remat(
                block_cls, static_argnums=(),
                policy=jax.checkpoint_policies.save_only_these_names(
                    *remat_keep) if remat_keep else None)
        # deepseek_v3: the leading dense layers, layers_0.. in both
        # layouts, stand outside the scanned stack of expert layers.
        n_lead = cfg.first_k_dense_replace if cfg.latent_attention else 0
        more = () if token_mask is None else (token_mask,)
        lead_cache = None
        if n_lead:
            if cache is not None:
                lead_cache = (cache["dense"] if cfg.scan_layers
                              else cache[:n_lead])
            new_lead = []
            for i in range(n_lead):
                x, c_i = block_cls(cfg, dense=True, name=f"layers_{i}")(
                    x, positions,
                    lead_cache[i] if lead_cache is not None else None)
                new_lead.append(c_i)

        if cfg.scan_layers:
            # One Block traced once, lax.scan over a stacked param tree
            # [num_layers, ...] — compile time is O(1) in depth (the
            # VERDICT r1 "compile-time win" flag, now real).  The cache
            # is likewise a stacked pytree (see init_cache /
            # init_paged_cache with scan_layers=True); positions are
            # broadcast.  Param metadata gains a leading "layers"
            # logical axis (replicated by LOGICAL_RULES).
            scan_block = nn.scan(
                block_cls,
                # "intermediates" must be listed or nn.scan silently
                # DROPS everything sown inside the scanned block — the
                # MoE router aux loss would read as zero under
                # scan_layers with no error.
                variable_axes={"params": 0, "intermediates": 0},
                split_rngs={"params": True},
                in_axes=(nn.broadcast, 0) + (nn.broadcast,) * len(more),
                out_axes=0,
                length=cfg.num_layers - n_lead,
                metadata_params={nn.meta.PARTITION_NAME: "layers"},
            )
            x, new_cache = scan_block(cfg, name="layers")(
                x, positions, cache["layers"] if lead_cache is not None
                else cache, *more)
            if cache is None:
                new_cache = None
            elif n_lead:
                new_cache = {"dense": new_lead, "layers": new_cache}
        else:
            new_cache = None
            if cache is not None:
                new_cache = list(new_lead) if n_lead else []
            for i in range(n_lead, cfg.num_layers):
                layer_cache = cache[i] if cache is not None else None
                x, new_layer_cache = block_cls(cfg, name=f"layers_{i}")(
                    x, positions, layer_cache, *more)
                if new_cache is not None:
                    new_cache.append(new_layer_cache)

        x = _norm(cfg, "final_norm")(x)
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


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype: Optional[Any] = None, quantized: bool = False):
    """Dense pre-allocated KV cache.  ``scan_layers`` models use a
    stacked [num_layers, ...] pytree (scanned over axis 0); unrolled
    models a per-layer list.  ``quantized`` stores int8 values with
    per-token-per-head f32 scales (RolloutConfig.quantize_kv — see
    ops/quant.py)."""
    dtype = dtype or _dt(cfg.dtype)
    # Round the length up to a multiple of 8: Mosaic tiles the cache
    # axis and needs multiple-of-8 blocks (an unlucky max_len like 350
    # = 2·5²·7 would otherwise force one full-length block — VMEM
    # pressure at long context, found on-chip r5 via the speculative
    # verify chunk).  Slots carry the slot==position causal rule, so
    # the padded tail is masked for every real query.
    max_len = -(-max_len // 8) * 8
    if cfg.latent_attention:
        if quantized:
            raise ValueError(
                "there is no int8 latent cache (rollout.quantize_kv) for "
                "arch='deepseek_v3' yet: ops/quant.py scales per head")

        def latent(pre=()):
            return {"c": jnp.zeros(pre + (batch, max_len, cfg.kv_lora_rank),
                                   dtype),
                    "k_rope": jnp.zeros(
                        pre + (batch, max_len, cfg.qk_rope_head_dim), dtype)}

        n_lead = cfg.first_k_dense_replace
        if cfg.scan_layers and n_lead:
            return {"dense": [latent() for _ in range(n_lead)],
                    "layers": latent((cfg.num_layers - n_lead,))}
        if cfg.scan_layers:
            return latent((cfg.num_layers,))
        return [latent() for _ in range(cfg.num_layers)]
    shape = (batch, max_len, cfg.num_kv_heads, cfg.head_dim)

    def layer(pre=()):
        if quantized:
            return {"k": jnp.zeros(pre + shape, jnp.int8),
                    "v": jnp.zeros(pre + shape, jnp.int8),
                    "k_scale": jnp.zeros(pre + shape[:-1], jnp.float32),
                    "v_scale": jnp.zeros(pre + shape[:-1], jnp.float32)}
        return {"k": jnp.zeros(pre + shape, dtype),
                "v": jnp.zeros(pre + shape, dtype)}

    if cfg.scan_layers:
        return layer((cfg.num_layers,))
    return [layer() for _ in range(cfg.num_layers)]


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
    n_lead = cfg.first_k_dense_replace if cfg.latent_attention else 0
    return unstack_params_tree(params, cfg.num_layers - n_lead, n_lead)


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


def unstack_params_tree(params: Any, num_layers: int, first: int = 0):
    """jit-safe inverse of the scan_layers stacking: every subtree
    holding a stacked "layers" entry [L, ...] becomes
    layers_<first>..<first+L-1> subtrees (recursing through wrappers
    like ActorCriticModel's "backbone"; ``first`` > 0 where leading
    layers already stand unstacked beside the stack).  XLA lowers the constant-index slices to views/copies
    it can fuse — used by the rollout engine to decode with an
    unrolled model twin (the stacked cache carried through nn.scan
    costs ~2x decode time; see RolloutEngine)."""
    if not isinstance(params, dict):
        return params
    out = {}
    for k, v in params.items():
        if k == "layers":
            for i in range(num_layers):
                out[f"layers_{first + i}"] = jax.tree.map(
                    lambda x: x[i], v)
        elif isinstance(v, dict):
            out[k] = unstack_params_tree(v, num_layers, first)
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
