"""Configuration dataclasses for every subsystem.

Plain dataclasses + a tiny yaml/flag loader (SURVEY.md §5 "Config/flag
system"): per-algorithm configs subclass a common ``TrainConfig`` the way
the reference's PPO/DPO/RLOO/GRPO configs share a common trainer config.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


#: The archs whose attention is latent
#: (``models.transformer.LatentAttention``).
LATENT_ARCHS = ("deepseek_v3", "kimi_linear")
#: The archs whose model is a per-layer pattern of (mixer, FFN) kinds
#: over RMSNorm blocks (``ModelConfig.layer_kinds``).
PATTERN_ARCHS = LATENT_ARCHS + ("olmo_hybrid", "keye_dsa", "nemotron_h",
                                "sdar_moe", "lfm2_moe", "mellum", "ouro")
#: The archs whose layers end in the dropless expert layer
#: (``ops.moe.TopKMoE``) and so share its fields and their checks.
EXPERT_ARCHS = LATENT_ARCHS + ("keye_dsa", "nemotron_h", "sdar_moe",
                               "lfm2_moe", "mellum")
#: nemotron_h's ``hybrid_override_pattern`` characters -> (mixer, ffn)
#: halves of a block.
PATTERN_HALVES = {"M": ("mamba2", None), "*": ("attention", None),
                  "E": (None, "experts")}
#: The published ``layer_types`` entries -> mixers, by arch.
LAYER_TYPE_MIXERS = {
    "olmo_hybrid": {"linear_attention": "gdn", "full_attention": "attention"},
    "lfm2_moe": {"conv": "conv", "full_attention": "attention"},
    "mellum": {"sliding_attention": "window", "full_attention": "attention"},
    "ouro": {"full_attention": "attention"}}


@dataclass
class ModelConfig:
    """Architecture hyperparameters for the decoder-only transformer:
    flat fields under the published key names of eleven model families,
    a ``_check_<arch>`` each, and the model's description derived from
    them, :meth:`layer_kinds`: one (mixer, feed-forward) pair per block.
    What follows from a kind is ``models/transformer.py``'s
    (``MIXERS``); the properties here that speak of kinds read it there.
    """

    # the family whose published keys and checks apply: "llama" | "neox"
    # | "deepseek_v3" | "kimi_linear" | "olmo_hybrid" | "keye_dsa"
    # | "nemotron_h" | "sdar_moe" | "lfm2_moe" | "mellum" | "ouro"
    arch: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 512
    intermediate_size: int = 1376
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: int = 8  # < num_heads => GQA (llama only)
    head_dim: int = 0  # 0 => hidden_size // num_heads
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0  # neox uses 0.25
    rms_norm_eps: float = 1e-5
    layernorm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    use_parallel_residual: bool = False  # neox style
    attn_bias: bool = False  # neox uses biases everywhere
    mlp_bias: bool = False
    dtype: str = "bfloat16"  # compute dtype
    param_dtype: str = "float32"  # master weights
    # jax.checkpoint each block: recompute in the backward what does not
    # fit.  Kept is the block's input and, in a trainer's update, the
    # tagged tensors (models/transformer.py REMAT_TAGS) that the device's
    # free memory holds; nothing more where no budget is known (the CPU).
    remat: bool = False
    attention_impl: str = "auto"  # "auto" | "reference" | "flash" | "ring"
    scan_layers: bool = False  # lax.scan over stacked layers (compile-time win)
    # Dense layers read int8 kernels (QuantDense layout — see
    # ops/quant.py).  Set only on the rollout engines' decode twin when
    # RolloutConfig.quantize_weights is on; never on a training model.
    quantize_dense: bool = False
    # Megatron-style sequence parallelism: residual-stream activations
    # between blocks sharded on seq over the TENSOR axis (GSPMD emits
    # the megatron AG/RS pattern; norms compute on L/tp tokens).  See
    # parallel.sharding.constrain_seq_activation.
    seq_shard_activations: bool = False
    # Mixture-of-Experts (ops.moe): 0 = dense MLP; > 0 replaces every
    # block's MLP with a top-2-routed expert bank of this size, stacked
    # on the "expert" logical axis (expert parallelism over the mesh's
    # ``expert`` dim).  capacity_factor bounds tokens/expert (GShard).
    num_experts: int = 0
    expert_capacity_factor: float = 1.25
    # Weight of the Switch load-balance auxiliary loss (consumed by the
    # trainer loss paths via BaseTrainer._logprobs_fn's aux output).
    router_aux_coef: float = 0.01
    # arch="deepseek_v3" (models/transformer.py: latent attention, a
    # dropless sigmoid-routed expert layer after first_k_dense_replace
    # dense layers), under the published key names.  The keys above
    # keep their meaning: intermediate_size is the dense layers' width,
    # num_experts stays 0 (that is the GShard layer's switch).
    kv_lora_rank: int = 0          # width of the cached latent
    qk_nope_head_dim: int = 0      # per-head key width without rotary
    qk_rope_head_dim: int = 0      # the one rotary key all heads share
    v_head_dim: int = 0
    n_routed_experts: int = 0      # the router's width: ALL experts
    num_experts_per_tok: int = 0
    n_shared_experts: int = 0      # ONE shared expert of n x its width
    moe_intermediate_size: int = 0
    first_k_dense_replace: int = 0
    routed_scaling_factor: float = 1.0
    # The chip's share under expert parallelism: this layer holds
    # experts_held consecutive experts from expert_offset on (0 held =>
    # all of them).  The router still scores and selects over all
    # n_routed_experts; what the absent experts would add is left out.
    experts_held: int = 0
    expert_offset: int = 0
    # arch="kimi_linear": the deepseek_v3 block with a mixer per layer.
    # The published linear_attn_config.kda_layers, whole and 1-based:
    # layer i runs the delta-rule mixer (ops/kda.py) where i is in it and
    # latent attention where it is not (the published full_attn_layers
    # is its complement); the model reads the entries up to num_layers.
    # mla_use_nope: the latent layers rotate nothing.
    kda_layers: tuple = ()
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    short_conv_kernel_size: int = 0
    mla_use_nope: bool = False
    # arch="olmo_hybrid": a post-norm RMSNorm block over a dense SwiGLU,
    # its mixer per layer by the published layer_types, whole:
    # "linear_attention" (the gated delta rule, models/transformer.py
    # GatedDeltaNet: one decay a head, heads of linear_key_head_dim x
    # linear_value_head_dim) or "full_attention" (num_heads heads of
    # head_dim with a per-head cache, a norm over the whole query and
    # key projections, rotated only where rope_theta > 0: the published
    # value is null); the model reads the entries up to num_layers.
    # linear_allow_neg_eigval: the step size runs to 2, not 1.
    layer_types: tuple = ()
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 0
    linear_allow_neg_eigval: bool = False
    # arch="keye_dsa" (Keye-VL-2.0's language model): the pre-norm
    # RMSNorm block of grouped-query attention (an RMSNorm over each
    # head's head_dim of q and of k, full rotary) over the dropless
    # expert layer, its attention cut to the sa_topk keys a query that a
    # learned indexer scores highest (models/transformer.py
    # SparseAttention, ops/indexer.py): sa_index_heads query heads of
    # sa_index_head_dim against ONE key head (the published sa_config:
    # indexer_num_heads, indexer_head_dim, indexer_num_kv_heads = 1,
    # topk); sa_q_chunk / sa_kv_chunk tile the indexer's scores and
    # change no equation.  moe_scoring: how the expert layer's router
    # scores ("sigmoid": deepseek_v3's, with its selection bias;
    # "softmax": a float32 softmax over all experts, no bias;
    # norm_topk_prob true in both: gates sum to routed_scaling_factor).
    sa_topk: int = 0
    sa_index_heads: int = 0
    sa_index_head_dim: int = 0
    sa_q_chunk: int = 512
    sa_kv_chunk: int = 512
    moe_scoring: str = "sigmoid"
    # arch="nemotron_h" (NVIDIA Nemotron-3's language model): pre-norm
    # RMSNorm blocks whose halves hybrid_override_pattern names, one
    # character a published layer, read up to num_layers characters: "M"
    # a Mamba-2 mixer (models/transformer.py Mamba2, ops/mamba2.py:
    # mamba_num_heads heads of mamba_head_dim, a float32 state of
    # head_dim x ssm_state_size a head, B and C shared by the heads of
    # one of mamba_n_groups groups (the published n_groups), ONE
    # depthwise convolution of mamba_conv_kernel taps over x, B and C,
    # chunks of mamba_chunk_size), "*" grouped-query attention, "E" the
    # expert layer.  A mixer and an "E" behind it are one block of this
    # repo; any other character is a block with that half alone
    # (layer_kinds).  The expert layer's own keys: moe_activation
    # ("swiglu": silu(gate) * up of a fused gate|up product; "relu2":
    # relu(up)^2, no gate), moe_latent_size > 0: the routed experts
    # work in a latent of that width between fc1_latent_proj and
    # fc2_latent_proj (router and shared expert read the block's own
    # width), moe_shared_expert_intermediate_size > 0: the shared
    # expert's own width (0: moe_intermediate_size).
    hybrid_override_pattern: str = ""
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    mamba_n_groups: int = 0
    ssm_state_size: int = 0
    mamba_conv_kernel: int = 0
    mamba_chunk_size: int = 128
    moe_activation: str = "swiglu"
    moe_latent_size: int = 0
    moe_shared_expert_intermediate_size: int = 0
    # The chip's share of every mixer's heads under tensor parallelism:
    # (which share, of how many).  A share holds its consecutive part of
    # the heads (num_heads, mamba_num_heads: the keys keep the published
    # counts) and what follows from them: a Mamba-2 layer's groups, and
    # the key-value heads its query heads read (one repeated where the
    # shares outnumber them).  What the absent heads would add to a
    # mixer's output is left out, as for the experts.  (0, 1): all.
    head_share: tuple = (0, 1)
    # arch="sdar_moe" (SDAR's language model): keye_dsa's block without
    # the indexer (grouped-query attention with the per-head q/k norm
    # over softmax-routed experts) that GENERATES BY DIFFUSION OVER
    # BLOCKS.  Positions fall into blocks of block_length, aligned to
    # position 0; a key is visible to a query iff its block is not a
    # later one (causal across blocks, both directions inside one); the
    # logit AT a position scores the token AT it (no shift).  A block
    # starts as mask_token_id wherever the prompt does not reach and is
    # revealed over denoising_steps forwards, block_length /
    # denoising_steps positions a forward, those of the highest
    # confidence (rollout/engine.py); a completion's log-probabilities
    # are those of its sampling trace (trainers/base.py).  These three
    # are the model's description, not a speed knob: no flag turns the
    # rule off.  mask_token_id counts from the vocabulary's end when
    # negative (-1: its last row, whatever slice of it a chip holds:
    # ``mask_id``).
    block_length: int = 0
    denoising_steps: int = 0
    mask_token_id: int = -1
    # arch="lfm2_moe" (LiquidAI's LFM2 with experts): pre-norm RMSNorm
    # blocks whose mixer the published layer_types names, whole and read
    # up to num_layers: "conv", a gated short convolution
    # (models/transformer.py ShortConv: [b | c | z] = u W_in, a depthwise
    # causal convolution of conv_L_cache taps over b * z with no bias and
    # no activation, W_out(c * conv); it keeps the convolution's last
    # conv_L_cache - 1 inputs a sequence and nothing else), or
    # "full_attention" (grouped-query attention with keye_dsa's per-head
    # q/k norm and full rotary).  The first first_k_dense_replace layers
    # (the published num_dense_layers) end in a dense SwiGLU of
    # intermediate_size, the others in the dropless expert layer with
    # sigmoid scores and the selection bias (the published
    # use_expert_bias); the head is the embedding (tie_word_embeddings).
    conv_L_cache: int = 0
    # arch="mellum" (JetBrains' Mellum 2): the pre-norm RMSNorm block of
    # grouped-query attention (keye_dsa's per-head q/k norm, full rotary)
    # over the dropless expert layer with softmax scores, its attention
    # per layer by the published layer_types, whole and read up to
    # num_layers: "sliding_attention" (models/transformer.py
    # WindowAttention: a query at position t sees the keys s <= t with
    # t - s < sliding_window, itself and the sliding_window - 1 before
    # it; its cache is a ring of sliding_window slots written at position
    # mod sliding_window) or "full_attention" (every key s <= t, a cache
    # of max_seq_len slots).  rope_parameters, as published, one entry a
    # layer type: {"rope_type": "default" | "yarn", "rope_theta", and for
    # yarn "factor", "original_max_position_embeddings", "beta_fast",
    # "beta_slow", "attention_factor"} (ops/rotary.py inv_frequencies;
    # the factor multiplies cos and sin); a layer type without an entry
    # rotates by rope_theta.
    sliding_window: int = 0
    rope_parameters: dict = field(default_factory=dict)
    # arch="ouro" (ByteDance's Ouro, a looped language model): ONE stack
    # of num_layers blocks run total_ut_steps times over with the same
    # parameters (models/transformer.py Transformer: pass t's input is
    # pass t - 1's output under the one final RMSNorm, which is applied
    # after every pass), each block in the sandwich order, four RMSNorms
    # around multi-head attention (layer_types all "full_attention", full
    # rotary at the token's position in every pass, no q/k norm, no bias)
    # and a dense SwiGLU; a key-value cache entry for every (pass, layer):
    # pass t of a token reads pass t's keys of the earlier tokens.  After
    # every pass a gate lambda_t = sigmoid(H_t w_g + b_g) gives the exit
    # masses p_t = lambda_t prod_{j<t} (1 - lambda_j), the last pass
    # taking the rest; the hidden state used is that of the first pass at
    # which the cumulative mass reaches early_exit_threshold: at the
    # published 1 the last pass's, for every token (no other value runs).
    total_ut_steps: int = 1
    early_exit_threshold: float = 1.0  # orion: ignore[config-drift] a published key that _check_ouro holds to 1: every token runs every pass, so no program reads it

    def __post_init__(self) -> None:
        if self.arch in EXPERT_ARCHS:
            self._check_experts()
        if self.arch in LATENT_ARCHS:
            self._check_deepseek_v3()
        if self.arch == "keye_dsa":
            self._check_keye_dsa()
        if self.arch == "kimi_linear":
            self._check_kimi_linear()
        if self.arch == "sdar_moe":
            self._check_sdar_moe()
        elif self.block_length or self.denoising_steps:
            raise ValueError(
                f"model.block_length={self.block_length}: only arch="
                "'sdar_moe' generates by diffusion over blocks")
        if self.arch == "olmo_hybrid":
            self._check_olmo_hybrid()
        if self.arch == "lfm2_moe":
            self._check_lfm2_moe()
        if self.arch == "mellum":
            self._check_mellum()
        elif self.sliding_window or self.rope_parameters:
            raise ValueError(
                f"model.sliding_window={self.sliding_window} / "
                "model.rope_parameters: only arch='mellum' has windowed "
                "layers and rotary parameters by layer type")
        if self.arch == "ouro":
            self._check_ouro()
        elif self.total_ut_steps != 1 or self.early_exit_threshold != 1.0:
            raise ValueError(
                f"model.total_ut_steps={self.total_ut_steps} / "
                f"model.early_exit_threshold={self.early_exit_threshold}: "
                "only arch='ouro' runs its stack several times over")
        self.head_share = tuple(self.head_share)
        if self.arch == "nemotron_h":
            self._check_nemotron_h()
        elif self.head_share != (0, 1):
            raise ValueError(
                f"model.head_share={self.head_share}: only arch="
                "'nemotron_h' was run against its reference on a share of "
                "its heads")
        if self.head_dim == 0:
            self.head_dim = self.hidden_size // self.num_heads
        if self.arch == "neox":
            # GPT-NeoX has no GQA.  (use_parallel_residual stays as
            # given — NeoX-family checkpoints exist with either value.)
            self.num_kv_heads = self.num_heads
        if self.attention_impl in ("ring", "ulysses"):
            from orion_tpu.models.transformer import cannot_run

            why = cannot_run(self, "sequence_parallel")
            if why:
                raise ValueError(
                    f"attention_impl={self.attention_impl!r} cannot run "
                    f"arch={self.arch!r}: {why}")

    def _check_experts(self) -> None:
        for key in ("n_routed_experts", "num_experts_per_tok",
                    "moe_intermediate_size"):
            if getattr(self, key) <= 0:
                raise ValueError(f"arch={self.arch!r} needs model.{key} > 0")
        if self.experts_held == 0:
            self.experts_held = self.n_routed_experts
        if not (0 <= self.expert_offset and self.expert_offset
                + self.experts_held <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.expert_offset}..+{self.experts_held} are "
                f"not among the {self.n_routed_experts} routed experts")
        if self.moe_scoring not in ("sigmoid", "softmax"):
            raise ValueError(
                f"model.moe_scoring={self.moe_scoring!r}: 'sigmoid' or "
                "'softmax'")
        if self.moe_activation not in ("swiglu", "relu2"):
            raise ValueError(
                f"model.moe_activation={self.moe_activation!r}: 'swiglu' "
                "or 'relu2'")
        tied = self.tie_word_embeddings and self.arch != "lfm2_moe"
        if self.num_experts or self.quantize_dense or tied:
            raise ValueError(
                f"arch={self.arch!r} has its own expert layer (num_experts "
                "is the GShard layer's), no int8 Dense twin and an untied "
                "head")

    def _check_deepseek_v3(self) -> None:
        for key in ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                    "v_head_dim"):
            if getattr(self, key) <= 0:
                raise ValueError(f"arch={self.arch!r} needs model.{key} > 0")
        self.num_kv_heads = self.num_heads
        if self.head_dim == 0:
            self.head_dim = self.qk_rope_head_dim   # as published
        if not 0 <= self.first_k_dense_replace <= self.num_layers:
            raise ValueError("first_k_dense_replace outside 0..num_layers")

    def _check_keye_dsa(self) -> None:
        for key in ("sa_topk", "sa_index_heads", "sa_index_head_dim",
                    "sa_q_chunk", "sa_kv_chunk"):
            if getattr(self, key) <= 0:
                raise ValueError(f"arch='keye_dsa' needs model.{key} > 0")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("arch='keye_dsa': num_kv_heads divides "
                             "num_heads (grouped-query attention)")
        if self.seq_shard_activations or self.first_k_dense_replace:
            raise ValueError(
                "arch='keye_dsa': every layer is an expert layer "
                "(first_k_dense_replace = 0, the published "
                "decoder_sparse_step 1 / mlp_only_layers []), and the "
                "indexer scores whole sequences (seq_shard_activations)")

    def _check_sdar_moe(self) -> None:
        bd, steps = self.block_length, self.denoising_steps
        if bd < 1 or steps < 1 or bd % steps:
            raise ValueError(
                f"arch='sdar_moe' needs model.block_length >= 1 and "
                f"model.denoising_steps dividing it (a forward reveals "
                f"block_length / denoising_steps positions), got {bd}, "
                f"{steps}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("arch='sdar_moe': num_kv_heads divides "
                             "num_heads (grouped-query attention)")
        if self.seq_shard_activations or self.first_k_dense_replace:
            raise ValueError(
                "arch='sdar_moe': every layer is an expert layer "
                "(first_k_dense_replace = 0, the published "
                "decoder_sparse_step 1 / mlp_only_layers []), and a "
                "block's positions see each other in both directions "
                "(seq_shard_activations)")

    def _check_nemotron_h(self) -> None:
        for key in ("mamba_num_heads", "mamba_head_dim", "mamba_n_groups",
                    "ssm_state_size", "mamba_conv_kernel",
                    "mamba_chunk_size"):
            if getattr(self, key) <= 0:
                raise ValueError(f"arch='nemotron_h' needs model.{key} > 0")
        pattern = self.hybrid_override_pattern[:self.num_layers]
        unknown = set(pattern) - set(PATTERN_HALVES)
        if unknown or len(pattern) < self.num_layers:
            raise ValueError(
                f"model.hybrid_override_pattern names {sorted(PATTERN_HALVES)}"
                f", one character a layer, at least num_layers="
                f"{self.num_layers} of them (got "
                f"{len(self.hybrid_override_pattern)}, unknown: "
                f"{sorted(unknown)}; '-', a dense MLP alone, is not "
                "written)")
        which, of = self.head_share if len(self.head_share) == 2 else (0, 0)
        if not 0 <= which < of:
            raise ValueError(f"model.head_share={self.head_share}: (which "
                             "share, of how many)")
        if self.mamba_num_heads % self.mamba_n_groups \
                or self.mamba_n_groups % of or self.num_heads % of \
                or self.num_heads % self.num_kv_heads \
                or max(of, self.num_kv_heads) % min(of, self.num_kv_heads):
            raise ValueError(
                f"arch='nemotron_h' with {of} head shares: a share holds "
                "whole Mamba-2 groups (the gated norm runs over a group: "
                "part of one would need an exchange), so mamba_n_groups "
                "divides by it, as do the query heads; key-value heads "
                "divide by the shares or the shares by them")
        if self.seq_shard_activations or self.first_k_dense_replace:
            raise ValueError(
                "arch='nemotron_h': the pattern names every layer "
                "(first_k_dense_replace = 0), and a state-space layer takes "
                "whole sequences (seq_shard_activations)")

    def _check_kimi_linear(self) -> None:
        for key in ("kda_num_heads", "kda_head_dim",
                    "short_conv_kernel_size"):
            if getattr(self, key) <= 0:
                raise ValueError(f"arch='kimi_linear' needs model.{key} > 0")
        self.kda_layers = tuple(self.kda_layers)
        if any(i < 1 for i in self.kda_layers):
            raise ValueError("model.kda_layers counts layers from 1 (the "
                             "published linear_attn_config.kda_layers)")

    def _check_olmo_hybrid(self) -> None:
        for key in ("linear_num_key_heads", "linear_key_head_dim",
                    "linear_value_head_dim", "linear_conv_kernel_dim"):
            if getattr(self, key) <= 0:
                raise ValueError(f"arch='olmo_hybrid' needs model.{key} > 0")
        if self.linear_num_value_heads != self.linear_num_key_heads:
            raise ValueError(
                "arch='olmo_hybrid' with linear_num_value_heads != "
                "linear_num_key_heads: there is no delta rule whose value "
                "heads share a key head (ops/kda.py takes one q, k a head)")
        self._check_layer_types()
        self.num_kv_heads = self.num_heads
        if (self.num_experts or self.quantize_dense
                or self.tie_word_embeddings or self.seq_shard_activations):
            raise ValueError(
                "arch='olmo_hybrid' is dense (num_experts is the GShard "
                "layer's), has no int8 Dense twin, an untied head, and "
                "its recurrent layers take whole sequences "
                "(seq_shard_activations)")

    def _check_layer_types(self) -> None:
        names = LAYER_TYPE_MIXERS[self.arch]
        self.layer_types = tuple(self.layer_types)
        unknown = set(self.layer_types) - set(names)
        if unknown or len(self.layer_types) < self.num_layers:
            raise ValueError(
                f"model.layer_types names {sorted(names)}, one "
                f"entry a layer, at least num_layers={self.num_layers} of "
                f"them (got {len(self.layer_types)}, unknown: "
                f"{sorted(unknown)})")

    def _check_lfm2_moe(self) -> None:
        if self.conv_L_cache < 2:
            raise ValueError("arch='lfm2_moe' needs model.conv_L_cache >= 2 "
                             "(the convolution's taps)")
        self._check_layer_types()
        if self.num_heads % self.num_kv_heads:
            raise ValueError("arch='lfm2_moe': num_kv_heads divides "
                             "num_heads (grouped-query attention)")
        if not 0 <= self.first_k_dense_replace <= self.num_layers:
            raise ValueError("first_k_dense_replace (the published "
                             "num_dense_layers) outside 0..num_layers")
        if self.moe_scoring != "sigmoid" or self.seq_shard_activations:
            raise ValueError(
                "arch='lfm2_moe': the router scores by a sigmoid and selects "
                "under the expert bias (moe_scoring), and a convolution "
                "takes whole sequences (seq_shard_activations)")

    def _check_mellum(self) -> None:
        self._check_layer_types()
        if self.sliding_window < 1:
            raise ValueError("arch='mellum' needs model.sliding_window >= 1 "
                             "(the keys a sliding layer's query sees)")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("arch='mellum': num_kv_heads divides "
                             "num_heads (grouped-query attention)")
        unknown = set(self.rope_parameters) - set(LAYER_TYPE_MIXERS["mellum"])
        kinds = {p.get("rope_type", "default")
                 for p in self.rope_parameters.values()}
        if unknown or kinds - {"default", "yarn"}:
            raise ValueError(
                "model.rope_parameters has one entry a layer type "
                f"({sorted(LAYER_TYPE_MIXERS['mellum'])}) of rope_type "
                f"'default' or 'yarn' (got {sorted(self.rope_parameters)}, "
                f"{sorted(kinds)})")
        if (self.moe_scoring != "softmax" or self.seq_shard_activations
                or self.first_k_dense_replace or self.n_shared_experts):
            raise ValueError(
                "arch='mellum': every layer is an expert layer "
                "(mlp_layer_types all sparse: first_k_dense_replace = 0) "
                "whose router scores by a softmax over all experts "
                "(moe_scoring), without a shared expert, and a window is "
                "not cut across sequence shards (seq_shard_activations)")

    def _check_ouro(self) -> None:
        self._check_layer_types()
        if self.total_ut_steps < 1:
            raise ValueError("arch='ouro' needs model.total_ut_steps >= 1 "
                             "(the passes over the stack)")
        if self.early_exit_threshold != 1.0:
            raise ValueError(
                f"model.early_exit_threshold={self.early_exit_threshold}: "
                "only the published 1 runs, at which the cumulative exit "
                "mass reaches the threshold at the last pass and every "
                "token runs total_ut_steps passes; a rollout that ends a "
                "token's passes early (and fills the cache entries of the "
                "passes it skipped) is another generation rule and is not "
                "written")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("arch='ouro': num_kv_heads divides num_heads")
        if (self.num_experts or self.quantize_dense
                or self.seq_shard_activations or self.tie_word_embeddings):
            raise ValueError(
                "arch='ouro' is dense (num_experts is the GShard layer's) "
                "with an untied head; its int8 Dense twin "
                "(rollout.quantize_weights) and sequence-sharded "
                "activations (seq_shard_activations) were not run against "
                "the reference over several passes")

    @property
    def latent_attention(self) -> bool:
        """The attention is latent (deepseek_v3, kimi_linear)."""
        return self.arch in LATENT_ARCHS

    @property
    def rms_norm(self) -> bool:
        """RMSNorm (neox: LayerNorm with a bias)."""
        return self.arch == "llama" or self.pattern

    @property
    def gated_mlp(self) -> bool:
        """The dense MLP is a SwiGLU (neox: GELU, no gate)."""
        return self.arch == "llama" or self.pattern

    @property
    def post_norm(self) -> bool:
        """A block norms each half's OUTPUT and nothing before it (the
        OLMo 2 / 3 order)."""
        return self.arch == "olmo_hybrid"

    @property
    def sandwich_norm(self) -> bool:
        """A block norms each half's input AND its output (Ouro's order:
        four norms a block)."""
        return self.arch == "ouro"

    def layer_visits(self) -> int:
        """The blocks a token passes: every layer ``total_ut_steps``
        times.  What counts "over all layers" by the work done (cache
        entries, kept residuals, a step's reads of the weights) counts
        these."""
        return self.total_ut_steps * len(self.layer_kinds())

    @property
    def mask_id(self) -> int:
        """The id a block-diffusion model shows for a position not yet
        revealed: ``mask_token_id``, from the end of the vocabulary held
        here when negative."""
        return self.mask_token_id % self.vocab_size

    def blocks_spanned(self, new_tokens: int) -> int:
        """The most blocks of ``block_length`` that ``new_tokens``
        consecutive positions lie in, wherever they start: what a
        block-diffusion rollout's loop runs at most and a trace
        forward's noisy streams hold a row."""
        return (new_tokens + self.block_length - 2) // self.block_length + 1

    def _kinds(self) -> tuple:
        from orion_tpu.models.transformer import kinds

        return kinds(self)

    @property
    def pattern(self) -> bool:
        """Whether the model is a per-layer pattern of (mixer, FFN)
        kinds over RMSNorm blocks: see :meth:`layer_kinds`."""
        return self.arch in PATTERN_ARCHS

    def heads_held(self) -> dict:
        """What ``head_share`` leaves here of each mixer's heads:
        {"q", "kv": attention's query and key-value heads, "mamba",
        "groups": a Mamba-2 layer's heads and groups}."""
        of = self.head_share[1]
        return {"q": self.num_heads // of,
                "kv": max(1, self.num_kv_heads // of),
                "mamba": self.mamba_num_heads // of,
                "groups": self.mamba_n_groups // of}

    def attn_heads_a_step(self) -> int:
        """The query heads one grid step of the flash kernels holds
        (ops/pallas/flash_attention.py: the heads that share a key
        head, of what ``head_share`` leaves here); 1 where no layer
        keeps a per-head K/V cache (``Kind.per_head_kv``)."""
        if not any(kind.per_head_kv for kind in self._kinds()):
            return 1
        held = self.heads_held()
        return held["q"] // held["kv"]

    def delta_head_dims(self) -> Optional[tuple]:
        """(dk, dv) of the delta-rule layers' heads: what
        ``ops.kda.chunk_form`` is asked with; None without such a
        layer."""
        return next((kind.head_dims(self) for kind in self._kinds()
                     if hasattr(kind, "head_dims")), None)

    def layer_kinds(self) -> tuple:
        """((mixer, ffn), ...) per block: the model's description.
        mixer: a key of ``models.transformer.MIXERS`` ("attention",
        "window", "sparse", "latent", "kda", "gdn", "mamba2", "conv": what
        each is and caches is stated there) or None (no mixer half); ffn: "dense" (a
        SwiGLU or GELU MLP), "gshard" (num_experts), "experts" (the
        dropless layer) or None (no feed-forward half).
        Every model but nemotron_h has both halves in every block, one
        block a published layer; nemotron_h's pattern names halves, and
        a mixer with an "E" behind it make one block ("M*" leaves the
        first without a feed-forward half, "EE" the second without a
        mixer)."""
        if self.arch == "nemotron_h":
            out = []
            for mixer, ffn in (PATTERN_HALVES[c] for c in
                               self.hybrid_override_pattern[:self.num_layers]):
                if ffn and out and out[-1][1] is None and out[-1][0]:
                    out[-1] = (out[-1][0], ffn)
                else:
                    out.append((mixer, ffn))
            return tuple(out)
        if not self.pattern:
            return (("attention",
                     "gshard" if self.num_experts else "dense"),
                    ) * self.num_layers
        if self.arch == "olmo_hybrid":
            return tuple((LAYER_TYPE_MIXERS[self.arch][t], "dense")
                         for t in self.layer_types[:self.num_layers])
        if self.arch == "lfm2_moe":
            return tuple(
                (LAYER_TYPE_MIXERS[self.arch][t],
                 "dense" if i < self.first_k_dense_replace else "experts")
                for i, t in enumerate(self.layer_types[:self.num_layers]))
        if self.arch == "mellum":
            return tuple((LAYER_TYPE_MIXERS[self.arch][t], "experts")
                         for t in self.layer_types[:self.num_layers])
        if self.arch == "ouro":
            return (("attention", "dense"),) * self.num_layers
        if self.arch == "keye_dsa":
            return (("sparse", "experts"),) * self.num_layers
        if self.arch == "sdar_moe":
            return (("attention", "experts"),) * self.num_layers
        return tuple(
            ("kda" if i + 1 in self.kda_layers else "latent",
             "dense" if i < self.first_k_dense_replace else "experts")
            for i in range(self.num_layers))

    def layer_runs(self) -> tuple:
        """((first, length, mixer, ffn), ...): the stretches of equal
        consecutive kinds, each of which ``scan_layers`` scans as one
        stack; the leading dense layers of a latent-attention model
        stand alone (length 1, never stacked), any other model's are a
        stretch like the rest."""
        out = []
        for i, kind in enumerate(self.layer_kinds()):
            alone = self.latent_attention and kind[1] == "dense"
            if out and not alone and out[-1][2:] == kind:
                first, length = out[-1][:2]
                out[-1] = (first, length + 1) + kind
            else:
                out.append((i, 1) + kind)
        return tuple(out)

    @property
    def takes_token_mask(self) -> bool:
        """Whether a layer treats a position that holds no token apart
        (the dropless expert layer routes it nowhere, the recurrent
        mixer leaves its state untouched): callers then pass
        ``token_mask``."""
        return any(kind.takes_token_mask for kind in self._kinds())

    @property
    def recurrent(self) -> bool:
        """Whether some layer's per-sequence state is not indexed by
        position."""
        return any(kind.cache_kind == "state" for kind in self._kinds())

    @staticmethod
    def llama3_8b() -> "ModelConfig":
        return ModelConfig(
            arch="llama", vocab_size=128256, hidden_size=4096,
            intermediate_size=14336, num_layers=32, num_heads=32,
            num_kv_heads=8, max_seq_len=8192, rope_theta=500000.0,
        )

    @staticmethod
    def llama3_1b() -> "ModelConfig":
        # Llama-3.2-1B shape — the "1B reward model" scale of SPEC config 2.
        return ModelConfig(
            arch="llama", vocab_size=128256, hidden_size=2048,
            intermediate_size=8192, num_layers=16, num_heads=32,
            num_kv_heads=8, max_seq_len=8192, rope_theta=500000.0,
        )

    @staticmethod
    def pythia_1b() -> "ModelConfig":
        return ModelConfig(
            arch="neox", vocab_size=50304, hidden_size=2048,
            intermediate_size=8192, num_layers=16, num_heads=8,
            rotary_pct=0.25, use_parallel_residual=True,
            attn_bias=True, mlp_bias=True, layernorm_eps=1e-5,
            tie_word_embeddings=False,
        )

    @staticmethod
    def kanana_2_30b_a3b() -> "ModelConfig":
        """kakaocorp/kanana-2-30b-a3b-instruct-2601 as published
        (config.json, model_type deepseek_v3): every expert held."""
        return ModelConfig(
            arch="deepseek_v3", vocab_size=128256, hidden_size=2048,
            intermediate_size=6144, num_layers=48, num_heads=32,
            max_seq_len=32768, rope_theta=1000000.0, rms_norm_eps=1e-6,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, n_routed_experts=128, num_experts_per_tok=6,
            n_shared_experts=2, moe_intermediate_size=768,
            first_k_dense_replace=1, routed_scaling_factor=2.448,
        )

    @staticmethod
    def kimi_linear_48b_a3b() -> "ModelConfig":
        """moonshotai/Kimi-Linear-48B-A3B-Instruct as published
        (config.json, model_type kimi_linear): every expert held."""
        return ModelConfig(
            arch="kimi_linear", vocab_size=163840, hidden_size=2304,
            intermediate_size=9216, num_layers=27, num_heads=32,
            max_seq_len=1048576, rope_theta=10000.0, rms_norm_eps=1e-5,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, n_routed_experts=256, num_experts_per_tok=8,
            n_shared_experts=1, moe_intermediate_size=1024,
            first_k_dense_replace=1, routed_scaling_factor=2.446,
            kda_layers=(1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                        19, 21, 22, 23, 25, 26),
            kda_num_heads=32, kda_head_dim=128, short_conv_kernel_size=4,
            mla_use_nope=True,
        )

    @staticmethod
    def olmo_hybrid_7b() -> "ModelConfig":
        """allenai/Olmo-Hybrid-7B as published (config.json, model_type
        olmo_hybrid); ``rope_parameters.rope_theta`` is null there:
        nothing is rotated."""
        return ModelConfig(
            arch="olmo_hybrid", vocab_size=100352, hidden_size=3840,
            intermediate_size=11008, num_layers=32, num_heads=30,
            max_seq_len=65536, rope_theta=0.0, rms_norm_eps=1e-6,
            layer_types=(("linear_attention",) * 3
                         + ("full_attention",)) * 8,
            linear_num_key_heads=30, linear_num_value_heads=30,
            linear_key_head_dim=96, linear_value_head_dim=192,
            linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
        )

    @staticmethod
    def keye_vl2_30b_a3b() -> "ModelConfig":
        """Kwai-Keye/Keye-VL-2.0-30B-A3B's language model as published
        (config.json, model_type KeyeVL2; the vision tower is not here):
        every expert held.  For token ids alone the three M-RoPE
        components are equal: the ordinary rotation."""
        return ModelConfig(
            arch="keye_dsa", vocab_size=151936, hidden_size=2048,
            intermediate_size=6144, num_layers=48, num_heads=32,
            num_kv_heads=4, head_dim=128, max_seq_len=262144,
            rope_theta=1e7, rms_norm_eps=1e-6, n_routed_experts=128,
            num_experts_per_tok=8, moe_intermediate_size=768,
            moe_scoring="softmax", sa_topk=2048, sa_index_heads=16,
            sa_index_head_dim=64, sa_q_chunk=512, sa_kv_chunk=512,
        )

    @staticmethod
    def sdar_30b_a3b() -> "ModelConfig":
        """JetLM/SDAR-30B-A3B-Chat as published (config.json, model_type
        sdar_moe): every expert held.  config.json is silent on the
        generation rule: blocks of 4 revealed in 4 steps are the
        family's released defaults, and the mask is the vocabulary's
        last row (the tokenizer's own id lies outside a slice of it)."""
        return ModelConfig(
            arch="sdar_moe", vocab_size=151936, hidden_size=2048,
            intermediate_size=6144, num_layers=48, num_heads=32,
            num_kv_heads=4, head_dim=128, max_seq_len=32768,
            rope_theta=1e6, rms_norm_eps=1e-6, n_routed_experts=128,
            num_experts_per_tok=8, moe_intermediate_size=768,
            moe_scoring="softmax", block_length=4, denoising_steps=4,
            mask_token_id=-1,
        )

    @staticmethod
    def lfm2_8b_a1b() -> "ModelConfig":
        """LiquidAI/LFM2-8B-A1B as published (config.json, model_type
        lfm2_moe): every expert held.  ``num_dense_layers`` is
        ``first_k_dense_replace`` here, ``num_experts`` is
        ``n_routed_experts`` (``num_experts`` is the GShard layer's
        switch), ``norm_eps`` is ``rms_norm_eps``; config.json as the
        catalog has it is silent on the head: tied, as the published
        8.3 B count implies."""
        return ModelConfig(
            arch="lfm2_moe", vocab_size=65536, hidden_size=2048,
            intermediate_size=7168, num_layers=24, num_heads=32,
            num_kv_heads=8, head_dim=64, max_seq_len=128000,
            rope_theta=1e6, rms_norm_eps=1e-5, tie_word_embeddings=True,
            layer_types=(("conv",) * 2 + ("full_attention",)
                         + (("conv",) * 3 + ("full_attention",)) * 4
                         + ("conv",) * 2 + ("full_attention",)
                         + ("conv",) * 2),
            conv_L_cache=3, n_routed_experts=32, num_experts_per_tok=4,
            moe_intermediate_size=1792, first_k_dense_replace=2,
            routed_scaling_factor=1.0, moe_scoring="sigmoid",
        )

    @staticmethod
    def mellum2_12b_a2_5b() -> "ModelConfig":
        """JetBrains/Mellum2-12B-A2.5B-Instruct as published
        (config.json, model_type mellum): every expert held.
        ``num_experts`` is ``n_routed_experts`` here (``num_experts`` is
        the GShard layer's switch); ``intermediate_size`` 7168 is
        published and belongs to no layer (``mlp_layer_types`` is
        ``sparse`` 28 times); the multi-token-prediction head is not
        here (config.json has no key for it)."""
        return ModelConfig(
            arch="mellum", vocab_size=98304, hidden_size=2304,
            intermediate_size=7168, num_layers=28, num_heads=32,
            num_kv_heads=4, head_dim=128, max_seq_len=131072,
            rope_theta=500000.0, rms_norm_eps=1e-6,
            layer_types=(("sliding_attention",) * 3
                         + ("full_attention",)) * 7,
            sliding_window=1024,
            rope_parameters={
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": 500000.0,
                    "factor": 16.0,
                    "original_max_position_embeddings": 8192,
                    "beta_fast": 32.0, "beta_slow": 1.0,
                    "attention_factor": 1.2772588722239782},
                "sliding_attention": {"rope_type": "default",
                                      "rope_theta": 500000.0}},
            n_routed_experts=64, num_experts_per_tok=8,
            moe_intermediate_size=896, moe_scoring="softmax",
        )

    @staticmethod
    def ouro_2_6b() -> "ModelConfig":
        """ByteDance/Ouro-2.6B as published (config.json, model_type
        ouro): 48 layers run total_ut_steps = 4 times over."""
        return ModelConfig(
            arch="ouro", vocab_size=49152, hidden_size=2048,
            intermediate_size=5632, num_layers=48, num_heads=16,
            num_kv_heads=16, head_dim=128, max_seq_len=65536,
            rope_theta=1000000.0, rms_norm_eps=1e-6,
            layer_types=("full_attention",) * 48,
            total_ut_steps=4, early_exit_threshold=1.0,
        )

    @staticmethod
    def tiny_ouro() -> "ModelConfig":
        """``model_preset=tiny_ouro``: the small sibling of ouro_2_6b
        (tests, CPU rehearsals)."""
        return ModelConfig.tiny("ouro")

    @staticmethod
    def tiny_mellum() -> "ModelConfig":
        """``model_preset=tiny_mellum``: the small sibling of
        mellum2_12b_a2_5b (tests, CPU rehearsals)."""
        return ModelConfig.tiny("mellum")

    @staticmethod
    def tiny_lfm2_moe() -> "ModelConfig":
        """``model_preset=tiny_lfm2_moe``: the small sibling of
        lfm2_8b_a1b (tests, CPU rehearsals)."""
        return ModelConfig.tiny("lfm2_moe")

    @staticmethod
    def tiny_sdar_moe() -> "ModelConfig":
        """``model_preset=tiny_sdar_moe``: the small sibling of
        sdar_30b_a3b (tests, CPU rehearsals)."""
        return ModelConfig.tiny("sdar_moe")

    @staticmethod
    def nemotron_3_super_120b_a12b() -> "ModelConfig":
        """nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 as published
        (config.json, model_type nemotron_h): every head and expert
        held.  The multi-token-prediction module
        (num_nextn_predict_layers 1) is not here."""
        return ModelConfig(
            arch="nemotron_h", vocab_size=131072, hidden_size=4096,
            intermediate_size=2688, num_layers=88, num_heads=32,
            num_kv_heads=2, head_dim=128, max_seq_len=262144,
            rope_theta=1e4, rms_norm_eps=1e-5,
            hybrid_override_pattern=(
                "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
            mamba_num_heads=128, mamba_head_dim=64, mamba_n_groups=8,
            ssm_state_size=128, mamba_conv_kernel=4, mamba_chunk_size=128,
            n_routed_experts=512, num_experts_per_tok=22,
            n_shared_experts=1, moe_intermediate_size=2688,
            moe_shared_expert_intermediate_size=5376, moe_latent_size=1024,
            moe_activation="relu2", routed_scaling_factor=5.0,
        )

    @staticmethod
    def tiny_nemotron_h() -> "ModelConfig":
        """``model_preset=tiny_nemotron_h``: the small sibling of
        nemotron_3_super_120b_a12b (tests, CPU rehearsals)."""
        return ModelConfig.tiny("nemotron_h")

    @staticmethod
    def tiny_keye_dsa() -> "ModelConfig":
        """``model_preset=tiny_keye_dsa``: the small sibling of
        keye_vl2_30b_a3b (tests, CPU rehearsals)."""
        return ModelConfig.tiny("keye_dsa")

    @staticmethod
    def tiny_olmo_hybrid() -> "ModelConfig":
        """``model_preset=tiny_olmo_hybrid``: the small sibling of
        olmo_hybrid_7b (tests, CPU rehearsals)."""
        return ModelConfig.tiny("olmo_hybrid")

    @staticmethod
    def tiny_kimi_linear() -> "ModelConfig":
        """``model_preset=tiny_kimi_linear``: the small sibling of
        kimi_linear_48b_a3b (tests, CPU rehearsals)."""
        return ModelConfig.tiny("kimi_linear")

    @staticmethod
    def tiny_deepseek_v3() -> "ModelConfig":
        """``model_preset=tiny_deepseek_v3``: the small sibling of
        kanana_2_30b_a3b (tests, CPU rehearsals)."""
        return ModelConfig.tiny("deepseek_v3")

    @staticmethod
    def tiny(arch: str = "llama", **kw: Any) -> "ModelConfig":
        """Small config for tests (runs on CPU in <1s)."""
        if arch == "nemotron_h":
            # one period with every kind of block: ME, M alone, *E;
            # 8 heads in 4 groups and 4 query / 2 key-value heads, so
            # that 2 and 4 head shares both divide them
            base = dict(
                arch=arch, vocab_size=256, hidden_size=64,
                intermediate_size=48, num_layers=7, num_heads=4,
                num_kv_heads=2, head_dim=16, max_seq_len=128,
                rope_theta=1e4, rms_norm_eps=1e-5,
                hybrid_override_pattern="MEMEM*E",
                mamba_num_heads=8, mamba_head_dim=8, mamba_n_groups=4,
                ssm_state_size=16, mamba_conv_kernel=4, mamba_chunk_size=16,
                n_routed_experts=8, num_experts_per_tok=3,
                n_shared_experts=1, moe_intermediate_size=48,
                moe_shared_expert_intermediate_size=80, moe_latent_size=32,
                moe_activation="relu2", routed_scaling_factor=5.0,
            )
            base.update(kw)
            return ModelConfig(**base)
        if arch == "lfm2_moe":
            # the published first eight layers' pattern: two dense conv
            # layers, then c c A c c c A c cut to A c c A c; 2 query
            # heads a key/value head; a tied head
            base = dict(
                arch=arch, vocab_size=256, hidden_size=64,
                intermediate_size=96, num_layers=7, num_heads=4,
                num_kv_heads=2, head_dim=16, max_seq_len=128,
                rope_theta=1e6, rms_norm_eps=1e-5, tie_word_embeddings=True,
                layer_types=("conv", "conv", "full_attention", "conv",
                             "conv", "full_attention", "conv"),
                conv_L_cache=3, n_routed_experts=8, num_experts_per_tok=2,
                moe_intermediate_size=32, first_k_dense_replace=2,
                routed_scaling_factor=1.0, moe_scoring="sigmoid",
            )
            base.update(kw)
            return ModelConfig(**base)
        if arch == "ouro":
            # three blocks run four times over; one query head a key head
            base = dict(
                arch=arch, vocab_size=256, hidden_size=64,
                intermediate_size=96, num_layers=3, num_heads=4,
                num_kv_heads=4, head_dim=16, max_seq_len=128,
                rope_theta=1e6, rms_norm_eps=1e-6,
                layer_types=("full_attention",) * 3,
                total_ut_steps=4, early_exit_threshold=1.0,
            )
            base.update(kw)
            return ModelConfig(**base)
        if arch == "mellum":
            # two whole periods S S S F; a window of 8 keys; YaRN over 16
            # original positions, so that sequences of 40 pass its ramp;
            # 2 query heads a key/value head
            base = dict(
                arch=arch, vocab_size=256, hidden_size=64,
                intermediate_size=96, num_layers=8, num_heads=4,
                num_kv_heads=2, head_dim=16, max_seq_len=128,
                rope_theta=1e4, rms_norm_eps=1e-6,
                layer_types=(("sliding_attention",) * 3
                             + ("full_attention",)) * 2,
                sliding_window=8,
                rope_parameters={
                    "full_attention": {
                        "rope_type": "yarn", "rope_theta": 1e4,
                        "factor": 4.0,
                        "original_max_position_embeddings": 16,
                        "beta_fast": 4.0, "beta_slow": 1.0,
                        "attention_factor": 1.138629436111989},
                    "sliding_attention": {"rope_type": "default",
                                          "rope_theta": 1e4}},
                n_routed_experts=8, num_experts_per_tok=2,
                moe_intermediate_size=32, moe_scoring="softmax",
            )
            base.update(kw)
            return ModelConfig(**base)
        if arch == "sdar_moe":
            # blocks of 4 revealed one position a step; 2 query heads a
            # key/value head; the mask is id 255
            base = dict(
                arch=arch, vocab_size=256, hidden_size=64,
                intermediate_size=96, num_layers=2, num_heads=4,
                num_kv_heads=2, head_dim=16, max_seq_len=128,
                rope_theta=1e6, rms_norm_eps=1e-6, n_routed_experts=8,
                num_experts_per_tok=2, moe_intermediate_size=32,
                moe_scoring="softmax", block_length=4, denoising_steps=4,
            )
            base.update(kw)
            return ModelConfig(**base)
        if arch == "keye_dsa":
            # topk 8 of up to 128 keys; 2 query heads a key/value head
            base = dict(
                arch=arch, vocab_size=256, hidden_size=64,
                intermediate_size=96, num_layers=2, num_heads=4,
                num_kv_heads=2, head_dim=16, max_seq_len=128,
                rope_theta=1e7, rms_norm_eps=1e-6, n_routed_experts=8,
                num_experts_per_tok=2, moe_intermediate_size=32,
                moe_scoring="softmax", sa_topk=8, sa_index_heads=4,
                sa_index_head_dim=8, sa_q_chunk=16, sa_kv_chunk=16,
            )
            base.update(kw)
            return ModelConfig(**base)
        if arch == "olmo_hybrid":
            # one whole period of two published; heads of (12, 24):
            # neither side a tile
            base = dict(
                arch=arch, vocab_size=256, hidden_size=64,
                intermediate_size=96, num_layers=4, num_heads=4,
                max_seq_len=128, rope_theta=0.0, rms_norm_eps=1e-6,
                layer_types=(("linear_attention",) * 3
                             + ("full_attention",)) * 2,
                linear_num_key_heads=3, linear_num_value_heads=3,
                linear_key_head_dim=12, linear_value_head_dim=24,
                linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
            )
            base.update(kw)
            return ModelConfig(**base)
        if arch == "kimi_linear":
            # one dense layer, then one whole period of the 3 : 1 pattern
            base = dict(
                arch=arch, vocab_size=256, hidden_size=64,
                intermediate_size=96, num_layers=5, num_heads=4,
                max_seq_len=128, rms_norm_eps=1e-5, kv_lora_rank=16,
                qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
                n_routed_experts=8, num_experts_per_tok=2,
                n_shared_experts=1, moe_intermediate_size=32,
                first_k_dense_replace=1, routed_scaling_factor=2.446,
                kda_layers=(1, 2, 3, 5, 6, 7), kda_num_heads=4, kda_head_dim=16,
                short_conv_kernel_size=4, mla_use_nope=True,
            )
            base.update(kw)
            return ModelConfig(**base)
        if arch == "deepseek_v3":
            base = dict(
                arch=arch, vocab_size=256, hidden_size=64,
                intermediate_size=96, num_layers=3, num_heads=4,
                max_seq_len=128, rms_norm_eps=1e-6, kv_lora_rank=16,
                qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
                n_routed_experts=8, num_experts_per_tok=2,
                n_shared_experts=2, moe_intermediate_size=32,
                first_k_dense_replace=1, routed_scaling_factor=2.448,
            )
            base.update(kw)
            return ModelConfig(**base)
        base = dict(
            arch=arch, vocab_size=256, hidden_size=64,
            intermediate_size=128, num_layers=2, num_heads=4,
            num_kv_heads=2 if arch == "llama" else 4, max_seq_len=128,
        )
        base.update(kw)
        return ModelConfig(**base)


# ---------------------------------------------------------------------------
# Mesh / parallelism
# ---------------------------------------------------------------------------


@dataclass
class MeshConfig:
    """Logical device mesh over which everything is sharded.

    Axes (SURVEY.md §2 parallelism table):
      data   — pure data parallelism (gradient all-reduce)
      fsdp   — ZeRO-3-style parameter/grad sharding (AG on use, RS on grads)
      tensor — megatron-style tensor parallelism (heads/mlp/vocab)
      seq    — sequence/context parallelism (Ulysses all-to-all, ring attn)
      stage  — pipeline parallelism (parallel.pipeline: GPipe schedule,
               ppermute activation ring over ICI)
      expert — expert parallelism (ops.moe: expert-stacked params
               sharded; dispatch/combine einsums become EP collectives)

    A size of 1 disables an axis; sizes must multiply to the device count.
    -1 for ``fsdp`` means "all remaining devices".
    """

    data: int = 1
    fsdp: int = -1
    tensor: int = 1
    seq: int = 1
    stage: int = 1
    expert: int = 1
    axis_names: tuple = ("stage", "data", "fsdp", "seq", "expert",
                         "tensor")

    def resolved_shape(self, n_devices: int) -> tuple:
        sizes = {"data": self.data, "fsdp": self.fsdp,
                 "seq": self.seq, "tensor": self.tensor,
                 "stage": self.stage, "expert": self.expert}
        fixed = 1
        free = None
        for name, s in sizes.items():
            if s == -1:
                if free is not None:
                    raise ValueError("only one mesh axis may be -1")
                free = name
            else:
                fixed *= s
        if free is not None:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {fixed}")
            sizes[free] = n_devices // fixed
        total = 1
        for s in sizes.values():
            total *= s
        if total != n_devices:
            raise ValueError(
                f"mesh {sizes} does not cover {n_devices} devices")
        return tuple(sizes[n] for n in self.axis_names)


# ---------------------------------------------------------------------------
# Optimizer / rollout / train
# ---------------------------------------------------------------------------


@dataclass
class OptimizerConfig:
    name: str = "adamw"
    learning_rate: float = 1e-6
    betas: tuple = (0.9, 0.95)
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    # Adam moment storage dtypes (None => param dtype).  bf16 halves a
    # moment's HBM residency — the difference between a 1B-model RLHF
    # session (policy+ref+critic+moments) fitting on one 16G chip or
    # not.  Setting nu_dtype routes through algos.optim.adamw_lp (the
    # TPU-native answer to the reference ecosystem's 8-bit Adam); math
    # stays f32 either way.
    mu_dtype: Optional[str] = None
    nu_dtype: Optional[str] = None
    warmup_steps: int = 0
    total_steps: int = 0  # 0 => constant lr after warmup
    schedule: str = "constant"  # "constant" | "linear" | "cosine"


@dataclass
class RolloutConfig:
    """Generation engine settings (the vLLM-equivalent, SURVEY.md §2 #5)."""

    max_prompt_len: int = 512
    max_new_tokens: int = 512
    temperature: float = 1.0
    top_k: int = 0  # 0 => disabled
    top_p: float = 1.0  # 1.0 => disabled
    # EOS is suppressed until each sequence has generated this many
    # tokens (vLLM min_tokens / HF min_new_tokens).
    min_new_tokens: int = 0
    # HF/vLLM repetition penalty over prompt+generated tokens; 1.0 =>
    # disabled (no [B, V] seen-mask state is carried when off).  Must
    # be > 0 — NOT the top_k-style "0 disables" convention (0 would
    # divide logits by zero); validated in __post_init__.
    repetition_penalty: float = 1.0
    # Extra terminator token ids beyond eos_token_id (vLLM
    # stop_token_ids): sampling any of them ends the sequence.  The
    # stop token itself is kept in the completion, like EOS.
    stop_token_ids: tuple = ()
    # Paged KV cache for RolloutEngine: capacity in pages; page_size
    # tokens per page.  Default False: for fixed-batch generate the
    # dense cache is ~2.6x faster on-chip (measured v5e, B=32/L=256 —
    # paging buys slot reuse and long-context memory, not per-step
    # speed); the ContinuousBatchingEngine always uses the paged pool,
    # which is where those wins live.
    paged: bool = False
    page_size: int = 64
    num_pages: int = 0  # 0 => derived from batch * max_len
    # Engine selection for the trainer path: "simple" (fixed-batch
    # RolloutEngine, dense or paged cache) or "continuous" (paged-pool
    # ContinuousBatchingEngine with slot recycling — wins when
    # completion lengths are ragged, since freed slots admit new work
    # instead of idling to the batch max).
    engine: str = "simple"
    # Continuous batching: engine slot count (sequences in flight) and
    # decode tokens per jitted segment.
    max_batch_size: int = 32
    segment_len: int = 16
    # (logprobs are always computed in f32 — both engines cast logits
    # to float32 before the softmax to avoid bf16 drift; the old
    # ``logprobs_dtype`` knob was never wired and was deleted by the
    # config-drift sweep rather than threaded through the engines.)
    # int8 decode (ops/quant.py): decode is HBM-bound, so storing the
    # decode twin's Dense kernels int8 (weight-only, per-out-channel
    # scales, convert fused into the dot — measured 1.76x on the matmul
    # stack) and/or the dense KV cache int8 (per-token-per-head scales)
    # moves the bandwidth floor itself.  Opt-in: off by default so
    # parity tests see the exact policy; the ppo1b-sync job turns both
    # on.  The training graph is never quantized.
    quantize_weights: bool = False
    quantize_kv: bool = False
    # Speculative decoding, continuous engine only (rollout.engine=
    # continuous; the fixed-batch RolloutEngine refuses k > 0): each
    # decoding slot drafts speculative_k tokens per verify wave by
    # prompt-lookup (the trailing spec_ngram-gram matched against the
    # slot's earlier content) and all k+1 positions are verified in
    # ONE chunked forward over the paged pool (k slack positions per
    # reservation) — decode is HBM-bound, so a wave that emits m+1
    # tokens reads the weights once instead of m+1 times.  0 disables.
    # Exact in both modes: greedy output is token-identical to
    # sequential decode; temperature>0 uses delta-draft speculative
    # sampling whose emitted-token marginal is exactly the tempered
    # sampling distribution (behavior logprobs stay correct for the
    # async importance ratio).  Composes with repetition_penalty /
    # min_new_tokens / stop ids inside a chunk, the prefix cache and
    # chunked prefill.
    speculative_k: int = 0
    spec_ngram: int = 2
    # Adaptive k: track a per-request acceptance EMA and skip the
    # verify chunk for waves whose decoding slots all draft below
    # `spec_breakeven` emitted tokens per verify step (the
    # measured chunk-cost breakeven, ~1.55-1.6x a plain decode step on
    # chip) — cold workloads degrade to plain decode instead of paying
    # the chunk tax, which is what makes speculative_k safe to leave
    # on for the continuous path.  `spec_probe_period` forces one
    # probing verify wave after that many consecutive plain waves so a
    # workload shift (random -> structured) is re-detected; 0 never
    # re-probes.
    spec_adaptive: bool = True
    spec_breakeven: float = 1.6
    spec_probe_period: int = 64
    # Shared-prefix group admission (continuous engine): when a trainer
    # samples k completions per prompt (GRPO/RLOO/Online-DPO), prefill
    # each unique prompt once and share its fully-filled prompt pages
    # across the k clones' block tables — prefill FLOPs and prompt-page
    # HBM drop ~k×.  False = admit k independent clones (A/B baseline).
    group_prefix_sharing: bool = True
    # -- serving-grade continuous engine (PR 8) ------------------------
    # Cross-request prefix caching: hash-matched FULL prompt pages are
    # shared read-only across requests (refcounted, LRU-evicted at
    # refs==0) and a retiring request's prompt pages graduate into the
    # cache instead of freeing — repeated prompts/prefixes skip their
    # prefill.  The cache is invalidated whenever new weights land
    # (cached KV is weight-dependent).  Disabled automatically when
    # repetition_penalty != 1.0 (the seen-set would need the full
    # prompt the skipped prefill never sees).
    prefix_cache: bool = True
    # Host-RAM KV tier (PR 17): when > 0, a prefix-cache page LRU-
    # evicted from the device pool spills its KV into a byte-budgeted
    # host cache of this many bytes instead of being dropped, and a
    # later prefix hit re-admits it device-side, skipping the prefill
    # forward — same chain-hash keying, so hits are bit-identical KV.
    # 0 disables the tier (single-tier PR 8 behavior).  Requires
    # prefix_cache; flushed together with it on weight reload.
    host_cache_bytes: int = 0
    # Chunked prefill: admission prefill runs at most this many tokens
    # per wave, so a long prompt is spread across decode segments
    # instead of stalling every in-flight slot for one full-width
    # prefill.  0 = one-shot prefill (the pre-PR8 behavior).
    chunked_prefill_tokens: int = 0
    # Admission order for the continuous scheduler: "fifo" (arrival
    # order), "priority" (higher RequestSpec.priority first), or
    # "deadline" (earliest deadline first).  No overtaking within the
    # chosen order — the head request that does not fit blocks
    # admission, which keeps every policy starvation-free.
    admission_policy: str = "fifo"
    # Pages held back from admission as growth headroom for in-flight
    # sequences (on-demand allocation acquires pages mid-flight; the
    # watermark makes preemption rare instead of structural).
    # -1 = auto: one page per engine slot.
    page_watermark: int = -1
    # -- multi-tenant serving QoS (PR 12) ------------------------------
    # Global admission-queue watermark: a submit() that would leave
    # more than this many requests WAITING (unadmitted) is refused
    # with a typed EngineOverloaded carrying queue depth + a
    # retry-after hint, instead of growing the queue without bound
    # under overload.  0 = unlimited (the trainer path, where the
    # caller owns the arrival rate).  Per-tenant caps/rate limits are
    # registered at runtime via engine.configure_tenant().
    max_queued_requests: int = 0
    # Waves between a slot's done-flag snapshot and its harvest.
    # 1 lets the flag fetch ride out the next segment's execution at
    # the price of one extra masked segment per request; 0 fetches
    # immediately.  -1 = auto: 1 on TPU, 0 elsewhere.  The fetch cost
    # on the local chip is not measured yet (ROADMAP D4), so auto is
    # a carried-over setting, not a tuned one.
    harvest_lag: int = -1

    def effective_min_new(self, eos_id) -> int:
        """min_new_tokens is only meaningful when SOME terminator can
        fire (eos or stop_token_ids) — the single source of truth for
        the engines' gating."""
        return (self.min_new_tokens
                if eos_id is not None or self.stop_token_ids else 0)

    def check_stop_ids(self, vocab_size: int, eos_id=None) -> None:
        """Engine-construction check (ADVICE r4): an out-of-vocab stop
        or EOS id can never be sampled, so ``is_stop_token`` never
        fires and the ``eos_forbid_mask`` scatter drops — a config typo
        (or a tokenizer/model vocab mismatch) would silently disable
        the terminator."""
        # (negative stop ids are already rejected in __post_init__ —
        # only the upper bound needs the engine's vocab size)
        bad = [t for t in self.stop_token_ids if t >= vocab_size]
        if bad:
            raise ValueError(
                f"stop_token_ids {bad} out of range for "
                f"vocab_size={vocab_size}: they could never be sampled, "
                "silently disabling the terminator")
        if eos_id is not None and not 0 <= int(eos_id) < vocab_size:
            raise ValueError(
                f"eos_token_id {eos_id} out of range for "
                f"vocab_size={vocab_size}: it could never be sampled, "
                "silently disabling the terminator")

    def __post_init__(self) -> None:
        # Normalize stop_token_ids: yaml scalars arrive as a bare int,
        # CLI overrides as floats — engines iterate a tuple of ints.
        ids = self.stop_token_ids
        if isinstance(ids, (int, float)):
            ids = (ids,)
        self.stop_token_ids = tuple(int(t) for t in ids)
        if any(t < 0 for t in self.stop_token_ids):
            raise ValueError(
                f"stop_token_ids must be non-negative, got "
                f"{self.stop_token_ids}")
        if self.repetition_penalty <= 0:
            raise ValueError(
                f"repetition_penalty must be > 0 (1.0 disables), got "
                f"{self.repetition_penalty} — this is NOT the "
                "top_k-style 0-disables convention")
        if self.speculative_k < 0:
            raise ValueError(
                f"speculative_k must be >= 0 (0 disables), got "
                f"{self.speculative_k}")
        if self.speculative_k > 0 and self.spec_ngram < 1:
            raise ValueError(
                f"spec_ngram must be >= 1, got {self.spec_ngram}")
        if self.spec_breakeven < 1.0:
            raise ValueError(
                f"spec_breakeven must be >= 1.0 (tokens per verify "
                f"step; a plain step emits exactly 1), got "
                f"{self.spec_breakeven}")
        if self.spec_probe_period < 0:
            raise ValueError(
                f"spec_probe_period must be >= 0 (0 never re-probes), "
                f"got {self.spec_probe_period}")
        if not 0 <= self.min_new_tokens <= self.max_new_tokens:
            raise ValueError(
                f"min_new_tokens={self.min_new_tokens} outside "
                f"[0, max_new_tokens={self.max_new_tokens}]")
        if self.admission_policy not in ("fifo", "priority", "deadline"):
            raise ValueError(
                f"admission_policy must be fifo|priority|deadline, got "
                f"{self.admission_policy!r}")
        if self.chunked_prefill_tokens < 0:
            raise ValueError(
                f"chunked_prefill_tokens must be >= 0 (0 disables), got "
                f"{self.chunked_prefill_tokens}")
        if self.host_cache_bytes < 0:
            raise ValueError(
                f"host_cache_bytes must be >= 0 (0 disables the host "
                f"KV tier), got {self.host_cache_bytes}")
        if self.max_queued_requests < 0:
            raise ValueError(
                f"max_queued_requests must be >= 0 (0 = unlimited), "
                f"got {self.max_queued_requests}")
        if self.page_watermark < -1:
            raise ValueError(
                f"page_watermark must be >= -1 (-1 = auto), got "
                f"{self.page_watermark}")
        if self.harvest_lag not in (-1, 0, 1):
            raise ValueError(
                f"harvest_lag must be -1 (auto), 0 or 1, got "
                f"{self.harvest_lag}")


@dataclass
class DataConfig:
    """Prompt data source (SURVEY.md §2 #15).

    dataset: "synthetic" (offline arithmetic, zero deps) | "tldr" |
    "hh" | "ultrafeedback" | "gsm8k" | any HF dataset with a "prompt"
    column.  tokenizer: HF path, or None/"byte" for the byte fallback.
    """

    dataset: str = "synthetic"
    split: str = "train"
    tokenizer: Optional[str] = None
    use_chat_template: bool = False
    system_prompt: Optional[str] = None
    synthetic_size: int = 512
    # Long synthetic prompts: with synthetic_max_len > 0 every synthetic
    # record is padded IN FRONT with printable filler bytes from the seed
    # to a length drawn uniformly from synthetic_min_len..synthetic_max_len
    # byte-tokenizer tokens (the bos counted; the question stays at the
    # tail).  0 (the default) leaves the records as they always were.
    synthetic_min_len: int = 0
    synthetic_max_len: int = 0
    # synthetic_vocab > 0: the filler is token IDS drawn uniformly from
    # 4..synthetic_vocab-1 (behind the bos, before the question's bytes)
    # instead of printable bytes: as many distinct tokens as a real
    # prompt has, where 95 distinct bytes make every position look alike
    # to a router.  The caller keeps it inside the model's vocabulary.
    synthetic_vocab: int = 0
    # Directory of <dataset>.jsonl files in the upstream HF schema —
    # the offline path for real datasets on a zero-egress box.
    data_dir: Optional[str] = None
    # Split used for the held-out eval loop (TrainConfig.eval_every).
    eval_split: str = "test"


@dataclass
class ObsConfig:
    """Observability (orion_tpu.obs): span tracing + flight recorder.

    Off by default — every call site is instrumented unconditionally,
    but a disabled tracer is a shared no-op (the overhead budget test
    holds the serving loop to <1%).  Armed at trainer construction,
    released by ``trainer.close()``.
    """

    # Enable span/event tracing: spans land in the per-process ring
    # and export as Chrome trace_event JSON (Perfetto-loadable,
    # alongside the jax.profiler xplane dumps).
    trace: bool = False
    # Per-process event ring capacity (events, not bytes); the flight
    # recorder dumps exactly this window.
    ring_size: int = 4096
    # Dump the ring to <trace_dir or log_dir>/flightrec-<ts>.json on
    # unhandled exception, degradation-ladder transitions, or SIGUSR1.
    # Needs `trace` on and a directory to write into.
    flight_recorder: bool = True
    # Where traces/flight dumps land; None => cfg.log_dir (dumps sit
    # next to metrics.jsonl).
    trace_dir: Optional[str] = None

    def __post_init__(self) -> None:
        if self.ring_size < 1:
            raise ValueError(
                f"obs.ring_size must be >= 1, got {self.ring_size}")


@dataclass
class ResilienceConfig:
    """Fault handling for the whole stack (orion_tpu.resilience).

    Defaults are the legacy fail-fast semantics everywhere except
    checkpoint saves (retried — a transient filesystem hiccup should
    never lose a step) and non-finite quarantine (a NaN score must
    never be donated into the optimizer).  Turn on the supervisor with
    ``max_rollout_restarts`` / ``degrade_to_sync`` for long unattended
    runs.
    """

    # -- supervised rollout recovery (AsyncOrchestrator) ---------------
    # Restart budget for a crashed/stalled rollout worker; each restart
    # re-syncs weights.  0 = fail fast (legacy behavior).
    max_rollout_restarts: int = 0
    # Past the restart budget: degrade to synchronous rollout on the
    # train mesh (run completes, slower) instead of raising.
    degrade_to_sync: bool = False
    # Seconds without a rollout-worker heartbeat before the supervisor
    # declares a stall (0 = stall detection off; crash detection is
    # always on).
    heartbeat_timeout: float = 0.0
    # Skip (+ count) dequeued batches whose scores/logprobs contain
    # non-finite values instead of feeding them to the update step.
    quarantine_nonfinite: bool = True
    # -- cross-process worker pool (orchestration.remote.WorkerPool) ---
    # Rollout worker PROCESSES: 0 (default) keeps async_mode on the
    # in-process AsyncOrchestrator rollout thread; > 0 makes launch.py
    # spawn this many rollout worker processes itself and train
    # through PoolOrchestrator, which waits for this quorum before the
    # first iteration (elastic after that: more may join, members may
    # leave/rejoin mid-run).  Callers assembling their own pool pass
    # it to PoolOrchestrator directly and set this to the quorum.
    pool_size: int = 0
    # Worker-side heartbeat send cadence (seconds).  The learner-side
    # stall cutoff is `heartbeat_timeout` above (shared with the
    # in-process supervisor); keep timeout >> interval.
    heartbeat_interval: float = 0.5
    # Admissions allowed AFTER the first death/leave (churn bound): a
    # worker flapping in a crash loop must not grind the learner
    # through endless re-admission weight syncs.
    rejoin_budget: int = 4
    # Seconds an EMPTY pool waits for a (re)join before the supervisor
    # invokes the ladder (degrade_to_sync → sync rollout on the train
    # mesh, else fail fast).
    rejoin_grace: float = 2.0
    # Idle-receive deadline (s) for the hardened PyTreeChannel: a recv
    # seeing no bytes this long raises instead of hanging the learner
    # on a silently dead peer.  0 = block forever (SO_KEEPALIVE still
    # bounds silent host death at the kernel level).
    channel_recv_deadline: float = 0.0
    # -- retries -------------------------------------------------------
    reward_attempts: int = 1        # reward_fn call attempts
    weight_sync_attempts: int = 1   # learner→rollout broadcast attempts
    checkpoint_save_attempts: int = 3
    # Deadline (s) for CheckpointManager.wait(); 0 = wait forever.
    checkpoint_wait_deadline: float = 0.0
    # -- shared backoff shape (RetryPolicy) ----------------------------
    backoff_base: float = 0.05
    backoff_multiplier: float = 2.0
    backoff_max: float = 2.0
    backoff_jitter: float = 0.1
    # -- deterministic chaos (orion_tpu.resilience.inject) -------------
    # Fault-plan spec string, e.g. "rollout.generate:at=4+5;
    # checkpoint.save:p=0.25,times=2"; armed at trainer construction.
    # The ORION_FAULT_PLAN env var arms the same thing with no code.
    fault_plan: Optional[str] = None
    fault_seed: int = 0

    def retry_policy(self, max_attempts: int, seed: int = 0):
        """A :class:`~orion_tpu.resilience.RetryPolicy` carrying this
        config's shared backoff shape — the one constructor every
        retry site (reward calls, weight sync) goes through, so a new
        backoff field propagates everywhere at once."""
        from orion_tpu.resilience import RetryPolicy

        return RetryPolicy(
            max_attempts=max_attempts, base_delay=self.backoff_base,
            multiplier=self.backoff_multiplier,
            max_delay=self.backoff_max, jitter=self.backoff_jitter,
            seed=seed)


@dataclass
class Setpoint:
    """One controlled signal's operating band for the SLO autopilot
    (orion_tpu.orchestration.autopilot).

    ``target`` is the value the controller steers toward (recorded as
    the error term in every decision), ``ceiling`` the escalate-above
    threshold and ``floor`` the relax-below threshold.  The floor <
    ceiling gap IS the hysteresis band — a signal oscillating inside it
    triggers nothing.  ``ceiling <= 0`` disables the signal entirely
    (the controller never reads it), which is how deterministic tests
    switch off wall-clock signals like TTFT p95.
    """

    target: float = 0.0
    floor: float = 0.0
    ceiling: float = 0.0

    def __post_init__(self) -> None:
        if self.target < 0 or self.floor < 0:
            raise ValueError(
                f"setpoint target/floor must be >= 0, got "
                f"target={self.target} floor={self.floor}")
        if self.ceiling > 0 and self.floor > self.ceiling:
            raise ValueError(
                f"setpoint floor {self.floor} above ceiling "
                f"{self.ceiling}: the hysteresis band would be empty "
                "and the controller would flap")


@dataclass
class ControllerConfig:
    """Closed-loop SLO autopilot (orion_tpu.orchestration.autopilot).

    The ROADMAP refactor: the engine's scattered tuning knobs become
    typed setpoints in ONE place.  Signals are read from
    ``server_stats()`` / scheduler gauges / pool recovery counters;
    actuators are the machinery PRs 6/10/12 already built
    (``apply_setpoints`` on the continuous engine, ``configure_tenant``
    envelopes, the launch.py worker-spawn path).  Off by default — the
    controller costs nothing unless armed.
    """

    enabled: bool = False
    # Wall-clock tick cadence (s) when a pump loop drives the
    # controller (gateway / orchestrators).  Deterministic tests call
    # tick() directly and never consult this.
    tick_interval: float = 0.25
    # Hysteresis: a signal must sit past its ceiling (or under its
    # floor) for this many CONSECUTIVE ticks before the ladder moves...
    hold_ticks: int = 3
    # ...and after any ladder transition the controller holds position
    # for this many ticks regardless of signals (anti-flap cooldown).
    cooldown_ticks: int = 4
    # -- controlled signals --------------------------------------------
    # Unadmitted (waiting) requests in the engine scheduler.
    queue_depth: Setpoint = field(default_factory=lambda: Setpoint(
        target=2.0, floor=1.0, ceiling=8.0))
    # Fraction of KV pages in use (1 - available/total).
    page_occupancy: Setpoint = field(default_factory=lambda: Setpoint(
        target=0.70, floor=0.50, ceiling=0.92))
    # Streaming TTFT p95 seconds from telemetry — a wall-clock signal,
    # disabled by default (ceiling 0) so seeded runs stay bit-exact;
    # real deployments arm it alongside the gauges.
    ttft: Setpoint = field(default_factory=Setpoint)
    # Speculative acceptance EMA (tokens/verify step): below floor the
    # controller raises spec_breakeven to tuned_spec_breakeven (the
    # verify chunk is not paying for itself), above ceiling it restores
    # the baseline.  ceiling 0 disables.
    spec_accept: Setpoint = field(default_factory=Setpoint)
    # Pool capacity: target = desired live workers (spawn below it),
    # ceiling = retire-above bound, floor = never retire below.
    # target 0 disables the capacity loop.
    workers: Setpoint = field(default_factory=Setpoint)
    # -- rung 1 (tuned) actuator values --------------------------------
    # Each 0 leaves that knob untouched at the tuned rung.
    tuned_spec_breakeven: float = 0.0   # >= 1.0 when set
    tuned_chunk_tokens: int = 0         # chunked_prefill_tokens under load
    tuned_watermark_delta: int = 0      # pages added to page_watermark
    # -- rung 2 (shed) actuator values ---------------------------------
    # QoS envelope clamped onto every non-protected tenant while the
    # shed rung holds (original envelopes restored on relax).
    shed_max_running: int = 1
    shed_max_queued: int = 1
    shed_rate_limit: float = 0.0        # 0 = leave the tenant's rate alone
    # Tenants the shed rung must never tighten (the paid tier).
    protect_tenants: tuple = ("paid",)

    def __post_init__(self) -> None:
        if isinstance(self.protect_tenants, str):
            self.protect_tenants = tuple(
                t.strip() for t in self.protect_tenants.split(",")
                if t.strip())
        self.protect_tenants = tuple(str(t) for t in self.protect_tenants)
        if self.tick_interval <= 0:
            raise ValueError(
                f"controller.tick_interval must be > 0, got "
                f"{self.tick_interval}")
        if self.hold_ticks < 1:
            raise ValueError(
                f"controller.hold_ticks must be >= 1, got "
                f"{self.hold_ticks}")
        if self.cooldown_ticks < 0:
            raise ValueError(
                f"controller.cooldown_ticks must be >= 0, got "
                f"{self.cooldown_ticks}")
        if self.tuned_spec_breakeven and self.tuned_spec_breakeven < 1.0:
            raise ValueError(
                f"controller.tuned_spec_breakeven must be >= 1.0 "
                f"(0 leaves spec_breakeven alone), got "
                f"{self.tuned_spec_breakeven}")
        if self.tuned_chunk_tokens < 0 or self.tuned_watermark_delta < 0:
            raise ValueError(
                "controller.tuned_chunk_tokens/tuned_watermark_delta "
                f"must be >= 0, got {self.tuned_chunk_tokens}/"
                f"{self.tuned_watermark_delta}")
        if self.shed_max_running < 1 or self.shed_max_queued < 1:
            raise ValueError(
                "controller.shed_max_running/shed_max_queued must be "
                ">= 1 (0 would mean UNLIMITED to the engine — the shed "
                f"rung would relax QoS, not tighten it), got "
                f"{self.shed_max_running}/{self.shed_max_queued}")
        if self.shed_rate_limit < 0:
            raise ValueError(
                f"controller.shed_rate_limit must be >= 0 (0 leaves "
                f"tenant rates alone), got {self.shed_rate_limit}")


@dataclass
class RolloutUpdateConfig:
    """Zero-downtime fleet weight rollout (orchestration.rollout_controller).

    Governs the blue/green per-engine cycle the
    ``WeightRolloutCoordinator`` runs when a new version-tagged param
    snapshot lands: DRAINING (stop admitting; in-flight requests finish
    or migrate with a RESTARTED stream marker at the drain deadline) →
    RELOAD (swap params, both KV tiers cleared) → CANARY (pinned greedy
    probes must return finite logprobs and match the recorded
    fingerprint shape) → READMIT.  Old params are retained until the
    fleet-wide commit point so every fault path can roll back."""

    # Pinned greedy probe requests per engine at the canary gate (0
    # disables the gate — reload goes straight to readmit).
    canary_prompts: int = 2
    # Token budget per canary probe (clamped to rollout.max_new_tokens).
    canary_budget: int = 4
    # Coordinator ticks (gateway pump iterations) an engine may spend
    # DRAINING before its in-flight requests are migrated to another
    # engine with a typed RESTARTED stream marker.  Tick-counted, not
    # wall-clock, so chaos runs replay bit-identically.
    drain_deadline_ticks: int = 200
    # Engines allowed in their blue/green cycle simultaneously.  1 =
    # strictly one-at-a-time (the default rolling update); must stay
    # below the fleet size or availability drops to zero.
    max_concurrent_drains: int = 1
    # What a failed step does: "auto" rolls every upgraded engine back
    # to the old snapshot; "halt" gates the failed engine off and stops
    # the roll (operator decides), leaving healthy engines serving.
    rollback_policy: str = "auto"

    def __post_init__(self) -> None:
        if self.canary_prompts < 0:
            raise ValueError(
                f"rollout_update.canary_prompts must be >= 0, got "
                f"{self.canary_prompts}")
        if self.canary_budget < 1:
            raise ValueError(
                f"rollout_update.canary_budget must be >= 1, got "
                f"{self.canary_budget}")
        if self.drain_deadline_ticks < 1:
            raise ValueError(
                f"rollout_update.drain_deadline_ticks must be >= 1, got "
                f"{self.drain_deadline_ticks}")
        if self.max_concurrent_drains < 1:
            raise ValueError(
                f"rollout_update.max_concurrent_drains must be >= 1, "
                f"got {self.max_concurrent_drains}")
        if self.rollback_policy not in ("auto", "halt"):
            raise ValueError(
                f"rollout_update.rollback_policy must be 'auto' or "
                f"'halt', got {self.rollback_policy!r}")


@dataclass
class TrainConfig:
    """Common trainer settings shared by all algorithms."""

    seed: int = 0
    model: ModelConfig = field(default_factory=ModelConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    rollout: RolloutConfig = field(default_factory=RolloutConfig)
    data: DataConfig = field(default_factory=DataConfig)
    # Policy init: HF checkpoint path (None => random init), or a
    # ModelConfig preset name ("llama3_8b"|"llama3_1b"|"pythia_1b") that
    # replaces `model` before any `model.*` key applies (load_config).
    hf_path: Optional[str] = None
    model_preset: Optional[str] = None  # orion: ignore[config-drift] consumed by load_config itself: the preset must land before the model.* overrides
    # Reward source: "math" (rule verifier), "length" (debug),
    # "model:<hf-or-ckpt-path>" (reward model scoring).
    reward: str = "math"

    total_iterations: int = 100
    # Held-out evaluation: every N iterations, generate on eval_batches
    # batches from the eval iterator (launch.py builds it from
    # data.eval_split) and log eval_reward_mean / eval lengths — no
    # parameter update.  0 disables.
    eval_every: int = 0
    eval_batches: int = 1
    # Experience batch: prompts per iteration; optimization runs
    # num_epochs passes of minibatches of size minibatch_size over it.
    rollout_batch_size: int = 32
    minibatch_size: int = 8
    num_epochs: int = 1
    # KL regularization against the frozen reference policy.
    kl_coef: float = 0.05
    # Storage dtype for the frozen reference snapshot (None => param
    # dtype).  The ref only ever runs forward; bf16 halves its HBM
    # share (2 GB saved at 1B) at the cost of ~1e-3 logprob drift.
    ref_param_dtype: Optional[str] = None
    adaptive_kl: bool = False
    kl_target: float = 6.0
    kl_horizon: int = 10000
    # Whitening / reward shaping.
    whiten_advantages: bool = True
    reward_clip: float = 10.0
    # Checkpointing / logging.
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0  # 0 => disabled
    checkpoint_keep: int = 3
    log_every: int = 1
    log_dir: Optional[str] = None  # jsonl (+tensorboard) metrics stream
    # Profiling (SURVEY.md §5 tracing): capture a jax.profiler trace
    # (xplane + perfetto) of `profile_steps` iterations, starting at
    # `profile_start` (default 1 = first post-compile iteration).
    profile_dir: Optional[str] = None
    profile_steps: int = 2
    profile_start: int = 1
    # Async mode (SPEC config 4).
    async_mode: bool = False
    async_staleness: int = 1  # max steps rollout weights may lag
    rollout_devices: int = 0  # devices reserved for rollout group (async)
    # Runtime guards (orion_tpu.analysis.runtime_guards).
    # transfer_guard: jax.transfer_guard level applied around the train
    # loop — None/"allow" off, "log" prints every IMPLICIT host
    # transfer, "disallow" raises on them (explicit device_get fetches
    # stay allowed).  recompile_budget: warn when any single jitted fn
    # compiles more than this many times (0 disables the sentinel).
    transfer_guard: Optional[str] = None
    recompile_budget: int = 0
    # Fault handling (orion_tpu.resilience): supervisor budgets,
    # retries, quarantine, and the deterministic fault-injection plan.
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    # Observability (orion_tpu.obs): span tracing, Perfetto export,
    # and the crash flight recorder.
    obs: ObsConfig = field(default_factory=ObsConfig)
    # Closed-loop SLO autopilot (orion_tpu.orchestration.autopilot):
    # typed setpoints + the load-shed rung of the degradation ladder.
    controller: ControllerConfig = field(
        default_factory=ControllerConfig)
    # Zero-downtime fleet weight rollout
    # (orion_tpu.orchestration.rollout_controller): blue/green drain →
    # reload → canary → readmit per engine, with auto-rollback.
    rollout_update: RolloutUpdateConfig = field(
        default_factory=RolloutUpdateConfig)


@dataclass
class PPOConfig(TrainConfig):
    clip_ratio: float = 0.2
    value_clip: float = 0.2
    vf_coef: float = 0.1
    gamma: float = 1.0
    gae_lambda: float = 0.95
    num_epochs: int = 4
    # Shared policy/value trunk (models.heads.ActorCriticModel): one
    # backbone pass serves both losses, and the critic costs one
    # Dense(E,1) instead of a second model+Adam state — how a 1B PPO
    # session fits a single 16G chip.  False => separate critic model.
    share_backbone: bool = False


@dataclass
class GRPOConfig(TrainConfig):
    group_size: int = 8  # completions per prompt
    clip_ratio: float = 0.2
    # DR-GRPO / GRPO variants: "grpo" normalizes by group std, "dr_grpo" skips.
    variant: str = "grpo"


@dataclass
class RLOOConfig(TrainConfig):
    group_size: int = 4  # k rollouts per prompt, leave-one-out baseline
    # RLOO applies KL inside the reward (sequence-level) by default.
    kl_in_reward: bool = True


@dataclass
class OnlineDPOConfig(TrainConfig):
    beta: float = 0.1
    group_size: int = 2  # sample a pair per prompt
    label_smoothing: float = 0.0


# ---------------------------------------------------------------------------
# Loading helpers
# ---------------------------------------------------------------------------


def _apply_overrides(cfg: Any, overrides: dict) -> Any:
    for key, value in overrides.items():
        parts = key.split(".")
        obj = cfg
        for p in parts[:-1]:
            obj = getattr(obj, p)
        leaf = parts[-1]
        if not hasattr(obj, leaf):
            raise KeyError(f"unknown config key: {key}")
        current = getattr(obj, leaf)
        if current is not None and not dataclasses.is_dataclass(current):
            if isinstance(current, bool) and isinstance(value, str):
                value = value.lower() in ("1", "true", "yes")
            elif isinstance(current, tuple) and isinstance(value, (list, tuple)):
                value = tuple(value)
            elif isinstance(current, tuple) and isinstance(value, str):
                elem_type = type(current[0]) if current else float
                value = tuple(elem_type(v) for v in value.split(","))
            elif current is not None and isinstance(value, str):
                value = type(current)(value)
        setattr(obj, leaf, value)
    return cfg


def load_config(cls, yaml_path: Optional[str] = None,
                cli_args: Optional[list] = None):
    """Build a config from an optional yaml file plus ``key=value`` CLI args.

    Nested keys use dots: ``model.hidden_size=1024 optimizer.learning_rate=3e-6``.
    ``model_preset=<name>`` replaces ``model`` with that ModelConfig
    preset FIRST, wherever it appears; ``model.*`` keys given beside it
    apply on top of the preset (``model_preset=pythia_1b
    model.remat=true``).
    """
    cfg = cls()
    overrides = {}
    if yaml_path:
        import yaml  # lazy: pyyaml ships with the base image

        with open(yaml_path) as f:
            data = yaml.safe_load(f) or {}

        def flatten(d, prefix=""):
            out = {}
            for k, v in d.items():
                kk = f"{prefix}{k}"
                if isinstance(v, dict):
                    out.update(flatten(v, kk + "."))
                else:
                    out[kk] = v
            return out

        overrides.update(flatten(data))
    for arg in cli_args or []:
        if "=" not in arg:
            raise ValueError(f"expected key=value, got {arg!r}")
        k, v = arg.split("=", 1)
        overrides.pop(k, None)  # CLI wins, and keeps CLI order
        overrides[k] = v
    preset = overrides.get("model_preset")
    if preset:
        _apply_overrides(cfg, {"model_preset": preset})
        cfg.model = getattr(ModelConfig, cfg.model_preset)()
    return _apply_overrides(cfg, overrides)
