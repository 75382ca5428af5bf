"""HF checkpoint → JAX param-tree conversion (SURVEY.md §2 #14).

Two entry points:
- ``convert_hf_state_dict(state_dict, cfg)`` — takes an in-memory
  mapping of HF parameter names to numpy/torch tensors (used by the
  parity tests, which build tiny HF torch models directly).
- ``load_hf_pretrained(path, cfg)`` — streams ``*.safetensors`` files
  from a local HF checkpoint directory (zero-egress box: weights must
  already be on disk).

torch is CPU-only in this image and used solely here, for tensor
deserialization — it never touches the compute path.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, Mapping

import numpy as np

from orion_tpu.config import ModelConfig


def _np(t: Any) -> np.ndarray:
    """To numpy, upcasting sub-f32 floats (bf16/f16 checkpoints) to f32
    so the f32-master-weights contract holds regardless of source dtype."""
    if not isinstance(t, np.ndarray):
        import torch

        if t.dtype == torch.bfloat16:
            t = t.float()
        t = t.detach().cpu().numpy()
    # ml_dtypes.bfloat16 (safetensors framework="np") registers as a
    # custom numpy dtype; detect by name.
    if t.dtype.name in ("bfloat16", "float16"):
        t = t.astype(np.float32)
    return t


def _lin(w: Any, bias: Any = None) -> Dict[str, np.ndarray]:
    out = {"kernel": _np(w).T.copy()}
    if bias is not None:
        out["bias"] = _np(bias)
    return out


_NO_DSV3_LOADER = (
    "there is no deepseek_v3 checkpoint loader yet: the per-expert "
    "gate/up/down tensors have to be stacked over the experts held "
    "(gate and up fused), and kv_a_proj_with_mqa / kv_b_proj mapped; "
    "arch='deepseek_v3' runs from random weights only")
_NO_KIMI_LOADER = (
    "there is no kimi_linear checkpoint loader yet: beside what a "
    "deepseek_v3 checkpoint needs (experts stacked over those held, "
    "kv_a / kv_b mapped), the KDA layers' three depthwise convolutions, "
    "low-rank gates, A_log and dt_bias have no mapping onto "
    "models.transformer.KimiDeltaAttention's names and [taps, channels] "
    "layout; arch='kimi_linear' runs from random weights only")
_NO_OLMO_HYBRID_LOADER = (
    "there is no olmo_hybrid checkpoint loader yet: the gated-delta-rule "
    "layers' convolutions, a / b / z projections, A_log and dt_bias have "
    "no mapping onto models.transformer.GatedDeltaNet's names and "
    "[taps, channels] layout; arch='olmo_hybrid' runs from random "
    "weights only")
_NO_NEMOTRON_H_LOADER = (
    "there is no nemotron_h checkpoint loader yet: a Mamba-2 layer's "
    "in_proj / conv1d / A_log / D / dt_bias / norm / out_proj, the "
    "per-expert up / down tensors and fc1 / fc2_latent_proj have no "
    "mapping onto models.transformer.Mamba2's and ops.moe.TopKMoE's names "
    "and [taps, channels] layout, nor is there a cut of a checkpoint to a "
    "share of the heads; arch='nemotron_h' runs from random weights only")
_NO_KEYE_LOADER = (
    "there is no KeyeVL2 checkpoint loader yet: the indexer's projections "
    "and norm, the per-expert tensors and the vision tower have no mapping "
    "onto models.transformer.SparseAttention's and ops.moe.TopKMoE's "
    "names; arch='keye_dsa' runs from random weights only")

_NO_SDAR_LOADER = (
    "there is no sdar_moe checkpoint loader yet: the per-expert tensors "
    "have no mapping onto ops.moe.TopKMoE's names and the tokenizer's mask "
    "id none onto model.mask_token_id; arch='sdar_moe' runs from random "
    "weights only")

_NO_LFM2_LOADER = (
    "there is no lfm2_moe checkpoint loader yet: a convolution layer's "
    "in_proj / conv [channels, 1, taps] / out_proj, the per-expert w1 / w2 "
    "/ w3 tensors and expert_bias have no mapping onto "
    "models.transformer.ShortConv's and ops.moe.TopKMoE's names and "
    "[taps, channels] layout; arch='lfm2_moe' runs from random weights "
    "only")

_NO_MELLUM_LOADER = (
    "there is no mellum checkpoint loader yet: the checkpoint's files are "
    "not in this repository (no network), so its tensor names (per-expert "
    "gate / up / down, the per-head q / k norms, the multi-token-prediction "
    "head this model leaves out) have no mapping onto models.transformer."
    "Attention's and ops.moe.TopKMoE's that was checked against them; "
    "arch='mellum' runs from random weights only")


_NO_OURO_LOADER = (
    "there is no ouro checkpoint loader yet: the checkpoint's files are "
    "not in this repository (no network), so its tensor names (the four "
    "norms a block, input_layernorm / input_layernorm_2 / "
    "post_attention_layernorm / post_attention_layernorm_2, and the exit "
    "gate) have no mapping onto models.transformer.Block's and "
    "Transformer's that was checked against them; arch='ouro' runs from "
    "random weights only")


def convert_hf_state_dict(sd: Mapping[str, Any], cfg: ModelConfig,
                          include_lm_head: bool = True) -> dict:
    if cfg.arch == "llama":
        p = _convert_llama(sd, cfg)
    elif cfg.arch == "neox":
        p = _convert_neox(sd, cfg)
    elif cfg.arch == "deepseek_v3":
        raise ValueError(_NO_DSV3_LOADER)
    elif cfg.arch == "kimi_linear":
        raise ValueError(_NO_KIMI_LOADER)
    elif cfg.arch == "olmo_hybrid":
        raise ValueError(_NO_OLMO_HYBRID_LOADER)
    elif cfg.arch == "keye_dsa":
        raise ValueError(_NO_KEYE_LOADER)
    elif cfg.arch == "sdar_moe":
        raise ValueError(_NO_SDAR_LOADER)
    elif cfg.arch == "nemotron_h":
        raise ValueError(_NO_NEMOTRON_H_LOADER)
    elif cfg.arch == "lfm2_moe":
        raise ValueError(_NO_LFM2_LOADER)
    elif cfg.arch == "mellum":
        raise ValueError(_NO_MELLUM_LOADER)
    elif cfg.arch == "ouro":
        raise ValueError(_NO_OURO_LOADER)
    else:
        raise ValueError(cfg.arch)
    if not include_lm_head:
        p.pop("lm_head", None)
    if cfg.scan_layers:
        p = stack_layer_params(p, cfg.num_layers)
    return p


def stack_layer_params(p: dict, num_layers: int) -> dict:
    """layers_0..layers_{N-1} sub-trees → one "layers" tree with a
    leading [N] axis (the scan_layers param layout).  Returns a new
    top-level dict; the input is not mutated."""
    import jax

    p = dict(p)
    layers = [p.pop(f"layers_{i}") for i in range(num_layers)]
    p["layers"] = jax.tree.map(lambda *xs: np.stack(xs), *layers)
    return p


def unstack_layer_params(p: dict, num_layers: int) -> dict:
    """Inverse of :func:`stack_layer_params` (HF export path), as host
    numpy.  Thin wrapper over the jit-safe
    models.transformer.unstack_params_tree (single source of truth for
    the stacked-layers inverse)."""
    import jax

    from orion_tpu.models.transformer import unstack_params_tree

    return jax.tree.map(np.asarray, unstack_params_tree(
        p, {"layers": (0, num_layers)}))


def _convert_llama(sd: Mapping[str, Any], cfg: ModelConfig) -> dict:
    p: dict = {"embed": {"embedding": _np(sd["model.embed_tokens.weight"])}}
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        p[f"layers_{i}"] = {
            "attn": {
                "q_proj": _lin(sd[pre + "self_attn.q_proj.weight"]),
                "k_proj": _lin(sd[pre + "self_attn.k_proj.weight"]),
                "v_proj": _lin(sd[pre + "self_attn.v_proj.weight"]),
                "o_proj": _lin(sd[pre + "self_attn.o_proj.weight"]),
            },
            "mlp": {
                "gate_proj": _lin(sd[pre + "mlp.gate_proj.weight"]),
                "up_proj": _lin(sd[pre + "mlp.up_proj.weight"]),
                "down_proj": _lin(sd[pre + "mlp.down_proj.weight"]),
            },
            "input_norm": {"scale": _np(sd[pre + "input_layernorm.weight"])},
            "post_attn_norm": {
                "scale": _np(sd[pre + "post_attention_layernorm.weight"])},
        }
    p["final_norm"] = {"scale": _np(sd["model.norm.weight"])}
    if not cfg.tie_word_embeddings:
        key = "lm_head.weight"
        if key not in sd:  # tied checkpoints omit it
            key = "model.embed_tokens.weight"
        p["lm_head"] = _lin(sd[key])
    return p


def _convert_neox(sd: Mapping[str, Any], cfg: ModelConfig) -> dict:
    H, D, E = cfg.num_heads, cfg.head_dim, cfg.hidden_size
    p: dict = {"embed": {"embedding": _np(sd["gpt_neox.embed_in.weight"])}}
    for i in range(cfg.num_layers):
        pre = f"gpt_neox.layers.{i}."
        # HF GPT-NeoX fuses qkv head-major: weight [H*3*D, E] viewed as
        # [H, 3, D, E]; split into per-head q/k/v then flatten back.
        qkv_w = _np(sd[pre + "attention.query_key_value.weight"])
        qkv_w = qkv_w.reshape(H, 3, D, E)
        qkv_b = _np(sd[pre + "attention.query_key_value.bias"]).reshape(H, 3, D)

        def proj(j):
            w = qkv_w[:, j].reshape(H * D, E)
            b = qkv_b[:, j].reshape(H * D)
            return {"kernel": w.T.copy(), "bias": b}

        p[f"layers_{i}"] = {
            "attn": {
                "q_proj": proj(0),
                "k_proj": proj(1),
                "v_proj": proj(2),
                "o_proj": _lin(sd[pre + "attention.dense.weight"],
                               sd[pre + "attention.dense.bias"]),
            },
            "mlp": {
                "up_proj": _lin(sd[pre + "mlp.dense_h_to_4h.weight"],
                                sd[pre + "mlp.dense_h_to_4h.bias"]),
                "down_proj": _lin(sd[pre + "mlp.dense_4h_to_h.weight"],
                                  sd[pre + "mlp.dense_4h_to_h.bias"]),
            },
            "input_norm": {
                "scale": _np(sd[pre + "input_layernorm.weight"]),
                "bias": _np(sd[pre + "input_layernorm.bias"]),
            },
            "post_attn_norm": {
                "scale": _np(sd[pre + "post_attention_layernorm.weight"]),
                "bias": _np(sd[pre + "post_attention_layernorm.bias"]),
            },
        }
    p["final_norm"] = {
        "scale": _np(sd["gpt_neox.final_layer_norm.weight"]),
        "bias": _np(sd["gpt_neox.final_layer_norm.bias"]),
    }
    p["lm_head"] = _lin(sd["embed_out.weight"])
    return p


def _read_safetensors(path: str) -> Dict[str, np.ndarray]:
    from safetensors import safe_open

    files = sorted(glob.glob(os.path.join(path, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no safetensors under {path}")
    sd: Dict[str, np.ndarray] = {}
    for f in files:
        with safe_open(f, framework="np") as st:
            for k in st.keys():
                sd[k] = st.get_tensor(k)
    return sd


def load_hf_pretrained(path: str, cfg: ModelConfig) -> dict:
    """Load a local HF safetensors checkpoint directory."""
    return convert_hf_state_dict(_read_safetensors(path), cfg)


def load_hf_scalar_model(path: str, cfg: ModelConfig) -> dict:
    """Params for ScalarHeadModel from a HF sequence-classification
    checkpoint (reward model / critic init, SURVEY.md §2 #6-7).

    Expects the usual ``score.weight`` [1, E] head; raises if absent —
    a reward model with a random head would silently produce noise
    scores, which is worse than failing.
    """
    sd = _read_safetensors(path)
    head_key = next((k for k in ("score.weight", "v_head.weight",
                                 "classifier.weight") if k in sd), None)
    if head_key is None:
        raise KeyError(
            f"{path} has no scalar head (score.weight); not a "
            "sequence-classification checkpoint")
    backbone = convert_hf_state_dict(sd, cfg, include_lm_head=False)
    return {"backbone": backbone,
            "score_head": {"kernel": _np(sd[head_key]).T.copy()}}


def config_from_hf(hf_cfg: Any) -> ModelConfig:
    """Build a ModelConfig from a transformers config object."""
    mt = getattr(hf_cfg, "model_type", "")
    if mt == "deepseek_v3":
        raise ValueError(_NO_DSV3_LOADER)
    if mt == "kimi_linear":
        raise ValueError(_NO_KIMI_LOADER)
    if mt == "olmo_hybrid":
        raise ValueError(_NO_OLMO_HYBRID_LOADER)
    if mt in ("KeyeVL2", "keye_vl2"):
        raise ValueError(_NO_KEYE_LOADER)
    if mt == "sdar_moe":
        raise ValueError(_NO_SDAR_LOADER)
    if mt == "nemotron_h":
        raise ValueError(_NO_NEMOTRON_H_LOADER)
    if mt == "lfm2_moe":
        raise ValueError(_NO_LFM2_LOADER)
    if mt == "mellum":
        raise ValueError(_NO_MELLUM_LOADER)
    if mt == "ouro":
        raise ValueError(_NO_OURO_LOADER)
    if mt == "llama":
        return ModelConfig(
            arch="llama",
            vocab_size=hf_cfg.vocab_size,
            hidden_size=hf_cfg.hidden_size,
            intermediate_size=hf_cfg.intermediate_size,
            num_layers=hf_cfg.num_hidden_layers,
            num_heads=hf_cfg.num_attention_heads,
            num_kv_heads=hf_cfg.num_key_value_heads,
            max_seq_len=hf_cfg.max_position_embeddings,
            rope_theta=hf_cfg.rope_theta,
            rms_norm_eps=hf_cfg.rms_norm_eps,
            tie_word_embeddings=hf_cfg.tie_word_embeddings,
        )
    if mt == "gpt_neox":
        return ModelConfig(
            arch="neox",
            vocab_size=hf_cfg.vocab_size,
            hidden_size=hf_cfg.hidden_size,
            intermediate_size=hf_cfg.intermediate_size,
            num_layers=hf_cfg.num_hidden_layers,
            num_heads=hf_cfg.num_attention_heads,
            max_seq_len=hf_cfg.max_position_embeddings,
            rope_theta=getattr(hf_cfg, "rotary_emb_base", 10000.0),
            rotary_pct=hf_cfg.rotary_pct,
            layernorm_eps=hf_cfg.layer_norm_eps,
            use_parallel_residual=hf_cfg.use_parallel_residual,
            attn_bias=True, mlp_bias=True,
            tie_word_embeddings=hf_cfg.tie_word_embeddings,
        )
    raise ValueError(f"unsupported HF model_type: {mt}")
