"""JAX param-tree → HF checkpoint export (SURVEY.md §5 checkpoint/
resume: "HF-format export for eval compatibility"; VERDICT r1 missing
#6).  Exact inverse of models.hf_loader: writes ``model.safetensors`` +
``config.json`` that ``transformers.AutoModelForCausalLM`` loads
directly, so policies trained here drop into the GPU ecosystem's eval
harnesses unchanged.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

import numpy as np

from orion_tpu.config import ModelConfig
from orion_tpu.models.hf_loader import unstack_layer_params


def _np32(x: Any) -> np.ndarray:
    x = np.asarray(x)
    if x.dtype.name in ("bfloat16", "float16"):
        x = x.astype(np.float32)
    return x


def _w(lin: Dict[str, Any]) -> np.ndarray:
    """flax Dense {kernel [in, out]} -> HF weight [out, in]."""
    return _np32(lin["kernel"]).T.copy()


def hf_state_dict(params: dict, cfg: ModelConfig) -> Dict[str, np.ndarray]:
    """Convert a policy param tree to the HF naming/layout."""
    if cfg.num_experts > 0:
        raise ValueError(
            "HF export of MoE models is not supported: the expert-"
            "stacked MLP (ops.moe) has no llama/neox HF layout")
    if cfg.arch == "deepseek_v3":
        raise ValueError(
            "HF export of arch='deepseek_v3' is not written: there is no "
            "deepseek_v3 checkpoint layout (per-expert gate/up/down "
            "tensors, kv_a/kv_b projections) on either side yet")
    if cfg.arch == "kimi_linear":
        raise ValueError(
            "HF export of arch='kimi_linear' is not written: there is no "
            "kimi_linear checkpoint layout on either side yet (the KDA "
            "layers' convolutions [channels, 1, taps], low-rank gates, "
            "A_log and dt_bias; per-expert tensors; kv_a/kv_b projections)")
    if cfg.arch == "sdar_moe":
        raise ValueError(
            "HF export of arch='sdar_moe' is not written: there is no "
            "sdar_moe checkpoint layout on either side yet (per-expert "
            "tensors, the tokenizer's mask id against mask_token_id)")
    if cfg.arch == "keye_dsa":
        raise ValueError(
            "HF export of arch='keye_dsa' is not written: there is no "
            "KeyeVL2 checkpoint layout on either side yet (the indexer's "
            "projections and norm, per-expert tensors, the vision tower)")
    if cfg.arch == "nemotron_h":
        raise ValueError(
            "HF export of arch='nemotron_h' is not written: there is no "
            "nemotron_h checkpoint layout on either side yet (a Mamba-2 "
            "layer's convolution [channels, 1, taps], A_log, D, dt_bias and "
            "gated norm; per-expert up / down tensors and the latent "
            "projections; a share of the heads is part of a checkpoint)")
    if cfg.arch == "ouro":
        raise ValueError(
            "HF export of arch='ouro' is not written: there is no ouro "
            "checkpoint layout on either side yet (four norms a block, the "
            "exit gate; the checkpoint's files are not in this repository)")
    if cfg.arch == "mellum":
        raise ValueError(
            "HF export of arch='mellum' is not written: there is no mellum "
            "checkpoint layout on either side yet (per-expert gate / up / "
            "down tensors, per-head q / k norms; the checkpoint's files are "
            "not in this repository)")
    if cfg.arch == "lfm2_moe":
        raise ValueError(
            "HF export of arch='lfm2_moe' is not written: there is no "
            "lfm2_moe checkpoint layout on either side yet (a convolution "
            "layer's in_proj / conv [channels, 1, taps] / out_proj, "
            "per-expert w1 / w2 / w3 tensors, expert_bias)")
    if cfg.arch == "olmo_hybrid":
        raise ValueError(
            "HF export of arch='olmo_hybrid' is not written: there is no "
            "olmo_hybrid checkpoint layout on either side yet (the gated-"
            "delta-rule layers' convolutions [channels, 1, taps], a / b / "
            "z projections, A_log and dt_bias)")
    params = dict(params)
    if "backbone" in params:  # ActorCriticModel / ScalarHeadModel tree
        params = dict(params["backbone"])
    if "layers" in params:  # scan_layers stacked layout
        params = unstack_layer_params(params, cfg.num_layers)
    if cfg.arch == "llama":
        return _export_llama(params, cfg)
    if cfg.arch == "neox":
        return _export_neox(params, cfg)
    raise ValueError(cfg.arch)


def _export_llama(p: dict, cfg: ModelConfig) -> Dict[str, np.ndarray]:
    sd = {"model.embed_tokens.weight": _np32(p["embed"]["embedding"])}

    def lin(dst, src):
        sd[dst + ".weight"] = _w(src)
        if "bias" in src:  # attn_bias/mlp_bias configs (Qwen2-style)
            sd[dst + ".bias"] = _np32(src["bias"])

    for i in range(cfg.num_layers):
        L = p[f"layers_{i}"]
        pre = f"model.layers.{i}."
        lin(pre + "self_attn.q_proj", L["attn"]["q_proj"])
        lin(pre + "self_attn.k_proj", L["attn"]["k_proj"])
        lin(pre + "self_attn.v_proj", L["attn"]["v_proj"])
        lin(pre + "self_attn.o_proj", L["attn"]["o_proj"])
        lin(pre + "mlp.gate_proj", L["mlp"]["gate_proj"])
        lin(pre + "mlp.up_proj", L["mlp"]["up_proj"])
        lin(pre + "mlp.down_proj", L["mlp"]["down_proj"])
        sd[pre + "input_layernorm.weight"] = _np32(L["input_norm"]["scale"])
        sd[pre + "post_attention_layernorm.weight"] = \
            _np32(L["post_attn_norm"]["scale"])
    sd["model.norm.weight"] = _np32(p["final_norm"]["scale"])
    if not cfg.tie_word_embeddings:
        sd["lm_head.weight"] = _w(p["lm_head"])
    return sd


def _export_neox(p: dict, cfg: ModelConfig) -> Dict[str, np.ndarray]:
    H, D, E = cfg.num_heads, cfg.head_dim, cfg.hidden_size
    sd = {"gpt_neox.embed_in.weight": _np32(p["embed"]["embedding"])}
    for i in range(cfg.num_layers):
        L = p[f"layers_{i}"]
        pre = f"gpt_neox.layers.{i}."
        # Re-fuse q/k/v head-major: [H, 3, D, E] -> [H*3*D, E]
        # (inverse of hf_loader._convert_neox).
        qw = _w(L["attn"]["q_proj"]).reshape(H, D, E)
        kw = _w(L["attn"]["k_proj"]).reshape(H, D, E)
        vw = _w(L["attn"]["v_proj"]).reshape(H, D, E)
        qkv_w = np.stack([qw, kw, vw], axis=1).reshape(H * 3 * D, E)
        qb = _np32(L["attn"]["q_proj"]["bias"]).reshape(H, D)
        kb = _np32(L["attn"]["k_proj"]["bias"]).reshape(H, D)
        vb = _np32(L["attn"]["v_proj"]["bias"]).reshape(H, D)
        qkv_b = np.stack([qb, kb, vb], axis=1).reshape(H * 3 * D)
        sd[pre + "attention.query_key_value.weight"] = qkv_w
        sd[pre + "attention.query_key_value.bias"] = qkv_b
        sd[pre + "attention.dense.weight"] = _w(L["attn"]["o_proj"])
        sd[pre + "attention.dense.bias"] = _np32(L["attn"]["o_proj"]["bias"])
        sd[pre + "mlp.dense_h_to_4h.weight"] = _w(L["mlp"]["up_proj"])
        sd[pre + "mlp.dense_h_to_4h.bias"] = _np32(L["mlp"]["up_proj"]["bias"])
        sd[pre + "mlp.dense_4h_to_h.weight"] = _w(L["mlp"]["down_proj"])
        sd[pre + "mlp.dense_4h_to_h.bias"] = \
            _np32(L["mlp"]["down_proj"]["bias"])
        sd[pre + "input_layernorm.weight"] = _np32(L["input_norm"]["scale"])
        sd[pre + "input_layernorm.bias"] = _np32(L["input_norm"]["bias"])
        sd[pre + "post_attention_layernorm.weight"] = \
            _np32(L["post_attn_norm"]["scale"])
        sd[pre + "post_attention_layernorm.bias"] = \
            _np32(L["post_attn_norm"]["bias"])
    sd["gpt_neox.final_layer_norm.weight"] = _np32(p["final_norm"]["scale"])
    sd["gpt_neox.final_layer_norm.bias"] = _np32(p["final_norm"]["bias"])
    if not cfg.tie_word_embeddings:  # tied models never create lm_head
        sd["embed_out.weight"] = _w(p["lm_head"])
    return sd


def hf_config_dict(cfg: ModelConfig) -> dict:
    if cfg.arch == "llama":
        return {
            "architectures": ["LlamaForCausalLM"],
            "model_type": "llama",
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim,
            "max_position_embeddings": cfg.max_seq_len,
            "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.rms_norm_eps,
            "tie_word_embeddings": cfg.tie_word_embeddings,
            "hidden_act": "silu",
            "torch_dtype": "float32",
            "attention_bias": cfg.attn_bias,
            "mlp_bias": cfg.mlp_bias,
        }
    if cfg.arch == "neox":
        return {
            "architectures": ["GPTNeoXForCausalLM"],
            "model_type": "gpt_neox",
            "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "max_position_embeddings": cfg.max_seq_len,
            "rotary_emb_base": cfg.rope_theta,
            "rotary_pct": cfg.rotary_pct,
            "layer_norm_eps": cfg.layernorm_eps,
            "use_parallel_residual": cfg.use_parallel_residual,
            "tie_word_embeddings": cfg.tie_word_embeddings,
            "hidden_act": "gelu",
            "torch_dtype": "float32",
        }
    raise ValueError(cfg.arch)


def save_hf_pretrained(params: dict, cfg: ModelConfig, path: str) -> None:
    """Write ``config.json`` + ``model.safetensors`` loadable by
    ``transformers.AutoModelForCausalLM.from_pretrained(path)``.

    ``params`` may be the plain Transformer tree, an ActorCritic/
    ScalarHead tree (the backbone is exported; heads are dropped — HF
    has no slot for them), stacked (scan_layers) or unrolled, on device
    or host; sharded arrays are gathered via one host fetch per leaf.
    """
    from safetensors.numpy import save_file

    os.makedirs(path, exist_ok=True)
    sd = hf_state_dict(params, cfg)
    # safetensors requires contiguous arrays
    sd = {k: np.ascontiguousarray(v) for k, v in sd.items()}
    save_file(sd, os.path.join(path, "model.safetensors"),
              metadata={"format": "pt"})
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf_config_dict(cfg), f, indent=2)
