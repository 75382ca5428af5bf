"""int8 quantization for the decode path (VERDICT r3 task #1).

Decode at RLHF shapes is HBM-bandwidth-bound (measured: 9.5 ms/step at
1B/B=32 vs a 2.5-3.5 ms weight-read floor, PERF.md anatomy), so halving
the bytes moved per step moves the floor itself.  Two independent,
opt-in (RolloutConfig) reductions:

- **Weight-only int8** (``quantize_params_int8`` + the transformer's
  ``QuantDense``): every 2-D Dense kernel is stored int8 with a
  per-output-channel f32 scale.  The matmul computes
  ``(x @ kernel_q.astype(bf16)) * scale`` — XLA fuses the int8→bf16
  convert into the dot's operand read (measured on-chip: 1.76x over
  bf16 for a 16-layer MLP stack), so HBM traffic is 1 byte/param and
  the MXU still runs bf16 math.  No activation quantization → no
  accumulation of activation error through the network.

- **int8 KV cache** (``quantize_kv``/dequant + the int8 decode
  attention in models/transformer.py): K/V stored int8 with per-token
  per-head scales over the head dim.  Scales are applied to the
  *scores* (K) and folded into the *probs* (V) — both small [B, H, 1,
  L] tensors — so the big cache operands enter the einsums as bare
  int8→bf16 converts that fuse the same way.

The training graph is untouched: sync-mode trainers recompute
old-logprobs under the full-precision training graph, so the update
math never sees quantization error; the rollout engine's sampled tokens
come from a (slightly) quantized policy, which is the same trade every
fp8/int8-serving RLHF stack makes (reference: vLLM quantized rollouts;
SURVEY.md §2 #5).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

_EPS = 1e-8


def quantize_kernel(kernel: jnp.ndarray):
    """[in, out] float kernel -> (int8 kernel, f32 per-out-column scale)."""
    k32 = kernel.astype(jnp.float32)
    amax = jnp.max(jnp.abs(k32), axis=0)
    scale = jnp.maximum(amax, _EPS) / 127.0
    q = jnp.clip(jnp.round(k32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_params_int8(params: Any) -> Any:
    """Map every Dense param subtree {kernel [in,out], bias?} to the
    QuantDense layout {kernel_q int8, scale f32[out], bias?}.  Leaves
    everything else (embeddings, norms, raw head params) untouched, so
    the result matches a model built with ``ModelConfig.quantize_dense
    = True``.  Runs fine inside jit (the rollout engine quantizes once
    per generate call — one pass over the weights, amortized over every
    decode step)."""
    if not isinstance(params, dict):
        return params
    if "kv_b_proj" in params or "experts_gate_up_proj" in params:
        raise ValueError(
            "quantize_params_int8 cannot quantise a deepseek_v3 tree: "
            "there are no int8 expert stacks and no int8 form of the "
            "absorbed kv_b_proj yet (run bfloat16 rollout weights)")
    out = {}
    for name, sub in params.items():
        if name == "router":
            # The MoE router stays a plain nn.Dense in the model
            # (quantize_dense only reroutes _dense call sites), and its
            # [Dm, E] kernel is tiny — no bandwidth to win.  Rewriting
            # it would desync the param tree from the module.
            out[name] = sub
        elif isinstance(sub, dict) and "kernel" in sub and \
                getattr(sub["kernel"], "ndim", 0) == 2 and \
                jnp.issubdtype(sub["kernel"].dtype, jnp.floating):
            q, scale = quantize_kernel(sub["kernel"])
            new = {"kernel_q": q, "scale": scale}
            if "bias" in sub:
                new["bias"] = sub["bias"]
            out[name] = new
        elif isinstance(sub, dict):
            out[name] = quantize_params_int8(sub)
        else:
            out[name] = sub
    return out


def quantize_kv(x: jnp.ndarray):
    """[..., D] K or V tensor -> (int8 values, f32 scale over [...]).

    Per-vector symmetric scale (one per token per head): the standard
    int8-KV-cache recipe — D-dim vectors quantize with ~0.4% RMS error,
    negligible against sampling temperature."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.maximum(amax, _EPS) / 127.0
    q = jnp.clip(jnp.round(x32 / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequant_kv(q: jnp.ndarray, scale: jnp.ndarray,
               dtype=jnp.bfloat16) -> jnp.ndarray:
    """Inverse of quantize_kv; used on the prefill path where the
    standard (unquantized) attention consumes the cache — XLA fuses the
    convert+mul into the attention's operand reads."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)
