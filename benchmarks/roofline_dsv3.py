"""Operations and bytes of the Pallas kernels the ``deepseek_v3`` block
runs, per training iteration of a ``train_job``, and the share of the
roofline a kernel reached in a traced window.

A kernel's share is the least time the chip could take for the work the
ALGORITHM needs in the window (the larger of operations over the peak
rate and bytes over the peak bandwidth, ``peaks.json``) over the
kernel's self time in the trace, found by the instruction's name
(``named_pallas_call``: ``%<kernel>.<n>``).  Padding inside a kernel (a
row tile that a group only partly fills, a causal block above the
diagonal) is the kernel's to pay: it is time, not work.

Which passes run a kernel in one iteration (minibatches of the update
see every sample once an epoch, so the update's tokens are the batch's):

- a forward over the prompts (the rollout's prefill; decode steps run
  no kernel: one token a step takes the absorbed attention and the
  dense expert path);
- two forwards over the whole sequences (experience: policy + values,
  reference);
- per epoch a forward, the same forward again under remat, and a
  backward, over the whole sequences.
"""

from __future__ import annotations

import json
import os
import re

BF16 = 2.0


def passes(job_counters: dict):
    """[(tokens per sample, keys per query on average, forward passes,
    backward passes)] per iteration."""
    P = float(job_counters["prompt_len"])
    S = P + float(job_counters["new_tokens"])
    epochs = float(job_counters["num_epochs"])
    return [(P, P / 2.0, 1.0, 0.0), (S, S / 2.0, 2.0 + 2.0 * epochs, epochs)]


def _moe_pairs(flops, model: dict, counters: dict, held_share: float):
    """(forward, backward) (token, choice) pairs computed here per
    iteration, summed over the expert layers."""
    _, moe = flops.layers_of(model)
    per_token = moe * float(model["num_experts_per_tok"]) * held_share
    n = float(counters["samples_per_iteration"])
    fwd = sum(t * f for t, _, f, _ in passes(counters)) * n * per_token
    bwd = sum(t * b for t, _, _, b in passes(counters)) * n * per_token
    return fwd, bwd


def _expert_bytes(flops, model: dict, n_calls: float) -> float:
    """Every held expert's weights read once a call."""
    _, moe = flops.layers_of(model)
    return (n_calls * moe * float(model["n_routed_experts"])
            * flops.expert_params(model) * BF16)


def work(flops, kernel: str, model: dict, counters: dict,
         held_share: float):
    """(operations, bytes) one iteration needs of ``kernel``; ``flops``
    is ``flops_dsv3`` (``ctx.lib``)."""
    h = float(model["hidden_size"])
    inter = float(model["moe_intermediate_size"])
    n = float(counters["samples_per_iteration"])
    per_pair = 2.0 * 3.0 * h * inter          # gate, up, down
    # a pair's rows: read hidden, write gate|up, read the product, write
    # hidden
    rows = h + 2.0 * inter + inter + h
    fwd_pairs, bwd_pairs = _moe_pairs(flops, model, counters, held_share)
    n_fwd = sum(f for _, _, f, _ in passes(counters))
    n_bwd = sum(b for _, _, _, b in passes(counters))
    if kernel == "moe_gmm":           # both products of every forward
        return (fwd_pairs * per_pair,
                fwd_pairs * rows * BF16 + _expert_bytes(flops, model, n_fwd))
    if kernel in ("moe_gmm_dlhs", "moe_tgmm"):   # the backward's two halves
        return (bwd_pairs * per_pair,
                bwd_pairs * rows * BF16 + _expert_bytes(flops, model, n_bwd))
    heads = float(model["num_attention_heads"])
    dk = float(model["qk_nope_head_dim"]) + float(model["qk_rope_head_dim"])
    dv = float(model["v_head_dim"])
    layers = float(model["num_hidden_layers"])
    # per query and key: q.k and p.v forward; backward recomputes q.k and
    # adds dp = do.v, then dq = ds.k (dq kernel) or dk = ds.q, dv = p.do
    per_qk = {"flash_fwd": 2.0 * (dk + dv),
              "flash_bwd_dq": 2.0 * (dk + dv + dk),
              "flash_bwd_dkv": 2.0 * (dk + dv + dk + dv)}
    if kernel not in per_qk:
        raise KeyError(f"no work function for kernel {kernel!r}")
    backward = kernel != "flash_fwd"
    ops = byts = 0.0
    for tokens, keys, f, b in passes(counters):
        calls = b if backward else f
        ops += calls * n * layers * heads * tokens * keys * per_qk[kernel]
        # q, k, v (and o, do, dq or dk, dv) once each
        width = {"flash_fwd": 2 * dk + 2 * dv,
                 "flash_bwd_dq": 3 * dk + 2 * dv,
                 "flash_bwd_dkv": 3 * dk + 3 * dv}[kernel]
        byts += calls * n * layers * heads * tokens * width * BF16
    return ops, byts


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)["device_kind"]
    if device_kind not in table:
        raise KeyError(f"no peak for device_kind {device_kind!r} in "
                       "peaks.json")
    return table[device_kind]


def kernel_seconds(trace: dict, kernel: str):
    """Self time of the instructions named ``<kernel>.<n>`` among the
    trace's top operations, or None where none is there."""
    name = re.compile(rf"^{re.escape(kernel)}(\.\d+)? custom-call")
    found = [s for label, s in trace["top_ops"] if name.match(label)]
    return sum(found) if found else None


def moe_counters(ctx):
    """Median over the traced iterations of the ``moe_*`` attributes of
    the ``stats.finalize`` spans, or None where the trace has none (a
    program without the expert layer's counters)."""
    hs = ctx.lib("host_spans")
    spans = hs.of_run(ctx)
    if spans is None:
        return None
    rows = [sp.stats for sp in spans.whole("stats.finalize")
            if "moe_pairs_here" in sp.stats]
    if not rows:
        return None
    return {k: hs.median([float(r[k]) for r in rows])
            for k in ("moe_pairs_here", "moe_pairs_total", "moe_load_max",
                      "moe_load_mean")}


def roofline_pct(kernel: str, trace: dict, counters: dict, ctx):
    """The reader behind every ``<kernel>_roofline_pct`` metric."""
    seconds = kernel_seconds(trace, kernel)
    update = ctx.lib("trace_reduce").program(trace, r"_epochs_fn")
    if not seconds or not update or not update["period_s"]:
        return None
    share = 0.0
    if kernel.startswith("moe_"):
        moe = moe_counters(ctx)
        if moe is None:
            return None
        share = moe["moe_pairs_here"] / moe["moe_pairs_total"]
    ops, byts = work(ctx.lib("flops_dsv3"), kernel, counters["model"],
                     counters, share)
    iterations = trace["window_s"] / update["period_s"]
    peak = peaks(counters["device_kind"])
    least = max(ops / peak["bf16_flops_per_s"], byts / peak["hbm_bytes_per_s"])
    return 100.0 * least * iterations / seconds
