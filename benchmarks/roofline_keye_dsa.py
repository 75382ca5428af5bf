"""Operations and bytes of the kernels that learned sparse attention
adds (``sparse_fwd``, ``sparse_bwd_dq``, ``sparse_bwd_dkv``: the flash
kernels with a selection as an operand; ``dsa_select``: the indexer's
scores and the exact top-k of a tile of queries), per training iteration
of a ``train_job``, and the share of its roofline a kernel reached in a
traced window.

The share is the least time the chip could take for the work the
ALGORITHM needs (the larger of operations over the bf16 peak and bytes
over the HBM peak, ``peaks.json``) over the self time of the kernel's
instructions in the device trace, found by name (``named_pallas_call``:
``%<kernel>.<n>``), every execution read from the run's xplane whatever
its rank (PERF.md section 7, "From PR 29").  The algorithm needs the
SELECTED pairs of the attention and the CAUSAL pairs of the indexer,
counted from real lengths (the spans' ``sa_keys_selected`` /
``sa_keys_valid``: over one whole-sequence forward of the batch on the
``update`` span, over the prefill's real queries on ``rollout.dispatch``);
what a kernel computes beyond (every causal block under a mask, padded
queries, causal blocks above the diagonal inside a tile) is the
implementation's to pay: time, not work.

Which passes run a kernel in one iteration: the rollout's prefill over
the prompts (the decode steps gather, no kernel); two experience
forwards over the whole sequences; per epoch a forward, the same
forward again under remat, and a backward.  Where the update's
checkpoints keep ``attn_out`` (the ``update`` span's ``remat_kept``: the
trainer's choice from the device's free memory), the backward reads the
attention's output as it lies and ``sparse_fwd`` does not run again;
``dsa_select`` always does (no tag keeps the selection).
"""

from __future__ import annotations

import re

BF16, F32 = 2.0, 4.0
KERNELS = ("sparse_fwd", "sparse_bwd_dq", "sparse_bwd_dkv", "dsa_select")


def span_counts(ctx):
    """{"prefill": (valid, selected), "whole": (valid, selected), topk,
    index_cache_bytes, remat_kept}: medians over the traced iterations
    of the spans' attributes (``remat_kept``: the tags the update's
    checkpoints keep, a tuple), or None where the program's spans carry
    none."""
    hs = ctx.lib("host_spans")
    spans = hs.of_run(ctx)
    if spans is None:
        return None

    def rows_of(name):
        return [sp.stats for sp in spans.whole(name)
                if "sa_keys_valid" in sp.stats]

    def med(rows):
        return {k: hs.median([float(r[k]) for r in rows])
                for k in rows[0] if k.startswith(("sa_", "index_"))}

    roll, upd = rows_of("rollout.dispatch"), rows_of("update")
    if not roll or not upd:
        return None
    kept = tuple(t for t in str(upd[0].get("remat_kept", "")).split("+") if t)
    roll, upd = med(roll), med(upd)
    return {"prefill": (roll["sa_keys_valid"], roll["sa_keys_selected"]),
            "whole": (upd["sa_keys_valid"], upd["sa_keys_selected"]),
            "topk": upd["sa_topk"], "remat_kept": kept,
            "index_cache_bytes": roll.get("index_cache_bytes", 0.0)}


def update_forwards(kernel: str, counts: dict) -> float:
    """Forward executions of ``kernel`` a minibatch of the update: the
    forward and, unless the checkpoints keep what it gives, remat's."""
    kept = kernel == "sparse_fwd" and "attn_out" in counts.get(
        "remat_kept", ())
    return 1.0 if kept else 2.0


def work(kernel: str, model: dict, counters: dict, counts: dict):
    """(operations, bytes) one iteration needs of ``kernel``."""
    sa = model["sa_config"]
    heads, kv = (float(model["num_attention_heads"]),
                 float(model["num_key_value_heads"]))
    d = float(model["head_dim"])
    layers = float(model["num_hidden_layers"])
    n = float(counters["samples_per_iteration"])
    epochs = float(counters["num_epochs"])
    P = float(counters["prompt_len"])
    S = P + float(counters["new_tokens"])
    (pv, ps), (wv, ws) = counts["prefill"], counts["whole"]
    fwd_whole = 2.0 + update_forwards(kernel, counts) * epochs
    bwd_whole = epochs
    if kernel == "dsa_select":
        hi, di = float(sa["indexer_num_heads"]), float(sa["indexer_head_dim"])
        ops = layers * 2.0 * hi * di * (pv + fwd_whole * wv)
        # qI and w a query, kI a key (read once a sequence), the
        # selection written: a byte a causal pair
        byts = layers * ((n * P + fwd_whole * n * S)
                         * (hi * di * BF16 + hi * F32 + di * BF16)
                         + pv + fwd_whole * wv)
        return ops, byts
    per_pair = {"sparse_fwd": 2.0 * 2.0 * d,
                "sparse_bwd_dq": 2.0 * 3.0 * d,
                "sparse_bwd_dkv": 2.0 * 4.0 * d}
    if kernel not in per_pair:
        raise KeyError(f"no work function for kernel {kernel!r}")
    # q and o (dq, do) a head; k and v (dk, dv) a key/value head
    width = {"sparse_fwd": 2.0 * heads + 2.0 * kv,
             "sparse_bwd_dq": 3.0 * heads + 2.0 * kv,
             "sparse_bwd_dkv": 2.0 * heads + 2.0 * kv + 2.0 * heads}[kernel]
    if kernel == "sparse_fwd":
        pairs, tokens, causal = ps + fwd_whole * ws, \
            n * P + fwd_whole * n * S, pv + fwd_whole * wv
    else:
        pairs, tokens, causal = bwd_whole * ws, bwd_whole * n * S, \
            bwd_whole * wv
    return (layers * heads * per_pair[kernel] * pairs,
            layers * (tokens * width * d * BF16 + causal))


def calls_per_iteration(kernel: str, model: dict, counters: dict,
                        minibatch: int, counts: dict) -> float:
    """Executions of ``kernel`` an iteration: one a layer and pass; the
    update's passes once a minibatch."""
    per_epoch = float(counters["samples_per_iteration"]) / float(minibatch)
    epochs = float(counters["num_epochs"])
    layers = float(model["num_hidden_layers"])
    if kernel in ("sparse_fwd", "dsa_select"):
        return layers * (1.0 + 2.0 + update_forwards(kernel, counts)
                         * epochs * per_epoch)
    return layers * epochs * per_epoch


def kernel_executions(ctx):
    """{kernel name: (executions, self seconds)} inside the traced
    window, over every instruction named ``<kernel>.<n>`` on the first
    device's operation line, or None where the run left no xplane or
    the xplane has no device plane."""
    tr = ctx.lib("trace_reduce")
    path = ctx.lib("harness").Tracer(True, ctx.out_dir + "/trace"
                                     ).xplane_path()
    if path is None:
        return None
    planes = tr.load(path)
    streams = tr.device_streams(planes)
    if not streams:
        return None
    lo, hi = tr.find_window(planes)
    ops = sorted((e for e in streams[0][1] if lo <= e[1] < hi),
                 key=lambda e: e[1])
    out = {name: [0, 0.0] for name in KERNELS}
    pattern = re.compile(r"^(%s)(\.\d+)? custom-call" % "|".join(KERNELS))
    for (label, _, _, _), self_ns in zip(ops, tr.self_times(ops)):
        m = pattern.match(label)
        if m:
            out[m.group(1)][0] += 1
            out[m.group(1)][1] += self_ns / 1e9
    return {k: tuple(v) for k, v in out.items()}


def roofline_pct(kernels, trace: dict, counters: dict, ctx):
    """The reader behind ``sparse_attn_roofline_pct.train`` and its
    like: the share over ``kernels`` together.  Nothing to read where
    the configuration has no ``sa_config``, the spans carry no counts,
    or the kernels' executions are fewer than the job's shapes say."""
    model = counters["model"]
    update = ctx.lib("trace_reduce").program(trace, r"_epochs_fn")
    if "sa_config" not in model or not update or not update["period_s"]:
        return None
    counts, found = span_counts(ctx), kernel_executions(ctx)
    if counts is None or found is None:
        return None
    iterations = trace["window_s"] / update["period_s"]
    peak = ctx.lib("roofline_dsv3").peaks(counters["device_kind"])
    mb = ctx.lib("roofline_olmo_hybrid").minibatch_of(ctx.traffic)
    least = seconds = 0.0
    for kernel in kernels:
        runs, self_s = found[kernel]
        expected = calls_per_iteration(kernel, model, counters, mb, counts)
        # a whole iteration's executions may straddle the window's ends
        if runs < expected * (iterations - 1.0) or not self_s:
            return None
        ops, byts = work(kernel, model, counters, counts)
        least += max(ops / peak["bf16_flops_per_s"],
                     byts / peak["hbm_bytes_per_s"])
        seconds += self_s
    return 100.0 * least * iterations / seconds
