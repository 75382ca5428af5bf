"""Operations and bytes of the chunked gated delta rule that an
``olmo_hybrid`` model runs per training iteration of a ``train_job``,
at the TRUE head sizes, and the share of the roofline that the chunk
kernels reached in a traced window.

The share is the least time the chip could take for the work the
ALGORITHM needs in the window (the larger of operations over the bf16
peak and bytes over the HBM peak, ``peaks.json``) over the self time of
the kernels' instructions in the device trace, found by name
(``named_pallas_call``: ``%kda_chunk_fwd.<n>``, ``%kda_chunk_bwd.<n>``:
the kernels Kimi-Linear's layers run, here around zero-padded heads).
What the kernels compute beyond the recurrence's own operations (a
chunk's pair products, its triangular inverse), the zero channels that
pad 96 x 192 to 128 x 256, one decay a head broadcast to a decay a
channel, and the float32 states kept at the chunk boundaries are the
implementation's to pay: time, not work.

Per head and token, forward: the recurrence's own ``7 dk dv``
operations (``flops_olmo_hybrid``); it reads q and k (dk each) and v
(dv) in bfloat16 and one decay and one step size in float32, and writes
o (dv) in float32, as ``ops.kda.kda_chunked`` gives it.  Backward: twice
the forward's operations; it reads the forward's inputs and ``do``
(float32) and writes dq, dk, dv (bfloat16), dg and dbeta (float32).

Which passes run the rule in one iteration (``passes``): the rollout's
prefill over the prompts (decode steps take ``kda_step``, no kernel);
two experience forwards over the whole sequences; per epoch a forward,
the same forward again under remat, and a backward.

**The instructions are read from the xplane of the traced run, whatever
their rank**, not from the reduction's 40 largest operations: the
prefill's calls are too small to be among those, and a share over a part
of the time reads high (PERF.md section 7, "From PR 29").  The reader
still returns nothing where it finds fewer executions of the kernels
than the job's shapes say an iteration makes (a program without the
kernels, a trace without a device plane, a window cut short).
"""

from __future__ import annotations

import re

BF16, F32 = 2.0, 4.0
KERNELS = {"forward": "kda_chunk_fwd", "backward": "kda_chunk_bwd"}


def gdn_layers(model: dict) -> float:
    return float(sum(t == "linear_attention" for t in
                     model["layer_types"][:int(model["num_hidden_layers"])]))


def passes(counters: dict):
    """[(tokens per sample, forward passes, backward passes)] per
    iteration."""
    P = float(counters["prompt_len"])
    S = P + float(counters["new_tokens"])
    epochs = float(counters["num_epochs"])
    return [(P, 1.0, 0.0), (S, 2.0 + 2.0 * epochs, epochs)]


def work(direction: str, model: dict, counters: dict):
    """(operations, bytes) one iteration needs of the chunked rule in
    ``direction`` ("forward" or "backward"), all GDN layers."""
    heads = float(model["linear_num_key_heads"])
    dk = float(model["linear_key_head_dim"])
    dv = float(model["linear_value_head_dim"])
    own = 7.0 * dk * dv
    inputs = (2.0 * dk + dv) * BF16 + 2.0 * F32       # q, k, v; g, beta
    if direction == "forward":
        ops_tok, bytes_tok = own, inputs + dv * F32
    elif direction == "backward":
        ops_tok = 2.0 * own
        bytes_tok = inputs + dv * F32 + (2.0 * dk + dv) * BF16 + 2.0 * F32
    else:
        raise KeyError(f"no work function for direction {direction!r}")
    col = 1 if direction == "forward" else 2
    tokens = sum(p[0] * p[col] for p in passes(counters)) \
        * float(counters["samples_per_iteration"])
    n = tokens * heads * gdn_layers(model)
    return n * ops_tok, n * bytes_tok


def calls_per_iteration(model: dict, counters: dict, minibatch: int) -> dict:
    """{direction: executions of its kernel an iteration}: one a GDN
    layer and pass; the update's passes once a minibatch."""
    per_epoch = float(counters["samples_per_iteration"]) / float(minibatch)
    epochs = float(counters["num_epochs"])
    layers = gdn_layers(model)
    return {"forward": layers * (1.0 + 2.0 + 2.0 * epochs * per_epoch),
            "backward": layers * epochs * per_epoch}


def minibatch_of(job: dict) -> int:
    for key in job["launch"]:
        if key.startswith("minibatch_size="):
            return int(key.split("=", 1)[1])
    raise KeyError("the job's launch keys give no minibatch_size")


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
    out = {name: [0, 0.0] for name in KERNELS.values()}
    pattern = re.compile(r"^(%s)(\.\d+)? custom-call" % "|".join(out))
    for (label, _, _, _), self_ns in zip(ops, tr.self_times(ops)):
        m = pattern.match(label)
        if m:
            out[m.group(1)][0] += 1
            out[m.group(1)][1] += self_ns / 1e9
    return {k: tuple(v) for k, v in out.items()}


def roofline_pct(trace: dict, counters: dict, ctx):
    """The reader behind ``gdn_chunk_roofline_pct.train``."""
    model = counters["model"]
    update = ctx.lib("trace_reduce").program(trace, r"_epochs_fn")
    if "linear_key_head_dim" not in model or not update \
            or not update["period_s"]:
        return None
    found = kernel_executions(ctx)
    if found is None:
        return None
    iterations = trace["window_s"] / update["period_s"]
    expected = calls_per_iteration(model, counters,
                                   minibatch_of(ctx.traffic))
    peak = ctx.lib("roofline_dsv3").peaks(counters["device_kind"])
    least = seconds = 0.0
    for direction, kernel in KERNELS.items():
        runs, self_s = found[kernel]
        # a whole iteration's executions may straddle the window's ends
        if runs < expected[direction] * (iterations - 1.0) or not self_s:
            return None
        ops, byts = work(direction, model, counters)
        least += max(ops / peak["bf16_flops_per_s"],
                     byts / peak["hbm_bytes_per_s"])
        seconds += self_s
    return 100.0 * least * iterations / seconds
