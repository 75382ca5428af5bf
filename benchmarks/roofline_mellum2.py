"""Operations and bytes of the windowed flash kernels (``flash_fwd_window``,
``flash_dq_window``, ``flash_dkv_window``: ``ops/pallas/flash_attention.py``
under a static ``window``) per training iteration of a ``train_job``, the
share of their roofline they reached in a traced window, and the spans'
readings the ``mellum`` metrics share.

As ``roofline_keye_dsa.py``: the share is the least time the chip could
take for the work the ALGORITHM needs (the larger of operations over the
bf16 peak and bytes over the HBM peak, ``peaks.json``) over the self time
of the kernels' instructions in the device trace, found by name, every
execution read from the run's xplane.  The algorithm needs the pairs
INSIDE the window, counted from real lengths (the spans'
``window_keys_seen``: over one whole-sequence forward of the batch on the
``update`` span, over the prompts' real queries on ``rollout.dispatch``)
and the tiles' q, k, v, o (do, dq, dk, dv) bytes; what a kernel computes
beyond (the masked corners of the tiles on the window's two edges, padded
queries) is the implementation's to pay: time, not work.

Which passes run a kernel in one iteration: the rollout's prefill over
the prompts (the decode steps read the ring through ``dense_step``, no
flash kernel); two experience forwards over the whole sequences; per
epoch a forward, the same forward again under remat, and a backward.
Where the update's checkpoints keep ``attn_out`` (the ``update`` span's
``remat_kept``) the forward does not run again.
"""

from __future__ import annotations

import re

BF16 = 2.0
KERNELS = ("flash_fwd_window", "flash_dq_window", "flash_dkv_window")
#: operations a (query, key) pair costs a query head, in head_dim's
PER_PAIR = {"flash_fwd_window": 2.0 * 2.0, "flash_dq_window": 2.0 * 3.0,
            "flash_dkv_window": 2.0 * 4.0}


def span_medians(ctx, name: str, need: tuple):
    """{attribute: median over the traced iterations} of the ``name``
    spans that carry every attribute of ``need`` (numbers alone; plus
    ``remat_kept``, a tuple, where the spans have it), or None where the
    program's spans carry none."""
    hs = ctx.lib("host_spans")
    spans = hs.of_run(ctx)
    if spans is None:
        return None
    rows = [sp.stats for sp in spans.whole(name)
            if all(k in sp.stats for k in need)]
    if not rows:
        return None
    out = {}
    for k in rows[0]:
        try:
            out[k] = hs.median([float(r[k]) for r in rows if k in r])
        except (TypeError, ValueError):
            pass
    out["remat_kept"] = tuple(
        t for t in str(rows[0].get("remat_kept", "")).split("+") if t)
    return out


def update_forwards(kernel: str, upd: dict) -> float:
    """Forward executions a minibatch of the update: the forward and,
    unless the checkpoints keep what it gives, remat's."""
    kept = kernel == "flash_fwd_window" and "attn_out" in upd.get(
        "remat_kept", ())
    return 1.0 if kept else 2.0


def work(kernel: str, model: dict, counters: dict, roll: dict, upd: dict):
    """(operations, bytes) one iteration needs of ``kernel``."""
    heads, kv = (float(model["num_attention_heads"]),
                 float(model["num_key_value_heads"]))
    d = float(model["head_dim"])
    layers = float(upd["window_layers"])
    epochs = float(counters["num_epochs"])
    # q and o (dq, do) a head; k and v (dk, dv) a key/value head
    width = {"flash_fwd_window": 2.0 * heads + 2.0 * kv,
             "flash_dq_window": 3.0 * heads + 2.0 * kv,
             "flash_dkv_window": 2.0 * heads + 2.0 * kv + 2.0 * heads}[kernel]
    if kernel == "flash_fwd_window":
        whole = 2.0 + update_forwards(kernel, upd) * epochs
        pairs = roll["window_keys_seen"] + whole * upd["window_keys_seen"]
        tokens = roll["seq_tokens"] + whole * upd["seq_tokens"]
    else:
        pairs = epochs * upd["window_keys_seen"]
        tokens = epochs * upd["seq_tokens"]
    return (layers * heads * PER_PAIR[kernel] * d * pairs,
            layers * tokens * width * d * BF16)


def calls_per_iteration(kernel: str, counters: dict, minibatch: int,
                        upd: dict) -> float:
    """Executions of ``kernel`` an iteration: one a window layer and
    pass; the update's passes once a minibatch."""
    per_epoch = float(counters["samples_per_iteration"]) / float(minibatch)
    epochs = float(counters["num_epochs"])
    layers = float(upd["window_layers"])
    if kernel == "flash_fwd_window":
        return layers * (1.0 + 2.0 + update_forwards(kernel, upd)
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


def roofline_pct(kernels, trace: dict, counters: dict, ctx, found=None):
    """The reader behind ``window_flash_roofline_pct.train``: the share
    over ``kernels`` together.  Nothing to read where the configuration
    has no ``sliding_window``, the spans carry no counts, or the
    kernels' executions are fewer than the job's shapes say.  ``found``:
    :func:`kernel_executions`' reading where the caller has one."""
    model = counters["model"]
    update = ctx.lib("trace_reduce").program(trace, r"_epochs_fn")
    if "sliding_window" not in model or not update \
            or not update["period_s"]:
        return None
    need = ("window_layers", "window_keys_seen", "seq_tokens")
    roll = span_medians(ctx, "rollout.dispatch", need)
    upd = span_medians(ctx, "update", need)
    found = kernel_executions(ctx) if found is None else found
    if roll is None or upd is None or found is None:
        return None
    iterations = trace["window_s"] / update["period_s"]
    peak = ctx.lib("roofline_dsv3").peaks(counters["device_kind"])
    mb = ctx.lib("roofline_olmo_hybrid").minibatch_of(ctx.traffic)
    least = seconds = 0.0
    for kernel in kernels:
        runs, self_s = found[kernel]
        expected = calls_per_iteration(kernel, counters, mb, upd)
        # a whole iteration's executions may straddle the window's ends
        if runs < expected * (iterations - 1.0) or not self_s:
            return None
        ops, byts = work(kernel, model, counters, roll, upd)
        least += max(ops / peak["bf16_flops_per_s"],
                     byts / peak["hbm_bytes_per_s"])
        seconds += self_s
    return 100.0 * least * iterations / seconds
