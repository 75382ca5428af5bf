"""Model FLOP/s utilisation of the traced iterations of an ``sdar_moe``
share (a block-diffusion model), in %: as ``mfu_pct.dsa``, with the
operations from ``flops_sdar.py`` (products over the entries the
programs go over: the rollout's ``denoise_forwards`` of ``block_length``
rows a sequence, a trace forward's clean and noisy streams; the routed
experts by the pairs really computed here; attention by the (query, key)
pairs the block rule and the two-part mask leave; the head over the rows
it is computed on), over the update program's period, over chips x the
bf16 peak of ``peaks.json``.  The whole traced iteration's share: under
100% by construction (nothing is counted that the masks leave out; what
the program computes beside it, padding and masked pairs, reads it
lower).  A configuration without ``block_length`` or a program without
the counters gives nothing to read."""

UPDATE = r"_epochs_fn"


def read(trace, counters, ctx):
    model = counters["model"]
    if "block_length" not in model:
        return None
    roof = ctx.lib("roofline_dsv3")
    p = ctx.lib("trace_reduce").program(trace, UPDATE)
    moe = roof.moe_counters(ctx)
    counts = ctx.lib("flops_sdar").span_counts(ctx)
    if not p or not p["period_s"] or moe is None or counts is None:
        return None
    flops = ctx.lib("flops_sdar").ppo_iteration_flops(
        model, samples=counters["samples_per_iteration"],
        prompt_len=counters["prompt_len"], new_tokens=counters["new_tokens"],
        num_epochs=counters["num_epochs"],
        held_share=moe["moe_pairs_here"] / moe["moe_pairs_total"],
        rollout=counts["rollout"], forward=counts["forward"])
    peak = roof.peaks(counters["device_kind"])["bf16_flops_per_s"] \
        * counters["chips"]
    return 100.0 * flops / p["period_s"] / peak
