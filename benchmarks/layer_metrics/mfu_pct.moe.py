"""Model FLOP/s utilisation of the traced iterations of a
``deepseek_v3`` share, in %: as ``mfu_pct.train``, with the operations
from ``flops_dsv3.py`` and the routed experts counted by the pairs
really computed here (``moe_pairs_here / moe_pairs_total`` from the
``stats.finalize`` spans, the update's own counters).  A program without
those counters gives nothing to read."""

UPDATE = r"_epochs_fn"


def read(trace, counters, ctx):
    roof = ctx.lib("roofline_dsv3")
    p = ctx.lib("trace_reduce").program(trace, UPDATE)
    moe = roof.moe_counters(ctx)
    if not p or not p["period_s"] or moe is None:
        return None
    flops = ctx.lib("flops_dsv3").ppo_iteration_flops(
        counters["model"], samples=counters["samples_per_iteration"],
        prompt_len=counters["prompt_len"], new_tokens=counters["new_tokens"],
        num_epochs=counters["num_epochs"],
        held_share=moe["moe_pairs_here"] / moe["moe_pairs_total"])
    peak = roof.peaks(counters["device_kind"])["bf16_flops_per_s"] \
        * counters["chips"]
    return 100.0 * flops / p["period_s"] / peak
