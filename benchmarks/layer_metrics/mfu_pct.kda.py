"""Model FLOP/s utilisation of the traced iterations of a
``kimi_linear`` share, in %: as ``mfu_pct.moe``, with the operations
from ``flops_kimi_linear.py`` (projections, the convolutions and the
recurrence's own operations of the KDA layers, latent attention on the
latent layers, the routed experts by the pairs really computed here:
``moe_pairs_here / moe_pairs_total`` from the ``stats.finalize`` spans).
A program without those counters gives nothing to read."""

UPDATE = r"_epochs_fn"


def read(trace, counters, ctx):
    roof = ctx.lib("roofline_dsv3")
    p = ctx.lib("trace_reduce").program(trace, UPDATE)
    moe = roof.moe_counters(ctx)
    if not p or not p["period_s"] or moe is None \
            or "linear_attn_config" not in counters["model"]:
        return None
    flops = ctx.lib("flops_kimi_linear").ppo_iteration_flops(
        counters["model"], samples=counters["samples_per_iteration"],
        prompt_len=counters["prompt_len"], new_tokens=counters["new_tokens"],
        num_epochs=counters["num_epochs"],
        held_share=moe["moe_pairs_here"] / moe["moe_pairs_total"])
    peak = roof.peaks(counters["device_kind"])["bf16_flops_per_s"] \
        * counters["chips"]
    return 100.0 * flops / p["period_s"] / peak
