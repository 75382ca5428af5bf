"""Model FLOP/s utilisation of the traced iterations of a ``keye_dsa``
share, in %: as ``mfu_pct.moe``, with the operations from
``flops_keye_dsa.py`` (products, the routed experts by the pairs really
computed here, attention over the SELECTED keys, the indexer's scores
over the causal pairs; the pairs from the ``update`` spans'
``sa_keys_selected`` / ``sa_keys_valid``), over the update program's
period, over chips x the bf16 peak of ``peaks.json``.  An implementation
that computes every causal pair under a mask reads low here, one that
skips reads higher, under 100% either way.  A configuration without
``sa_config`` or a program without the counters gives nothing to read."""

UPDATE = r"_epochs_fn"


def read(trace, counters, ctx):
    model = counters["model"]
    if "sa_config" not in model:
        return None
    roof = ctx.lib("roofline_dsv3")
    p = ctx.lib("trace_reduce").program(trace, UPDATE)
    moe = roof.moe_counters(ctx)
    counts = ctx.lib("roofline_keye_dsa").span_counts(ctx)
    if not p or not p["period_s"] or moe is None or counts is None:
        return None
    flops = ctx.lib("flops_keye_dsa").ppo_iteration_flops(
        model, samples=counters["samples_per_iteration"],
        prompt_len=counters["prompt_len"], new_tokens=counters["new_tokens"],
        num_epochs=counters["num_epochs"],
        held_share=moe["moe_pairs_here"] / moe["moe_pairs_total"],
        keys_valid=counts["whole"][0], keys_selected=counts["whole"][1])
    peak = roof.peaks(counters["device_kind"])["bf16_flops_per_s"] \
        * counters["chips"]
    return 100.0 * flops / p["period_s"] / peak
