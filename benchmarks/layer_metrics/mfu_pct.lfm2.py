"""Model FLOP/s utilisation of the traced iterations of an ``lfm2_moe``
share, in %: as ``mfu_pct.mamba2``, with the operations from
``flops_lfm2.py`` (the projections of both mixers, the convolution's own
taps and gates, attention over causal pairs at its layers, the dense
MLPs, the router's published outputs, the routed experts by the pairs
really computed here: ``moe_pairs_here / moe_pairs_total`` from the
``stats.finalize`` spans, the tied head over the rows held).  The
experts held are the program's own: the ``update`` spans'
``experts_held`` (``models/transformer.py::ShortConv.forward_attrs``,
beside ``conv_layers`` and ``conv_taps``).  A program without those
counters, or a configuration that is no ``lfm2_moe`` one, gives nothing
to read."""

UPDATE = r"_epochs_fn"


def read(trace, counters, ctx):
    roof = ctx.lib("roofline_dsv3")
    flops_lib = ctx.lib("flops_lfm2")
    p = ctx.lib("trace_reduce").program(trace, UPDATE)
    moe = roof.moe_counters(ctx)
    spans = ctx.lib("host_spans").of_run(ctx)
    if not p or not p["period_s"] or moe is None or spans is None \
            or "conv_L_cache" not in counters["model"]:
        return None
    held = [sp.stats for sp in spans.whole("update")
            if "conv_layers" in sp.stats
            and all(k in sp.stats for k in flops_lib.KEYS)]
    if not held:
        return None
    flops = flops_lib.ppo_iteration_flops(
        counters["model"], samples=counters["samples_per_iteration"],
        prompt_len=counters["prompt_len"], new_tokens=counters["new_tokens"],
        num_epochs=counters["num_epochs"],
        held_share=moe["moe_pairs_here"] / moe["moe_pairs_total"],
        held={k: float(held[0][k]) for k in flops_lib.KEYS})
    peak = roof.peaks(counters["device_kind"])["bf16_flops_per_s"] \
        * counters["chips"]
    return 100.0 * flops / p["period_s"] / peak
