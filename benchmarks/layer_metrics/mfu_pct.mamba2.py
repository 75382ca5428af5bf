"""Model FLOP/s utilisation of the traced iterations of a ``nemotron_h``
share, in %: as ``mfu_pct.kda``, with the operations from
``flops_nemotron_h.py`` (projections, the convolution and the
recurrence's own operations of the Mamba-2 layers at the heads held,
attention on its one layer, latent projections, shared expert, router,
the routed experts by the pairs really computed here: ``moe_pairs_here /
moe_pairs_total`` from the ``stats.finalize`` spans, the head over the
rows held).  The share of the heads and experts is the program's own:
the ``update`` spans' ``heads_held`` ... ``experts_held``
(``trainers/base.py::share_counters``).  A program without those
counters, or a configuration that is no ``nemotron_h`` one, gives
nothing to read."""

UPDATE = r"_epochs_fn"


def read(trace, counters, ctx):
    roof = ctx.lib("roofline_dsv3")
    flops_lib = ctx.lib("flops_nemotron_h")
    p = ctx.lib("trace_reduce").program(trace, UPDATE)
    moe = roof.moe_counters(ctx)
    spans = ctx.lib("host_spans").of_run(ctx)
    if not p or not p["period_s"] or moe is None or spans is None \
            or "hybrid_override_pattern" not in counters["model"]:
        return None
    held = [sp.stats for sp in spans.whole("update")
            if all(k in sp.stats for k in flops_lib.KEYS)]
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
