"""Model FLOP/s utilisation of the traced iterations of a ``mellum``
share, in %: the whole iteration's useful operations by
``flops_mellum2.py`` (products over the real tokens, the routed experts
by the pairs really computed here: ``moe_pairs_here / moe_pairs_total``
from the ``stats.finalize`` spans, attention over the (query, key) pairs
the two kinds of layer see: ``window_keys_seen`` on the sliding layers,
``causal_keys`` on the full ones, from the ``update`` spans, which also
say how many layers of either kind and how many experts are held) over
the update program's period, over chips x the bf16 peak of
``peaks.json``.  Masked keys are not counted, so it reads under 100 by
construction.  A program without those counters, or a configuration
without ``sliding_window``, gives nothing to read."""

UPDATE = r"_epochs_fn"


def read(trace, counters, ctx):
    model = counters["model"]
    if "sliding_window" not in model:
        return None
    roof = ctx.lib("roofline_dsv3")
    flops_lib = ctx.lib("flops_mellum2")
    p = ctx.lib("trace_reduce").program(trace, UPDATE)
    moe = roof.moe_counters(ctx)
    upd = ctx.lib("roofline_mellum2").span_medians(ctx, "update",
                                                   flops_lib.KEYS)
    if not p or not p["period_s"] or moe is None or upd is None:
        return None
    flops = flops_lib.ppo_iteration_flops(
        model, samples=counters["samples_per_iteration"],
        new_tokens=counters["new_tokens"], num_epochs=counters["num_epochs"],
        held_share=moe["moe_pairs_here"] / moe["moe_pairs_total"], counts=upd)
    peak = roof.peaks(counters["device_kind"])["bf16_flops_per_s"] \
        * counters["chips"]
    return 100.0 * flops / p["period_s"] / peak
