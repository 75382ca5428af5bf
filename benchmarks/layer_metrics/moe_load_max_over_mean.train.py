"""Largest over mean load of a held expert (``program_span``): median
over the traced iterations of ``moe_load_max / moe_load_mean`` from the
``stats.finalize`` spans' attributes (pairs routed to a held expert,
largest and mean over layers and experts, mean over the update's
minibatches).  1 is an even routing; the excess is what a grouped
product pays in part-filled row tiles and what an expert-parallel
deployment pays in waiting for its fullest chip."""


def read(trace, counters, ctx):
    moe = ctx.lib("roofline_dsv3").moe_counters(ctx)
    if moe is None or not moe["moe_load_mean"] > 0:
        return None
    return moe["moe_load_max"] / moe["moe_load_mean"]
