"""Share of its roofline that the flash forward kernel (keys of 192, values of 128: every forward over prompts and over whole sequences) reached in the traced
iterations, in %: the least time the chip could take for the work the
algorithm needs (``roofline_dsv3.work``: the larger of operations over
the bf16 peak and bytes over the HBM peak of ``peaks.json``) over the
kernel's self time (``%flash_fwd.<n>`` among the trace's top operations)."""


def read(trace, counters, ctx):
    return ctx.lib("roofline_dsv3").roofline_pct("flash_fwd", trace, counters,
                                                 ctx)
