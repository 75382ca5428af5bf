"""Share of its roofline that the selection kernel (``dsa_select``: a
tile of 128 queries scores up to 8192 keys with the indexer's 16 heads
on the MXU and finds its top 2048 exactly by a search over the scores'
bits; every forward over whole sequences, remat's too, and the prefill)
reached in the traced iterations, in %: the least time the chip could
take for the indexer's scores over the CAUSAL pairs
(``roofline_keye_dsa.work``: the larger of operations over the bf16 peak
and bytes over the HBM peak of ``peaks.json``; the selection a byte a
causal pair) over the kernel's self time, every execution read from the
run's xplane by the instruction's name.  The search's 45 passes of
compares and counts run on the vector unit and are no work by this
count: the share says how far the kernel is from a selection that costs
nothing beyond its scores.  Nothing to read for a configuration without
``sa_config`` or a program without the kernel or the counters."""


def read(trace, counters, ctx):
    return ctx.lib("roofline_keye_dsa").roofline_pct(
        ("dsa_select",), trace, counters, ctx)
