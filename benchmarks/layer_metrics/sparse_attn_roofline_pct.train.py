"""Share of its roofline that the selection-taking forward kernel
(``sparse_fwd``: the flash forward with a query's selected keys as an
operand; every forward over whole sequences and the prefill) reached in
the traced iterations, in %: the least time the chip could take for the
attention over the SELECTED keys (``roofline_keye_dsa.work``: the larger
of operations over the bf16 peak and bytes over the HBM peak of
``peaks.json``, the pairs from the spans' ``sa_keys_selected``) over the
kernel's self time, every execution read from the run's xplane by the
instruction's name.  The kernel goes over every causal block and masks
inside it, so with 44-52% of the causal pairs kept it cannot pass about
half of what the dense kernel reaches: a later one that skips blocks
none of whose keys is kept reads higher on weights that cluster them.
Nothing to read for a configuration without ``sa_config`` or a program
without the kernel or the counters."""


def read(trace, counters, ctx):
    return ctx.lib("roofline_keye_dsa").roofline_pct(
        ("sparse_fwd",), trace, counters, ctx)
