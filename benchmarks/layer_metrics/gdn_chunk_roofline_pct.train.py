"""Share of its roofline that the chunked gated delta rule (the
``kda_chunk_fwd`` / ``kda_chunk_bwd`` kernels around heads of 96 x 192
padded to 128 x 256: the update's forward, remat's and backward, both
experience forwards, the prefill) reached in the traced iterations, in %:
the least time the chip could take for the work the algorithm needs at
the TRUE head sizes (``roofline_olmo_hybrid.work``: the larger of
operations over the bf16 peak and bytes over the HBM peak of
``peaks.json``) over the kernels' self time, every execution read from
the run's xplane by the instruction's name.  Padding, the chunked form's
extra products and the states kept for the backward show here as share
lost.  Nothing to read where the kernels' executions are fewer than the
job's shapes say (``roofline_olmo_hybrid.roofline_pct``)."""


def read(trace, counters, ctx):
    return ctx.lib("roofline_olmo_hybrid").roofline_pct(trace, counters, ctx)
