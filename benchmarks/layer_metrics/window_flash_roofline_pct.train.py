"""Share of their roofline that the three windowed flash kernels
(``flash_fwd_window``, ``flash_dq_window``, ``flash_dkv_window``: every
forward and backward of a sliding-window layer over prompts and whole
sequences) reached together in the traced iterations, in %: the least
time the chip could take for the attention over the keys INSIDE the
window (``roofline_mellum2.work``: the larger of operations over the
bf16 peak and bytes over the HBM peak of ``peaks.json``, the pairs from
the spans' ``window_keys_seen``, the bytes the tiles' q, k, v, o and do)
over the kernels' summed self time, every execution read from the run's
xplane by the instruction's name.  The masked corners of the tiles on
the window's two edges are computed and are no work: with tiles of 512
under a window of 1024 a late query tile multiplies 1536 keys for its
1024.  The full layers' kernels keep their names (``flash_fwd``, ...)
and are not read here.  Nothing to read for a configuration without
``sliding_window`` or a program without the kernels or the counters."""


def read(trace, counters, ctx):
    roof = ctx.lib("roofline_mellum2")
    return roof.roofline_pct(roof.KERNELS, trace, counters, ctx)
