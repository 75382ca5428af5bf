"""The interpreter's garbage collector per training iteration, in ms
(``program_span``).

MEAN, over the ``train.iteration`` spans that lie wholly inside the
traced window, of the span's ``gc_us``: the pauses of every collection
the trainer's thread ran inside the iteration, all generations, counted
by the program's own ``gc.callbacks`` hook (``orion_tpu/obs/gcwatch.py``).
A mean and not a median: young collections are tens an iteration and
cheap, the expensive one is rare by nature, and a median would never
show it.  A generation-2 collection is also a span ``host.gc`` in the
same trace, so the gap it makes in the device's timeline is labelled.

A program whose iterations carry no ``gc_us`` gives nothing to read:
None, and the metric is left out of the line.
"""

ITERATION = "train.iteration"
GC = "gc_us"


def read(trace, counters, ctx):
    hs = ctx.lib("host_spans")
    spans = hs.of_run(ctx)
    if spans is None:
        return None
    pauses = [float(it.stats[GC]) for it in spans.whole(ITERATION)
              if GC in it.stats]
    return 1e-3 * sum(pauses) / len(pauses) if pauses else None
