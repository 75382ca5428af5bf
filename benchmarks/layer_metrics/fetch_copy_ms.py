"""The exposed copy of a training iteration, in ms (``program_span``).

Median, over the ``train.iteration`` spans that lie wholly inside the
traced window, of the ``fetch.copy`` span inside them: from the moment
the rollout's results are ready on the device until they are numpy
arrays on the host (what is left of the device-to-host copies, started
before the wait, and the conversion).  Nothing is enqueued before it
returns, so this is the floor of the device's idle time an iteration;
``fetch.wait``, the rest of ``rollout.fetch``, is time the device works.

A program without the span (the parent of the PR that split the fetch)
gives nothing to read: None, and the metric is left out of the line.
"""

ITERATION = "train.iteration"
COPY = "fetch.copy"


def read(trace, counters, ctx):
    hs = ctx.lib("host_spans")
    spans = hs.of_run(ctx)
    if spans is None:
        return None
    if not any(sp.name == COPY for _, thread in spans.threads
               for sp in thread):
        return None
    copies = spans.per_parent(ITERATION, (COPY,))
    return 1e3 * hs.median(copies) if copies else None
