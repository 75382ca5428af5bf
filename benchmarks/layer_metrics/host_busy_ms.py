"""The host's own work per training iteration, in ms (``program_span``).

Median, over the ``train.iteration`` spans that lie wholly inside the
traced window, of the span's duration less the time its *wait* spans
cover (the thread blocks on the device there: ``rollout.fetch``, the one
batched ``jax.device_get`` of an iteration), plus the ``data.next_batch``
span that precedes it where the trace has it (the program keeps the
batch fetch outside ``train.iteration``; the harness starts and stops
the profiler from inside the iterator, which cuts that span at both
ends of the window and leaves the iterations whole).

What is left is the host's enqueueing of the iteration's device work,
reward scoring, stats and logging.  The cell turns host-bound when this
reaches the device's time per iteration.  With ``host_wait_ms`` it adds
up to the iteration's wall: that sum against the device trace's update
period is the check that both timelines share a clock and that the
spans cover the loop.

A program without these spans (the parent of the PR that added them)
gives nothing to read: None, and the metric is left out of the line.
"""

ITERATION = "train.iteration"
WAITS = ("rollout.fetch",)
BEFORE = "data.next_batch"


def read(trace, counters, ctx):
    hs = ctx.lib("host_spans")
    spans = hs.of_run(ctx)
    if spans is None:
        return None
    busy = [it.dur / 1e9 - spans.inside(it, WAITS) + spans.before(it, BEFORE)
            for it in spans.whole(ITERATION)]
    return 1e3 * hs.median(busy) if busy else None
