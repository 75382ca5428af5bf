"""Time per training iteration the trainer's thread spends blocked on
the device, in ms (``program_span``).

Median, over the ``train.iteration`` spans that lie wholly inside the
traced window, of the time their *wait* spans cover: ``rollout.fetch``,
the one batched ``jax.device_get`` of an iteration (generation result
and the previous iteration's deferred stats).  While the thread waits
the device runs the previous update and this iteration's rollout, so
this falls when the device gets faster; ``host_busy_ms`` is the rest of
the iteration.

A program without these spans gives nothing to read: None.
"""

ITERATION = "train.iteration"
WAITS = ("rollout.fetch",)


def read(trace, counters, ctx):
    hs = ctx.lib("host_spans")
    spans = hs.of_run(ctx)
    if spans is None:
        return None
    waits = spans.per_parent(ITERATION, WAITS)
    return 1e3 * hs.median(waits) if waits else None
