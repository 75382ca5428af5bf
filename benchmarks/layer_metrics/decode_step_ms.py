"""Device time of one decode step, in ms: the continuous engine's
decode-segment program (``_segment_fn``, ``segment_len`` steps per
execution): the median duration of its executions wholly inside the
traced window, over ``segment_len``."""

SEGMENT = r"_segment_fn"


def read(trace, counters, ctx):
    p = ctx.lib("trace_reduce").program(trace, SEGMENT)
    if not p or not counters.get("segment_len"):
        return None
    return 1e3 * p["median_s"] / counters["segment_len"]
