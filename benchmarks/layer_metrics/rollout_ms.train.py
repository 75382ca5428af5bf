"""Device time of one iteration's fixed-batch rollout, in ms: the
``RolloutEngine._generate`` program (prefill + the decode loop, one
program per iteration).  Median duration of its whole executions in the
traced window."""

ROLLOUT = r"jit__generate"


def read(trace, counters, ctx):
    p = ctx.lib("trace_reduce").program(trace, ROLLOUT)
    return 1e3 * p["median_s"] if p else None
