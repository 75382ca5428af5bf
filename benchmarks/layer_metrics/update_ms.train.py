"""Device time of one iteration's update program, in ms.

The update is one XLA program per iteration (``BaseTrainer._epochs_fn``
jitted: every minibatch of every epoch inside one scan).  Median
duration of its executions that lie wholly inside the traced window (an
execution cut by the trace's start would pull a mean down).
"""

UPDATE = r"_epochs_fn"


def read(trace, counters, ctx):
    p = ctx.lib("trace_reduce").program(trace, UPDATE)
    return 1e3 * p["median_s"] if p else None
