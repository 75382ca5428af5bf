"""Mean staleness of the iterations in the run, in iterations: how many
learner updates behind the rollout's weights were when its batch was
consumed (``staleness`` of the trainer's metrics rows: a count made by
the program).  Only an asynchronous job has such rows."""


def read(trace, counters, ctx):
    rows = counters.get("staleness") or []
    return sum(rows) / len(rows) if rows else None
