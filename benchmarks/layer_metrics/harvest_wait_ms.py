"""Time per engine wave the serving thread spends in the harvest, in ms
(``program_span``).

Median, over the ``engine.step`` spans wholly inside the traced window,
of the ``engine.harvest`` spans inside the wave: the fetch of the
lagged done-flags (the thread blocks until the segment that produced
them has run), the fetch of the finished rows, and retiring them.  With
``harvest_lag=1`` the flags are one segment old and the wait should be
short; it grows to a whole segment when the lag is 0.

In no manifest entry until a serve cell exists; rehearsed on the CPU by
``tests/bench``.  None where the trace has no ``engine.step`` span.
"""

WAVE = "engine.step"
HARVEST = ("engine.harvest",)


def read(trace, counters, ctx):
    hs = ctx.lib("host_spans")
    spans = hs.of_run(ctx)
    if spans is None:
        return None
    per_wave = spans.per_parent(WAVE, HARVEST)
    return 1e3 * hs.median(per_wave) if per_wave else None
