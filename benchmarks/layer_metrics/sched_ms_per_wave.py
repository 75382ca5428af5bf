"""Host time the scheduler takes per engine wave, in ms
(``program_span``).

Median, over the ``engine.step`` spans wholly inside the traced window,
of the self time of ``sched.admit`` (admission and the slot bookkeeping
after it) plus ``sched.extend`` (reservation growth, preemption) inside
the wave: their durations less the one span of the program that can
nest in them, a harvest that reservation growth forces
(``engine.harvest`` inside ``sched.extend``), which is the harvest's and
not the scheduler's.  Taken by name and not as "less every child",
because the harness's wrappers (``sched_admit``, ``extend_running``)
still sit between these spans and the scheduler calls.  ``sched.admit``
carries ``impl`` (``native`` / ``python``), so the two schedulers can be
compared wave for wave.

In no manifest entry until a serve cell exists; rehearsed on the CPU by
``tests/bench``.  None where the trace has no ``engine.step`` span.
"""

WAVE = "engine.step"
SCHEDULER = ("sched.admit", "sched.extend")
NOT_THE_SCHEDULERS = ("engine.harvest",)


def read(trace, counters, ctx):
    hs = ctx.lib("host_spans")
    spans = hs.of_run(ctx)
    if spans is None:
        return None
    per_wave = spans.per_parent(WAVE, SCHEDULER, less=NOT_THE_SCHEDULERS)
    return 1e3 * hs.median(per_wave) if per_wave else None
