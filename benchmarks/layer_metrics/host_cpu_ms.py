"""The CPU time the trainer's thread burns per training iteration, in ms
(``program_span``): the host's WORK, where ``host_busy_ms`` is the wall
outside the fetch (and holds every dispatch that blocks).

Median, over the ``train.iteration`` spans that lie wholly inside the
traced window, of the span's ``cpu_us`` (the thread's CPU clock read at
the span's two ends by the program, ``orion_tpu/obs/trace.py``) plus
the ``cpu_us`` of the ``data.next_batch`` span before it where the trace
has it.  The cell turns host-bound when THIS nears the iteration's
wall: a thread that waits on the device, or sits in a dispatch that
blocks without spinning, burns nothing.

A program whose spans carry no ``cpu_us`` (the parent of the PR that
added it) gives nothing to read: None, and the metric is left out.
"""

ITERATION = "train.iteration"
BEFORE = "data.next_batch"
CPU = "cpu_us"


def read(trace, counters, ctx):
    hs = ctx.lib("host_spans")
    spans = hs.of_run(ctx)
    if spans is None:
        return None
    # a batch fetch and its iteration carry the same ``it``; the first
    # one of a traced window is cut by the profiler's start and absent
    batch_cpu = {sp.stats.get("it"): float(sp.stats.get(CPU, 0.0))
                 for _, thread in spans.threads for sp in thread
                 if sp.name == BEFORE}
    cpu = [float(it.stats[CPU]) + batch_cpu.get(it.stats.get("it"), 0.0)
           for it in spans.whole(ITERATION) if CPU in it.stats]
    return 1e-3 * hs.median(cpu) if cpu else None
