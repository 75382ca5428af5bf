"""Tokens kept over slot-steps executed, in %: tokens the clients
received while the trace ran, over executions of the decode-segment
program in the trace x ``segment_len`` x slots.  Slots that idle,
decode past a finished request until the segment ends, or wait for a
harvest all execute rows nobody keeps.

A mixed source: the numerator is the load generator's count of tokens
received while the tracer was active (host side), the denominator comes
from the device trace.  The PR that lists it in the manifest declares
it ``program_counter``, or takes the numerator from a counter of the
engine's once it has one."""

SEGMENT = r"_segment_fn"


def read(trace, counters, ctx):
    _, runs = ctx.lib("trace_reduce").programs(trace, SEGMENT)
    rows = runs * counters["segment_len"] * counters["slots"]
    return 100.0 * counters["trace_tokens"] / rows if rows else None
