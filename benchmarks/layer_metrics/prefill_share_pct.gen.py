"""Device time in prefill programs over device time in all programs,
in %, in the traced window (``_prefill_fn``, and ``_chunk_fn`` when
prefill is chunked)."""

PREFILL = r"_prefill_fn|_chunk_fn"


def read(trace, counters, ctx):
    tr = ctx.lib("trace_reduce")
    pre, _ = tr.programs(trace, PREFILL)
    total, _ = tr.programs(trace, r".")
    return 100.0 * pre / total if total > 0 else None
