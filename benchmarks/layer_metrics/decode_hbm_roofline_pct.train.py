"""Share of the HBM roofline that the fixed-batch rollout reached in the
traced iterations, in %: the least time the chip could take to move what
``new_tokens`` decode steps need (a step reads the decode copy of the
weights once, reads AND writes the state that is not indexed by position
once each, and reads the cache up to where it is filled) over the median
execution of ``jit__generate`` (prefill and the whole decode loop).

Bytes from the ``rollout.dispatch`` spans' attributes (``weight_bytes``,
``state_bytes``, ``cache_bytes``: the program's own shapes), the peak
from ``peaks.json``.  The cache is ``cache_bytes`` over ``prompt_len +
new_tokens`` slots; a step need read only the filled ones, and since a
prompt's real length is not in the span, the new tokens alone are
counted: ``new_tokens / 2`` slots on average.  A batch that ends early
(every row at a stop token) runs fewer steps than ``new_tokens`` and
would read too high: the cells' length reward has no stop token.  A
program whose spans lack the attributes gives nothing to read."""

ROLLOUT = r"jit__generate"


def read(trace, counters, ctx):
    hs = ctx.lib("host_spans")
    p = ctx.lib("trace_reduce").program(trace, ROLLOUT)
    spans = hs.of_run(ctx)
    if not p or not p["median_s"] or spans is None:
        return None
    rows = [sp.stats for sp in spans.whole("rollout.dispatch")
            if "state_bytes" in sp.stats and "weight_bytes" in sp.stats]
    if not rows:
        return None
    weights, state, cache = (
        hs.median([float(r[k]) for r in rows])
        for k in ("weight_bytes", "state_bytes", "cache_bytes"))
    steps = float(counters["new_tokens"])
    slots = float(counters["prompt_len"]) + steps
    per_step = weights + 2.0 * state + cache * (steps / 2.0) / slots
    peak = ctx.lib("roofline_dsv3").peaks(
        counters["device_kind"])["hbm_bytes_per_s"] * counters["chips"]
    return 100.0 * steps * per_step / peak / p["median_s"]
