"""Share of the HBM roofline that a block-diffusion rollout reached in
the traced iterations, in %: the least time the chip could take to move
what its ``denoise_forwards`` forwards need (each reads the decode copy
of the weights once and the cache up to where it is filled) over the
median execution of ``jit__generate`` (prefill and the whole loop):
``decode_hbm_roofline_pct.train`` with the count of forwards from the
program, not ``new_tokens`` (a block of 4 tokens takes 5 forwards).

Bytes from the ``rollout.dispatch`` spans' attributes: ``weight_bytes``,
and of ``cache_bytes`` (the whole cache, every layer) the share a
forward reads, ``kv_step_slots`` (the slots of the filled prefix, mean
over the forwards) over the cache's slots, which the span's ``prompt_len``
and the counters give (prompt + whole blocks of new tokens, rounded up to
8).  A batch that ends early runs fewer forwards than the span says and
would read too high: the cell's length reward has no stop token.  A
program whose spans lack the attributes gives nothing to read."""

ROLLOUT = r"jit__generate"


def read(trace, counters, ctx):
    hs = ctx.lib("host_spans")
    p = ctx.lib("trace_reduce").program(trace, ROLLOUT)
    counts = ctx.lib("flops_sdar").span_counts(ctx)
    if not p or not p["median_s"] or counts is None:
        return None
    r = counts["rollout"]
    block = r["block_length"]
    new = (float(counters["new_tokens"]) + block - 2.0) // block + 1.0
    slots = -(-(float(counters["prompt_len"]) + new * block) // 8.0) * 8.0
    per_forward = r["weight_bytes"] + r["cache_bytes"] * min(
        r["kv_step_slots"] / slots, 1.0)
    peak = ctx.lib("roofline_dsv3").peaks(
        counters["device_kind"])["hbm_bytes_per_s"] * counters["chips"]
    return 100.0 * r["denoise_forwards"] * per_forward / peak / p["median_s"]
