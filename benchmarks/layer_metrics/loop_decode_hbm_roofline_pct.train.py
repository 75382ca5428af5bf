"""Share of the HBM roofline that the fixed-batch rollout of a stack run
several times over reached in the traced iterations, in %: as
``decode_hbm_roofline_pct.train`` reads, but a step's bytes are the
blocks' weights once a PASS (``ut_steps x stack_weight_bytes``), what
stands behind the stack once (``once_weight_bytes``: the head) and, for
every row and every (pass, layer) entry, the slots the
``rollout.dispatch`` spans say the step read (``kv_step_slots``, the mean
over the steps from the real lengths, times the bytes of one slot of one
entry over the batch, ``cache_bytes / (layer_visits x slots)``: 8192
bytes a token a visit a row at 16 key heads of 128 in bfloat16, times
``layer_visits``), times ``new_tokens`` steps, over the median execution
of ``jit__generate`` at the HBM's peak (``peaks.json``).

Prefill's time is in the denominator and its bytes are not in the
numerator, so it reads LOW, never over 100.  A batch that ends early
would read too high: the cells' length reward has no stop token.  A
program whose spans lack the attributes gives nothing to read."""

ROLLOUT = r"jit__generate"


def read(trace, counters, ctx):
    flops_lib = ctx.lib("flops_ouro")
    p = ctx.lib("trace_reduce").program(trace, ROLLOUT)
    if not p or not p["median_s"]:
        return None
    row = ctx.lib("roofline_mellum2").span_medians(ctx, "rollout.dispatch",
                                                   flops_lib.STEP_KEYS)
    if row is None or not row["layer_visits"] > 0:
        return None
    # the cache's slots a row (models/transformer.py::cache_slots)
    slots = -(-(float(counters["prompt_len"])
                + float(counters["new_tokens"])) // 8) * 8
    per_step = flops_lib.decode_step_bytes(row, slots)
    peak = ctx.lib("roofline_dsv3").peaks(
        counters["device_kind"])["hbm_bytes_per_s"] * counters["chips"]
    return 100.0 * float(counters["new_tokens"]) * per_step / peak \
        / p["median_s"]
