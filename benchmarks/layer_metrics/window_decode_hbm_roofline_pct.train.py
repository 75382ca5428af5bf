"""Share of the HBM roofline that the fixed-batch rollout over a ring
cache beside full caches reached in the traced iterations, in %: as
``decode_hbm_roofline_pct.train`` reads, but a step's bytes are the
decode copy of the weights (``weight_bytes``) plus the slots the
``rollout.dispatch`` spans say the step read: ``kv_slots_read_window``
and ``kv_slots_read_full`` (means over rows and steps, from the real
lengths: the blocks ``dense_step`` visits under ``reach``), each times
its layers (``window_layers``, ``full_layers``) and the bytes of one
slot of one layer over the batch (``ring_cache_bytes / (window_layers x
window_slots)``: 2048 bytes a token a layer a row at 4 key heads of 128
in bfloat16), times ``new_tokens`` steps, over the median execution of
``jit__generate`` at the HBM's peak (``peaks.json``).

Prefill's time is in the denominator and its bytes are not in the
numerator, so it reads LOW, never over 100: at 8 prompts of 5-7 k tokens
the prefill is a sizeable part of ``jit__generate``.  A batch that ends
early would read too high: the cells' length reward has no stop token.
A program whose spans lack the attributes gives nothing to read."""

ROLLOUT = r"jit__generate"
NEED = ("weight_bytes", "window_layers", "full_layers", "window_slots",
        "ring_cache_bytes", "kv_slots_read_window", "kv_slots_read_full")


def read(trace, counters, ctx):
    p = ctx.lib("trace_reduce").program(trace, ROLLOUT)
    if not p or not p["median_s"]:
        return None
    row = ctx.lib("roofline_mellum2").span_medians(ctx, "rollout.dispatch",
                                                   NEED)
    if row is None or not row["window_layers"] * row["window_slots"] > 0:
        return None
    slot = row["ring_cache_bytes"] / (row["window_layers"]
                                      * row["window_slots"])
    per_step = ctx.lib("flops_mellum2").decode_step_bytes(
        row["weight_bytes"], slot, row)
    peak = ctx.lib("roofline_dsv3").peaks(
        counters["device_kind"])["hbm_bytes_per_s"] * counters["chips"]
    return 100.0 * float(counters["new_tokens"]) * per_step / peak \
        / p["median_s"]
