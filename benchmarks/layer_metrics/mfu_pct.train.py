"""Model FLOP/s utilisation of the traced iterations, in %.

Operations the algorithm needs per iteration (``flops.py``: forward 2
per matmul parameter and token, backward twice that, attention scores
and values; remat's recomputation NOT counted) over the device period
of one iteration (the median distance between the starts of successive
update programs in the trace), over chips x the chip's peak (``peaks.json``).
It is ``train_samples_per_s`` times a constant while the shapes stay."""

UPDATE = r"_epochs_fn"


def read(trace, counters, ctx):
    import json
    import os

    p = ctx.lib("trace_reduce").program(trace, UPDATE)
    if not p or not p["period_s"]:
        return None
    period = p["period_s"]
    flops = ctx.lib("flops").ppo_iteration_flops(
        counters["model"], samples=counters["samples_per_iteration"],
        prompt_len=counters["prompt_len"], new_tokens=counters["new_tokens"],
        num_epochs=counters["num_epochs"])
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "peaks.json")) as f:
        peaks = json.load(f)["device_kind"]
    kind = counters["device_kind"]
    if kind not in peaks:
        raise KeyError(f"no peak for device_kind {kind!r} in peaks.json")
    peak = peaks[kind]["bf16_flops_per_s"] * counters["chips"]
    return 100.0 * flops / period / peak
