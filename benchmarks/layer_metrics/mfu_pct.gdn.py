"""Model FLOP/s utilisation of the traced iterations of an
``olmo_hybrid`` model, in %: as ``mfu_pct.kda``, with the operations from
``flops_olmo_hybrid.py`` (projections, the convolutions and the
recurrence's own operations of the GDN layers at their true head sizes,
attention on the full-attention layers, the MLPs, the head over the
rows held), over the update program's period, over chips x the bf16 peak
of ``peaks.json``.  A configuration that is no ``olmo_hybrid`` one gives
nothing to read."""

UPDATE = r"_epochs_fn"


def read(trace, counters, ctx):
    p = ctx.lib("trace_reduce").program(trace, UPDATE)
    if not p or not p["period_s"] \
            or "linear_key_head_dim" not in counters["model"]:
        return None
    flops = ctx.lib("flops_olmo_hybrid").ppo_iteration_flops(
        counters["model"], samples=counters["samples_per_iteration"],
        prompt_len=counters["prompt_len"], new_tokens=counters["new_tokens"],
        num_epochs=counters["num_epochs"])
    peak = ctx.lib("roofline_dsv3").peaks(
        counters["device_kind"])["bf16_flops_per_s"] * counters["chips"]
    return 100.0 * flops / p["period_s"] / peak
