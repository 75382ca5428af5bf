"""Model FLOP/s utilisation of the traced iterations of an ``ouro``
configuration, in %: the whole iteration's useful operations by
``flops_ouro.py`` (every block's products over the real tokens and
attention over their causal (query, key) pairs once a layer VISIT, the
head once: ``ut_steps``, ``layer_visits``, ``seq_tokens`` and
``causal_keys`` from the ``update`` spans) over the iteration's period
(the median time from one execution of the rollout's program,
``jit__generate``, to the next: an iteration runs it once, and two traced
iterations hold two of them where they hold one whole update), over
chips x the bf16 peak of ``peaks.json``.  Masked keys and
remat's recomputation are not counted, so it reads under 100 by
construction.  A program whose spans lack the counters, or a
configuration without ``total_ut_steps``, gives nothing to read."""

ROLLOUT = r"jit__generate"


def read(trace, counters, ctx):
    model = counters["model"]
    if "total_ut_steps" not in model:
        return None
    flops_lib = ctx.lib("flops_ouro")
    p = ctx.lib("trace_reduce").program(trace, ROLLOUT)
    upd = ctx.lib("roofline_mellum2").span_medians(ctx, "update",
                                                   flops_lib.KEYS)
    if not p or not p["period_s"] or upd is None:
        return None
    flops = flops_lib.ppo_iteration_flops(
        model, samples=counters["samples_per_iteration"],
        new_tokens=counters["new_tokens"], num_epochs=counters["num_epochs"],
        counts=upd)
    peak = ctx.lib("roofline_dsv3").peaks(
        counters["device_kind"])["bf16_flops_per_s"] * counters["chips"]
    return 100.0 * flops / p["period_s"] / peak
