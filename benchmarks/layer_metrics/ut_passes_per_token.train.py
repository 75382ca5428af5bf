"""The passes over the stack that the experience forward ran for a token
(``program_span``): median over the window's iterations of
``ut_passes_per_token`` on the ``stats.finalize`` spans, which is the
number of passes that forward sowed exit masses for
(``trainers/base.py::ut_exit_stats``).  It mirrors ``total_ut_steps``
(4.0 for Ouro-2.6B): at ``early_exit_threshold`` 1, the only threshold a
configuration may state, every token runs every pass.  It reads the
experience forward, not the rollout: a rollout that ends a token's
passes early has to bring a counter of its own.  A program whose spans
lack the counter gives nothing to read."""


def read(trace, counters, ctx):
    hs = ctx.lib("host_spans")
    spans = hs.of_run(ctx)
    if spans is None:
        return None
    rows = [float(sp.stats["ut_passes_per_token"])
            for sp in spans.whole("stats.finalize")
            if "ut_passes_per_token" in sp.stats]
    return hs.median(rows) if rows else None
