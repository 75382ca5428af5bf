"""Share of a query's causal keys that a sliding-window layer leaves it
(``program_span``), in %: median over the traced iterations of
``window_keys_seen / causal_keys`` from the ``update`` spans' attributes
(sums over the real queries of one whole-sequence forward of the
iteration's batch on ONE layer, from the lengths the rollout's fetch
brought: query t has ``t + 1`` causal keys and sees ``min(t + 1,
sliding_window)``).  For real lengths n of 6-8 k and a window of 1024,
(1024 n - 1024^2 / 2) / (n^2 / 2) = 23-31%: a wrong length distribution
(short prompts: 100%) or a window rule that changes shows here.  A
program whose spans lack the attributes gives nothing to read."""


def read(trace, counters, ctx):
    hs = ctx.lib("host_spans")
    spans = hs.of_run(ctx)
    if spans is None:
        return None
    rows = [sp.stats for sp in spans.whole("update")
            if "window_keys_seen" in sp.stats and "causal_keys" in sp.stats]
    rows = [r for r in rows if float(r["causal_keys"]) > 0]
    if not rows:
        return None
    return 100.0 * hs.median([float(r["window_keys_seen"])
                              / float(r["causal_keys"]) for r in rows])
