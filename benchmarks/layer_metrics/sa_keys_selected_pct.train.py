"""Share of a query's valid keys that sparse attention keeps
(``program_span``), in %: median over the traced iterations of
``sa_keys_selected / sa_keys_valid`` from the ``update`` spans'
attributes (sums over the real queries of one whole-sequence forward of
the iteration's batch, from the lengths the rollout's fetch brought:
query t has t + 1 valid keys and keeps ``min(sa_topk, t + 1)``).  For
real lengths n of 6656-8192 and topk 2048, (2048^2 / 2 + (n - 2048) x
2048) / (n^2 / 2) = 44-52%: a wrong length distribution (short prompts:
100%) or a selection that takes everything shows here.  A program whose
spans lack the attributes gives nothing to read."""


def read(trace, counters, ctx):
    hs = ctx.lib("host_spans")
    spans = hs.of_run(ctx)
    if spans is None:
        return None
    rows = [sp.stats for sp in spans.whole("update")
            if "sa_keys_valid" in sp.stats and "sa_keys_selected" in sp.stats]
    rows = [r for r in rows if float(r["sa_keys_valid"]) > 0]
    if not rows:
        return None
    return 100.0 * hs.median([float(r["sa_keys_selected"])
                              / float(r["sa_keys_valid"]) for r in rows])
