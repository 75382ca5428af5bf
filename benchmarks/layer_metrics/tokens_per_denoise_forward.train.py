"""Completion tokens a block-diffusion rollout placed a forward of a
row (``program_span``), median over the traced iterations: the batch's
``completion_tokens`` (the ``experience.dispatch`` span) over its rows
(``batch``) and its ``denoise_forwards`` (both of the ``rollout.dispatch``
span).  0.8 under 4 denoising forwards and a commit a block of 4: what a
commit fused into the next block's first forward or a bolder reveal
schedule would move.  A program whose spans lack the counters gives
nothing to read."""


def read(trace, counters, ctx):
    hs = ctx.lib("host_spans")
    spans = hs.of_run(ctx)
    if spans is None:
        return None
    forwards = [float(sp.stats["denoise_forwards"]) * float(sp.stats["batch"])
                for sp in spans.whole("rollout.dispatch")
                if "denoise_forwards" in sp.stats and "batch" in sp.stats]
    placed = [float(sp.stats["completion_tokens"])
              for sp in spans.whole("experience.dispatch")
              if "completion_tokens" in sp.stats]
    if not forwards or not placed or not hs.median(forwards) > 0:
        return None
    return hs.median(placed) / hs.median(forwards)
