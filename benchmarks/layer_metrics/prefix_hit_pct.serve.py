"""Prompt tokens served from cached pages over prompt tokens admitted,
in %: the engine's ``prefix_cached_pages`` counter over the window
(reset when it opens) times the page size, over the prompt tokens of
the requests due in the window."""


def read(trace, counters, ctx):
    pages = counters.get("server_stats", {}).get("prefix_cached_pages")
    total = counters.get("prompt_tokens_measured")
    if pages is None or not total:
        return None
    return 100.0 * float(pages) * counters["page_size"] / float(total)
