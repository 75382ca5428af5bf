"""Device time per iteration of the experience forwards, in ms: every
program that is neither the update nor the rollout — the policy+value
forward (``_lp_values_fwd``), the reference forward (``_logprobs_fn``),
advantages and the small glue programs between them.  A program that
runs once an iteration counts its median execution; one that runs many
times counts its total over the iterations traced (= executions of the
update program)."""

import re

UPDATE = r"_epochs_fn"
ROLLOUT = r"jit__generate"


def read(trace, counters, ctx):
    upd = ctx.lib("trace_reduce").program(trace, UPDATE)
    if not upd or not upd["runs"]:
        return None
    iters = upd["runs"]
    total = 0.0
    for name, p in trace["by_program"].items():
        if re.search(UPDATE, name) or re.search(ROLLOUT, name):
            continue
        once = p["runs"] <= iters + 1
        total += p["median_s"] if once else p["s"] / iters
    return 1e3 * total
