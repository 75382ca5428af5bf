"""95th percentile of the engine's own queue wait (submit at the engine
to admission into a slot), in ms, from ``server_stats()``'s histogram,
which the harness resets when the window opens.  A count made by the
program, on the program's clock: a per-layer number, never the
end-to-end one."""


def read(trace, counters, ctx):
    v = counters.get("server_stats", {}).get("queue_wait_s_p95")
    return None if v is None else 1e3 * float(v)
