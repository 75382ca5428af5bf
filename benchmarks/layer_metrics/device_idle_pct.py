"""Share of the traced window in which no operation ran on the device,
in %: 1 - union of the device-operation intervals over the window,
mean over the cell's chips."""


def read(trace, counters, ctx):
    if not trace["window_s"] > 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
