"""Share of the device's busy time spent inside custom calls (the
Pallas kernels are ``tpu_custom_call`` operations), in %, by the
operations' self time in the traced window."""


def read(trace, counters, ctx):
    busy = sum(trace["by_kind_s"].values())
    return 100.0 * trace["custom_call_s"] / busy if busy > 0 else None
