"""Share of the traced window, in %, in which the chip ran no operation:
1 - (union of device-op intervals / window)."""
from bench import tracefile


def read(ctx):
    trace = ctx["trace"]
    w = tracefile.window(trace) if trace is not None else None
    if w is None or w[1] <= w[0] or not trace.devices:
        return None
    return 100.0 * (1.0 - tracefile.busy_ns(trace) / (w[1] - w[0]))
