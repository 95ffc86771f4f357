"""Device operations per request: the operations in the traced window over
the requests served by the steps inside it."""
from bench import tracefile


def read(ctx):
    trace, steps = ctx["trace"], ctx["window"].traced_steps
    served = sum(n for _, _, n in steps)
    if trace is None or not served:
        return None
    ops = tracefile.op_count(trace)
    return ops / served if ops else None
