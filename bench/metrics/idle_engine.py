"""Share of the traced window, in %, in which the first chip ran nothing
while the innermost program span on the host was the serving engine's
``engine.step``: ``spans.idle_share`` of layer ``engine``."""
from bench import spans


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    return spans.idle_share(trace, spans.program_spans(trace), "engine")
