"""Share of the traced window, in %, in which the first chip ran nothing
while the innermost program span on the host was the chain runtime's
(``chain.infer``) or a wire hop's (``wire.*``): ``spans.idle_share`` of
layer ``runtime``."""
from bench import spans


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    return spans.idle_share(trace, spans.program_spans(trace), "runtime")
