"""Host ms of the wire codec per request: the self time of the program
spans ``wire.encode``, ``wire.send`` and ``wire.decode`` in the traced
window (``spans.codec_ms``), over the requests served by the traced
steps."""
from bench import spans


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    served = sum(n for _, _, n in ctx["window"].traced_steps)
    return spans.codec_ms(trace, spans.program_spans(trace), served)
