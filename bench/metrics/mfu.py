"""Whole serving step's share of the chip's peak, in %: the analytic FLOPs
of the requests served by the traced steps, over the wall seconds of those
steps (``step()`` and the wait for its logits), over the peak FLOP/s.
The bf16 peak serves every dtype (see ``bench/peaks.py``)."""


def read(ctx):
    steps, peak = ctx["window"].traced_steps, ctx["peak"]
    served = sum(n for _, _, n in steps)
    busy = sum(ready - start for start, ready, _ in steps)
    if peak is None or not served or busy <= 0:
        return None
    return 100.0 * served * ctx["flops_per_request"] / busy \
        / float(peak["peak_flops_per_s"])
