"""The conv kernel's share of its roofline, in %: the least time the chip
needs for the traced conv kernel calls over their device time.

A call's least time is the larger of FLOPs / peak FLOP/s and bytes / HBM
bytes/s, from the benchmark's own count (``bench/flops.py``).  Only the
Mosaic kernel's own events count: the ``tpu_custom_call`` that XLA names
after the program's jitted wrapper (``kernels/ops.py::_conv2d``), as
``_conv2d.<n>``.  The wrapper's pad and transpose fusions are device ops
of the layer walk, and the int8 codec's kernel is ``_quantize.<n>``.
Every request served in the traced window makes the same calls, so the
traced calls must number requests x calls per request; where they do not,
the share is not read.  The calls are counted over the whole trace and
not clipped to the host spans' window: the profiler runs from just before
the first traced step to just after the last one waited for its logits,
so every conv call it holds is one of those steps', while the device's
timeline can sit a millisecond or so early against the host's."""
import sys

from bench import tracefile

KERNEL = r"_conv2d\.\d+"


def read(ctx):
    trace, peak = ctx["trace"], ctx["peak"]
    served = sum(n for _, _, n in ctx["window"].traced_steps)
    launches = ctx["conv_launches"]
    if trace is None or peak is None or not served or not launches:
        return None
    events = tracefile.kernel_events(trace, KERNEL, whole=True)
    if len(events) != served * len(launches):
        print(f"conv_roofline: {len(events)} conv kernel events for "
              f"{served} requests x {len(launches)} calls; not read",
              file=sys.stderr)
        return None
    device_ns = sum(e.dur_ns for e in events)
    least_s = served * sum(
        max(c["flops"] / float(peak["peak_flops_per_s"]),
            c["bytes"] / float(peak["hbm_bytes_per_s"])) for c in launches)
    return 100.0 * least_s / (device_ns / 1e9) if device_ns > 0 else None
