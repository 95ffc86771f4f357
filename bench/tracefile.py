"""Reduction of a profiler trace to device busy time, op counts, kernel
time and idle gaps.

The harness writes its own host spans (``HOST_SPANS``) with
``jax.profiler.TraceAnnotation``; the device's operations come from the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane.  The program's own
spans are the host events whose name has the program's span form
(``PROGRAM_SPAN``): lowercase words joined by dots, at least two
(``engine.step``, ``wire.sync``).  A span the program adds later is kept
as long as its name has that form, and a reader finds it in
``Trace.program``.  All of them lie on the trace's one timeline.  The
traced window runs from the start of the first harness span to the end of
the last, and every device operation is clipped to it."""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

HOST_SPANS = ("submit", "step", "wait_logits", "idle_no_request")
PROGRAM_SPAN = re.compile(r"[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: dict

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    devices: list[list[Event]]    # per chip: its device ops, by start
    spans: list[Event]            # the harness's host spans, by start
    # the program's host spans (``PROGRAM_SPAN``), by start
    program: list[Event] = dataclasses.field(default_factory=list)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def _stats(event) -> dict:
    out = {}
    for item in event.stats:
        if isinstance(item, tuple) and len(item) == 2:
            out[str(item[0])] = item[1]
    return out


def from_profile(profile) -> Trace:
    """Build a ``Trace`` from a ``jax.profiler.ProfileData``."""
    devices, spans, program = [], [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [Event(e.name, float(e.start_ns), float(e.duration_ns),
                         _stats(e))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices.append(sorted(ops, key=lambda e: e.start_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        spans.append(Event(e.name, float(e.start_ns),
                                           float(e.duration_ns), {}))
                    elif PROGRAM_SPAN.fullmatch(e.name):
                        program.append(Event(e.name, float(e.start_ns),
                                             float(e.duration_ns), {}))
    return Trace(devices, sorted(spans, key=lambda e: e.start_ns),
                 sorted(program, key=lambda e: e.start_ns))


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file, or a text-proto XSpace (``.pbtxt``)."""
    from jax.profiler import ProfileData
    if path.endswith(".pbtxt"):
        with open(path) as f:
            return from_profile(ProfileData.from_text_proto(f.read()))
    return from_profile(ProfileData.from_file(path))


def window(trace: Trace) -> tuple[float, float] | None:
    if not trace.spans:
        return None
    return (trace.spans[0].start_ns, max(s.end_ns for s in trace.spans))


def _union(events: list[Event], w0: float, w1: float) -> list[list[float]]:
    merged: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start_ns):
        a, b = max(e.start_ns, w0), min(e.end_ns, w1)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def in_window(events: list[Event], w0: float, w1: float) -> list[Event]:
    return [e for e in events if e.end_ns > w0 and e.start_ns < w1]


def busy_ns(trace: Trace) -> float:
    """Union of device-op intervals in the window, averaged over chips."""
    w = window(trace)
    if w is None or not trace.devices:
        return 0.0
    per_chip = [sum(b - a for a, b in _union(ops, *w))
                for ops in trace.devices]
    return sum(per_chip) / len(per_chip)


def op_count(trace: Trace) -> int:
    """Device operations in the window, summed over chips."""
    w = window(trace)
    if w is None:
        return 0
    return sum(len(in_window(ops, *w)) for ops in trace.devices)


def op_name(e: Event) -> str:
    """The HLO instruction's own name, without its ``%``.  The TPU trace
    names each op by its whole HLO text (``%_conv2d.1 = f32[...]
    custom-call(...), ...``), whose operands name other ops; only the part
    before `` = `` is this op's."""
    name = e.stats.get("hlo_op") or e.name.split(" = ", 1)[0]
    return str(name).strip().lstrip("%")


def kernel_events(trace: Trace, pattern: str) -> list[Event]:
    """Device ops in the window whose instruction name (``op_name``)
    matches ``pattern``, a regular expression, in full."""
    w = window(trace)
    if w is None:
        return []
    rx = re.compile(pattern)
    return [e for ops in trace.devices for e in in_window(ops, *w)
            if rx.fullmatch(op_name(e))]


def _op_label(e: Event) -> str:
    module = e.stats.get("hlo_module")
    return f"{module}/{e.name}" if module else e.name


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """[[label, seconds], ...]: device time per op label, largest first."""
    w = window(trace)
    if w is None:
        return []
    total: dict[str, float] = {}
    for ops in trace.devices:
        for e in in_window(ops, *w):
            dur = min(e.end_ns, w[1]) - max(e.start_ns, w[0])
            total[_op_label(e)] = total.get(_op_label(e), 0.0) + dur
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def idle_gaps(trace: Trace) -> list[tuple[float, float]]:
    """Intervals of the window in which the first chip ran nothing."""
    w = window(trace)
    if w is None or not trace.devices:
        return []
    gaps, t = [], w[0]
    for a, b in _union(trace.devices[0], *w):
        if a > t:
            gaps.append((t, a))
        t = b
    if w[1] > t:
        gaps.append((t, w[1]))
    return gaps


def idle_by_host_span(trace: Trace, n: int = 10) -> list[list]:
    """[[host span, seconds], ...]: the first chip's idle time, split by
    the harness span the host was in, largest first.  Idle time under no
    harness span is ``between_spans``."""
    total: dict[str, float] = {}
    spans = trace.spans               # sequential, so ends are sorted too
    ends = [s.end_ns for s in spans]
    for a, b in idle_gaps(trace):
        covered = 0.0
        i = bisect.bisect_right(ends, a)
        while i < len(spans) and spans[i].start_ns < b:
            s = spans[i]
            i += 1
            lo, hi = max(a, s.start_ns), min(b, s.end_ns)
            if hi > lo:
                total[s.name] = total.get(s.name, 0.0) + (hi - lo)
                covered += hi - lo
        if b - a > covered:
            total["between_spans"] = total.get("between_spans", 0.0) \
                + (b - a - covered)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]
