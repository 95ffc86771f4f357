"""The program's own host spans in a profiler trace, and the first chip's
idle time split by them.

The serving path names its spans in ``repro.runtime.events.SPANS``; this
is the benchmark's own copy (a test holds the two equal).  Each span is a
``jax.profiler.TraceAnnotation`` on the host plane, on the trace's one
clock with the device's operations.  They nest on the serving thread:

    engine.step > chain.infer > chain.stage
                              > wire.encode > wire.sync
                              > wire.send
                              > wire.decode

Program spans are clipped to the traced window (``tracefile.window``).
Each idle interval of the first chip goes to the innermost program span
that covers it, and a span's self time is its duration less what its
child spans cover.  Each span's layer is in ``LAYERS``."""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from collections import Counter

from bench import harness, tracefile
from bench import spec as spec_lib
from bench.tracefile import Event, Trace

PROGRAM_SPANS = ("engine.step", "chain.infer", "chain.stage", "wire.sync",
                 "wire.encode", "wire.send", "wire.decode")
LAYERS = {
    "walk": ("chain.stage",),
    "runtime": ("chain.infer", "wire.sync", "wire.encode", "wire.send",
                "wire.decode"),
    "engine": ("engine.step",),
}
# host time of the wire codec: wire.encode's self time (wire.sync, the
# wait for the device work it copies, is its child), the send and decode
CODEC = ("wire.encode", "wire.send", "wire.decode")


def from_profile(profile) -> list[Event]:
    """The program spans of a ``jax.profiler.ProfileData``, by start."""
    found = [Event(e.name, float(e.start_ns), float(e.duration_ns), {})
             for plane in profile.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name in PROGRAM_SPANS]
    return sorted(found, key=lambda e: e.start_ns)


def load(path: str) -> tuple[Trace, list[Event]]:
    """(``tracefile.Trace``, program spans) of one ``.xplane.pb`` file, or
    of a text-proto XSpace (``.pbtxt``)."""
    from jax.profiler import ProfileData
    if path.endswith(".pbtxt"):
        with open(path) as f:
            profile = ProfileData.from_text_proto(f.read())
    else:
        profile = ProfileData.from_file(path)
    return tracefile.from_profile(profile), from_profile(profile)


def innermost(program: list[Event], w0: float, w1: float
              ) -> list[tuple[float, float, str]]:
    """The part of ``[w0, w1]`` that program spans cover, cut into
    ``(start, end, name)`` pieces, each named after the innermost span
    over it.  Spans nest, so a child ends within its parent."""
    pieces: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []       # (end, name), outermost first
    t = w0

    def close_to(upto: float) -> None:
        nonlocal t
        if stack and upto > t:
            pieces.append((t, upto, stack[-1][1]))
        t = max(t, upto)

    for s in sorted(program, key=lambda s: (s.start_ns, -s.dur_ns)):
        a, b = max(s.start_ns, w0), min(s.end_ns, w1)
        if b <= a:
            continue
        while stack and stack[-1][0] <= a:
            close_to(stack[-1][0])
            stack.pop()
        close_to(a)
        stack.append((min(b, stack[-1][0]) if stack else b, s.name))
    while stack:
        close_to(stack[-1][0])
        stack.pop()
    return pieces


def self_ns(trace: Trace, program: list[Event]) -> dict[str, float]:
    """Each program span's self time in the window, summed by name."""
    w = tracefile.window(trace)
    total: dict[str, float] = {}
    if w is None:
        return total
    for a, b, name in innermost(program, *w):
        total[name] = total.get(name, 0.0) + (b - a)
    return total


def idle_ns(trace: Trace, program: list[Event]) -> dict[str, float]:
    """The first chip's idle time in the window, by the innermost program
    span the host was in; idle time under no program span is left out."""
    w = tracefile.window(trace)
    total: dict[str, float] = {}
    if w is None:
        return total
    gaps = tracefile.idle_gaps(trace)
    pieces = innermost(program, *w)
    i = 0
    for a, b, name in pieces:              # both lists sorted, disjoint
        while i < len(gaps) and gaps[i][1] <= a:
            i += 1
        j = i
        while j < len(gaps) and gaps[j][0] < b:
            lo, hi = max(a, gaps[j][0]), min(b, gaps[j][1])
            total[name] = total.get(name, 0.0) + (hi - lo)
            j += 1
    return total


def idle_share(trace: Trace, program: list[Event], layer: str
               ) -> float | None:
    """Share of the window, in %, in which the first chip was idle and
    the innermost program span belonged to ``layer`` (``LAYERS``); None
    where the window holds no program span."""
    w = tracefile.window(trace)
    if w is None or w[1] <= w[0] or not trace.devices or \
            not tracefile.in_window(program, *w):
        return None
    idle = idle_ns(trace, program)
    return 100.0 * sum(idle.get(n, 0.0) for n in LAYERS[layer]) \
        / (w[1] - w[0])


def codec_ms(trace: Trace, program: list[Event], served: int
             ) -> float | None:
    """Host ms of the wire codec (``CODEC``'s self time in the window)
    per request served by the traced steps; None where the window holds
    no program span or no request was served."""
    w = tracefile.window(trace)
    if w is None or not served or not tracefile.in_window(program, *w):
        return None
    own = self_ns(trace, program)
    return sum(own.get(n, 0.0) for n in CODEC) / 1e6 / served


# ---------------------------------------------------------------------------
# One traced window of a cell: python3 bench/idle_by_span.py
# ---------------------------------------------------------------------------
COST_CALLS = 100_000


def span_cost_us(jax, calls: int = COST_CALLS) -> tuple[float, float]:
    """(off, on): µs per call that ``annotate_function``, the program's
    way of spanning a function, adds to a no-op, without and with a
    running profiler."""
    def noop():
        return None

    spanned = jax.profiler.annotate_function(noop, name="span_cost")

    def per_call(fn) -> float:
        t = time.perf_counter()
        for _ in range(calls):
            fn()
        return (time.perf_counter() - t) / calls * 1e6

    off = per_call(spanned) - per_call(noop)
    trace_dir = tempfile.mkdtemp(prefix="bench_span_cost_")
    jax.profiler.start_trace(trace_dir)
    try:
        on = per_call(spanned) - per_call(noop)
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(trace_dir, ignore_errors=True)
    return off, on


def traced_window(jax, engine, images, mix: dict, seed: int,
                  seconds: float):
    """``harness.drive`` with the trace on, keeping the program spans:
    (Window, Trace, program spans)."""
    driver = harness.Driver(jax, engine, images, time.perf_counter())
    tracer = harness.Tracer(jax, True, seconds)
    loop = harness.drive_closed if mix["loop"] == "closed" \
        else harness.drive_open
    win = loop(driver, tracer, mix, seed, seconds)
    try:
        trace, program = load(tracefile.find_xplane(tracer.dir))
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    return win, trace, program


def _rate(steps, seconds: float) -> float | None:
    return sum(n for _, _, n in steps) / seconds if seconds > 0 else None


def split(win, trace, program) -> dict:
    """The numbers of one traced window:

    * ``idle_walk``, ``idle_runtime``, ``idle_engine``: ``idle_share`` of
      each layer, beside ``idle_step``, the % of the window idle under the
      harness span ``step``, which the three should add up to;
    * ``codec_ms`` per request served by the traced steps
      (``served_traced``);
    * ``spans_per_request`` and ``self_ms_per_request``: each program
      span's count and self time in the window over those requests;
    * ``rps_traced`` / ``rps_untraced``: requests answered per second by
      the steps inside and outside the traced slice of the window."""
    served = sum(n for _, _, n in win.traced_steps)
    w = tracefile.window(trace)
    window_ns = w[1] - w[0] if w else 0.0
    by_host = dict(tracefile.idle_by_host_span(trace))
    counts = Counter(s.name for s in tracefile.in_window(program, *w)) \
        if w else Counter()
    traced = win.traced_steps
    t_traced = traced[-1][1] - traced[0][0] if traced else 0.0
    rest = [s for s in win.steps if s not in traced and s[1] <= win.seconds]
    out = {f"idle_{layer}": idle_share(trace, program, layer)
           for layer in LAYERS}
    out.update(
        idle_step=100.0 * by_host.get("step", 0.0) * 1e9 / window_ns
        if window_ns else None,
        codec_ms=codec_ms(trace, program, served),
        served_traced=served, window_s=window_ns / 1e9,
        spans_per_request={n: counts[n] / served for n in sorted(counts)}
        if served else {},
        self_ms_per_request={n: v / 1e6 / served for n, v in
                             sorted(self_ns(trace, program).items())}
        if served else {},
        rps_traced=_rate(traced, t_traced),
        rps_untraced=_rate(rest, win.seconds - t_traced))
    return out


def measure(cell: spec_lib.Cell, seed: int, seconds: float, *,
            platform: str = "tpu", root: str = spec_lib.ROOT) -> dict:
    """Set-up as ``bench/run.py`` makes it, the span cost, then one traced
    window; the numbers of ``split`` and the span cost."""
    import jax
    _, _, compiles = harness.open_session(jax, cell, platform, root)
    jax.monitoring.unregister_event_duration_listener(compiles)
    _, images, engine = harness.build(jax, cell.config, cell.traffic, seed)
    off, on = span_cost_us(jax)
    win, trace, program = traced_window(jax, engine, images, cell.traffic,
                                        seed, seconds)
    return {"workload": cell.name, "seed": seed,
            **split(win, trace, program),
            "span_us_off": off, "span_us_on": on}


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(prog="bench/idle_by_span.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        out = measure(spec_lib.resolve(args.workload), args.seed,
                      args.seconds)
    except harness.NoAccelerator as e:
        print(f"idle_by_span: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0
