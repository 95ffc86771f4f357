"""One benchmark run of one cell: build, warm up, drive, check, report.

The system under test is the program's serving entry point,
``serving/cnn_engine.py::CnnServingEngine`` (``submit`` and ``step``),
which runs every chain stage through ``runtime/runtime.py::ChainRuntime``,
``models/cnn.py::apply_cnn`` on the pallas backend and each hop's wire
codec.  Links and tier faults stay on the engine's virtual clock; those
numbers are printed on earlier lines, labelled modelled, and are never a
metric.  Every metric here is from the host clock or the device trace.

A request's latency runs from its due time on the wall clock to the moment
its logits are ready (``block_until_ready``).  The loop submits a request
once its due time has passed, so requests that fall due during a step wait
for it, as they would at a server that runs one step at a time."""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any

import numpy as np

from bench import flops, peaks, reference, tracefile, weights
from bench import spec as spec_lib
from bench import traffic as traffic_lib

TRACE_START_FRACTION = 0.25     # the trace starts this far into the window
TRACE_SECONDS = 3.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
REFERENCE_BLOCK = 8             # images per reference call


class NoAccelerator(RuntimeError):
    """JAX found no chip of the kind the cell needs, or too few."""


@dataclasses.dataclass
class Rec:
    """One request as the harness saw it (seconds from the window start)."""

    idx: int
    image: int
    due: float
    submit: float = math.nan
    step_start: float = math.nan
    ready: float = math.nan
    status: str = "pending"
    req: Any = None


@dataclasses.dataclass
class Window:
    recs: list[Rec]
    seconds: float                  # wall length of the measured window
    served_in_window: int
    steps: list[tuple[float, float, int]]   # (start, ready, requests done)
    traced_steps: list[tuple[float, float, int]]
    generator_late_s: list[float]   # submit - due after an idle wait
    # mean queued requests, sampled before each step, over the window's
    # first and second halves
    depth_halves: tuple[float, float] = (0.0, 0.0)


class CompileCounter:
    """Counts executables built or loaded (compiles and cache hits)."""

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.count += 1


def require_devices(jax, chips: int, platform: str = "tpu") -> list:
    devices = jax.devices()
    if not devices or devices[0].platform != platform:
        found = devices[0].platform if devices else "nothing"
        raise NoAccelerator(f"needs a {platform}; JAX's first device is "
                            f"{found}")
    if len(devices) < chips:
        raise NoAccelerator(f"cell needs {chips} chips, JAX sees "
                            f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache(jax, root: str) -> str:
    """JAX's persistent cache at a fixed path in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), caching every program however
    quickly it compiled, so that a warm run compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------
def wire_formats(config: dict, n_hops: int) -> tuple[str, ...]:
    wire = config["wire"]
    ws = [wire] * n_hops if isinstance(wire, str) else list(wire)
    return tuple(config["storage_dtype"] if w == "follow" else w for w in ws)


def build_engine(config: dict, params):
    """The program's serving engine for ``config``, with its weights."""
    from repro.core.hardware import paper_chain
    from repro.models import cnn
    from repro.serving.cnn_engine import CnnServingEngine
    layers = [cnn.Layer(**layer) for layer in config["layers"]]
    return CnnServingEngine(
        {config["model"]: (layers, params)},
        hw=paper_chain(int(config["chain_tiers"])),
        max_batch=int(config["max_batch"]),
        max_queue=int(config["max_queue"]),
        pipelined=bool(config["pipelined"]),
        dtype=config["storage_dtype"], wire=config["wire"],
        backend=config["backend"])


class Driver:
    """Submits, steps and waits, with a host span around each."""

    def __init__(self, jax, engine, images, t0: float):
        from repro.serving.cnn_engine import QueueFullError
        self._jax = jax
        self._full = QueueFullError
        self.engine = engine
        self.images = images
        self.t0 = t0
        self.inflight: list[Rec] = []

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def submit(self, rec: Rec) -> None:
        with self._jax.profiler.TraceAnnotation("submit"):
            rec.submit = self.now()
            try:
                rec.req = self.engine.submit(self.images[rec.image])
            except self._full:
                rec.status = "shed"
                return
        self.inflight.append(rec)

    def step(self) -> tuple[float, float, list[Rec]]:
        t = self.now()
        with self._jax.profiler.TraceAnnotation("step"):
            self.engine.step()
        done = [r for r in self.inflight if r.req.done]
        with self._jax.profiler.TraceAnnotation("wait_logits"):
            self._jax.block_until_ready([r.req.logits for r in done
                                         if r.req.status == "served"])
        ready = self.now()
        if not done and not self.engine.n_pending:
            raise RuntimeError("a step dispatched nothing and left nothing "
                               "queued, yet requests are in flight")
        for r in done:
            r.step_start, r.ready, r.status = t, ready, r.req.status
        if done:
            self.inflight = [r for r in self.inflight if not r.req.done]
        return t, ready, done

    def idle_until(self, t: float) -> None:
        wait = t - self.now()
        if wait > 0:
            with self._jax.profiler.TraceAnnotation("idle_no_request"):
                time.sleep(wait)


def warm_up(jax, engine, images, max_batch: int, rounds: int = 2) -> None:
    """Every batch size the window can form, through the engine itself:
    the engine stacks and slices per batch size."""
    for _ in range(rounds):
        for n in range(1, max_batch + 1):
            before = engine.stats()["batches"]
            reqs = [engine.submit(images[i % len(images)]) for i in range(n)]
            engine.run_until_idle()
            jax.block_until_ready([r.logits for r in reqs])
            if engine.stats()["batches"] != before + 1 or \
                    any(r.status != "served" for r in reqs):
                raise RuntimeError(f"warm-up batch of {n} was not served as "
                                   f"one batch: {engine.stats()}")


class Tracer:
    """Starts and stops the profiler between steps, inside the window."""

    def __init__(self, jax, on: bool, seconds: float):
        self._jax = jax
        self.on = on
        self.start_at = TRACE_START_FRACTION * seconds
        self.stop_at = self.start_at + min(TRACE_SECONDS, 0.5 * seconds)
        self.dir = None
        self.active = False
        self.done = False

    def tick(self, driver: "Driver", last: bool = False) -> None:
        """Start or stop the profiler when due.  The seconds that takes
        are left out of the run's clock: no request waits for it."""
        if not self.on or self.done:
            return
        now = driver.now()
        t = time.perf_counter()
        if not self.active and now >= self.start_at and not last:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            self._jax.profiler.start_trace(self.dir)
            self.active = True
        elif self.active and (now >= self.stop_at or last):
            self._jax.profiler.stop_trace()
            self.active = False
            self.done = True
        driver.t0 += time.perf_counter() - t


def drive_open(driver: Driver, tracer: Tracer, mix: dict, seed: int,
               seconds: float) -> Window:
    due = traffic_lib.open_schedule(seed, float(mix["rate_rps"]), seconds)
    n_img = int(mix["images"])
    recs = [Rec(i, traffic_lib.image_of(i, n_img), float(d))
            for i, d in enumerate(due)]
    steps, traced, late = [], [], []
    nxt, idled = 0, False
    depth: tuple[list, list] = ([], [])
    while nxt < len(recs) or driver.inflight:
        now = driver.now()
        first = True
        while nxt < len(recs) and recs[nxt].due <= now:
            driver.submit(recs[nxt])
            if idled and first:
                late.append(recs[nxt].submit - recs[nxt].due)
            first = False
            nxt += 1
        idled = False
        if driver.inflight:
            if now < seconds:
                depth[now >= 0.5 * seconds].append(driver.engine.n_pending)
            tracer.tick(driver)
            t, ready, done = driver.step()
            steps.append((t, ready, len(done)))
            if tracer.active:
                traced.append(steps[-1])
        elif nxt < len(recs):
            driver.idle_until(recs[nxt].due)
            idled = True
    tracer.tick(driver, last=True)
    served = sum(r.status == "served" and r.ready <= seconds for r in recs)
    return Window(recs, seconds, served, steps, traced, late,
                  tuple(float(np.mean(d)) if d else 0.0 for d in depth))


def drive_closed(driver: Driver, tracer: Tracer, mix: dict, seed: int,
                 seconds: float) -> Window:
    """``clients`` phones, each sending its next image when its reply is
    ready, until the window closes; replies still in flight then are
    drained.  The seed orders the images."""
    n_img = int(mix["images"])
    order = traffic_lib.rng(seed, 1).permutation(n_img)
    recs: list[Rec] = []

    def send(due: float) -> None:
        recs.append(Rec(len(recs), int(order[len(recs) % n_img]), due))
        driver.submit(recs[-1])

    for _ in range(int(mix["clients"])):
        send(driver.now())
    steps, traced = [], []
    while driver.inflight:
        tracer.tick(driver)
        t, ready, done = driver.step()
        steps.append((t, ready, len(done)))
        if tracer.active:
            traced.append(steps[-1])
        for _ in done:
            if ready < seconds:
                send(ready)
    tracer.tick(driver, last=True)
    served = sum(r.status == "served" and r.ready <= seconds for r in recs)
    return Window(recs, seconds, served, steps, traced, [])


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------
# The numbers a configuration may compare (its ``check.limits`` names
# them), each over the per-request gaps of the sampled served requests.
GAP_NUMBERS = {
    "logit_rel_err": np.max,        # the widest gap of any request
    "logit_rel_err_mean": np.mean,  # the mean gap over the requests
}


def rel_err(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per row: max |got - want| / max |want|."""
    got = np.asarray(got, np.float64).reshape(len(got), -1)
    want = np.asarray(want, np.float64).reshape(len(want), -1)
    scale = np.maximum(np.max(np.abs(want), axis=1), 1e-30)
    return np.max(np.abs(got - want), axis=1) / scale


def served_boundaries(req, wires: tuple[str, ...]) -> tuple[tuple, tuple]:
    """(cuts, wire format per cut) the request finished under."""
    res = req.result
    hops = [h for h in range(len(wires)) if h not in res.merged_hops]
    if len(hops) != len(res.cuts):
        raise RuntimeError(f"request {req.rid}: {len(res.cuts)} cuts but "
                           f"{len(hops)} surviving hops")
    return tuple(res.cuts), tuple(wires[h] for h in hops)


def reference_logits(layers, params, images, jobs, mode="highest"):
    """Reference logits for ``jobs`` = [(image index, cuts, wires)],
    grouped by boundaries and run in blocks."""
    import jax
    import jax.numpy as jnp
    out = [None] * len(jobs)
    groups: dict[tuple, list[int]] = {}
    for j, (_, cuts, wires) in enumerate(jobs):
        groups.setdefault((cuts, wires), []).append(j)
    for (cuts, wires), idx in groups.items():
        for b in range(0, len(idx), REFERENCE_BLOCK):
            block = idx[b:b + REFERENCE_BLOCK]
            pad = block + [block[-1]] * (REFERENCE_BLOCK - len(block))
            x = jnp.stack([images[jobs[j][0]] for j in pad])
            y = np.asarray(jax.block_until_ready(reference.forward(
                layers, params, x, cuts=cuts, wires=wires, mode=mode)))
            for k, j in enumerate(block):
                out[j] = y[k]
    return np.stack(out)


def sample_served(recs: list[Rec], seed: int, k: int) -> list[Rec]:
    served = [r for r in recs if r.status == "served"]
    if len(served) <= k:
        return served
    pick = traffic_lib.rng(seed, 2).choice(len(served), size=k,
                                           replace=False)
    return [served[i] for i in sorted(pick)]


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
def open_session(jax, cell: spec_lib.Cell, platform: str, root: str):
    """Device check, compile cache, matmul precision and the compile
    counter; returns (devices, peaks row or None, counter)."""
    devices = require_devices(jax, cell.chips, platform)
    dev = devices[0]
    _log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
         f"jax {jax.__version__}")
    peak = peaks.peaks_for(dev.device_kind) if platform == "tpu" else None
    _log(f"compile cache: {enable_compile_cache(jax, root)}")
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    return devices, peak, compiles


def build(jax, cfg: dict, mix: dict, seed: int):
    """Weights and images from the seed, and the warmed-up engine."""
    in_shape = tuple(cfg["in_shape"])
    t = time.perf_counter()
    params = jax.block_until_ready(
        weights.make_params(seed, cfg["layers"], in_shape))
    t_weights = time.perf_counter() - t
    t = time.perf_counter()
    images = jax.block_until_ready(
        weights.make_images(seed, int(mix["images"]), in_shape))
    t_images = time.perf_counter() - t
    t = time.perf_counter()
    engine = build_engine(cfg, params)
    warm_up(jax, engine, images, int(cfg["max_batch"]))
    _log(f"weights {t_weights:.3f}s, images {t_images:.3f}s, engine "
         f"build+warm-up {time.perf_counter() - t:.3f}s")
    return params, images, engine


def drive(jax, engine, images, mix: dict, seed: int, seconds: float,
          trace: bool):
    """The measured window; returns (Window, Trace or None)."""
    driver = Driver(jax, engine, images, time.perf_counter())
    tracer = Tracer(jax, trace, seconds)
    loop = drive_closed if mix["loop"] == "closed" else drive_open
    win = loop(driver, tracer, mix, seed, seconds)
    trace_data = None
    if tracer.dir is not None:
        try:
            trace_data = tracefile.load(tracefile.find_xplane(tracer.dir))
        finally:
            shutil.rmtree(tracer.dir, ignore_errors=True)
    return win, trace_data


def collect(win: Window, cfg: dict, seed: int):
    """The sampled served requests' logits on the host and what the
    reference needs for them; drops the window's hold on device arrays.
    Returns (logits, jobs, unanswered)."""
    wires = wire_formats(cfg, int(cfg["chain_tiers"]) - 1)
    sample = sample_served(win.recs, seed, int(cfg["check"]["samples"]))
    got = np.stack([np.asarray(r.req.logits) for r in sample]) \
        if sample else np.zeros((0,))
    jobs = [(r.image, *served_boundaries(r.req, wires)) for r in sample]
    unanswered = sum(r.status != "served" for r in win.recs)
    for r in win.recs:
        r.req = None
    return got, jobs, unanswered


def logit_gaps(cfg: dict, params, images, got, jobs,
               mode: str = "highest") -> np.ndarray | None:
    """Each compared request's relative logit gap from the reference
    (``rel_err``); None where there is nothing finite to compare."""
    if not len(jobs) or not np.all(np.isfinite(got)):
        return None
    return rel_err(got, reference_logits(cfg["layers"], params, images,
                                         jobs, mode))


def gap_checks(cfg: dict, gaps: np.ndarray | None) -> dict:
    """The configuration's compared numbers, each beside its limit; a run
    with nothing to compare reads inf."""
    return {name: {"value": math.inf if gaps is None
                   else float(GAP_NUMBERS[name](gaps)),
                   "limit": float(limit)}
            for name, limit in cfg["check"]["limits"].items()}


def run_cell(cell: spec_lib.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, platform: str = "tpu",
             root: str = spec_lib.ROOT) -> dict:
    import jax
    devices, peak, compiles = open_session(jax, cell, platform, root)
    dev = devices[0]
    _log(f"imports and device: {time.perf_counter() - t_start:.3f}s")
    cfg, mix = cell.config, cell.traffic
    try:
        params, images, engine = build(jax, cfg, mix, seed)
        before = engine.stats()
        compiles_setup = compiles.count
        setup_s = time.perf_counter() - t_start
        _log(f"set-up {setup_s:.3f}s with {compiles_setup} executables "
             f"built or loaded; traffic: "
             f"{traffic_lib.describe(mix, seconds)}")
        win, trace_data = drive(jax, engine, images, mix, seed, seconds,
                                trace)
        in_window = compiles.count - compiles_setup
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    after = engine.stats()
    mem_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    cuts = tuple(after["buckets"][0]["cuts"])
    _log(f"window {win.seconds:.3f}s: {len(win.recs)} requests, "
         f"{win.served_in_window} served in it, {len(win.steps)} steps; "
         f"{in_window} executables built or loaded in the window; "
         f"peak device memory {mem_peak} bytes")
    if win.generator_late_s:
        late = np.asarray(win.generator_late_s) * 1e3
        _log(f"generator lateness after idle waits: p50 "
             f"{np.percentile(late, 50):.3f} ms, p99 "
             f"{np.percentile(late, 99):.3f} ms, max {late.max():.3f} ms")
    _log(f"modelled (virtual clock, not a metric): cuts {list(cuts)}, "
         f"latency_p50_s {after['latency_p50_s']:.6f}, latency_p99_s "
         f"{after['latency_p99_s']:.6f}, requests_per_s "
         f"{after['requests_per_s']:.3f}; hops "
         f"{[(h['wire_dtype'], h['wire_bytes']) for h in after['hops']]}")

    layers, in_shape = cfg["layers"], tuple(cfg["in_shape"])
    elem = 4 if cfg["storage_dtype"] == "fp32" else 2
    ctx = {
        "cell": cell.name, "config": cfg, "peak": peak,
        "setup_s": setup_s, "window": win,
        "trace": trace_data, "cuts": cuts,
        "flops_per_request": flops.model_flops(layers, in_shape),
        "conv_launches": flops.conv_launches(layers, in_shape, elem, cuts),
        # the engine's own counters over the window and its drain
        "engine_window": {k: after[k] - before[k]
                          for k in ("served", "batches")},
        # the engine's whole stats() at the window's start and after its
        # drain, for a reader of any other counter
        "engine_stats": {"before": before, "after": after},
    }
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec_lib.load_reader(m.name)(ctx)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    breakdown = None
    if trace_data is not None:
        w = tracefile.window(trace_data)
        device["busy_s"] = tracefile.busy_ns(trace_data) / 1e9
        device["window_s"] = (w[1] - w[0]) / 1e9 if w else 0.0
        breakdown = {"device_ops": tracefile.top_ops(trace_data),
                     "idle_gaps": tracefile.idle_by_host_span(trace_data)}

    # correctness, once the window is closed and the peak is read
    got, jobs, unanswered = collect(win, cfg, seed)
    del engine
    gc.collect()
    t = time.perf_counter()
    gaps = logit_gaps(cfg, params, images, got, jobs)
    _log(f"reference: {len(jobs)} served requests compared in "
         f"{time.perf_counter() - t:.3f}s")
    checks = gap_checks(cfg, gaps)
    checks["unanswered"] = {"value": unanswered, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": len(win.recs),
              "failed": unanswered, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv: list[str], t_start: float) -> int:
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not args.seconds > 0:
        ap.error("--seconds must be positive")
    cell = spec_lib.resolve(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
