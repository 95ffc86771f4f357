"""The program's spans: their reduction on a small recorded trace with known
numbers (``data/trace_spans.pbtxt``), the spans a served batch writes on
the CPU, and one traced window of a tiny cell through
``bench/idle_by_span.py``'s path."""
import math
import os

import numpy as np
import pytest

from bench import spans, tracefile

DATA = os.path.join(os.path.dirname(__file__), "data")
SPANS_TRACE = os.path.join(DATA, "trace_spans.pbtxt")
SMALL_TRACE = os.path.join(DATA, "trace_small.pbtxt")
WINDOW_NS = 40_000.0


@pytest.fixture(scope="module")
def recorded():
    return spans.load(SPANS_TRACE)


def test_names_match_the_program():
    """A span renamed in the program breaks this test, not the benchmark."""
    from repro.runtime import events
    assert spans.PROGRAM_SPANS == events.SPANS
    assert sorted(n for names in spans.LAYERS.values() for n in names) == \
        sorted(spans.PROGRAM_SPANS)


def test_program_spans_are_read_beside_the_harness_spans(recorded):
    trace, program = recorded
    assert tracefile.window(trace) == (1_010_000.0, 1_050_000.0)
    assert [s.name for s in trace.spans] == [
        "step", "wait_logits", "idle_no_request", "step"]
    assert len(program) == 16
    # the span with a kwarg stat keeps its bare name
    assert [s.name for s in program].count("engine.step") == 3
    assert program[0].end_ns <= tracefile.window(trace)[0]


def test_self_time_leaves_out_child_spans(recorded):
    """``wire.encode`` lasts 5000 ns, 2000 of them in ``wire.sync``; the
    engine.step before the window counts nowhere."""
    assert spans.self_ns(*recorded) == {
        "engine.step": 4000.0, "chain.infer": 4000.0,
        "chain.stage": 13000.0, "wire.sync": 2000.0,
        "wire.encode": 3000.0, "wire.send": 2000.0, "wire.decode": 2000.0}


def test_idle_goes_to_the_innermost_span(recorded):
    assert spans.idle_ns(*recorded) == {
        "engine.step": 4000.0, "chain.infer": 4000.0,
        "chain.stage": 5500.0, "wire.sync": 500.0, "wire.encode": 3000.0,
        "wire.send": 2000.0, "wire.decode": 2000.0}


def test_layer_shares_and_codec_ms(recorded):
    trace, program = recorded
    assert spans.idle_share(trace, program, "walk") == \
        pytest.approx(100 * 5500 / WINDOW_NS)
    assert spans.idle_share(trace, program, "runtime") == \
        pytest.approx(100 * 11500 / WINDOW_NS)
    assert spans.idle_share(trace, program, "engine") == \
        pytest.approx(100 * 4000 / WINDOW_NS)
    # encode's 3000 ns of self time + send 2000 + decode 2000, 2 requests
    assert spans.codec_ms(trace, program, served=2) == pytest.approx(3.5e-3)
    assert spans.codec_ms(trace, program, served=0) is None


def test_layers_add_up_to_the_idle_time_under_step(recorded):
    trace, program = recorded
    step_ns = dict(tracefile.idle_by_host_span(trace))["step"] * 1e9
    shares = [spans.idle_share(trace, program, layer)
              for layer in spans.LAYERS]
    assert sum(shares) == pytest.approx(100 * step_ns / WINDOW_NS)
    assert step_ns == pytest.approx(21_000.0)


def test_nothing_is_read_from_a_trace_without_program_spans():
    trace, program = spans.load(SMALL_TRACE)
    assert program == [] and len(trace.spans) == 5
    for layer in spans.LAYERS:
        assert spans.idle_share(trace, program, layer) is None
    assert spans.codec_ms(trace, program, served=2) is None


def test_innermost_pieces_partition_what_the_spans_cover(recorded):
    trace, program = recorded
    pieces = spans.innermost(program, *tracefile.window(trace))
    assert all(a < b for a, b, _ in pieces)
    assert all(p[1] <= q[0] for p, q in zip(pieces, pieces[1:]))
    assert sum(b - a for a, b, _ in pieces) == 30_000.0   # two engine.steps


# ---------------------------------------------------------------------------
# The spans the serving path writes
# ---------------------------------------------------------------------------
CONV = {"kind": "conv", "cout": 8, "ksize": 3, "pad": 1}
SMALL_CNN = [CONV, {"kind": "relu"}, CONV, {"kind": "relu"},
             {"kind": "maxpool", "ksize": 2, "stride": 2},
             dict(CONV, cout=16), {"kind": "relu"},
             {"kind": "maxpool", "ksize": 2, "stride": 2},
             {"kind": "avgpool", "out_hw": 2},
             {"kind": "linear", "features": 32}, {"kind": "relu"},
             {"kind": "linear", "features": 10}]


def _inside(child, parent) -> bool:
    return parent.start_ns <= child.start_ns and \
        child.end_ns <= parent.end_ns


def test_served_batches_write_the_program_spans(tmp_path):
    """A 3-tier chain with the int8 wire on its second hop, on the XLA
    backend: one engine.step per batch, three stages and two hops per
    request, each span inside its parent."""
    import jax
    from repro.core.hardware import paper_chain
    from repro.models import cnn
    from repro.serving.cnn_engine import CnnServingEngine
    layers = [cnn.Layer(**layer) for layer in SMALL_CNN]
    params = cnn.init_cnn(jax.random.PRNGKey(0), layers, (3, 16, 16))
    engine = CnnServingEngine({"small": (layers, params)},
                              hw=paper_chain(3), max_batch=2,
                              dtype="fp32", wire=("fp32", "int8"),
                              backend="xla")
    images = np.random.default_rng(0).normal(size=(5, 3, 16, 16)) \
        .astype(np.float32)
    warm = engine.submit(images[0])
    engine.run_until_idle()
    jax.block_until_ready(warm.logits)
    with jax.profiler.trace(str(tmp_path)):
        reqs = [engine.submit(x) for x in images]
        assert all(engine.step() for _ in range(3))    # batches of 2, 2, 1
        jax.block_until_ready([r.logits for r in reqs])
    assert all(r.status == "served" for r in reqs)
    assert len(reqs[0].result.cuts) == 2
    _, program = spans.load(tracefile.find_xplane(str(tmp_path)))
    by_name: dict[str, list] = {}
    for s in program:
        by_name.setdefault(s.name, []).append(s)
    assert len(by_name["engine.step"]) == 3
    assert len(by_name["chain.infer"]) == 3
    assert len(by_name["chain.stage"]) == 3 * len(reqs)
    for name in ("wire.sync", "wire.encode", "wire.send", "wire.decode"):
        assert len(by_name[name]) == 2 * len(reqs), name
    parent_of = {"chain.infer": "engine.step", "chain.stage": "chain.infer",
                 "wire.encode": "chain.infer", "wire.send": "chain.infer",
                 "wire.decode": "chain.infer", "wire.sync": "wire.encode"}
    for name, parent in parent_of.items():
        for s in by_name[name]:
            assert any(_inside(s, p) for p in by_name[parent]), (name, s)


@pytest.fixture
def cpu_session(tmp_path, monkeypatch):
    """JAX's process-wide settings that a cell's set-up changes come back
    afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    names = ("jax_default_matmul_precision", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    yield str(tmp_path)
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_one_traced_window_of_a_tiny_cell(cpu_session):
    """``bench/idle_by_span.py``'s path on the CPU: the CPU trace has no
    chip, so no idle share is read, but the codec ms and the span counts
    per request are."""
    import json
    from bench import spec
    with open(spec.config_path("mbv2-fp32-chain3-int8")) as f:
        cfg = json.load(f)
    cfg.update(in_shape=[3, 16, 16], layers=SMALL_CNN, backend="xla")
    cell = spec.Cell("mbv2-fp32-chain3-int8.tiny", cfg,
                     {"loop": "closed", "clients": 4, "images": 8}, 1,
                     (), ())
    out = spans.measure(cell, 2**31 + 11, 0.8, platform="cpu",
                        root=cpu_session)
    assert out["served_traced"] > 0
    assert {out[f"idle_{layer}"] for layer in spans.LAYERS} == {None}
    assert out["codec_ms"] > 0
    per_req = out["spans_per_request"]
    assert per_req["chain.stage"] == 3.0
    for name in ("wire.sync", "wire.encode", "wire.send", "wire.decode"):
        assert per_req[name] == 2.0
    assert 0 < per_req["engine.step"] <= 1.0
    own = out["self_ms_per_request"]
    assert sum(own[n] for n in spans.CODEC) == pytest.approx(out["codec_ms"])
    assert math.isfinite(out["span_us_off"])
    assert math.isfinite(out["span_us_on"])
    assert out["rps_traced"] > 0 and out["rps_untraced"] > 0
