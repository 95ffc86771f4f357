"""The trace reduction on a small recorded trace with known numbers
(``data/trace_small.pbtxt``), and the per-layer readers that use it."""
import os

import pytest

from bench import flops, spec, tracefile
from bench.harness import Window

TRACE = os.path.join(os.path.dirname(__file__), "data", "trace_small.pbtxt")
PEAK = {"peak_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CONV_OP = ("%_conv2d.1 = f32[1,1,1,56,56,64]{5,4,3,2,1,0} custom-call("
           "%pad_bitcast_fusion, %bitcast.15, %bitcast.16), "
           'custom_call_target="tpu_custom_call"')
FUSION_OP = ("%copy_fusion = f32[1,64,56,56]{3,2,1,0} fusion(%bitcast.9, "
             "%_conv2d.1), kind=kLoop")
QUANT_OP = ("%_quantize.1 = (s8[32,896]{1,0}, f32[32,1]{1,0}) custom-call("
            '%pad.0), custom_call_target="tpu_custom_call"')


@pytest.fixture(scope="module")
def trace():
    return tracefile.load(TRACE)


def test_window_busy_and_op_count(trace):
    assert tracefile.window(trace) == (1_000_000.0, 1_027_000.0)
    assert tracefile.busy_ns(trace) == 11_000.0
    assert tracefile.op_count(trace) == 5        # one op lies past the end
    assert len(trace.devices) == 1 and len(trace.spans) == 5


def test_kernel_time(trace):
    """The conv reader's pattern finds the conv kernel's calls by their
    instruction name, and neither the fusion that reads their output nor
    the codec's kernel."""
    from bench.metrics import conv_roofline
    conv = tracefile.kernel_events(trace, conv_roofline.KERNEL)
    assert [e.dur_ns for e in conv] == [4000.0, 3000.0]
    assert {tracefile.op_name(e) for e in conv} == {"_conv2d.1"}
    quant = tracefile.kernel_events(trace, r"_quantize\.\d+")
    assert [e.dur_ns for e in quant] == [1000.0]


def test_breakdown(trace):
    assert tracefile.top_ops(trace) == [
        ["jit__conv2d/" + CONV_OP, 7e-06], ["jit_stack/fusion.1", 2e-06],
        [FUSION_OP, 1.5e-06], [QUANT_OP, 1e-06]]
    assert tracefile.idle_by_host_span(trace) == [
        ["idle_no_request", 8e-06], ["step", 5e-06], ["wait_logits", 3e-06]]


def _ctx(trace, served=2):
    layers = [{"kind": "conv", "cout": 64, "ksize": 3, "pad": 1},
              {"kind": "relu"}]
    win = Window(recs=[], seconds=1.0, served_in_window=served, steps=[],
                 traced_steps=[(0.0, 0.004, served)], generator_late_s=[])
    return {"trace": trace, "peak": PEAK, "window": win,
            "flops_per_request": flops.model_flops(layers, (64, 56, 56)),
            "conv_launches": flops.conv_launches(layers, (64, 56, 56), 4),
            "engine_window": {"served": 10, "batches": 4}}


def test_readers_on_the_recorded_trace(trace):
    ctx = _ctx(trace)
    read = spec.load_reader
    assert read("ops_per_req.lat")(ctx) == 2.5
    assert read("idle_share.tput")(ctx) == pytest.approx(
        100 * 16_000 / 27_000)
    launch = ctx["conv_launches"][0]
    least = max(launch["flops"] / 197e12, launch["bytes"] / 819e9)
    assert read("conv_roofline.lat")(ctx) == pytest.approx(
        100 * 2 * least / 7e-6)
    assert read("mfu.tput")(ctx) == pytest.approx(
        100 * 2 * ctx["flops_per_request"] / 0.004 / 197e12)
    assert read("batch_avg.tput")(ctx) == 2.5
    assert read("throughput_rps")(ctx) == 2.0     # 2 served in 1 s


def test_conv_roofline_is_not_read_when_calls_do_not_add_up(trace):
    assert spec.load_reader("conv_roofline.lat")(_ctx(trace, served=3)) \
        is None
    assert spec.load_reader("conv_roofline.lat")(
        dict(_ctx(trace), trace=None)) is None


NEW_SPAN_TRACE = """
planes {
  id: 1
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 500000 }
    events { metadata_id: 4 offset_ps: 7000000 duration_ps: 500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "step" } }
  event_metadata { key: 2 value { id: 2 name: "cache.lookup" } }
  event_metadata { key: 3 value { id: 3 name: "Cache.Lookup" } }
  event_metadata { key: 4 value { id: 4 name: "lookup" } }
}
"""

LOOKUP_READER = """
def read(ctx):
    found = [s for s in ctx["trace"].program if s.name == "cache.lookup"]
    return sum(s.dur_ns for s in found) / 1e6 if found else None
"""


def test_a_new_program_span_reaches_a_new_reader(tmp_path):
    """A span the program does not have yet, named in the program's span
    form, reaches a reader file that only a later change would add; names
    of another form stay out of ``Trace.program``."""
    path = tmp_path / "new_span.pbtxt"
    path.write_text(NEW_SPAN_TRACE)
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "lookup_ms.py").write_text(LOOKUP_READER)
    trace = tracefile.load(str(path))
    assert [s.name for s in trace.spans] == ["step"]
    assert [(s.name, s.start_ns) for s in trace.program] == [
        ("cache.lookup", 2000.0), ("cache.lookup", 7000.0)]
    read = spec.load_reader("lookup_ms.lat", bench_dir=str(tmp_path))
    assert read({"trace": trace}) == pytest.approx(2.5e-3)
    # the harness's spans alone: the reader finds nothing and says so
    assert read({"trace": tracefile.load(TRACE)}) is None
