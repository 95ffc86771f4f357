"""Int8 quantized boundary streaming: wire-dtype policy resolution,
quantize/dequantize codec, multipart framing, wire-aware cost pricing and
planning, and the runtime end-to-end paths.

Two invariants anchor everything:

* ``follow``/fp32/bf16 wire formats are *bit-identical* to the legacy
  serialisation (the wire tier must be invisible until asked for), and
* the fault-free runtime int8 path decodes to exactly
  ``apply_split(..., wire="int8")`` -- the codec has one reference
  implementation (``kernels.quant.boundary_roundtrip``) and every layer
  agrees with it bitwise.

Randomised round-trip bounds live in tests/test_wire_quant_properties.py
(hypothesis, dev-only dep)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (PAPER_ENV_J6, latency_terms, paper_chain,
                        smartsplit_chain, smartsplit_exhaustive)
from repro.core.costs import (INT8_FRAME_OVERHEAD_BYTES, WIRE_SCALE_BYTES,
                              total_latency)
from repro.core.dtype_policy import resolve_wire_dtype, wire_dtype
from repro.kernels.quant import (boundary_roundtrip, default_channel_axis,
                                 dequantize_boundary, dequantize_jnp,
                                 quantize_boundary, quantize_jnp,
                                 scale_count)
from repro.models import cnn as cnn_lib
from repro.models.cnn import avgpool, conv, linear, maxpool, relu
from repro.models.profiles import cnn_profile
from repro.runtime import (ChainRuntime, FaultSpec, FaultyLink, FrameError,
                           SplitRuntime, TransferFailed, decode_boundary,
                           encode_boundary, events, pack_frames,
                           send_with_retry, unpack_frames)
from repro.runtime.wire import finish_boundary, queue_boundary

TINY_LAYERS = [conv(8, 3, 1, 1), relu(), maxpool(2, 2),
               conv(16, 3, 1, 1), relu(), avgpool(2), linear(10)]
TINY_SHAPE = (3, 16, 16)


@pytest.fixture(scope="module")
def tiny():
    params = cnn_lib.init_cnn(jax.random.PRNGKey(0), TINY_LAYERS,
                              TINY_SHAPE)
    rng = np.random.default_rng(0)
    x = np.asarray(rng.normal(size=(2,) + TINY_SHAPE), np.float32)
    return params, x


def _plan(wire=None):
    prof = cnn_profile("tiny", in_shape=TINY_SHAPE, layers=TINY_LAYERS)
    return prof, smartsplit_exhaustive(prof, PAPER_ENV_J6, wire=wire)


# ---------------------------------------------------------------------------
# Wire-dtype policy resolution
# ---------------------------------------------------------------------------
def test_wire_policy_default_follows_storage(monkeypatch):
    monkeypatch.delenv("REPRO_WIRE_DTYPE", raising=False)
    assert wire_dtype() == "follow"
    assert resolve_wire_dtype(None, storage="fp32") == "fp32"
    assert resolve_wire_dtype(None, storage="bf16") == "bf16"
    assert resolve_wire_dtype("follow", storage="bf16") == "bf16"
    assert resolve_wire_dtype("int8", storage="bf16") == "int8"


def test_wire_policy_env_and_per_hop_override(monkeypatch):
    monkeypatch.setenv("REPRO_WIRE_DTYPE", "int8")
    assert resolve_wire_dtype(None, storage="fp32") == "int8"
    # per-hop env beats the chain-wide env; explicit arg beats both
    monkeypatch.setenv("REPRO_LINK1_WIRE_DTYPE", "fp32")
    assert resolve_wire_dtype(None, storage="fp32", hop=1) == "fp32"
    assert resolve_wire_dtype(None, storage="fp32", hop=0) == "int8"
    assert resolve_wire_dtype("bf16", storage="fp32", hop=1) == "bf16"


def test_wire_policy_rejects_unknown(monkeypatch):
    with pytest.raises(ValueError, match="wire argument"):
        wire_dtype("int4")
    monkeypatch.setenv("REPRO_WIRE_DTYPE", "fp8")
    with pytest.raises(ValueError, match="REPRO_WIRE_DTYPE"):
        wire_dtype()


# ---------------------------------------------------------------------------
# Quantize/dequantize codec
# ---------------------------------------------------------------------------
def test_quantize_roundtrip_bounds_and_grid():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 5, 4, 4)), jnp.float32) * 3.0
    q, scales = quantize_boundary(x)
    assert q.dtype == jnp.int8 and scales.shape == (5,)
    assert int(jnp.max(jnp.abs(q))) <= 127
    y = dequantize_boundary(q, scales, out_dtype=jnp.float32)
    # error bound: half a quantization step per channel
    err = np.max(np.abs(np.asarray(y - x)), axis=(0, 2, 3))
    assert np.all(err <= np.asarray(scales) / 2 + 1e-7)


def test_quantize_zero_channel_is_safe():
    x = jnp.zeros((1, 3, 2, 2), jnp.float32)
    q, scales = quantize_boundary(x)
    np.testing.assert_array_equal(np.asarray(scales), np.ones(3))
    np.testing.assert_array_equal(np.asarray(q), 0)
    np.testing.assert_array_equal(
        np.asarray(dequantize_boundary(q, scales)), 0.0)


def test_channel_convention_matches_ndim():
    assert default_channel_axis(4) == 1
    assert default_channel_axis(3) == 1
    assert default_channel_axis(2) is None
    assert scale_count((2, 5, 4, 4), 1) == 5
    assert scale_count((2, 4096), None) == 1
    # flat boundary quantizes per-tensor: one scale
    flat = jnp.asarray(np.random.default_rng(0).normal(size=(2, 64)),
                       jnp.float32)
    _, scales = quantize_boundary(flat)
    assert scales.shape == (1,)


def test_pallas_and_jnp_backends_agree_bitwise():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(1, 6, 8, 8)), jnp.float32)
    qp, sp = quantize_boundary(x, backend="pallas")
    qj, sj = quantize_jnp(x, axis=1)
    np.testing.assert_array_equal(np.asarray(qp), np.asarray(qj))
    np.testing.assert_array_equal(np.asarray(sp), np.asarray(sj))
    yp = dequantize_boundary(qp, sp, backend="pallas")
    yj = dequantize_jnp(qj, sj, axis=1)
    np.testing.assert_array_equal(np.asarray(yp), np.asarray(yj))


def test_float_wire_roundtrip_identity():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(1, 4, 6, 6)), jnp.float32)
    np.testing.assert_array_equal(np.asarray(boundary_roundtrip(x, "fp32")),
                                  np.asarray(x))
    xb = x.astype(jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(boundary_roundtrip(xb, "bf16").astype(jnp.float32)),
        np.asarray(xb.astype(jnp.float32)))


# ---------------------------------------------------------------------------
# Multipart framing
# ---------------------------------------------------------------------------
def test_pack_unpack_frames_roundtrip():
    parts = (b"scales-bytes", b"payload" * 100, b"")
    got = unpack_frames(pack_frames(*parts), ("a", "b", "c"))
    assert tuple(got) == parts


def test_unpack_frames_localises_corruption():
    buf = bytearray(pack_frames(b"S" * 16, b"D" * 64))
    # flip one byte inside the second part's data
    buf[-1] ^= 0xFF
    with pytest.raises(FrameError) as ei:
        unpack_frames(bytes(buf), ("scales", "data"))
    assert ei.value.part == "data"
    # flip inside the first part
    buf2 = bytearray(pack_frames(b"S" * 16, b"D" * 64))
    buf2[13] ^= 0x01
    with pytest.raises(FrameError) as ei:
        unpack_frames(bytes(buf2), ("scales", "data"))
    assert ei.value.part == "scales"
    # structural damage: wrong part count
    buf3 = bytearray(pack_frames(b"S", b"D"))
    buf3[0] = 9
    with pytest.raises(FrameError) as ei:
        unpack_frames(bytes(buf3), ("scales", "data"))
    assert ei.value.part == "header"


def test_send_with_retry_framed_corruption_sets_part():
    payload = pack_frames(b"S" * 8, b"D" * 128)
    link = FaultyLink(1e6, faults=FaultSpec(corrupt_rate=1.0), seed=0)
    log = events.EventLog()
    with pytest.raises(TransferFailed):
        send_with_retry(link, payload, log=log,
                        framed=("scales", "data"))
    fails = [e for e in log.events if e.kind == events.CHECKSUM_FAIL]
    assert fails and all(e.detail["part"] in ("scales", "data", "header")
                         for e in fails)


# ---------------------------------------------------------------------------
# Boundary codec == reference roundtrip, and the raw path == legacy bytes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("wire", ["fp32", "bf16", "int8"])
def test_encode_decode_matches_boundary_roundtrip(wire):
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 6, 5, 5)), jnp.float32)
    payload, meta = encode_boundary(x, wire)
    got = decode_boundary(payload, meta)
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(boundary_roundtrip(x, wire)))
    assert meta.raw_bytes == x.size * 4
    if wire == "int8":
        assert meta.framed == ("scales", "data")
        assert len(payload) == x.size + WIRE_SCALE_BYTES * x.shape[1] \
            + INT8_FRAME_OVERHEAD_BYTES
    else:
        assert meta.framed is None


@pytest.mark.parametrize("wire", ["fp32", "bf16", "int8"])
def test_boundary_halves_match_encode(wire):
    """``queue_boundary`` then ``finish_boundary``, with other device work
    queued between them, give ``encode_boundary``'s bytes and meta."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(2, 6, 5, 5)), jnp.float32)
    queued = queue_boundary(x, wire)
    behind = jnp.tanh(x) * 2.0
    assert finish_boundary(*queued) == encode_boundary(x, wire)
    behind.block_until_ready()


def test_raw_wire_path_is_legacy_bytes():
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(1, 4, 5, 5)), jnp.float32)
    payload, _ = encode_boundary(x, "fp32")
    assert payload == SplitRuntime._serialize(x)[0]


# ---------------------------------------------------------------------------
# Cost model pricing
# ---------------------------------------------------------------------------
def test_wire_boundary_pricing():
    prof = cnn_profile("alexnet")
    b = prof.boundary()
    live = b > 0
    # follow == storage: unchanged, exactly
    np.testing.assert_array_equal(prof.wire_boundary("follow"), b)
    np.testing.assert_array_equal(prof.wire_boundary("fp32"), b)
    np.testing.assert_array_equal(prof.wire_boundary("bf16")[live],
                                  b[live] / 2)
    wb8 = prof.wire_boundary("int8")
    elems = b[live] / 4
    expect = elems + WIRE_SCALE_BYTES * prof.boundary_groups()[live] \
        + INT8_FRAME_OVERHEAD_BYTES
    np.testing.assert_allclose(wb8[live], expect)
    assert np.all(wb8[~live] == 0)
    # the paper-split acceptance ratio: >= 3.5x on every live split
    assert np.min(b[live] / wb8[live]) >= 3.5


def test_int8_wire_shrinks_upload_latency():
    prof = cnn_profile("alexnet")
    t_up32 = latency_terms(prof, PAPER_ENV_J6, wire="fp32")[1]
    t_up8 = latency_terms(prof, PAPER_ENV_J6, wire="int8")[1]
    live = prof.boundary() > 0
    assert np.all(t_up8[live] < t_up32[live])
    # but total latency never ignores the codec surcharge entirely
    assert np.all(total_latency(prof, PAPER_ENV_J6, wire="int8") > 0)


def test_planner_is_wire_aware():
    prof = cnn_profile("alexnet")
    p32 = smartsplit_exhaustive(prof, PAPER_ENV_J6, wire="fp32")
    p8 = smartsplit_exhaustive(prof, PAPER_ENV_J6, wire="int8")
    # int8 pricing can only improve the latency objective at a given split
    assert p8.objectives[0] <= p32.objectives[0] + 1e-12
    chain = smartsplit_chain(prof, paper_chain(2), wire="int8")
    assert chain.wire_dtypes == ("int8",)
    follow = smartsplit_chain(prof, paper_chain(2))
    assert follow.wire_dtypes == ("fp32",)


# ---------------------------------------------------------------------------
# Runtime end to end
# ---------------------------------------------------------------------------
def test_split_runtime_int8_matches_reference(tiny):
    params, x = tiny
    prof, plan = _plan(wire="int8")
    rt = SplitRuntime(TINY_LAYERS, params, plan, prof, PAPER_ENV_J6,
                      wire="int8")
    r = rt.infer(x)
    want, _ = cnn_lib.apply_split(TINY_LAYERS, params, x,
                                  plan.split_index, wire="int8")
    np.testing.assert_array_equal(np.asarray(r.logits), np.asarray(want))
    h = rt.stats()["hops"][0]
    assert h["wire_dtype"] == "int8"
    assert h["raw_bytes"] > 0 and h["wire_bytes"] < h["raw_bytes"]
    assert rt.log.count(events.WIRE_ENCODE) == 1


@pytest.mark.parametrize("wire", [None, "follow", "fp32"])
def test_split_runtime_float_wire_bit_identical_to_legacy(tiny, wire):
    params, x = tiny
    prof, plan = _plan()
    legacy = SplitRuntime(TINY_LAYERS, params, plan, prof, PAPER_ENV_J6)
    got = SplitRuntime(TINY_LAYERS, params, plan, prof, PAPER_ENV_J6,
                       wire=wire)
    rl, rg = legacy.infer(x), got.infer(x)
    np.testing.assert_array_equal(np.asarray(rl.logits),
                                  np.asarray(rg.logits))
    assert got.log.count(events.WIRE_ENCODE) == 0
    assert legacy.stats()["hops"][0]["wire_bytes"] \
        == got.stats()["hops"][0]["wire_bytes"]


def test_split_runtime_int8_recovers_from_corruption(tiny):
    params, x = tiny
    prof, plan = _plan(wire="int8")
    link = FaultyLink(PAPER_ENV_J6.link.bandwidth,
                      faults=FaultSpec(corrupt_rate=0.5), seed=2)
    rt = SplitRuntime(TINY_LAYERS, params, plan, prof, PAPER_ENV_J6,
                      link=link, wire="int8")
    want, _ = cnn_lib.apply_split(TINY_LAYERS, params, x,
                                  plan.split_index, wire="int8")
    for _ in range(4):
        r = rt.infer(x)
        np.testing.assert_array_equal(np.asarray(r.logits),
                                      np.asarray(want))
    fails = [e for e in rt.log.events if e.kind == events.CHECKSUM_FAIL]
    assert fails  # seed 2 at 50% corrupt must hit at least once
    assert all(e.detail.get("part") in ("scales", "data", "header")
               for e in fails)


def test_chain_runtime_per_hop_wire(tiny):
    params, x = tiny
    prof = cnn_profile("tiny", in_shape=TINY_SHAPE, layers=TINY_LAYERS)
    hw = paper_chain(3)
    plan = smartsplit_chain(prof, hw, wire=("int8", "fp32"))
    assert plan.wire_dtypes == ("int8", "fp32")
    rt = ChainRuntime(TINY_LAYERS, params, plan, prof, hw)
    assert rt.wire_dtypes == ("int8", "fp32")
    r = rt.infer(x)
    # hop0 re-encodes int8, hop1 ships storage fp32 raw: the reference
    # walk round-trips the boundary only at the int8 hop
    h = cnn_lib.apply_cnn(TINY_LAYERS, params, x, stop=plan.cuts[0])
    h = boundary_roundtrip(h, "int8")
    h = cnn_lib.apply_cnn(TINY_LAYERS, params, h, start=plan.cuts[0],
                          stop=plan.cuts[1])
    want = cnn_lib.apply_cnn(TINY_LAYERS, params, h, start=plan.cuts[1])
    np.testing.assert_array_equal(np.asarray(r.logits), np.asarray(want))
    hops = rt.stats()["hops"]
    assert [h["wire_dtype"] for h in hops] == ["int8", "fp32"]
    assert hops[0]["wire_bytes"] < hops[0]["raw_bytes"]
    assert hops[1]["wire_bytes"] == hops[1]["raw_bytes"] \
        + 8 * hops[1]["attempts"]  # outer frame header per attempt
