"""Compile rehearsals: the main path's Pallas kernels at real widths,
compiled for a described (not attached) TPU v5e chip.

Interpret-mode parity says nothing about whether Mosaic accepts a kernel's
blocks, slices and reshapes; these compiles do, at no chip time.  The
topology is described inside a module fixture -- never at import, in a
``skipif`` or in ``parametrize`` -- because only one process may load the
TPU library: under pytest-xdist every worker collects the same tests and
only the worker given this file loads it.  Each compiled program must hold
a Mosaic kernel (``tpu_custom_call``), so a silent fallback cannot pass."""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import conv2d as K
from repro.kernels import quant


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache off here (the
    # reset drops a cache an earlier test in this process already opened)
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    # under the strictest caller setting: a kernel that left its matmul
    # precision to the context would ask for fp32 contractions of bf16
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn).lower(*args).compile().as_text()


# (x shape, w shape, stride, pad, groups, activation, pool_k, pool_s,
#  pinned tile_w) -- paper-model convs at 224 px
_CONVS = {
    "alexnet_conv1_triple": ((1, 3, 224, 224), (64, 3, 11, 11), 4, 2, 1,
                             "relu", 3, 2, 0),
    # VGG16 conv1_2 + pool with both row and column tiles (pinned tile_w)
    "vgg16_conv1_2_tiled": ((1, 64, 224, 224), (64, 64, 3, 3), 1, 1, 1,
                            "relu", 2, 2, 40),
    "vgg16_14x14x512": ((1, 512, 14, 14), (512, 512, 3, 3), 1, 1, 1,
                        "relu", 0, 0, 0),
    "mbv2_dw_s2": ((1, 144, 56, 56), (144, 1, 3, 3), 2, 1, 144, "relu6",
                   0, 0, 0),
    "mbv2_pointwise": ((1, 320, 7, 7), (1280, 320, 1, 1), 1, 0, 1, None,
                       0, 0, 0),
}


@pytest.mark.parametrize("name,dtype", [
    ("alexnet_conv1_triple", jnp.float32),
    ("alexnet_conv1_triple", jnp.bfloat16),
    ("vgg16_conv1_2_tiled", jnp.float32),
    ("vgg16_conv1_2_tiled", jnp.bfloat16),
    ("vgg16_14x14x512", jnp.float32),
    ("mbv2_dw_s2", jnp.float32),
    ("mbv2_dw_s2", jnp.bfloat16),
    ("mbv2_pointwise", jnp.float32),
])
def test_conv2d_compiles_for_v5e(one_chip, name, dtype):
    xs, ws, s, p, g, act, pk, ps, tw = _CONVS[name]
    plan = K.plan_conv(xs, ws, stride=s, pad=p, groups=g, pool_k=pk,
                       pool_s=ps, tile_w=tw,
                       dtype_bytes=jnp.dtype(dtype).itemsize)
    assert plan.vmem_bytes <= K.DEFAULT_VMEM_BUDGET
    if tw:
        assert plan.n_h_blocks > 1 and plan.n_w_blocks > 1
    def fn(x, w, b):
        return K.conv2d(x, w, bias=b, stride=s, pad=p, groups=g,
                        activation=act, pool_k=pk, pool_s=ps, tile_w=tw,
                        interpret=False)

    text = _compiled_text(fn, _sds(xs, dtype, one_chip),
                          _sds(ws, dtype, one_chip),
                          _sds((ws[0],), jnp.float32, one_chip))
    assert "tpu_custom_call" in text


def test_int8_codec_compiles_for_v5e(one_chip):
    """Quantize + dequantize of a VGG16-sized boundary (per channel)."""
    shape, axis = (4, 512, 14, 14), 1
    q = functools.partial(quant._quantize, axis=axis, use_pallas=True,
                          interpret=False)
    x = _sds(shape, jnp.float32, one_chip)
    assert "tpu_custom_call" in _compiled_text(q, x)
    values, scales = jax.eval_shape(q, x)
    dq = functools.partial(quant._dequantize, axis=axis, use_pallas=True,
                           interpret=False, out_dtype=jnp.float32)
    assert "tpu_custom_call" in _compiled_text(
        dq, _sds(values.shape, values.dtype, one_chip),
        _sds(scales.shape, scales.dtype, one_chip))


def test_stage_program_keeps_conv_names_for_v5e(one_chip, monkeypatch):
    """A compiled stage of ``apply_cnn`` keeps every Mosaic conv's
    instruction name ``_conv2d.<n>`` (the jitted wrapper's), which the
    benchmark's ``conv_roofline`` reader matches: MobileNetV2's last seven
    layers at 224 px, thirteen conv launches in one program."""
    import re

    from repro.models import cnn
    # compile the kernels rather than interpret them, and keep this
    # program out of the shared stage cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(cnn, "_WALKS", {})
    layers, start = cnn.MOBILENET_V2, 14
    key = cnn._walk_key(layers, start, len(layers), "pallas", "fp32")
    assert key[5] is False                  # interpret_mode()
    params = jax.eval_shape(
        lambda: cnn.init_cnn(jax.random.PRNGKey(0), layers))[start:]
    x = _sds((1,) + cnn.shapes_through(layers)[start - 1], jnp.float32,
             one_chip)
    with jax.default_matmul_precision("highest"):
        text = cnn._compiled_walk(key).lower(
            jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), params),
            x).compile().as_text()
    names = re.findall(
        r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    launches = sum(1 if l.kind == "conv" else 3 if l.expand != 1 else 2
                   for l in layers[start:] if l.kind in ("conv", "invres"))
    assert launches == 13
    assert len(names) == launches
    assert all(re.fullmatch(r"_conv2d\.\d+", n) for n in names), names
