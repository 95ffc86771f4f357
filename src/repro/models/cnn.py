"""The paper's CNN models as splittable JAX networks.

Layer granularity matches the paper: one entry per *PyTorch module*, which is
how the paper counts layers (AlexNet 21, VGG11 29, VGG13 33, VGG16 39,
MobileNetV2 21 -- verified against torchvision's module lists).  Each layer
knows how to (a) infer its output shape, (b) init parameters, (c) apply, and
(d) report analytic FLOPs/params so `models/profiles.py` can build the
``ModelProfile`` the optimiser consumes.

Tensors are NCHW, fp32 (PyTorch-for-Android runs fp32; the paper stresses it
does not quantise).  ``apply_split`` executes the network with an explicit
client/server handoff, returning the boundary payload -- the runtime used by
the split-execution tests and the serving example."""
from __future__ import annotations

import dataclasses
import functools
import math
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# Storage-dtype policy (re-exported: models-layer callers resolve the
# policy through cnn.* like they resolve conv_backend).
from repro.core.dtype_policy import CONV_DTYPES as CONV_DTYPES
from repro.core.dtype_policy import conv_dtype as conv_dtype
from repro.core.dtype_policy import dtype_bytes as dtype_bytes
from repro.core.dtype_policy import policy_jnp_dtype as policy_jnp_dtype

CONV_BACKENDS = ("xla", "pallas")


def conv_backend(backend: str | None = None) -> str:
    """Resolve the conv execution backend.

    ``xla`` (default) keeps the seed's bit-exact ``lax.conv_general_dilated``
    path; ``pallas`` routes conv(+bias)(+relu/relu6) pairs through the fused
    spatially-tiled kernel in ``repro.kernels.conv2d``.  Overridable per
    call, else by env ``REPRO_CONV_BACKEND``."""
    b = backend or os.environ.get("REPRO_CONV_BACKEND", "xla")
    if b not in CONV_BACKENDS:
        source = "backend argument" if backend else "REPRO_CONV_BACKEND"
        raise ValueError(f"{source} must be one of {CONV_BACKENDS}, "
                         f"got {b!r}")
    return b


@dataclasses.dataclass(frozen=True)
class Layer:
    """One paper-granularity layer."""

    kind: str                    # conv/relu/relu6/maxpool/avgpool/dropout/
                                 # linear/invres
    name: str = ""
    # conv / linear / invres hyper-params (unused fields stay 0)
    cout: int = 0
    ksize: int = 0
    stride: int = 1
    pad: int = 0
    features: int = 0            # linear out features
    expand: int = 0              # invres expansion ratio
    out_hw: int = 0              # adaptive avgpool target


def conv(cout, k, s=1, p=0):
    return Layer(kind="conv", cout=cout, ksize=k, stride=s, pad=p)


def relu():
    return Layer(kind="relu")


def relu6():
    return Layer(kind="relu6")


def maxpool(k, s):
    return Layer(kind="maxpool", ksize=k, stride=s)


def avgpool(out_hw):
    return Layer(kind="avgpool", out_hw=out_hw)


def dropout():
    return Layer(kind="dropout")


def linear(features):
    return Layer(kind="linear", features=features)


def invres(cout, stride, expand):
    return Layer(kind="invres", cout=cout, stride=stride, expand=expand)


def gap_linear(features):
    """Global-average-pool + linear (MobileNetV2 classifier head: the pool
    is functional in torchvision's forward(), not a module, so it shares a
    paper-layer with the Linear)."""
    return Layer(kind="gap_linear", features=features)


# ---------------------------------------------------------------------------
# Shape / cost inference
# ---------------------------------------------------------------------------
def _conv_out(h, k, s, p):
    return (h + 2 * p - k) // s + 1


def _check_spatial(layer: Layer, in_shape: tuple, oh: int, ow: int) -> None:
    """Reject degenerate geometry with a layer-naming error instead of an
    opaque lax shape failure deep inside the conv/reduce_window lowering."""
    if oh < 1 or ow < 1:
        label = layer.name or layer.kind
        raise ValueError(
            f"layer {label!r} (ksize={layer.ksize}, stride={layer.stride}, "
            f"pad={layer.pad}) produces empty output {oh}x{ow} from input "
            f"(H, W)=({in_shape[1]}, {in_shape[2]}): input too small for "
            f"this kernel/stride")


def layer_out_shape(layer: Layer, in_shape: tuple) -> tuple:
    """in_shape: (C, H, W) or (F,) -- batch handled outside."""
    if layer.kind == "conv":
        c, h, w = in_shape
        oh = _conv_out(h, layer.ksize, layer.stride, layer.pad)
        ow = _conv_out(w, layer.ksize, layer.stride, layer.pad)
        _check_spatial(layer, in_shape, oh, ow)
        return (layer.cout, oh, ow)
    if layer.kind in ("relu", "relu6", "dropout"):
        return in_shape
    if layer.kind == "maxpool":
        c, h, w = in_shape
        oh = _conv_out(h, layer.ksize, layer.stride, 0)
        ow = _conv_out(w, layer.ksize, layer.stride, 0)
        _check_spatial(layer, in_shape, oh, ow)
        return (c, oh, ow)
    if layer.kind == "avgpool":
        c, h, w = in_shape
        if layer.out_hw < 1 or h < 1 or w < 1:
            raise ValueError(
                f"layer {layer.name or layer.kind!r}: adaptive avgpool "
                f"needs out_hw >= 1 and a non-empty input, got "
                f"out_hw={layer.out_hw}, (H, W)=({h}, {w})")
        return (c, layer.out_hw, layer.out_hw)
    if layer.kind in ("linear", "gap_linear"):
        return (layer.features,)
    if layer.kind == "invres":
        c, h, w = in_shape
        oh = -(-h // layer.stride)  # stride with SAME padding
        ow = -(-w // layer.stride)
        return (layer.cout, oh, ow)
    raise ValueError(layer.kind)


def layer_flops_params(layer: Layer, in_shape: tuple) -> tuple[float, float]:
    """(FLOPs, param count) for one inference at batch 1."""
    out = layer_out_shape(layer, in_shape)
    n_out = float(np.prod(out))
    if layer.kind == "conv":
        cin = in_shape[0]
        macs = layer.ksize**2 * cin * n_out
        params = layer.ksize**2 * cin * layer.cout + layer.cout
        return 2 * macs, params
    if layer.kind in ("relu", "relu6"):
        return n_out, 0.0
    if layer.kind == "dropout":
        return 0.0, 0.0
    if layer.kind == "maxpool":
        return layer.ksize**2 * n_out, 0.0
    if layer.kind == "avgpool":
        n_in = float(np.prod(in_shape))
        return n_in, 0.0
    if layer.kind == "linear":
        fin = float(np.prod(in_shape))
        return 2 * fin * layer.features, fin * layer.features + layer.features
    if layer.kind == "gap_linear":
        fin = float(in_shape[0])
        pool = float(np.prod(in_shape))
        return pool + 2 * fin * layer.features, \
            fin * layer.features + layer.features
    if layer.kind == "invres":
        cin, h, w = in_shape
        hidden = cin * layer.expand
        oh, ow = out[1], out[2]
        f = p = 0.0
        if layer.expand != 1:                       # expand 1x1
            f += 2 * cin * hidden * h * w
            p += cin * hidden + 2 * hidden          # conv + bn
            f += hidden * h * w                     # relu6
        f += 2 * 9 * hidden * oh * ow               # depthwise 3x3
        p += 9 * hidden + 2 * hidden
        f += hidden * oh * ow                       # relu6
        f += 2 * hidden * layer.cout * oh * ow      # project 1x1
        p += hidden * layer.cout + 2 * layer.cout
        if layer.stride == 1 and cin == layer.cout:
            f += layer.cout * oh * ow               # residual add
        return f, p
    raise ValueError(layer.kind)


# ---------------------------------------------------------------------------
# Parameter init + apply
# ---------------------------------------------------------------------------
def _init_conv(key, cin, cout, k):
    fan_in = cin * k * k
    w = jax.random.normal(key, (cout, cin, k, k)) * math.sqrt(2 / fan_in)
    return {"w": w.astype(jnp.float32), "b": jnp.zeros((cout,), jnp.float32)}


def _init_linear(key, fin, fout):
    w = jax.random.normal(key, (fin, fout)) * math.sqrt(2 / fin)
    return {"w": w.astype(jnp.float32), "b": jnp.zeros((fout,), jnp.float32)}


def init_layer(key, layer: Layer, in_shape: tuple) -> Any:
    if layer.kind == "conv":
        return _init_conv(key, in_shape[0], layer.cout, layer.ksize)
    if layer.kind == "linear":
        return _init_linear(key, int(np.prod(in_shape)), layer.features)
    if layer.kind == "gap_linear":
        return _init_linear(key, int(in_shape[0]), layer.features)
    if layer.kind == "invres":
        cin = in_shape[0]
        hidden = cin * layer.expand
        keys = jax.random.split(key, 3)
        p = {}
        if layer.expand != 1:
            p["expand"] = _init_conv(keys[0], cin, hidden, 1)
        p["dw"] = {"w": jax.random.normal(keys[1], (hidden, 1, 3, 3))
                   * math.sqrt(2 / 9), "b": jnp.zeros((hidden,))}
        p["project"] = _init_conv(keys[2], hidden, layer.cout, 1)
        return p
    return {}


def _maxpool(x, k, s):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, k, k), (1, 1, s, s), "VALID")


def _conv2d(x, w, b, stride, pad, groups=1, activation=None,
            pool_k=0, pool_s=0, backend=None, dtype=None):
    """Backend-dispatched conv(+bias)(+act)(+maxpool).

    On pallas the whole chain is one kernel launch; on xla the pool (if
    any) runs as a separate reduce_window so both backends share the same
    call signature and semantics.  ``dtype`` is the storage policy
    (``conv_dtype``): under bf16 both backends store inputs/weights and
    the returned activation in bfloat16 while accumulating in fp32.

    Tiling on the pallas path comes from the ``plan_conv`` joint search
    (``REPRO_CONV_SEARCH`` / ``REPRO_CONV_TILE_W`` knobs): with column
    tiles the kernel also handles high-resolution client inputs (1080p
    frames, panoramic strips) whose single output row overflows VMEM --
    ``INPUT_SHAPE`` is just the paper default, not a limit."""
    policy = conv_dtype(dtype)
    if conv_backend(backend) == "pallas":
        from repro.kernels import ops
        return ops.conv2d(x, w, stride=stride, pad=pad, bias=b,
                          activation=activation, groups=groups,
                          pool_k=pool_k, pool_s=pool_s, dtype=policy)
    from repro.kernels import ref
    accum = None
    if policy == "bf16":
        jdt = policy_jnp_dtype(policy)
        x = x if x.dtype == jdt else x.astype(jdt)
        w = w if w.dtype == jdt else w.astype(jdt)
        accum = jnp.float32
    y = ref.conv2d_ref(x, w, stride=stride, pad=pad, bias=b,
                       activation=activation, groups=groups,
                       accum_dtype=accum)
    return _maxpool(y, pool_k, pool_s or pool_k) if pool_k else y


def _adaptive_avgpool_1d(x: jnp.ndarray, axis: int, out: int) -> jnp.ndarray:
    """torchvision AdaptiveAvgPool semantics along one axis: output index i
    averages input [floor(i*n/out), ceil((i+1)*n/out)) -- variable windows,
    every input element covered (no truncation when ``n % out != 0``)."""
    n = x.shape[axis]
    if n % out == 0:                  # uniform windows: one cheap reshape
        k = n // out
        shape = x.shape[:axis] + (out, k) + x.shape[axis + 1:]
        return x.reshape(shape).mean(axis=axis + 1)
    pieces = []
    for i in range(out):
        s, e = (i * n) // out, -(-((i + 1) * n) // out)
        pieces.append(jax.lax.slice_in_dim(x, s, e, axis=axis)
                      .mean(axis=axis, keepdims=True))
    return jnp.concatenate(pieces, axis=axis)


def apply_layer(layer: Layer, params: Any, x: jnp.ndarray,
                train: bool = False, backend: str | None = None,
                dtype: str | None = None) -> jnp.ndarray:
    if layer.kind in ("conv", "maxpool", "avgpool"):
        layer_out_shape(layer, x.shape[1:])   # fail with a named layer
    if layer.kind == "conv":
        return _conv2d(x, params["w"], params["b"], layer.stride, layer.pad,
                       backend=backend, dtype=dtype)
    if layer.kind == "relu":
        return jax.nn.relu(x)
    if layer.kind == "relu6":
        return jnp.clip(x, 0.0, 6.0)
    if layer.kind == "dropout":
        return x                      # inference: identity (paper: inference)
    if layer.kind == "maxpool":
        return _maxpool(x, layer.ksize, layer.stride)
    if layer.kind == "avgpool":
        # Adaptive average pool to (out_hw, out_hw), variable-window like
        # torch's AdaptiveAvgPool2d (the old reshape path truncated
        # trailing rows/cols whenever H % out_hw != 0, e.g. 227-px AlexNet)
        x = _adaptive_avgpool_1d(x, 2, layer.out_hw)
        return _adaptive_avgpool_1d(x, 3, layer.out_hw)
    if layer.kind in ("linear", "gap_linear"):
        if layer.kind == "linear" and x.ndim > 2:
            x = x.reshape(x.shape[0], -1)
        if layer.kind == "gap_linear" and x.ndim == 4:
            x = x.mean(axis=(2, 3))
        # same storage/accumulate split as the conv kernel: weights and
        # activations stored in the policy dtype, matmul in fp32 (so the
        # analytic profile's per-layer weight bytes match the runtime)
        jdt = policy_jnp_dtype(conv_dtype(dtype))
        w = params["w"].astype(jdt).astype(jnp.float32)
        y = x.astype(jnp.float32) @ w + params["b"]
        return y.astype(jdt)
    if layer.kind == "invres":
        # conv+relu6 pairs fuse into one kernel launch on the pallas backend
        y = x
        hidden_in = x
        if "expand" in params:
            y = _conv2d(y, params["expand"]["w"], params["expand"]["b"], 1, 0,
                        activation="relu6", backend=backend, dtype=dtype)
        y = _conv2d(y, params["dw"]["w"], params["dw"]["b"], layer.stride, 1,
                    groups=y.shape[1], activation="relu6", backend=backend,
                    dtype=dtype)
        y = _conv2d(y, params["project"]["w"], params["project"]["b"], 1, 0,
                    backend=backend, dtype=dtype)
        if layer.stride == 1 and hidden_in.shape == y.shape:
            y = y + hidden_in.astype(y.dtype)
        return y
    raise ValueError(layer.kind)


# ---------------------------------------------------------------------------
# Model definitions (module lists match torchvision; counts match the paper)
# ---------------------------------------------------------------------------
def _vgg_features(cfg: list) -> list[Layer]:
    layers = []
    for v in cfg:
        if v == "M":
            layers.append(maxpool(2, 2))
        else:
            layers += [conv(v, 3, 1, 1), relu()]
    return layers


_CLASSIFIER_VGG = [linear(4096), relu(), dropout(),
                   linear(4096), relu(), dropout(), linear(1000)]

ALEXNET = [
    conv(64, 11, 4, 2), relu(), maxpool(3, 2),
    conv(192, 5, 1, 2), relu(), maxpool(3, 2),
    conv(384, 3, 1, 1), relu(),
    conv(256, 3, 1, 1), relu(),
    conv(256, 3, 1, 1), relu(), maxpool(3, 2),
    avgpool(6),
    dropout(), linear(4096), relu(),
    dropout(), linear(4096), relu(), linear(1000),
]                                                     # 21 layers

VGG11 = _vgg_features([64, "M", 128, "M", 256, 256, "M",
                       512, 512, "M", 512, 512, "M"]) \
    + [avgpool(7)] + _CLASSIFIER_VGG                  # 29 layers

VGG13 = _vgg_features([64, 64, "M", 128, 128, "M", 256, 256, "M",
                       512, 512, "M", 512, 512, "M"]) \
    + [avgpool(7)] + _CLASSIFIER_VGG                  # 33 layers

VGG16 = _vgg_features([64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
                       512, 512, 512, "M", 512, 512, 512, "M"]) \
    + [avgpool(7)] + _CLASSIFIER_VGG                  # 39 layers

_MBV2_SETTING = [  # (expand, cout, repeats, stride)
    (1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
    (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1)]


def _mobilenet_v2() -> list[Layer]:
    layers: list[Layer] = [conv(32, 3, 2, 1)]         # ConvBNReLU stem
    cin = 32
    for t, c, n, s in _MBV2_SETTING:
        for i in range(n):
            layers.append(invres(c, s if i == 0 else 1, t))
            cin = c
    layers.append(conv(1280, 1, 1, 0))                # last ConvBNReLU
    layers.append(dropout())
    layers.append(gap_linear(1000))
    return layers                                     # 21 layers


MOBILENET_V2 = _mobilenet_v2()

CNN_MODELS: dict[str, list[Layer]] = {
    "alexnet": ALEXNET,        # 21
    "vgg11": VGG11,            # 29
    "vgg13": VGG13,            # 33
    "vgg16": VGG16,            # 39
    "mobilenetv2": MOBILENET_V2,  # 21
}

INPUT_SHAPE = (3, 224, 224)


# ---------------------------------------------------------------------------
# Whole-network helpers
# ---------------------------------------------------------------------------
def shapes_through(layers: list[Layer],
                   in_shape: tuple = INPUT_SHAPE) -> list[tuple]:
    """Per-layer output shapes (len == len(layers))."""
    out = []
    shape = in_shape
    for l in layers:
        shape = layer_out_shape(l, shape)
        out.append(shape)
    return out


def conv_pool_triples(layers: list[Layer],
                      in_shape: tuple = INPUT_SHAPE) -> list[tuple]:
    """(layer_index, cin, hw, cout, ksize, stride, pad, act, pool_k, pool_s)
    for every conv->relu/relu6->maxpool triple ``apply_cnn`` fuses on the
    pallas backend when wholly on one side of the split.  Single source of
    truth for the fusion benchmarks and tests -- the condition here mirrors
    the walk in ``apply_cnn`` exactly."""
    shape = in_shape
    out = []
    for i, l in enumerate(layers):
        if (l.kind == "conv" and i + 2 < len(layers)
                and layers[i + 1].kind in ("relu", "relu6")
                and layers[i + 2].kind == "maxpool"):
            mp = layers[i + 2]
            out.append((i, shape[0], shape[1], l.cout, l.ksize, l.stride,
                        l.pad, layers[i + 1].kind, mp.ksize, mp.stride))
        shape = layer_out_shape(l, shape)
    return out


def conv_plans(layers: list[Layer], in_shape: tuple = INPUT_SHAPE, *,
               batch: int = 1, dtype: str | None = None,
               search: bool | None = None) -> list[tuple]:
    """``(layer_index, ConvPlan)`` for every conv paper-layer, planned
    exactly as the pallas fusion walk will launch it: a conv heading a
    conv->relu->maxpool triple is planned *with* its fused pool geometry
    (``conv_pool_triples`` supplies the window -- the same source
    ``apply_cnn`` mirrors), and the planner sees the storage policy's
    element size, so the plan/BlockSpec geometry the runtime executes and
    the launch/VMEM numbers benches and tests reason about can never
    desynchronise.  ``search`` forwards to ``plan_conv`` (None = resolve
    ``REPRO_CONV_SEARCH``)."""
    from repro.kernels.conv2d import plan_conv
    nbytes = dtype_bytes(conv_dtype(dtype))
    triples = {t[0]: t for t in conv_pool_triples(layers, in_shape)}
    shape = in_shape
    out = []
    for i, l in enumerate(layers):
        if l.kind == "conv":
            pk, ps = (triples[i][-2], triples[i][-1]) if i in triples \
                else (0, 0)
            out.append((i, plan_conv(
                (batch,) + shape, (l.cout, shape[0], l.ksize, l.ksize),
                stride=l.stride, pad=l.pad, pool_k=pk, pool_s=ps,
                dtype_bytes=nbytes, search=search)))
        shape = layer_out_shape(l, shape)
    return out


def init_cnn(key, layers: list[Layer], in_shape: tuple = INPUT_SHAPE):
    params = []
    shape = in_shape
    for l in layers:
        key, sub = jax.random.split(key)
        params.append(init_layer(sub, l, shape))
        shape = layer_out_shape(l, shape)
    return params


# One compiled program per stage: (layer range, backend, dtype, conv knobs)
# -> jitted walk; jit adds the input's shape and dtype.  Module-level like
# ``core.smartsplit``'s plan cache, so every runtime, engine and reference
# caller that walks the same range shares the program.
_WALKS: dict[tuple, Any] = {}
_WALK_TRACES = 0
_WALK_HITS = 0


def _walk(layers: tuple, bk: str, dt: str, params, x):
    """The traced body of ``apply_cnn``: layers (already the range) in
    order, with the pallas fusion peek inside the range only."""
    global _WALK_TRACES
    _WALK_TRACES += 1               # Python runs only while jit traces
    if dt != "fp32":
        # the storage invariant starts at the input: even a degenerate
        # l1=0 split (COC) uploads the policy-dtype tensor the profile's
        # input_bytes term charges
        jdt = policy_jnp_dtype(dt)
        x = x if x.dtype == jdt else x.astype(jdt)
    i, stop = 0, len(layers)
    while i < stop:
        layer = layers[i]
        if (bk == "pallas" and layer.kind == "conv" and i + 1 < stop
                and layers[i + 1].kind in ("relu", "relu6")):
            pool_k = pool_s = 0
            step = 2
            conv_out = layer_out_shape(layer, x.shape[1:])
            if i + 2 < stop and layers[i + 2].kind == "maxpool":
                layer_out_shape(layers[i + 2], conv_out)  # named geom check
                pool_k = layers[i + 2].ksize
                pool_s = layers[i + 2].stride
                step = 3
            x = _conv2d(x, params[i]["w"], params[i]["b"], layer.stride,
                        layer.pad, activation=layers[i + 1].kind,
                        pool_k=pool_k, pool_s=pool_s, backend=bk, dtype=dt)
            i += step
            continue
        x = apply_layer(layer, params[i], x, backend=bk, dtype=dt)
        i += 1
    return x


def _walk_key(layers: list[Layer], start: int, stop: int,
              backend: str | None, dtype: str | None) -> tuple:
    """The cache key of one stage's program.  The conv knobs are resolved
    here because inside the program ``kernels/ops.py::conv2d`` reads them
    once, at trace time: a flipped env var must select another program."""
    from repro.kernels import interpret_mode
    from repro.kernels.conv2d import search_enabled, tile_w_override
    return (tuple(layers[start:stop]), start, stop, conv_backend(backend),
            conv_dtype(dtype), interpret_mode(), tile_w_override(0),
            search_enabled(None))


def _compiled_walk(key: tuple):
    fn = _WALKS.get(key)
    if fn is None:
        layers, _, _, bk, dt = key[:5]
        # no excess precision: every op's result is rounded to its dtype,
        # so the bf16 storage policy holds inside a program as at its ends
        fn = _WALKS[key] = jax.jit(
            functools.partial(_walk, layers, bk, dt),
            compiler_options={"xla_allow_excess_precision": False})
    return fn


def walk_cache_stats() -> dict[str, int]:
    """Stage programs traced (new range, knobs, shape or dtype) and calls
    served by an already traced program, since the process started."""
    return {"traces": _WALK_TRACES, "hits": _WALK_HITS}


def apply_cnn(layers: list[Layer], params, x, *, start: int = 0,
              stop: int | None = None, backend: str | None = None,
              dtype: str | None = None):
    """Run layers [start, stop) -- the split runtime building block.

    The range runs as one compiled program, cached per (layers of the
    range, start, stop, resolved backend and dtype, and the conv knobs
    ``kernels/ops.py::conv2d`` resolves: ``interpret_mode()``,
    ``REPRO_CONV_TILE_W``, ``REPRO_CONV_SEARCH``); jit adds the input's
    shape and dtype.  The knobs are in the key because the program reads
    them once, when it is traced, so flipping one selects another
    program instead of reusing a stale grid.  Only ``params[start:stop]``
    is passed, as an argument: no weight is baked into a program.  A range
    first seen -- a stage merge, re-pick, failover or on-device fallback,
    or the non-pipelined engine's batch sizes 1..``max_batch`` -- traces
    and compiles once, which costs wall-clock time only, never virtual
    time.  ``walk_cache_stats`` counts traces and hits.  Called inside a
    caller's trace (jit, grad, vmap), the walk is traced into the caller's
    program instead, under the caller's compiler options.

    On the pallas backend the walk peeks up to two layers ahead: a conv
    paper-layer immediately followed by relu/relu6 collapses into a single
    fused kernel launch (conv + bias + activation in the epilogue), and if
    a maxpool follows the activation the whole conv->relu->maxpool *triple*
    becomes one launch with the pool running on the fp32 accumulator (no
    intermediate activation ever written to HBM).  All layers are still
    *counted* -- split indices keep paper-layer semantics -- and fusion
    only happens when every member sits wholly on one side of the split
    ([start, stop)), so the boundary payload is bit-identical to the
    unfused walk.

    ``dtype`` is the storage policy (``conv_dtype``; env
    ``REPRO_CONV_DTYPE``): under ``bf16`` every conv stores its weights /
    activations / pooled outputs in bfloat16 (fp32 accumulate), so the
    activation stream -- including any split-boundary payload -- flows at
    half the bytes.  Linear/gap_linear heads follow the same rule (bf16
    weight/activation storage, fp32 matmul), so the analytic profile's
    per-layer weight and activation bytes match the runtime everywhere."""
    global _WALK_HITS
    stop = len(layers) if stop is None else stop
    if not 0 <= start <= stop <= len(layers):
        raise ValueError(
            f"apply_cnn: need 0 <= start <= stop <= {len(layers)} "
            f"(L), got start={start}, stop={stop}")
    stage_params = params[start:stop]
    if any(isinstance(leaf, jax.core.Tracer)
           for leaf in jax.tree.leaves((stage_params, x))):
        # under a caller's jit, grad or vmap the walk joins the caller's
        # program, as a nested jit would: only a top-level jit takes
        # compiler options
        return _walk(tuple(layers[start:stop]), conv_backend(backend),
                     conv_dtype(dtype), stage_params, x)
    fn = _compiled_walk(_walk_key(layers, start, stop, backend, dtype))
    traces = _WALK_TRACES
    y = fn(stage_params, x)
    if _WALK_TRACES == traces:
        _WALK_HITS += 1
    return y


def apply_split(layers: list[Layer], params, x, split_index: int,
                backend: str | None = None, dtype: str | None = None,
                wire: str | None = None):
    """Client runs [0, l1), payload crosses the link, server runs [l1, L).

    Returns (logits, boundary_payload) so callers can account the transfer.
    Under the bf16 storage policy the boundary tensor is serialized in
    bfloat16 -- exactly the halved I|l1 the dtype-aware cost model feeds
    the optimiser.

    ``wire`` (``fp32``/``bf16``/``int8``/``follow``; None resolves
    ``REPRO_WIRE_DTYPE``) applies the wire-format round-trip to the
    boundary the server stage consumes -- ``kernels.quant.
    boundary_roundtrip``, the same math the runtime codec performs -- so
    this is the bit-exact fault-free reference for a quantized-wire
    runtime run.  The returned boundary is the client's (pre-encode)
    activation either way.

    ``split_index`` must lie in [0, L]: the degenerate ends are the
    paper's COC (l1=0, boundary = the input upload) and COS-like
    all-on-device placement (l1=L, nothing crosses the link)."""
    from repro.core.dtype_policy import resolve_wire_dtype
    from repro.kernels.quant import boundary_roundtrip
    if not 0 <= split_index <= len(layers):
        raise ValueError(
            f"apply_split: split_index must be in [0, {len(layers)}] "
            f"(L={len(layers)} layers), got {split_index}")
    boundary = apply_cnn(layers, params, x, start=0, stop=split_index,
                         backend=backend, dtype=dtype)
    w = resolve_wire_dtype(wire, storage=conv_dtype(dtype))
    received = boundary if w == conv_dtype(dtype) \
        else boundary_roundtrip(boundary, w, backend=backend)
    logits = apply_cnn(layers, params, received, start=split_index,
                       backend=backend, dtype=dtype)
    return logits, boundary
