"""Plain reference of the configurations' CNNs, and its control.

Straightforward ``jax.numpy``/``lax`` in float32 over the configuration
file's layer list, with no kernel, batching or cache, and nothing imported
from the program.  It follows the program's layer semantics
(``models/cnn.py``): a ``conv`` layer is convolution plus bias; relu,
relu6, maxpool and dropout are separate layers; an inverted residual block
is expand 1x1 + relu6, depthwise 3x3 + relu6, project 1x1, plus the input
when stride is 1 and the channels match; ``gap_linear`` is a global mean
followed by a linear layer.

At each stage boundary (``cuts``) the boundary activation crosses the
configured wire format.  ``int8`` is symmetric per-channel quantization
of each image on its own (per image for a flat activation): scale =
absmax / 127 (1 for an all-zero group), q = clip(round(x / scale), -127,
127), received = q * scale.

``mode`` sets the arithmetic of every contraction:

* ``highest``: float32 at HIGHEST matmul precision (the reference);
* ``bf16x3``: the control.  Each operand is split into a bfloat16 high part
  and a bfloat16 low part, and the product is hi*hi + hi*lo + lo*hi: three
  bf16 passes, the nearest precision below HIGHEST, on any platform.  The
  parts are rounded with ``lax.reduce_precision`` and stay float32: on the
  TPU, a round trip through ``astype(bfloat16)`` let XLA contract the
  parts in one bf16 pass (the control then read ~5e-3 on VGG16, where
  three passes read ~1.5e-5)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

MODES = ("highest", "bf16x3")
HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def _contract(op, a, b, mode: str):
    """``op(a, b, precision)`` under ``mode``."""
    if mode == "highest":
        return op(a, b, HIGHEST)
    ah, al = _split(a)
    bh, bl = _split(b)
    return op(ah, bh, HIGHEST) + (op(ah, bl, HIGHEST) + op(al, bh, HIGHEST))


def _conv(x, p, stride, pad, mode, groups=1):
    def op(a, b, precision):
        return jax.lax.conv_general_dilated(
            a, b, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=groups, precision=precision)
    return _contract(op, x, p["w"], mode) + p["b"][None, :, None, None]


def _linear(x, p, mode):
    def op(a, b, precision):
        return jnp.matmul(a, b, precision=precision)
    return _contract(op, x, p["w"], mode) + p["b"]


def _relu6(x):
    return jnp.clip(x, 0.0, 6.0)


def _adaptive_avgpool(x, out: int):
    """torch AdaptiveAvgPool2d: output i averages input
    [floor(i*n/out), ceil((i+1)*n/out)) along each spatial axis."""
    for axis in (2, 3):
        n = x.shape[axis]
        parts = []
        for i in range(out):
            s, e = (i * n) // out, -(-((i + 1) * n) // out)
            parts.append(jax.lax.slice_in_dim(x, s, e, axis=axis)
                         .mean(axis=axis, keepdims=True))
        x = jnp.concatenate(parts, axis=axis)
    return x


def apply_layer(layer: dict, p, x, mode: str):
    kind = layer["kind"]
    if kind == "conv":
        return _conv(x, p, int(layer.get("stride", 1)),
                     int(layer.get("pad", 0)), mode)
    if kind == "relu":
        return jnp.maximum(x, 0.0)
    if kind == "relu6":
        return _relu6(x)
    if kind == "dropout":
        return x
    if kind == "maxpool":
        k, s = int(layer["ksize"]), int(layer.get("stride", 1))
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, k, k),
                                     (1, 1, s, s), "VALID")
    if kind == "avgpool":
        return _adaptive_avgpool(x, int(layer["out_hw"]))
    if kind == "linear":
        return _linear(x.reshape(x.shape[0], -1), p, mode)
    if kind == "gap_linear":
        return _linear(x.mean(axis=(2, 3)) if x.ndim == 4 else x, p, mode)
    if kind == "invres":
        y = x
        if "expand" in p:
            y = _relu6(_conv(y, p["expand"], 1, 0, mode))
        stride = int(layer.get("stride", 1))
        y = _relu6(_conv(y, p["dw"], stride, 1, mode, groups=y.shape[1]))
        y = _conv(y, p["project"], 1, 0, mode)
        if stride == 1 and x.shape == y.shape:
            y = y + x
        return y
    raise ValueError(f"unknown layer kind {kind!r}")


def wire_roundtrip(x, wire: str):
    """What the next tier receives when ``x`` crosses a hop as ``wire``;
    each image (row of the batch) is quantized on its own."""
    if wire == "fp32":
        return x
    if wire == "bf16":
        return x.astype(jnp.bfloat16).astype(x.dtype)
    if wire != "int8":
        raise ValueError(f"unknown wire format {wire!r}")
    red = tuple(range(2, x.ndim)) if x.ndim >= 3 else tuple(range(1, x.ndim))
    absmax = jnp.max(jnp.abs(x), axis=red, keepdims=True)
    scale = jnp.where(absmax > 0.0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    return q * scale


@functools.lru_cache(maxsize=None)
def _forward(layers: tuple, cuts: tuple, wires: tuple, mode: str):
    layer_dicts = [dict(items) for items in layers]

    def run(params, x):
        hop = 0
        for i, layer in enumerate(layer_dicts):
            if i in cuts:
                x = wire_roundtrip(x, wires[hop])
                hop += 1
            x = apply_layer(layer, params[i], x, mode)
        return x

    return jax.jit(run)


def forward(layers: list[dict], params, x, *, cuts=(), wires=(),
            mode: str = "highest"):
    """Logits of the batch ``x`` (N, C, H, W) through every layer, with
    ``wires[k]`` applied at ``cuts[k]``."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if len(wires) != len(cuts):
        raise ValueError(f"{len(cuts)} cuts need {len(cuts)} wire formats, "
                         f"got {len(wires)}")
    key = tuple(tuple(sorted(layer.items())) for layer in layers)
    return _forward(key, tuple(int(c) for c in cuts), tuple(wires), mode)(
        params, x)
