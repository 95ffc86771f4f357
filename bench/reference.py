"""Plain reference of the configurations' CNNs, and its control.

Straightforward ``jax.numpy``/``lax`` in float32 over the configuration
file's layer list, with no kernel, batching or cache, and nothing imported
from the program.  It follows the program's layer semantics
(``models/cnn.py``), each kind's in its own file, ``bench/kinds/<kind>.py``
(its ``apply``): a ``conv`` layer is convolution plus bias; relu, relu6,
maxpool and dropout are separate layers.

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

from bench import spec

MODES = ("highest", "bf16x3")
HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def contract(op, a, b, mode: str):
    """``op(a, b, precision)`` under ``mode``."""
    if mode == "highest":
        return op(a, b, HIGHEST)
    ah, al = _split(a)
    bh, bl = _split(b)
    return op(ah, bh, HIGHEST) + (op(ah, bl, HIGHEST) + op(al, bh, HIGHEST))


def conv(x, p, stride, pad, mode, groups=1):
    """Convolution of the NCHW batch ``x`` plus bias."""
    def op(a, b, precision):
        return jax.lax.conv_general_dilated(
            a, b, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=groups, precision=precision)
    return contract(op, x, p["w"], mode) + p["b"][None, :, None, None]


def linear(x, p, mode):
    def op(a, b, precision):
        return jnp.matmul(a, b, precision=precision)
    return contract(op, x, p["w"], mode) + p["b"]


def relu6(x):
    return jnp.clip(x, 0.0, 6.0)


def apply_layer(layer: dict, p, x, mode: str):
    return spec.load_kind(layer["kind"]).apply(layer, p, x, mode)


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
