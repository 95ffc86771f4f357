"""Adaptive average pooling to ``out_hw`` x ``out_hw``, as torch's
AdaptiveAvgPool2d: output i averages input [floor(i*n/out),
ceil((i+1)*n/out)) along each spatial axis."""
import math

import jax
import jax.numpy as jnp

from bench.flops import field


def out_shape(layer, in_shape):
    return (in_shape[0], field(layer, "out_hw"), field(layer, "out_hw"))


def flops_params(layer, in_shape):
    return float(math.prod(in_shape)), 0.0


def init(key, layer, in_shape):
    return {}


def apply(layer, p, x, mode):
    out = int(layer["out_hw"])
    for axis in (2, 3):
        n = x.shape[axis]
        parts = []
        for i in range(out):
            s, e = (i * n) // out, -(-((i + 1) * n) // out)
            parts.append(jax.lax.slice_in_dim(x, s, e, axis=axis)
                         .mean(axis=axis, keepdims=True))
        x = jnp.concatenate(parts, axis=axis)
    return x
