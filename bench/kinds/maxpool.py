"""Max pooling with no padding: ``{"kind": "maxpool", "ksize",
"stride"}``.  A conv and its epilogue right before fold it into their
call as the pool."""
import math

import jax
import jax.numpy as jnp

from bench import flops
from bench.flops import field

FOLDS_INTO_CONV = "pool"
EXAMPLE = {"kind": "maxpool", "ksize": 3, "stride": 2}


def out_shape(layer, in_shape):
    c, h, w = in_shape
    k, s = field(layer, "ksize"), field(layer, "stride")
    return (c, flops.conv_out(h, k, s, 0), flops.conv_out(w, k, s, 0))


def flops_params(layer, in_shape):
    n_out = float(math.prod(out_shape(layer, in_shape)))
    return field(layer, "ksize") ** 2 * n_out, 0.0


def init(key, layer, in_shape):
    return {}


def apply(layer, p, x, mode):
    k, s = int(layer["ksize"]), int(layer.get("stride", 1))
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 1, k, k), (1, 1, s, s), "VALID")
