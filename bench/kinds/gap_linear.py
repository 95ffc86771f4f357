"""A global mean over the spatial axes, then a fully connected layer:
``{"kind": "gap_linear", "features"}``."""
import math

from bench import reference, weights
from bench.flops import field


def out_shape(layer, in_shape):
    return (field(layer, "features"),)


def flops_params(layer, in_shape):
    fin, f = float(in_shape[0]), field(layer, "features")
    return float(math.prod(in_shape)) + 2 * fin * f, fin * f + f


def init(key, layer, in_shape):
    return weights.linear_params(key, in_shape[0], int(layer["features"]))


def apply(layer, p, x, mode):
    return reference.linear(x.mean(axis=(2, 3)) if x.ndim == 4 else x, p,
                            mode)
