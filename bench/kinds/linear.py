"""A fully connected layer over the flattened input: ``{"kind":
"linear", "features"}``."""
import math

from bench import reference, weights
from bench.flops import field


def out_shape(layer, in_shape):
    return (field(layer, "features"),)


def flops_params(layer, in_shape):
    fin, f = float(math.prod(in_shape)), field(layer, "features")
    return 2 * fin * f, fin * f + f


def init(key, layer, in_shape):
    return weights.linear_params(key, math.prod(in_shape),
                                 int(layer["features"]))


def apply(layer, p, x, mode):
    return reference.linear(x.reshape(x.shape[0], -1), p, mode)
