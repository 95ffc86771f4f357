"""Dropout at inference: the identity."""

EXAMPLE = {"kind": "dropout"}


def out_shape(layer, in_shape):
    return tuple(in_shape)


def flops_params(layer, in_shape):
    return 0.0, 0.0


def init(key, layer, in_shape):
    return {}


def apply(layer, p, x, mode):
    return x
