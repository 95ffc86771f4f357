"""min(max(x, 0), 6), elementwise; a conv right before folds it into its
call."""
import math

from bench import reference

FOLDS_INTO_CONV = "epilogue"
EXAMPLE = {"kind": "relu6"}


def out_shape(layer, in_shape):
    return tuple(in_shape)


def flops_params(layer, in_shape):
    return float(math.prod(in_shape)), 0.0


def init(key, layer, in_shape):
    return {}


def apply(layer, p, x, mode):
    return reference.relu6(x)
