"""Convolution plus bias: ``{"kind": "conv", "cout", "ksize", "stride",
"pad"}``.  On the pallas path it is one kernel call, with a relu or relu6
right after it folded in as the epilogue and a maxpool after that as the
pool, where they run in the same stage."""
import math

from bench import flops, reference, weights
from bench.flops import field


def out_shape(layer, in_shape):
    _, h, w = in_shape
    k, s, p = field(layer, "ksize"), field(layer, "stride"), \
        field(layer, "pad")
    return (field(layer, "cout"), flops.conv_out(h, k, s, p),
            flops.conv_out(w, k, s, p))


def flops_params(layer, in_shape):
    n_out = float(math.prod(out_shape(layer, in_shape)))
    cin, k, cout = in_shape[0], field(layer, "ksize"), field(layer, "cout")
    return 2 * k * k * cin * n_out, k * k * cin * cout + cout


def init(key, layer, in_shape):
    return weights.conv_params(key, in_shape[0], int(layer["cout"]),
                               int(layer["ksize"]))


def apply(layer, p, x, mode):
    return reference.conv(x, p, int(layer.get("stride", 1)),
                          int(layer.get("pad", 0)), mode)


def launches(layers, i, in_shape, elem_bytes, fuses):
    c, h, w = in_shape
    layer = layers[i]
    step, pool = 1, None
    if fuses(i + 1) and flops.folds_into_conv(layers[i + 1]) == "epilogue":
        step = 2
        if fuses(i + 2) and flops.folds_into_conv(layers[i + 2]) == "pool":
            mp = layers[i + 2]
            pool, step = (field(mp, "ksize"), field(mp, "stride")), 3
    return [flops.launch(c, h, w, field(layer, "cout"), field(layer, "ksize"),
                         field(layer, "stride"), field(layer, "pad"), 1,
                         pool, elem_bytes)], step
