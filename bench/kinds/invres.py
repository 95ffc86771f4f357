"""MobileNetV2's inverted residual block: ``{"kind": "invres", "cout",
"stride", "expand"}``.  Expand 1x1 + relu6 (left out where ``expand`` is
1), depthwise 3x3 + relu6, project 1x1, plus the input when stride is 1
and the channels match.  Weights ``{"expand"?, "dw", "project"}``; on the
pallas path each conv is one kernel call."""
import jax

from bench import flops, reference, weights
from bench.flops import field


def out_shape(layer, in_shape):
    _, h, w = in_shape
    s = field(layer, "stride")
    return (field(layer, "cout"), -(-h // s), -(-w // s))


def flops_params(layer, in_shape):
    cin, h, w = in_shape
    t, cout = field(layer, "expand"), field(layer, "cout")
    hidden = cin * t
    _, oh, ow = out_shape(layer, in_shape)
    f = p = 0.0
    if t != 1:
        f += 2 * cin * hidden * h * w + hidden * h * w
        p += cin * hidden + 2 * hidden
    f += 2 * 9 * hidden * oh * ow + hidden * oh * ow
    p += 9 * hidden + 2 * hidden
    f += 2 * hidden * cout * oh * ow
    p += hidden * cout + 2 * cout
    if field(layer, "stride") == 1 and cin == cout:
        f += cout * oh * ow
    return f, p


def init(key, layer, in_shape):
    cin = in_shape[0]
    hidden = cin * int(layer["expand"])
    k0, k1, k2 = jax.random.split(key, 3)
    p = {}
    if int(layer["expand"]) != 1:
        p["expand"] = weights.conv_params(k0, cin, hidden, 1)
    p["dw"] = weights.conv_params(k1, 1, hidden, 3)
    p["project"] = weights.conv_params(k2, hidden, int(layer["cout"]), 1)
    return p


def apply(layer, p, x, mode):
    y = x
    if "expand" in p:
        y = reference.relu6(reference.conv(y, p["expand"], 1, 0, mode))
    stride = int(layer.get("stride", 1))
    y = reference.relu6(reference.conv(y, p["dw"], stride, 1, mode,
                                       groups=y.shape[1]))
    y = reference.conv(y, p["project"], 1, 0, mode)
    if stride == 1 and x.shape == y.shape:
        y = y + x
    return y


def launches(layers, i, in_shape, elem_bytes, fuses):
    c, h, w = in_shape
    layer = layers[i]
    hidden = c * field(layer, "expand")
    out = []
    if field(layer, "expand") != 1:
        out.append(flops.launch(c, h, w, hidden, 1, 1, 0, 1, None,
                                elem_bytes))
    s = field(layer, "stride")
    out.append(flops.launch(hidden, h, w, hidden, 3, s, 1, hidden, None,
                            elem_bytes))
    oh, ow = flops.conv_out(h, 3, s, 1), flops.conv_out(w, 3, s, 1)
    out.append(flops.launch(hidden, oh, ow, field(layer, "cout"), 1, 1, 0, 1,
                            None, elem_bytes))
    return out, 1
