"""Analytic operation and byte counts, kept with the benchmark.

A copy of the program's per-layer counter (``models/cnn.py::
layer_flops_params`` and ``layer_out_shape``) so that no change to the
program can move the yardstick.  Layers are the configuration files' plain
dicts (``{"kind": "conv", "cout": 64, "ksize": 3, ...}``), never the
program's objects.

``conv_launches`` lists the convolution kernel calls one request makes on
the pallas path: a conv followed by relu/relu6 (and a maxpool after that)
is one call, and an inverted residual block is two or three.  Each call's
FLOPs are the multiply-adds of its convolution (2 per MAC); its bytes are
input, weights, bias and output at the storage dtype: the least the chip
must move for it."""
from __future__ import annotations

import math

def _g(layer: dict, key: str) -> int:
    return int(layer.get(key, 1 if key == "stride" else 0))


def _conv_out(h: int, k: int, s: int, p: int) -> int:
    return (h + 2 * p - k) // s + 1


def out_shape(layer: dict, in_shape: tuple) -> tuple:
    """Output shape of one layer; in_shape is (C, H, W) or (F,)."""
    kind = layer["kind"]
    if kind == "conv":
        _, h, w = in_shape
        k, s, p = _g(layer, "ksize"), _g(layer, "stride"), _g(layer, "pad")
        return (_g(layer, "cout"), _conv_out(h, k, s, p),
                _conv_out(w, k, s, p))
    if kind in ("relu", "relu6", "dropout"):
        return tuple(in_shape)
    if kind == "maxpool":
        c, h, w = in_shape
        k, s = _g(layer, "ksize"), _g(layer, "stride")
        return (c, _conv_out(h, k, s, 0), _conv_out(w, k, s, 0))
    if kind == "avgpool":
        return (in_shape[0], _g(layer, "out_hw"), _g(layer, "out_hw"))
    if kind in ("linear", "gap_linear"):
        return (_g(layer, "features"),)
    if kind == "invres":
        _, h, w = in_shape
        s = _g(layer, "stride")
        return (_g(layer, "cout"), -(-h // s), -(-w // s))
    raise ValueError(f"unknown layer kind {kind!r}")


def shapes_through(layers: list[dict], in_shape: tuple) -> list[tuple]:
    """Per-layer output shapes."""
    out, shape = [], tuple(in_shape)
    for layer in layers:
        shape = out_shape(layer, shape)
        out.append(shape)
    return out


def layer_flops_params(layer: dict, in_shape: tuple) -> tuple[float, float]:
    """(FLOPs, parameter count) of one layer for one image."""
    kind = layer["kind"]
    out = out_shape(layer, in_shape)
    n_out = float(math.prod(out))
    if kind == "conv":
        cin, k, cout = in_shape[0], _g(layer, "ksize"), _g(layer, "cout")
        return 2 * k * k * cin * n_out, k * k * cin * cout + cout
    if kind in ("relu", "relu6"):
        return n_out, 0.0
    if kind == "dropout":
        return 0.0, 0.0
    if kind == "maxpool":
        return _g(layer, "ksize") ** 2 * n_out, 0.0
    if kind == "avgpool":
        return float(math.prod(in_shape)), 0.0
    if kind == "linear":
        fin, f = float(math.prod(in_shape)), _g(layer, "features")
        return 2 * fin * f, fin * f + f
    if kind == "gap_linear":
        fin, f = float(in_shape[0]), _g(layer, "features")
        return float(math.prod(in_shape)) + 2 * fin * f, fin * f + f
    if kind == "invres":
        cin, h, w = in_shape
        t, cout = _g(layer, "expand"), _g(layer, "cout")
        hidden = cin * t
        oh, ow = out[1], out[2]
        f = p = 0.0
        if t != 1:
            f += 2 * cin * hidden * h * w + hidden * h * w
            p += cin * hidden + 2 * hidden
        f += 2 * 9 * hidden * oh * ow + hidden * oh * ow
        p += 9 * hidden + 2 * hidden
        f += 2 * hidden * cout * oh * ow
        p += hidden * cout + 2 * cout
        if _g(layer, "stride") == 1 and cin == cout:
            f += cout * oh * ow
        return f, p
    raise ValueError(f"unknown layer kind {kind!r}")


def model_flops(layers: list[dict], in_shape: tuple) -> float:
    """FLOPs of one forward pass of one image."""
    total, shape = 0.0, tuple(in_shape)
    for layer in layers:
        total += layer_flops_params(layer, shape)[0]
        shape = out_shape(layer, shape)
    return total


def _launch(cin, h, w, cout, k, s, p, groups, pool, elem_bytes):
    oh, ow = _conv_out(h, k, s, p), _conv_out(w, k, s, p)
    flops = 2.0 * k * k * (cin // groups) * cout * oh * ow
    if pool:
        pk, ps = pool
        oh, ow = _conv_out(oh, pk, ps, 0), _conv_out(ow, pk, ps, 0)
    weights = k * k * (cin // groups) * cout + cout
    nbytes = elem_bytes * (cin * h * w + weights + cout * oh * ow)
    return {"flops": flops, "bytes": float(nbytes)}


def conv_launches(layers: list[dict], in_shape: tuple, elem_bytes: int,
                  cuts: tuple = ()) -> list[dict]:
    """The conv kernel calls of one image's walk through every layer,
    fused as the pallas path fuses them (see the module docstring).  No
    call fuses layers across a stage boundary (``cuts``)."""
    out, shape, i = [], tuple(in_shape), 0
    ends = set(cuts) | {len(layers)}

    def fuses(j: int) -> bool:       # layer j runs in the same stage as j-1
        return j < len(layers) and j not in ends
    while i < len(layers):
        layer = layers[i]
        kind = layer["kind"]
        if kind == "conv":
            c, h, w = shape
            step, pool = 1, None
            if fuses(i + 1) and layers[i + 1]["kind"] in ("relu", "relu6"):
                step = 2
                if fuses(i + 2) and layers[i + 2]["kind"] == "maxpool":
                    mp = layers[i + 2]
                    pool, step = (_g(mp, "ksize"), _g(mp, "stride")), 3
            out.append(_launch(c, h, w, _g(layer, "cout"), _g(layer, "ksize"),
                               _g(layer, "stride"), _g(layer, "pad"), 1,
                               pool, elem_bytes))
            for j in range(step):
                shape = out_shape(layers[i + j], shape)
            i += step
            continue
        if kind == "invres":
            c, h, w = shape
            hidden = c * _g(layer, "expand")
            if _g(layer, "expand") != 1:
                out.append(_launch(c, h, w, hidden, 1, 1, 0, 1, None,
                                   elem_bytes))
            s = _g(layer, "stride")
            out.append(_launch(hidden, h, w, hidden, 3, s, 1, hidden, None,
                               elem_bytes))
            oh, ow = _conv_out(h, 3, s, 1), _conv_out(w, 3, s, 1)
            out.append(_launch(hidden, oh, ow, _g(layer, "cout"), 1, 1, 0, 1,
                               None, elem_bytes))
        shape = out_shape(layer, shape)
        i += 1
    return out
