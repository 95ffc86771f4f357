"""Analytic operation and byte counts, kept with the benchmark.

A copy of the program's per-layer counter (``models/cnn.py::
layer_flops_params`` and ``layer_out_shape``) so that no change to the
program can move the yardstick.  Layers are the configuration files' plain
dicts (``{"kind": "conv", "cout": 64, "ksize": 3, ...}``), never the
program's objects; each kind's arithmetic is in its own file,
``bench/kinds/<kind>.py`` (``spec.load_kind``).

``conv_launches`` lists the convolution kernel calls one request makes on
the pallas path: a conv followed by relu/relu6 (and a maxpool after that)
is one call, and an inverted residual block is two or three.  Each call's
FLOPs are the multiply-adds of its convolution (2 per MAC); its bytes are
input, weights, bias and output at the storage dtype: the least the chip
must move for it."""
from __future__ import annotations

from bench import spec


def field(layer: dict, key: str) -> int:
    """A layer's whole-number field; stride defaults to 1, the rest to 0."""
    return int(layer.get(key, 1 if key == "stride" else 0))


def conv_out(h: int, k: int, s: int, p: int) -> int:
    return (h + 2 * p - k) // s + 1


def _kind(layer: dict):
    return spec.load_kind(layer["kind"])


def out_shape(layer: dict, in_shape: tuple) -> tuple:
    """Output shape of one layer; in_shape is (C, H, W) or (F,)."""
    return _kind(layer).out_shape(layer, in_shape)


def shapes_through(layers: list[dict], in_shape: tuple) -> list[tuple]:
    """Per-layer output shapes."""
    out, shape = [], tuple(in_shape)
    for layer in layers:
        shape = out_shape(layer, shape)
        out.append(shape)
    return out


def layer_flops_params(layer: dict, in_shape: tuple) -> tuple[float, float]:
    """(FLOPs, parameter count) of one layer for one image."""
    return _kind(layer).flops_params(layer, in_shape)


def model_flops(layers: list[dict], in_shape: tuple) -> float:
    """FLOPs of one forward pass of one image."""
    total, shape = 0.0, tuple(in_shape)
    for layer in layers:
        total += layer_flops_params(layer, shape)[0]
        shape = out_shape(layer, shape)
    return total


def launch(cin, h, w, cout, k, s, p, groups, pool, elem_bytes) -> dict:
    """One conv kernel call on a (cin, h, w) input, its output pooled by
    ``pool`` = (ksize, stride) where that is not None: its ``flops``,
    ``bytes`` and ``groups`` (``cin`` for a depthwise call)."""
    oh, ow = conv_out(h, k, s, p), conv_out(w, k, s, p)
    flops = 2.0 * k * k * (cin // groups) * cout * oh * ow
    if pool:
        pk, ps = pool
        oh, ow = conv_out(oh, pk, ps, 0), conv_out(ow, pk, ps, 0)
    weights = k * k * (cin // groups) * cout + cout
    nbytes = elem_bytes * (cin * h * w + weights + cout * oh * ow)
    return {"flops": flops, "bytes": float(nbytes), "groups": groups}


def folds_into_conv(layer: dict) -> str | None:
    """How a conv right before folds ``layer`` into its kernel call:
    ``"epilogue"``, ``"pool"`` (after an epilogue) or None."""
    return getattr(_kind(layer), "FOLDS_INTO_CONV", None)


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
        calls = getattr(_kind(layers[i]), "launches", None)
        step = 1
        if calls is not None:
            made, step = calls(layers, i, shape, elem_bytes, fuses)
            out += made
        for j in range(step):
            shape = out_shape(layers[i + j], shape)
        i += step
    return out
