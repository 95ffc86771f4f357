"""Per-channel symmetric int8 boundary quantization (wire-dtype tier).

The split-boundary upload is the term SmartSplit's objectives are most
sensitive to (``I|l1 / B`` dominates both Eq. 4 latency and Eq. 9 energy on
mobile uplinks).  Shipping the boundary activation as int8 -- one byte per
element plus one fp32 absmax scale per channel -- cuts the wire payload
~4x vs fp32 at a bounded, reported accuracy cost.

Scheme (deterministic, so fault-free runs are reproducible bit-for-bit):

    absmax_c = max(|x_c|)                    per channel c
    scale_c  = absmax_c / 127   (1.0 when the channel is all-zero)
    q        = clip(round(x / scale_c), -127, 127)  as int8
    dequant  = q * scale_c                   (error <= scale_c / 2)

The fused Pallas kernel does the absmax reduce, scale, and round/clip in
one pass over each channel block (the channel axis is moved to the front
and the rest flattened to lanes); ``quantize_jnp`` / ``dequantize_jnp``
are the plain-jnp fallback -- the same ops in the same order, so the two
backends agree bitwise and either side of a link may use either path.

Channel convention: feature maps (ndim >= 3, layout (B, C, H, W)) quantize
per channel axis 1; flat tensors (ndim <= 2) quantize per-tensor (a single
scale) -- per-feature scales on a (B, 4096) flatten boundary would cost
more wire bytes than they save.  ``default_channel_axis`` encodes this so
the runtime codec, ``apply_split``, and the cost model all agree.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.dtype_policy import policy_jnp_dtype
from repro.kernels import interpret_mode

# Per-tile VMEM budget for the quantize kernel (fp32 in + int8 out + scales).
_VMEM_BUDGET = 8 * 1024 * 1024
_LANE = 128


def _use_pallas(backend: str | None = None) -> bool:
    """Quantize on the Pallas path iff the conv path does (same knob)."""
    b = backend or os.environ.get("REPRO_CONV_BACKEND", "xla")
    return b == "pallas"


def default_channel_axis(ndim: int) -> int | None:
    """Quantization-group axis: channels for feature maps, whole-tensor
    (None) for flat activations."""
    return 1 if ndim >= 3 else None


def scale_count(shape: tuple[int, ...], axis: int | None) -> int:
    """Number of fp32 scales shipped alongside an int8 payload."""
    return 1 if axis is None else int(shape[axis])


# ---------------------------------------------------------------------------
# Fused Pallas kernels (one pass per channel block)
# ---------------------------------------------------------------------------
def _quantize_kernel(x_ref, values_ref, scales_ref):
    x = x_ref[:].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0.0, absmax / 127.0, 1.0)
    scales_ref[:] = scale
    q = jnp.round(x / scale)
    values_ref[:] = jnp.clip(q, -127.0, 127.0).astype(jnp.int8)


def _dequantize_kernel(values_ref, scales_ref, out_ref):
    out_ref[:] = values_ref[:].astype(jnp.float32) * scales_ref[:]


def _block_c(c: int, n: int) -> int:
    """Channel-block rows whose fp32+int8 tile fits the VMEM budget."""
    rows = max(1, _VMEM_BUDGET // max(1, n * 5))
    rows = min(rows, 128)
    if rows >= 8:
        rows -= rows % 8  # sublane-friendly when compiled
    return max(1, min(rows, c))


def _quantize_pallas_2d(x2d, interpret: bool):
    """x2d: (C, N) fp32 -> (values int8 (C, N), scales fp32 (C, 1))."""
    c, n = x2d.shape
    n_pad = (-n) % _LANE
    xp = jnp.pad(x2d, ((0, 0), (0, n_pad))) if n_pad else x2d
    bc = _block_c(c, xp.shape[1])
    c_pad = (-c) % bc
    if c_pad:
        xp = jnp.pad(xp, ((0, c_pad), (0, 0)))
    cp, np_ = xp.shape
    values, scales = pl.pallas_call(
        _quantize_kernel,
        grid=(cp // bc,),
        in_specs=[pl.BlockSpec((bc, np_), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bc, np_), lambda i: (i, 0)),
                   pl.BlockSpec((bc, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((cp, np_), jnp.int8),
                   jax.ShapeDtypeStruct((cp, 1), jnp.float32)],
        interpret=interpret,
    )(xp)
    return values[:c, :n], scales[:c]


def _dequantize_pallas_2d(v2d, s2d, interpret: bool):
    """(C, N) int8 + (C, 1) fp32 scales -> (C, N) fp32."""
    c, n = v2d.shape
    n_pad = (-n) % _LANE
    vp = jnp.pad(v2d, ((0, 0), (0, n_pad))) if n_pad else v2d
    bc = _block_c(c, vp.shape[1])
    c_pad = (-c) % bc
    sp = s2d
    if c_pad:
        vp = jnp.pad(vp, ((0, c_pad), (0, 0)))
        sp = jnp.pad(sp, ((0, c_pad), (0, 0)))
    cp, np_ = vp.shape
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=(cp // bc,),
        in_specs=[pl.BlockSpec((bc, np_), lambda i: (i, 0)),
                  pl.BlockSpec((bc, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bc, np_), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cp, np_), jnp.float32),
        interpret=interpret,
    )(vp, sp)
    return out[:c, :n]


# ---------------------------------------------------------------------------
# Plain-jnp fallback (usable inside shard_map; bitwise-equal to the kernel)
# ---------------------------------------------------------------------------
def quantize_jnp(x, axis: int | None = None):
    """Quantize ``x`` per channel ``axis`` (None = per-tensor).

    Returns ``(values int8 like x, scales fp32 (C,))`` with C = 1 when
    per-tensor."""
    x32 = x.astype(jnp.float32)
    if axis is None:
        absmax = jnp.max(jnp.abs(x32)).reshape(1)
        sb = absmax  # broadcasts over everything
    else:
        axis = axis % x.ndim
        red = tuple(a for a in range(x.ndim) if a != axis)
        absmax = jnp.max(jnp.abs(x32), axis=red)
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]
        sb = absmax.reshape(shape)
    scale = jnp.where(absmax > 0.0, absmax / 127.0, 1.0)
    sb = jnp.where(sb > 0.0, sb / 127.0, 1.0)
    q = jnp.clip(jnp.round(x32 / sb), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def dequantize_jnp(values, scales, axis: int | None = None,
                   out_dtype=jnp.float32):
    """Invert ``quantize_jnp``: values * scale, cast to ``out_dtype``."""
    if axis is None:
        sb = scales.reshape(())
    else:
        axis = axis % values.ndim
        shape = [1] * values.ndim
        shape[axis] = values.shape[axis]
        sb = scales.reshape(shape)
    return (values.astype(jnp.float32) * sb).astype(out_dtype)


# ---------------------------------------------------------------------------
# Jit'd public wrappers (env knobs resolved at call time, ops.py idiom)
# ---------------------------------------------------------------------------
def _to_2d(x, axis: int):
    xm = jnp.moveaxis(x, axis, 0)
    return xm.reshape(x.shape[axis], -1), xm.shape


def _from_2d(x2d, moved_shape, axis: int, ndim: int):
    return jnp.moveaxis(x2d.reshape(moved_shape), 0, axis % ndim)


@functools.partial(jax.jit, static_argnames=("axis", "use_pallas",
                                             "interpret"))
def _quantize(x, *, axis, use_pallas, interpret):
    if not use_pallas:
        return quantize_jnp(x, axis)
    if axis is None:
        x2d = x.astype(jnp.float32).reshape(1, -1)
        v2d, s2d = _quantize_pallas_2d(x2d, interpret)
        return v2d.reshape(x.shape), s2d.reshape(1)
    x2d, moved = _to_2d(x.astype(jnp.float32), axis % x.ndim)
    v2d, s2d = _quantize_pallas_2d(x2d, interpret)
    return _from_2d(v2d, moved, axis, x.ndim), s2d.reshape(-1)


@functools.partial(jax.jit, static_argnames=("axis", "use_pallas",
                                             "interpret", "out_dtype"))
def _dequantize(values, scales, *, axis, use_pallas, interpret, out_dtype):
    if not use_pallas:
        return dequantize_jnp(values, scales, axis, out_dtype)
    if axis is None:
        v2d = values.reshape(1, -1)
        s2d = jnp.broadcast_to(scales.reshape(1, 1), (1, 1))
        out = _dequantize_pallas_2d(v2d, s2d, interpret)
        return out.reshape(values.shape).astype(out_dtype)
    v2d, moved = _to_2d(values, axis % values.ndim)
    out = _dequantize_pallas_2d(v2d, scales.reshape(-1, 1), interpret)
    return _from_2d(out, moved, axis, values.ndim).astype(out_dtype)


def quantize_boundary(x, axis: int | None = None, *,
                      backend: str | None = None):
    """Fused absmax+scale+round/clip quantize of a boundary activation.

    ``axis`` defaults to the channel convention for ``x.ndim``; ``backend``
    picks pallas-vs-jnp like the conv path (``REPRO_CONV_BACKEND``)."""
    if axis is None:
        axis = default_channel_axis(x.ndim)
    return _quantize(x, axis=axis, use_pallas=_use_pallas(backend),
                     interpret=interpret_mode())


def dequantize_boundary(values, scales, axis: int | None = None, *,
                        out_dtype=None, backend: str | None = None):
    """Invert ``quantize_boundary`` (values must carry its dtype/shape)."""
    if axis is None:
        axis = default_channel_axis(values.ndim)
    return _dequantize(values, scales, axis=axis,
                       use_pallas=_use_pallas(backend),
                       interpret=interpret_mode(),
                       out_dtype=out_dtype or jnp.float32)


def boundary_roundtrip(x, wire: str, *, axis: int | None = None,
                       backend: str | None = None):
    """What the receiver decodes when ``x`` ships under wire format
    ``wire``: quantize->dequantize for int8, downcast->upcast for a float
    wire format, back in ``x.dtype`` either way.  This is the exact math
    the runtime codec performs, so planners/tests/benches can model the
    end-to-end effect without a link."""
    if wire == "int8":
        if axis is None:
            axis = default_channel_axis(x.ndim)
        q, scales = quantize_boundary(x, axis, backend=backend)
        return dequantize_boundary(q, scales, axis, out_dtype=x.dtype,
                                   backend=backend)
    jdt = policy_jnp_dtype(wire)
    if x.dtype == jdt:
        return x
    return x.astype(jdt).astype(x.dtype)
