"""Jit'd public wrappers around the Pallas kernels.

Handles padding to block multiples, GQA head broadcasting, and the
CPU-vs-TPU switch: ``interpret_mode()`` (interpret exactly when the
platform is the CPU, compile with Mosaic everywhere else) is read at
*call* time (mirroring ``models/cnn.py::conv_backend``) and threaded into
the jit'd inner functions as a static argument, so a trace made for one
platform is never reused for another.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.dtype_policy import conv_dtype, policy_jnp_dtype
from repro.kernels import interpret_mode
from repro.kernels import conv2d as _conv
from repro.kernels import flash_attention as _fa
from repro.kernels import mamba2_ssd as _ssd
from repro.kernels import rwkv6_wkv as _wkv
# Wire-dtype boundary codec (fused int8 quantize/dequantize + jnp fallback);
# re-exported here so callers reach every kernel through one surface.
from repro.kernels.quant import (boundary_roundtrip,  # noqa: F401
                                 dequantize_boundary, quantize_boundary)


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def _flash_attention_gqa(q, k, v, *, causal, block_q, block_k, interpret):
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    kb = jnp.repeat(k, g, axis=2)
    vb = jnp.repeat(v, g, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = kb.transpose(0, 2, 1, 3).reshape(B * H, -1, hd)
    vf = vb.transpose(0, 2, 1, 3).reshape(B * H, -1, hd)
    # sequence lengths must be block multiples (padding keys would need an
    # extra mask; callers pick block sizes that divide their seq lens)
    assert Sq % block_q == 0 and kf.shape[1] % block_k == 0, \
        (Sq, kf.shape[1], block_q, block_k)
    out = _fa.flash_attention(qf, kf, vf, causal=causal,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)


def flash_attention_gqa(q, k, v, *, causal: bool = True,
                        block_q: int = 128, block_k: int = 128):
    """q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd) with H % KV == 0."""
    return _flash_attention_gqa(q, k, v, causal=causal, block_q=block_q,
                                block_k=block_k, interpret=interpret_mode())


@functools.partial(jax.jit,
                   static_argnames=("stride", "pad", "activation", "groups",
                                    "pool_k", "pool_s", "tile_w", "search",
                                    "interpret"))
def _conv2d(x, w, *, stride, pad, bias, activation, groups, pool_k, pool_s,
            tile_w, search, interpret):
    return _conv.conv2d(x, w, stride=stride, pad=pad, bias=bias,
                        activation=activation, groups=groups,
                        pool_k=pool_k, pool_s=pool_s, tile_w=tile_w,
                        search=search, interpret=interpret)


def conv2d(x, w, *, stride: int = 1, pad: int = 0, bias=None,
           activation: str | None = None, groups: int = 1,
           pool_k: int = 0, pool_s: int = 0, dtype: str | None = None,
           tile_w: int = 0, search: bool | None = None):
    """Fused conv(+bias)(+relu/relu6)(+maxpool): one tiled kernel launch.

    ``bias`` (Cout,) and ``activation`` run in the kernel epilogue on the
    fp32 accumulator; ``groups`` is lax's ``feature_group_count`` (set to
    Cin for depthwise).  ``pool_k > 0`` fuses a VALID
    ``maxpool(pool_k, pool_s)`` after the activation so a paper-layer
    conv->relu->maxpool triple is a single launch -- the conv activation
    never round-trips HBM.

    ``dtype`` is the storage policy (``fp32`` | ``bf16``; default resolves
    ``REPRO_CONV_DTYPE`` at call time).  Under ``bf16`` the input and
    weights are stored/staged as bfloat16 -- the planner sees 2-byte
    elements and doubles ``tile_h`` for the same VMEM budget -- while the
    accumulator, bias add, activation, and pool epilogue all stay fp32;
    the output tensor is returned in the storage dtype.  ``fp32`` is the
    no-downcast default: tensors keep whatever dtype they already have.

    Tiling comes from the joint ``plan_conv`` cost-model search by
    default; ``tile_w`` pins the column tile and ``search=False`` falls
    back to the legacy greedy planner.  Both resolve their env knobs
    (``REPRO_CONV_TILE_W`` / ``REPRO_CONV_SEARCH``) at *call* time and are
    threaded into the jit as static arguments, so flipping an env var
    between calls retraces with the new plan instead of silently reusing
    the old grid."""
    if conv_dtype(dtype) == "bf16":
        jdt = policy_jnp_dtype("bf16")
        x = x if x.dtype == jdt else x.astype(jdt)
        w = w if w.dtype == jdt else w.astype(jdt)
    return _conv2d(x, w, stride=stride, pad=pad, bias=bias,
                   activation=activation, groups=groups,
                   pool_k=pool_k, pool_s=pool_s,
                   tile_w=_conv.tile_w_override(tile_w),
                   search=_conv.search_enabled(search),
                   interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def _rwkv6_wkv(r, k, v, w, u, *, block_t, interpret):
    r2, p = _pad_to(r, 1, block_t)
    k2, _ = _pad_to(k, 1, block_t)
    v2, _ = _pad_to(v, 1, block_t)
    w2, _ = _pad_to(w, 1, block_t)
    if p:
        # pad decay with ones (identity) so state evolution is unaffected
        w2 = w2.at[:, -p:].set(1.0)
    out = _wkv.rwkv6_wkv(r2, k2, v2, w2, u, block_t=block_t,
                         interpret=interpret)
    return out[:, :r.shape[1]]


def rwkv6_wkv(r, k, v, w, u, *, block_t: int = 64):
    return _rwkv6_wkv(r, k, v, w, u, block_t=block_t,
                      interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _mamba2_ssd(x, dt, A, B, C, *, chunk, interpret):
    T = x.shape[1]
    (x2, p) = _pad_to(x, 1, chunk)
    dt2, _ = _pad_to(dt, 1, chunk)
    B2, _ = _pad_to(B, 1, chunk)
    C2, _ = _pad_to(C, 1, chunk)
    out = _ssd.mamba2_ssd(x2, dt2, A, B2, C2, chunk=chunk,
                          interpret=interpret)
    return out[:, :T]


def mamba2_ssd(x, dt, A, B, C, *, chunk: int = 64):
    return _mamba2_ssd(x, dt, A, B, C, chunk=chunk,
                       interpret=interpret_mode())
