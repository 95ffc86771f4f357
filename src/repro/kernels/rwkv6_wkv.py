"""RWKV6 WKV recurrence as a Pallas TPU kernel.

The WKV state S is an (hd, hd) matrix per (batch, head); the recurrence
  out_t = r_t . (S + diag(u) k_t v_t^T)
  S     = diag(w_t) S + k_t v_t^T
is strictly sequential in t, so the TPU adaptation keeps S resident in VMEM
scratch across a time-block loop (grid dim 2, "arbitrary") while (batch,
head) parallelise across cores.  Each grid step loads a (block_t, hd) tile
of r/k/v/w and walks it with a fori_loop -- HBM traffic is O(T*hd) per
head instead of O(T*hd^2) for a naive state-materialising implementation."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scr,
                *, block_t: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    u = u_ref[0].astype(jnp.float32)                    # (hd,)

    def step(t, s):
        rt = r_ref[0, t, 0].astype(jnp.float32)         # (hd,)
        kt = k_ref[0, t, 0].astype(jnp.float32)
        vt = v_ref[0, t, 0].astype(jnp.float32)
        wt = w_ref[0, t, 0].astype(jnp.float32)
        kv = kt[:, None] * vt[None, :]                  # (hd, hd)
        out = (rt[:, None] * (s + u[:, None] * kv)).sum(axis=0)
        o_ref[0, t, 0] = out.astype(o_ref.dtype)
        return s * wt[:, None] + kv

    s_scr[...] = jax.lax.fori_loop(0, block_t, step, s_scr[...])


def rwkv6_wkv(r, k, v, w, u, *, block_t: int = 64,
              interpret: bool | None = None):
    """r,k,v,w: (B, T, H, hd); u: (H, hd). Returns out (B, T, H, hd).

    T must be a multiple of block_t (ops.py pads).  ``interpret`` None
    follows the platform."""
    B, T, H, hd = r.shape
    assert T % block_t == 0
    nt = T // block_t
    kernel = functools.partial(_wkv_kernel, block_t=block_t)
    spec = pl.BlockSpec((1, block_t, 1, hd), lambda b, h, t: (b, t, h, 0))
    return pl.pallas_call(
        kernel,
        grid=(B, H, nt),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, hd), lambda b, h, t: (h, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, T, H, hd), r.dtype),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode() if interpret is None else interpret,
    )(r, k, v, w, u)
