"""Mamba2 SSD (state-space dual) chunked scan as a Pallas TPU kernel.

TPU adaptation of the SSD insight: within a chunk the recurrence
  h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T,   y_t = C_t . h_t
collapses into attention-like matmuls (MXU work), while the cross-chunk
state (hp, ds) lives in VMEM scratch and is carried across the sequential
chunk grid dimension:

  y_intra = ((C B^T) o decay_mask) @ (dt * x)       -- (c,c)x(c,hp) matmuls
  y_inter = exp(cum) * (C @ h_prev^T)
  h_next  = chunk_decay * h_prev + sum_u w_u B_u (dt_u x_u)^T

Grid: (batch, heads, chunks) with chunks "arbitrary" (sequential)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _ssd_kernel(x_ref, dt_ref, la_ref, b_ref, c_ref, o_ref, h_scr,
                *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, :, 0].astype(jnp.float32)        # (chunk, hp)
    dt = dt_ref[0, :, 0].astype(jnp.float32)      # (chunk,)
    la = la_ref[0, :, 0].astype(jnp.float32)      # (chunk,) log decay
    B = b_ref[0, :, 0].astype(jnp.float32)        # (chunk, ds)
    C = c_ref[0, :, 0].astype(jnp.float32)        # (chunk, ds)

    cs = jnp.cumsum(la)                           # (chunk,)
    # intra-chunk attention-like term
    seg = cs[:, None] - cs[None, :]               # (t, u)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(cols <= rows, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    att = cb * decay                              # (chunk, chunk)
    xdt = x * dt[:, None]
    y = jax.lax.dot_general(att, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: read carried state
    h = h_scr[...]                                # (hp, ds)
    y += jnp.exp(cs)[:, None] * jax.lax.dot_general(
        C, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    # state update: h_next = exp(cs_last) h + sum_u exp(cs_last-cs_u) dt_u x_u B_u^T
    w_u = jnp.exp(cs[-1] - cs) * dt               # (chunk,)
    new_contrib = jax.lax.dot_general(
        x * w_u[:, None], B, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)       # (hp, ds)
    h_scr[...] = h * jnp.exp(cs[-1]) + new_contrib
    o_ref[0, :, 0] = y.astype(o_ref.dtype)


def mamba2_ssd(x, dt, A, B, C, *, chunk: int = 64,
               interpret: bool | None = None):
    """x: (Bb, T, H, hp); dt: (Bb, T, H); A: (H,); B, C: (Bb, T, H, ds).
    Returns y (Bb, T, H, hp) with h0 = 0.  T must be a chunk multiple.
    ``interpret`` None follows the platform."""
    Bb, T, H, hp = x.shape
    ds = B.shape[-1]
    assert T % chunk == 0
    la = dt * A[None, None, :]                     # (Bb, T, H) log decay
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    sx = pl.BlockSpec((1, chunk, 1, hp), lambda b, h, c: (b, c, h, 0))
    s1 = pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h))
    sb = pl.BlockSpec((1, chunk, 1, ds), lambda b, h, c: (b, c, h, 0))
    return pl.pallas_call(
        kernel,
        grid=(Bb, H, T // chunk),
        in_specs=[sx, s1, s1, sb, sb],
        out_specs=sx,
        out_shape=jax.ShapeDtypeStruct((Bb, T, H, hp), x.dtype),
        scratch_shapes=[pltpu.VMEM((hp, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode() if interpret is None else interpret,
    )(x, dt, la, B, C)
