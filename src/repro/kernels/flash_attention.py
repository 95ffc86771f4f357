"""Flash attention (online softmax) as a Pallas TPU kernel.

TPU adaptation of the memory-hierarchy insight behind FlashAttention: tile
Q/K/V into VMEM blocks sized for the MXU (multiples of 128 on the matmul
dims), keep the running (m, l, acc) statistics in VMEM scratch across the
K-block loop, and never materialise the (Sq, Sk) score matrix in HBM.

Grid: (batch*heads, q_blocks, k_blocks); the k dimension is sequential
("arbitrary"), q and batch are parallel.  GQA is handled by the ops.py
wrapper (K/V indexed at kv_head = head // group)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr,
                  *, scale: float, causal: bool,
                  block_q: int, block_k: int, num_k_blocks: int,
                  sk_minus_sq: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)              # (block_q, hd)
    k = k_ref[0].astype(jnp.float32)              # (block_k, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if causal:
        qi = pl.program_id(1)
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0) + sk_minus_sq
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(cols <= rows, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)           # fully-masked rows -> 0
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None) -> jnp.ndarray:
    """q: (BH, Sq, hd); k, v: (BH, Sk, hd) -- pre-broadcast for GQA.

    Sq/Sk must be multiples of the block sizes (ops.py pads); hd should be
    a multiple of 128 on real hardware for MXU alignment (any hd works in
    interpret mode).  ``interpret`` None follows the platform."""
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    assert Sq % block_q == 0 and Sk % block_k == 0
    scale = scale if scale is not None else 1.0 / hd**0.5
    nq, nk = Sq // block_q, Sk // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_k_blocks=nk, sk_minus_sq=Sk - Sq)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode() if interpret is None else interpret,
    )(q, k, v)
