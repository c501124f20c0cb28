"""Causal flash attention (optionally sliding-window) as a Pallas TPU
kernel — blocked online-softmax (Rabe&Staats / FlashAttention), adapted
to the TPU memory hierarchy:

 * grid = (B, H, num_q_blocks, num_k_blocks); the k dimension is the
   innermost, sequential ("arbitrary") axis; (m, l, acc) running
   statistics live in VMEM scratch across k iterations.
 * The wrapper moves heads ahead of time, (B, T, H, hd) -> (B, H, T, hd),
   so each block's tiled dims are (block, hd): the TPU tiles the last
   two block dims in (8, 128) units or takes them whole.
 * Block shapes default to (128, head_dim): 128 is the MXU systolic
   dimension, so q @ k^T and p @ v are full-width MXU ops.
 * Causal + window masking is computed from absolute block offsets;
   fully-masked blocks still iterate (TPU grid is static) but write
   nothing — the hillclimb experiments quantify this (EXPERIMENTS.md).

Oracle: kernels/ref.py::flash_attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale, block_q, block_k, num_k_blocks, window, seq_len):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                             # (bq, hd)
    k = k_ref[0, 0]                             # (bk, hd)
    v = v_ref[0, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                         # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    correction = jnp.exp(m_prev - m_new)
    l_new = correction * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_scr[...] * correction + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ik == num_k_blocks - 1)
    def _flush():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, window: int = 0,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K, *,
                    interpret: bool):
    """q, k, v: (B, T, H, hd) — GQA already expanded.  Causal."""
    B, T, H, hd = q.shape
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    assert T % block_q == 0 and T % block_k == 0, (T, block_q, block_k)
    nq, nk = T // block_q, T // block_k
    scale = hd ** -0.5

    grid = (B, H, nq, nk)
    q_spec = pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j: (b, h, j, 0))

    kernel = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k,
        num_k_blocks=nk, window=window, seq_len=T)

    heads_first = lambda a: jnp.swapaxes(a, 1, 2)      # (B, H, T, hd)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, k_spec, k_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        name="flash_attention",
        interpret=interpret,
    )(heads_first(q), heads_first(k), heads_first(v))
    return heads_first(out)
