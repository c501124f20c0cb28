"""Paged decode attention as a Pallas TPU kernel.

One query token per batch row (the serving engine's decode step), KV
scattered across fixed-size pages addressed through a per-slot page
table.  The table and the per-row live lengths are SCALAR-PREFETCH
operands (``pltpu.PrefetchScalarGridSpec``): they are available before
the kernel body runs, so each grid step's BlockSpec index_map picks the
page to DMA directly from the table — the kernel never gathers the
whole extent into a contiguous buffer the way the jnp reference path
(``attention.paged_gather``) must.

 * grid = (B, max_pages); pages are the innermost, sequential axis —
   (m, l, acc) online-softmax statistics for all H heads live in VMEM
   scratch across page iterations, exactly the flash_attention
   recurrence with a page as the k-block.
 * A grid step DMAs one whole page, every kv head of it: its tiled dims
   (KV, hd) are the pool's own, as the TPU requires of a block that is
   not a multiple of (8, 128).  GQA is folded into the body: kv head g
   serves query heads [g*group, (g+1)*group) in one (group, ps) score
   matmul; no materialized head expansion.
 * Positions past a row's live length mask to -inf; a slot's unused
   table entries name the trash page (paging.TRASH_PAGE) whose
   positions are always past the length, so garbage pages never
   contribute.

Oracle: kernels/ref.py::paged_attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(table_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale, page_size, num_pages_per_row,
            num_kv, group):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    live = pos < lengths_ref[b]

    # one kv head at a time, with the `group` query heads that read it
    for g in range(num_kv):
        heads = slice(g * group, (g + 1) * group)
        q = q_ref[0, heads, :]                  # (group, hd)
        k = k_ref[0, :, g, :]                   # (ps, hd)
        v = v_ref[0, :, g, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(live, s, NEG_INF)         # (group, ps)

        m_prev = m_scr[heads, :]                # (group, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_scr[heads, :] = (correction * l_scr[heads, :]
                           + jnp.sum(p, axis=1, keepdims=True))
        acc_scr[heads, :] = acc_scr[heads, :] * correction + \
            jax.lax.dot_general(p.astype(v.dtype), v,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m_scr[heads, :] = m_new

    @pl.when(j == num_pages_per_row - 1)
    def _flush():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pool, v_pool, table, lengths, interpret: bool):
    """q: (B, H, hd) — ONE decode token per row, GQA unexpanded.
    k_pool/v_pool: (P, ps, KV, hd); table: (B, M) int32 page ids;
    lengths: (B,) int32 live positions (>= 1).  Returns (B, H, hd)."""
    B, H, hd = q.shape
    P, ps, KV, _ = k_pool.shape
    M = table.shape[1]

    kernel = functools.partial(_kernel, scale=hd ** -0.5, page_size=ps,
                               num_pages_per_row=M, num_kv=KV,
                               group=H // KV)
    row = pl.BlockSpec((1, H, hd), lambda b, j, tbl, ln: (b, 0, 0))
    page = pl.BlockSpec((1, ps, KV, hd),
                        lambda b, j, tbl, ln: (tbl[b, j], 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # table, lengths
        grid=(B, M),
        in_specs=[row, page, page],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="paged_attention",
        interpret=interpret,
    )(table.astype(jnp.int32), lengths.astype(jnp.int32), q, k_pool, v_pool)
