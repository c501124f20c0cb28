"""Jitted public wrappers for the Pallas kernels.

On a TPU backend the kernels compile for the chip; on any other backend
they run in interpret mode.  The Mamba2 block takes the fused SSD on a
TPU by itself (``models/mamba2.py::ssm_block_forward``), with the pure-jnp
``ssd_chunked`` as its oracle and its path elsewhere; attention and the
Parle updates call their kernels through ``use_flash=True`` /
``use_kernel=True``, with the pure-XLA paths as default and oracle.
"""
from __future__ import annotations

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa
from repro.kernels import parle_update as _pu
from repro.kernels import ssd_scan as _ssd


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, window: int = 0, block_q: int = 128,
                    block_k: int = 128):
    return _fa.flash_attention(q, k, v, window=window, block_q=block_q,
                               block_k=block_k, interpret=_interpret())


def paged_attention(q, k_pool, v_pool, table, lengths):
    """Single-token paged decode attention: q (B, H, hd) against the
    pages named by ``table`` (B, M), ``lengths`` (B,) live positions."""
    return _pa.paged_attention(q, k_pool, v_pool, table, lengths,
                               interpret=_interpret())


def ssd_scan(x, dt, A, B_mat, C_mat, chunk: int = 128):
    """The differentiable fused SSD from a zero state: x (B, T, nh*P),
    dt (B, T, nh), A (nh,), B/C (B, T, N), T a multiple of ``chunk``.
    Returns y (B, T, nh*P) and the final state (B, nh, N, P)."""
    return _ssd.ssd_scan(x, dt, A, B_mat, C_mat, chunk=chunk,
                         interpret=_interpret())


def parle_inner_update(y, z, v, g, x, *, inv_gamma, lr, mu, alpha,
                       shard_ctx=None):
    """``shard_ctx`` (repro.sharding.planner.ShardContext): present when
    the leaves are FSDP x TP sharded over in-replica mesh axes — each
    leaf's kernel then runs under a nested shard_map so the block grid
    covers the LOCAL shard only."""
    return _pu.parle_update_tree(y, z, v, g, x, inv_gamma=inv_gamma,
                                 lr=lr, mu=mu, alpha=alpha,
                                 interpret=_interpret(),
                                 shard_ctx=shard_ctx)


def parle_sync_update(x, z, v, xbar, *, gamma_scale, inv_rho, lr, mu,
                      shard_ctx=None, y_dtype=None):
    """Always returns (x', v', y') where y' is the inner-loop reset.
    For f32 compute y' IS x' (the same buffers — no cost); for bf16 the
    cast is fused into the kernel as a third output stream."""
    import jax.numpy as jnp
    emit_y = y_dtype is not None and jnp.dtype(y_dtype) != jnp.float32
    out = _pu.parle_sync_tree(x, z, v, xbar, gamma_scale=gamma_scale,
                              inv_rho=inv_rho, lr=lr, mu=mu,
                              interpret=_interpret(),
                              shard_ctx=shard_ctx,
                              y_dtype=y_dtype if emit_y else None)
    if emit_y:
        return out
    x2, v2 = out
    return x2, v2, x2


def elastic_worker_update(x, v, g, ref, *, inv_rho, lr, mu,
                          shard_ctx=None):
    return _pu.elastic_update_tree(x, v, g, ref, inv_rho=inv_rho,
                                   lr=lr, mu=mu, interpret=_interpret(),
                                   shard_ctx=shard_ctx)
