"""Pure-jnp oracles for every Pallas kernel.

These are the ground truth the kernels are asserted against
(tests/test_kernels_*.py sweep shapes and dtypes).  They are written in
the most direct form available — e.g. the SSD oracle is the O(T) naive
recurrence, deliberately NOT the chunked algorithm the kernel uses.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ------------------------------------------------------------------
# parle_update: fused Eq. (8a)-(8b) elementwise update
# ------------------------------------------------------------------

def parle_inner_update(y, z, v, g, x, *, inv_gamma, lr, mu, alpha):
    """One fused Parle inner step on flat arrays.

    g_y = g + inv_gamma * (y - x)
    v'  = mu v + g_y
    y'  = y - lr (g_y + mu v')
    z'  = alpha z + (1 - alpha) y'
    Returns (y', z', v').
    """
    g_y = g + inv_gamma * (y - x)
    v_new = mu * v + g_y
    y_new = y - lr * (g_y + mu * v_new)
    z_new = alpha * z + (1.0 - alpha) * y_new
    return y_new, z_new, v_new


# ------------------------------------------------------------------
# parle_sync_update: fused Eq. (8c)-(8d) elementwise update
# ------------------------------------------------------------------

def parle_sync_update(x, z, v, xbar, *, gamma_scale, inv_rho, lr, mu):
    """One fused Parle sync step on flat arrays (xbar precomputed — the
    cross-replica mean is the collective, not the kernel's job).

    g_x = gamma_scale (x - z) + inv_rho (x - xbar)
    v'  = mu v + g_x
    x'  = x - lr (g_x + mu v')
    Returns (x', v').
    """
    g_x = gamma_scale * (x - z) + inv_rho * (x - xbar)
    v_new = mu * v + g_x
    x_new = x - lr * (g_x + mu * v_new)
    return x_new, v_new


# ------------------------------------------------------------------
# elastic_update: fused Eq. (7a) worker update (Elastic-SGD)
# ------------------------------------------------------------------

def elastic_worker_update(x, v, g, ref, *, inv_rho, lr, mu):
    """One fused Elastic-SGD worker step on flat arrays (ref is the
    shared reference variable — its (7b) update is not the kernel's job).

    g_e = g + inv_rho (x - ref)
    v'  = mu v + g_e
    x'  = x - lr (g_e + mu v')
    Returns (x', v').
    """
    g_e = g + inv_rho * (x - ref)
    v_new = mu * v + g_e
    x_new = x - lr * (g_e + mu * v_new)
    return x_new, v_new


# ------------------------------------------------------------------
# flash_attention: causal (optionally sliding-window) MHA
# ------------------------------------------------------------------

def flash_attention(q, k, v, window: int = 0):
    """q, k, v: (B, T, H, hd) — post-GQA-expansion.  Causal softmax."""
    T = q.shape[1]
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    qpos = jnp.arange(T)[:, None]
    kpos = jnp.arange(T)[None, :]
    mask = kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ------------------------------------------------------------------
# paged_attention: single-token decode over a paged KV pool
# ------------------------------------------------------------------

def paged_attention(q, k_pool, v_pool, table, lengths):
    """q: (B, H, hd) — one decode token per row, GQA unexpanded.
    k_pool/v_pool: (P, ps, KV, hd); table: (B, M) page ids; lengths:
    (B,) live positions.  Gathers each row's pages into the contiguous
    extent and runs masked softmax attention — the most direct form.
    Returns (B, H, hd)."""
    B, H, hd = q.shape
    P, ps, KV, _ = k_pool.shape
    M = table.shape[1]
    S = M * ps
    group = H // KV
    k = k_pool[table].reshape(B, S, KV, hd)
    v = v_pool[table].reshape(B, S, KV, hd)
    k = jnp.repeat(k, group, axis=2)                   # (B, S, H, hd)
    v = jnp.repeat(v, group, axis=2)
    scale = hd ** -0.5
    logits = jnp.einsum("bhd,bshd->bhs", q, k).astype(jnp.float32) * scale
    live = jnp.arange(S)[None, None, :] < lengths[:, None, None]
    logits = jnp.where(live, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhs,bshd->bhd", probs, v)


# ------------------------------------------------------------------
# ssd_scan: naive O(T) selective-scan recurrence
# ------------------------------------------------------------------

def ssd_scan(x, dt, A, B_mat, C_mat, h0=None):
    """Naive recurrence oracle.

    x: (B, T, nh, P); dt: (B, T, nh); A: (nh,); B/C: (B, T, N).
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t;  y_t = C_t . h_t
    Returns y: (B, T, nh, P), h_final: (B, nh, N, P).
    """
    Bsz, T, nh, P = x.shape
    N = B_mat.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((Bsz, nh, N, P), jnp.float32)

    def step(h, inp):
        xt, dtt, bt, ct = inp          # (B,nh,P), (B,nh), (B,N), (B,N)
        a = jnp.exp(dtt * A)           # (B, nh)
        dBx = jnp.einsum("bh,bn,bhp->bhnp", dtt, bt, xt)
        h_new = a[:, :, None, None] * h + dBx
        y = jnp.einsum("bn,bhnp->bhp", ct, h_new)
        return h_new, y

    inps = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
            jnp.moveaxis(B_mat, 1, 0), jnp.moveaxis(C_mat, 1, 0))
    h_final, ys = jax.lax.scan(step, h0.astype(jnp.float32),
                               jax.tree.map(lambda a: a.astype(jnp.float32), inps))
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), h_final.astype(x.dtype)
