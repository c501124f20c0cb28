"""Chunked SSD (Mamba2) selective scan as a Pallas TPU kernel.

Mapping of the SSD algorithm to TPU:
 * grid = (B, nh, num_chunks); the chunk axis is sequential
   ("arbitrary") — the running SSM state h (N x P) is carried across
   chunk iterations in a VMEM scratch buffer, so the inter-chunk
   recurrence never leaves VMEM.
 * Within a chunk everything is dense matmul work for the MXU: the
   (Q x Q) decay-masked score matrix, the (Q x N) x (N x P) state
   readout, the (N x Q) x (Q x P) state update.  Q = chunk length
   (default 128, MXU-aligned).
 * B/C are single-group (shared across heads) — blocked per (b, chunk)
   and broadcast over the head grid axis.
 * The wrapper moves heads ahead of time and lays dt out as a lane-dense
   (1, T) row per head: the TPU tiles the last two block dims in
   (8, 128) units or takes them whole.

Oracle: kernels/ref.py::ssd_scan (the NAIVE O(T) recurrence, so the
kernel and the pure-jnp chunked path in models/mamba2.py are validated
against an independent formulation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, h_scr, *, chunk):
    ih = pl.program_id(1)
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)            # (Q, P)
    dt_row = dt_ref[0, 0].astype(jnp.float32)      # (1, Q)
    A = a_ref[ih]                                  # scalar, from SMEM
    Bm = b_ref[0].astype(jnp.float32)              # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)              # (Q, N)

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # the TPU lowers no cumsum and no (1, Q) -> (Q, 1) transpose: both
    # orientations of dt and of the inclusive prefix sum of log-decays
    # come from masked reductions over the (Q, Q) tile instead
    dt_col = jnp.sum(jnp.where(ii == jj, dt_row, 0.0), axis=1,
                     keepdims=True)                # (Q, 1)
    la_row = dt_row * A                            # negative
    la_col = dt_col * A
    cum_col = jnp.sum(jnp.where(jj <= ii, la_row, 0.0), axis=1,
                      keepdims=True)               # (Q, 1)
    cum_row = jnp.sum(jnp.where(ii <= jj, la_col, 0.0), axis=0,
                      keepdims=True)               # (1, Q)
    cum_last = jnp.sum(la_row, axis=1, keepdims=True)   # (1, 1)

    # intra-chunk: scores[i, j] = (C_i . B_j) exp(cum_i - cum_j) dt_j, j<=i
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (Q, Q)
    decay = jnp.exp(jnp.where(jj <= ii, cum_col - cum_row, -jnp.inf))
    scores = cb * decay * dt_row
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # (Q, P)

    # inter-chunk: y += (C exp(cum)) @ h_prev
    h_prev = h_scr[...]                            # (N, P)
    c_decay = Cm * jnp.exp(cum_col)                # (Q, N)
    y = y + jax.lax.dot_general(c_decay, h_prev, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # state update: h = exp(cum_last) h_prev + sum_j w_j B_j (x) x_j
    w = jnp.exp(cum_last - cum_col) * dt_col       # (Q, 1)
    bw = Bm * w                                    # (Q, N)
    h_new = jnp.exp(cum_last) * h_prev + jax.lax.dot_general(
        bw, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    h_scr[...] = h_new
    y_ref[0, 0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B_mat, C_mat, chunk: int = 128, *, interpret: bool):
    """x: (B, T, nh, P); dt: (B, T, nh); A: (nh,); B/C: (B, T, N).
    Returns y: (B, T, nh, P), h_final: (B, nh, N, P).

    Note: final state is recomputed by a cheap jnp epilogue (the kernel
    streams y); training only needs y — prefill uses the jnp path.
    """
    Bsz, T, nh, P = x.shape
    N = B_mat.shape[-1]
    Q = min(chunk, T)
    assert T % Q == 0, (T, Q)
    nc = T // Q

    # heads ahead of time, so every block's tiled dims are (Q, P) or
    # (1, Q); A rides in SMEM as a scalar-prefetch operand
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Bsz, nh, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c, a: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c, a: (b, h, 0, c)),
            pl.BlockSpec((1, Q, N), lambda b, h, c, a: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c, a: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda b, h, c, a: (b, h, c, 0)),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
    )
    y = pl.pallas_call(
        functools.partial(_kernel, chunk=Q),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Bsz, nh, T, P), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="ssd_scan",
        interpret=interpret,
    )(A.astype(jnp.float32), jnp.transpose(x, (0, 2, 1, 3)),
      jnp.transpose(dt, (0, 2, 1))[:, :, None, :], B_mat, C_mat)
    y = jnp.transpose(y, (0, 2, 1, 3))

    # epilogue: final chunk states via the closed-form per-chunk sums
    log_a = dt * A[None, None, :]
    cum = jnp.cumsum(log_a.reshape(Bsz, nc, Q, nh), axis=2)
    last = cum[:, :, -1:, :]
    w = jnp.exp(last - cum) * dt.reshape(Bsz, nc, Q, nh)
    s_local = jnp.einsum("bcqh,bcqn,bcqhp->bchnp", w,
                         B_mat.reshape(Bsz, nc, Q, N),
                         x.reshape(Bsz, nc, Q, nh, P))
    cd = jnp.exp(last[:, :, 0, :])                 # (B, nc, nh)

    def scan_body(h, inp):
        s, c = inp
        return c[:, :, None, None] * h + s, None

    h0 = jnp.zeros((Bsz, nh, N, P), jnp.float32)
    h_final, _ = jax.lax.scan(
        scan_body, h0, (jnp.moveaxis(s_local.astype(jnp.float32), 1, 0),
                        jnp.moveaxis(cd.astype(jnp.float32), 1, 0)))
    return y, h_final.astype(x.dtype)
