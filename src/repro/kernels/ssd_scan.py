"""Chunked SSD (Mamba2) selective scan as one differentiable op whose
forward and backward are Pallas TPU kernels.

``ssd_scan(x, dt, A, B_mat, C_mat)`` computes what
``models/mamba2.py::ssd_chunked`` computes from a zero state, and its
``jax.custom_vjp`` gives dx, ddt, dA, dB_mat and dC_mat.  Per chunk of Q
positions and head h (state N, head dim P):

    L[i, j]  = exp(cum_i - cum_j) for j <= i, else 0   (masked before exp)
    S        = (C B^T) * L * dt_j
    y        = S x + exp(cum_i) C h_prev
    h_new    = exp(cum_last) h_prev + B^T diag(exp(cum_last - cum) dt) x

Mapping to the TPU:
 * x, y and dx stay in the model's (B, T, nh*P) layout.  A grid step
   takes a block of ``k`` heads (``_block_heads``: 16 heads of 64, 1,024
   lanes, at the Mamba2 widths) and works through it in 128-lane groups
   of 128 // P heads, so every matmul has a full-width operand and no
   head-major copy of x or y is made.
 * grid = (B, chunks, head blocks), both inner axes sequential.  C B^T
   is built once per (batch, chunk), at the first head block, and kept
   in VMEM for the others; every (Q, Q) tile (C B^T, the masked decay,
   the scores and their gradients) lives only in VMEM.
 * The forward carries each head block's state (N, k*P) across chunks
   in VMEM and writes the state after every chunk: that (B, nc, N,
   nh*P) set is the op's one residual besides its inputs, and its last
   chunk is the final state.  The backward walks the chunks in reverse,
   carrying dh in VMEM, and sums dB and dC over every head of a chunk in
   VMEM before writing them once.
 * The in-chunk prefix sums of dt*A are computed outside (a small
   (B, T, nh) array), and d(cum) goes back through the reverse prefix sum
   to ddt and dA there.  dt and cum enter as lane-dense rows per head,
   (B, nh/k, k, T); the TPU lowers no (1, Q) -> (Q, 1) transpose, so
   column views come from masked reductions over the (Q, Q) tile.

Oracle: ``models/mamba2.py::ssd_chunked`` (and kernels/ref.py::ssd_scan,
the naive O(T) recurrence).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_BLOCK_LANES = 1024         # lanes of x a grid step takes (k heads x P)


def fits(nh: int, P: int, chunk: int) -> bool:
    """Whether the kernels tile these widths on a TPU: dt and cum enter
    as (k, chunk) blocks, so a chunk is whole 128-lane rows, and the
    heads fill 128-lane groups of x."""
    return chunk % _LANES == 0 and (
        P % _LANES == 0 or (_LANES % P == 0 and nh % (_LANES // P) == 0))


def _block_heads(nh: int, P: int) -> tuple[int, int]:
    """(heads per 128-lane group, heads per block)."""
    assert fits(nh, P, _LANES), (nh, P)         # heads fill lane groups
    hpg = max(1, _LANES // P)
    k = hpg
    while nh % (2 * k) == 0 and 2 * k * P <= _BLOCK_LANES:
        k *= 2
    return hpg, k


def _mxu(interpret: bool):
    """Operand dtype and precision of the kernels' dots.

    XLA gives the model's float32 einsums one bfloat16 MXU pass with a
    float32 accumulator on a TPU (its DEFAULT precision), so the compiled
    kernels round their dot operands to bfloat16 and accumulate in
    float32: the same precision as the XLA path they replace, and no
    lower.  Where XLA would multiply in float32 instead (a
    ``default_matmul_precision`` above DEFAULT, or the CPU, where the
    kernels are interpreted) the dots take float32 at HIGHEST.  A
    ``default_matmul_precision`` of bfloat16 asks for the chip's branch
    everywhere, so the CPU can check it interpreted.
    """
    setting = jax.config.jax_default_matmul_precision
    if setting in ("bfloat16", "BF16_BF16_F32") or (
            setting in (None, "default") and not interpret):
        return jnp.bfloat16, None
    return jnp.float32, jax.lax.Precision.HIGHEST


def _dot(a, b, dims, mxu):
    dtype, precision = mxu
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype),
                               (dims, ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)


_NN = ((1,), (0,))          # a @ b
_TN = ((0,), (0,))          # a.T @ b
_NT = ((1,), (1,))          # a @ b.T


class _Tile:
    """Iotas and masks over one chunk's (Q, Q) tile."""

    def __init__(self, Q):
        self.ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        self.jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        self.causal = self.jj <= self.ii
        self.eye = self.ii == self.jj
        self.last = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1) == Q - 1

    def col(self, row):
        """(1, Q) -> (Q, 1), exactly."""
        return jnp.sum(jnp.where(self.eye, row, 0.0), axis=1, keepdims=True)

    def row(self, col):
        """(Q, 1) -> (1, Q), exactly."""
        return jnp.sum(jnp.where(self.eye, col, 0.0), axis=0, keepdims=True)


def _head(tile, dt_ref, cum_ref, hd, cb):
    """One head's chunk quantities: dt and cum as rows and columns,
    cum_last, the masked decay and the scores."""
    dt_row = dt_ref[0, 0, pl.ds(hd, 1), :].astype(jnp.float32)   # (1, Q)
    cum_row = cum_ref[0, 0, pl.ds(hd, 1), :]                     # (1, Q)
    dt_col, cum_col = tile.col(dt_row), tile.col(cum_row)        # (Q, 1)
    cum_last = jnp.sum(jnp.where(tile.last, cum_row, 0.0), axis=1,
                       keepdims=True)                            # (1, 1)
    # mask BEFORE the exp: above the diagonal cum_i - cum_j > 0 can
    # overflow
    decay = jnp.exp(jnp.where(tile.causal, cum_col - cum_row, -jnp.inf))
    scores = cb * decay * dt_row
    return dt_row, dt_col, cum_col, cum_last, decay, scores


def _lane_heads(P, gw):
    """(1, gw) head index, within its 128-lane group, of every lane."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, gw), 1) // P


def _fwd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, y_ref, st_ref,
                h_scr, cb_scr, *, P, hpg, mxu):
    c, hb = pl.program_id(1), pl.program_id(2)
    Q, W = x_ref.shape[1], x_ref.shape[2]
    gw = hpg * P
    tile = _Tile(Q)

    @pl.when(c == 0)
    def _():
        h_scr[hb] = jnp.zeros(h_scr.shape[1:], jnp.float32)

    Bm = b_ref[0].astype(jnp.float32)                # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)

    @pl.when(hb == 0)
    def _():
        cb_scr[...] = _dot(Cm, Bm, _NT, mxu)

    cb = cb_scr[...]
    lane_head = _lane_heads(P, gw)
    for g in range(W // gw):
        lanes = pl.ds(g * gw, gw)
        x = x_ref[0, :, lanes].astype(jnp.float32)   # (Q, gw)
        h_prev = h_scr[hb, :, lanes]                 # (N, gw)
        y = jnp.zeros((Q, gw), jnp.float32)
        e_in = jnp.zeros((Q, gw), jnp.float32)       # exp(cum_i) by lane
        w_in = jnp.zeros((Q, gw), jnp.float32)       # exp(cum_last-cum_i) dt_i
        e_last = jnp.zeros((1, gw), jnp.float32)     # exp(cum_last)
        for j in range(hpg):
            dt_row, dt_col, cum_col, cum_last, _, scores = _head(
                tile, dt_ref, cum_ref, g * hpg + j, cb)
            mine = lane_head == j
            y = jnp.where(mine, _dot(scores, x, _NN, mxu), y)
            e_in = jnp.where(mine, jnp.exp(cum_col), e_in)
            w_in = jnp.where(mine, jnp.exp(cum_last - cum_col) * dt_col,
                             w_in)
            e_last = jnp.where(mine, jnp.exp(cum_last), e_last)
        y = y + e_in * _dot(Cm, h_prev, _NN, mxu)
        h_new = e_last * h_prev + _dot(Bm, w_in * x, _TN, mxu)
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        h_scr[hb, :, lanes] = h_new
        st_ref[0, 0, :, lanes] = h_new


def _bwd_kernel(x_ref, dy_ref, dt_ref, cum_ref, b_ref, c_ref, st_ref,
                dhf_ref, dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref,
                dh_scr, cb_scr, dcb_scr, db_scr, dc_scr, *, P, hpg, mxu):
    r, hb = pl.program_id(1), pl.program_id(2)
    nc, nhb = pl.num_programs(1), pl.num_programs(2)
    Q, W = x_ref.shape[1], x_ref.shape[2]
    k = dt_ref.shape[2]
    gw = hpg * P
    tile = _Tile(Q)

    @pl.when(r == 0)
    def _():
        dh_scr[hb] = dhf_ref[0].astype(jnp.float32)

    Bm = b_ref[0].astype(jnp.float32)
    Cm = c_ref[0].astype(jnp.float32)

    @pl.when(hb == 0)
    def _():
        cb_scr[...] = _dot(Cm, Bm, _NT, mxu)
        dcb_scr[...] = jnp.zeros(dcb_scr.shape, jnp.float32)
        db_scr[...] = jnp.zeros(db_scr.shape, jnp.float32)
        dc_scr[...] = jnp.zeros(dc_scr.shape, jnp.float32)

    cb = cb_scr[...]
    first = r == nc - 1                              # chunk 0: h_prev = 0
    lane_head = _lane_heads(P, gw)
    sub = jax.lax.broadcasted_iota(jnp.int32, (k, Q), 0)
    ddt_out = jnp.zeros((k, Q), jnp.float32)
    dcum_out = jnp.zeros((k, Q), jnp.float32)
    for g in range(W // gw):
        lanes = pl.ds(g * gw, gw)
        x = x_ref[0, :, lanes].astype(jnp.float32)   # (Q, gw)
        dy = dy_ref[0, :, lanes].astype(jnp.float32)
        h_prev = jnp.where(first, 0.0, st_ref[0, 0, :, lanes])   # (N, gw)
        dh = dh_scr[hb, :, lanes]                    # dL/dh after the chunk
        dx = jnp.zeros((Q, gw), jnp.float32)
        dcb = jnp.zeros((Q, Q), jnp.float32)
        e_in = jnp.zeros((Q, gw), jnp.float32)
        w_in = jnp.zeros((Q, gw), jnp.float32)
        e_last = jnp.zeros((1, gw), jnp.float32)
        heads = []
        for j in range(hpg):
            dt_row, dt_col, cum_col, cum_last, decay, scores = _head(
                tile, dt_ref, cum_ref, g * hpg + j, cb)
            mine = lane_head == j
            # intra-chunk: y = S x
            dS = _dot(jnp.where(mine, dy, 0.0), x, _NT, mxu)      # (Q, Q)
            dx = jnp.where(mine, _dot(scores, dy, _TN, mxu), dx)
            k_ = dS * decay
            dcb_j = k_ * dt_row
            dcb = dcb + dcb_j
            grad_s = dcb_j * cb                      # dS * S
            ddt_row = jnp.sum(k_ * cb, axis=0, keepdims=True)
            dcum_row = -jnp.sum(grad_s, axis=0, keepdims=True)
            dcum_col = jnp.sum(grad_s, axis=1, keepdims=True)
            w_col = jnp.exp(cum_last - cum_col) * dt_col
            e_in = jnp.where(mine, jnp.exp(cum_col), e_in)
            w_in = jnp.where(mine, w_col, w_in)
            e_last = jnp.where(mine, jnp.exp(cum_last), e_last)
            heads.append((mine, cum_col, cum_last, w_col, ddt_row,
                          dcum_row, dcum_col))
        dcb_scr[...] += dcb
        # inter-chunk: y += exp(cum) C h_prev; h_new = e_last h_prev + B^T (w x)
        y_inter = e_in * _dot(Cm, h_prev, _NN, mxu)
        e_dy = e_in * dy
        b_dh = _dot(Bm, dh, _NN, mxu)                # (Q, gw)
        dx = dx + w_in * b_dh
        dh_scr[hb, :, lanes] = e_last * dh + _dot(Cm, e_dy, _TN, mxu)
        dc_scr[...] += _dot(e_dy, h_prev, _NT, mxu)
        db_scr[...] += _dot(w_in * x, dh, _NT, mxu)
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
        b_dh_x, dy_y, dh_h = b_dh * x, dy * y_inter, dh * h_prev
        for j, (mine, cum_col, cum_last, w_col, ddt_row, dcum_row,
                dcum_col) in enumerate(heads):
            dw = jnp.sum(jnp.where(mine, b_dh_x, 0.0), axis=1,
                         keepdims=True)              # (Q, 1)
            dcum_col = (dcum_col - dw * w_col
                        + jnp.sum(jnp.where(mine, dy_y, 0.0), axis=1,
                                  keepdims=True))
            ddt_col = dw * jnp.exp(cum_last - cum_col)
            dh_h_j = jnp.sum(jnp.where(mine, dh_h, 0.0), axis=1,
                             keepdims=True)
            d_last = (jnp.sum(dw * w_col, axis=0, keepdims=True)
                      + jnp.exp(cum_last)
                      * jnp.sum(dh_h_j, axis=0, keepdims=True))
            ddt_row = ddt_row + tile.row(ddt_col)
            dcum_row = (dcum_row + tile.row(dcum_col)
                        + jnp.where(tile.last, d_last, 0.0))
            at = sub == g * hpg + j
            ddt_out = jnp.where(at, ddt_row, ddt_out)
            dcum_out = jnp.where(at, dcum_row, dcum_out)
    ddt_ref[0, 0] = ddt_out.astype(ddt_ref.dtype)
    dcum_ref[0, 0] = dcum_out

    @pl.when(hb == nhb - 1)
    def _():
        dcb_all = dcb_scr[...]
        db_ref[0] = (db_scr[...] + _dot(dcb_all, Cm, _TN, mxu)).astype(
            db_ref.dtype)
        dc_ref[0] = (dc_scr[...] + _dot(dcb_all, Bm, _NN, mxu)).astype(
            dc_ref.dtype)


def _rows(a, k):
    """(B, T, nh) -> per-head rows (B, nh // k, k, T)."""
    Bsz, T, nh = a.shape
    return jnp.transpose(a, (0, 2, 1)).reshape(Bsz, nh // k, k, T)


def _unrows(r):
    Bsz, nhb, k, T = r.shape
    return jnp.transpose(r.reshape(Bsz, nhb * k, T), (0, 2, 1))


def _cum(dt, A, Q):
    """In-chunk inclusive prefix sums of dt * A, (B, T, nh) float32."""
    Bsz, T, nh = dt.shape
    la = (dt.astype(jnp.float32) * A.astype(jnp.float32)).reshape(
        Bsz, T // Q, Q, nh)
    return jnp.cumsum(la, axis=2).reshape(Bsz, T, nh)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"))


def _forward(x, dt, A, B_mat, C_mat, chunk, interpret):
    Bsz, T, di = x.shape
    nh, N, Q = dt.shape[-1], B_mat.shape[-1], chunk
    P = di // nh
    hpg, k = _block_heads(nh, P)
    W, nc, nhb = k * P, T // Q, nh // k
    kernel = functools.partial(_fwd_kernel, P=P, hpg=hpg,
                               mxu=_mxu(interpret))
    row = pl.BlockSpec((1, 1, k, Q), lambda b, c, h: (b, h, 0, c))
    bc = pl.BlockSpec((1, Q, N), lambda b, c, h: (b, c, 0))
    blk = pl.BlockSpec((1, Q, W), lambda b, c, h: (b, c, h))
    y, states = pl.pallas_call(
        kernel,
        grid=(Bsz, nc, nhb),
        in_specs=[blk, row, row, bc, bc],
        out_specs=[blk, pl.BlockSpec((1, 1, N, W),
                                     lambda b, c, h: (b, c, 0, h))],
        out_shape=[jax.ShapeDtypeStruct((Bsz, T, di), x.dtype),
                   jax.ShapeDtypeStruct((Bsz, nc, N, di), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((nhb, N, W), jnp.float32),
                        pltpu.VMEM((Q, Q), jnp.float32)],
        compiler_params=_params(),
        name="ssd_fwd",
        interpret=interpret,
    )(x, _rows(dt, k), _rows(_cum(dt, A, Q), k), B_mat, C_mat)
    return y, states


def _final_state(states, nh, dtype):
    """(B, nc, N, nh*P) states -> the last chunk's as (B, nh, N, P)."""
    Bsz, _, N, di = states.shape
    h = states[:, -1].reshape(Bsz, N, nh, di // nh)
    return jnp.transpose(h, (0, 2, 1, 3)).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dt, A, B_mat, C_mat, chunk, interpret):
    y, states = _forward(x, dt, A, B_mat, C_mat, chunk, interpret)
    return y, _final_state(states, dt.shape[-1], x.dtype)


def _ssd_fwd(x, dt, A, B_mat, C_mat, chunk, interpret):
    y, states = _forward(x, dt, A, B_mat, C_mat, chunk, interpret)
    return ((y, _final_state(states, dt.shape[-1], x.dtype)),
            (x, dt, A, B_mat, C_mat, states))


def _ssd_bwd(chunk, interpret, res, cts):
    x, dt, A, B_mat, C_mat, states = res
    dy, dh_final = cts
    Bsz, T, di = x.shape
    nh, N, Q = dt.shape[-1], B_mat.shape[-1], chunk
    P = di // nh
    hpg, k = _block_heads(nh, P)
    W, nc, nhb = k * P, T // Q, nh // k
    cum = _cum(dt, A, Q)
    dhf = jnp.transpose(dh_final.astype(jnp.float32), (0, 2, 1, 3)).reshape(
        Bsz, N, di)
    kernel = functools.partial(_bwd_kernel, P=P, hpg=hpg,
                               mxu=_mxu(interpret))
    rev = lambda c: nc - 1 - c                       # noqa: E731
    row = pl.BlockSpec((1, 1, k, Q), lambda b, c, h: (b, h, 0, rev(c)))
    bc = pl.BlockSpec((1, Q, N), lambda b, c, h: (b, rev(c), 0))
    blk = pl.BlockSpec((1, Q, W), lambda b, c, h: (b, rev(c), h))
    # the state entering chunk rev(c) is the one the forward wrote after
    # chunk rev(c) - 1 (chunk 0 starts from zero: the kernel masks it)
    st = pl.BlockSpec((1, 1, N, W),
                      lambda b, c, h: (b, jnp.maximum(rev(c) - 1, 0), 0, h))
    dx, ddt, dcum, dB, dC = pl.pallas_call(
        kernel,
        grid=(Bsz, nc, nhb),
        in_specs=[blk, blk, row, row, bc, bc, st,
                  pl.BlockSpec((1, N, W), lambda b, c, h: (b, 0, h))],
        out_specs=[blk, row, row, bc, bc],
        out_shape=[jax.ShapeDtypeStruct((Bsz, T, di), x.dtype),
                   jax.ShapeDtypeStruct((Bsz, nhb, k, T), jnp.float32),
                   jax.ShapeDtypeStruct((Bsz, nhb, k, T), jnp.float32),
                   jax.ShapeDtypeStruct((Bsz, T, N), B_mat.dtype),
                   jax.ShapeDtypeStruct((Bsz, T, N), C_mat.dtype)],
        scratch_shapes=[pltpu.VMEM((nhb, N, W), jnp.float32),
                        pltpu.VMEM((Q, Q), jnp.float32),
                        pltpu.VMEM((Q, Q), jnp.float32),
                        pltpu.VMEM((Q, N), jnp.float32),
                        pltpu.VMEM((Q, N), jnp.float32)],
        compiler_params=_params(),
        name="ssd_bwd",
        interpret=interpret,
    )(x, dy, _rows(dt, k), _rows(cum, k), B_mat, C_mat, states, dhf)
    # d(cum) -> d(dt * A) through the reverse in-chunk prefix sum
    dla = jnp.flip(jnp.cumsum(jnp.flip(
        _unrows(dcum).reshape(Bsz, nc, Q, nh), axis=2), axis=2), axis=2)
    dla = dla.reshape(Bsz, T, nh)
    ddt = _unrows(ddt) + dla * A.astype(jnp.float32)
    dA = jnp.sum(dla * dt.astype(jnp.float32), axis=(0, 1))
    return dx, ddt.astype(dt.dtype), dA.astype(A.dtype), dB, dC


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B_mat, C_mat, chunk: int = 128, *, interpret: bool):
    """x: (B, T, nh*P) in the model's layout; dt: (B, T, nh), already
    softplus'd; A: (nh,); B/C: (B, T, N), single group; T a multiple of
    ``chunk``.  Returns y: (B, T, nh*P) and the final state (B, nh, N, P)
    from a zero initial state, differentiably."""
    assert x.shape[1] % chunk == 0, (x.shape, chunk)
    return _ssd(x, dt, A, B_mat, C_mat, chunk, interpret)
