"""Mamba2 (SSD — state-space duality, arXiv:2405.21060).

The selective state space recurrence per head h (state N, head dim P):

    h_t = a_t * h_{t-1} + dt_t * B_t (x) x_t        a_t = exp(dt_t * A)
    y_t = C_t . h_t + D * x_t

computed with the chunked SSD algorithm: quadratic attention-like math
inside chunks of length Q = cfg.ssm_chunk, a linear recurrence across
chunk states.  ``ssd_chunked`` here is the pure-jnp oracle, and the path
off the TPU and from a carried state; on a TPU the block takes the fused
Pallas forward and backward of kernels/ssd_scan.py (``_fused_ssd``).

Single group (B, C shared across heads), depthwise causal conv of width
``ssm_conv`` over the xBC streams, gated RMSNorm before out-projection —
the standard Mamba2 block.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.kernels import ops as kops
from repro.kernels import ssd_scan as ssd_kernel
from repro.models.layers import dense_init, rms_norm, silu, softplus
from repro.utils.scan import layer_unroll


class SSMCache(NamedTuple):
    conv: jax.Array     # (L, B, W-1, conv_dim) ring of recent xBC inputs
    state: jax.Array    # (L, B, nh, N, P) SSM states
    pos: jax.Array      # () int32


# ------------------------------------------------------------------
# Parameters
# ------------------------------------------------------------------

def init_ssm_layer(key, cfg, dtype=jnp.float32):
    d, di, N = cfg.d_model, cfg.ssm_inner, cfg.ssm_state
    nh = cfg.ssm_num_heads
    conv_dim = di + 2 * N
    k1, k2, k3, k4 = jax.random.split(key, 4)
    # in_proj -> [z(di), xBC(di+2N), dt(nh)]
    p = {
        "ln": jnp.ones((d,), dtype),
        "in_proj": dense_init(k1, (d, 2 * di + 2 * N + nh), dtype=dtype),
        "conv_w": (jax.random.normal(k2, (cfg.ssm_conv, conv_dim)) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(dtype),
        "D": jnp.ones((nh,), dtype),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(k3, (nh,),
                    minval=jnp.log(1e-3), maxval=jnp.log(1e-1))))).astype(dtype),
        "norm": jnp.ones((di,), dtype),
        "out_proj": dense_init(k4, (di, d), dtype=dtype),
    }
    return p


def init_stacked_ssm(key, cfg, num_layers=None, dtype=jnp.float32):
    L = cfg.num_layers if num_layers is None else num_layers
    keys = jax.random.split(key, L)
    layers = [init_ssm_layer(k, cfg, dtype) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


# ------------------------------------------------------------------
# Chunked SSD (pure-jnp oracle; the Pallas kernel mirrors this)
# ------------------------------------------------------------------

def ssd_chunked(x, dt, A, B_mat, C_mat, chunk: int, h0=None):
    """Chunked selective scan.

    x:     (B, T, nh, P)
    dt:    (B, T, nh)           already softplus'd
    A:     (nh,)                negative reals
    B_mat: (B, T, N)            single group
    C_mat: (B, T, N)
    h0:    optional (B, nh, N, P) initial state
    Returns y: (B, T, nh, P), final state (B, nh, N, P).
    """
    Bsz, T, nh, P = x.shape
    N = B_mat.shape[-1]
    Q = min(chunk, T)
    T_orig = T
    if T % Q:
        # pad with dt=0 positions: a=1 and dB=0, so padding is inert
        pad = Q - T % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_mat = jnp.pad(B_mat, ((0, 0), (0, pad), (0, 0)))
        C_mat = jnp.pad(C_mat, ((0, 0), (0, pad), (0, 0)))
        T = T + pad
    nc = T // Q

    xc = x.reshape(Bsz, nc, Q, nh, P)
    dtc = dt.reshape(Bsz, nc, Q, nh)
    Bc = B_mat.reshape(Bsz, nc, Q, N)
    Cc = C_mat.reshape(Bsz, nc, Q, N)

    log_a = dtc * A                                  # (B, nc, Q, nh), negative
    cum = jnp.cumsum(log_a, axis=2)                  # inclusive within chunk

    # intra-chunk: scores[i,j] = (C_i . B_j) exp(cum_i - cum_j) dt_j, j <= i
    cb = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)       # (B, nc, Q, Q)
    delta = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # (B,nc,Q,Q,nh)
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    # mask BEFORE the exp: above the diagonal delta > 0 can overflow, and
    # where(mask, inf, 0) has a NaN gradient (0 * inf)
    decay = jnp.exp(jnp.where(mask[None, None, :, :, None], delta, -jnp.inf))
    scores = cb[..., None] * decay * dtc[:, :, None, :, :]  # (B,nc,Q,Q,nh)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", scores, xc)

    # per-chunk local state: sum_j exp(cum_last - cum_j) dt_j B_j (x) x_j
    last = cum[:, :, -1:, :]                         # (B, nc, 1, nh)
    w = jnp.exp(last - cum) * dtc                    # (B, nc, Q, nh)
    s_local = jnp.einsum("bcqh,bcqn,bcqhp->bchnp", w, Bc, xc)
    chunk_decay = jnp.exp(last[:, :, 0, :])          # (B, nc, nh)

    def scan_body(h_prev, inp):
        s_loc, c_dec, cum_c, C_ch = inp
        # h_prev: (B, nh, N, P)
        y_int = jnp.einsum("bqn,bhnp,bqh->bqhp", C_ch, h_prev,
                           jnp.exp(cum_c))
        h_new = c_dec[:, :, None, None] * h_prev + s_loc
        return h_new, y_int

    if h0 is None:
        h0 = jnp.zeros((Bsz, nh, N, P), x.dtype)
    # move chunk axis first for the scan
    inps = (
        jnp.moveaxis(s_local, 1, 0),
        jnp.moveaxis(chunk_decay, 1, 0),
        jnp.moveaxis(cum, 1, 0),
        jnp.moveaxis(Cc, 1, 0),
    )
    h_final, y_inter = jax.lax.scan(scan_body, h0.astype(x.dtype), inps)
    y_inter = jnp.moveaxis(y_inter, 0, 1)            # (B, nc, Q, nh, P)

    y = (y_intra + y_inter).reshape(Bsz, T, nh, P)
    return y[:, :T_orig], h_final


def ssd_decode(x, dt, A, B_mat, C_mat, h):
    """One token.  x: (B, nh, P); dt: (B, nh); B/C: (B, N); h: (B, nh, N, P)."""
    a = jnp.exp(dt * A)                              # (B, nh)
    dBx = jnp.einsum("bh,bn,bhp->bhnp", dt, B_mat, x)
    h_new = a[:, :, None, None] * h + dBx
    y = jnp.einsum("bn,bhnp->bhp", C_mat, h_new)
    return y, h_new


# ------------------------------------------------------------------
# Block forward
# ------------------------------------------------------------------

def _split_proj(cfg, proj):
    di, N, nh = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_num_heads
    z = proj[..., :di]
    xBC = proj[..., di:di + di + 2 * N]
    dt = proj[..., di + di + 2 * N:]
    return z, xBC, dt


def _causal_conv(xBC, w, b, prefix=None):
    """Depthwise causal conv.  xBC: (B, T, C); w: (W, C).

    ``prefix``: optional (B, W-1, C) ring of raw xBC inputs preceding
    this segment (chunk-resumed prefill); None pads with zeros — and a
    zero prefix is bitwise identical to the zero padding.
    """
    W = w.shape[0]
    if prefix is None:
        pad = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    else:
        pad = jnp.concatenate([prefix.astype(xBC.dtype), xBC], axis=1)
    out = jnp.zeros_like(xBC)
    for i in range(W):
        out = out + pad[:, i:i + xBC.shape[1], :] * w[i]
    return silu(out + b)


def _fused_ssd(cfg, T, h0) -> bool:
    """Whether the block takes the fused Pallas SSD: on a TPU, the
    backend it is compiled for, from a zero state, over whole chunks of
    widths the kernels tile (``kernels/ssd_scan.py::fits``)."""
    return (h0 is None and T % cfg.ssm_chunk == 0
            and ssd_kernel.fits(cfg.ssm_num_heads, cfg.ssm_head_dim,
                                cfg.ssm_chunk)
            and jax.default_backend() == "tpu")


def ssm_block_forward(lp, cfg, x, h0=None):
    """x: (B, T, d) -> (B, T, d), final_state."""
    Bsz, T, d = x.shape
    di, N, nh, P = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_num_heads, cfg.ssm_head_dim
    with jax.named_scope("in_proj"):
        u = rms_norm(x, lp["ln"], cfg.norm_eps)
        proj = checkpoint_name(
            jnp.einsum("btd,de->bte", u, lp["in_proj"]), "proj")
    with jax.named_scope("conv"):
        z, xBC, dt = _split_proj(cfg, proj)
        xBC = _causal_conv(xBC, lp["conv_w"], lp["conv_b"])
        xs = xBC[..., :di]
        B_mat = xBC[..., di:di + N]
        C_mat = xBC[..., di + N:]
    with jax.named_scope("ssd"):
        dt = softplus(dt + lp["dt_bias"])
        A = -jnp.exp(lp["A_log"])
        if _fused_ssd(cfg, T, h0):
            # heads stay in the (B, T, nh*P) layout, D repeated per lane
            y, hf = kops.ssd_scan(xs, dt, A, B_mat, C_mat, cfg.ssm_chunk)
            y = y + jnp.repeat(lp["D"], P) * xs
        else:
            xs = xs.reshape(Bsz, T, nh, P)
            y, hf = ssd_chunked(xs, dt, A, B_mat, C_mat, cfg.ssm_chunk, h0=h0)
            y = (y + lp["D"][None, None, :, None] * xs).reshape(Bsz, T, di)
    with jax.named_scope("out_proj"):
        y = rms_norm(y * silu(z), lp["norm"], cfg.norm_eps)
        return x + jnp.einsum("bte,ed->btd", y, lp["out_proj"]), hf


def ssm_block_prefill(lp, cfg, x, h0, conv0, valid):
    """Chunk-resumable SSM block: state AND conv ring threaded across
    segment boundaries, padded tail made exactly inert.

    x: (B, C, d); h0: (B, nh, N, P); conv0: (B, W-1, conv_dim) raw-xBC
    ring entering this segment; valid: () int32 — positions >= valid
    are padding.  Forcing their dt to exactly 0 AFTER softplus makes
    them inert in the SSD recurrence (decay exp(0·A)=1, update
    dt·B⊗x=0), matching ``ssd_chunked``'s own dt=0 chunk padding, so a
    segmented prefill reproduces the one-shot scan state.  Segment
    length must be a multiple of cfg.ssm_chunk for the chunk
    decomposition to coincide bitwise (the engine rounds prefill_chunk
    up).  Returns (out, h_final, new_ring).
    """
    Bsz, T, d = x.shape
    di, N, nh, P = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_num_heads, cfg.ssm_head_dim
    u = rms_norm(x, lp["ln"], cfg.norm_eps)
    proj = jnp.einsum("btd,de->bte", u, lp["in_proj"])
    z, xBC_raw, dt = _split_proj(cfg, proj)
    xBC = _causal_conv(xBC_raw, lp["conv_w"], lp["conv_b"], prefix=conv0)
    xs = xBC[..., :di].reshape(Bsz, T, nh, P)
    B_mat = xBC[..., di:di + N]
    C_mat = xBC[..., di + N:]
    dt = softplus(dt + lp["dt_bias"])
    dt = jnp.where((jnp.arange(T) < valid)[None, :, None], dt, 0.0)
    A = -jnp.exp(lp["A_log"])
    y, hf = ssd_chunked(xs, dt, A, B_mat, C_mat, cfg.ssm_chunk, h0=h0)
    y = y + lp["D"][None, None, :, None] * xs
    y = y.reshape(Bsz, T, di)
    y = rms_norm(y * silu(z), lp["norm"], cfg.norm_eps)
    out = x + jnp.einsum("bte,ed->btd", y, lp["out_proj"])
    # ring leaving the segment: raw xBC of the W-1 positions before
    # ``valid`` (reaching into conv0 when the segment is shorter)
    hist = jnp.concatenate([conv0.astype(xBC_raw.dtype), xBC_raw], axis=1)
    W = cfg.ssm_conv
    ring = jax.lax.dynamic_slice(
        hist, (0, valid, 0), (Bsz, W - 1, hist.shape[-1]))
    return out, hf, ring


def ssm_block_decode(lp, cfg, x, conv_cache, h):
    """x: (B, 1, d); conv_cache: (B, W-1, conv_dim); h: (B, nh, N, P)."""
    Bsz = x.shape[0]
    di, N, nh, P = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_num_heads, cfg.ssm_head_dim
    u = rms_norm(x, lp["ln"], cfg.norm_eps)
    proj = jnp.einsum("btd,de->bte", u, lp["in_proj"])[:, 0]
    z, xBC, dt = _split_proj(cfg, proj)
    # conv over [cache, current]
    W = cfg.ssm_conv
    window = jnp.concatenate([conv_cache, xBC[:, None, :]], axis=1)  # (B, W, C)
    conv_out = silu(jnp.einsum("bwc,wc->bc", window, lp["conv_w"]) + lp["conv_b"])
    new_conv = window[:, 1:]
    xs = conv_out[..., :di].reshape(Bsz, nh, P)
    B_mat = conv_out[..., di:di + N]
    C_mat = conv_out[..., di + N:]
    dtv = softplus(dt + lp["dt_bias"])
    A = -jnp.exp(lp["A_log"])
    y, h_new = ssd_decode(xs, dtv, A, B_mat, C_mat, h)
    y = y + lp["D"][None, :, None] * xs
    y = y.reshape(Bsz, di)
    y = rms_norm(y * silu(z), lp["norm"], cfg.norm_eps)
    out = x + jnp.einsum("be,ed->bd", y, lp["out_proj"])[:, None, :]
    return out, new_conv, h_new


# ------------------------------------------------------------------
# Full model (family == "ssm")
# ------------------------------------------------------------------

def init_params(key, cfg, dtype=jnp.float32):
    from repro.models.layers import embed_init
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "embed": embed_init(k1, (cfg.vocab_size, cfg.d_model), dtype),
        "layers": init_stacked_ssm(k2, cfg, dtype=dtype),
        "ln_f": jnp.ones((cfg.d_model,), dtype),
        "head": dense_init(k3, (cfg.d_model, cfg.vocab_size), dtype=dtype),
    }


# What the layer scan keeps for the backward besides each layer's input:
# the in_proj output.  The backward recomputes the rest from it: the norms,
# the split, the conv, the SiLUs, softplus(dt), the gate, and the SSD's
# forward (on a TPU its fused kernel runs again, which costs less than
# writing and reading back its output and chunk states).  Outside a
# gradient the name does nothing.
SAVED = ("proj",)


def forward_hidden(params, cfg, tokens, remat=False):
    """``remat`` False saves by ``SAVED``; True recomputes the whole layer,
    "dots" keeps matmul outputs (``transformer._remat``)."""
    with jax.named_scope("embed"):
        x = params["embed"][tokens]

    def body(h, lp):
        out, _ = ssm_block_forward(lp, cfg, h)
        return out, jnp.zeros((), jnp.float32)

    if remat:
        from repro.models.transformer import _remat
        body = _remat(body, remat)
    else:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names(*SAVED))
    x, _ = jax.lax.scan(body, x, params["layers"], unroll=layer_unroll())
    return rms_norm(x, params["ln_f"], cfg.norm_eps), jnp.zeros((), jnp.float32)


def forward(params, cfg, tokens, remat=False):
    h, aux = forward_hidden(params, cfg, tokens, remat=remat)
    return jnp.einsum("btd,dv->btv", h, params["head"]), aux


def init_cache(cfg, batch, dtype=jnp.float32, num_layers=None) -> SSMCache:
    L = cfg.num_layers if num_layers is None else num_layers
    di, N = cfg.ssm_inner, cfg.ssm_state
    nh, P = cfg.ssm_num_heads, cfg.ssm_head_dim
    conv_dim = di + 2 * N
    return SSMCache(
        conv=jnp.zeros((L, batch, cfg.ssm_conv - 1, conv_dim), dtype),
        state=jnp.zeros((L, batch, nh, N, P), dtype),
        pos=jnp.zeros((), jnp.int32),
    )


def prefill(params, cfg, tokens, cache: SSMCache, valid=None):
    """Absorb a prompt; returns logits + populated state cache.

    ``valid``: optional () int32 — positions >= valid are padding (the
    engine's bucketed prompts); they are made inert in the scan and the
    conv ring ends at ``valid``.  None keeps the historical unpadded
    path bit-for-bit.
    """
    x = params["embed"][tokens]
    T = tokens.shape[1]

    if valid is not None:
        def body(h, inp):
            lp, h0, c0 = inp
            out, hf, ring = ssm_block_prefill(lp, cfg, h, h0, c0, valid)
            return out, (hf, ring)

        x, (states, convs) = jax.lax.scan(
            body, x, (params["layers"], cache.state, cache.conv),
            unroll=layer_unroll())
    else:
        def body(h, inp):
            lp, h0 = inp
            out, hf = ssm_block_forward(lp, cfg, h, h0=h0)
            # conv cache = last W-1 raw xBC inputs of this layer
            u = rms_norm(h, lp["ln"], cfg.norm_eps)
            proj = jnp.einsum("btd,de->bte", u[:, -(cfg.ssm_conv - 1):],
                              lp["in_proj"])
            _, xBC, _ = _split_proj(cfg, proj)
            return out, (hf, xBC)

        x, (states, convs) = jax.lax.scan(
            body, x, (params["layers"], cache.state), unroll=layer_unroll())
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = jnp.einsum("btd,dv->btv", x, params["head"])
    return logits, SSMCache(conv=convs, state=states, pos=cache.pos + T)


def decode_step(params, cfg, token, cache: SSMCache):
    x = params["embed"][token]

    def body(h, inp):
        lp, cc, st = inp
        out, new_cc, new_st = ssm_block_decode(lp, cfg, h, cc, st)
        return out, (new_cc, new_st)

    x, (convs, states) = jax.lax.scan(body, x,
                                      (params["layers"], cache.conv, cache.state),
                                      unroll=layer_unroll())
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = jnp.einsum("btd,dv->btv", x, params["head"])
    return logits, SSMCache(conv=convs, state=states, pos=cache.pos + 1)


# ------------------------------------------------------------------
# Paged-engine entry points.  SSM state is O(1) per slot (no KV pages
# to manage) — "paged" here buys the chunked-prefill interleaving and
# the shared engine plumbing: pos is a per-slot vector, decode rows can
# be inactive, prefill runs one resumable chunk at a time.
# ------------------------------------------------------------------

def init_paged_cache(params, cfg, num_slots, num_pages, page_size, max_pages,
                     dtype=jnp.float32):
    del params, num_pages, page_size, max_pages
    base = init_cache(cfg, num_slots, dtype)
    return base._replace(pos=jnp.zeros((num_slots,), jnp.int32))


def prefill_chunk(params, cfg, tokens, cache: SSMCache, slot, frontier,
                  valid):
    """One resumable prefill chunk for a single slot.  tokens: (1, C)."""
    del frontier                      # state carry IS the position
    x = params["embed"][tokens]

    def body(h, inp):
        lp, h0, c0 = inp
        out, hf, ring = ssm_block_prefill(lp, cfg, h, h0, c0, valid)
        return out, (hf, ring)

    h0s = cache.state[:, slot][:, None]          # (L, 1, nh, N, P)
    c0s = cache.conv[:, slot][:, None]           # (L, 1, W-1, conv_dim)
    x, (states, convs) = jax.lax.scan(body, x, (params["layers"], h0s, c0s),
                                      unroll=layer_unroll())
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = jnp.einsum("btd,dv->btv", x, params["head"])
    return logits, SSMCache(conv=cache.conv.at[:, slot].set(convs[:, 0]),
                            state=cache.state.at[:, slot].set(states[:, 0]),
                            pos=cache.pos)


def decode_step_paged(params, cfg, token, cache: SSMCache, active):
    """decode_step over the slot batch with inactive rows frozen: their
    conv ring / state / pos keep their old values (the computed row is
    garbage the engine never reads)."""
    logits, nc = decode_step(params, cfg, token, cache)
    conv = jnp.where(active[None, :, None, None], nc.conv, cache.conv)
    state = jnp.where(active[None, :, None, None, None], nc.state,
                      cache.state)
    return logits, SSMCache(conv=conv, state=state,
                            pos=cache.pos + active.astype(jnp.int32))


def paged_to_dense(cache: SSMCache) -> SSMCache:
    """SSM state is already dense per slot — the chunk view is the cache
    itself; ``paged_restore`` does the per-row freezing once per chunk
    instead of every step."""
    return cache


def paged_restore(cache: SSMCache, dense: SSMCache, active,
                  steps) -> SSMCache:
    conv = jnp.where(active[None, :, None, None], dense.conv, cache.conv)
    state = jnp.where(active[None, :, None, None, None], dense.state,
                      cache.state)
    return SSMCache(conv=conv, state=state,
                    pos=cache.pos + steps * active.astype(jnp.int32))
