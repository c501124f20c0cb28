"""The benchmark's Mamba2 architecture module: a configuration whose
``reference`` is ``mamba2`` finds here its plain reference, its FLOP
count, the program's scopes inside ``model`` and its size for a CPU test.

The reference is float32, written from the paper's equations in
straightforward ``jax.numpy``: no kernels, no chunked scan, no cache, no
batching tricks.  Run it under ``jax.default_matmul_precision("highest")``:
on a TPU a float32 matmul otherwise runs in bfloat16 passes.

Mamba2 (arXiv:2405.21060, Sec. 6-7): pre-norm block, one in-projection
to [z, xBC, dt], a depthwise causal conv of width W with SiLU over xBC,
the selective state space model with a single group (B and C shared by
all heads), a D skip, a gated RMSNorm and an out-projection.  The SSM is
evaluated in its quadratic (dual) form over the whole sequence: y_t =
sum_{s<=t} (C_t . B_s) exp(sum_{r=s+1..t} dt_r A) dt_s x_s, which is the
recurrence h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T, y_t = C_t . h_t
unrolled, with no chunking.

``init`` draws the seeded weights the benchmark defines for a
configuration, with the same random streams as the system under test
draws them, so both start from the same point.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HEAD_BLOCK = 8          # SSM heads per block of the quadratic form

# the program's named scopes inside ``model`` (models/model.py,
# models/mamba2.py), innermost wins
SCOPES = ("embed", "in_proj", "conv", "ssd", "out_proj", "head_loss")

# the model keys a CPU test shrinks, and their values there
CPU_SIZE = {"num_layers": 2, "d_model": 64, "vocab_size": 128,
            "ssm_state": 16, "ssm_head_dim": 16, "ssm_chunk": 16}


# ------------------------------------------------------------------
# FLOPs (the convention of bench/flops.py)
# ------------------------------------------------------------------

def layer_fwd_flops(m) -> float:
    """Forward FLOPs per token of one Mamba2 layer (chunked SSD)."""
    d, N, P, W = m["d_model"], m["ssm_state"], m["ssm_head_dim"], m["ssm_conv"]
    di = m["ssm_expand"] * d
    nh, Q = di // P, m["ssm_chunk"]
    in_proj = 2 * d * (2 * di + 2 * N + nh)
    conv = 2 * W * (di + 2 * N)
    # within a chunk each token meets (Q + 1) / 2 earlier ones on average:
    # C.B over N, then the weighted sum over P for every head
    intra = 2 * (Q + 1) / 2 * (N + nh * P)
    # the chunk's state update and its read-out: N x P per head each
    inter = 2 * 2 * N * P * nh
    out_proj = 2 * di * d
    return in_proj + conv + intra + inter + out_proj


def fwd_flops_per_token(m) -> float:
    """Forward FLOPs per token of the whole model: its layers and the
    head."""
    return m["num_layers"] * layer_fwd_flops(m) \
        + 2 * m["d_model"] * m["vocab_size"]


def train_flops_per_token(m) -> float:
    """Forward and backward FLOPs per trained token."""
    return 3 * fwd_flops_per_token(m)


# ------------------------------------------------------------------
# Seeded weights
# ------------------------------------------------------------------

def _dense(key, shape):
    return jax.random.normal(key, shape) / math.sqrt(shape[-2])


def _ssm_layer_init(key, m):
    d, di, N = m["d_model"], m["ssm_expand"] * m["d_model"], m["ssm_state"]
    nh = di // m["ssm_head_dim"]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt0 = jnp.exp(jax.random.uniform(k3, (nh,), minval=jnp.log(1e-3),
                                     maxval=jnp.log(1e-1)))
    return {
        "ln": jnp.ones((d,)),
        "in_proj": _dense(k1, (d, 2 * di + 2 * N + nh)),
        "conv_w": jax.random.normal(k2, (m["ssm_conv"], di + 2 * N)) * 0.1,
        "conv_b": jnp.zeros((di + 2 * N,)),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh)),
        "D": jnp.ones((nh,)),
        "dt_bias": jnp.log(jnp.expm1(dt0)),
        "norm": jnp.ones((di,)),
        "out_proj": _dense(k4, (di, d)),
    }


def init(key, m):
    """Seeded float32 weights of configuration ``m`` (the ``model`` dict of
    a configuration file)."""
    if m["family"] != "ssm":
        raise ValueError(f"no reference for family {m['family']!r}")
    d, V = m["d_model"], m["vocab_size"]
    k1, k2, k3 = jax.random.split(key, 3)
    layers = [_ssm_layer_init(k, m)
              for k in jax.random.split(k2, m["num_layers"])]
    return {
        "embed": jax.random.normal(k1, (V, d)) * 0.02,
        "layers": jax.tree.map(lambda *xs: jnp.stack(xs), *layers),
        "ln_f": jnp.ones((d,)),
        "head": _dense(k3, (d, V)),
    }


# ------------------------------------------------------------------
# Layers
# ------------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def ssm_quadratic(x, dt, A, B, C):
    """x (b, T, nh, P), dt (b, T, nh), A (nh,), B and C (b, T, N) ->
    y (b, T, nh, P), over the whole sequence at once."""
    T, nh = x.shape[1], x.shape[2]
    cum = jnp.cumsum(dt * A, axis=1)                       # (b, T, nh)
    cb = jnp.einsum("btn,bsn->bts", C, B)                  # (b, T, S)
    causal = jnp.tril(jnp.ones((T, T), bool))[None, :, :, None]

    @jax.checkpoint
    def heads(sl):
        c, d, xs = sl                                      # (b,T,h) ...
        seg = c[:, :, None, :] - c[:, None, :, :]          # (b, T, S, h)
        w = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        w = w * cb[..., None] * d[:, None, :, :]
        return jnp.einsum("btsh,bshp->bthp", w, xs)

    hb = math.gcd(nh, HEAD_BLOCK)
    split = lambda a: jnp.moveaxis(
        a.reshape(a.shape[:2] + (nh // hb, hb) + a.shape[3:]), 2, 0)
    ys = jax.lax.map(heads, (split(cum), split(dt), split(x)))
    return jnp.moveaxis(ys, 0, 2).reshape(x.shape)


def ssm_layer(lp, m, x):
    di, N = m["ssm_expand"] * m["d_model"], m["ssm_state"]
    P = m["ssm_head_dim"]
    nh, W = di // P, m["ssm_conv"]
    b, T, _ = x.shape
    proj = rms_norm(x, lp["ln"], m["norm_eps"]) @ lp["in_proj"]
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * N], \
        proj[..., 2 * di + 2 * N:]
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + T] * lp["conv_w"][i] for i in range(W))
    xbc = silu(conv + lp["conv_b"])
    xs = xbc[..., :di].reshape(b, T, nh, P)
    Bm, Cm = xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    y = ssm_quadratic(xs, dt, -jnp.exp(lp["A_log"]), Bm, Cm)
    y = (y + lp["D"][:, None] * xs).reshape(b, T, di)
    y = rms_norm(y * silu(z), lp["norm"], m["norm_eps"])
    return x + y @ lp["out_proj"]


# ------------------------------------------------------------------
# Whole model
# ------------------------------------------------------------------

def hidden(p, m, tokens):
    """Final normed hidden states (b, T, d).  Each layer is recomputed in
    the backward pass, so a gradient holds one layer's internals at a
    time."""
    layer = jax.checkpoint(lambda h, lp: (ssm_layer(lp, m, h), None))
    x, _ = jax.lax.scan(layer, p["embed"][tokens], p["layers"])
    return rms_norm(x, p["ln_f"], m["norm_eps"])


def logits(p, m, tokens):
    return hidden(p, m, tokens) @ p["head"]


def loss(p, m, tokens, labels):
    """Mean next-token cross-entropy over every position."""
    lg = logits(p, m, tokens)
    lse = jax.nn.logsumexp(lg, -1)
    gold = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold)
