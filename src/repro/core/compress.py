"""Lossy compression of the Eq. (8d) sync collective payload.

Model-averaging methods tolerate infrequent, lossy communication well
(Zhang et al., Elastic Averaging SGD; Yu et al., Parallel Restarted
SGD), so the one per-L all-reduce is the natural place to cut bytes
without new hyper-parameters.  Two codecs:

  bf16 — round-to-nearest bfloat16 cast; half the f32 bytes.
  int8 — symmetric per-chunk quantization (chunk = 1024 elements, one
         f32 scale per chunk = max|c|/127); a quarter of the f32 bytes
         plus ~0.4% of scale overhead.

Both compress each replica's contribution ``c_a = x_a + e_a``
individually (NOT the local mean), which makes the dequantized replica
mean independent of how replicas are laid out over devices — the local
vmap path and any shard_map placement produce bit-identical xbar.

Error feedback: the residual ``e_a' = c_a - dequant(quant(c_a))`` is
carried in the optimizer state and added back before the next sync, so
the quantization error telescopes: the running mean of the dequantized
payloads converges to the true mean at O(1/K) over K syncs
(tests/test_sync_compress.py).

All functions operate on FLAT (R, M) streams; the tree-level drivers
live with their consumers (core/parle.py pads and flattens per leaf).
"""
from __future__ import annotations

import jax.numpy as jnp

METHODS = ("none", "bf16", "int8")
CHUNK = 1024            # elements per int8 scale (= the kernel lane dim)
# streams are padded to whole (8, 1024) tiles: the payload's layout on
# the wire and in the async coordinator's flat vectors
PAD_MULTIPLE = 8 * CHUNK


def check_method(method: str):
    if method not in METHODS:
        raise ValueError(f"sync_compress must be one of {METHODS}, "
                         f"got {method!r}")


def pad_to_chunk(flat):
    """Pad the trailing dim of (..., M) to a PAD_MULTIPLE multiple
    (zeros — an all-zero chunk quantizes to scale 1 / payload 0, so
    padding never perturbs scales or the dequantized mean)."""
    m = flat.shape[-1]
    pad = (-m) % PAD_MULTIPLE
    if pad:
        cfg = [(0, 0)] * (flat.ndim - 1) + [(0, pad)]
        flat = jnp.pad(flat, cfg)
    return flat


def quantize(c, method: str):
    """c: (..., M) f32 with M % CHUNK == 0.  Returns (q, scales):
    bf16 -> (bf16 array, None); int8 -> (int8 array, (..., M/CHUNK) f32).
    """
    if method == "bf16":
        return c.astype(jnp.bfloat16), None
    if method == "int8":
        chunked = c.reshape(*c.shape[:-1], c.shape[-1] // CHUNK, CHUNK)
        amax = jnp.max(jnp.abs(chunked), axis=-1)
        # multiply by the reciprocal explicitly: XLA strength-reduces
        # x/127 to x*(1/127) under jit, so jitted and eager scales agree
        scales = jnp.where(amax == 0, 1.0, amax * (1.0 / 127.0))
        q = jnp.clip(jnp.round(chunked / scales[..., None]), -127, 127)
        return q.astype(jnp.int8).reshape(c.shape), scales
    raise ValueError(f"no quantizer for method {method!r}")


def dequantize(q, scales, method: str):
    """Inverse of :func:`quantize`, back to f32."""
    if method == "bf16":
        return q.astype(jnp.float32)
    if method == "int8":
        chunked = q.reshape(*q.shape[:-1], q.shape[-1] // CHUNK, CHUNK)
        deq = chunked.astype(jnp.float32) * scales[..., None]
        return deq.reshape(q.shape)
    raise ValueError(f"no dequantizer for method {method!r}")


def quantize_ef(c, method: str):
    """Quantize with error feedback: returns (q, scales, residual) where
    residual = c - dequantize(q) is what the caller carries to the next
    sync."""
    q, scales = quantize(c, method)
    return q, scales, c - dequantize(q, scales, method)
