"""MusicGen-style audio decoder (arXiv:2306.05284).

Decoder-only transformer over ``num_codebooks`` parallel EnCodec token
streams.  Input embedding = sum of per-codebook embeddings; output = one
LM head per codebook.  The EnCodec tokenizer and the T5 text conditioner
are STUBS per the assignment carve-out: ``input_specs`` supplies
``cond_len`` precomputed conditioning frames (B, cond_len, d_model) that
are prepended to the sequence (MusicGen's prepend-conditioning mode; the
released model's cross-attention variant is noted in DESIGN.md).

The codebook delay pattern is applied at the data layer (data/synthetic
emits delayed streams); the model treats codebooks as parallel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import transformer
from repro.models.layers import dense_init, embed_init


def init_params(key, cfg, dtype=jnp.float32):
    K = cfg.num_codebooks
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "embed": embed_init(k1, (K, cfg.vocab_size, cfg.d_model), dtype),
        "blocks": transformer.init_stacked_blocks(k2, cfg, dtype),
        "ln_f": jnp.ones((cfg.d_model,), dtype),
        "head": dense_init(k3, (cfg.d_model, K * cfg.vocab_size), dtype=dtype),
    }
    return p


@jax.named_scope("embed")
def _embed(params, cfg, tokens):
    """tokens: (B, K, T) -> (B, T, d) summed codebook embeddings."""
    B, K, T = tokens.shape
    out = 0.0
    for k in range(K):
        out = out + params["embed"][k][tokens[:, k]]
    return out


def _with_cond(x, cond):
    if cond is None:
        return x
    return jnp.concatenate([cond.astype(x.dtype), x], axis=1)


def forward_hidden(params, cfg, tokens, cond=None, use_flash=False,
                   remat=False):
    """Returns final-normed hidden over the token region: (B, T, d)."""
    from repro.models.layers import rms_norm
    B, K, T = tokens.shape
    x = _embed(params, cfg, tokens)
    x = _with_cond(x, cond)
    Tt = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(Tt, dtype=jnp.int32), (B, Tt))
    h, aux = transformer.stack_forward(params, cfg, x, positions,
                                       use_flash=use_flash, remat=remat)
    return rms_norm(h[:, -T:], params["ln_f"], cfg.norm_eps), aux


def forward(params, cfg, tokens, cond=None, use_flash=False, remat=False):
    """tokens: (B, K, T); cond: (B, cond_len, d).
    Returns logits (B, T, K, V) over the token region only."""
    B, K, T = tokens.shape
    h, aux = forward_hidden(params, cfg, tokens, cond=cond,
                            use_flash=use_flash, remat=remat)
    logits = jnp.einsum("btd,dv->btv", h, params["head"])
    return logits.reshape(B, T, K, cfg.vocab_size), aux


def init_cache(params, cfg, batch, max_len, dtype=jnp.float32):
    return transformer.init_cache(params, cfg, batch, max_len, dtype)


def prefill(params, cfg, tokens, cache, cond=None, use_flash=False):
    B, K, T = tokens.shape
    x = _embed(params, cfg, tokens)
    x = _with_cond(x, cond)
    # feed merged embeddings through the shared stack via a zero-token trick
    zero_tokens = jnp.zeros((B, x.shape[1]), jnp.int32)
    extra = x - params["embed"][0][zero_tokens]
    logits_flat, cache = transformer.prefill(
        {**params, "embed": params["embed"][0], "head": params["head"]},
        cfg, zero_tokens, cache, use_flash=use_flash, extra_embeds=extra)
    logits = logits_flat[:, -T:].reshape(B, T, K, cfg.vocab_size)
    return logits, cache


def decode_step(params, cfg, token, cache, cond=None):
    """token: (B, K, 1) -> logits (B, 1, K, V)."""
    B, K, _ = token.shape
    x = _embed(params, cfg, token)                  # (B, 1, d)
    zero_tokens = jnp.zeros((B, 1), jnp.int32)
    extra = x - params["embed"][0][zero_tokens]
    logits_flat, cache = transformer.decode_step(
        {**params, "embed": params["embed"][0], "head": params["head"]},
        cfg, zero_tokens, cache, extra_embeds=extra)
    return logits_flat.reshape(B, 1, K, cfg.vocab_size), cache


# ------------------------------------------------------------------
# Paged-engine entry points.  Positions are MERGED coordinates: the
# cond frames occupy [0, cond_len) of the cache, tokens follow — the
# engine's frontier/total/pos all count merged positions.
# ------------------------------------------------------------------

def init_paged_cache(params, cfg, num_slots, num_pages, page_size, max_pages,
                     dtype=jnp.float32):
    return transformer.init_paged_cache(params, cfg, num_slots, num_pages,
                                        page_size, max_pages, dtype)


def prefill_chunk(params, cfg, tokens, cache, slot, frontier, valid,
                  cond=None):
    """One prefill chunk.  tokens: (1, K, C) aligned to MERGED positions
    frontier..frontier+C-1 (the engine zero-fills entries whose position
    falls in the cond region or the padded tail).  Rows in the cond
    region take the conditioning frame instead of the token embedding —
    row-for-row what ``_with_cond`` builds for the whole prompt.
    Returns logits (1, C, K, V): only token-region rows are meaningful.
    """
    B, K, C = tokens.shape
    emb = _embed(params, cfg, tokens)               # (1, C, d)
    p = frontier + jnp.arange(C, dtype=jnp.int32)
    if cond is not None:
        cl = cond.shape[1]
        crow = cond[0][jnp.clip(p, 0, cl - 1)].astype(emb.dtype)[None]
        x = jnp.where((p < cl)[None, :, None], crow, emb)
    else:
        x = emb
    zero_tokens = jnp.zeros((B, C), jnp.int32)
    extra = x - params["embed"][0][zero_tokens]
    logits_flat, cache = transformer.prefill_chunk(
        {**params, "embed": params["embed"][0], "head": params["head"]},
        cfg, zero_tokens, cache, slot, frontier, valid, extra_embeds=extra)
    return logits_flat.reshape(B, C, K, cfg.vocab_size), cache


def decode_step_paged(params, cfg, token, cache, active, cond=None,
                      use_kernel=False):
    del cond
    B, K, _ = token.shape
    x = _embed(params, cfg, token)
    zero_tokens = jnp.zeros((B, 1), jnp.int32)
    extra = x - params["embed"][0][zero_tokens]
    logits_flat, cache = transformer.decode_step_paged(
        {**params, "embed": params["embed"][0], "head": params["head"]},
        cfg, zero_tokens, cache, active, extra_embeds=extra,
        use_kernel=use_kernel)
    return logits_flat.reshape(B, 1, K, cfg.vocab_size), cache
