"""Pre-norm decoder transformer with GQA; layer stack via lax.scan over
stacked parameters (keeps HLO size O(1) in depth — essential for the
126-layer llama3-405b dry-run).

The same block serves the dense, moe (MLP swapped for the routed MoE),
vlm and audio families; family-specific embedding/head handling lives in
model.py / vlm.py / audio.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models.layers import (chunked_cross_entropy, dense_init,
                                 embed_init, rms_norm, swiglu)
from repro.utils.scan import layer_unroll


# ------------------------------------------------------------------
# Parameters
# ------------------------------------------------------------------

def init_block_params(key, cfg, dtype=jnp.float32):
    """One decoder block (un-stacked)."""
    k_attn, k_mlp = jax.random.split(key)
    p = {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "attn": attn.init_attn_params(k_attn, cfg, dtype),
    }
    if cfg.family == "moe":
        p["moe"] = moe_mod.init_moe_params(k_mlp, cfg, dtype)
    else:
        ks = jax.random.split(k_mlp, 3)
        p["mlp"] = {
            "w_gate": dense_init(ks[0], (cfg.d_model, cfg.d_ff), dtype=dtype),
            "w_up": dense_init(ks[1], (cfg.d_model, cfg.d_ff), dtype=dtype),
            "w_down": dense_init(ks[2], (cfg.d_ff, cfg.d_model), dtype=dtype),
        }
    return p


def init_stacked_blocks(key, cfg, dtype=jnp.float32):
    """Stack num_layers blocks along a leading axis (for lax.scan)."""
    keys = jax.random.split(key, cfg.num_layers)
    blocks = [init_block_params(k, cfg, dtype) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)


def init_params(key, cfg, dtype=jnp.float32):
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    p = {
        "embed": embed_init(k_embed, (cfg.vocab_size, cfg.d_model), dtype),
        "blocks": init_stacked_blocks(k_blocks, cfg, dtype),
        "ln_f": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["head"] = dense_init(k_head, (cfg.d_model, cfg.vocab_size), dtype=dtype)
    return p


def _remat(body, remat):
    """remat=True: full recompute.  remat="dots": save matmul outputs,
    recompute only elementwise ops (cheaper recompute FLOPs/bytes at
    slightly higher live memory) — a §Perf hillclimb lever."""
    if remat == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(body)


# ------------------------------------------------------------------
# Forward
# ------------------------------------------------------------------

def block_forward(bp, cfg, x, positions, use_flash=False):
    """x: (B, T, d) -> (B, T, d); returns (x, aux_loss)."""
    h = attn.attn_forward(bp["attn"], cfg, rms_norm(x, bp["ln1"], cfg.norm_eps),
                          positions, use_flash=use_flash)
    x = x + h
    u = rms_norm(x, bp["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        m, aux = moe_mod.moe_forward(bp["moe"], cfg, u)
    else:
        m, aux = swiglu(u, **bp["mlp"]), jnp.zeros((), jnp.float32)
    return x + m, aux


def stack_forward(params, cfg, x, positions, use_flash=False, remat=False):
    """Scan the stacked blocks.  Returns (hidden, total_aux_loss)."""

    def body(carry, bp):
        h, aux = block_forward(bp, cfg, carry, positions, use_flash=use_flash)
        return h, aux

    if remat:
        body = _remat(body, remat)
    x, auxs = jax.lax.scan(body, x, params["blocks"], unroll=layer_unroll())
    return x, jnp.sum(auxs)


def head_matrix(params, cfg):
    return params["embed"].T if cfg.tie_embeddings else params["head"]


def forward_hidden(params, cfg, tokens, use_flash=False, remat=False,
                   extra_embeds=None):
    """Returns (final-normed hidden (B, T, d), aux_loss) — pair with
    chunked_cross_entropy to avoid materializing (B, T, V) logits."""
    B, T = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    if extra_embeds is not None:
        x = x + extra_embeds
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    h, aux = stack_forward(params, cfg, x, positions,
                           use_flash=use_flash, remat=remat)
    return rms_norm(h, params["ln_f"], cfg.norm_eps), aux


def logits_from_hidden(params, cfg, h):
    h = rms_norm(h, params["ln_f"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return jnp.einsum("btd,vd->btv", h, params["embed"])
    return jnp.einsum("btd,dv->btv", h, params["head"])


def forward(params, cfg, tokens, use_flash=False, remat=False,
            extra_embeds=None):
    """tokens: (B, T) -> logits (B, T, V).

    ``extra_embeds``: optional (B, T, d) added to the token embeddings
    (used by the VLM path to inject patch embeddings).
    """
    B, T = tokens.shape
    x = params["embed"][tokens]
    if extra_embeds is not None:
        x = x + extra_embeds
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    h, aux = stack_forward(params, cfg, x, positions,
                           use_flash=use_flash, remat=remat)
    return logits_from_hidden(params, cfg, h), aux


# ------------------------------------------------------------------
# Serving: prefill + single-token decode with per-layer KV caches
# ------------------------------------------------------------------

def init_cache(params, cfg, batch, max_len, dtype=jnp.float32):
    one = attn.init_kv_cache(cfg, batch, max_len, dtype)
    L = cfg.num_layers
    return attn.KVCache(
        k=jnp.zeros((L,) + one.k.shape, dtype),
        v=jnp.zeros((L,) + one.v.shape, dtype),
        pos=jnp.zeros((), jnp.int32),
    )


def prefill(params, cfg, tokens, cache, use_flash=False, extra_embeds=None):
    B, T = tokens.shape
    x = params["embed"][tokens]
    if extra_embeds is not None:
        x = x + extra_embeds
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def body(carry, layer):
        h = carry
        bp, ck, cv = layer
        lc = attn.KVCache(ck, cv, cache.pos)
        a, lc = attn.attn_prefill(bp["attn"], cfg,
                                  rms_norm(h, bp["ln1"], cfg.norm_eps),
                                  positions, lc, use_flash=use_flash)
        h = h + a
        u = rms_norm(h, bp["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            m, _ = moe_mod.moe_forward(bp["moe"], cfg, u)
        else:
            m = swiglu(u, **bp["mlp"])
        return h + m, (lc.k, lc.v)

    h, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], cache.k, cache.v),
                               unroll=layer_unroll())
    new_cache = attn.KVCache(ks, vs, cache.pos + T)
    return logits_from_hidden(params, cfg, h), new_cache


def decode_step(params, cfg, token, cache, extra_embeds=None):
    """token: (B, 1) int32 -> logits (B, 1, V), updated cache."""
    x = params["embed"][token]
    if extra_embeds is not None:
        x = x + extra_embeds

    def body(carry, layer):
        h = carry
        bp, ck, cv = layer
        lc = attn.KVCache(ck, cv, cache.pos)
        a, lc = attn.attn_decode(bp["attn"], cfg,
                                 rms_norm(h, bp["ln1"], cfg.norm_eps), lc)
        h = h + a
        u = rms_norm(h, bp["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            m, _ = moe_mod.moe_forward(bp["moe"], cfg, u)
        else:
            m = swiglu(u, **bp["mlp"])
        return h + m, (lc.k, lc.v)

    h, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], cache.k, cache.v),
                               unroll=layer_unroll())
    new_cache = attn.KVCache(ks, vs, cache.pos + 1)
    return logits_from_hidden(params, cfg, h), new_cache


# ------------------------------------------------------------------
# Serving: paged cache (page pools + per-slot tables) + chunked prefill
# ------------------------------------------------------------------

def init_paged_cache(params, cfg, num_slots, num_pages, page_size, max_pages,
                     dtype=jnp.float32):
    k1, v1, table, pos = attn.init_paged_kv_pool(cfg, num_slots, num_pages,
                                                 page_size, max_pages, dtype)
    L = cfg.num_layers
    return attn.PagedKVCache(
        k=jnp.zeros((L,) + k1.shape, dtype),
        v=jnp.zeros((L,) + v1.shape, dtype),
        table=table, pos=pos,
    )


def prefill_chunk(params, cfg, tokens, cache, slot, frontier, valid,
                  extra_embeds=None):
    """One chunk of a single slot's prefill through the page table.

    tokens: (1, C) — the chunk's slice of the prompt, zero-padded past
    ``valid``; ``frontier`` is the chunk's absolute start position.  The
    padded tail's writes land past the slot's allocated pages (-> trash)
    or in not-yet-live positions later overwritten by decode, so only
    ``valid`` logit rows are meaningful.  Returns (logits (1, C, V),
    cache); cache.pos is NOT advanced (the engine sets it once the whole
    prompt is in).
    """
    del valid  # attention needs no masking: padded rows are causal-future
    B, C = tokens.shape
    x = params["embed"][tokens]
    if extra_embeds is not None:
        x = x + extra_embeds
    positions = (frontier + jnp.arange(C, dtype=jnp.int32))[None]
    table_row = cache.table[slot]

    def body(carry, layer):
        h = carry
        bp, pk, pv = layer
        a, pk, pv = attn.attn_prefill_paged(
            bp["attn"], cfg, rms_norm(h, bp["ln1"], cfg.norm_eps),
            positions, pk, pv, table_row)
        h = h + a
        u = rms_norm(h, bp["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            m, _ = moe_mod.moe_forward(bp["moe"], cfg, u)
        else:
            m = swiglu(u, **bp["mlp"])
        return h + m, (pk, pv)

    h, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], cache.k, cache.v),
                               unroll=layer_unroll())
    return logits_from_hidden(params, cfg, h), cache._replace(k=ks, v=vs)


def decode_step_paged(params, cfg, token, cache, active, extra_embeds=None,
                      use_kernel=False):
    """token: (B, 1) int32 -> logits (B, 1, V), updated paged cache.
    ``active``: (B,) bool — inactive rows write to the trash page and
    keep their pos."""
    x = params["embed"][token]
    if extra_embeds is not None:
        x = x + extra_embeds

    def body(carry, layer):
        h = carry
        bp, pk, pv = layer
        a, pk, pv = attn.attn_decode_paged(
            bp["attn"], cfg, rms_norm(h, bp["ln1"], cfg.norm_eps),
            pk, pv, cache.table, cache.pos, active, use_kernel=use_kernel)
        h = h + a
        u = rms_norm(h, bp["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            m, _ = moe_mod.moe_forward(bp["moe"], cfg, u)
        else:
            m = swiglu(u, **bp["mlp"])
        return h + m, (pk, pv)

    h, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], cache.k, cache.v),
                               unroll=layer_unroll())
    new_cache = cache._replace(k=ks, v=vs,
                               pos=cache.pos + active.astype(jnp.int32))
    return logits_from_hidden(params, cfg, h), new_cache


def paged_to_dense(cache):
    """Page tables are constant within a decode chunk, so the engine
    gathers the pool into a dense per-slot view ONCE per chunk and runs
    the plain ``decode_step`` inside the scan (bitwise the same values
    the per-step paged path attends over)."""
    return attn.paged_to_dense_kv(cache)


def paged_restore(cache, dense, active, steps):
    """Scatter the chunk's dense view back into the page pool; inactive
    rows land on the trash page and keep their pos."""
    return attn.dense_to_paged_kv(cache, dense, active, steps)
