"""Zamba2-style hybrid: Mamba2 backbone + ONE shared attention block
(arXiv:2411.15242) applied after every ``cfg.attn_every`` SSM layers.
The attention block's weights are shared across all of its applications
(the paper's parameter-efficiency trick); each application keeps its own
KV cache.

Simplification vs the released model (noted in DESIGN.md): the shared
block here consumes the hidden stream directly rather than
concat(hidden, original embedding), and LoRA-per-invocation adapters are
omitted.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mamba2
from repro.models.layers import dense_init, embed_init, rms_norm, swiglu
from repro.utils.scan import layer_unroll


class HybridCache(NamedTuple):
    ssm: mamba2.SSMCache
    kv: attn.KVCache            # leading axis = number of shared-attn sites
    pos: jax.Array


def _group_sizes(cfg):
    L, k = cfg.num_layers, cfg.attn_every
    sizes = [k] * (L // k)
    if L % k:
        sizes.append(L % k)
    return sizes


def num_attn_sites(cfg) -> int:
    return len(_group_sizes(cfg))


def init_params(key, cfg, dtype=jnp.float32):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    shared = {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "attn": attn.init_attn_params(k4, cfg, dtype),
        "mlp": {
            "w_gate": dense_init(jax.random.fold_in(k4, 1), (cfg.d_model, cfg.d_ff), dtype=dtype),
            "w_up": dense_init(jax.random.fold_in(k4, 2), (cfg.d_model, cfg.d_ff), dtype=dtype),
            "w_down": dense_init(jax.random.fold_in(k4, 3), (cfg.d_ff, cfg.d_model), dtype=dtype),
        },
    }
    return {
        "embed": embed_init(k1, (cfg.vocab_size, cfg.d_model), dtype),
        "layers": mamba2.init_stacked_ssm(k2, cfg, dtype=dtype),
        "shared_attn": shared,
        "ln_f": jnp.ones((cfg.d_model,), dtype),
        "head": dense_init(k3, (cfg.d_model, cfg.vocab_size), dtype=dtype),
    }


def _shared_block(sp, cfg, x, positions, use_flash=False):
    h = attn.attn_forward(sp["attn"], cfg, rms_norm(x, sp["ln1"], cfg.norm_eps),
                          positions, use_flash=use_flash)
    x = x + h
    return x + swiglu(rms_norm(x, sp["ln2"], cfg.norm_eps), **sp["mlp"])


def _slice_layers(layers, start, size):
    return jax.tree.map(lambda a: jax.lax.slice_in_dim(a, start, start + size, axis=0), layers)


def forward_hidden(params, cfg, tokens, remat=False, use_flash=False):
    B, T = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def ssm_body(h, lp):
        out, _ = mamba2.ssm_block_forward(lp, cfg, h)
        return out, None

    if remat:
        from repro.models.transformer import _remat
        ssm_body = _remat(ssm_body, remat)
    start = 0
    for size in _group_sizes(cfg):
        grp = _slice_layers(params["layers"], start, size)
        x, _ = jax.lax.scan(ssm_body, x, grp, unroll=layer_unroll())
        x = _shared_block(params["shared_attn"], cfg, x, positions, use_flash)
        start += size
    return rms_norm(x, params["ln_f"], cfg.norm_eps), jnp.zeros((), jnp.float32)


def forward(params, cfg, tokens, remat=False, use_flash=False):
    h, aux = forward_hidden(params, cfg, tokens, remat=remat,
                            use_flash=use_flash)
    return jnp.einsum("btd,dv->btv", h, params["head"]), aux


def init_cache(cfg, batch, max_len, dtype=jnp.float32) -> HybridCache:
    sites = num_attn_sites(cfg)
    one = attn.init_kv_cache(cfg, batch, max_len, dtype)
    return HybridCache(
        ssm=mamba2.init_cache(cfg, batch, dtype),
        kv=attn.KVCache(
            k=jnp.zeros((sites,) + one.k.shape, dtype),
            v=jnp.zeros((sites,) + one.v.shape, dtype),
            pos=jnp.zeros((), jnp.int32)),
        pos=jnp.zeros((), jnp.int32),
    )


def prefill(params, cfg, tokens, cache: HybridCache, use_flash=False,
            valid=None):
    """``valid``: optional () int32 for bucketed (zero-padded) prompts —
    positions >= valid are made inert in the SSM scan and the conv ring
    ends at ``valid`` (their KV-cache rows hold garbage that decode
    overwrites before its live mask exposes them).  None keeps the
    historical unpadded path bit-for-bit."""
    B, T = tokens.shape
    x = params["embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    sp = params["shared_attn"]

    states, convs, ks, vs = [], [], [], []
    start = 0
    for g, size in enumerate(_group_sizes(cfg)):
        grp = _slice_layers(params["layers"], start, size)

        if valid is not None:
            def body(h, inp):
                lp, h0, c0 = inp
                out, hf, ring = mamba2.ssm_block_prefill(lp, cfg, h, h0, c0,
                                                         valid)
                return out, (hf, ring)

            h0s = jax.lax.slice_in_dim(cache.ssm.state, start, start + size,
                                       axis=0)
            c0s = jax.lax.slice_in_dim(cache.ssm.conv, start, start + size,
                                       axis=0)
            x, (st, cv) = jax.lax.scan(body, x, (grp, h0s, c0s),
                                       unroll=layer_unroll())
        else:
            def body(h, inp):
                lp, h0 = inp
                out, hf = mamba2.ssm_block_forward(lp, cfg, h, h0=h0)
                u = rms_norm(h, lp["ln"], cfg.norm_eps)
                proj = jnp.einsum("btd,de->bte", u[:, -(cfg.ssm_conv - 1):], lp["in_proj"])
                _, xBC, _ = mamba2._split_proj(cfg, proj)
                return out, (hf, xBC)

            h0s = jax.lax.slice_in_dim(cache.ssm.state, start, start + size, axis=0)
            x, (st, cv) = jax.lax.scan(body, x, (grp, h0s), unroll=layer_unroll())
        states.append(st)
        convs.append(cv)

        lc = attn.KVCache(cache.kv.k[g], cache.kv.v[g], cache.kv.pos)
        a, lc = attn.attn_prefill(sp["attn"], cfg,
                                  rms_norm(x, sp["ln1"], cfg.norm_eps),
                                  positions, lc, use_flash=use_flash)
        x = x + a
        x = x + swiglu(rms_norm(x, sp["ln2"], cfg.norm_eps), **sp["mlp"])
        ks.append(lc.k)
        vs.append(lc.v)
        start += size

    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = jnp.einsum("btd,dv->btv", x, params["head"])
    new_cache = HybridCache(
        ssm=mamba2.SSMCache(conv=jnp.concatenate(convs, axis=0),
                            state=jnp.concatenate(states, axis=0),
                            pos=cache.ssm.pos + T),
        kv=attn.KVCache(jnp.stack(ks), jnp.stack(vs), cache.kv.pos + T),
        pos=cache.pos + T,
    )
    return logits, new_cache


def decode_step(params, cfg, token, cache: HybridCache):
    x = params["embed"][token]
    sp = params["shared_attn"]

    states, convs, ks, vs = [], [], [], []
    start = 0
    for g, size in enumerate(_group_sizes(cfg)):
        grp = _slice_layers(params["layers"], start, size)

        def body(h, inp):
            lp, cc, st = inp
            out, ncc, nst = mamba2.ssm_block_decode(lp, cfg, h, cc, st)
            return out, (ncc, nst)

        cc = jax.lax.slice_in_dim(cache.ssm.conv, start, start + size, axis=0)
        st = jax.lax.slice_in_dim(cache.ssm.state, start, start + size, axis=0)
        x, (ncc, nst) = jax.lax.scan(body, x, (grp, cc, st),
                                     unroll=layer_unroll())
        convs.append(ncc)
        states.append(nst)

        lc = attn.KVCache(cache.kv.k[g], cache.kv.v[g], cache.kv.pos)
        a, lc = attn.attn_decode(sp["attn"], cfg,
                                 rms_norm(x, sp["ln1"], cfg.norm_eps), lc)
        x = x + a
        x = x + swiglu(rms_norm(x, sp["ln2"], cfg.norm_eps), **sp["mlp"])
        ks.append(lc.k)
        vs.append(lc.v)
        start += size

    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = jnp.einsum("btd,dv->btv", x, params["head"])
    new_cache = HybridCache(
        ssm=mamba2.SSMCache(conv=jnp.concatenate(convs, axis=0),
                            state=jnp.concatenate(states, axis=0),
                            pos=cache.ssm.pos + 1),
        kv=attn.KVCache(jnp.stack(ks), jnp.stack(vs), cache.kv.pos + 1),
        pos=cache.pos + 1,
    )
    return logits, new_cache


# ------------------------------------------------------------------
# Paged-engine entry points: the shared-attn KV goes through page
# tables (pool leading axis = attention sites), the SSM state stays
# dense per slot (O(1) per request — nothing to page).
# ------------------------------------------------------------------

def init_paged_cache(params, cfg, num_slots, num_pages, page_size, max_pages,
                     dtype=jnp.float32):
    del params
    sites = num_attn_sites(cfg)
    k1, v1, table, pos = attn.init_paged_kv_pool(cfg, num_slots, num_pages,
                                                 page_size, max_pages, dtype)
    ssm = mamba2.init_cache(cfg, num_slots, dtype)
    return HybridCache(
        ssm=ssm._replace(pos=jnp.zeros((num_slots,), jnp.int32)),
        kv=attn.PagedKVCache(
            k=jnp.zeros((sites,) + k1.shape, dtype),
            v=jnp.zeros((sites,) + v1.shape, dtype),
            table=table, pos=pos),
        pos=jnp.zeros((num_slots,), jnp.int32),
    )


def prefill_chunk(params, cfg, tokens, cache: HybridCache, slot, frontier,
                  valid):
    """One resumable prefill chunk for a single slot.  tokens: (1, C)."""
    B, C = tokens.shape
    x = params["embed"][tokens]
    positions = (frontier + jnp.arange(C, dtype=jnp.int32))[None]
    table_row = cache.kv.table[slot]
    sp = params["shared_attn"]

    states, convs, pks, pvs = [], [], [], []
    start = 0
    for g, size in enumerate(_group_sizes(cfg)):
        grp = _slice_layers(params["layers"], start, size)

        def body(h, inp):
            lp, h0, c0 = inp
            out, hf, ring = mamba2.ssm_block_prefill(lp, cfg, h, h0, c0,
                                                     valid)
            return out, (hf, ring)

        h0s = jax.lax.slice_in_dim(cache.ssm.state, start, start + size,
                                   axis=0)[:, slot][:, None]
        c0s = jax.lax.slice_in_dim(cache.ssm.conv, start, start + size,
                                   axis=0)[:, slot][:, None]
        x, (st, cv) = jax.lax.scan(body, x, (grp, h0s, c0s),
                                   unroll=layer_unroll())
        states.append(st[:, 0])
        convs.append(cv[:, 0])

        a, pk, pv = attn.attn_prefill_paged(
            sp["attn"], cfg, rms_norm(x, sp["ln1"], cfg.norm_eps),
            positions, cache.kv.k[g], cache.kv.v[g], table_row)
        x = x + a
        x = x + swiglu(rms_norm(x, sp["ln2"], cfg.norm_eps), **sp["mlp"])
        pks.append(pk)
        pvs.append(pv)
        start += size

    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = jnp.einsum("btd,dv->btv", x, params["head"])
    st_all = jnp.concatenate(states, axis=0)
    cv_all = jnp.concatenate(convs, axis=0)
    new_cache = HybridCache(
        ssm=mamba2.SSMCache(conv=cache.ssm.conv.at[:, slot].set(cv_all),
                            state=cache.ssm.state.at[:, slot].set(st_all),
                            pos=cache.ssm.pos),
        kv=cache.kv._replace(k=jnp.stack(pks), v=jnp.stack(pvs)),
        pos=cache.pos,
    )
    return logits, new_cache


def decode_step_paged(params, cfg, token, cache: HybridCache, active,
                      use_kernel=False):
    """decode_step over the slot batch: shared-attn KV through the page
    tables (inactive rows -> trash page), SSM state frozen on inactive
    rows."""
    x = params["embed"][token]
    sp = params["shared_attn"]

    states, convs, pks, pvs = [], [], [], []
    start = 0
    for g, size in enumerate(_group_sizes(cfg)):
        grp = _slice_layers(params["layers"], start, size)

        def body(h, inp):
            lp, cc, st = inp
            out, ncc, nst = mamba2.ssm_block_decode(lp, cfg, h, cc, st)
            return out, (ncc, nst)

        cc = jax.lax.slice_in_dim(cache.ssm.conv, start, start + size, axis=0)
        st = jax.lax.slice_in_dim(cache.ssm.state, start, start + size, axis=0)
        x, (ncc, nst) = jax.lax.scan(body, x, (grp, cc, st),
                                     unroll=layer_unroll())
        convs.append(jnp.where(active[None, :, None, None], ncc, cc))
        states.append(jnp.where(active[None, :, None, None, None], nst, st))

        a, pk, pv = attn.attn_decode_paged(
            sp["attn"], cfg, rms_norm(x, sp["ln1"], cfg.norm_eps),
            cache.kv.k[g], cache.kv.v[g], cache.kv.table, cache.kv.pos,
            active, use_kernel=use_kernel)
        x = x + a
        x = x + swiglu(rms_norm(x, sp["ln2"], cfg.norm_eps), **sp["mlp"])
        pks.append(pk)
        pvs.append(pv)
        start += size

    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = jnp.einsum("btd,dv->btv", x, params["head"])
    step = active.astype(jnp.int32)
    new_cache = HybridCache(
        ssm=mamba2.SSMCache(conv=jnp.concatenate(convs, axis=0),
                            state=jnp.concatenate(states, axis=0),
                            pos=cache.ssm.pos + step),
        kv=cache.kv._replace(k=jnp.stack(pks), v=jnp.stack(pvs),
                             pos=cache.kv.pos + step),
        pos=cache.pos + step,
    )
    return logits, new_cache


def paged_to_dense(cache: HybridCache) -> HybridCache:
    """Chunk view for decode: gather the shared-attn page pool into a
    dense per-slot KV cache (the SSM half is already dense)."""
    return HybridCache(ssm=cache.ssm,
                       kv=attn.paged_to_dense_kv(cache.kv),
                       pos=cache.pos)


def paged_restore(cache: HybridCache, dense: HybridCache, active,
                  steps) -> HybridCache:
    step = steps * active.astype(jnp.int32)
    return HybridCache(
        ssm=mamba2.paged_restore(cache.ssm, dense.ssm, active, steps),
        kv=attn.dense_to_paged_kv(cache.kv, dense.kv, active, steps),
        pos=cache.pos + step,
    )
