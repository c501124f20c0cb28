"""Family dispatch: a uniform Model API over all six architecture
families.

    model = build_model(cfg)
    params = model.init(key)
    logits, aux = model.apply(params, batch)          # training forward
    loss, aux  = model.loss(params, batch)
    cache      = model.init_cache(params, batch_size, max_len)
    logits, cache = model.prefill(params, batch, cache)
    logits, cache = model.decode(params, batch, cache)

``batch`` is a dict; keys per family (see data/synthetic.py and
launch/specs.py):
    dense/moe/ssm/hybrid : tokens, labels
    vlm                  : tokens, labels, patch_embeds
    audio                : tokens (B,K,T), labels (B,K,T), cond

Cache position contract (``cache_positions`` / ``with_cache_positions``):
every cache pytree carries one or more ``pos`` leaves counting tokens
absorbed so far.  ``prefill`` over T tokens advances pos by EXACTLY T and
each ``decode`` call by EXACTLY 1 — so after prefill(T) + G decodes,
``cache_positions(cache) == T + G``.  The first generated token comes
from the PREFILL logits (``logits[:, -1]``); feeding the last prompt
token through ``decode`` instead writes its KV twice (slots T-1 and T)
and shifts every later position by one.  ``pos`` may be a scalar or a
(B,) vector — the serving engine uses the vector form so every batch
row (slot) keeps its own offset.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.models import audio as audio_mod
from repro.models import hybrid as hybrid_mod
from repro.models import mamba2 as ssm_mod
from repro.models import transformer as tfm
from repro.models import vlm as vlm_mod
from repro.models.layers import chunked_cross_entropy, cross_entropy


@dataclass(frozen=True)
class Model:
    cfg: Any
    init: Callable
    apply: Callable              # (params, batch) -> (logits, aux)
    loss: Callable               # (params, batch) -> (scalar, aux)
    init_cache: Callable         # (params, batch_size, max_len) -> cache
    prefill: Callable            # (params, batch, cache[, valid]) ->
                                 # (logits, cache); ``valid`` () int32 marks
                                 # tokens >= valid as bucket padding
    decode: Callable             # (params, batch, cache) -> (logits, cache)
    # paged serving (PR 7) — page-pool cache, chunked prefill, masked decode
    init_paged_cache: Callable = None
    # (params, num_slots, num_pages, page_size, max_pages) -> cache
    prefill_chunk: Callable = None
    # (params, batch, cache, slot, frontier, valid, total) -> (logits, cache):
    # one (1, C)-token chunk of one slot's prompt; ``frontier`` its absolute
    # start, ``valid`` the live rows, ``total`` the full prompt extent
    decode_paged: Callable = None
    # (params, batch, cache, active) -> (logits, cache): one decode step over
    # the slot batch; ``active`` (B,) bool freezes inactive rows
    paged_to_dense: Callable = None
    # (paged_cache) -> dense cache view: page tables are constant within a
    # decode chunk, so the engine gathers once and scans plain ``decode``
    paged_restore: Callable = None
    # (paged_cache, dense_cache, active, steps) -> paged_cache: scatter the
    # chunk's view back (inactive rows -> trash page, pos frozen)


def is_pos_entry(entry) -> bool:
    """Whether a tree-path entry names a cache position counter."""
    name = getattr(entry, "name", getattr(entry, "key", None))
    return name == "pos"


def cache_positions(cache):
    """The cache's token count: () or (B,) int32.

    Every cache NamedTuple (KVCache / SSMCache / HybridCache, nested or
    not) tags its counters as ``pos`` leaves; they all advance in
    lockstep, so any one of them is *the* position.  Returns the first.
    """
    leaves = jax.tree_util.tree_leaves_with_path(cache)
    for path, leaf in leaves:
        if path and is_pos_entry(path[-1]):
            return leaf
    raise ValueError("cache has no 'pos' leaf")


def with_cache_positions(cache, pos):
    """Return ``cache`` with EVERY ``pos`` leaf replaced by ``pos``.

    Passing a (num_slots,) vector switches the cache to per-slot
    offsets — the layout the serving engine decodes with.
    """
    pos = jnp.asarray(pos, jnp.int32)

    def repl(path, leaf):
        if path and is_pos_entry(path[-1]):
            # a fresh buffer per leaf: caches with several pos leaves
            # (HybridCache) must not alias, or donation rejects them
            return pos.copy()
        return leaf

    return jax.tree_util.tree_map_with_path(repl, cache)


def _lm_loss(hidden_fn, cfg):
    """Hidden-states + T-chunked CE: the (B, T, V) logits tensor is
    never materialized (V reaches 202k for llama4-scout)."""
    @jax.named_scope("model")
    def loss(params, batch):
        h, aux = hidden_fn(params, batch)
        head = params["embed"].T if cfg.tie_embeddings else params["head"]
        with jax.named_scope("head_loss"):
            ce = chunked_cross_entropy(h, head, batch["labels"])
        return ce + aux, {"ce": ce, "aux": aux}
    return loss


def _audio_loss(hidden_fn, cfg):
    @jax.named_scope("model")
    def loss(params, batch):
        h, aux = hidden_fn(params, batch)            # (B, T, d)
        labels = batch["labels"].transpose(0, 2, 1)  # (B, T, K)
        with jax.named_scope("head_loss"):
            ce = chunked_cross_entropy(h, params["head"], labels,
                                       num_streams=cfg.num_codebooks)
        return ce + aux, {"ce": ce, "aux": aux}
    return loss


def build_model(cfg, use_flash: bool = False, remat: bool = False,
                use_paged_kernel: bool = False) -> Model:
    fam = cfg.family

    if fam in ("dense", "moe"):
        apply_fn = lambda p, b: tfm.forward(p, cfg, b["tokens"],
                                            use_flash=use_flash, remat=remat)
        hidden_fn = lambda p, b: tfm.forward_hidden(p, cfg, b["tokens"],
                                                    use_flash=use_flash, remat=remat)
        return Model(
            cfg=cfg,
            init=lambda key, dtype=jnp.float32: tfm.init_params(key, cfg, dtype),
            apply=apply_fn,
            loss=_lm_loss(hidden_fn, cfg),
            init_cache=lambda p, bs, ml, dtype=jnp.float32: tfm.init_cache(p, cfg, bs, ml, dtype),
            prefill=lambda p, b, c, valid=None: tfm.prefill(p, cfg, b["tokens"], c, use_flash=use_flash),
            decode=lambda p, b, c: tfm.decode_step(p, cfg, b["tokens"], c),
            init_paged_cache=lambda p, bs, np_, ps, mp, dtype=jnp.float32:
                tfm.init_paged_cache(p, cfg, bs, np_, ps, mp, dtype),
            prefill_chunk=lambda p, b, c, slot, frontier, valid, total:
                tfm.prefill_chunk(p, cfg, b["tokens"], c, slot, frontier, valid),
            decode_paged=lambda p, b, c, active:
                tfm.decode_step_paged(p, cfg, b["tokens"], c, active,
                                      use_kernel=use_paged_kernel),
            paged_to_dense=tfm.paged_to_dense,
            paged_restore=tfm.paged_restore,
        )

    if fam == "ssm":
        apply_fn = lambda p, b: ssm_mod.forward(p, cfg, b["tokens"], remat=remat)
        hidden_fn = lambda p, b: ssm_mod.forward_hidden(p, cfg, b["tokens"], remat=remat)
        return Model(
            cfg=cfg,
            init=lambda key, dtype=jnp.float32: ssm_mod.init_params(key, cfg, dtype),
            apply=apply_fn,
            loss=_lm_loss(hidden_fn, cfg),
            init_cache=lambda p, bs, ml, dtype=jnp.float32: ssm_mod.init_cache(cfg, bs, dtype),
            prefill=lambda p, b, c, valid=None: ssm_mod.prefill(p, cfg, b["tokens"], c, valid=valid),
            decode=lambda p, b, c: ssm_mod.decode_step(p, cfg, b["tokens"], c),
            init_paged_cache=lambda p, bs, np_, ps, mp, dtype=jnp.float32:
                ssm_mod.init_paged_cache(p, cfg, bs, np_, ps, mp, dtype),
            prefill_chunk=lambda p, b, c, slot, frontier, valid, total:
                ssm_mod.prefill_chunk(p, cfg, b["tokens"], c, slot, frontier, valid),
            decode_paged=lambda p, b, c, active:
                ssm_mod.decode_step_paged(p, cfg, b["tokens"], c, active),
            paged_to_dense=ssm_mod.paged_to_dense,
            paged_restore=ssm_mod.paged_restore,
        )

    if fam == "hybrid":
        apply_fn = lambda p, b: hybrid_mod.forward(p, cfg, b["tokens"],
                                                   remat=remat, use_flash=use_flash)
        hidden_fn = lambda p, b: hybrid_mod.forward_hidden(p, cfg, b["tokens"],
                                                           remat=remat, use_flash=use_flash)
        return Model(
            cfg=cfg,
            init=lambda key, dtype=jnp.float32: hybrid_mod.init_params(key, cfg, dtype),
            apply=apply_fn,
            loss=_lm_loss(hidden_fn, cfg),
            init_cache=lambda p, bs, ml, dtype=jnp.float32: hybrid_mod.init_cache(cfg, bs, ml, dtype),
            prefill=lambda p, b, c, valid=None: hybrid_mod.prefill(p, cfg, b["tokens"], c,
                                                                   use_flash=use_flash, valid=valid),
            decode=lambda p, b, c: hybrid_mod.decode_step(p, cfg, b["tokens"], c),
            init_paged_cache=lambda p, bs, np_, ps, mp, dtype=jnp.float32:
                hybrid_mod.init_paged_cache(p, cfg, bs, np_, ps, mp, dtype),
            prefill_chunk=lambda p, b, c, slot, frontier, valid, total:
                hybrid_mod.prefill_chunk(p, cfg, b["tokens"], c, slot, frontier, valid),
            decode_paged=lambda p, b, c, active:
                hybrid_mod.decode_step_paged(p, cfg, b["tokens"], c, active,
                                             use_kernel=use_paged_kernel),
            paged_to_dense=hybrid_mod.paged_to_dense,
            paged_restore=hybrid_mod.paged_restore,
        )

    if fam == "vlm":
        apply_fn = lambda p, b: vlm_mod.forward(p, cfg, b["tokens"], b["patch_embeds"],
                                                use_flash=use_flash, remat=remat)
        hidden_fn = lambda p, b: vlm_mod.forward_hidden(p, cfg, b["tokens"],
                                                        b["patch_embeds"],
                                                        use_flash=use_flash, remat=remat)
        return Model(
            cfg=cfg,
            init=lambda key, dtype=jnp.float32: vlm_mod.init_params(key, cfg, dtype),
            apply=apply_fn,
            loss=_lm_loss(hidden_fn, cfg),
            init_cache=lambda p, bs, ml, dtype=jnp.float32: vlm_mod.init_cache(p, cfg, bs, ml, dtype),
            prefill=lambda p, b, c, valid=None: vlm_mod.prefill(p, cfg, b["tokens"], b["patch_embeds"], c),
            decode=lambda p, b, c: vlm_mod.decode_step(p, cfg, b["tokens"], c),
            init_paged_cache=lambda p, bs, np_, ps, mp, dtype=jnp.float32:
                vlm_mod.init_paged_cache(p, cfg, bs, np_, ps, mp, dtype),
            prefill_chunk=lambda p, b, c, slot, frontier, valid, total:
                vlm_mod.prefill_chunk(p, cfg, b["tokens"], b["patch_embeds"], c,
                                      slot, frontier, valid, total),
            decode_paged=lambda p, b, c, active:
                vlm_mod.decode_step_paged(p, cfg, b["tokens"], c, active,
                                          use_kernel=use_paged_kernel),
            paged_to_dense=tfm.paged_to_dense,
            paged_restore=tfm.paged_restore,
        )

    if fam == "audio":
        apply_fn = lambda p, b: audio_mod.forward(p, cfg, b["tokens"], b.get("cond"),
                                                  use_flash=use_flash, remat=remat)
        hidden_fn = lambda p, b: audio_mod.forward_hidden(p, cfg, b["tokens"],
                                                          b.get("cond"),
                                                          use_flash=use_flash, remat=remat)
        return Model(
            cfg=cfg,
            init=lambda key, dtype=jnp.float32: audio_mod.init_params(key, cfg, dtype),
            apply=apply_fn,
            loss=_audio_loss(hidden_fn, cfg),
            init_cache=lambda p, bs, ml, dtype=jnp.float32: audio_mod.init_cache(p, cfg, bs, ml, dtype),
            prefill=lambda p, b, c, valid=None: audio_mod.prefill(p, cfg, b["tokens"], c, cond=b.get("cond")),
            decode=lambda p, b, c: audio_mod.decode_step(p, cfg, b["tokens"], c, cond=None),
            init_paged_cache=lambda p, bs, np_, ps, mp, dtype=jnp.float32:
                audio_mod.init_paged_cache(p, cfg, bs, np_, ps, mp, dtype),
            prefill_chunk=lambda p, b, c, slot, frontier, valid, total:
                audio_mod.prefill_chunk(p, cfg, b["tokens"], c, slot, frontier,
                                        valid, cond=b.get("cond")),
            decode_paged=lambda p, b, c, active:
                audio_mod.decode_step_paged(p, cfg, b["tokens"], c, active,
                                            use_kernel=use_paged_kernel),
            paged_to_dense=tfm.paged_to_dense,
            paged_restore=tfm.paged_restore,
        )

    raise ValueError(f"unknown family: {fam}")
