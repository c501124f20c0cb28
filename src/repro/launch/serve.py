"""Serving CLI — a thin driver over the continuous-batching engine
(``repro/serving/``).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke
    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b --smoke \\
        --requests 8 --slots 4 --prompt-len 32 --mixed-lens --gen 16
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke --naive

What gets served is the registry surface, not raw ``model.init``
params: ``--algo`` resolves an :class:`~repro.core.algorithm.Algorithm`,
the state comes from ``algo.init`` (or ``--resume`` a training
checkpoint — algo-stamp validated), and the served weights are
``algo.deployable(state)`` — for Parle, the replica average the paper
evaluates (§1.2), i.e. exactly what the trainer would ship.

Modes:

* default — the engine: ``--slots``-wide continuous batching, mixed
  prompt lengths (``--mixed-lens``), staggered arrivals
  (``--arrive-every``), greedy or ``--temperature``/``--top-k``.
* ``--naive`` — the fixed one-request-at-a-time reference loop (first
  token from the prefill logits; measured post-warm-up).
* ``--paged`` — the paged KV cache: ``--page-size`` token pages behind
  per-slot page tables, ``--prefill-chunk``-token chunked prefill
  interleaved with decode, hash-matched prefix sharing, and
  page-exhaustion backpressure (``--num-pages`` bounds the pool).

All throughput numbers are measured AFTER warm-up with
``block_until_ready``; compile time is reported as its own field.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint as ckpt
from repro.configs import ParleConfig, get_config, smoke_variant
from repro.core import registry
from repro.data.synthetic import TokenStream
from repro.models.model import build_model, cache_positions
from repro.obs import Obs
from repro.serving import (Engine, SamplingParams, make_naive_fns,
                           naive_generate)
from repro.utils.compile_cache import enable_compile_cache


def _prompt_lengths(args):
    if not args.mixed_lens:
        return [args.prompt_len] * args.requests
    # a deterministic spread around --prompt-len (at least 4 tokens)
    base = args.prompt_len
    return [max(4, base - 1 + (3 * i) % (base // 2 + 2))
            for i in range(args.requests)]


def _make_requests(cfg, args, key):
    """Per-request prompts (+ per-request conditioning, keys split off
    the conditioning stream — never the params-init key)."""
    stream = TokenStream(vocab_size=cfg.vocab_size,
                         seq_len=max(_prompt_lengths(args)),
                         batch_size=args.requests, seed=args.seed,
                         num_codebooks=cfg.num_codebooks)
    toks = np.asarray(stream.batch(0)["tokens"])
    out = []
    for i, T in enumerate(_prompt_lengths(args)):
        req = {"tokens": toks[i, ..., :T]}
        if cfg.family == "vlm":
            req["patch_embeds"] = jax.random.normal(
                jax.random.fold_in(key, 2 * i),
                (cfg.num_patches, cfg.d_model))
        if cfg.family == "audio":
            req["cond"] = jax.random.normal(
                jax.random.fold_in(key, 2 * i + 1),
                (cfg.cond_len, cfg.d_model))
        out.append(req)
    return out


def _naive_serve(cfg, params, requests, args, obs):
    """One request at a time, batch=1 — the engine's oracle.  The first
    timed pass doubles as the warm-up measurement (compile included);
    the second pass, device-synced, is the reported throughput."""
    fns = make_naive_fns(cfg, SamplingParams(args.temperature, args.top_k))
    model = build_model(cfg)
    max_len = max(r["tokens"].shape[-1] for r in requests) + args.gen

    sample_key = jax.random.PRNGKey(args.seed + 1)

    def one_pass():
        outs, pos = [], []
        t0 = time.perf_counter()
        for i, r in enumerate(requests):
            batch = {k: jnp.asarray(v)[None] for k, v in r.items()}
            cache = model.init_cache(params, 1, max_len)
            toks, cache = naive_generate(fns, params, batch, cache, args.gen,
                                         key=jax.random.fold_in(sample_key, i))
            outs.append(np.asarray(toks[0]))
            pos.append(int(np.asarray(cache_positions(cache))[()]))
        jax.block_until_ready(toks)
        return outs, pos, time.perf_counter() - t0

    _, _, cold_s = one_pass()            # warm-up: includes jit compile
    outs, pos, warm_s = one_pass()       # steady state
    gen_total = sum(o.size for o in outs)
    print(json.dumps(obs.emit(
        "serve_summary", phase="naive", requests=len(requests),
        new_tokens=int(gen_total),
        compile_s=round(cold_s - warm_s, 2),
        wall_s=round(warm_s, 3),
        tokens_per_s=round(gen_total / max(warm_s, 1e-9), 1),
        cache_positions=pos,
        sample=outs[0].reshape(-1)[:8].tolist(),
    )), flush=True)


def _engine_serve(cfg, params, requests, args, obs):
    engine = Engine(cfg, params, num_slots=args.slots,
                    max_len=max(r["tokens"].shape[-1] for r in requests)
                    + args.gen,
                    decode_chunk=args.decode_chunk,
                    sampling=SamplingParams(args.temperature, args.top_k),
                    seed=args.seed, paged=args.paged,
                    page_size=args.page_size,
                    num_pages=args.num_pages if args.num_pages > 0 else None,
                    prefill_chunk=args.prefill_chunk,
                    registry=obs.registry, tracer=obs.tracer)
    for i, r in enumerate(requests):
        engine.submit(r["tokens"], max_new_tokens=args.gen,
                      eos_id=args.eos_id if args.eos_id >= 0 else None,
                      arrival=(i // max(args.slots, 1)) * args.arrive_every,
                      cond=r.get("cond"), patch_embeds=r.get("patch_embeds"))
    t0 = time.perf_counter()
    results = engine.run()
    wall = time.perf_counter() - t0
    gen_total = sum(int(np.asarray(t).size) for t in results.values())
    rep = engine.throughput()
    rep.update({
        "phase": "engine", "requests": len(requests), "slots": args.slots,
        "decode_chunk": args.decode_chunk, "new_tokens": gen_total,
        "wall_s": round(wall, 3),
        "sample": np.asarray(results[0]).reshape(-1)[:8].tolist(),
    })
    if args.paged:
        rep.update({"paged": True, "page_size": args.page_size,
                    "num_pages": engine.num_pages,
                    "prefill_chunk": engine.prefill_chunk_len})
    print(json.dumps(obs.emit("serve_summary", **rep)), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--algo", default="parle", choices=registry.names())
    ap.add_argument("--replicas", type=int, default=3,
                    help="replica count of the (fresh or restored) state")
    ap.add_argument("--resume", default="",
                    help="training checkpoint to serve (validated "
                         "against --algo's stamp)")
    ap.add_argument("--requests", "--batch", dest="requests", type=int,
                    default=4, help="number of requests to serve")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode-batch width of the engine")
    ap.add_argument("--decode-chunk", type=int, default=8,
                    help="decode steps fused per engine step (lax.scan)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--mixed-lens", action="store_true",
                    help="vary prompt lengths across requests")
    ap.add_argument("--arrive-every", type=int, default=0,
                    help="stagger arrivals: each slot-sized wave of "
                         "requests arrives this many engine steps apart")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="stop a request early on this token (-1: off)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--naive", action="store_true",
                    help="the one-request-at-a-time reference loop")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: page-pool layout, chunked "
                         "prefill, prefix sharing, backpressure")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged mode)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page-pool size incl. the trash page "
                         "(0: slots * ceil(max_len/page_size) + 1)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prompt tokens prefilled per engine step "
                         "(paged mode; interleaves with decode)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default="",
                    help="write schema-versioned metrics/event JSONL here")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome-trace JSON (compile / prefill / "
                         "decode spans) here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    model = build_model(cfg)
    # independent streams: params init vs conditioning inputs (never
    # reuse the init key verbatim for data)
    key_init, key_cond = jax.random.split(jax.random.PRNGKey(args.seed))

    algo = registry.get(args.algo)
    pcfg = algo.canonicalize_cfg(ParleConfig(n_replicas=args.replicas))
    state = algo.init(model.init(key_init), pcfg)
    if args.resume:
        state = ckpt.restore(args.resume, state, algo=args.algo)
    params = algo.deployable(state)
    print(json.dumps({"serving": args.algo, "arch": cfg.name,
                      "mode": "naive" if args.naive else "engine",
                      "replicas": pcfg.n_replicas,
                      "restored": bool(args.resume)}), flush=True)

    obs = Obs(args.metrics_out, args.trace_out, process_name="serve")
    requests = _make_requests(cfg, args, key_cond)
    if args.naive:
        _naive_serve(cfg, params, requests, args, obs)
    else:
        _engine_serve(cfg, params, requests, args, obs)
    obs.finalize()


if __name__ == "__main__":
    main()
