#!/usr/bin/env python3
"""Smoke run of the Parle trainer and the serving engine on a TPU.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chip   # the replica:4 path, four chips

One chip: mamba2-1.3b at its published widths, cut to one layer, trains
Parle (n=2, L=4, fused rounds, f32, batch 2 x 512 per replica, 3 rounds)
twice from one seed, through the objects ``launch/train.py`` uses: once
on the XLA update path and once with the Pallas inner and sync kernels
compiled (``--use-kernel``).  Every loss must be finite and the two
runs' per-step losses must agree.  The engine then serves the trained
model (``algo.deployable(state)``) with its dense cache, and its greedy
tokens are held to the naive loop's.

Four chips (``--four-chip``): the same model with n=4 on a replica:4
mesh, under the barrier sync and under the int8 sync with the Pallas
sync update (``--use-kernel --sync-compress int8``); then the
smoke config with n=4 on the mesh against the same four replicas held
on one device.

Each phase prints its evidence as one JSON line: compile seconds, step
losses, ``peak_bytes_in_use``, token agreement.  The last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any failure
exits non-zero, and so does a run that finds no TPU.  The compile cache
is ``$JAX_COMPILATION_CACHE_DIR`` where set, else ``.jax_cache`` here.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "results" / "chip_smoke"      # span traces (gitignored)

L, ROUNDS, BATCH, SEQ = 4, 3, 2, 512
# the XLA and kernel update paths, and the mesh and one-device layouts,
# compute the same updates in a different order of float operations
LOSS_RTOL = 1e-3
PROMPT_LENS = (32, 61, 96, 128)
GEN = 16
# The engine's batch-of-4 programs and the naive loop's batch-1 programs
# tile their reductions differently.  Where the naive loop's own two best
# logits lie closer than this, the engine may pick the other one; any
# wider gap at a divergence fails the run.
TIE_TOL = 1e-2


def emit(**rec):
    print(json.dumps(rec), flush=True)


def peak_bytes():
    import jax
    return jax.devices()[0].memory_stats()["peak_bytes_in_use"]


def one_layer_mamba2():
    from repro.configs import get_config
    return dataclasses.replace(get_config("mamba2-1.3b"), num_layers=1)


def train(tag, cfg, flags):
    """Parle through ``launch/train.py``'s run; returns (params, losses)."""
    from repro.launch import train as train_lib
    trace = OUT / f"{tag}.trace.json"
    args = train_lib.build_argparser().parse_args([
        "--algo", "parle", "--L", str(L), "--steps", str(L * ROUNDS),
        "--batch", str(BATCH), "--seq", str(SEQ), "--round-fused",
        "--log-every", str(L), "--seed", "0", "--trace-out", str(trace),
        *flags])
    t0 = time.perf_counter()
    params, history = train_lib.run(args, cfg)
    wall = time.perf_counter() - t0
    losses = [x for rec in history for x in rec["step_losses"]]
    events = json.loads(trace.read_text())["traceEvents"]
    compile_s = sum(e["dur"] for e in events
                    if e["name"] == "compile:round") / 1e6
    emit(phase="train", tag=tag, flags=flags, step_losses=losses,
         compile_s=compile_s, wall_s=wall, peak_bytes_in_use=peak_bytes())
    if len(losses) != L * ROUNDS or not all(map(math.isfinite, losses)):
        raise SystemExit(f"{tag}: expected {L * ROUNDS} finite step "
                         f"losses, got {losses}")
    return params, losses


def agree(tag, got, want):
    rel = max(abs(a - b) / abs(b) for a, b in zip(got, want))
    emit(phase="agree", tag=tag, max_rel_diff=rel, rtol=LOSS_RTOL)
    if rel > LOSS_RTOL:
        raise SystemExit(f"{tag}: step losses differ by {rel} relative")


def _naive_logits(fns, model, params, prompt, toks, d, max_len):
    """The naive loop's logits for its token ``d``, fed ``toks[:d]``."""
    import jax.numpy as jnp
    import numpy as np
    prefill_j, decode_j, _ = fns
    logits, cache = prefill_j(params, {"tokens": jnp.asarray(prompt)[None]},
                              model.init_cache(params, 1, max_len))
    for t in toks[:d]:
        logits, cache = decode_j(
            params, {"tokens": jnp.asarray([[t]], jnp.int32)}, cache)
    return np.asarray(logits[0, -1], np.float32)


def serve(cfg, params):
    """The engine (dense cache, greedy) against the naive loop."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data.synthetic import TokenStream
    from repro.models.model import build_model
    from repro.serving import Engine, make_naive_fns, naive_generate

    params = jax.device_put(params)
    max_len = max(PROMPT_LENS) + GEN
    toks = np.asarray(TokenStream(
        vocab_size=cfg.vocab_size, seq_len=max(PROMPT_LENS),
        batch_size=len(PROMPT_LENS), seed=1).batch(0)["tokens"])
    prompts = [toks[i, :n] for i, n in enumerate(PROMPT_LENS)]

    eng = Engine(cfg, params, num_slots=len(prompts), max_len=max_len)
    for p in prompts:
        eng.submit(p, max_new_tokens=GEN)
    t0 = time.perf_counter()
    got = eng.run()
    wall = time.perf_counter() - t0

    fns = make_naive_fns(cfg)
    model = build_model(cfg)
    exact, divergences = 0, []
    for i, p in enumerate(prompts):
        want, _ = naive_generate(fns, params, {"tokens": jnp.asarray(p)[None]},
                                 model.init_cache(params, 1, max_len), GEN)
        want = np.asarray(want[0])
        diff = np.flatnonzero(want != got[i])
        if not diff.size:
            exact += 1
            continue
        d = int(diff[0])
        lg = _naive_logits(fns, model, params, p, want, d, max_len)
        divergences.append({"request": i, "token": d,
                            "margin": float(lg[want[d]] - lg[got[i][d]])})
    emit(phase="serve", prompt_lens=PROMPT_LENS, new_tokens=GEN,
         exact_requests=exact, divergences=divergences,
         engine_compile_s=eng.stats["compile_s"], engine_wall_s=wall,
         sample=got[0].tolist(), peak_bytes_in_use=peak_bytes())
    wide = [d for d in divergences if d["margin"] > TIE_TOL]
    if wide:
        raise SystemExit(f"engine tokens differ from the naive loop's "
                         f"beyond a tie: {wide}")


def one_chip():
    import jax
    cfg = one_layer_mamba2()
    params, xla = train("xla", cfg, ["--replicas", "2"])
    params = jax.device_get(params)          # off the chip for the next run
    _, kernel = train("kernel", cfg, ["--replicas", "2", "--use-kernel"])
    agree("xla_vs_kernel", kernel, xla)
    serve(cfg, params)


def four_chip():
    from repro.configs import get_config, smoke_variant
    cfg = one_layer_mamba2()
    mesh = ["--replicas", "4", "--mesh", "replica:4"]
    train("replica4_barrier", cfg, mesh)
    train("replica4_int8_kernel", cfg,
          mesh + ["--use-kernel", "--sync-compress", "int8"])
    smoke = smoke_variant(get_config("mamba2-1.3b"))
    _, on_mesh = train("smoke_replica4", smoke, mesh)
    _, on_one = train("smoke_one_device", smoke, ["--replicas", "4"])
    agree("mesh_vs_one_device", on_mesh, on_one)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the replica:4 mesh path and what it is "
                         "compared with (needs four chips)")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found "
                         f"{devices[0].platform}")
    need = 4 if args.four_chip else 1
    if len(devices) < need:
        raise SystemExit(f"needs {need} chips, JAX found {len(devices)}")

    sys.path.insert(0, str(ROOT / "src"))
    from repro.utils.compile_cache import enable_compile_cache
    emit(phase="device", kind=devices[0].device_kind, count=len(devices),
         jax=jax.__version__, compile_cache=enable_compile_cache())
    if args.four_chip:
        four_chip()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
