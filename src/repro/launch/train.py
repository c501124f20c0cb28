"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --smoke \\
        --algo parle --replicas 2 --steps 60 --batch 4 --seq 64

Runs any registered algorithm (``repro.core.registry``: parle,
entropy_sgd, elastic_sgd, sgd) through ONE driver code path — no
per-algorithm branching: ``--algo`` resolves an ``Algorithm`` object and
the loop only ever talks to the protocol (init / make_step /
make_sharded_step / deployable / diagnostics).  Trains on the synthetic
token stream with checkpointing (algo-stamped sidecars) and the
replica-diagnostic metrics from §1.2 (overlap / spread).  On a real TPU
slice the same driver runs under a production mesh (``--mesh
replica:n``); on this CPU container use ``--smoke`` (reduced config,
host mesh) plus ``--host-devices n``.
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro.checkpoint import checkpoint as ckpt
from repro.configs import ParleConfig, get_config, smoke_variant
from repro.core import registry
from repro.data.synthetic import TokenStream, replica_batches
from repro.models.model import build_model
from repro.obs import Obs
from repro.runtime import (CheckpointSpec, RoundRunner, emit_progress,
                           resolve_train_policy)
from repro.utils.compile_cache import enable_compile_cache


def build_argparser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config of the same family (CPU-runnable)")
    ap.add_argument("--algo", default="parle", choices=registry.names())
    ap.add_argument("--replicas", type=int, default=0,
                    help="replica count (sgd: data-parallel shards); 0 = "
                         "the mesh replica-axis size, or 3 without --mesh")
    ap.add_argument("--L", type=int, default=25)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4, help="per-replica batch")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--lr-drop-steps", default="",
                    help="comma-separated step boundaries where lr (and "
                         "lr_inner) drop by --lr-drop-factor (paper §4)")
    ap.add_argument("--lr-drop-factor", type=float, default=0.2)
    ap.add_argument("--split-data", action="store_true",
                    help="paper §5: each replica sees a disjoint shard")
    ap.add_argument("--use-kernel", action="store_true",
                    help="fused Pallas updates (interpret on CPU)")
    ap.add_argument("--round-fused", action="store_true",
                    help="compile one whole L-step round (inner scan + "
                         "sync) into a single donated-buffer program and "
                         "stage each round's batches in one jitted "
                         "dispatch, double-buffered against the round — "
                         "Python re-enters once per L steps.  --steps is "
                         "rounded down to a multiple of L")
    ap.add_argument("--precision", default="f32", choices=("f32", "bf16"),
                    help="bf16: store the compute iterate (y / activations"
                         " / grads) in bfloat16; x, z and momenta stay "
                         "f32 masters")
    ap.add_argument("--sync-compress", default="none",
                    choices=("none", "bf16", "int8"),
                    help="quantize the Eq. 8d sync payload (parle/"
                         "entropy_sgd): bf16 halves, int8 (per-chunk "
                         "scales + error-feedback residual in the state) "
                         "quarters the wire bytes")
    ap.add_argument("--sync-policy", default="",
                    choices=("", "barrier", "overlap", "async"),
                    help="consensus schedule (repro.runtime): 'barrier' "
                         "(default; fleet blocks on the Eq. 8d sync), "
                         "'overlap' (= --sync-overlap, staleness-1), "
                         "'async' (elastic, multi-process only — run "
                         "through repro.launch.dist_run)")
    ap.add_argument("--sync-overlap", action="store_true",
                    help="staleness-1 overlapped sync (parle/entropy_sgd "
                         "with --round-fused): issue each round's Eq. 8d "
                         "collective BEFORE its inner steps and apply the "
                         "consensus at the start of the next round, so "
                         "the collective overlaps compute instead of "
                         "barriering; the trajectory equals the barrier "
                         "path's after the end-of-training flush")
    ap.add_argument("--mesh", default="",
                    help="shard replicas over a device mesh, e.g. "
                         "'replica:4' or 'replica:2,data:2,model:2'; parle "
                         "syncs lower to one all-reduce every L steps, "
                         "elastic_sgd/sgd to one per step.  'data'/'model' "
                         "axes run planner-driven FSDP x TP INSIDE each "
                         "replica (state leaves shard as (replica, "
                         "*plan(leaf)))")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force this many XLA host-platform devices "
                         "(CPU-only; must be set before jax initializes)")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--resume", default="",
                    help="checkpoint file OR directory to restore (a "
                         "directory resolves to its newest valid "
                         "checkpoint; digests are verified and a corrupt "
                         "file falls back to the newest valid sibling; "
                         "validates that it was written by the same "
                         "--algo)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default="",
                    help="write schema-versioned JSONL events + a final "
                         "metrics_snapshot (counters / gauges / "
                         "histograms) to this path")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome-trace/Perfetto JSON of the "
                         "run's spans (compile, rounds/steps, sync "
                         "flush, eval) to this path; spans end on "
                         "block_until_ready")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv=None):
    """Train from a command line; returns the progress history."""
    enable_compile_cache()
    return run(build_argparser().parse_args(argv))[1]


def run(args, cfg=None):
    """Train as ``args`` say; returns ``(deployable params, history)``.

    ``cfg``: a ModelConfig to train in place of ``--arch``/``--smoke``
    (a registered config cut to size, as chip_smoke.py does)."""
    if args.host_devices:
        import os
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.host_devices}")
    policy = resolve_train_policy(args)
    if cfg is None:
        cfg = get_config(args.arch)
        if args.smoke:
            cfg = smoke_variant(cfg)
    model = build_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)

    algo = registry.get(args.algo)
    mesh, raxis = None, None
    if args.mesh:
        from repro.launch.mesh import make_mesh_from_spec, replica_axis_of
        mesh = make_mesh_from_spec(args.mesh)
        raxis = replica_axis_of(mesh)
        if raxis is None:
            raise SystemExit(f"--mesh {args.mesh!r} has no replica axis")
    n = args.replicas or (mesh.shape[raxis] if mesh is not None else 3)
    drops = tuple(int(s) for s in args.lr_drop_steps.split(",") if s)
    pcfg = algo.canonicalize_cfg(ParleConfig(
        n_replicas=n, L=args.L, lr=args.lr, lr_inner=args.lr,
        batches_per_epoch=max(args.steps // 4, 1),
        lr_drop_steps=drops, lr_drop_factor=args.lr_drop_factor,
        precision=args.precision, sync_compress=args.sync_compress,
        sync_overlap=args.sync_overlap))
    n = pcfg.n_replicas                 # canonicalized (entropy_sgd -> 1)
    _validate_replicas(args, pcfg, mesh, raxis)
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=args.seq,
                         batch_size=args.batch, seed=args.seed)

    obs = Obs(args.metrics_out, args.trace_out, process_name="train")
    # jitted: one fresh buffer per state leaf, so nothing is aliased (a
    # donated round needs that) and no leaf is held twice.  On a mesh
    # the state is built on its planner shardings: each device holds
    # only its replicas and its 1/(data*model) of every leaf, so state
    # too big for one device's HBM is loadable from step 0
    state_sh = None
    if mesh is not None:
        from repro.sharding import partition
        specs = algo.state_pspecs(raxis, params=jax.eval_shape(
            lambda: params), mesh=mesh, cfg=pcfg)
        state_sh = partition.shardings(mesh, specs)
    state = jax.jit(lambda p: algo.init(p, pcfg),
                    out_shardings=state_sh)(params)
    params = jax.eval_shape(lambda: params)      # the planner needs shapes
    start = 0
    if args.resume:
        # resolve ONCE (directory -> newest valid checkpoint; corrupt
        # file -> newest valid sibling) so the restore, the step stamp,
        # and the counter stamp all read the SAME verified file
        args.resume = ckpt.resolve(args.resume)
        state = ckpt.restore(args.resume, state, algo=args.algo)
        if state_sh is not None:
            state = jax.device_put(state, state_sh)
        try:                    # continue the stream + checkpoint numbering
            start = ckpt.latest_step(args.resume)
        except FileNotFoundError:       # sidecar-less foreign checkpoint
            start = 0
        # counters continue monotonically from the checkpoint's stamp
        obs.registry.restore_counters(ckpt.saved_metrics(args.resume))
    if mesh is not None:
        from repro.sharding import planner
        step_fn = policy.make_step_fn(algo, model.loss, pcfg, mesh=mesh,
                                      replica_axis=raxis,
                                      use_kernel=args.use_kernel)
        inner_axes = planner.in_replica_axes(mesh, raxis)
        print(json.dumps(obs.emit(
            "mesh", mesh=dict(mesh.shape), replica_axis=raxis,
            in_replica_axes=list(inner_axes),
            replicas_per_device=n // mesh.shape[raxis])))
    else:
        step_fn = policy.make_step_fn(algo, model.loss, pcfg,
                                      use_kernel=args.use_kernel)

    t0 = time.time()
    runner = RoundRunner(obs, ns="train", checkpoint=CheckpointSpec(
        dir=args.checkpoint_dir, every=args.checkpoint_every,
        algo=args.algo, arch=cfg.name))

    def progress(step, rnd, st, metrics):
        return emit_progress(obs, algo, st, metrics, step, rnd, t0)

    if args.round_fused:
        state, history = _run_rounds(args, algo, policy, pcfg, model,
                                     mesh, raxis, stream, state, start,
                                     n, runner, progress)
    else:
        state, history = runner.run_steps(
            state, step_fn,
            lambda i: replica_batches(stream, i, args.batch, n,
                                      split=args.split_data),
            start=start, steps=args.steps, L=pcfg.L,
            tokens_per_step=args.batch * args.seq * n,
            mesh=mesh, pcfg=pcfg, progress_every=args.log_every,
            progress=progress)

    final = algo.deployable(state)
    with obs.tracer.span("eval") as sp:
        loss, _ = jax.jit(model.loss)(final, _eval_batch(stream, cfg))
        sp.block(loss)
    print(json.dumps(obs.emit(
        "train_final", final_eval_loss=round(float(loss), 4),
        algo=args.algo, arch=cfg.name,
        total_wall_s=round(time.time() - t0, 1))))
    obs.finalize()
    return final, history


def _validate_replicas(args, pcfg, mesh, raxis):
    """Fail fast with a readable message when --replicas and the mesh
    replica axis disagree — the shard_map error this preempts names
    neither flag.  Runs AFTER canonicalize_cfg so the entropy_sgd n->1
    rewrite is covered: ``--algo entropy_sgd --mesh replica:4`` dies
    here with the fix spelled out instead of failing divisibility on a
    count the user never asked for."""
    if mesh is None:
        return
    n_dev = mesh.shape[raxis]
    n = pcfg.n_replicas
    if args.replicas and n != args.replicas and n_dev != n:
        raise SystemExit(
            f"--algo {args.algo} canonicalizes --replicas "
            f"{args.replicas} to n_replicas={n}, which does not fit the "
            f"mesh replica axis {raxis!r} of size {n_dev}; use --algo "
            f"parle to keep {args.replicas} replicas, or a mesh with "
            f"{raxis}:{n}")
    if n % n_dev != 0:
        raise SystemExit(
            f"--replicas {n} is not divisible by the mesh replica axis "
            f"{raxis!r} of size {n_dev} (each device must hold a whole "
            f"number of replicas); pick a multiple of {n_dev} or resize "
            f"the mesh")


def _run_rounds(args, algo, policy, pcfg, model, mesh, raxis, stream,
                state, start, n, runner, progress):
    """Fused-round driver setup: build the policy's round program and
    the jitted batch stager, then hand the loop to the runtime
    (``RoundRunner.run_rounds`` owns staging/spans/counters/checkpoints
    — see repro/runtime/runner.py; this function no longer contains a
    step loop)."""
    from repro.data.synthetic import make_round_batch_fn

    obs = runner.obs
    L = pcfg.L
    rounds = args.steps // L
    if args.steps % L:
        print(json.dumps(obs.emit(
            "note", msg=f"--round-fused runs whole L={L} rounds; "
            f"running {rounds * L} of {args.steps} steps")), flush=True)
    if start % L:
        raise SystemExit(f"--round-fused resumes only from round "
                         f"boundaries (step {start} % L={L} != 0)")
    round_fn = policy.make_round_fn(algo, model.loss, pcfg, mesh=mesh,
                                    replica_axis=raxis or "replica",
                                    use_kernel=args.use_kernel)
    stage = make_round_batch_fn(stream, L, args.batch, n,
                                split=args.split_data)
    return runner.run_rounds(
        state, round_fn, stage, start=start, rounds=rounds, L=L,
        tokens_per_round=L * args.batch * args.seq * n,
        mesh=mesh, pcfg=pcfg,
        progress_every=max(1, args.log_every // L), progress=progress,
        flush_fn=policy.make_flush_fn(algo, pcfg))


def _eval_batch(stream, cfg):
    return stream.batch(10_000_019)      # held-out step index


if __name__ == "__main__":
    main()
