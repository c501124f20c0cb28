"""Multi-process pod-axis launcher: N REAL worker processes on one
machine, under either of two runtime sync policies (repro.runtime):

``--sync-policy barrier`` (default) — the ``pod`` mesh layout of
launch/mesh.py across N processes with ``jax.distributed.initialize``
(CPU collectives via gloo), asserting that the global-mesh sync is
equivalent to the single-process run:

    PYTHONPATH=src python -m repro.launch.dist_run --nproc 2 \\
        --mesh pod:2 --algo parle --smoke --steps 12 --L 3

Workers build the SAME compiled program as a single-process run of the
same mesh spec (same global mesh shape, same shard_map, same per-device
shard layout), so the cross-process gloo all-reduce is the only moving
part — and the parent then runs the single-process reference and
compares the loss streams BIT-FOR-BIT (float hex, not allclose).
Composed specs work too: ``--mesh pod:2,data:2`` runs 2 processes x 2
devices with planner-driven FSDP inside each pod-replica.

``--sync-policy async`` — asynchronous/ELASTIC replica execution: no
global mesh, no gloo, no barrier.  Each worker owns replicas
[i*k, (i+1)*k) of the fleet (k = replicas/nproc), runs fused inner-only
rounds (Eq. 8a-8b) at its own pace, and after ITS round pushes its
quantized ``x+e`` contribution to the parent's consensus
``Coordinator`` (repro.runtime.coordinator), pulling back the
staleness-weighted mean (weights decay with rounds-behind, see
``core.parle.staleness_weighted_mean``).  A straggler delays nobody:
the only wait is the exchange RPC, measured per worker as
``pod.sync_wait_ms``.  Workers may join/leave mid-run (a dead worker is
an implicit leave) and the consensus rebalances over the survivors;
``--checkpoint-out``/``--resume`` let a pod stop and resume with a
DIFFERENT worker count (the checkpoint carries the model-shaped
consensus, not any per-worker layout):

    PYTHONPATH=src python -m repro.launch.dist_run --nproc 3 \\
        --sync-policy async --algo parle --smoke --steps 9 --L 3 \\
        --straggle-ms 300 --straggle-worker 2

All jax imports are deferred: XLA_FLAGS (per-process device count) and
the distributed runtime must be configured before jax initializes.

The pod is a host-CPU stand-in for a multi-host pod: every worker runs
with ``JAX_PLATFORMS=cpu``.  Its barrier collectives are gloo's, which
are CPU-only, and N workers on one TPU host would each claim every chip.
Across the chips of one host, one process drives them all:
``python -m repro.launch.train --mesh replica:4``.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

LOSS_TAG = "DISTLOSS "


def build_argparser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nproc", type=int, default=2,
                    help="number of processes to span the mesh across")
    ap.add_argument("--mesh", default="",
                    help="mesh spec (default 'pod:<nproc>'); the first "
                         "axis must be divisible by --nproc")
    ap.add_argument("--algo", default="parle")
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--replicas", type=int, default=0,
                    help="0 = the mesh replica-axis size (barrier) or "
                         "--nproc (async; must divide by --nproc)")
    ap.add_argument("--L", type=int, default=3)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--batch", type=int, default=2, help="per-replica batch")
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--port", type=int, default=9876,
                    help="coordinator port for jax.distributed")
    ap.add_argument("--sync-policy", default="barrier",
                    choices=("barrier", "async"),
                    help="barrier: bulk-synchronous global-mesh pod "
                         "(bit-for-bit vs single-process); async: "
                         "elastic per-worker rounds + staleness-weighted "
                         "consensus via the host coordinator")
    ap.add_argument("--sync-compress", default="none",
                    choices=("none", "bf16", "int8"),
                    help="async contribution codec (the x+e payload "
                         "each worker pushes; error feedback rides the "
                         "worker state)")
    ap.add_argument("--decay", type=float, default=0.5,
                    help="async staleness decay: a contribution r rounds "
                         "behind the freshest weighs count * decay**r")
    ap.add_argument("--coord-port", type=int, default=0,
                    help="consensus coordinator port (async; default "
                         "--port + 1)")
    ap.add_argument("--straggle-ms", type=float, default=0.0,
                    help="inject this per-round delay into "
                         "--straggle-worker (straggler-tolerance probe)")
    ap.add_argument("--straggle-worker", type=int, default=-1)
    ap.add_argument("--checkpoint-out", default="",
                    help="async: checkpoint the final consensus (+ "
                         "per-worker contribution stamps) here")
    ap.add_argument("--resume", default="",
                    help="async: resume the consensus from a "
                         "--checkpoint-out file OR a checkpoint "
                         "directory (resolves to its newest valid "
                         "checkpoint; a corrupt file falls back to the "
                         "newest valid sibling); the worker count may "
                         "differ from the writing pod's")
    ap.add_argument("--fault-plan", default="",
                    help="chaos harness: a seeded FaultPlan as inline "
                         "JSON or @file (runtime/faults.py) — scripted "
                         "worker crash/hang/drop/corrupt/poison/jitter "
                         "faults plus coordinator kills, replayed "
                         "deterministically from the plan seed")
    ap.add_argument("--liveness-s", type=float, default=30.0,
                    help="async: coordinator heartbeat-liveness "
                         "deadline; a worker silent this long is "
                         "evicted from the consensus table")
    ap.add_argument("--no-compare", action="store_true",
                    help="skip the single-process reference run")
    ap.add_argument("--tol", type=float, default=0.0,
                    help="relative loss tolerance for the comparison; "
                         "0 (default) = bit-for-bit.  Pure replica/pod "
                         "meshes are bit-exact; composed specs (e.g. "
                         "pod:2,data:2) compile per-topology GSPMD "
                         "programs that differ by a few ulps")
    ap.add_argument("--metrics-out", default="",
                    help="pod metrics JSONL: each worker writes "
                         "<path>.worker<i>; the parent merges the "
                         "per-process registry snapshots into <path> "
                         "as a pod_merged event")
    ap.add_argument("--trace-out", default="",
                    help="pod Chrome trace: workers write "
                         "<path>.worker<i>; the parent concatenates "
                         "them into <path> (one pid per process)")
    ap.add_argument("--_worker", type=int, default=-1,
                    help="(internal) worker index; set by the parent")
    return ap


def _mesh_spec(args) -> str:
    return args.mesh or f"pod:{args.nproc}"


def _mesh_size(spec: str) -> int:
    from functools import reduce
    sizes = [int(p.partition(":")[2]) for p in spec.split(",") if p.strip()]
    return reduce(lambda a, b: a * b, sizes, 1)


def _make_global(x, sharding):
    """Assemble a global jax.Array from a host value every process holds
    in full (deterministic streams / replicated init): each process
    device_puts exactly its addressable shards."""
    import jax
    import numpy as np
    x = np.asarray(x)
    idx_map = sharding.addressable_devices_indices_map(x.shape)
    arrs = [jax.device_put(x[idx], d) for d, idx in idx_map.items()]
    return jax.make_array_from_single_device_arrays(x.shape, sharding, arrs)


def _maybe_fail_for_test(worker: int):
    """Orphan-handling test hook: REPRO_TEST_FAIL_WORKER=<i> makes
    worker i die with rc 41 right after joining the collective group —
    its peers then hang in their first collective, which is exactly the
    wedge the parent's process-group kill must break."""
    if os.environ.get("REPRO_TEST_FAIL_WORKER", "") == str(worker):
        sys.stderr.write(f"worker {worker}: injected test failure\n")
        sys.exit(41)


def run_worker(args) -> list:
    """One process of the barrier pod: initialize the distributed
    runtime (when nproc > 1), build the global mesh, and hand the step
    stream to the runtime's ``RoundRunner`` (repro/runtime/runner.py —
    this function no longer contains its own step loop).  Emits
    bit-exact losses (proc 0 only)."""
    need = _mesh_size(_mesh_spec(args))
    if need % args.nproc != 0:
        raise SystemExit(f"mesh {_mesh_spec(args)!r} ({need} devices) not "
                         f"divisible by --nproc {args.nproc}")
    per_proc = need // args.nproc
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={per_proc}")

    import jax
    if args.nproc > 1:
        # gloo is the CPU cross-process collective backend; must be
        # configured before the backend initializes
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=f"127.0.0.1:{args.port}",
            num_processes=args.nproc, process_id=args._worker)
    proc = jax.process_index()
    _maybe_fail_for_test(args._worker)

    import jax.numpy as jnp
    import numpy as np

    from repro.configs import ParleConfig, get_config, smoke_variant
    from repro.core import registry
    from repro.data.synthetic import TokenStream, replica_batches
    from repro.launch.mesh import make_mesh_from_spec, replica_axis_of
    from repro.models.model import build_model
    from repro.obs import Obs
    from repro.runtime import RoundRunner
    from repro.sharding import partition

    # each worker writes its own telemetry files (the parent passed
    # per-worker paths); the trace pid is the process index so the
    # merged pod trace shows one lane per process
    obs = Obs(args.metrics_out, args.trace_out, pid=proc,
              process_name=f"pod-worker{proc}")

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    model = build_model(cfg)
    algo = registry.get(args.algo)

    mesh = make_mesh_from_spec(_mesh_spec(args))
    raxis = replica_axis_of(mesh)
    if raxis is None:
        raise SystemExit(f"--mesh {_mesh_spec(args)!r} has no replica axis")
    n = args.replicas or mesh.shape[raxis]
    pcfg = algo.canonicalize_cfg(ParleConfig(
        n_replicas=n, L=args.L, lr=args.lr, lr_inner=args.lr,
        batches_per_epoch=max(args.steps // 4, 1)))
    n = pcfg.n_replicas

    # init ON the global mesh (out_shardings = the planner state specs):
    # every process traces the same closure, each device materializes
    # exactly its shard — no host-side global state is ever gathered
    key = jax.random.PRNGKey(args.seed)
    params_sds = jax.eval_shape(model.init, key)
    specs = algo.state_pspecs(raxis, params=params_sds, mesh=mesh)
    state_sh = partition.shardings(mesh, specs)
    state = jax.jit(lambda: algo.init(model.init(key), pcfg),
                    out_shardings=state_sh)()

    step_fn = algo.make_sharded_step(model.loss, pcfg, mesh,
                                     replica_axis=raxis)
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=args.seq,
                         batch_size=args.batch, seed=args.seed)
    from jax.sharding import NamedSharding, PartitionSpec as P
    bshard = NamedSharding(mesh, P(raxis))

    mesh_rec = obs.emit("mesh", mesh=dict(mesh.shape), replica_axis=raxis,
                        processes=jax.process_count(),
                        devices_per_process=per_proc,
                        global_devices=jax.device_count())
    if proc == 0:
        print(json.dumps(mesh_rec), flush=True)

    records = []
    local_replicas = max(n // max(jax.process_count(), 1), 1)

    def batch_fn(i):
        host_batch = replica_batches(stream, i, args.batch, n)
        return jax.tree.map(lambda b: _make_global(b, bshard), host_batch)

    # barrier-wait probe: a SEPARATE tiny all-reduce program over a
    # pod-sharded vector, timed at every round start.  Every process
    # dispatches it at the same point of the step sequence, so the
    # measured duration is how long THIS worker waits for the slowest
    # peer to arrive — per-worker sync_wait evidence without touching
    # the training program (the loss stream stays bit-for-bit).
    probe = None
    if args.nproc > 1 and obs.enabled:
        probe_arr = _make_global(np.ones(mesh.shape[raxis], np.float32),
                                 NamedSharding(mesh, P(raxis)))
        psum = jax.jit(lambda x: jnp.sum(x))
        jax.block_until_ready(psum(probe_arr))     # compile (symmetric)
        probe = lambda: jax.block_until_ready(psum(probe_arr))

    round_t = {"t": None}

    def pre_step(i):
        if i % args.L:
            return
        # round boundary: injected straggle, then the sync-wait probe
        if args.straggle_ms > 0 and proc == args.straggle_worker:
            time.sleep(args.straggle_ms / 1e3)
        if probe is not None:
            t = time.perf_counter()
            probe()
            obs.registry.histogram("pod.sync_wait_ms", worker=proc) \
               .observe((time.perf_counter() - t) * 1e3)
        now = time.perf_counter()
        if round_t["t"] is not None and obs.enabled:
            obs.registry.histogram("pod.round_wall_ms", worker=proc) \
               .observe((now - round_t["t"]) * 1e3)
        round_t["t"] = now

    def on_step(i, metrics, sp):
        loss = float(metrics["loss"])    # out_specs P() => replicated
        sp.set(loss=round(loss, 6))
        rec = {"step": i + 1, "loss_hex": loss.hex(),
               "loss": round(loss, 6)}
        if obs.enabled:
            obs.registry.gauge("pod.loss").set(rec["loss"])
        obs.emit("pod_step", step=i + 1, loss=rec["loss"], proc=proc,
                 loss_hex=rec["loss_hex"])
        records.append(rec)
        if proc == 0:
            print(LOSS_TAG + json.dumps(rec), flush=True)

    runner = RoundRunner(obs, ns="pod")
    state, _ = runner.run_steps(
        state, step_fn, batch_fn, start=0, steps=args.steps, L=args.L,
        tokens_per_step=args.batch * args.seq * local_replicas,
        mesh=mesh, pcfg=pcfg, span_cat="train",
        on_step=on_step, pre_step=pre_step)
    if round_t["t"] is not None and obs.enabled:
        obs.registry.histogram("pod.round_wall_ms", worker=proc) \
           .observe((time.perf_counter() - round_t["t"]) * 1e3)
    obs.finalize()
    return records


def _run_async_worker(args) -> list:
    """One process of the async/elastic pod: PLAIN process (no
    jax.distributed — a fixed-size collective world cannot be elastic),
    owning replicas [offset, offset + local_n) of the fleet via the
    local vmap path.  Rounds are the inner-only fused program; consensus
    is the AsyncElasticPolicy exchange after each round."""
    if args.algo != "parle":
        raise SystemExit("--sync-policy async implements the Parle Eq. 8 "
                         f"consensus; --algo {args.algo} has no round "
                         "contribution to push")
    proc = args._worker
    _maybe_fail_for_test(proc)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import ParleConfig, get_config, smoke_variant
    from repro.core import parle, registry
    from repro.data.synthetic import TokenStream, make_round_batch_fn
    from repro.models.model import build_model
    from repro.obs import Obs
    from repro.runtime import (AsyncElasticPolicy, CoordinatorClient,
                               FaultPlan, RoundRunner, consensus_digest)

    n_total = args.replicas or args.nproc
    if n_total % args.nproc:
        raise SystemExit(f"--replicas {n_total} not divisible by --nproc "
                         f"{args.nproc} (each async worker owns an equal "
                         "replica block)")
    local_n = n_total // args.nproc
    offset = proc * local_n

    obs = Obs(args.metrics_out, args.trace_out, pid=proc,
              process_name=f"pod-worker{proc}")
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    model = build_model(cfg)
    algo = registry.get(args.algo)
    pcfg = algo.canonicalize_cfg(ParleConfig(
        n_replicas=local_n, L=args.L, lr=args.lr, lr_inner=args.lr,
        batches_per_epoch=max(args.steps // 4, 1),
        sync_compress=args.sync_compress))

    wfaults = (FaultPlan.from_spec(args.fault_plan).worker_faults(proc)
               if args.fault_plan else None)
    coord_port = args.coord_port or args.port + 1
    # heartbeat a few times per liveness window so only a TRUE hang
    # (frozen beater included) crosses the eviction deadline
    client = CoordinatorClient(
        coord_port, worker=f"worker{proc}", count=local_n,
        heartbeat_s=min(max(args.liveness_s / 3.0, 0.05), 1.0))
    hello = client.join()
    base_round = hello["round"]

    key = jax.random.PRNGKey(args.seed)
    state = algo.init(model.init(key), pcfg)
    if hello["consensus"] is not None:
        # join an in-flight/resumed consensus: all replicas start AT it
        xbar = parle.consensus_from_flat(hello["consensus"], state.x)
        rep = jax.tree.map(
            lambda m, x: jnp.broadcast_to(m, x.shape).astype(x.dtype),
            xbar, state.x)
        state = state._replace(x=rep, y=rep, z=rep)
    state = parle.dealias_state(state)  # donated rounds need own buffers

    policy = AsyncElasticPolicy(client, pcfg, obs, worker=proc,
                                faults=wfaults)
    round_fn = policy.make_round_fn(algo, model.loss, pcfg)
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=args.seq,
                         batch_size=args.batch, seed=args.seed)
    stage = make_round_batch_fn(stream, args.L, args.batch, local_n,
                                replica_offset=offset, n_total=n_total)
    rounds = args.steps // args.L
    start = base_round * args.L

    rec0 = obs.emit("mesh", mesh={"async": args.nproc},
                    replica_axis="replica", n_total=n_total,
                    local_replicas=local_n, replica_offset=offset,
                    base_round=base_round)
    if proc == 0:
        print(json.dumps(rec0), flush=True)

    records = []
    round_t = {"t": time.perf_counter()}

    def pre_round(r):
        if args.straggle_ms > 0 and proc == args.straggle_worker:
            time.sleep(args.straggle_ms / 1e3)
        if wfaults is not None:
            # the fault "round" is the GLOBAL consensus round this
            # local round's exchange will carry (base_round + r + 1)
            wfaults.pre_round(base_round + r + 1, client=client, obs=obs)

    def post_round(state, r, gstep, metrics):
        return policy.exchange(state, base_round + r, gstep, metrics)

    def on_round(r, gstep, metrics):
        losses = np.asarray(metrics["losses"]).reshape(-1)
        for j, lv in enumerate(losses.tolist()):
            stepno = gstep - args.L + j + 1
            rec = {"step": stepno, "loss_hex": float(lv).hex(),
                   "loss": round(float(lv), 6)}
            obs.emit("pod_step", step=stepno, loss=rec["loss"], proc=proc,
                     loss_hex=rec["loss_hex"])
            records.append(rec)
            if proc == 0:
                print(LOSS_TAG + json.dumps(rec), flush=True)
        if obs.enabled:
            obs.registry.gauge("pod.loss").set(
                round(float(losses[-1]), 6))
            now = time.perf_counter()
            # steady-state only: the first round's wall includes the
            # AOT compile, which would swamp the ms-scale series
            if r > 0:
                obs.registry.histogram("pod.round_wall_ms", worker=proc) \
                   .observe((now - round_t["t"]) * 1e3)
            round_t["t"] = now
        if r == 0 and proc == 0 and policy.last_reply is not None:
            # continuity markers for the elastic-resume tests: the first
            # pulled consensus, as a digest and an order-free L2 norm
            # (identical contributions folded in a different arrival
            # order can differ in the last ulp, so the norm is the
            # robust cross-reshape comparison)
            vecs = policy.last_reply["consensus"]
            l2 = float(np.sqrt(sum(
                float(np.sum(np.square(np.asarray(v, np.float64))))
                for v in vecs)))
            print(json.dumps({"first_consensus_digest":
                              consensus_digest(vecs),
                              "first_consensus_l2": round(l2, 6)}),
                  flush=True)

    runner = RoundRunner(obs, ns="pod")
    state, _ = runner.run_rounds(
        state, round_fn, stage, start=start, rounds=rounds, L=args.L,
        tokens_per_round=args.L * args.batch * args.seq * local_n,
        pcfg=pcfg, progress_every=0, progress=None,
        pre_round=pre_round, post_round=post_round, on_round=on_round)
    client.leave()
    obs.finalize()
    return records


def _spawn(args, worker_args, env_extra):
    env = dict(os.environ, **env_extra, JAX_PLATFORMS="cpu")
    # each worker leads its own process group/session so a wedged pod
    # can be killed as a unit (workers + any children they forked)
    return subprocess.Popen(
        [sys.executable, "-m", "repro.launch.dist_run"] + worker_args,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, start_new_session=True)


def _losses(output: str) -> list:
    return [json.loads(line[len(LOSS_TAG):])
            for line in output.splitlines() if line.startswith(LOSS_TAG)]


def _wait_workers(procs, tolerate=frozenset()):
    """Reap the pod, draining all pipes concurrently (a failed worker
    can fill its pipe with a long traceback while its peers block in a
    collective — a serial read would deadlock the launcher).

    If any worker exits nonzero while peers are still running, the
    survivors are wedged (their next collective waits on a corpse
    forever): kill each survivor's whole process group and report the
    FAILING worker, not the -9s we inflicted.  ``tolerate`` names the
    worker indices a chaos plan crashes on purpose: exactly those, at
    exactly the scripted exit code, are NOT failures (the async
    survivors keep running — an elastic pod outlives a dead member).
    Returns (outputs, failed_index_or_None, n_killed)."""
    from concurrent.futures import ThreadPoolExecutor

    from repro.runtime.faults import CRASH_RC
    pool = ThreadPoolExecutor(max_workers=len(procs))
    futs = [pool.submit(p.communicate) for p in procs]
    failed, killed = None, 0
    while True:
        codes = [p.poll() for p in procs]
        if failed is None:
            for i, rc in enumerate(codes):
                if rc not in (None, 0) and not (i in tolerate
                                                and rc == CRASH_RC):
                    failed = i
                    break
        if failed is not None and any(c is None for c in codes):
            for p in procs:
                if p.poll() is None:
                    try:
                        os.killpg(os.getpgid(p.pid), signal.SIGKILL)
                    except (ProcessLookupError, PermissionError,
                            OSError):              # pragma: no cover
                        p.kill()
                    killed += 1
            break
        if all(c is not None for c in codes):
            break
        time.sleep(0.05)
    outs = [f.result()[0] for f in futs]
    pool.shutdown()
    return outs, failed, killed


def _fail_pod(procs, outs, failed, killed):
    """Surface the failing worker's output tail and exit nonzero."""
    rc = procs[failed].returncode
    tail = "\n".join(outs[failed].splitlines()[-40:])
    sys.stderr.write(f"--- worker {failed} exited rc={rc}; killed "
                     f"{killed} orphaned peer(s) ---\n{tail}\n")
    return rc if rc else 1


def _merge_pod_obs(args, sink=None, extra_counters=None,
                   evicted_workers=0):
    """Coordinator-side aggregation: fold every worker's final registry
    snapshot into one pod view (merge is associative — any fold order
    gives the same result) and concatenate the worker traces into one
    Chrome trace, one pid lane per process.

    A worker whose ``<path>.worker<i>`` file is missing (or holds no
    final snapshot — it crashed mid-run) is logged as a ``note`` event
    and counted in the ``pod_merged`` event's ``missing_workers`` field
    instead of silently shrinking the pod view; a crashed worker's
    SURVIVING events still fold in (torn final line tolerated — the
    per-event flush means everything before the crash is on disk).
    ``evicted_workers`` (the coordinator's heartbeat-eviction count) is
    recorded as its own field: an evicted worker was hung-but-alive and
    usually finalizes, so it is a DIFFERENT failure than a missing
    file.  ``extra_counters`` (a checkpoint counter stamp) folds
    resumed totals in so pod counters stay monotonic across elastic
    resumes.  Returns the merged snapshot (or None without
    --metrics-out)."""
    merged = None
    if args.metrics_out:
        from repro.obs import EventSink, merge_snapshots, read_events
        snaps, missing = [], []
        for i in range(args.nproc):
            try:
                evs = read_events(f"{args.metrics_out}.worker{i}",
                                  tolerate_torn_tail=True)
            except FileNotFoundError:
                missing.append(i)
                continue
            final = [e for e in evs if e["kind"] == "metrics_snapshot"]
            if final:
                snaps.append(final[-1]["snapshot"])
            else:
                missing.append(i)
        own_sink = sink is None
        if own_sink:
            sink = EventSink(args.metrics_out)
        for i in missing:
            sink.emit("note", msg=f"pod merge: no metrics snapshot from "
                      f"worker {i} ({args.metrics_out}.worker{i})")
        merged = merge_snapshots(*snaps)
        if extra_counters:
            merged = merge_snapshots(
                merged, {"counters": list(extra_counters), "gauges": [],
                         "hists": []})
        rec = sink.emit("pod_merged", processes=len(snaps),
                        missing_workers=len(missing),
                        evicted_workers=int(evicted_workers),
                        snapshot=merged)
        if own_sink:
            sink.close()
        print(json.dumps({"pod_merged": args.metrics_out,
                          "processes": rec["processes"],
                          "missing_workers": rec["missing_workers"],
                          "evicted_workers": rec["evicted_workers"]}),
              flush=True)
    if args.trace_out:
        events = []
        for i in range(args.nproc):
            try:
                with open(f"{args.trace_out}.worker{i}") as f:
                    events.extend(json.load(f)["traceEvents"])
            except FileNotFoundError:
                sys.stderr.write(f"pod merge: no trace from worker {i} "
                                 f"({args.trace_out}.worker{i})\n")
                continue
        with open(args.trace_out, "w") as f:
            json.dump({"traceEvents": events}, f)
    return merged


def _worker_flags(args, i):
    """Per-worker flags the reference run must NOT inherit."""
    flags = ["--straggle-ms", str(args.straggle_ms),
             "--straggle-worker", str(args.straggle_worker)]
    if args.metrics_out:
        flags += ["--metrics-out", f"{args.metrics_out}.worker{i}"]
    if args.trace_out:
        flags += ["--trace-out", f"{args.trace_out}.worker{i}"]
    return flags


def _base_args(args):
    return ["--mesh", _mesh_spec(args), "--algo", args.algo,
            "--arch", args.arch, "--replicas", str(args.replicas),
            "--L", str(args.L), "--steps", str(args.steps),
            "--batch", str(args.batch), "--seq", str(args.seq),
            "--lr", str(args.lr), "--seed", str(args.seed),
            "--port", str(args.port)] + (["--smoke"] if args.smoke else [])


def _run_async_pod(args) -> int:
    """Async-pod parent: host the consensus coordinator (behind its
    kill/restart supervisor), spawn the elastic workers, merge their
    telemetry, optionally checkpoint the consensus for an elastic
    resume.  With ``--fault-plan`` the parent fires the plan's
    coordinator kills and tolerates exactly the worker crashes the plan
    scripts; the merged snapshot carries the pod-lifetime fault
    counters (quarantines, evictions, restarts, corrupt frames)."""
    import tempfile

    from repro.checkpoint import checkpoint as ckpt
    from repro.obs import EventSink
    from repro.runtime import CoordinatorSupervisor, FaultPlan, \
        load_consensus

    plan = (FaultPlan.from_spec(args.fault_plan) if args.fault_plan
            else FaultPlan())
    kills = plan.coordinator_kills()
    tolerate = plan.crash_workers()
    coord_port = args.coord_port or args.port + 1
    sink = EventSink(args.metrics_out) if args.metrics_out else None
    consensus, start_round, extra_counters = None, 0, None
    if args.resume:
        args.resume = ckpt.resolve(args.resume)   # dir / corrupt-fallback
        vectors, rnd, meta = load_consensus(args.resume)
        consensus, start_round = vectors, rnd
        extra_counters = ckpt.saved_metrics(args.resume)
        print(json.dumps({"async_resume": args.resume, "round": rnd,
                          "consensus_digest": meta.get("digest", "")}),
              flush=True)
    # periodic crash-recovery checkpoints: required for scripted
    # coordinator kills (the restart source), and kept next to
    # --checkpoint-out when one was asked for
    ck_dir = ""
    if args.checkpoint_out:
        ck_dir = args.checkpoint_out + ".d"
    elif kills:
        ck_dir = tempfile.mkdtemp(prefix="repro_async_ck_")
    sup = CoordinatorSupervisor(
        coord_port, kills=kills, sink=sink, method=args.sync_compress,
        decay=args.decay, consensus=consensus, start_round=start_round,
        liveness_s=args.liveness_s, ck_dir=ck_dir)
    print(json.dumps({"launch": "dist_run", "mode": "async",
                      "platform": "cpu",
                      "nproc": args.nproc, "coord_port": coord_port,
                      "replicas": args.replicas or args.nproc,
                      "rounds": args.steps // args.L,
                      "faults": len(plan.faults)}), flush=True)

    base = _base_args(args) + [
        "--sync-policy", "async", "--sync-compress", args.sync_compress,
        "--decay", str(args.decay), "--coord-port", str(coord_port),
        "--liveness-s", str(args.liveness_s)]
    if args.fault_plan:
        base += ["--fault-plan", plan.to_json()]
    procs = [_spawn(args, base + ["--nproc", str(args.nproc),
                                  "--_worker", str(i)]
                    + _worker_flags(args, i), {})
             for i in range(args.nproc)]
    outs, failed, killed = _wait_workers(procs, tolerate=tolerate)
    try:
        if failed is not None:
            return _fail_pod(procs, outs, failed, killed)
        crashed = [i for i, p in enumerate(procs) if p.returncode]
        for i in crashed:
            sys.stderr.write(f"worker {i} crashed per fault plan "
                             f"(rc={procs[i].returncode}); pod "
                             f"continued without it\n")
        sys.stdout.write(outs[0])
        if not _losses(outs[0]) and 0 not in crashed:
            sys.stderr.write("worker 0 produced no loss records\n"
                             + outs[0])
            return 1
        fault_counters = [
            {"name": "pod.evicted_workers", "labels": {},
             "total": sup.counter("evictions")},
            {"name": "pod.coordinator_restarts", "labels": {},
             "total": sup.restarts},
            {"name": "pod.worker_crashes", "labels": {},
             "total": len(crashed)},
            {"name": "pod.corrupt_frames", "labels": {},
             "total": sup.counter("corrupt_frames")},
            {"name": "pod.duplicate_exchanges", "labels": {},
             "total": sup.counter("duplicates")},
        ]
        merged = _merge_pod_obs(
            args, sink=sink,
            extra_counters=fault_counters + list(extra_counters or []),
            evicted_workers=sup.counter("evictions"))
        if args.checkpoint_out:
            sup.save(args.checkpoint_out,
                     metrics=(merged or {}).get("counters"))
            print(json.dumps({"async_checkpoint": args.checkpoint_out,
                              "round": sup.round,
                              "consensus_digest": sup.digest()}),
                  flush=True)
        return 0
    finally:
        sup.close()
        if sink is not None:
            sink.close()


def _check_platform():
    """Refuse a pod asked to run on anything but the host CPU."""
    plat = os.environ.get("JAX_PLATFORMS") or "cpu"
    if plat != "cpu":
        raise SystemExit(
            f"dist_run runs its pod on the host CPU, but JAX_PLATFORMS="
            f"{plat!r}: its workers would each claim the same chips.  To "
            f"train across the chips of one host run one process: python "
            f"-m repro.launch.train --mesh replica:4")


def main(argv=None):
    args = build_argparser().parse_args(argv)
    if args._worker >= 0:
        if args.sync_policy == "async":
            _run_async_worker(args)
        else:
            run_worker(args)
        return 0
    _check_platform()
    if args.sync_policy == "async":
        return _run_async_pod(args)

    spec = _mesh_spec(args)
    base = _base_args(args)
    print(json.dumps({"launch": "dist_run", "platform": "cpu",
                      "nproc": args.nproc, "mesh": spec}), flush=True)

    procs = [_spawn(args, base + ["--nproc", str(args.nproc),
                                  "--_worker", str(i)]
                    + _worker_flags(args, i), {})
             for i in range(args.nproc)]
    outs, failed, killed = _wait_workers(procs)
    if failed is not None:
        return _fail_pod(procs, outs, failed, killed)
    sys.stdout.write(outs[0])
    dist = _losses(outs[0])
    if not dist:
        sys.stderr.write("worker 0 produced no loss records\n" + outs[0])
        return 1
    _merge_pod_obs(args)
    if args.no_compare:
        return 0

    # single-process reference: SAME mesh spec, all devices in one
    # process — the compiled program is identical, only the process
    # boundary (and its gloo collectives) disappears
    ref_proc = _spawn(args, base + ["--nproc", "1", "--_worker", "0"], {})
    ref_out = ref_proc.communicate()[0]
    if ref_proc.returncode != 0:
        sys.stderr.write(f"--- reference run failed ---\n{ref_out}\n")
        return ref_proc.returncode
    ref = _losses(ref_out)

    mismatches = [
        {"step": d["step"], "dist": d["loss_hex"], "single": r["loss_hex"]}
        for d, r in zip(dist, ref) if d["loss_hex"] != r["loss_hex"]]
    rel = [abs(float.fromhex(d["loss_hex"]) - float.fromhex(r["loss_hex"]))
           / max(abs(float.fromhex(r["loss_hex"])), 1e-12)
           for d, r in zip(dist, ref)]
    verdict = {
        "compared_steps": min(len(dist), len(ref)),
        "bitwise_equal": not mismatches and len(dist) == len(ref),
        "max_rel_diff": max(rel) if rel else None,
        "mismatches": mismatches[:5],
    }
    print(json.dumps(verdict), flush=True)
    ok = verdict["bitwise_equal"] or (
        args.tol > 0 and len(dist) == len(ref)
        and verdict["max_rel_diff"] <= args.tol)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
