"""Seed/refresh ``benchmarks/BENCH_parle.json`` — the tracked perf
trajectory of the Parle hot path on a PINNED smoke config.

Timing discipline (PR 4): every program is AOT-compiled
(``jit().lower().compile()``) so compile time never leaks into a timed
window, warmed up, and every timed window ends in ``block_until_ready``;
compile time is reported as its own field.

Fields:
  * ``inner_step_us`` / ``sync_step_us`` / ``fused_step_us`` — one
    compiled call of each program (pre-staged batch).
  * ``step_loop_us`` / ``step_loop_steps_per_s`` — the per-step dispatch
    loop AS THE DRIVER RUNS IT: per-step host-side batch construction
    (~20 un-jitted ops) + one compiled step per step.
  * ``round_us`` / ``steps_per_s`` — the fused L-step round: one
    donated-buffer compiled program per L steps, batches staged by one
    jitted dispatch, double-buffered.  ``round_speedup`` =
    steps_per_s / step_loop_steps_per_s (acceptance: >= 1.5x).
  * ``obs_round_us`` / ``obs_overhead_ratio`` — the SAME fused round
    driven with full telemetry (round span ending on
    ``block_until_ready``, counters, round-latency histogram — what
    ``launch/train.py --metrics-out --trace-out`` adds per round),
    interleaved with the bare trials so noise hits both alike.
    Acceptance: ratio <= 1.02.
  * ``recovery`` — rounds-to-reconverge and final consensus rel-L2 of
    an async pod whose coordinator is killed at round 3 and restarted
    from its periodic checkpoint, vs a fault-free twin.
  * ``compile_s`` — AOT compile seconds per program.
  * per-axis collective bytes of the composed-mesh compiled step and
    ``sync_compress_bytes`` — the replica-axis sync payload at
    none/bf16/int8 (subprocesses, so the forced host device counts
    never leak into this process).
  * ``device`` — where the in-process timings above ran.  Every other
    field comes from a child probe pinned to the host CPU
    (``child_probes``: platform cpu), which never contends for a chip
    with this process.

  PYTHONPATH=src python benchmarks/bench_parle.py          # write JSON
  PYTHONPATH=src python -m benchmarks.run parle            # suite line
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

OUT_PATH = os.path.join(os.path.dirname(__file__), "BENCH_parle.json")

# the pinned smoke config (v2, this PR): sized so that per-step
# DISPATCH/staging overhead — what fused rounds eliminate — is a large
# fraction of the step, not hidden under CI-CPU matmul time (the v1
# pin's d_model=128/seq=32/batch=2 model spent ~20 ms/step in compute
# identical on both paths, capping any honest loop-vs-round ratio at
# ~1.3x; v1 numbers live in git history).  The mesh/param_size comm
# probe is unchanged, so the per-axis byte fields stay comparable.
PIN = {"d_model": 64, "num_layers": 2, "d_ff": 128, "vocab": 512,
       "seq": 16, "batch": 1, "n_replicas": 2, "L": 5,
       "mesh": "replica:2,data:2,model:2", "param_size": 1 << 20}


def _cpu_env(host_devices: int = 0) -> dict:
    """Environment of a child probe: pinned to the host CPU, so it never
    contends for a chip with this process, and never silently moves."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if host_devices:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count="
                              f"{host_devices}")
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    return env


def _time_us(fn, *args, warmup=2, iters=10):
    import jax
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _aot(jitted, *args):
    """AOT-compile; returns (compiled, compile_seconds)."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def measure_steps() -> dict:
    import jax

    from repro.configs.base import ModelConfig, ParleConfig
    from repro.core import registry
    from repro.core.parle import dealias_state
    from repro.data.synthetic import (TokenStream, make_round_batch_fn,
                                      replica_batches)
    from repro.launch import steps as steps_lib
    from repro.models.model import build_model

    mcfg = ModelConfig(name="bench-dense", family="dense",
                       num_layers=PIN["num_layers"], d_model=PIN["d_model"],
                       num_heads=4, num_kv_heads=2, d_ff=PIN["d_ff"],
                       vocab_size=PIN["vocab"],
                       head_dim=PIN["d_model"] // 4)
    pcfg = ParleConfig(n_replicas=PIN["n_replicas"], L=PIN["L"],
                       batches_per_epoch=5)
    algo = registry.get("parle")
    model = build_model(mcfg)
    params = model.init(jax.random.PRNGKey(0))
    state = algo.init(params, pcfg)
    stream = TokenStream(vocab_size=mcfg.vocab_size, seq_len=PIN["seq"],
                         batch_size=PIN["batch"], seed=0)
    batch = replica_batches(stream, 0, PIN["batch"], PIN["n_replicas"])
    L, n = PIN["L"], PIN["n_replicas"]

    compile_s = {}
    inner, sync = steps_lib.make_parle_steps(mcfg, pcfg)
    inner_c, compile_s["inner"] = _aot(jax.jit(inner), state, batch)
    sync_c, compile_s["sync"] = _aot(jax.jit(sync), state)
    step_c, compile_s["fused"] = _aot(
        jax.jit(algo.make_step(model.loss, pcfg)), state, batch)
    out = {
        "inner_step_us": round(_time_us(inner_c, state, batch), 1),
        "sync_step_us": round(_time_us(sync_c, state), 1),
        "fused_step_us": round(_time_us(step_c, state, batch), 1),
    }

    # --- per-step dispatch loop, as launch/train.py runs it without
    # --round-fused: per-step host batch construction + jit-dispatched
    # step (the driver calls jax.jit(step), not an AOT handle)
    step_j = jax.jit(algo.make_step(model.loss, pcfg))

    def loop_trial(s, k, start):
        t0 = time.perf_counter()
        for i in range(start, start + k):
            b = replica_batches(stream, i, PIN["batch"], n)
            s, _m = step_j(s, b)
        jax.block_until_ready(s)
        return s, (time.perf_counter() - t0) / k * 1e6

    # --- fused round: donated state, one jitted staging dispatch per
    # round, double-buffered against the round's compute
    round_j = algo.make_round_fn(model.loss, pcfg)
    stage = make_round_batch_fn(stream, L, PIN["batch"], n)
    rb0 = stage(0)
    round_c, compile_s["round"] = _aot(round_j, state, rb0)

    def round_trial(rs, k, start_round):
        nxt = stage(start_round * L)
        jax.block_until_ready(nxt)
        t0 = time.perf_counter()
        for r in range(start_round, start_round + k):
            cur, nxt = nxt, None
            rs, m = round_c(rs, cur)
            nxt = stage((r + 1) * L)
        jax.block_until_ready(m)
        return rs, nxt, (time.perf_counter() - t0) / (k * L) * 1e6

    # --- the same fused round under full telemetry, exactly as
    # launch/train.py --metrics-out --trace-out drives it: a round span
    # ending on block_until_ready (staging inside the span, before the
    # block, so double-buffering survives), counters, round histogram
    from repro.obs.metrics import Registry
    from repro.obs.trace import Tracer
    reg, tracer = Registry(), Tracer(enabled=True, collect=True)
    tok_per_round = L * PIN["batch"] * PIN["seq"] * n

    def round_trial_obs(rs, k, start_round):
        nxt = stage(start_round * L)
        jax.block_until_ready(nxt)
        t0 = time.perf_counter()
        for r in range(start_round, start_round + k):
            cur, nxt = nxt, None
            with tracer.span("round", cat="train", round=r) as sp:
                rs, m = round_c(rs, cur)
                nxt = stage((r + 1) * L)
                sp.block(m)
            reg.counter("train.steps").inc(L)
            reg.counter("train.rounds").inc()
            reg.counter("train.tokens").inc(tok_per_round)
            reg.histogram("train.round_ms").observe(sp.dur_s * 1e3)
        jax.block_until_ready(m)
        return rs, nxt, (time.perf_counter() - t0) / (k * L) * 1e6

    # warmup both paths (jit trace + sync-cond branch + donation chain)
    s, _ = loop_trial(state, 2 * L, 0)
    rs = dealias_state(state)
    rs, nxt, _ = round_trial(rs, 2, 0)
    # interleave trials so machine-load noise hits both paths equally;
    # per-path MIN is the least-noise throughput estimate
    loop_us, round_us, obs_us = [], [], []
    for trial in range(3):
        s, us = loop_trial(s, 8 * L, (2 + trial * 8) * L)
        loop_us.append(us)
        rs, nxt, us = round_trial(rs, 8, 2 + (trial + 1) * 8)
        round_us.append(us)
        rs, nxt, us = round_trial_obs(rs, 8, 2 + (trial + 1) * 8)
        obs_us.append(us)
    # extra bare/obs pairs: the overhead ratio compares two nearly-equal
    # times, so it needs more min-samples than the 3.1x speedup does
    bare_us = list(round_us)
    for trial in range(3, 6):
        rs, nxt, us = round_trial(rs, 8, 2 + (trial + 1) * 8)
        bare_us.append(us)
        rs, nxt, us = round_trial_obs(rs, 8, 2 + (trial + 1) * 8)
        obs_us.append(us)
    out["step_loop_us"] = round(min(loop_us), 1)
    out["step_loop_us_trials"] = [round(u, 1) for u in loop_us]
    out["step_loop_steps_per_s"] = round(1e6 / min(loop_us), 2)
    out["round_us"] = round(min(round_us) * L, 1)
    out["round_us_trials"] = [round(u * L, 1) for u in round_us]
    out["steps_per_s"] = round(1e6 / min(round_us), 2)
    out["round_speedup"] = round(out["steps_per_s"]
                                 / out["step_loop_steps_per_s"], 2)
    out["obs_round_us"] = round(min(obs_us) * L, 1)
    out["obs_round_us_trials"] = [round(u * L, 1) for u in obs_us]
    out["obs_overhead_ratio"] = round(min(obs_us) / min(bare_us), 4)
    out["compile_s"] = {k: round(v, 2) for k, v in compile_s.items()}
    return out


def measure_comm() -> dict:
    """Per-axis collective bytes of the composed-mesh step, via the
    comm_volume CLI in a subprocess (forced host device count)."""
    res = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__),
                                      "comm_volume.py"),
         "--mesh", PIN["mesh"], "--host-devices", "8",
         "--algo", "parle", "--param-size", str(PIN["param_size"])],
        capture_output=True, text=True, timeout=900, env=_cpu_env())
    if res.returncode != 0:
        raise RuntimeError(res.stdout + res.stderr)
    row = next(l for l in res.stdout.splitlines()
               if l.startswith("comm_mesh_parle"))
    fields = dict(kv.split("=") for kv in row.split(",")[2].split(";"))
    axes = {m.group(1): int(fields[m.group(0)])
            for m in (re.match(r"axis_(\w+)_bytes", k)
                      for k in fields) if m}
    return {
        "mesh": PIN["mesh"],
        "per_axis_comm_bytes": axes,
        "sync_all_reduce_bytes_per_device": int(
            fields["all_reduce_bytes_per_device"]),
        "expected_sync_shard_bytes": int(fields["expected_sync_bytes"]),
        "per_step_entry_bytes": int(fields["per_step_bytes"]),
        "amortized_bytes_per_step": float(
            fields["amortized_bytes_per_step"]),
    }


_COMPRESS_CHILD = r"""
import json, jax, jax.numpy as jnp
from repro.configs.base import ParleConfig
from repro.core import parle
from repro.launch.mesh import make_mesh_from_spec
from repro.launch import hlo_stats

def loss(p, b):
    return 0.5 * jnp.sum((p["w"] - b["t"]) ** 2), ()

size = %d // 4
mesh = make_mesh_from_spec("replica:2")
batch = {"t": jnp.zeros((2, 1), jnp.float32)}
out = {}
for method in ("none", "bf16", "int8"):
    cfg = ParleConfig(n_replicas=2, L=%d, batches_per_epoch=10,
                      sync_compress=method)
    st = parle.init({"w": jnp.zeros((size,), jnp.float32)}, cfg)
    step = parle.make_sharded_train_step(loss, cfg, mesh)
    txt = step.lower(st, batch).compile().as_text()
    stats = hlo_stats.collective_bytes_by_axis(txt, dict(mesh.shape))
    out[method] = sum(stats["by_axis"]["replica"].values()) - 4
print("COMPRESS_BYTES " + json.dumps(out))
"""


def measure_compress() -> dict:
    """Replica-axis sync payload bytes per device at each
    --sync-compress setting, from compiled HLO (child process: 2 forced
    host devices, 1 MiB f32 model)."""
    env = _cpu_env(host_devices=2)
    res = subprocess.run(
        [sys.executable, "-c",
         _COMPRESS_CHILD % (PIN["param_size"], PIN["L"])],
        capture_output=True, text=True, timeout=900, env=env)
    if res.returncode != 0:
        raise RuntimeError(res.stdout + res.stderr)
    row = next(l for l in res.stdout.splitlines()
               if l.startswith("COMPRESS_BYTES"))
    bytes_by_method = json.loads(row.split(" ", 1)[1])
    base = bytes_by_method["none"]
    return {"sync_compress_bytes": bytes_by_method,
            "sync_compress_ratio": {
                k: round(v / base, 4) for k, v in bytes_by_method.items()}}


_OVERLAP_CHILD = r"""
import dataclasses, json, time
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.utils.compat import shard_map
from repro.configs.base import ParleConfig
from repro.core import parle, compress
from repro.launch.mesh import make_mesh_from_spec
from repro.launch import hlo_stats

def loss(p, b):
    return 0.5 * jnp.sum((p["w"] - b["t"]) ** 2), ()

size = %d // 4
L = %d
mesh = make_mesh_from_spec("replica:8")
batch = {"t": jnp.zeros((L, 8, 1), jnp.float32)}

def timed(fn, *a, iters=8):
    out = fn(*a)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6

# the payload collective alone, per method (what the barrier exposes)
w8 = jnp.ones((8, size), jnp.float32)
ar = jax.jit(shard_map(lambda w: jax.lax.pmean(w, "replica"), mesh,
                       in_specs=P("replica", None),
                       out_specs=P("replica", None)))
q8, s8, _ = compress.quantize_ef(compress.pad_to_chunk(w8), "int8")
ag = jax.jit(shard_map(
    lambda q, s: (jax.lax.all_gather(q, "replica"),
                  jax.lax.all_gather(s, "replica")), mesh,
    in_specs=(P("replica", None), P("replica", None)),
    out_specs=(P("replica", None, None), P("replica", None, None))))
coll_us = {"none": timed(ar, w8), "int8": timed(ag, q8, s8)}

out = {}
for method in ("none", "int8"):
    cfg = ParleConfig(n_replicas=8, L=L, batches_per_epoch=10,
                      sync_compress=method)
    ocfg = dataclasses.replace(cfg, sync_overlap=True)
    reps = {"w": jnp.ones((8, size), jnp.float32)}
    st_b = parle.dealias_state(parle.init_from_replicas(reps, cfg))
    st_o = parle.dealias_state(parle.init_from_replicas(reps, ocfg))
    cb = parle.make_round_fn(loss, cfg, mesh=mesh) \
        .lower(st_b, batch).compile()
    co = parle.make_round_fn(loss, ocfg, mesh=mesh) \
        .lower(st_o, batch).compile()
    hb = hlo_stats.overlap_structure(cb.as_text())
    ho = hlo_stats.overlap_structure(co.as_text())

    def trial(fn, st, iters=8):
        t0 = time.perf_counter()
        for _ in range(iters):
            st, m = fn(st, batch)
        jax.block_until_ready(st)
        return st, (time.perf_counter() - t0) / iters * 1e6

    st_b, _ = trial(cb, st_b, 3)    # warmup (donation chain)
    st_o, _ = trial(co, st_o, 3)
    bus, ous = [], []
    for t in range(5):              # interleaved: noise hits both alike
        st_b, us = trial(cb, st_b); bus.append(us)
        st_o, us = trial(co, st_o); ous.append(us)
    sync_us = coll_us[method]
    compute_us = max(0.0, min(bus) - sync_us)
    out[method] = {
        "barrier_round_us": round(min(bus), 1),
        "overlap_round_us": round(min(ous), 1),
        "barrier_trials_us": [round(u, 1) for u in bus],
        "overlap_trials_us": [round(u, 1) for u in ous],
        "sync_collective_us": round(sync_us, 1),
        # exposed sync per round: the barrier serializes the FULL
        # collective behind the inner scan (hlo_barrier.after_loop);
        # the overlapped program's collective is dataflow-independent
        # of the scan (hlo_overlap.independent_of_loop), so an
        # async-collective backend exposes only the part that does not
        # fit under the round's compute.  Derived from the measured
        # component times; raw wall clocks above are reported as-is
        # (this host backend runs collectives synchronously -- no
        # all-reduce-start/done pairs -- so they stay at parity).
        "exposed_sync_us": {
            "barrier": round(sync_us, 1),
            "overlap": round(max(0.0, sync_us - compute_us), 1)},
        "exposed_sync_us_saved": round(
            sync_us - max(0.0, sync_us - compute_us), 1),
        "hlo_barrier": hb, "hlo_overlap": ho,
    }
print("OVERLAP_PROBE " + json.dumps(out))
"""


def measure_overlap() -> dict:
    """Exposed-vs-hidden sync probe (--sync-overlap): barrier vs
    overlapped fused round on an 8-replica mesh (child process, 8 forced
    host devices, 1 MiB f32 model, L from the pin), f32 and int8
    payloads.  Wall-clock is min-over-interleaved-trials.  The HLO
    structure fields carry the scheduling claim deterministically (the
    barrier round's all-reduce depends on the inner-scan while loop,
    the overlapped one is dataflow-independent of it); the exposed-sync
    fields combine that structure with the separately measured
    collective time, since this CPU backend has no async collectives to
    realize the overlap in wall clock."""
    env = _cpu_env(host_devices=8)
    res = subprocess.run(
        [sys.executable, "-c",
         _OVERLAP_CHILD % (PIN["param_size"], PIN["L"])],
        capture_output=True, text=True, timeout=900, env=env)
    if res.returncode != 0:
        raise RuntimeError(res.stdout + res.stderr)
    row = next(l for l in res.stdout.splitlines()
               if l.startswith("OVERLAP_PROBE"))
    probe = json.loads(row.split(" ", 1)[1])
    return {"sync_overlap": {"mesh": "replica:8", "L": PIN["L"],
                             "param_bytes": PIN["param_size"], **probe}}


def _dist_pod(extra, metrics_out, timeout=1200):
    """One launch/dist_run pod in a subprocess; returns the merged
    registry snapshot from the pod_merged event."""
    env = _cpu_env()
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.dist_run", "--nproc", "3",
         "--algo", "parle", "--smoke", "--steps", "9", "--L", "3",
         "--no-compare", "--metrics-out", metrics_out] + extra,
        capture_output=True, text=True, timeout=timeout, env=env)
    if res.returncode != 0:
        raise RuntimeError(res.stdout + res.stderr)
    from repro.obs import read_events
    return [e for e in read_events(metrics_out)
            if e["kind"] == "pod_merged"][-1]["snapshot"]


def _worker_hist(snap, name):
    """worker label -> {mean_ms, p95_ms, count} for one hist series."""
    out = {}
    for h in snap["hists"]:
        if h["name"] == name:
            out[int(h["labels"]["worker"])] = {
                "mean_ms": round(h["sum"] / max(h["count"], 1), 1),
                "max_ms": round(h["max"], 1), "count": h["count"]}
    return out


def measure_straggler() -> dict:
    """Straggler-tolerance probe: a 3-process pod (9 steps, L=3) in four
    configurations — {async, barrier} x {clean, one worker delayed 3x the
    clean round wall at every round start}.  The metric is the
    NON-straggler workers' mean ``pod.round_wall_ms``: under the barrier
    policy every peer absorbs the delay through the round-start
    collective (ratio ~= 1 + 3), under the async policy the consensus
    exchange never waits for the straggler (ratio ~= 1).  Per-worker
    ``pod.sync_wait_ms`` histograms carry the same evidence at the sync
    point itself."""
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        def pod(tag, policy, port, straggle_ms=0.0):
            extra = ["--port", str(port)]
            if policy == "async":
                extra += ["--sync-policy", "async"]
            else:
                extra += ["--mesh", "pod:3"]
            if straggle_ms:
                extra += ["--straggle-ms", str(round(straggle_ms, 1)),
                          "--straggle-worker", "2"]
            snap = _dist_pod(extra, os.path.join(td, f"{tag}.jsonl"))
            return {"round_wall": _worker_hist(snap, "pod.round_wall_ms"),
                    "sync_wait": _worker_hist(snap, "pod.sync_wait_ms")}

        def clean_mean(r):
            walls = [w["mean_ms"] for w in r["round_wall"].values()]
            return sum(walls) / len(walls)

        def nonstraggler_mean(r):
            walls = [w["mean_ms"] for k, w in r["round_wall"].items()
                     if k != 2]
            return sum(walls) / len(walls)

        out = {}
        for policy, base_port in (("async", 9651), ("barrier", 9661)):
            clean = pod(f"{policy}_clean", policy, base_port)
            straggle_ms = 3.0 * clean_mean(clean)
            slow = pod(f"{policy}_straggled", policy, base_port + 4,
                       straggle_ms=straggle_ms)
            out[policy] = {
                "clean_round_wall_ms": round(clean_mean(clean), 1),
                "straggle_ms": round(straggle_ms, 1),
                "nonstraggler_round_wall_ms": round(
                    nonstraggler_mean(slow), 1),
                "straggle_ratio": round(
                    nonstraggler_mean(slow) / clean_mean(clean), 2),
                "sync_wait_ms": slow["sync_wait"],
                "round_wall_ms": slow["round_wall"],
            }
        return {"straggler": out}


def measure_recovery() -> dict:
    """Coordinator-recovery probe: a 3-process async pod (15 steps,
    L=3, 5 consensus rounds) with a scripted coordinator SIGKILL at
    round 3, against a fault-free twin.  The supervisor restarts the
    coordinator from its newest valid periodic checkpoint and the
    workers rejoin through their retry loops.  Reported:

    * ``restart_from_round`` — the checkpointed round the supervisor
      recovered from (the ``coordinator_restart`` event).
    * ``rounds_to_reconverge`` — consensus rounds run AFTER the
      restart to reach the final consensus (final - restart source);
      the recovery cost a kill adds over a clean run.
    * ``final_rel_l2_vs_clean`` — rel L2 between the killed and clean
      pods' final consensus.  A MID-RUN kill diverges slightly (the
      restart discards the in-flight contribution table and replays
      from the checkpointed consensus, so staleness weights differ),
      ~1e-2 on this pin; only a kill after the final round is exactly
      recoverable."""
    import tempfile

    import numpy as np

    from repro.obs import read_events
    from repro.runtime import load_consensus

    kill_round = 3
    plan = json.dumps({"seed": 5, "faults": [
        {"kind": "coordinator_kill", "round": kill_round,
         "down_ms": 300}]})
    env = _cpu_env()
    with tempfile.TemporaryDirectory() as td:
        def pod(tag, port, fault_plan=""):
            ck = os.path.join(td, f"{tag}.npz")
            mpath = os.path.join(td, f"{tag}.jsonl")
            cmd = [sys.executable, "-m", "repro.launch.dist_run",
                   "--nproc", "3", "--algo", "parle", "--smoke",
                   "--sync-policy", "async", "--steps", "15", "--L", "3",
                   "--port", str(port), "--metrics-out", mpath,
                   "--checkpoint-out", ck]
            if fault_plan:
                cmd += ["--fault-plan", fault_plan]
            res = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=1200, env=env)
            if res.returncode != 0:
                raise RuntimeError(res.stdout + res.stderr)
            final = next(json.loads(l) for l in res.stdout.splitlines()
                         if l.startswith('{"async_checkpoint"'))
            return ck, mpath, final

        clean_ck, _, clean_final = pod("recovery_clean", 9681)
        kill_ck, kill_mpath, kill_final = pod("recovery_killed", 9685,
                                              fault_plan=plan)
        restart = [e for e in read_events(kill_mpath)
                   if e["kind"] == "coordinator_restart"][-1]
        cv, _, _ = load_consensus(clean_ck)
        kv, _, _ = load_consensus(kill_ck)
        clean_vec = np.concatenate(cv)
        kill_vec = np.concatenate(kv)
        rel = float(np.linalg.norm(kill_vec - clean_vec)
                    / max(np.linalg.norm(clean_vec), 1e-12))
    return {"recovery": {
        "kill_round": kill_round,
        "restarts": restart["restarts"],
        "restart_from_round": restart["round"],
        "final_round": kill_final["round"],
        "rounds_to_reconverge": kill_final["round"] - restart["round"],
        "final_rel_l2_vs_clean": round(rel, 9),
        "clean_final_round": clean_final["round"],
    }}


def main(out_path: str = OUT_PATH):
    import jax
    dev = jax.devices()
    # measure_steps runs here; every other probe is a CPU-pinned child
    rec = {"pinned_config": PIN,
           "device": {"platform": dev[0].platform,
                      "kind": dev[0].device_kind, "count": len(dev)}}
    rec.update(measure_steps())
    probes = {}
    for probe in (measure_comm, measure_compress, measure_overlap,
                  measure_straggler, measure_recovery):
        probes.update(probe())
    rec.update(probes)
    rec["child_probes"] = {"platform": "cpu", "fields": sorted(probes)}
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1, sort_keys=True)
        f.write("\n")
    # benchmark-suite CSV contract: name,us_per_call,derived
    print(f"bench_parle_round,{rec['round_us']},"
          f"steps_per_s={rec['steps_per_s']};"
          f"step_loop_steps_per_s={rec['step_loop_steps_per_s']};"
          f"round_speedup={rec['round_speedup']};"
          f"obs_overhead={rec['obs_overhead_ratio']};"
          f"fused_us={rec['fused_step_us']};"
          f"sync_ar_bytes={rec['sync_all_reduce_bytes_per_device']};"
          f"int8_sync_bytes={rec['sync_compress_bytes']['int8']};"
          f"overlap_saved_f32_us="
          f"{rec['sync_overlap']['none']['exposed_sync_us_saved']};"
          f"overlap_saved_int8_us="
          f"{rec['sync_overlap']['int8']['exposed_sync_us_saved']};"
          f"async_straggle_ratio="
          f"{rec['straggler']['async']['straggle_ratio']};"
          f"barrier_straggle_ratio="
          f"{rec['straggler']['barrier']['straggle_ratio']};"
          f"recovery_rounds={rec['recovery']['rounds_to_reconverge']};"
          f"recovery_rel_l2={rec['recovery']['final_rel_l2_vs_clean']};"
          f"out={os.path.relpath(out_path)}")
    return rec


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=OUT_PATH)
    main(ap.parse_args().out)
