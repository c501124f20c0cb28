"""RoundRunner: the ONE step/round execution loop behind the drivers.

Before this layer the loop was hardwired three ways — the per-step loop
in launch/train.py, ``_run_rounds``'s fused-round loop in the same
file, and the per-worker loop in launch/dist_run.py — each re-implementing
batch staging, AOT compile spans, obs counters/histograms, progress
emission and checkpointing with slightly drifting details.  The runner
owns those mechanics once, namespaced per driver (``train.*`` /
``pod.*`` metric series), and the drivers inject only what genuinely
differs through small hooks:

* ``batch_fn`` / ``stage_fn`` — how a step's (or round's) batches are
  produced and placed (host stack, jitted round stager, global-mesh
  device_put).
* ``on_step`` / ``on_round`` — driver-specific emission (the pod
  launcher's bit-exact ``DISTLOSS`` records and ``pod_step`` events).
* ``pre_step`` / ``pre_round`` — barrier-wait probes and injected
  straggler delay (launch/dist_run.py).
* ``post_round`` — the sync policy's out-of-program consensus exchange
  (the async policy pushes x+e to the coordinator and applies the
  staleness-weighted mean it gets back).
* ``progress`` — the unified train_progress record.

The loops are verbatim moves of the historical drivers' code: with the
barrier/overlap policies and no extra hooks the executed program
sequence — and therefore the trajectory — is bit-for-bit identical to
the pre-refactor paths (tests/test_round_fused.py,
tests/test_sync_overlap.py and tests/test_dist_run.py run unchanged on
this runner).
"""
from __future__ import annotations

import json
import time
from typing import Any, Callable, NamedTuple, Optional

from repro.checkpoint import checkpoint as ckpt


class CheckpointSpec(NamedTuple):
    """Where/when the runner checkpoints, and how the sidecar is
    stamped.  ``every`` <= 0 or an empty ``dir`` disables saving."""
    dir: str = ""
    every: int = 0
    algo: str = ""
    arch: str = ""


def aot_with_span(obs, jitted, name, lower_args):
    """AOT-compile a jitted program under a ``compile`` span so compile
    time is separated from the steady-state spans.  A compile error
    propagates: there is no fallback path."""
    with obs.tracer.span(f"compile:{name}", cat="compile"):
        return jitted.lower(*lower_args).compile()


def record_hlo_bytes(obs, compiled, mesh, pcfg, scope, ns="train"):
    """Bytes-on-wire accounting of the compiled hot program: per-axis
    collective bytes (the Eq. 8d sync payload under the active
    ``--sync-compress`` codec rides the replica axis) as gauges + one
    ``hlo_sync_bytes`` event.  Best-effort: a non-AOT handle or an HLO
    parser hiccup must never kill a training run."""
    if mesh is None or not obs.metrics_path:
        return
    try:
        from repro.launch import hlo_stats
        stats = hlo_stats.collective_bytes_by_axis(
            compiled.as_text(), dict(mesh.shape))
        by_axis = {ax: int(sum(ops.values()))
                   for ax, ops in stats["by_axis"].items()}
        codec = getattr(pcfg, "sync_compress", "none") or "none"
        for ax, b in by_axis.items():
            obs.registry.gauge(f"{ns}.collective_bytes", axis=ax,
                               codec=codec, scope=scope).set(b)
        obs.emit("hlo_sync_bytes", codec=codec, scope=scope,
                 bytes_by_axis=by_axis)
    except Exception as e:
        obs.emit("note", msg=f"hlo byte accounting skipped: {e}")


class RoundRunner:
    """Owns the step/round loop for one driver process.

    ``ns`` prefixes every metric series ("train" for launch/train.py,
    "pod" for a dist_run worker), so the merged pod snapshot and the
    single-process trainer keep their historical series names."""

    def __init__(self, obs, ns: str = "train",
                 checkpoint: Optional[CheckpointSpec] = None):
        self.obs = obs
        self.ns = ns
        self.checkpoint = checkpoint

    # -- checkpointing --------------------------------------------
    def _save(self, state, gstep: int):
        ck = self.checkpoint
        path = f"{ck.dir}/step{gstep:06d}.npz"
        with self.obs.tracer.span("checkpoint", step=gstep):
            ckpt.save(path, state, step=gstep, meta={"arch": ck.arch},
                      algo=ck.algo,
                      metrics=self.obs.registry.counter_stamp())
        self.obs.emit("checkpoint", step=gstep, path=path)

    def _ckpt_enabled(self) -> bool:
        ck = self.checkpoint
        return bool(ck and ck.every and ck.dir)

    # -- per-step loop --------------------------------------------
    def run_steps(self, state, step_fn, batch_fn: Callable[[int], Any], *,
                  start: int, steps: int, L: int, tokens_per_step: int,
                  mesh=None, pcfg=None, span_cat: str = "",
                  progress_every: int = 0, progress=None,
                  on_step=None, pre_step=None, aot: bool = True):
        """The per-step dispatch loop (one compiled program per step).

        ``progress(step, round, state, metrics)`` -> record is invoked
        on the historical cadence (every ``progress_every`` steps and on
        the first step), printed, and collected into the returned
        history.  ``on_step(i, metrics, sp)`` runs inside the step span,
        before the blocking read, for driver-specific emission.
        Spans: ``step``, with ``stage`` (the batch) inside it, then
        ``progress`` and ``checkpoint``."""
        obs, ns = self.obs, self.ns
        history = []
        if aot and obs.enabled:
            # AOT so compile is its own span and the timed steps are
            # steady-state only (the bench timing discipline)
            step_fn = aot_with_span(obs, step_fn, "step",
                                    (state, batch_fn(start)))
            record_hlo_bytes(obs, step_fn, mesh, pcfg, scope="step", ns=ns)
        obs.tracer.add_hlo_ops(step_fn)
        for i in range(start, start + steps):
            if pre_step is not None:
                pre_step(i)
            with obs.tracer.span("step", cat=span_cat, step=i + 1) as sp:
                with obs.tracer.span("stage", step=i + 1):
                    batch = batch_fn(i)
                state, metrics = step_fn(state, batch)
                if on_step is not None:
                    on_step(i, metrics, sp)
                sp.block(metrics)
            obs.registry.counter(f"{ns}.steps").inc()
            obs.registry.counter(f"{ns}.tokens").inc(tokens_per_step)
            if (i + 1) % L == 0:
                obs.registry.counter(f"{ns}.rounds").inc()
            if obs.enabled:
                obs.registry.histogram(f"{ns}.step_ms").observe(
                    sp.dur_s * 1e3)
            if progress is not None and ((i + 1) % progress_every == 0
                                         or i == start):
                with obs.tracer.span("progress", step=i + 1):
                    rec = progress(i + 1, (i + 1) // L, state, metrics)
                    print(json.dumps(rec), flush=True)
                history.append(rec)
            if self._ckpt_enabled() and (i + 1) % self.checkpoint.every == 0:
                self._save(state, i + 1)
        return state, history

    # -- fused-round loop -----------------------------------------
    def run_rounds(self, state, round_fn, stage_fn: Callable[[int], Any], *,
                   start: int, rounds: int, L: int, tokens_per_round: int,
                   mesh=None, pcfg=None, progress_every: int = 1,
                   progress=None, on_round=None, pre_round=None,
                   post_round=None, flush_fn=None, aot: bool = True):
        """The fused-round loop: one donated-buffer compiled program per
        L steps, with each round's batches staged by a single dispatch
        that is double-buffered against the round's compute (Python
        enqueues round r+1's batches right after dispatching round r,
        before touching any of round r's results).

        Instrumented: the program is AOT-compiled under a ``compile``
        span, every round is a ``round`` span that ends on
        ``block_until_ready`` (staging of the next round happens INSIDE
        the span, before the block, so double-buffering is preserved;
        it is a ``stage`` span of its own), the progress callback is a
        ``progress`` span and a save a ``checkpoint`` span, and the sync
        policy's ``flush_fn`` is a ``sync_flush`` span +
        ``staleness_flush`` event.  A compiled ``round_fn`` has its
        instructions' scopes recorded (``Tracer.add_hlo_ops``).
        ``post_round(state, r, gstep, metrics) -> state`` runs after the
        round's results are on host — the async policy's coordinator
        exchange lives there."""
        obs, ns = self.obs, self.ns
        history = []
        with obs.tracer.span("stage", step=start + L):
            nxt = stage_fn(start)
        if aot and obs.enabled and rounds:
            round_fn = aot_with_span(obs, round_fn, "round", (state, nxt))
            record_hlo_bytes(obs, round_fn, mesh, pcfg, scope="round", ns=ns)
        obs.tracer.add_hlo_ops(round_fn)
        for r in range(rounds):
            if pre_round is not None:
                pre_round(r)
            cur, nxt = nxt, None
            gstep = start + (r + 1) * L
            with obs.tracer.span("round", round=r + 1, step=gstep) as sp:
                state, metrics = round_fn(state, cur)   # async dispatch
                if r + 1 < rounds:
                    with obs.tracer.span("stage", step=gstep + L):
                        nxt = stage_fn(gstep)            # prefetch r+1
                sp.block(metrics)
            obs.registry.counter(f"{ns}.steps").inc(L)
            obs.registry.counter(f"{ns}.rounds").inc()
            obs.registry.counter(f"{ns}.tokens").inc(tokens_per_round)
            if obs.enabled:
                obs.registry.histogram(f"{ns}.round_ms").observe(
                    sp.dur_s * 1e3)
            if post_round is not None:
                state = post_round(state, r, gstep, metrics)
            if on_round is not None:
                on_round(r, gstep, metrics)
            if progress is not None and ((r + 1) % progress_every == 0
                                         or r == 0):
                with obs.tracer.span("progress", step=gstep):
                    rec = progress(gstep, r + 1, state, metrics)
                    print(json.dumps(rec), flush=True)
                history.append(rec)
            # a round advances L steps at once: checkpoint whenever it
            # CROSSES a checkpoint_every boundary, not only on exact
            # multiples (e.g. --L 3 --checkpoint-every 50 writes at 51)
            if (self._ckpt_enabled()
                    and gstep // self.checkpoint.every
                    > (gstep - L) // self.checkpoint.every):
                self._save(state, gstep)
        # the overlap policy leaves the last round's consensus in
        # flight: apply it once before eval/deploy.  Checkpoints above
        # are intentionally pre-flush — resumed runs re-enter the
        # overlap loop, which applies the carried consensus itself
        # (flushing a checkpointed state would double-apply on resume).
        if flush_fn is not None:
            with obs.tracer.span("sync_flush", cat="sync") as sp:
                state = flush_fn(state)
                sp.block(state)
            obs.registry.counter(f"{ns}.staleness_flushes").inc()
            obs.emit("staleness_flush", step=start + rounds * L,
                     flush_ms=round(sp.dur_s * 1e3, 3))
        return state, history


def emit_progress(obs, algo, state, metrics, step, rnd, t0):
    """ONE schema for every progress emit site (per-step and fused-round
    drivers): kind=train_progress with the same key set — ``round`` is
    the number of completed Eq. 8 rounds in both, and a fused round adds
    its per-step ``step_losses`` unrounded.  Per-replica losses (when the
    step emits them) land as labeled gauges."""
    import numpy as np
    diag = {k: round(v, 4) for k, v in algo.diagnostics(state).items()}
    extra = {}
    if "losses" in metrics:             # a fused round: its L step losses
        extra["step_losses"] = np.asarray(metrics["losses"]).tolist()
    rec = obs.emit("train_progress", step=step, round=rnd,
                   loss=round(float(metrics["loss"]), 4),
                   wall_s=round(time.time() - t0, 1), diag=diag, **extra)
    if obs.enabled:
        obs.registry.gauge("train.loss").set(rec["loss"])
        for k, v in diag.items():
            obs.registry.gauge(f"train.diag.{k}").set(v)
        per = metrics.get("loss_per_replica", metrics.get("losses"))
        if per is not None:
            for j, lv in enumerate(
                    np.asarray(per).reshape(-1).tolist()):
                obs.registry.gauge("train.replica_loss",
                                   replica=j).set(round(lv, 6))
    return rec
