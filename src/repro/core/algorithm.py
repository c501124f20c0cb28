"""The unified ``Algorithm`` protocol — one optimizer API for the whole
Parle family.

The paper frames Entropy-SGD and Elastic-SGD as special cases of Parle
(§2.1, §3): Entropy-SGD is Parle with n=1, Elastic-SGD is the L=1
per-step-coupling limit, and plain data-parallel SGD is the degenerate
member where the coupling is infinitely stiff.  This module states that
family relationship as an interface: each algorithm is a named,
registered object (see :mod:`repro.core.registry`) exposing

  canonicalize_cfg(cfg)      -> cfg with the algorithm's invariants
                                applied (e.g. entropy_sgd forces n=1)
  init(params, cfg)          -> State
  make_step(loss_fn, cfg, *, weight_decay, use_kernel, lr_schedule)
                             -> step(state, batch) -> (state, metrics)
  make_sharded_step(loss_fn, cfg, mesh, replica_axis, *, ...)
                             -> the same step under shard_map, replica
                                axis sharded over the mesh; in-replica
                                "data"/"model" axes run FSDP x TP from
                                the sharding planner under the SAME
                                shard_map (replica manual, rest auto)
  make_round_fn(loss_fn, cfg, *, mesh=None, ...)
                             -> round(state, batches) -> (state, metrics):
                                one compiled, state-DONATING program per
                                L = cfg.L steps (lax.scan over the inner
                                steps, the sync at the end for Parle);
                                batches leaves are (L, n, B, ...) and
                                the state's step counter must be a
                                multiple of L on entry.  metrics carry
                                the round-mean "loss" + per-step
                                "losses" (L,).  With a mesh, replica-
                                sharded like make_sharded_step (see the
                                per-module docstrings for the two-
                                program round on composed meshes).
                                With cfg.sync_overlap (parle/
                                entropy_sgd) the returned round is the
                                staleness-1 overlapped variant: the
                                Eq. (8d) collective is issued at the
                                round's START and applied at the start
                                of the NEXT round (core/parle.py)
  make_round_flush_fn(cfg, *, lr_schedule=None)
                             -> flush(state) -> state, or None: only
                                non-None for algorithms/configs whose
                                rounds leave work in flight
                                (cfg.sync_overlap).  Apply it ONCE
                                after the last round, before eval /
                                deployable — never to a state that will
                                be checkpointed and resumed
  state_pspecs(replica_axis, params=None, mesh=None, cfg=None)
                             -> PartitionSpec tree for State: the
                                replica-axis prefix form without
                                ``params``; with ``params`` the
                                planner-composed per-leaf form
                                ``P(replica, *plan(leaf))`` (what
                                device_put / checkpoint restore use).
                                ``cfg`` shapes feature-dependent leaves
                                (the compressed-sync residual ``e``)
  deployable(state)          -> the single servable model pytree
  diagnostics(state)         -> dict of host-side floats (overlap /
                                spread where a replica axis exists)

Uniform contracts shared by all four implementations:

* ``batch`` leaves carry a leading replica axis of size
  ``cfg.n_replicas`` (SGD reads it as plain data-parallel shards).
* ``metrics`` always contains a scalar ``"loss"``.
* ``lr_schedule`` maps the state's step counter to a MULTIPLIER on the
  config learning rates (both lr and lr_inner for Parle).  When left
  None it is derived from ``cfg.lr_drop_steps``/``cfg.lr_drop_factor``
  — the paper's §4 step-decay — via :func:`resolve_lr_schedule`.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Protocol, runtime_checkable

from repro.core import elastic_sgd, ensemble, parle
from repro.core.registry import register
from repro.optim import sgd


@runtime_checkable
class Algorithm(Protocol):
    """Structural type of a registered optimizer (see module docstring)."""

    name: str

    def canonicalize_cfg(self, cfg): ...

    def init(self, params, cfg): ...

    def make_step(self, loss_fn: Callable, cfg, *,
                  weight_decay: float = 0.0, use_kernel: bool = False,
                  lr_schedule=None): ...

    def make_sharded_step(self, loss_fn: Callable, cfg, mesh,
                          replica_axis: str = "replica", *,
                          weight_decay: float = 0.0,
                          use_kernel: bool = False, lr_schedule=None): ...

    def make_round_fn(self, loss_fn: Callable, cfg, *, mesh=None,
                      replica_axis: str = "replica",
                      weight_decay: float = 0.0, use_kernel: bool = False,
                      lr_schedule=None): ...

    def make_round_flush_fn(self, cfg, *, lr_schedule=None): ...

    def state_pspecs(self, replica_axis: str, params=None, mesh=None,
                     cfg=None): ...

    def deployable(self, state): ...

    def diagnostics(self, state) -> dict: ...


def resolve_lr_schedule(cfg, lr_schedule=None):
    """The protocol's schedule resolution: an explicit ``lr_schedule``
    wins; otherwise ``cfg.lr_drop_steps`` builds the §4 step-decay as a
    multiplier schedule (base 1.0); otherwise None (constant lr)."""
    if lr_schedule is not None:
        return lr_schedule
    if cfg.lr_drop_steps:
        return sgd.step_decay_schedule(1.0, cfg.lr_drop_steps,
                                       cfg.lr_drop_factor)
    return None


def _replica_diagnostics(replica_tree) -> dict:
    return {
        "overlap": float(ensemble.replica_overlap(replica_tree)),
        "spread": float(ensemble.replica_spread(replica_tree)),
    }


# ------------------------------------------------------------------
# Parle (Eq. 8a-8d) and Entropy-SGD (= Parle n=1)
# ------------------------------------------------------------------

class ParleAlgorithm:
    name = "parle"

    def canonicalize_cfg(self, cfg):
        return dataclasses.replace(cfg, mode=self.name)

    def init(self, params, cfg) -> parle.ParleState:
        return parle.init(params, cfg)

    def make_step(self, loss_fn, cfg, *, weight_decay=0.0, use_kernel=False,
                  lr_schedule=None):
        return parle.make_train_step(
            loss_fn, cfg, weight_decay=weight_decay, use_kernel=use_kernel,
            lr_schedule=resolve_lr_schedule(cfg, lr_schedule))

    def make_sharded_step(self, loss_fn, cfg, mesh, replica_axis="replica",
                          *, weight_decay=0.0, use_kernel=False,
                          lr_schedule=None):
        return parle.make_sharded_train_step(
            loss_fn, cfg, mesh, replica_axis=replica_axis,
            weight_decay=weight_decay, use_kernel=use_kernel,
            lr_schedule=resolve_lr_schedule(cfg, lr_schedule))

    def make_round_fn(self, loss_fn, cfg, *, mesh=None,
                      replica_axis="replica", weight_decay=0.0,
                      use_kernel=False, lr_schedule=None):
        return parle.make_round_fn(
            loss_fn, cfg, mesh=mesh, replica_axis=replica_axis,
            weight_decay=weight_decay, use_kernel=use_kernel,
            lr_schedule=resolve_lr_schedule(cfg, lr_schedule))

    def make_round_flush_fn(self, cfg, *, lr_schedule=None):
        if not getattr(cfg, "sync_overlap", False):
            return None
        return parle.make_flush_fn(cfg,
                                   lr_schedule=resolve_lr_schedule(
                                       cfg, lr_schedule))

    def state_pspecs(self, replica_axis: str, params=None, mesh=None,
                     cfg=None):
        from repro.sharding.partition import parle_state_pspecs
        return parle_state_pspecs(replica_axis, params=params, mesh=mesh,
                                  cfg=cfg)

    def deployable(self, state):
        return parle.average_model(state)

    def diagnostics(self, state) -> dict:
        out = {"gamma": float(state.scopes.gamma),
               "rho": float(state.scopes.rho)}
        out.update(_replica_diagnostics(state.x))
        return out


class EntropySGDAlgorithm(ParleAlgorithm):
    """Exactly Parle with n=1 (§2.1/§3): the elastic term vanishes
    identically, so every capability (kernels, mesh path, checkpoints)
    is inherited rather than re-plumbed.  The n=1 invariant is enforced
    here even when the caller skips canonicalize_cfg."""

    name = "entropy_sgd"

    def canonicalize_cfg(self, cfg):
        return dataclasses.replace(cfg, n_replicas=1, mode=self.name)

    def init(self, params, cfg):
        return super().init(params, self.canonicalize_cfg(cfg))

    def make_step(self, loss_fn, cfg, **kw):
        return super().make_step(loss_fn, self.canonicalize_cfg(cfg), **kw)

    def make_round_fn(self, loss_fn, cfg, **kw):
        return super().make_round_fn(loss_fn, self.canonicalize_cfg(cfg),
                                     **kw)

    def make_round_flush_fn(self, cfg, **kw):
        return super().make_round_flush_fn(self.canonicalize_cfg(cfg), **kw)

    def make_sharded_step(self, loss_fn, cfg, mesh, replica_axis="replica",
                          **kw):
        if mesh.shape[replica_axis] != 1:
            raise ValueError(
                "entropy_sgd runs a single replica (Parle n=1), so a "
                f"replica-sharded mesh ({replica_axis}:"
                f"{mesh.shape[replica_axis]}) has nothing to shard — use "
                "--algo parle for n>1 replicas, or --algo sgd for plain "
                "data parallelism over the axis")
        return super().make_sharded_step(
            loss_fn, self.canonicalize_cfg(cfg), mesh, replica_axis, **kw)


# ------------------------------------------------------------------
# Elastic-SGD (Eq. 7) — the per-step-coupling O(2nN) baseline
# ------------------------------------------------------------------

class ElasticSGDAlgorithm:
    name = "elastic_sgd"

    def canonicalize_cfg(self, cfg):
        return dataclasses.replace(cfg, mode=self.name)

    def init(self, params, cfg) -> elastic_sgd.ElasticState:
        return elastic_sgd.init(params, cfg)

    def make_step(self, loss_fn, cfg, *, weight_decay=0.0, use_kernel=False,
                  lr_schedule=None):
        return elastic_sgd.make_train_step(
            loss_fn, cfg, weight_decay=weight_decay, use_kernel=use_kernel,
            lr_schedule=resolve_lr_schedule(cfg, lr_schedule))

    def make_sharded_step(self, loss_fn, cfg, mesh, replica_axis="replica",
                          *, weight_decay=0.0, use_kernel=False,
                          lr_schedule=None):
        return elastic_sgd.make_sharded_train_step(
            loss_fn, cfg, mesh, replica_axis=replica_axis,
            weight_decay=weight_decay, use_kernel=use_kernel,
            lr_schedule=resolve_lr_schedule(cfg, lr_schedule))

    def make_round_fn(self, loss_fn, cfg, *, mesh=None,
                      replica_axis="replica", weight_decay=0.0,
                      use_kernel=False, lr_schedule=None):
        sched = resolve_lr_schedule(cfg, lr_schedule)
        if mesh is None:
            return elastic_sgd.make_round_fn(
                loss_fn, cfg, weight_decay=weight_decay,
                use_kernel=use_kernel, lr_schedule=sched)
        return elastic_sgd.make_sharded_round_fn(
            loss_fn, cfg, mesh, replica_axis=replica_axis,
            weight_decay=weight_decay, use_kernel=use_kernel,
            lr_schedule=sched)

    def make_round_flush_fn(self, cfg, *, lr_schedule=None):
        del cfg, lr_schedule    # per-step coupling: nothing in flight
        return None

    def state_pspecs(self, replica_axis: str, params=None, mesh=None,
                     cfg=None):
        from repro.sharding.partition import elastic_state_pspecs
        del cfg                 # no feature-dependent leaves
        return elastic_state_pspecs(replica_axis, params=params, mesh=mesh)

    def deployable(self, state):
        return elastic_sgd.average_model(state)

    def diagnostics(self, state) -> dict:
        out = {"rho": float(state.scopes.rho)}
        out.update(_replica_diagnostics(state.x))
        return out


# ------------------------------------------------------------------
# SGD — the paper's §4 baseline; the replica axis is read as plain
# data-parallel shards (grads averaged every step)
# ------------------------------------------------------------------

class SGDAlgorithm:
    name = "sgd"

    def canonicalize_cfg(self, cfg):
        return dataclasses.replace(cfg, mode=self.name)

    def init(self, params, cfg) -> sgd.SGDState:
        del cfg
        return sgd.init(params)

    def make_step(self, loss_fn, cfg, *, weight_decay=0.0, use_kernel=False,
                  lr_schedule=None):
        del use_kernel      # XLA already fuses the single update stream
        return sgd.make_replica_train_step(
            loss_fn, cfg, weight_decay=weight_decay,
            lr_schedule=resolve_lr_schedule(cfg, lr_schedule))

    def make_sharded_step(self, loss_fn, cfg, mesh, replica_axis="replica",
                          *, weight_decay=0.0, use_kernel=False,
                          lr_schedule=None):
        return sgd.make_sharded_train_step(
            loss_fn, cfg, mesh, replica_axis=replica_axis,
            weight_decay=weight_decay, use_kernel=use_kernel,
            lr_schedule=resolve_lr_schedule(cfg, lr_schedule))

    def make_round_fn(self, loss_fn, cfg, *, mesh=None,
                      replica_axis="replica", weight_decay=0.0,
                      use_kernel=False, lr_schedule=None):
        del use_kernel      # XLA already fuses the single update stream
        sched = resolve_lr_schedule(cfg, lr_schedule)
        if mesh is None:
            return sgd.make_round_fn(loss_fn, cfg,
                                     weight_decay=weight_decay,
                                     lr_schedule=sched)
        return sgd.make_sharded_round_fn(
            loss_fn, cfg, mesh, replica_axis=replica_axis,
            weight_decay=weight_decay, lr_schedule=sched)

    def make_round_flush_fn(self, cfg, *, lr_schedule=None):
        del cfg, lr_schedule    # grads averaged every step: no sync debt
        return None

    def state_pspecs(self, replica_axis: str, params=None, mesh=None,
                     cfg=None):
        from repro.sharding.partition import sgd_state_pspecs
        del replica_axis, cfg   # one replicated model; nothing rides the
        return sgd_state_pspecs(params=params, mesh=mesh)   # axis

    def deployable(self, state):
        return state.params

    def diagnostics(self, state) -> dict:
        del state
        return {}


PARLE = register(ParleAlgorithm())
ENTROPY_SGD = register(EntropySGDAlgorithm())
ELASTIC_SGD = register(ElasticSGDAlgorithm())
SGD = register(SGDAlgorithm())
