"""Step functions shared by the trainer, the server, and the dry-run.

 * ``make_algorithm_step`` / ``make_algorithm_sharded_step`` — the ONE
   training-step factory: any registered algorithm (parle, entropy_sgd,
   elastic_sgd, sgd) by name, via ``repro.core.registry``.  No
   per-algorithm branching lives here — the registry object carries it,
   and the program SHAPE (which consensus schedule is compiled in) is
   delegated to the runtime's :class:`~repro.runtime.SyncPolicy`
   contract — these factories are thin name-resolving fronts over
   ``policy.make_step_fn`` / ``make_round_fn`` / ``make_flush_fn``, the
   same objects launch/train.py and launch/dist_run.py drive.
 * ``make_parle_steps``  — the dry-run DECOMPOSITION of the Parle step
   into inner_step (8a-8b; no cross-replica traffic) and sync_step
   (8c-8d; the single cross-replica all-reduce), compiled as separate
   programs so launch/dryrun.py can account their collectives
   independently.  Analysis tooling, not driver dispatch.
 * ``make_prefill_step`` / ``make_decode_step`` — serving programs.
"""
from __future__ import annotations

import jax

from repro.core import parle as parle_mod
from repro.core import registry
from repro.models.model import build_model
from repro.runtime import policy_for


def make_loss_fn(cfg, use_flash: bool = False, remat: bool = False):
    model = build_model(cfg, use_flash=use_flash, remat=remat)
    return model.loss


def make_algorithm_step(algo_name: str, cfg, pcfg, weight_decay: float = 0.0,
                        use_flash: bool = False, remat: bool = False,
                        use_kernel: bool = False, lr_schedule=None):
    """step(state, batch) -> (state, metrics) for any registered algo.
    ``batch`` leaves carry a leading replica axis of pcfg.n_replicas."""
    loss_fn = make_loss_fn(cfg, use_flash=use_flash, remat=remat)
    return policy_for(pcfg).make_step_fn(
        registry.get(algo_name), loss_fn, pcfg, weight_decay=weight_decay,
        use_kernel=use_kernel, lr_schedule=lr_schedule, jit=False)


def make_algorithm_sharded_step(algo_name: str, cfg, pcfg, mesh,
                                replica_axis: str = "replica",
                                weight_decay: float = 0.0,
                                use_flash: bool = False, remat: bool = False,
                                use_kernel: bool = False, lr_schedule=None):
    """The shard_map variant: replica axis sharded over ``replica_axis``."""
    loss_fn = make_loss_fn(cfg, use_flash=use_flash, remat=remat)
    return policy_for(pcfg).make_step_fn(
        registry.get(algo_name), loss_fn, pcfg, mesh=mesh,
        replica_axis=replica_axis, weight_decay=weight_decay,
        use_kernel=use_kernel, lr_schedule=lr_schedule)


def make_algorithm_round(algo_name: str, cfg, pcfg, mesh=None,
                         replica_axis: str = "replica",
                         weight_decay: float = 0.0,
                         use_flash: bool = False, remat: bool = False,
                         use_kernel: bool = False, lr_schedule=None):
    """The fused L-step round for any registered algo: ONE compiled,
    state-donating program per pcfg.L steps — round(state, batches) ->
    (state, metrics) with batches leaves (L, n, B, ...).  Python
    re-enters once per round (see the Algorithm protocol docstring for
    the donation and step-counter contracts)."""
    loss_fn = make_loss_fn(cfg, use_flash=use_flash, remat=remat)
    return policy_for(pcfg).make_round_fn(
        registry.get(algo_name), loss_fn, pcfg, mesh=mesh,
        replica_axis=replica_axis, weight_decay=weight_decay,
        use_kernel=use_kernel, lr_schedule=lr_schedule)


def make_algorithm_round_flush(algo_name: str, pcfg, lr_schedule=None):
    """The end-of-training pairing of the sync-overlap round: a jitted
    flush(state) -> state that applies the in-flight staleness-1
    consensus once, or None when the algo/config has nothing in flight
    (barrier sync, elastic_sgd, sgd).  Call it on the FINAL state before
    eval/deploy — never on a state that will be checkpointed and resumed
    (the resumed overlap loop applies the carry itself)."""
    return policy_for(pcfg).make_flush_fn(registry.get(algo_name), pcfg,
                                          lr_schedule=lr_schedule)


def make_parle_steps(cfg, pcfg, weight_decay: float = 0.0,
                     use_flash: bool = False, remat: bool = False,
                     use_kernel: bool = False):
    loss_fn = make_loss_fn(cfg, use_flash=use_flash, remat=remat)
    replica_step = parle_mod.make_replica_step(
        loss_fn, pcfg, weight_decay=weight_decay, use_kernel=use_kernel)

    def inner_step(state, batch):
        """(8a)-(8b): per-replica grad + fused update. Cross-replica: NONE
        (the grad all-reduce over "data" is *intra*-replica)."""
        state, loss, _ = replica_step(state, batch)
        return state, {"loss": loss}

    def sync_step(state):
        """(8c)-(8d): the one all-reduce over the replica axis."""
        return parle_mod.sync_step(state, pcfg)

    return inner_step, sync_step


def make_prefill_step(cfg, use_flash: bool = False):
    model = build_model(cfg, use_flash=use_flash)

    def prefill(params, batch, cache):
        return model.prefill(params, batch, cache)

    return prefill


def make_decode_step(cfg, sampling=None):
    """One-token decode + token selection.  Selection rides the serving
    sampler (greedy by default), so this, the naive reference loop, and
    the continuous-batching engine share one code path."""
    from repro.serving.sampling import SamplingParams, make_token_selector
    model = build_model(cfg)
    selector = make_token_selector(cfg, sampling or SamplingParams())

    def decode(params, batch, cache, key=None):
        logits, cache = model.decode(params, batch, cache)
        if key is None:
            key = jax.random.PRNGKey(0)
        return selector(logits, key), cache
    return decode
