"""Parle (Chaudhari et al., 2017) — Eq. (8a)-(8d) — as a composable JAX
optimizer transform.

State layout: every leaf carries a leading **replica axis** of size n.
Locally (CPU tests, single host) the replica axis is just vmapped; on a
mesh it is sharded over the ``replica``/``pod`` mesh axis, so the single
cross-replica reduction in ``sync_step`` (the mean of Eq. 8d with
eta'' = rho/n, §3.1) lowers to one all-reduce over that axis — the ONLY
cross-replica collective, fired once every L inner steps.  That is the
paper's O(2nN/L) amortized-communication property, stated in mesh terms.

Updates (Nesterov momentum mu=0.9 per Remark 2, none on the reference):

  inner_step (every step; zero cross-replica traffic):
    g_y   = grad f(y) + (y - x)/gamma            (8a)
    v_y  <- mu v_y + g_y ;  y <- y - lr' (g_y + mu v_y)
    z    <- alpha z + (1-alpha) y                (8b)

  sync_step (when k/L integer; one all-reduce):
    xbar  = mean_a x^a                           (8d with eta''=rho/n)
    g_x   = (x - z) + (x - xbar)/rho             (8c; first term already
                                                  gamma-scaled per Remark 1)
    v_x  <- mu v_x + g_x ;  x <- x - lr (g_x + mu v_x)
    y, z <- x  (inner-loop reset);  gamma, rho <- scoping decay (Eq. 9)

Baselines: ``mode="entropy_sgd"`` is exactly Parle with n=1 (the elastic
term vanishes identically — §2.1/§3); Elastic-SGD lives in
core/elastic_sgd.py (per-step coupling, Eq. 7).

Profiles: ``inner_step`` runs under ``jax.named_scope("parle_inner")``
and the sync (``sync_step``, ``consensus_step``, ``overlap_head``, the
flush and the async apply) under ``"parle_sync"``; XLA keeps the scope
in the metadata of every op they lower to, so every step and round
program built below inherits the split without naming it.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import compress
from repro.core.scoping import Scopes, init_scopes, update_scopes
from repro.utils.pytree import (tree_broadcast_axis0, tree_cast,
                                tree_mean_axis0, tree_unzip,
                                tree_zeros_like)


class ParleState(NamedTuple):
    """Dtype layout under mixed precision (cfg.precision="bf16"): ``y``
    (the compute iterate — what the loss/grad sees) is bfloat16; ``x``,
    ``z`` and both momenta stay float32 masters.  ``e`` is the
    error-feedback residual of the compressed sync (cfg.sync_compress
    in {"bf16","int8"}), float32, same shape as ``x``; None otherwise
    (an absent pytree subtree, so tree structure only changes when the
    feature is on).  ``c`` is the in-flight staleness-1 consensus of the
    overlapped sync (cfg.sync_overlap): the reduced Eq. (8d) replica
    mean issued by the CURRENT round and applied at the start of the
    next one — model-shaped f32 leaves with no replica axis (like
    elastic's ``ref``); None when overlap is off."""

    x: Any            # (n, ...) replicas x^a                 [f32 master]
    y: Any            # (n, ...) inner Entropy-SGD iterate    [compute dtype]
    z: Any            # (n, ...) exponential average of y     [f32 master]
    v_y: Any          # (n, ...) Nesterov momentum of y       [f32 master]
    v_x: Any          # (n, ...) Nesterov momentum of x^a     [f32 master]
    step: jnp.ndarray  # () int32, counts inner steps k
    scopes: Scopes
    e: Any = None     # (n, ...) sync-compression error-feedback residual
    c: Any = None     # (...) in-flight staleness-1 consensus (sync_overlap)


def _compute_dtype(cfg):
    get = getattr(cfg, "compute_dtype", None)
    return get() if get is not None else jnp.float32


def _sync_compress(cfg) -> str:
    method = getattr(cfg, "sync_compress", "none")
    compress.check_method(method)
    return method


def _sync_overlap(cfg) -> bool:
    return bool(getattr(cfg, "sync_overlap", False))


def init(params, cfg) -> ParleState:
    """``params``: single-model pytree; replicated n_replicas times.

    All replicas start at the same point (the paper initializes each
    replica from the same random init; diversity comes from data order).
    """
    return init_from_replicas(tree_broadcast_axis0(params, cfg.n_replicas),
                              cfg)


def init_from_replicas(replica_params, cfg) -> ParleState:
    """Start from distinct per-replica params (leading axis n)."""
    x = jax.tree.map(lambda l: l.astype(jnp.float32), replica_params)
    return ParleState(
        x=x, y=tree_cast(x, _compute_dtype(cfg)), z=x,
        v_y=tree_zeros_like(x), v_x=tree_zeros_like(x),
        step=jnp.zeros((), jnp.int32),
        scopes=init_scopes(cfg),
        e=tree_zeros_like(x) if _sync_compress(cfg) != "none" else None,
        # placeholder until the first overlap round issues a real
        # consensus — never applied (the apply is gated on step > 0)
        c=jax.tree.map(lambda l: jnp.zeros(l.shape[1:], jnp.float32), x)
        if _sync_overlap(cfg) else None,
    )


# ------------------------------------------------------------------
# Inner step (8a)-(8b)
# ------------------------------------------------------------------

@jax.named_scope("parle_inner")
def inner_step(state: ParleState, grads, cfg, use_kernel: bool = False,
               lr_scale=1.0, shard_ctx=None) -> ParleState:
    """grads: pytree with leading replica axis = grad f(y^a) per replica.
    ``lr_scale``: multiplier on lr_inner (step-decay schedules, §4).
    ``shard_ctx``: planner context when the leaves are FSDP x TP sharded
    over in-replica mesh axes — the kernels then grid over the LOCAL
    shard of each leaf (see kernels/parle_update.py).

    Mixed precision: y and grads may be bf16 (cfg.precision="bf16") while
    z, v, x are f32 masters.  The update always accumulates in f32 —
    bf16 operands are upcast on read and only the y output is cast back,
    so the f32 path is bit-identical to the historical all-f32 code (the
    casts are identities XLA elides)."""
    mu, lr = cfg.momentum, cfg.lr_inner * lr_scale
    inv_gamma = 1.0 / state.scopes.gamma
    alpha = cfg.alpha

    if use_kernel:
        from repro.kernels import ops as kops
        y, z, v_y = kops.parle_inner_update(
            state.y, state.z, state.v_y, grads, state.x,
            inv_gamma=inv_gamma, lr=lr, mu=mu, alpha=alpha,
            shard_ctx=shard_ctx)
    else:
        def upd(y, z, v, g, x):
            yf = y.astype(jnp.float32)
            g_y = g.astype(jnp.float32) + inv_gamma * (yf - x)   # (8a)
            v_new = mu * v + g_y                   # Nesterov
            y_new = yf - lr * (g_y + mu * v_new)
            z_new = alpha * z + (1.0 - alpha) * y_new   # (8b)
            return y_new.astype(y.dtype), z_new, v_new

        out = jax.tree.map(upd, state.y, state.z, state.v_y, grads, state.x)
        y, z, v_y = tree_unzip(state.y, out, 3)

    return state._replace(y=y, z=z, v_y=v_y, step=state.step + 1)


# ------------------------------------------------------------------
# Sync step (8c)-(8d): the one cross-replica collective
# ------------------------------------------------------------------

def _quantized_leaf_stats(xl, el, method, axis_name):
    """One leaf's compressed-sync statistics: quantize each replica's
    contribution with error feedback, gather the payload across the
    replica axis, dequantize, mean.  Shapes: xl/el (r, ...); returns
    (xbar (...), e_new (r, ...))."""
    r, shape, m = xl.shape[0], xl.shape, xl[0].size
    c = compress.pad_to_chunk((xl.astype(jnp.float32) + el).reshape(r, -1))
    q, s, res = compress.quantize_ef(c, method)
    e_new = res[:, :m].reshape(shape)
    if axis_name is not None:
        # pin the QUANTIZED width on the wire.  A bf16 all-gather gets
        # upcast back to f32 by XLA's float-normalization pass on
        # backends without bf16 collectives (this CPU container), so
        # the payload travels as its uint16 bit pattern — integer
        # collectives are never normalized; bitcasts are free
        wire_cast = (q.dtype == jnp.bfloat16)
        if wire_cast:
            q = jax.lax.bitcast_convert_type(q, jnp.uint16)
        q = jax.lax.all_gather(q, axis_name, axis=0, tiled=True)
        if wire_cast:
            q = jax.lax.bitcast_convert_type(q, jnp.bfloat16)
        if s is not None:
            s = jax.lax.all_gather(s, axis_name, axis=0, tiled=True)
    deq = compress.dequantize(q, s, method)
    xbar = jnp.mean(deq, axis=0)[:m].reshape(shape[1:])
    return xbar, e_new


def _quantized_sync_stats(x, e, method: str, axis_name, shard_ctx=None):
    """Compress each replica's sync contribution and produce the Eq. (8d)
    replica mean from the compressed payloads.

    Per leaf: c_a = x_a + e_a is quantized PER REPLICA (so the result is
    independent of replica-to-device layout), the error-feedback residual
    e_a' = c_a - dequant(q_a) is kept for the next sync, and the mean is
    taken over ALL n dequantized contributions.  Under shard_map
    (axis_name set) the cross-device traffic is the all_gather of the
    QUANTIZED payloads — bf16 halves, int8 (+ per-1024-chunk f32 scales)
    quarters the f32 wire bytes, asserted from compiled HLO in
    tests/test_sync_compress.py.

    With a planner ``shard_ctx`` (composed FSDP x TP mesh) each leaf's
    quantize/gather/dequant runs under a nested shard_map over the
    in-replica axes — fully manual, because the flatten-reshape of an
    auto-sharded leaf trips XLA's manual-subgroup propagation on jax
    0.4.37 (same workaround as the Pallas kernel drivers).  The payload
    then chunks per LOCAL SHARD, so the gather moves shard-size
    compressed bytes per device and quantization boundaries follow the
    shard layout (composed-mesh trajectories match the local path to
    tolerance, not bit-for-bit — like the rest of the composed path).

    Returns (xbar_tree, e_new_tree); xbar leaves are un-broadcast (...).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(x)
    flat_e = treedef.flatten_up_to(e)
    xbars, e_news = [], []
    for (path, xl), el in zip(flat, flat_e):
        call = lambda a, b: _quantized_leaf_stats(a, b, method, axis_name)
        if shard_ctx is not None:
            from jax.sharding import PartitionSpec as P

            from repro.sharding.planner import path_names
            from repro.utils.compat import shard_map
            spec = shard_ctx.leaf_spec(path_names(path), xl.shape[1:])
            rep_spec = P(None, *spec)
            call = shard_map(call, shard_ctx.mesh,
                             in_specs=(rep_spec, rep_spec),
                             out_specs=(spec, rep_spec))
        xbar, e_new = call(xl, el)
        xbars.append(xbar)
        e_news.append(e_new)
    un = jax.tree_util.tree_unflatten
    return un(treedef, xbars), un(treedef, e_news)


@jax.named_scope("parle_sync")
def consensus_step(state: ParleState, xbar, cfg, *,
                   use_kernel: bool = False, lr_scale=1.0,
                   shard_ctx=None) -> ParleState:
    """The Eq. (8c)-(8d) consensus update given an ALREADY-reduced
    ``xbar`` (un-broadcast model-shaped leaves — the replica mean the
    collective produced), plus the inner-loop reset and the Eq. (9)
    scope decay.  ``e``, ``c`` and ``step`` pass through untouched —
    the caller owns them (the barrier sync updates ``e`` from its
    stats; the overlapped head updates both ``e`` and ``c`` from the
    NEXT payload)."""
    mu, lr = cfg.momentum, cfg.lr * lr_scale
    inv_rho = 1.0 / state.scopes.rho
    cdtype = _compute_dtype(cfg)
    gamma_scale = 1.0 if cfg.scale_lr_by_gamma else 1.0 / state.scopes.gamma

    if use_kernel:
        # the kernel consumes the UN-broadcast mean: one model-size xbar
        # buffer shared across replicas, never materialized at n x N.
        # Under bf16 the compute-copy cast y' = cast(x') is fused into
        # the kernel (third output) — no separate cast pass.
        from repro.kernels import ops as kops
        x, v_x, y = kops.parle_sync_update(
            state.x, state.z, state.v_x, xbar,
            gamma_scale=gamma_scale, inv_rho=inv_rho, lr=lr, mu=mu,
            shard_ctx=shard_ctx, y_dtype=cdtype)
    else:
        xbar = jax.tree.map(lambda m, x: jnp.broadcast_to(m[None], x.shape),
                            xbar, state.x)

        def upd(x, z, v, xb):
            g_x = gamma_scale * (x - z) + inv_rho * (x - xb)    # (8c)
            v_new = mu * v + g_x
            x_new = x - lr * (g_x + mu * v_new)
            return x_new, v_new

        out = jax.tree.map(upd, state.x, state.z, state.v_x, xbar)
        x, v_x = tree_unzip(state.x, out, 2)
        y = tree_cast(x, cdtype)         # f32: the identity (y is x)

    return state._replace(
        x=x, y=y, z=x,                    # reset y,z to x^a (paper: "we
        v_y=tree_zeros_like(x),           # initialize y to x every L")
        v_x=v_x,
        scopes=update_scopes(state.scopes, cfg),
    )


def _sync_stats(state: ParleState, cfg, axis_name, shard_ctx):
    """The Eq. (8d) replica mean of the (optionally compressed) ``x+e``
    payload — the collective half of the sync, shared by the barrier
    sync and the overlapped head.  Returns (xbar, e_new): xbar holds
    reduced model-shaped leaves."""
    method = _sync_compress(cfg)
    if method != "none":
        return _quantized_sync_stats(state.x, state.e, method, axis_name,
                                     shard_ctx=shard_ctx)
    if axis_name is None:
        return tree_mean_axis0(state.x), state.e
    return jax.tree.map(lambda v: jax.lax.pmean(jnp.mean(v, axis=0),
                                                axis_name), state.x), state.e


@jax.named_scope("parle_sync")
def sync_step(state: ParleState, cfg, axis_name: str | None = None,
              use_kernel: bool = False, lr_scale=1.0,
              shard_ctx=None) -> ParleState:
    # (8d) with eta'' = rho/n: the reference IS the replica mean.
    # Local path: leading-axis mean.  shard_map path (axis_name given):
    # the global n replicas are laid out as (devices, n_per_device), so
    # the global mean = pmean over the mesh axis of the LOCAL leading-
    # axis mean — still exactly one all-reduce, of model-size bytes,
    # regardless of how many replicas ride each device.  With
    # cfg.sync_compress the payload is quantized per replica and the
    # collective becomes an all_gather of the compressed bytes.
    xbar, e_new = _sync_stats(state, cfg, axis_name, shard_ctx)
    return consensus_step(state._replace(e=e_new), xbar, cfg,
                          use_kernel=use_kernel, lr_scale=lr_scale,
                          shard_ctx=shard_ctx)


def fused_step(state: ParleState, grads, cfg, use_kernel: bool = False,
               axis_name: str | None = None, lr_scale=1.0,
               shard_ctx=None) -> ParleState:
    """One Parle step: inner update + conditional sync (k/L integer)."""
    state = inner_step(state, grads, cfg, use_kernel=use_kernel,
                       lr_scale=lr_scale, shard_ctx=shard_ctx)
    do_sync = (state.step % cfg.L) == 0
    return jax.lax.cond(do_sync,
                        lambda s: sync_step(s, cfg, axis_name=axis_name,
                                            use_kernel=use_kernel,
                                            lr_scale=lr_scale,
                                            shard_ctx=shard_ctx),
                        lambda s: s,
                        state)


# ------------------------------------------------------------------
# Staleness-1 overlapped sync (cfg.sync_overlap): the Eq. (8d)
# collective is issued at the START of a round — before the L inner
# steps, which do not consume it — and applied at the start of the NEXT
# round, carried in ParleState.c.  Because x only changes at the
# consensus update, the payload snapshotted right after the apply equals
# the barrier path's end-of-round x exactly: the overlapped trajectory
# is the barrier trajectory with rotated program boundaries, and R
# overlap rounds + one flush reproduce R barrier rounds bit-for-bit on
# the f32 local/replica-sharded paths.
# ------------------------------------------------------------------

@jax.named_scope("parle_sync")
def overlap_head(state: ParleState, cfg, axis_name: str | None = None,
                 use_kernel: bool = False, lr_scale=1.0,
                 shard_ctx=None) -> ParleState:
    """The overlapped round's head: (1) apply the carried consensus
    ``state.c`` (gated on step > 0 — the first round has nothing in
    flight), (2) snapshot + (optionally compress) the NEW x+e as the
    next payload, issue its collective, update the error-feedback
    residual, and carry the reduced mean in ``c``.  ``lr_scale`` is the
    apply's outer-lr multiplier — schedule(step - 1), the same value
    the barrier sync it replays would have used."""
    applied = jax.lax.cond(
        state.step > 0,
        lambda s: consensus_step(s, s.c, cfg, use_kernel=use_kernel,
                                 lr_scale=lr_scale, shard_ctx=shard_ctx),
        lambda s: s, state)
    xbar, e_new = _sync_stats(applied, cfg, axis_name, shard_ctx)
    return applied._replace(e=e_new, c=xbar)


def _sync_scale(lr_schedule, state: ParleState):
    """The outer-lr multiplier of a consensus update that closes the
    round ending at ``state.step``: schedule(step - 1), the value of the
    round's last inner step (the cond'd sync of the step loop fires
    there)."""
    return lr_schedule(state.step - 1) if lr_schedule is not None else 1.0


def make_flush_fn(cfg, lr_schedule=None):
    """flush(state) -> state: apply the still-in-flight consensus after
    the LAST overlap round, completing the rotation — the flushed state
    equals the barrier trajectory's.  Gated on step > 0 (a never-run
    state flushes to itself).  Pure elementwise (the collective already
    ran), so one GSPMD jit covers every mesh layout; always the jnp
    apply (bit-identical to the interpret-mode kernel).

    Call exactly once, on the state you are about to evaluate or
    deploy; checkpoints written at round boundaries stay PRE-flush so
    resuming continues the overlapped trajectory exactly (flushing a
    checkpointed state and then resuming from it would double-apply)."""

    @jax.named_scope("parle_sync")
    def flush(state):
        lr_scale = _sync_scale(lr_schedule, state)
        return jax.lax.cond(
            state.step > 0,
            lambda s: consensus_step(s, s.c, cfg, lr_scale=lr_scale),
            lambda s: s, state)

    return jax.jit(flush)


# ------------------------------------------------------------------
# The per-replica inner step, and the step and round programs built
# from it
# ------------------------------------------------------------------

def make_replica_step(loss_fn: Callable, cfg, *, weight_decay: float = 0.0,
                      use_kernel: bool = False, axis_name: str | None = None,
                      lr_schedule=None, shard_ctx=None, fused: bool = False):
    """The one per-replica inner step every Parle program runs:
    step(state, batch) -> (state, loss, losses).

    Per-replica grads of ``loss_fn(params, batch) -> (scalar, aux)`` at
    y^a (vmap over the leading replica axis), plus weight decay, then
    :func:`inner_step` at this step's multiplier — ``lr_schedule``:
    step -> multiplier on BOTH cfg.lr and cfg.lr_inner (the paper fixes
    eta' to the initial eta, so they decay together).  With ``fused`` it
    is :func:`fused_step` instead: the inner update plus the cond'd sync
    every L steps, the body of the step loop.  ``losses`` is the
    per-replica loss vector, ``loss`` its mean, pmean'd to its global
    value over ``axis_name`` when that is set.  ``inner_step`` and
    ``sync_step`` are looked up when the step is traced."""

    def replica_grad(params, batch):
        (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        return loss, g

    def step(s: ParleState, batch):
        losses, grads = jax.vmap(replica_grad)(s.y, batch)
        if weight_decay:
            grads = jax.tree.map(lambda g, p: g + weight_decay * p,
                                 grads, s.y)
        lr_scale = lr_schedule(s.step) if lr_schedule is not None else 1.0
        if fused:
            s = fused_step(s, grads, cfg, use_kernel=use_kernel,
                           axis_name=axis_name, lr_scale=lr_scale,
                           shard_ctx=shard_ctx)
        else:
            s = inner_step(s, grads, cfg, use_kernel=use_kernel,
                           lr_scale=lr_scale, shard_ctx=shard_ctx)
        loss = jnp.mean(losses)
        if axis_name is not None:
            loss = jax.lax.pmean(loss, axis_name)
        return s, loss, losses

    return step


def _make_step_body(loss_fn: Callable, cfg, weight_decay: float,
                    use_kernel: bool, axis_name: str | None,
                    lr_schedule=None, shard_ctx=None):
    """Shared step body of the local and sharded train steps: the fused
    replica step and its metrics.

    Per-replica-loss metric contract: with ``axis_name`` set the leading
    axis inside this body holds only the LOCAL replicas, so the vector
    metric is emitted under the honest name ``local_loss_per_replica``
    (shape (n_local,)); the shard_map wrapper reassembles the global
    (n,) vector from its P(replica) out-spec and republishes it as
    ``loss_per_replica`` (see partition.make_sharded_step_fn), so the
    public metric always covers every replica.  The scalar ``loss`` is
    pmean'd to its global value by the replica step."""
    replica_step = make_replica_step(
        loss_fn, cfg, weight_decay=weight_decay, use_kernel=use_kernel,
        axis_name=axis_name, lr_schedule=lr_schedule, shard_ctx=shard_ctx,
        fused=True)
    loss_key = ("loss_per_replica" if axis_name is None
                else "local_loss_per_replica")

    def step(state: ParleState, batch):
        state, loss, losses = replica_step(state, batch)
        return state, {"loss": loss, loss_key: losses,
                       "gamma": state.scopes.gamma, "rho": state.scopes.rho,
                       "step": state.step}

    return step


def make_train_step(loss_fn: Callable, cfg, weight_decay: float = 0.0,
                    use_kernel: bool = False, lr_schedule=None):
    """loss_fn(params, batch) -> (scalar, aux).  Returns

        step(state, batch) -> (state, metrics)

    where ``batch`` leaves carry a leading replica axis of size n (each
    replica sees its own mini-batch — data-parallel *inside* a replica is
    handled by the mesh ``data`` axis at the sharding layer).
    """
    return _make_step_body(loss_fn, cfg, weight_decay, use_kernel,
                           axis_name=None, lr_schedule=lr_schedule)


def make_sharded_train_step(loss_fn: Callable, cfg, mesh,
                            replica_axis: str = "replica",
                            weight_decay: float = 0.0,
                            use_kernel: bool = False, lr_schedule=None):
    """Distributed variant of :func:`make_train_step`: the leading
    replica axis of ``ParleState`` (and of the batch) is sharded over
    the ``replica_axis`` of ``mesh`` via shard_map.

    Each device holds n/|replica_axis| replicas and runs the inner loop
    with ZERO cross-device traffic; the sync step's replica mean lowers
    to a single pmean all-reduce over ``replica_axis`` — the paper's
    O(2nN/L) amortized-communication property, in mesh terms.

    State and batch arrive as GLOBAL arrays (leading axis n); outputs
    keep the same layout, so checkpointing / ``average_model`` work
    unchanged.

    Mesh axes beyond ``replica_axis`` ("data"/"model") ride INSIDE each
    replica: the shard_map leaves them auto, and the sharding planner's
    constraints (FSDP over "data", TP over "model", per leaf) pin every
    state leaf to its shard — so the Eq. (8d) all-reduce carries only
    shard-size bytes per device, while weight all-gathers / partial-sum
    reductions stay intra-replica.
    """
    from jax.sharding import PartitionSpec as P

    from repro.sharding import planner
    from repro.sharding.partition import (make_sharded_step_fn,
                                          parle_state_pspecs)

    shard_ctx = planner.make_shard_context(mesh, replica_axis)
    constrain = None
    if shard_ctx is not None:
        def constrain(state):
            c = lambda t: planner.constrain_tree(t, mesh, lead=1)
            return state._replace(x=c(state.x), y=c(state.y), z=c(state.z),
                                  v_y=c(state.v_y), v_x=c(state.v_x),
                                  e=c(state.e) if state.e is not None
                                  else None)

    axis_name = _replica_axis_name(mesh, replica_axis)
    local_step = _make_step_body(loss_fn, cfg, weight_decay, use_kernel,
                                 axis_name=axis_name,
                                 lr_schedule=lr_schedule,
                                 shard_ctx=shard_ctx)
    loss_key = ("local_loss_per_replica" if axis_name is not None
                else "loss_per_replica")
    metric_specs = {"loss": P(), loss_key: P(replica_axis),
                    "gamma": P(), "rho": P(), "step": P()}
    return make_sharded_step_fn(local_step, mesh, replica_axis,
                                parle_state_pspecs(replica_axis, cfg=cfg),
                                metric_specs, cfg.n_replicas,
                                constrain=constrain)


def _replica_axis_name(mesh, replica_axis: str):
    """The collective axis of a replica-sharded body.  Each device holds
    n / |replica_axis| replicas on the leading axis.  A size-1 replica
    axis (entropy_sgd under FSDP x TP) carries ALL replicas locally: the
    leading-axis mean already is the global mean, and XLA rejects a
    cross-partition pmean over a trivial manual axis."""
    return replica_axis if mesh.shape[replica_axis] > 1 else None


def _round_metrics(state: ParleState, losses):
    return {"loss": jnp.mean(losses), "losses": losses,
            "gamma": state.scopes.gamma, "rho": state.scopes.rho,
            "step": state.step}


def _scan_steps(step, state: ParleState, batches):
    """The round's L inner steps as one ``lax.scan`` -> (state, losses
    (L,))."""
    def body(s, b):
        s, loss, _ = step(s, b)
        return s, loss

    return jax.lax.scan(body, state, batches)


def make_round_fn(loss_fn: Callable, cfg, *, mesh=None,
                  replica_axis: str = "replica", weight_decay: float = 0.0,
                  use_kernel: bool = False, lr_schedule=None):
    """One whole Parle round as compiled, state-DONATING code:
    round(state, batches) -> (state, metrics).  ``batches`` leaves are
    (L, n, B, ...); metrics are the scalar round-mean ``loss``, the
    per-step ``losses`` (L,), ``gamma``, ``rho`` and ``step``.  Python
    re-enters once per round instead of once per step, and no per-step
    ``k % L`` cond sits in the hot loop.

    The round scans the L = cfg.L inner steps (8a-8b; zero
    cross-replica traffic) and places the sync (8c-8d) by
    ``cfg.sync_overlap``: after the scan (barrier), or as
    :func:`overlap_head` before it (staleness-1; pair with
    :func:`make_flush_fn`).  The overlapped scan carry EXCLUDES ``c``
    and ``e``: the inner steps never read them, and keeping the
    collective's result out of the while loop's operands frees the
    latency-hiding scheduler to run the collective concurrently with
    the scan.

    Contract: the state's step counter is a multiple of L on entry
    (rounds tile the trajectory).  Then the barrier round is
    BIT-identical to L calls of the fused step (the per-step lr_scale is
    evaluated at the same counters and the sync fires at
    schedule(step - 1), as the cond'd path does), and R overlap rounds
    plus one flush are bit-identical to R barrier rounds.

    With a ``mesh`` the replica axis of state and batches is sharded
    over ``replica_axis``:

    * replica-only mesh: the round runs under a fully-manual shard_map,
      the sync pmean / compressed all_gather firing once per round; the
      result is bit-identical to the sharded step loop on the same mesh
      (local vs sharded differ by the all-reduce's summation order).
    * composed mesh (in-replica "data"/"model" axes): two programs, see
      :func:`_round_program`.

    Donation note: the input state's buffers are consumed.  A state
    fresh out of :func:`init` aliases x = y = z (one buffer); de-alias
    it once with :func:`dealias_state` before the first call.
    """
    return _round_program(loss_fn, cfg,
                          "overlap" if _sync_overlap(cfg) else "barrier",
                          mesh, replica_axis, weight_decay, use_kernel,
                          lr_schedule)


def _round_program(loss_fn: Callable, cfg, sync: str | None, mesh,
                   replica_axis: str, weight_decay: float, use_kernel: bool,
                   lr_schedule):
    """Builds every Parle round program, for :func:`make_round_fn` and
    :func:`make_inner_round_fn`.  ``sync`` places the sync: "barrier"
    (:func:`sync_step` after the scan), "overlap" (:func:`overlap_head`
    before it) or None (the scan alone; the async policy applies the
    consensus outside the program).

    A composed mesh splits the round into two compiled programs, called
    in the round's order: ``inner_jit``, the L inner steps as a
    pure-GSPMD scan over globally sharded state (they carry no
    cross-replica collective to lower manually), and ``sync_jit``, the
    sync or head under the partial-manual shard_map of the step loop,
    which keeps the explicit pmean / compressed gather on the wire.  The
    form dates from jax 0.4.37, where XLA's manual-subgroup check
    rejected a scan inside a partial-manual shard_map, and a scan and a
    partial-manual region in one module.  On jax 0.9.0 both forms
    compile and run on 4 CPU devices (replica:2,data:2 and
    replica:2,model:2, a two-layer Mamba2 and a toy model, with and
    without int8 compression), their x within 1.2e-7 of this form's after
    two rounds; the split stays because the benchmark lowers the two
    programs by name.  The GSPMD scan takes the XLA update and no
    ``shard_ctx`` whatever ``use_kernel`` says: the Pallas kernels run
    each leaf under a nested shard_map, a manual region that would put
    back into the scan what the split takes out.  GSPMD partitions the inner steps'
    reductions differently from the manual path, so composed rounds
    match the step loop to float tolerance, not bit-for-bit."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding import planner
    from repro.sharding.partition import parle_state_pspecs
    from repro.utils.compat import shard_map

    axis_name = shard_ctx = None
    if mesh is not None:
        n_dev = mesh.shape[replica_axis]
        if cfg.n_replicas % n_dev != 0:
            raise ValueError(
                f"n_replicas={cfg.n_replicas} not divisible by "
                f"mesh axis {replica_axis!r} of size {n_dev}")
        axis_name = _replica_axis_name(mesh, replica_axis)
        shard_ctx = planner.make_shard_context(mesh, replica_axis)
        specs = parle_state_pspecs(replica_axis, cfg=cfg)

    def sync_body(state):
        place = overlap_head if sync == "overlap" else sync_step
        return place(state, cfg, axis_name=axis_name, use_kernel=use_kernel,
                     lr_scale=_sync_scale(lr_schedule, state),
                     shard_ctx=shard_ctx)

    if shard_ctx is not None:
        gspmd_step = make_replica_step(loss_fn, cfg,
                                       weight_decay=weight_decay,
                                       lr_schedule=lr_schedule)

        def inner_scan(state, batches):
            return _scan_steps(gspmd_step, state, batches)

        inner_jit = jax.jit(inner_scan, donate_argnums=(0,))
        auto = frozenset(planner.in_replica_axes(mesh, replica_axis))
        sync_jit = jax.jit(shard_map(sync_body, mesh, in_specs=(specs,),
                                     out_specs=specs, auto=auto),
                           donate_argnums=(0,))

        def round_fn(state, batches):
            if sync == "overlap":
                state = sync_jit(state)
            state, losses = inner_jit(state, batches)
            if sync == "barrier":
                state = sync_jit(state)
            return state, _round_metrics(state, losses)

        return round_fn

    step = make_replica_step(loss_fn, cfg, weight_decay=weight_decay,
                             use_kernel=use_kernel, axis_name=axis_name,
                             lr_schedule=lr_schedule)

    def round_fn(state: ParleState, batches):
        if sync == "overlap":
            head = sync_body(state)     # c and e stay out of the carry
            state, losses = _scan_steps(
                step, head._replace(c=None, e=None), batches)
            state = state._replace(c=head.c, e=head.e)
        else:
            state, losses = _scan_steps(step, state, batches)
            if sync == "barrier":
                state = sync_body(state)
        return state, _round_metrics(state, losses)

    if mesh is not None:
        metric_specs = {"loss": P(), "losses": P(), "gamma": P(),
                        "rho": P(), "step": P()}
        round_fn = shard_map(round_fn, mesh,
                             in_specs=(specs, P(None, replica_axis)),
                             out_specs=(specs, metric_specs))
    return jax.jit(round_fn, donate_argnums=(0,))


# ------------------------------------------------------------------
# Asynchronous / elastic consensus (the runtime "async" sync policy):
# each worker runs rounds at its own pace, pushes its (optionally
# quantized) x+e contribution to a host-side coordinator when ITS round
# ends, and pulls back a staleness-weighted mean — no barrier.  The
# pieces here are the math halves; the wire/coordination halves live in
# repro/runtime/coordinator.py.
# ------------------------------------------------------------------

def staleness_weighted_mean(means, counts, rounds, decay=0.5):
    """The async Eq. (8d) reference: a staleness-weighted average of
    per-worker replica means.

    ``means``: one pytree (or flat list of arrays) per worker, each the
    mean of that worker's ``counts[a]`` replica contributions.
    ``rounds``: each worker's completed-round index; a worker that is
    ``r_max - r_a`` rounds behind the freshest contribution has its
    weight decayed by ``decay ** (r_max - r_a)``:

        w_a = counts[a] * decay ** (r_max - r_a)
        xbar = sum_a w_a * mean_a / sum_a w_a

    With every worker at the same round this reduces to the plain
    count-weighted mean — i.e. the barrier path's global replica mean —
    and a single worker's consensus is exactly its own mean (returned
    untouched, so no float round-trip perturbs the n=1 equivalence).
    Workers joining/leaving need no rebalancing constant: n only ever
    appears through the membership of ``means`` itself."""
    if not means:
        raise ValueError("staleness_weighted_mean of zero contributions")
    if len(means) == 1:
        return means[0]
    r_max = max(rounds)
    ws = [float(c) * float(decay) ** (r_max - r)
          for c, r in zip(counts, rounds)]
    tot = sum(ws)

    def leaf(*vals):
        acc = ws[0] * vals[0]
        for w, v in zip(ws[1:], vals[1:]):
            acc = acc + w * v
        return (acc / tot).astype(vals[0].dtype)

    return jax.tree.map(leaf, *means)


def contribution_norm(means) -> float:
    """L2 norm of a worker's dequantized contribution (flat per-leaf
    vectors), accumulated in float64 on host.  NaN/Inf anywhere in the
    contribution propagates into the result — the quarantine check
    keys off exactly that."""
    import numpy as np
    total = 0.0
    for v in means:
        a = np.asarray(v, np.float64).ravel()
        total += float(np.dot(a, a))
    return float(np.sqrt(total))


def should_quarantine(norm: float, trailing, k: float = 10.0,
                      min_history: int = 3):
    """Poisoned-update gate for :func:`staleness_weighted_mean` ingest:
    a contribution is quarantined when its norm is non-finite (NaN/Inf
    — one poisoned replica would otherwise contaminate the consensus
    for EVERY worker) or, once ``min_history`` accepted contributions
    established a trailing baseline, more than ``k``× the trailing
    median norm (a diverged-but-finite replica).  Returns
    ``(quarantine, reason)``; quarantined contributions never enter the
    trailing window, so one outlier cannot drag the baseline up."""
    import numpy as np
    if not np.isfinite(norm):
        return True, "nonfinite"
    hist = list(trailing)
    if len(hist) >= min_history:
        med = float(np.median(np.asarray(hist, np.float64)))
        if med > 0.0 and norm > k * med:
            return True, (f"norm {norm:.3e} exceeds {k:g}x trailing "
                          f"median {med:.3e}")
    return False, ""


def reseed_from_consensus(state: ParleState, xbar) -> ParleState:
    """Recovery for a quarantined worker: restart every local replica
    FROM the consensus — x = y = z = xbar (broadcast over the replica
    axis), momenta and the error-feedback residual zeroed, ``step``
    and scopes kept so the annealing schedule is undisturbed.  Each
    field gets its own freshly materialized buffers (broadcast views
    would alias x/y/z into one buffer, which a donating round fn
    rejects)."""

    def bcast(leaf, like, dtype):
        return jnp.array(jnp.broadcast_to(
            jnp.asarray(leaf, jnp.float32), like.shape), dtype=dtype)

    x = jax.tree.map(lambda v, l: bcast(v, l, jnp.float32), xbar, state.x)
    y = jax.tree.map(lambda v, l: bcast(v, l, l.dtype), xbar, state.y)
    z = jax.tree.map(lambda v, l: bcast(v, l, jnp.float32), xbar, state.z)
    return state._replace(
        x=x, y=y, z=z,
        v_y=tree_zeros_like(x), v_x=tree_zeros_like(x),
        e=tree_zeros_like(x) if state.e is not None else None)


def make_inner_round_fn(loss_fn: Callable, cfg, weight_decay: float = 0.0,
                        use_kernel: bool = False, lr_schedule=None):
    """The async round's compute half: ONE donated compiled program
    scanning the L = cfg.L inner steps (8a-8b) with NO sync — the worker
    then pushes :func:`async_contribution` to the coordinator and applies
    the consensus it gets back via :func:`make_async_apply_fn`.  Same
    entry invariants and metric contract as :func:`make_round_fn`;
    because ``x`` only changes at the consensus apply, the pushed payload
    is identical whether it is snapshotted before or after the scan."""
    return _round_program(loss_fn, cfg, None, None, "replica", weight_decay,
                          use_kernel, lr_schedule)


def async_contribution(state: ParleState, cfg):
    """The async worker's push payload: each LOCAL replica's sync
    contribution ``c_a = x_a + e_a``, flattened per leaf and quantized
    per ``cfg.sync_compress`` — the same per-replica compression as the
    barrier sync, so the coordinator's dequantized mean matches
    :func:`_sync_stats` semantics (and the wire carries the quantized
    bytes, not f32).

    Returns ``(payload, e_new)``: ``payload`` is a list in
    ``tree_flatten(state.x)`` leaf order of ``{"q": (r, M) ndarray,
    "scales": ndarray | None}`` host arrays (M padded to the codec chunk
    for bf16/int8, unpadded f32 for "none"); ``e_new`` is the refreshed
    error-feedback tree (None when compression is off).  The coordinator
    never needs the model's tree structure — it works on the flat
    vectors, and the worker reshapes the consensus back via
    :func:`consensus_from_flat`."""
    import numpy as np
    method = _sync_compress(cfg)
    flat, treedef = jax.tree_util.tree_flatten(state.x)
    flat_e = (treedef.flatten_up_to(state.e) if state.e is not None
              else [None] * len(flat))
    payload, e_news = [], []
    for xl, el in zip(flat, flat_e):
        r, shape, m = xl.shape[0], xl.shape, xl[0].size
        c = xl.astype(jnp.float32).reshape(r, -1)
        if el is not None:
            c = c + el.reshape(r, -1)
        if method == "none":
            payload.append({"q": np.asarray(c), "scales": None})
            e_news.append(el)
            continue
        cpad = compress.pad_to_chunk(c)
        q, s, res = compress.quantize_ef(cpad, method)
        payload.append({"q": np.asarray(q),
                        "scales": None if s is None else np.asarray(s)})
        e_news.append(res[:, :m].reshape(shape))
    e_new = (jax.tree_util.tree_unflatten(treedef, e_news)
             if state.e is not None else None)
    return payload, e_new


def consensus_from_flat(vectors, like):
    """Rebuild a model-shaped xbar tree from the coordinator's flat
    consensus vectors (one per leaf of ``like``'s x, in tree_flatten
    order; each may carry codec padding past the leaf's true size)."""
    flat, treedef = jax.tree_util.tree_flatten(like)
    leaves = [jnp.asarray(v[: l[0].size], jnp.float32).reshape(l.shape[1:])
              for v, l in zip(vectors, flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def make_async_apply_fn(cfg, lr_schedule=None):
    """jitted ``apply(state, xbar) -> state``: the Eq. (8c)-(8d)
    consensus update against a coordinator-supplied staleness-weighted
    mean, at the same outer-lr scale the barrier sync would have used
    (schedule(step - 1)).  ``e`` passes through — the caller installs
    the refreshed residual from :func:`async_contribution` first."""

    @jax.named_scope("parle_sync")
    def apply(state, xbar):
        return consensus_step(state, xbar, cfg,
                              lr_scale=_sync_scale(lr_schedule, state))

    return jax.jit(apply, donate_argnums=(0,))


def dealias_state(state):
    """Copy every array leaf of a state into a fresh buffer, so the
    state is safe to hand to a DONATING round fn: ``init`` aliases
    x = y = z to one buffer (donation rejects duplicates), and some
    states alias buffers the caller still holds (Elastic-SGD's ``ref``
    IS the caller's params tree — donating it would delete the caller's
    arrays).  One full copy, once, before the training loop; shardings
    are preserved."""
    return jax.tree.map(
        lambda l: jnp.array(l, copy=True) if hasattr(l, "devices") else l,
        state)


def average_model(state: ParleState):
    """The deployable single model: mean of replicas (what the paper
    evaluates after scoping collapses the ensemble)."""
    return tree_mean_axis0(state.x)


def replica_model(state: ParleState, a: int):
    return jax.tree.map(lambda v: v[a], state.x)
