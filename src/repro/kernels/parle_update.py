"""Fused Parle updates (Eq. 8a-8b inner, Eq. 8c-8d sync) as Pallas TPU
kernels.

Why kernels: both steps are purely memory-bound elementwise updates over
model-sized streams.  The inner step touches five N-sized streams (y, z,
v_y, grad, x^a) and writes three; left to XLA as separate HLO ops this
is ~7 HBM round-trips of N each; fused it is exactly 5 reads + 3 writes.
The sync step (fired once every L steps, right after the one all-reduce
produces xbar) reads four streams (x, z, v_x, xbar) and writes two
(x', v_x') instead of the ~6 round-trips XLA emits for Eq. 8c-8d.

TPU mapping: each leaf is viewed as (rows, cols) — its leading dims
collapsed onto its last one, a free reshape of the TPU's tiled layout —
and tiled into (8, 1024)-shaped VMEM blocks (a dim shorter than that is
taken whole; a dim's last block may run past its end, where Pallas masks
the writes).  Flattening a leaf to 1024-wide rows instead would relayout
(copy) every stream whose last dim is not a multiple of 1024, e.g. a
50280-wide head.  The updated streams alias their inputs, and scalars
ride in SMEM via scalar prefetch.

Oracles: kernels/ref.py::parle_inner_update / parle_sync_update.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# (sublane, lane)-aligned tile: 8 x 1024 f32 = 32 KiB per stream;
# 8 streams resident => ~256 KiB of VMEM per program instance.
BLOCK = (8, 1024)
BLOCK_ELEMS = BLOCK[0] * BLOCK[1]


def _view(a, lead: int = 0):
    """``a`` as (*a.shape[:lead], rows, cols): the dims after ``lead``
    collapsed onto their last one."""
    s = a.shape[lead:]
    cols = s[-1] if s else 1
    return a.reshape(*a.shape[:lead], math.prod(s[:-1]), cols)


def _tiling(rows: int, cols: int):
    """Block and grid for a (rows, cols) view."""
    block = (min(BLOCK[0], rows), min(BLOCK[1], cols))
    return block, (pl.cdiv(rows, block[0]), pl.cdiv(cols, block[1]))


def _kernel(scal_ref, y_ref, z_ref, v_ref, g_ref, x_ref,
            y_out, z_out, v_out):
    inv_gamma = scal_ref[0]
    lr = scal_ref[1]
    mu = scal_ref[2]
    alpha = scal_ref[3]
    # mixed precision: y/g may arrive bf16 — upcast on read, accumulate
    # in f32, downcast only the y output.  The casts live INSIDE the
    # kernel so no separate model-size cast pass ever materializes.
    y = y_ref[...].astype(jnp.float32)
    x = x_ref[...]
    g_y = g_ref[...].astype(jnp.float32) + inv_gamma * (y - x)
    v_new = mu * v_ref[...] + g_y
    y_new = y - lr * (g_y + mu * v_new)
    z_new = alpha * z_ref[...] + (1.0 - alpha) * y_new
    y_out[...] = y_new.astype(y_out.dtype)
    z_out[...] = z_new
    v_out[...] = v_new


@functools.partial(jax.jit, static_argnames=("interpret",))
def parle_update_leaf(y, z, v, g, x, scalars, interpret: bool):
    """One leaf's inner update; all operands share one shape.  z, v, x
    are f32 masters, y and g carry the compute dtype (f32 or bf16).
    scalars: (4,) f32 = [inv_gamma, lr, mu, alpha]."""
    rows, cols = _view(y).shape
    block, grid = _tiling(rows, cols)
    # index maps under PrefetchScalarGridSpec also receive the scalar ref
    spec = pl.BlockSpec(block, lambda i, j, _s: (i, j))
    out_shape = [jax.ShapeDtypeStruct((rows, cols), a.dtype)
                 for a in (y, z, v)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[spec] * 5,
        out_specs=[spec] * 3,
    )
    outs = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases={1: 0, 2: 1, 3: 2},     # y, z, v in place
        interpret=interpret,
    )(scalars, *[_view(a) for a in (y, z, v, g, x)])
    return tuple(o.reshape(y.shape) for o in outs)


def _pack_scalars(*vals):
    return jnp.stack([jnp.asarray(s, jnp.float32) for s in vals])


def _local_shard_wrap(call, shard_ctx, path, rep_shapes, shared_shape,
                      num_out):
    """Wrap a per-leaf kernel call in a nested shard_map over the
    in-replica mesh axes (planner :class:`ShardContext`), so the kernel's
    block grid covers only the LOCAL shard of the leaf.

    Inside the algorithm's outer shard_map the replica axis is already
    manual and the "data"/"model" axes are auto: this nested shard_map
    makes them manual too for exactly the (elementwise) update, handing
    the kernel local blocks.  ``call(scalars, *leaves)`` takes the
    scalars as its first (replicated) operand, never as a closure: a
    value captured from the outer body lives on the outer body's mesh
    and the nested body rejects it.  ``rep_shapes`` leaves carry a leading
    (local-)replica dim that stays unsharded; the optional
    ``shared_shape`` operand (xbar / elastic ref) has no replica dim.
    """
    from jax.sharding import PartitionSpec as P

    from repro.sharding.planner import path_names
    from repro.utils.compat import shard_map

    spec = shard_ctx.leaf_spec(path_names(path), rep_shapes[0][1:])
    rep_spec = P(None, *spec)
    in_specs = (P(),) + (rep_spec,) * len(rep_shapes)
    if shared_shape is not None:
        in_specs = in_specs + (spec,)
    return shard_map(call, shard_ctx.mesh, in_specs=in_specs,
                     out_specs=(rep_spec,) * num_out)


def _leafwise(leaf_fn, trees, scalars, num_out, interpret, shard_ctx=None):
    """Apply a per-leaf fused kernel leafwise over pytrees.  With a
    planner ``shard_ctx`` each leaf's call runs under a nested shard_map
    over the in-replica axes (block grid over the local shard).  Leaf
    dtypes pass through untouched — the kernels handle mixed precision
    (bf16 compute streams next to f32 masters) internally."""
    flat0, treedef = jax.tree_util.tree_flatten_with_path(trees[0])
    leaves = [[l for _, l in flat0]] \
        + [treedef.flatten_up_to(t) for t in trees[1:]]
    outs = [[] for _ in range(num_out)]
    for (path, _), *leaf_group in zip(flat0, *leaves):
        call = lambda sc, *g: leaf_fn(*g, sc, interpret=interpret)
        if shard_ctx is not None:
            call = _local_shard_wrap(
                call, shard_ctx, path,
                [l.shape for l in leaf_group], None, num_out)
        res = call(scalars, *leaf_group)
        for acc, r in zip(outs, res):
            acc.append(r)
    un = jax.tree_util.tree_unflatten
    return tuple(un(treedef, o) for o in outs)


def parle_update_tree(y, z, v, g, x, *, inv_gamma, lr, mu, alpha,
                      interpret: bool, shard_ctx=None):
    """Fused inner update (8a-8b) leafwise over pytrees."""
    scalars = _pack_scalars(inv_gamma, lr, mu, alpha)
    return _leafwise(parle_update_leaf, (y, z, v, g, x), scalars,
                     num_out=3, interpret=interpret, shard_ctx=shard_ctx)


# ------------------------------------------------------------------
# Sync step (8c)-(8d): x, v_x update applied right after the all-reduce
# ------------------------------------------------------------------

def _sync_kernel(scal_ref, x_ref, z_ref, v_ref, xbar_ref, x_out, v_out,
                 *maybe_y_out):
    gamma_scale = scal_ref[0]
    inv_rho = scal_ref[1]
    lr = scal_ref[2]
    mu = scal_ref[3]
    x = x_ref[0]                       # (8, 1024); replica dim blocked at 1
    g_x = gamma_scale * (x - z_ref[0]) + inv_rho * (x - xbar_ref[...])
    v_new = mu * v_ref[0] + g_x
    x_new = x - lr * (g_x + mu * v_new)
    x_out[0] = x_new
    v_out[0] = v_new
    if maybe_y_out:                    # fused y' = cast(x') (bf16 path)
        maybe_y_out[0][0] = x_new.astype(maybe_y_out[0].dtype)


def _replicated_call(kernel, reps, shared, scalars, out_dtypes, aliases,
                     interpret):
    """Run ``kernel`` over (R, *s) replica streams plus one shared s-shaped
    stream, which stays at size M and is re-read per replica grid step —
    never materialized at R*M.  Outputs are (R, *s) in ``out_dtypes``."""
    r = reps[0].shape[0]
    _, rows, cols = _view(reps[0], 1).shape
    block, grid = _tiling(rows, cols)
    spec = pl.BlockSpec((1,) + block, lambda a, i, j, _s: (a, i, j))
    shared_spec = pl.BlockSpec(block, lambda a, i, j, _s: (i, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r,) + grid,
        in_specs=[spec] * len(reps) + [shared_spec],
        out_specs=[spec] * len(out_dtypes),
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((r, rows, cols), d)
                   for d in out_dtypes],
        input_output_aliases=aliases,
        interpret=interpret,
    )(scalars, *[_view(a, 1) for a in reps], _view(shared))
    return tuple(o.reshape(reps[0].shape) for o in outs)


@functools.partial(jax.jit, static_argnames=("interpret", "y_dtype"))
def parle_sync_leaf(x, z, v, xbar, scalars, interpret: bool,
                    y_dtype=None):
    """x, z, v: (R, *s) f32; xbar: s f32, the (already all-reduced)
    replica mean, so the sync's HBM budget is 3 R*M + M reads and
    2 R*M writes.  scalars: (4,) f32 = [gamma_scale, inv_rho, lr, mu].

    ``y_dtype``: when given and different from x's dtype, the kernel
    also emits the inner-loop reset ``y' = cast(x')`` as a third output
    — the mixed-precision compute copy, cast fused into the same pass.
    Returns (x', v') or (x', v', y').
    """
    emit_y = y_dtype is not None and jnp.dtype(y_dtype) != x.dtype
    out_dtypes = [x.dtype, v.dtype] + ([jnp.dtype(y_dtype)] if emit_y else [])
    return _replicated_call(_sync_kernel, (x, z, v), xbar, scalars,
                            out_dtypes, {1: 0, 3: 1}, interpret)


def _replicated_shared_tree(leaf_fn, rep_trees, shared_tree, scalars,
                            interpret, num_out: int = 2, shard_ctx=None,
                            **kw):
    """Shared leafwise driver for the (R, ...)-streams + one shared
    (...)-stream kernels (sync: xbar; elastic: ref).  With a planner
    ``shard_ctx`` each leaf runs under a nested shard_map over the
    in-replica axes: the kernel grids over the LOCAL shard and the
    shared stream stays at local-shard size too (sharded exactly like
    the replica streams' trailing dims)."""
    flat0, treedef = jax.tree_util.tree_flatten_with_path(rep_trees[0])
    rep_leaves = [[l for _, l in flat0]] \
        + [treedef.flatten_up_to(t) for t in rep_trees[1:]]
    shared_leaves = treedef.flatten_up_to(shared_tree)
    outs = [[] for _ in range(num_out)]
    for (path, _), *group in zip(flat0, *rep_leaves, shared_leaves):
        *reps, shared = group
        call = lambda sc, *rs: leaf_fn(*rs, sc, interpret=interpret, **kw)
        if shard_ctx is not None:
            call = _local_shard_wrap(
                call, shard_ctx, path, [l.shape for l in reps],
                shared.shape, num_out=num_out)
        res = call(scalars, *reps, shared)
        for acc, o in zip(outs, res):
            acc.append(o)
    un = jax.tree_util.tree_unflatten
    return tuple(un(treedef, o) for o in outs)


def parle_sync_tree(x, z, v, xbar, *, gamma_scale, inv_rho, lr, mu,
                    interpret: bool, shard_ctx=None, y_dtype=None):
    """Fused sync update (8c-8d) leafwise over pytrees.

    x, z, v leaves carry the leading replica axis (R, ...); xbar leaves
    are the UN-broadcast replica mean of shape (...) — one copy shared
    by all R replicas.  With a bf16 ``y_dtype`` the kernel also emits
    the fused compute copy y' = cast(x') (third tree); returns
    (x', v') otherwise.
    """
    scalars = _pack_scalars(gamma_scale, inv_rho, lr, mu)
    emit_y = y_dtype is not None and jnp.dtype(y_dtype) != jnp.float32
    return _replicated_shared_tree(parle_sync_leaf, (x, z, v), xbar,
                                   scalars, interpret,
                                   num_out=3 if emit_y else 2,
                                   shard_ctx=shard_ctx,
                                   y_dtype=y_dtype if emit_y else None)


# ------------------------------------------------------------------
# Elastic-SGD worker step (7a): same block machinery as the sync step —
# per-replica streams plus ONE shared model-size stream (the reference
# variable, analogous to xbar) re-read per replica grid step.
# ------------------------------------------------------------------

def _elastic_kernel(scal_ref, x_ref, v_ref, g_ref, ref_ref, x_out, v_out):
    inv_rho = scal_ref[0]
    lr = scal_ref[1]
    mu = scal_ref[2]
    x = x_ref[0]                       # (8, 1024); replica dim blocked at 1
    # g may be the bf16 compute grad — upcast on read (fused cast)
    g_e = g_ref[0].astype(jnp.float32) + inv_rho * (x - ref_ref[...])
    v_new = mu * v_ref[0] + g_e
    x_out[0] = x - lr * (g_e + mu * v_new)
    v_out[0] = v_new


@functools.partial(jax.jit, static_argnames=("interpret",))
def elastic_update_leaf(x, v, g, ref, scalars, interpret: bool):
    """x, v, g: (R, *s) f32; ref: s f32, the shared reference variable,
    so the worker step's HBM budget is 3 R*M + M reads and 2 R*M writes.
    scalars: (3,) f32 = [inv_rho, lr, mu]."""
    return _replicated_call(_elastic_kernel, (x, v, g), ref, scalars,
                            [x.dtype, v.dtype], {1: 0, 2: 1}, interpret)


def elastic_update_tree(x, v, g, ref, *, inv_rho, lr, mu,
                        interpret: bool, shard_ctx=None):
    """Fused Elastic-SGD worker update (7a) leafwise over pytrees.

    x, v, g leaves carry the leading replica axis (R, ...); ref leaves
    are the UN-broadcast reference variable of shape (...).
    """
    scalars = _pack_scalars(inv_rho, lr, mu)
    return _replicated_shared_tree(elastic_update_leaf, (x, v, g), ref,
                                   scalars, interpret, shard_ctx=shard_ctx)


# ------------------------------------------------------------------
# Compressed sync (Eq. 8d payload): fused quantize+error-feedback and
# dequantize+mean+update kernels.  Chunk layout matches
# core/compress.py exactly (CHUNK = the 1024 lane dim, streams padded
# to BLOCK_ELEMS), so kernel and jnp reference produce bit-identical
# payloads; oracles in kernels/ref.py.
# ------------------------------------------------------------------

def _quant_ef_kernel(c_ref, q_out, s_out, e_out):
    """Per block (1, 8, 1024): one int8 payload row + one f32 scale per
    1024-chunk + the error-feedback residual, in a single pass (1 read,
    ~1.25 writes of the stream)."""
    c = c_ref[0]                             # (8, 1024) f32
    amax = jnp.max(jnp.abs(c), axis=-1, keepdims=True)      # (8, 1)
    scale = jnp.where(amax == 0, 1.0, amax * (1.0 / 127.0))
    q = jnp.clip(jnp.round(c / scale), -127, 127)
    deq = q * scale
    q_out[0] = q.astype(jnp.int8)
    s_out[0] = scale
    e_out[0] = c - deq


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_ef_flat(c, interpret: bool):
    """c: (R, M) f32 with M % BLOCK_ELEMS == 0.  Returns (q, scales, e):
    q (R, M) int8, scales (R, M // 1024) f32, e = c - dequant(q) f32.

    Inside the kernel the scales are an (R, rows, 1) column, one (8, 1)
    block per grid step: the TPU tiles the last two block dims in
    (8, 128) units or takes them whole."""
    r, m = c.shape
    rows = m // BLOCK[1]
    grid = (r, rows // BLOCK[0])
    spec = pl.BlockSpec((1,) + BLOCK, lambda a, i: (a, i, 0))
    s_spec = pl.BlockSpec((1, BLOCK[0], 1), lambda a, i: (a, i, 0))
    out_shape = [
        jax.ShapeDtypeStruct((r, rows, BLOCK[1]), jnp.int8),
        jax.ShapeDtypeStruct((r, rows, 1), jnp.float32),
        jax.ShapeDtypeStruct((r, rows, BLOCK[1]), jnp.float32),
    ]
    q, s, e = pl.pallas_call(
        _quant_ef_kernel,
        grid=grid,
        in_specs=[spec],
        out_specs=[spec, s_spec, spec],
        out_shape=out_shape,
        name="quantize_ef",
        interpret=interpret,
    )(c.reshape(r, rows, BLOCK[1]))
    return q.reshape(r, m), s.reshape(r, rows), e.reshape(r, m)


def _dequant_sync_kernel(scal_ref, x_ref, z_ref, v_ref, q_ref, s_ref,
                         x_out, v_out, *maybe_y_out):
    """Sync update with the replica mean reconstructed INSIDE the kernel
    from the gathered quantized payloads: dequantize (n, 8, 1024) int8
    blocks with their per-chunk scales, mean over n, then Eq. 8c-8d —
    xbar never round-trips HBM as f32."""
    gamma_scale = scal_ref[0]
    inv_rho = scal_ref[1]
    lr = scal_ref[2]
    mu = scal_ref[3]
    deq = q_ref[...].astype(jnp.float32) * s_ref[...]   # (n, 8, 1) scales
    xbar = jnp.mean(deq, axis=0)             # (8, 1024)
    x = x_ref[0]
    g_x = gamma_scale * (x - z_ref[0]) + inv_rho * (x - xbar)
    v_new = mu * v_ref[0] + g_x
    x_new = x - lr * (g_x + mu * v_new)
    x_out[0] = x_new
    v_out[0] = v_new
    if maybe_y_out:                          # fused y' = cast(x')
        maybe_y_out[0][0] = x_new.astype(maybe_y_out[0].dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "y_dtype"))
def parle_sync_dequant_flat(x, z, v, q, s, scalars, interpret: bool,
                            y_dtype=None):
    """Fused dequantize + replica-mean + sync update.

    x, z, v: (R, M) f32 (R = local replicas); q: (n, M) int8 — the
    all-gathered per-replica payloads of ALL n global replicas; s:
    (n, M // 1024) f32 per-chunk scales; scalars as parle_sync_leaf.
    Returns (x', v') or (x', v', y') like :func:`parle_sync_leaf`.
    """
    r, m = x.shape
    n = q.shape[0]
    rows = m // BLOCK[1]
    grid = (r, rows // BLOCK[0])
    shaped = lambda a: a.reshape(r, rows, BLOCK[1])
    spec = pl.BlockSpec((1,) + BLOCK, lambda a, i, _s: (a, i, 0))
    q_spec = pl.BlockSpec((n,) + BLOCK, lambda a, i, _s: (0, i, 0))
    s_spec = pl.BlockSpec((n, BLOCK[0], 1), lambda a, i, _s: (0, i, 0))
    emit_y = y_dtype is not None and jnp.dtype(y_dtype) != x.dtype
    out_dtypes = [x.dtype, v.dtype] + ([jnp.dtype(y_dtype)] if emit_y else [])
    out_shape = [jax.ShapeDtypeStruct((r, rows, BLOCK[1]), d)
                 for d in out_dtypes]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[spec] * 3 + [q_spec, s_spec],
        out_specs=[spec] * len(out_shape),
    )
    outs = pl.pallas_call(
        _dequant_sync_kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        name="dequant_sync",
        interpret=interpret,
    )(scalars, shaped(x), shaped(z), shaped(v),
      q.reshape(n, rows, BLOCK[1]), s.reshape(n, rows, 1))
    return tuple(o.reshape(r, m) for o in outs)


def _apply_quant_kernel(scal_ref, x_ref, z_ref, v_ref, c_ref, e_ref,
                        x_out, v_out, q_out, s_out, e_out, *maybe_y_out):
    """Staleness-1 overlap head, one pass: apply the CARRIED consensus
    (Eq. 8c-8d with the stale mean c) and immediately quantize the new
    x + e as the NEXT sync's int8 payload with error feedback — the
    overlap counterpart of _dequant_sync_kernel (which fuses the other
    end of the pipe).  5 reads + ~4.25 writes of the stream instead of
    the two separate kernels' 7 reads + ~5.25 writes."""
    gamma_scale = scal_ref[0]
    inv_rho = scal_ref[1]
    lr = scal_ref[2]
    mu = scal_ref[3]
    x = x_ref[0]                       # (8, 1024); replica dim blocked at 1
    g_x = gamma_scale * (x - z_ref[0]) + inv_rho * (x - c_ref[...])
    v_new = mu * v_ref[0] + g_x
    x_new = x - lr * (g_x + mu * v_new)
    ctot = x_new + e_ref[0]            # next payload, error fed back
    amax = jnp.max(jnp.abs(ctot), axis=-1, keepdims=True)   # (8, 1)
    scale = jnp.where(amax == 0, 1.0, amax * (1.0 / 127.0))
    q = jnp.clip(jnp.round(ctot / scale), -127, 127)
    x_out[0] = x_new
    v_out[0] = v_new
    q_out[0] = q.astype(jnp.int8)
    s_out[0] = scale
    e_out[0] = ctot - q * scale
    if maybe_y_out:                    # fused y' = cast(x') (bf16 path)
        maybe_y_out[0][0] = x_new.astype(maybe_y_out[0].dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "y_dtype"))
def parle_apply_quantize_flat(x, z, v, c, e, scalars, interpret: bool,
                              y_dtype=None):
    """x, z, v, e: (R, M) f32; c: (M,) f32 with M % BLOCK_ELEMS == 0 —
    the carried staleness-1 consensus, re-read per replica grid step
    like xbar in parle_sync_leaf; scalars: (4,) f32 =
    [gamma_scale, inv_rho, lr, mu].

    Returns (x', v', q, s, e') or (x', v', q, s, e', y'): the applied
    iterates plus the next sync's quantized payload — q (R, M) int8,
    s (R, M // 1024) f32 per-chunk scales, e' the error-feedback
    residual.  Chunking matches core/compress.py exactly, so payloads
    are bit-identical to the jnp codec's."""
    r, m = x.shape
    rows = m // BLOCK[1]
    grid = (r, rows // BLOCK[0])
    shaped = lambda a: a.reshape(r, rows, BLOCK[1])
    spec = pl.BlockSpec((1,) + BLOCK, lambda a, i, _s: (a, i, 0))
    bar_spec = pl.BlockSpec(BLOCK, lambda a, i, _s: (i, 0))
    s_spec = pl.BlockSpec((1, BLOCK[0], 1), lambda a, i, _s: (a, i, 0))
    emit_y = y_dtype is not None and jnp.dtype(y_dtype) != x.dtype
    out_shape = [
        jax.ShapeDtypeStruct((r, rows, BLOCK[1]), x.dtype),
        jax.ShapeDtypeStruct((r, rows, BLOCK[1]), v.dtype),
        jax.ShapeDtypeStruct((r, rows, BLOCK[1]), jnp.int8),
        jax.ShapeDtypeStruct((r, rows, 1), jnp.float32),
        jax.ShapeDtypeStruct((r, rows, BLOCK[1]), jnp.float32),
    ] + ([jax.ShapeDtypeStruct((r, rows, BLOCK[1]), jnp.dtype(y_dtype))]
         if emit_y else [])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[spec] * 3 + [bar_spec, spec],
        out_specs=[spec] * 3 + [s_spec, spec] + ([spec] if emit_y else []),
    )
    outs = pl.pallas_call(
        _apply_quant_kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        name="apply_quantize",
        interpret=interpret,
    )(scalars, shaped(x), shaped(z), shaped(v),
      c.reshape(rows, BLOCK[1]), shaped(e))
    x2, v2, q, s, e2, *ys = outs
    flat = lambda a: a.reshape(r, m)
    res = (flat(x2), flat(v2), flat(q), s.reshape(r, rows), flat(e2))
    return res + (flat(ys[0]),) if ys else res


def parle_apply_quantize_tree(x, z, v, c, e, *, gamma_scale, inv_rho, lr,
                              mu, interpret: bool, y_dtype=None):
    """Fused overlap head leafwise over pytrees: x, z, v, e leaves carry
    the leading replica axis (R, ...); c leaves are the UN-broadcast
    carried consensus of shape (...).  Iterate outputs are cut back to
    leaf shape; the payload outputs q (R, Mpad) int8 / s (R, Mpad//1024)
    f32 stay FLAT (padded like core/compress.pad_to_chunk) — that is the
    wire format the gather ships.  Returns (x', v', q, s, e') or
    (x', v', q, s, e', y')."""
    scalars = _pack_scalars(gamma_scale, inv_rho, lr, mu)
    emit_y = y_dtype is not None and jnp.dtype(y_dtype) != jnp.float32
    flat0, treedef = jax.tree_util.tree_flatten(x)
    fz = treedef.flatten_up_to(z)
    fv = treedef.flatten_up_to(v)
    fc = treedef.flatten_up_to(c)
    fe = treedef.flatten_up_to(e)
    num_out = 6 if emit_y else 5
    outs = [[] for _ in range(num_out)]
    for xl, zl, vl, cl, el in zip(flat0, fz, fv, fc, fe):
        r, shape, size = xl.shape[0], xl.shape, xl[0].size
        pad = (-size) % BLOCK_ELEMS
        fl = lambda a: jnp.pad(a.reshape(r, -1), ((0, 0), (0, pad)))
        res = parle_apply_quantize_flat(
            fl(xl), fl(zl), fl(vl), jnp.pad(cl.reshape(-1), (0, pad)),
            fl(el), scalars, interpret=interpret,
            y_dtype=y_dtype if emit_y else None)
        x2, v2, q, s, e2, *ys = res
        cut = lambda a: a[:, :size].reshape(shape)
        vals = [cut(x2), cut(v2), q, s, cut(e2)] \
            + ([cut(ys[0])] if ys else [])
        for acc, o in zip(outs, vals):
            acc.append(o)
    un = jax.tree_util.tree_unflatten
    return tuple(un(treedef, o) for o in outs)


def parle_sync_dequant_tree(x, z, v, q_tree, s_tree, *, gamma_scale,
                            inv_rho, lr, mu, interpret: bool,
                            y_dtype=None):
    """Fused dequantize+mean+sync-update leafwise over pytrees.

    x, z, v leaves carry the leading (local-)replica axis (R, ...);
    q_tree/s_tree leaves are the all-gathered FLAT payloads (n, Mpad)
    int8 / (n, Mpad // 1024) f32 produced by the quantize side (Mpad =
    the leaf's per-replica size padded to the block multiple)."""
    scalars = _pack_scalars(gamma_scale, inv_rho, lr, mu)
    emit_y = y_dtype is not None and jnp.dtype(y_dtype) != jnp.float32
    flat0, treedef = jax.tree_util.tree_flatten(x)
    flat_z = treedef.flatten_up_to(z)
    flat_v = treedef.flatten_up_to(v)
    flat_q = treedef.flatten_up_to(q_tree)
    flat_s = treedef.flatten_up_to(s_tree)
    num_out = 3 if emit_y else 2
    outs = [[] for _ in range(num_out)]
    for xl, zl, vl, ql, sl in zip(flat0, flat_z, flat_v, flat_q, flat_s):
        r, shape, size = xl.shape[0], xl.shape, xl[0].size
        mpad = ql.shape[1]
        fl = lambda a: jnp.pad(a.reshape(r, -1), ((0, 0), (0, mpad - size)))
        res = parle_sync_dequant_flat(
            fl(xl), fl(zl), fl(vl), ql, sl, scalars, interpret=interpret,
            y_dtype=y_dtype if emit_y else None)
        for acc, o in zip(outs, res):
            acc.append(o[:, :size].reshape(shape))
    un = jax.tree_util.tree_unflatten
    return tuple(un(treedef, o) for o in outs)
