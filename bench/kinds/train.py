"""Training cells: Parle rounds through the trainer's own objects.

Set-up builds what ``launch/train.py::run`` builds (its argument parser,
``registry.get``, ``resolve_train_policy``, ``policy.make_round_fn``,
``make_round_batch_fn``, ``RoundRunner``), with the weights and the Parle
state made on the device in one jitted call from the seed, and the round
compiled ahead of time.  It then drives that one round program through
``RoundRunner.run_rounds`` for the cell's first rounds: they are the
warm-up and what the reference is compared with.  The window is one more
``run_rounds`` call of as many whole rounds as fill ``--seconds``, whose
losses are read ``AHEAD_S`` seconds of rounds late (``_LateReader``), so
that the device has that much work queued while the host waits and a
host stall shorter than it costs no device time.

A traffic with ``--mesh`` runs over the trainer's mesh as
``launch/train.py`` does: the mesh from the flag, the state made on its
planner shardings (``algo.state_pspecs``), the round built for the mesh
(``make_round_fn(..., mesh=, replica_axis=)``).  The mesh covers the
cell's chips exactly.  A replica-only mesh gives one round program,
compiled ahead of time as on one chip; a mesh with axes inside a replica
gives a round of two programs (``core/parle.py::make_sharded_round_fn``),
which the first warm-up round compiles.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import math
import time

from bench import harness
from bench import trace as bench_trace
from bench.reference import parle as ref_parle
from bench.traffic import tokens as token_rows


def program_config(conf: dict):
    """The registered configuration with the keys the file lists under
    ``reduced`` changed; every other size in the file must be the
    registered one."""
    from repro.configs import get_config
    base = get_config(conf["registered"])
    model = conf["model"]
    for k, v in model.items():
        if k not in conf["reduced"] and getattr(base, k) != v:
            raise SystemExit(f"{conf['name']}: {k}={v} differs from the "
                             f"registered {getattr(base, k)} and is not "
                             f"listed under reduced")
    return dataclasses.replace(base, name=conf["name"],
                               **{k: model[k] for k in conf["reduced"]})


def _mesh(args, pcfg, chips):
    """(mesh, replica axis) of the traffic's ``--mesh``, checked as
    ``launch/train.py`` checks them, over exactly the cell's chips;
    (None, None) without one, on one chip."""
    if not args.mesh:
        if chips != 1:
            raise SystemExit(f"traffic: {chips} chips need a --mesh")
        return None, None
    from repro.launch import train as train_lib
    from repro.launch.mesh import make_mesh_from_spec, replica_axis_of
    mesh = make_mesh_from_spec(args.mesh)
    raxis = replica_axis_of(mesh)
    if raxis is None:
        raise SystemExit(f"traffic: --mesh {args.mesh!r} has no replica axis")
    if mesh.size != chips:
        raise SystemExit(f"traffic: --mesh {args.mesh!r} spans {mesh.size} "
                         f"devices, the cell {chips} chips")
    train_lib._validate_replicas(args, pcfg, mesh, raxis)
    return mesh, raxis


def _round_programs(round_fn, state, batches):
    """The two compiled programs of a round over a mesh with axes inside a
    replica (``core/parle.py::make_sharded_round_fn``: its ``inner_jit``
    and ``sync_jit``), lowered as the round calls them, so that the tracer
    maps their instructions to scopes as it does a one-program round's."""
    import jax
    jits = dict(zip(round_fn.__code__.co_freevars,
                    (c.cell_contents for c in round_fn.__closure__)))
    inner = jits["inner_jit"].lower(state, batches).compile()
    mid = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh,
                                           weak_type=a.weak_type),
        state, inner.output_shardings[0])
    return inner, jits["sync_jit"].lower(mid).compile()


def _norms(tree, minus=None):
    """{path: [norm of replica a's leaf for each a]} of a (n, ...) tree,
    of ``tree - minus`` where ``minus`` (no replica axis) is given."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(t, m):
        if m is not None:
            t = jax.tree.map(lambda a, b: a - b[None], t, m)
        return jax.tree.map(
            lambda a: jnp.sqrt(jnp.sum(jnp.square(a.reshape(a.shape[0], -1)),
                                       axis=1)), t)

    flat = jax.tree_util.tree_flatten_with_path(f(tree, minus))[0]
    return {jax.tree_util.keystr(p): [float(v) for v in l] for p, l in flat}


def norm_gap(prog: dict, ref: list) -> tuple[float, str, list]:
    """Worst leaf of | |prog| - |ref| | / max(|ref|, median |ref|) over the
    replicas and leaves, leaving out leaves whose reference norm is under a
    thousandth of the median's (their change is round-off); returns the
    gap, its leaf and the leaves left out."""
    import statistics
    pairs = [(f"{k}[{a}]", prog[k][a], ref[a][k])
             for a in range(len(ref)) for k in ref[a]]
    med = statistics.median(r for _, _, r in pairs)
    worst, where, skipped = 0.0, "", []
    for name, p, r in pairs:
        if r < 1e-3 * med:
            skipped.append(name)
            continue
        gap = abs(p - r) / max(r, med)
        if not gap <= worst:           # NaN wins
            worst, where = gap, name
    return worst, where, skipped


AHEAD_S = 6.0    # seconds of rounds queued ahead of the one read


class _LateReader:
    """``run_rounds``' ``on_round`` hook for the window: keeps each round's
    losses on the device and reads them ``lag`` rounds late, so that the
    host never waits on the round it has just dispatched."""

    def __init__(self, lag: int):
        self.lag = lag
        self.pending = collections.deque()
        self.losses = []         # each round's step losses, in order
        self.read_at = []        # harness.age() when each was read

    def __call__(self, r, gstep, metrics):
        self.pending.append(metrics["losses"])
        if len(self.pending) > self.lag:
            self._read()

    def _read(self):
        import numpy as np
        self.losses.append(np.asarray(self.pending.popleft()).tolist())
        self.read_at.append(harness.age())

    def drain(self):
        while self.pending:
            self._read()


def run(ctx) -> dict:
    import jax

    from repro.configs import ParleConfig
    from repro.core import registry
    from repro.data.synthetic import TokenStream, make_round_batch_fn
    from repro.launch import train as train_lib
    from repro.models.model import build_model
    from repro.obs import Obs
    from repro.runtime import RoundRunner, emit_progress, resolve_train_policy

    spec, job = ctx.spec, ctx.spec["traffic"]
    seed = token_rows.train_seed(ctx.seed)
    args = train_lib.build_argparser().parse_args(
        job["flags"] + ["--seed", str(seed)] + list(ctx.extra_flags))
    for k in ("replicas", "L", "batch", "seq"):
        if getattr(args, k) != job[k]:
            raise SystemExit(f"traffic: --{k} {getattr(args, k)} != {job[k]}")
    cfg = program_config(spec["config"])
    L, n, chips = args.L, args.replicas, ctx.chips
    t = {"jax": ctx.t_jax}

    # -- the trainer's objects, as launch/train.py builds them ------------
    policy = resolve_train_policy(args)
    model = build_model(cfg)
    algo = registry.get(args.algo)
    pcfg = algo.canonicalize_cfg(ParleConfig(
        n_replicas=n, L=L, lr=args.lr, lr_inner=args.lr,
        batches_per_epoch=max(args.steps // 4, 1),
        lr_drop_factor=args.lr_drop_factor, precision=args.precision,
        sync_compress=args.sync_compress, sync_overlap=args.sync_overlap))
    mesh, raxis = _mesh(args, pcfg, chips)
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=args.seq,
                         batch_size=args.batch, seed=args.seed)
    key = jax.random.PRNGKey(args.seed)
    # on a mesh, each leaf made where its planner sharding puts it
    state_sh, x0_sh = {}, {}
    if mesh is not None:
        from repro.sharding import partition, planner
        shapes = jax.eval_shape(model.init, key)
        state_sh["out_shardings"] = partition.shardings(
            mesh, algo.state_pspecs(raxis, params=shapes, mesh=mesh,
                                    cfg=pcfg))
        x0_sh["out_shardings"] = planner.plan_tree(
            shapes, mesh=mesh).shardings(mesh)
    state = jax.jit(lambda k: algo.init(model.init(k), pcfg),
                    **state_sh)(key)
    jax.block_until_ready(state)
    t["state"] = harness.age()

    round_fn = policy.make_round_fn(algo, model.loss, pcfg, mesh=mesh,
                                    replica_axis=raxis or "replica",
                                    use_kernel=args.use_kernel)
    stage = make_round_batch_fn(stream, L, args.batch, n)
    c0 = ctx.counter.snapshot()
    if hasattr(round_fn, "lower"):
        compiled = round_fn.lower(state, stage(0)).compile()
        memory = compiled.memory_analysis()
    else:                  # two programs: the first warm-up round compiles
        compiled, memory = round_fn, None
    c1 = ctx.counter.snapshot()
    t["compile"] = harness.age()

    spans = ctx.out / "spans.json"
    obs = Obs(trace_out=str(spans) if ctx.trace else "", process_name="bench")
    runner = RoundRunner(obs, ns="train")
    tokens_per_round = L * args.batch * args.seq * n
    t_wall0 = time.time()

    def progress(step, rnd, st, metrics):
        return emit_progress(obs, algo, st, metrics, step, rnd, t_wall0)

    def rounds(state, start, count, progress=progress, on_round=None):
        return runner.run_rounds(
            state, compiled, stage, start=start, rounds=count, L=L,
            tokens_per_round=tokens_per_round, mesh=mesh, pcfg=pcfg,
            progress_every=max(1, args.log_every // L), progress=progress,
            on_round=on_round,
            flush_fn=policy.make_flush_fn(algo, pcfg), aot=False)

    # -- the first rounds: warm-up, and what the reference follows --------
    check = job["check_rounds"]
    losses, grad, round_s = [], None, []
    for r in range(check):
        t_r = harness.age()
        state, hist = rounds(state, r * L, 1)
        round_s.append(harness.age() - t_r)
        losses += hist[0]["step_losses"]
        if r == 0:
            grad = _norms(state.v_x)
    x0 = jax.jit(model.init, **x0_sh)(key)
    change = _norms(state.x, x0)
    del x0
    if ctx.trace and memory is None:
        for program in _round_programs(round_fn, state, stage(check * L)):
            obs.tracer.add_hlo_ops(program)
    t["warmup"] = harness.age()

    # -- the window --------------------------------------------------------
    # The window's work is fixed by the steadiest warm-up round (the first
    # may carry a first execution's cost, any one a host stall).
    # --seconds 0: the readings alone, no window (bench/tests/readings.py)
    round_est = min(round_s[1:] or round_s)
    R = max(1, round(ctx.seconds / round_est)) if ctx.seconds else 0
    if ctx.trace:
        R = min(R, job["trace_rounds"])
    late = _LateReader(max(1, round(AHEAD_S / round_est)))

    def window(state):
        """R rounds, read late; the clock stops once all of them are done."""
        state, _ = rounds(state, check * L, R, progress=None, on_round=late)
        late.drain()
        jax.block_until_ready(state)
        return state

    c2 = ctx.counter.snapshot()
    setup_s = harness.age()
    t_w0 = harness.age()
    if ctx.trace:
        with jax.profiler.trace(str(ctx.out / "xprof")), \
                jax.profiler.TraceAnnotation(bench_trace.WINDOW):
            t_w0 = harness.age()
            state = window(state)
            t_w1 = harness.age()
    else:
        state = window(state)
        t_w1 = harness.age()
    c3 = ctx.counter.snapshot()
    window_s = t_w1 - t_w0
    failed = sum(1 for l in late.losses if not all(map(math.isfinite, l)))
    peak = harness.device_info(ctx.devices)["memory_peak_bytes"]
    if ctx.trace:
        obs.finalize()
    del state, compiled, runner, obs
    gc.collect()

    # -- the reference, once the program's state is freed -----------------
    t_ref = harness.age()
    ref = ref_parle.run(spec["config"], job, seed, check,
                        devices=ctx.devices)
    ref_s = harness.age() - t_ref
    loss_gap = max(abs(p - q) / abs(q) for p, q in zip(losses, ref["losses"]))
    grad_gap, grad_at, grad_skipped = norm_gap(grad, ref["grad"])
    change_gap, change_at, change_skipped = norm_gap(change, ref["change"])
    readings = {"loss_gap": loss_gap, "grad_gap": grad_gap,
                "change_gap": change_gap}

    info = {
        "setup": {"process_and_jax_init_s": t["jax"],
                  "state_init_s": t["state"] - t["jax"],
                  "compile_s": t["compile"] - t["state"],
                  "compile_cache": "hit" if c1["cache_hits"] > c0["cache_hits"]
                  else "miss",
                  "warmup_s": t["warmup"] - t["compile"],
                  "setup_s": setup_s},
        "compiles_in_window": c3["compiles"] - c2["compiles"],
        "rounds": R, "round_s_warmup": round_s, "window_s": window_s,
        "rounds_read_late": late.lag,
        "window_read_s": [b - a for a, b in zip([t_w0] + late.read_at,
                                                late.read_at)],
        "reference_s": ref_s, "worst_grad_leaf": grad_at,
        "worst_change_leaf": change_at,
        "leaves_left_out": {"grad": grad_skipped, "change": change_skipped},
        "losses_program": losses, "losses_reference": ref["losses"],
        "grad_norms": {"program": grad, "reference": ref["grad"]},
        "change_norms": {"program": change, "reference": ref["change"]},
        "memory": str(memory),
    }
    return {
        "readings": readings,
        "attempted": R, "failed": failed,
        "e2e": {"train_tokens_per_s": R * tokens_per_round
                / max(window_s, 1e-9) / chips,
                "setup_s": setup_s},
        "peak_bytes": peak,
        "window_s": window_s,
        "info": info,
        "art": {"kind": "train", "cfg": cfg, "args": args, "rounds": R,
                "tokens_per_round": tokens_per_round, "chips": chips,
                "window_s": window_s, "spans": spans,
                "xprof": ctx.out / "xprof", "devices": ctx.devices,
                "config": spec["config"], "job": job},
    }
