"""Staleness-1 overlapped sync (--sync-overlap) and its satellites.

The overlap round is a ROTATION of the barrier round: round k applies
the consensus carried from round k-1's collective, then issues round
k's collective before its inner scan.  R overlap rounds + one flush
must therefore reproduce R barrier rounds:

 * f32 local: BIT-identical (state and per-round losses), including
   across an lr-drop boundary.
 * int8 error-feedback sync: matches the barrier int8 trajectory to
   float tolerance on the XLA and the Pallas sync update; the EF
   residual telescopes the same.
 * resume: checkpoints are PRE-flush; restoring one and continuing
   re-applies the carried consensus itself — bit-identical to never
   having stopped.
 * 8-device shard_map (subprocess): replica-only mesh bit-identical to
   the sharded barrier round; composed FSDP x TP mesh to tolerance.

Satellite regressions: the token-stream split=True fix (disjoint key
blocks; split=False bit-compatible with the legacy interleave), the
round stager threading split, checkpoint restore naming the offending
leaf on shape/dtype drift, and the replicas-vs-mesh SystemExit.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt
from repro.configs.base import ParleConfig
from repro.core import parle, registry
from repro.data.synthetic import (TokenStream, make_round_batch_fn,
                                  replica_batches)


def _loss(p, b):
    return jnp.mean((p["w"] @ p["m"] - b["t"]) ** 2), ()


def _params(key):
    return {"w": jax.random.normal(key, (8, 16)) * 0.1,
            "m": jax.random.normal(jax.random.fold_in(key, 1), (16, 4)) * 0.1}


def _round_batches(key, L, n):
    return {"t": jax.random.normal(key, (L, n, 8, 4))}


def _cfg(**kw):
    return ParleConfig(n_replicas=2, L=3, lr=0.05, lr_inner=0.05,
                       batches_per_epoch=5, lr_drop_steps=(4,),
                       lr_drop_factor=0.5, **kw)   # schedule crosses round 2


def _run(cfg, rounds=3, use_kernel=False, flush=False):
    algo = registry.get("parle")
    state = parle.dealias_state(algo.init(_params(jax.random.PRNGKey(0)),
                                          cfg))
    round_fn = algo.make_round_fn(_loss, cfg, use_kernel=use_kernel)
    losses = []
    for r in range(rounds):
        rb = _round_batches(jax.random.PRNGKey(10 + r), cfg.L,
                            cfg.n_replicas)
        state, m = round_fn(state, rb)
        losses.append(np.asarray(m["losses"]))
    if flush:
        state = algo.make_round_flush_fn(cfg)(state)
    return state, np.concatenate(losses)


def _assert_states(sa, sb, exact=True):
    for name in ("x", "y", "z", "v_x", "v_y"):
        for a, b in zip(jax.tree_util.tree_leaves(getattr(sa, name)),
                        jax.tree_util.tree_leaves(getattr(sb, name))):
            if exact:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=name)
            else:
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-5, atol=1e-7,
                                           err_msg=name)
    assert int(sa.step) == int(sb.step)


def test_overlap_plus_flush_bit_identical_f32():
    s_bar, l_bar = _run(_cfg())
    s_ovl, l_ovl = _run(_cfg(sync_overlap=True), flush=True)
    np.testing.assert_array_equal(l_bar, l_ovl)   # per-round losses too
    _assert_states(s_bar, s_ovl, exact=True)


@pytest.mark.parametrize("use_kernel", (False, True))
def test_overlap_int8_error_feedback(use_kernel):
    """int8 EF sync under overlap: same trajectory as the barrier int8
    path (whose telescoping is regression-tested in test_sync_compress)
    — the overlap round quantizes the SAME payload x+e the barrier
    round would, so the residuals telescope identically."""
    s_bar, _ = _run(_cfg(sync_compress="int8"), use_kernel=use_kernel)
    s_ovl, _ = _run(_cfg(sync_compress="int8", sync_overlap=True),
                    use_kernel=use_kernel, flush=True)
    _assert_states(s_bar, s_ovl, exact=False)
    for a, b in zip(jax.tree_util.tree_leaves(s_bar.e),
                    jax.tree_util.tree_leaves(s_ovl.e)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)


def test_overlap_round_boundary_resume(tmp_path):
    """Checkpoints are written PRE-flush; a resumed run re-enters the
    overlap loop, which applies the carried consensus itself."""
    cfg = _cfg(sync_overlap=True)
    algo = registry.get("parle")
    round_fn = algo.make_round_fn(_loss, cfg)
    state = parle.dealias_state(algo.init(_params(jax.random.PRNGKey(0)),
                                          cfg))
    for r in range(2):
        state, _ = round_fn(state, _round_batches(jax.random.PRNGKey(10 + r),
                                                  cfg.L, cfg.n_replicas))
    path = str(tmp_path / "mid.npz")
    ckpt.save(path, state, step=int(state.step), algo="parle")

    template = algo.init(_params(jax.random.PRNGKey(0)), cfg)
    resumed = parle.dealias_state(ckpt.restore(path, template, algo="parle"))
    resumed, _ = round_fn(resumed, _round_batches(jax.random.PRNGKey(12),
                                                  cfg.L, cfg.n_replicas))
    resumed = algo.make_round_flush_fn(cfg)(resumed)

    uninterrupted, _ = _run(cfg, rounds=3, flush=True)
    _assert_states(uninterrupted, resumed, exact=True)


# ------------------------------------------------------------------
# Satellite regressions
# ------------------------------------------------------------------

def test_token_stream_split_actually_splits():
    """split=True partitions the PRNG key space (disjoint per-shard
    blocks); split=False keeps the legacy interleave bit-for-bit."""
    stream = TokenStream(vocab_size=512, seq_len=16, batch_size=2, seed=3)
    n = 2
    b_split = replica_batches(stream, 5, 2, n, split=True)
    b_plain = replica_batches(stream, 5, 2, n, split=False)
    # before the fix both modes produced identical batches
    assert not np.array_equal(np.asarray(b_split["tokens"]),
                              np.asarray(b_plain["tokens"]))
    # disjointness: across a window of steps, shard 0 and shard 1 never
    # draw the same batch (their key blocks are 2^20 apart)
    draws = [set(), set()]
    for s in range(8):
        b = replica_batches(stream, s, 2, n, split=True)
        for a in range(n):
            draws[a].add(np.asarray(b["tokens"][a]).tobytes())
    assert not (draws[0] & draws[1])
    # split=False replica a at step s is the unsharded stream at step
    # s*n + a — the pre-fix derivation, unchanged
    flat = TokenStream(vocab_size=512, seq_len=16, batch_size=2, seed=3)
    for a in range(n):
        np.testing.assert_array_equal(
            np.asarray(b_plain["tokens"][a]),
            np.asarray(flat.batch(5 * n + a)["tokens"]))


@pytest.mark.parametrize("split", (False, True))
def test_round_stager_matches_per_step_both_modes(split):
    stream = TokenStream(vocab_size=512, seq_len=16, batch_size=2, seed=3)
    L, n = 4, 3
    stage = make_round_batch_fn(stream, L, 2, n, split=split)
    staged = stage(8)
    for j in range(L):
        want = replica_batches(stream, 8 + j, 2, n, split=split)
        got = jax.tree.map(lambda x: x[j], staged)
        for k in want:
            np.testing.assert_array_equal(np.asarray(want[k]),
                                          np.asarray(got[k]))


def test_restore_names_offending_leaf(tmp_path):
    path = str(tmp_path / "t.npz")
    ckpt.save(path, {"a": jnp.zeros((4, 3)), "b": jnp.ones((2,))}, step=1)
    with pytest.raises(ValueError, match=r"leaf 'a'.*shape"):
        ckpt.restore(path, {"a": jnp.zeros((4, 2)), "b": jnp.ones((2,))})
    # f32 checkpoint into a bf16 template must not silently restore f32
    with pytest.raises(ValueError, match=r"leaf 'b'.*dtype"):
        ckpt.restore(path, {"a": jnp.zeros((4, 3)),
                            "b": jnp.ones((2,), jnp.bfloat16)})
    # and the reverse: bf16 bits on disk, f32 template
    ckpt.save(path, {"a": jnp.zeros((4, 3), jnp.bfloat16)}, step=1)
    with pytest.raises(ValueError, match=r"leaf 'a'.*bfloat16"):
        ckpt.restore(path, {"a": jnp.zeros((4, 3))})


def test_replicas_mesh_mismatch_exits():
    from repro.launch import train
    mesh = SimpleNamespace(shape={"replica": 2})
    args = SimpleNamespace(replicas=3, algo="parle")
    cfg = registry.get("parle").canonicalize_cfg(
        ParleConfig(n_replicas=3, batches_per_epoch=5))
    with pytest.raises(SystemExit, match="divisible"):
        train._validate_replicas(args, cfg, mesh, "replica")
    # entropy_sgd canonicalizes n -> 1: a replica:4 mesh must die with
    # the rewrite spelled out, not a divisibility error on n=1
    args = SimpleNamespace(replicas=4, algo="entropy_sgd")
    cfg = registry.get("entropy_sgd").canonicalize_cfg(
        ParleConfig(n_replicas=4, batches_per_epoch=5))
    with pytest.raises(SystemExit, match="canonicalizes"):
        train._validate_replicas(args, cfg,
                                 SimpleNamespace(shape={"replica": 4}),
                                 "replica")
    # flag-combination guards fire before any model is built
    with pytest.raises(SystemExit, match="round-fused"):
        train.main(["--sync-overlap"])
    with pytest.raises(SystemExit, match="no round-level sync"):
        train.main(["--sync-overlap", "--round-fused", "--algo",
                    "elastic_sgd"])


# ------------------------------------------------------------------
# 8-device shard_map overlap (subprocess; see test_round_fused)
# ------------------------------------------------------------------

_CHILD = textwrap.dedent("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    assert len(jax.devices()) == 8
    from repro.configs.base import ParleConfig
    from repro.core import parle
    from repro.launch.mesh import make_mesh_from_spec

    cfg = ParleConfig(n_replicas=8, L=3, lr=0.05, lr_inner=0.05,
                      batches_per_epoch=5)
    ocfg = dataclasses.replace(cfg, sync_overlap=True)
    key = jax.random.PRNGKey(0)

    def loss(p, b):
        return jnp.mean((p["w"] - b["t"]) ** 2), ()

    reps = {"w": jax.random.normal(key, (8, 6))}
    rbs = [{"t": jax.random.normal(jax.random.PRNGKey(1 + r), (3, 8, 1))}
           for r in range(3)]

    # replica-only mesh: overlap + flush is BIT-identical to the
    # sharded barrier round (same psum, same placement, rotated)
    mesh8 = make_mesh_from_spec("replica:8")
    st_b = parle.dealias_state(parle.init_from_replicas(reps, cfg))
    round_b = parle.make_round_fn(loss, cfg, mesh=mesh8)
    st_o = parle.dealias_state(parle.init_from_replicas(reps, ocfg))
    round_o = parle.make_round_fn(loss, ocfg, mesh=mesh8)
    for rb in rbs:
        st_b, m_b = round_b(st_b, rb)
        st_o, m_o = round_o(st_o, rb)
        np.testing.assert_array_equal(np.asarray(m_b["losses"]),
                                      np.asarray(m_o["losses"]))
    st_o = parle.make_flush_fn(ocfg)(st_o)
    for name in ("x", "y", "z", "v_x", "v_y"):
        np.testing.assert_array_equal(
            np.asarray(getattr(st_b, name)["w"]),
            np.asarray(getattr(st_o, name)["w"]), err_msg=name)
    assert int(st_o.step) == int(st_b.step) == 9
    print("OVERLAP_MANUAL_OK")

    # composed FSDP x TP mesh (split head + GSPMD inner scan): matches
    # the local barrier trajectory to float tolerance
    meshc = make_mesh_from_spec("replica:2,data:2,model:2")
    cfgc = ParleConfig(n_replicas=2, L=3, lr=0.05, lr_inner=0.05,
                       batches_per_epoch=5)
    ocfgc = dataclasses.replace(cfgc, sync_overlap=True)
    repsc = {"w": jax.random.normal(key, (2, 8, 16)) * 0.1,
             "m": jax.random.normal(jax.random.fold_in(key, 1),
                                    (2, 16, 4)) * 0.1}
    rbcs = [{"t": jax.random.normal(jax.random.PRNGKey(20 + r),
                                    (3, 2, 8, 4))} for r in range(3)]

    def lossc(p, b):
        return jnp.mean((p["w"] @ p["m"] - b["t"]) ** 2), ()

    st_l = parle.dealias_state(parle.init_from_replicas(repsc, cfgc))
    round_l = parle.make_round_fn(lossc, cfgc)
    st_c = parle.dealias_state(parle.init_from_replicas(repsc, ocfgc))
    round_c = parle.make_round_fn(lossc, ocfgc, mesh=meshc)
    for rb in rbcs:
        st_l, m_l = round_l(st_l, rb)
        st_c, m_c = round_c(st_c, rb)
        np.testing.assert_allclose(np.asarray(m_c["losses"]),
                                   np.asarray(m_l["losses"]), rtol=1e-5)
    st_c = parle.make_flush_fn(ocfgc)(st_c)
    np.testing.assert_allclose(np.asarray(st_c.x["w"]),
                               np.asarray(st_l.x["w"]),
                               rtol=1e-5, atol=1e-6)
    print("OVERLAP_COMPOSED_OK")
""")


def _run_child(code):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=420)


@pytest.fixture(scope="module")
def overlap_child():
    return _run_child(_CHILD)


def test_sharded_overlap_replica_only_bit_identical(overlap_child):
    assert overlap_child.returncode == 0, \
        f"stdout:\n{overlap_child.stdout}\nstderr:\n{overlap_child.stderr}"
    assert "OVERLAP_MANUAL_OK" in overlap_child.stdout


def test_sharded_overlap_composed_mesh_tolerance(overlap_child):
    assert overlap_child.returncode == 0, \
        f"stdout:\n{overlap_child.stdout}\nstderr:\n{overlap_child.stderr}"
    assert "OVERLAP_COMPOSED_OK" in overlap_child.stdout
