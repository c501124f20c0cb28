"""Fused L-step rounds: one compiled, state-donating program per round
must reproduce the per-step dispatch loop exactly.

 * f32 local rounds are BIT-identical to L single steps for all four
   registry algorithms (the scan re-traces the same update bodies; the
   sync fires with the same lr_scale the cond'd path would use).
 * the jitted round-batch stager equals per-step replica_batches.
 * donation safety: init-time buffer aliasing (x=y=z, elastic ref=params)
   is neutralized by dealias_state, and steady-state outputs re-donate.
 * 8-device shard_map rounds (subprocess, like test_distributed_sync):
   replica-only mesh bit-identical; composed FSDP x TP mesh to float
   tolerance (its GSPMD inner scan, documented in
   parle._round_program).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ParleConfig
from repro.core import parle, registry
from repro.data.synthetic import (TokenStream, make_round_batch_fn,
                                  replica_batches)

ALGOS = ("parle", "entropy_sgd", "elastic_sgd", "sgd")


def _loss(p, b):
    return jnp.mean((p["w"] @ p["m"] - b["t"]) ** 2), ()


def _params(key):
    return {"w": jax.random.normal(key, (8, 16)) * 0.1,
            "m": jax.random.normal(jax.random.fold_in(key, 1), (16, 4)) * 0.1}


def _round_batches(key, L, n):
    return {"t": jax.random.normal(key, (L, n, 8, 4))}


@pytest.mark.parametrize("algo_name", ALGOS)
def test_round_bit_identical_to_step_loop(algo_name):
    algo = registry.get(algo_name)
    cfg = algo.canonicalize_cfg(ParleConfig(
        n_replicas=2, L=3, lr=0.05, lr_inner=0.05, batches_per_epoch=5,
        lr_drop_steps=(4,), lr_drop_factor=0.5))   # schedule crosses round 2
    n = cfg.n_replicas
    params = _params(jax.random.PRNGKey(0))
    step = jax.jit(algo.make_step(_loss, cfg))
    round_fn = algo.make_round_fn(_loss, cfg)

    s_step = algo.init(params, cfg)
    s_round = parle.dealias_state(algo.init(params, cfg))
    for r in range(2):                    # two rounds = 2 syncs for parle
        rb = _round_batches(jax.random.PRNGKey(10 + r), cfg.L, n)
        for j in range(cfg.L):
            s_step, m_step = step(s_step, jax.tree.map(lambda x: x[j], rb))
        s_round, m_round = round_fn(s_round, rb)
    flat_a = jax.tree_util.tree_leaves(jax.tree.map(np.asarray, s_step))
    flat_b = jax.tree_util.tree_leaves(jax.tree.map(np.asarray, s_round))
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(a, b)
    # metrics contract: per-step losses (L,), loss = round mean
    assert m_round["losses"].shape == (cfg.L,)
    np.testing.assert_allclose(float(m_round["loss"]),
                               float(np.mean(np.asarray(m_round["losses"]))),
                               rtol=1e-6)
    np.testing.assert_allclose(float(m_round["losses"][-1]),
                               float(m_step["loss"]), rtol=1e-6)


def test_round_batch_stager_matches_per_step():
    stream = TokenStream(vocab_size=512, seq_len=16, batch_size=2, seed=3)
    L, n = 4, 3
    stage = make_round_batch_fn(stream, L, 2, n)
    staged = stage(8)                     # round starting at step 8
    for j in range(L):
        want = replica_batches(stream, 8 + j, 2, n)
        got = jax.tree.map(lambda x: x[j], staged)
        for k in want:
            np.testing.assert_array_equal(np.asarray(want[k]),
                                          np.asarray(got[k]))


def test_donation_protects_caller_params():
    """Donating a round must never delete buffers the CALLER still
    holds: elastic's state.ref IS the params tree passed to init."""
    algo = registry.get("elastic_sgd")
    cfg = algo.canonicalize_cfg(ParleConfig(n_replicas=2, L=2,
                                            batches_per_epoch=5))
    params = _params(jax.random.PRNGKey(1))
    state = parle.dealias_state(algo.init(params, cfg))
    round_fn = algo.make_round_fn(_loss, cfg)
    state, _ = round_fn(state, _round_batches(jax.random.PRNGKey(2), 2, 2))
    np.asarray(params["w"])               # must not raise "deleted"
    # steady state: round outputs re-donate cleanly
    state, _ = round_fn(state, _round_batches(jax.random.PRNGKey(3), 2, 2))
    jax.block_until_ready(jax.tree_util.tree_leaves(state)[0])


# ------------------------------------------------------------------
# 8-device shard_map rounds (subprocess; see test_distributed_sync)
# ------------------------------------------------------------------

_CHILD = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    assert len(jax.devices()) == 8
    from repro.configs.base import ParleConfig
    from repro.core import parle
    from repro.launch.mesh import make_mesh_from_spec

    cfg = ParleConfig(n_replicas=8, L=3, lr=0.05, lr_inner=0.05,
                      batches_per_epoch=5)
    key = jax.random.PRNGKey(0)

    def loss(p, b):
        return jnp.mean((p["w"] - b["t"]) ** 2), ()

    reps = {"w": jax.random.normal(key, (8, 6))}
    rb = {"t": jax.random.normal(jax.random.PRNGKey(1), (3, 8, 1))}

    # reference on the SAME placement: the sharded per-step loop (its
    # all-reduce reduction order differs from the local leading-axis
    # mean by ulps, so cross-placement equality is rtol-level while
    # round-vs-step-loop on one placement is BIT-exact)
    mesh8 = make_mesh_from_spec("replica:8")
    st_steps = parle.init_from_replicas(reps, cfg)
    step8 = parle.make_sharded_train_step(loss, cfg, mesh8)
    st8 = parle.dealias_state(parle.init_from_replicas(reps, cfg))
    round8 = parle.make_round_fn(loss, cfg, mesh=mesh8)
    # 2 replicas per device
    mesh4 = jax.make_mesh((4,), ("replica",))
    st4 = parle.dealias_state(parle.init_from_replicas(reps, cfg))
    round4 = parle.make_round_fn(loss, cfg, mesh=mesh4)
    # local reference (rtol-level cross-placement check)
    st_ref = parle.dealias_state(parle.init_from_replicas(reps, cfg))
    round_ref = parle.make_round_fn(loss, cfg)

    for r in range(2):
        for j in range(3):
            st_steps, m_steps = step8(st_steps,
                                      jax.tree.map(lambda x: x[j], rb))
        st_ref, m_ref = round_ref(st_ref, rb)
        st8, m8 = round8(st8, rb)
        st4, m4 = round4(st4, rb)
    np.testing.assert_array_equal(np.asarray(st8.x["w"]),
                                  np.asarray(st_steps.x["w"]))
    np.testing.assert_array_equal(np.asarray(st8.z["w"]),
                                  np.asarray(st_steps.z["w"]))
    np.testing.assert_allclose(float(m8["losses"][-1]),
                               float(m_steps["loss"]), rtol=1e-6)
    for st, m in ((st8, m8), (st4, m4)):
        np.testing.assert_allclose(np.asarray(st.x["w"]),
                                   np.asarray(st_ref.x["w"]),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(m["losses"]),
                                   np.asarray(m_ref["losses"]), rtol=1e-6)
        assert int(st.step) == int(st_ref.step) == 6
    print("MANUAL_ROUND_OK")

    # composed FSDP x TP mesh: GSPMD inner scan + manual sync — matches
    # to float tolerance (GSPMD partitions reductions differently)
    meshc = make_mesh_from_spec("replica:2,data:2,model:2")
    cfgc = ParleConfig(n_replicas=2, L=3, lr=0.05, lr_inner=0.05,
                       batches_per_epoch=5)
    repsc = {"w": jax.random.normal(key, (2, 8, 16)) * 0.1,
             "m": jax.random.normal(jax.random.fold_in(key, 1),
                                    (2, 16, 4)) * 0.1}
    rbc = {"t": jax.random.normal(jax.random.PRNGKey(2), (3, 2, 8, 4))}

    def lossc(p, b):
        return jnp.mean((p["w"] @ p["m"] - b["t"]) ** 2), ()

    st_lc = parle.dealias_state(parle.init_from_replicas(repsc, cfgc))
    round_lc = parle.make_round_fn(lossc, cfgc)
    st_c = parle.dealias_state(parle.init_from_replicas(repsc, cfgc))
    round_c = parle.make_round_fn(lossc, cfgc, mesh=meshc)
    for r in range(2):
        st_lc, m_lc = round_lc(st_lc, rbc)
        st_c, m_c = round_c(st_c, rbc)
    np.testing.assert_allclose(np.asarray(st_c.x["w"]),
                               np.asarray(st_lc.x["w"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(m_c["loss"]), float(m_lc["loss"]),
                               rtol=1e-5)
    print("COMPOSED_ROUND_OK")
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
def round_child():
    return _run_child(_CHILD)


def test_sharded_round_replica_only_bit_identical(round_child):
    assert round_child.returncode == 0, \
        f"stdout:\n{round_child.stdout}\nstderr:\n{round_child.stderr}"
    assert "MANUAL_ROUND_OK" in round_child.stdout


def test_sharded_round_composed_mesh_tolerance(round_child):
    assert round_child.returncode == 0, \
        f"stdout:\n{round_child.stdout}\nstderr:\n{round_child.stderr}"
    assert "COMPOSED_ROUND_OK" in round_child.stdout
