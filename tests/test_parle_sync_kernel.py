"""Oracle-equivalence tests for the fused Pallas sync-step kernel
(Eq. 8c-8d), mirroring the inner-step kernel's coverage: exact-block,
non-aligned, odd/rank-y shapes, pytree leafwise application, and the
use_kernel path through sync_step / fused_step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ParleConfig
from repro.core import parle
from repro.kernels import ops, ref
from repro.kernels.parle_update import BLOCK_ELEMS, parle_sync_tree

SCALARS = dict(gamma_scale=1.0, inv_rho=2.5, lr=0.1, mu=0.9)


def _rand(key, shape):
    """x, z, v with leading replica axis; xbar WITHOUT it (the kernel
    contract: one un-broadcast mean shared by all replicas)."""
    ks = jax.random.split(key, 4)
    x, z, v = [jax.random.normal(k, shape) for k in ks[:3]]
    xbar = jax.random.normal(ks[3], shape[1:])
    return x, z, v, xbar


@pytest.mark.parametrize("shape", [
    (1, BLOCK_ELEMS),         # one replica, exactly one block
    (2, 2 * BLOCK_ELEMS),     # multi-replica, multi-block, aligned
    (3, 5),                   # tiny: all padding lanes
    (2, 3, 17),               # odd trailing dims
    (4, BLOCK_ELEMS + 1),     # one element past a block boundary
])
def test_sync_kernel_matches_oracle(shape):
    x, z, v, xbar = _rand(jax.random.PRNGKey(0), shape)
    want = ref.parle_sync_update(x, z, v, xbar, **SCALARS)
    got = parle_sync_tree(x, z, v, xbar, interpret=True, **SCALARS)
    for w, g in zip(want, got):
        np.testing.assert_allclose(np.asarray(w), np.asarray(g),
                                   rtol=1e-5, atol=1e-6)


def test_sync_kernel_scalar_sensitivity():
    """Each scalar must actually reach the kernel (guards against a
    mis-ordered SMEM prefetch)."""
    shape = (3, 17)
    x, z, v, xbar = _rand(jax.random.PRNGKey(1), shape)
    base = parle_sync_tree(x, z, v, xbar, interpret=True, **SCALARS)
    for name in SCALARS:
        bumped = dict(SCALARS, **{name: SCALARS[name] * 1.7 + 0.1})
        want = ref.parle_sync_update(x, z, v, xbar, **bumped)
        got = parle_sync_tree(x, z, v, xbar, interpret=True, **bumped)
        np.testing.assert_allclose(np.asarray(want[0]), np.asarray(got[0]),
                                   rtol=1e-5, atol=1e-6)
        assert not np.allclose(np.asarray(got[0]), np.asarray(base[0])), name


def test_sync_kernel_pytree_leafwise():
    key = jax.random.PRNGKey(2)
    mk = lambda k, lead: {
        "a": jax.random.normal(k, lead + (9,)),
        "nested": {"b": jax.random.normal(jax.random.fold_in(k, 1),
                                          lead + (3, 5))}}
    ks = jax.random.split(key, 4)
    x, z, v = [mk(k, (2,)) for k in ks[:3]]
    xbar = mk(ks[3], ())
    want = jax.tree.map(
        lambda *ls: ref.parle_sync_update(*ls, **SCALARS), x, z, v, xbar)
    got_x, got_v, got_y = ops.parle_sync_update(x, z, v, xbar, **SCALARS)
    assert got_y is got_x          # f32 compute: y' IS x' (no extra pass)
    np.testing.assert_allclose(np.asarray(want["a"][0]),
                               np.asarray(got_x["a"]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(want["nested"]["b"][1]),
                               np.asarray(got_v["nested"]["b"]),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("sync_compress", ["none", "bf16", "int8"])
def test_sync_step_kernel_path_matches_jnp(sync_compress):
    """The kernel sync matches the XLA sync; with a compressed sync both
    quantize the same payload through the jnp codec, and only the
    update's rounding can differ."""
    cfg = ParleConfig(n_replicas=3, L=2, batches_per_epoch=10,
                      sync_compress=sync_compress)
    key = jax.random.PRNGKey(3)
    st = parle.init_from_replicas(
        {"w": jax.random.normal(key, (3, 7)),
         "b": jax.random.normal(jax.random.fold_in(key, 1), (3, 4, 5))}, cfg)
    st = st._replace(z=jax.tree.map(lambda a: a * 0.3, st.z),
                     v_x=jax.tree.map(jnp.ones_like, st.v_x))
    a = parle.sync_step(st, cfg, use_kernel=False)
    b = parle.sync_step(st, cfg, use_kernel=True)
    for field in ("x", "v_x", "y", "z"):
        for leaf in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(getattr(a, field)[leaf]),
                np.asarray(getattr(b, field)[leaf]),
                rtol=1e-5, atol=1e-6, err_msg=f"{field}[{leaf}]")
    if sync_compress == "none":
        assert a.e is None and b.e is None
    else:           # the same payload: the residual is bit-identical
        for leaf in ("w", "b"):
            np.testing.assert_array_equal(np.asarray(a.e[leaf]),
                                          np.asarray(b.e[leaf]))
            assert np.any(np.asarray(b.e[leaf]) != 0)
    # scoping decay fired identically
    assert float(a.scopes.gamma) == pytest.approx(float(b.scopes.gamma))


@pytest.mark.parametrize("sync_compress", ["none", "int8"])
def test_fused_step_kernel_path_through_sync(sync_compress):
    """use_kernel=True drives BOTH fused kernels (inner + sync) through
    a sync boundary and must match the jnp path, with the int8 sync as
    without compression."""
    cfg = ParleConfig(n_replicas=2, L=2, batches_per_epoch=10,
                      sync_compress=sync_compress)
    key = jax.random.PRNGKey(4)
    st_a = st_b = parle.init(
        {"w": jax.random.normal(key, (6,))}, cfg)
    for i in range(4):
        g = {"w": jax.random.normal(jax.random.fold_in(key, i), (2, 6))}
        st_a = parle.fused_step(st_a, g, cfg, use_kernel=False)
        st_b = parle.fused_step(st_b, g, cfg, use_kernel=True)
    np.testing.assert_allclose(np.asarray(st_a.x["w"]),
                               np.asarray(st_b.x["w"]), rtol=1e-5, atol=1e-6)
    if sync_compress != "none":
        np.testing.assert_allclose(np.asarray(st_a.e["w"]),
                                   np.asarray(st_b.e["w"]),
                                   rtol=1e-5, atol=1e-6)
    assert int(st_b.step) == 4
