"""Forward/loss/grad sanity for every model family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import FAMILY_CONFIGS, family_params, make_batch
from repro.models.model import build_model


@pytest.mark.parametrize("family", family_params())
def test_forward_shapes_and_finiteness(family, key):
    cfg = FAMILY_CONFIGS[family]
    model = build_model(cfg)
    params = model.init(key)
    batch = make_batch(cfg, key)
    logits, aux = model.apply(params, batch)
    if family == "audio":
        assert logits.shape == (2, 32, cfg.num_codebooks, cfg.vocab_size)
    else:
        assert logits.shape == (2, 32, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("family", family_params())
def test_loss_and_grads_finite(family, key):
    cfg = FAMILY_CONFIGS[family]
    model = build_model(cfg)
    params = model.init(key)
    batch = make_batch(cfg, key)
    loss, metrics = model.loss(params, batch)
    assert np.isfinite(float(loss))
    # loss near ln(V) at init
    assert float(metrics["ce"]) < np.log(cfg.vocab_size) * 1.5
    grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    for g in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(g)).all()


@pytest.mark.parametrize("family", family_params())
def test_loss_decreases_under_sgd(family, key):
    cfg = FAMILY_CONFIGS[family]
    model = build_model(cfg)
    params = model.init(key)
    batch = make_batch(cfg, key)
    from repro.optim import sgd
    st = sgd.init(params)
    step = jax.jit(sgd.make_train_step(model.loss, 0.1))
    l0 = None
    for _ in range(10):
        st, m = step(st, batch)
        if l0 is None:
            l0 = float(m["loss"])
    assert float(m["loss"]) < l0, (family, l0, float(m["loss"]))


def test_moe_routing_load_balance(key):
    """Aux loss is >= 1 * weight at perfect balance and grows with skew."""
    cfg = FAMILY_CONFIGS["moe"]
    model = build_model(cfg)
    params = model.init(key)
    batch = make_batch(cfg, key)
    _, metrics = model.loss(params, batch)
    assert float(metrics["aux"]) >= 0.0


def test_sliding_window_masks_out_far_context(key):
    """With window w, logits at position t do not depend on tokens < t - w."""
    import dataclasses
    cfg = dataclasses.replace(FAMILY_CONFIGS["dense"], sliding_window=4)
    model = build_model(cfg)
    params = model.init(key)
    t1 = jax.random.randint(key, (1, 16), 0, cfg.vocab_size)
    t2 = t1.at[:, 0].set((t1[:, 0] + 7) % cfg.vocab_size)  # perturb far past
    l1, _ = model.apply(params, {"tokens": t1, "labels": t1})
    l2, _ = model.apply(params, {"tokens": t2, "labels": t2})
    # last position attends to [12..15]; token 0 cannot influence it
    np.testing.assert_allclose(np.asarray(l1[0, -1]), np.asarray(l2[0, -1]),
                               rtol=1e-5, atol=1e-5)


def test_moe_grouped_dispatch_matches_flat(key):
    """GShard-style grouped dispatch (moe_groups>1) must be numerically
    identical to the flat path at drop-free capacity (§Perf lever)."""
    import dataclasses
    from repro.models import moe as moe_mod
    cfg = dataclasses.replace(FAMILY_CONFIGS["moe"], num_shared_experts=0)
    params = moe_mod.init_moe_params(key, cfg)
    x = jax.random.normal(key, (2, 16, cfg.d_model))
    flat, _ = moe_mod.moe_forward(params, cfg, x)
    gcfg = dataclasses.replace(cfg, moe_groups=4)
    from jax.sharding import AxisType
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         (AxisType.Auto, AxisType.Auto))
    with jax.set_mesh(mesh):
        grouped, _ = jax.jit(
            lambda p, x: moe_mod.moe_forward(p, gcfg, x))(params, x)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(flat),
                               rtol=1e-5, atol=1e-6)


def test_ssd_chunked_grads_finite_under_fast_decay(key):
    """Steep per-step decay makes exp(cum_i - cum_j) overflow above the
    chunk's diagonal; the masked entries must not turn the backward pass
    into NaN (0 * inf).  mamba2 and zamba2 at published widths reach
    such decays at initialization."""
    from repro.models.mamba2 import ssd_chunked
    B, T, nh, P, N = 1, 64, 2, 8, 4
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (B, T, nh, P))
    dt = jnp.full((B, T, nh), 8.0)
    A = jnp.full((nh,), -16.0)              # log decay -128 per step
    Bm = jax.random.normal(ks[1], (B, T, N))
    Cm = jax.random.normal(ks[2], (B, T, N))

    def f(x, dt):
        y, _ = ssd_chunked(x, dt, A, Bm, Cm, 64)
        return jnp.sum(y)

    for g in jax.grad(f, argnums=(0, 1))(x, dt):
        assert np.isfinite(np.asarray(g)).all()


def test_fused_ssd_trains_like_chunked_under_the_ssd_scope(monkeypatch, key):
    """A small Mamba2 through the fused Pallas SSD (interpreted here; on a
    TPU the block takes it by itself): the loss and every parameter
    gradient match the ``ssd_chunked`` path, and the ops of both
    directions of the fused op sit in the ``ssd`` scope that
    ``ssd_ms.train`` reads."""
    from bench.scopes import scope_of
    from repro.configs.base import ModelConfig
    from repro.models import mamba2
    from repro.obs.trace import hlo_op_names
    cfg = ModelConfig(name="t-ssm", family="ssm", num_layers=2, d_model=64,
                      num_heads=0, num_kv_heads=0, d_ff=0, vocab_size=128,
                      ssm_state=16, ssm_head_dim=32, ssm_chunk=16)
    model = build_model(cfg)
    params = model.init(key)
    batch = make_batch(cfg, key, batch=2, seq=32)
    loss = lambda p: model.loss(p, batch)[0]               # noqa: E731
    want_l, want_g = jax.value_and_grad(loss)(params)
    monkeypatch.setattr(mamba2, "_fused_ssd",
                        lambda cfg, T, h0: h0 is None
                        and T % cfg.ssm_chunk == 0)
    got_l, got_g = jax.value_and_grad(loss)(params)
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got_g)[0],
                            jax.tree.leaves(want_g)):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * np.abs(b).max(),
                                   err_msg=jax.tree_util.keystr(path))
    _, ops = hlo_op_names(jax.jit(jax.grad(loss)).lower(params).compile()
                          .as_text())
    fused = [v for v in ops.values() if "jit(ssd_scan)" in v]
    assert any("ssd_fwd" in v and "transpose" not in v for v in fused)
    assert any("ssd_bwd" in v and "transpose(" in v for v in fused)
    for v in fused:
        assert scope_of(v) == "ssd", v
