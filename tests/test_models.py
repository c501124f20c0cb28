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


def _t_ssm():
    from repro.configs.base import ModelConfig
    return ModelConfig(name="t-ssm", family="ssm", num_layers=2, d_model=64,
                       num_heads=0, num_kv_heads=0, d_ff=0, vocab_size=128,
                       ssm_state=16, ssm_head_dim=32, ssm_chunk=16)


def _take_ssd(monkeypatch, fused):
    """Steer the Mamba2 block to the fused Pallas SSD (interpreted here; on
    a TPU the block takes it by itself) or to ``ssd_chunked``."""
    from repro.models import mamba2
    monkeypatch.setattr(mamba2, "_fused_ssd",
                        lambda cfg, T, h0: fused and h0 is None
                        and T % cfg.ssm_chunk == 0)


def test_fused_ssd_trains_like_chunked_under_the_ssd_scope(monkeypatch, key):
    """A small Mamba2 through the fused Pallas SSD (interpreted here; on a
    TPU the block takes it by itself): the loss and every parameter
    gradient match the ``ssd_chunked`` path, and the ops of both
    directions of the fused op sit in the ``ssd`` scope that
    ``ssd_ms.train`` reads."""
    from bench.scopes import scope_of
    from repro.obs.trace import hlo_op_names
    cfg = _t_ssm()
    model = build_model(cfg)
    params = model.init(key)
    batch = make_batch(cfg, key, batch=2, seq=32)
    loss = lambda p: model.loss(p, batch)[0]               # noqa: E731
    want_l, want_g = jax.value_and_grad(loss)(params)
    _take_ssd(monkeypatch, fused=True)
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


def _plain_hidden(params, cfg, tokens, remat=False):
    """``mamba2.forward_hidden`` with the layer scan's body as it is, with
    no checkpoint: autodiff keeps every intermediate it wants."""
    from repro.models import mamba2
    from repro.models.layers import rms_norm
    del remat
    x = params["embed"][tokens]
    x, _ = jax.lax.scan(
        lambda h, lp: (mamba2.ssm_block_forward(lp, cfg, h)[0], None),
        x, params["layers"])
    return (rms_norm(x, params["ln_f"], cfg.norm_eps),
            jnp.zeros((), jnp.float32))


@pytest.mark.parametrize("fused", [False, True], ids=["chunked", "fused"])
def test_mamba2_save_policy_keeps_the_gradient(fused, monkeypatch, key):
    """The layer scan saves by ``mamba2.SAVED`` and recomputes the rest in
    the backward: the same ops on the same inputs, so the loss and every
    parameter gradient equal those of the body with no checkpoint, on both
    SSD paths."""
    from repro.models import mamba2
    _take_ssd(monkeypatch, fused)
    cfg = _t_ssm()
    model = build_model(cfg)
    params = model.init(key)
    batch = make_batch(cfg, key, batch=2, seq=32)
    loss = lambda p: model.loss(p, batch)[0]               # noqa: E731
    got_l, got_g = jax.jit(jax.value_and_grad(loss))(params)
    monkeypatch.setattr(mamba2, "forward_hidden", _plain_hidden)
    want_l, want_g = jax.jit(jax.value_and_grad(loss))(params)
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got_g)[0],
                            jax.tree.leaves(want_g)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(a - b).max() <= 1e-5 * np.abs(b).max(), (
            jax.tree_util.keystr(path), np.abs(a - b).max(), np.abs(b).max())


def _residual_shapes(f, *args):
    """Shapes of what the backward of ``f`` keeps, as
    ``jax.ad_checkpoint.print_saved_residuals`` lists them, with where each
    comes from."""
    import contextlib
    import io
    import re
    from jax.ad_checkpoint import print_saved_residuals
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        print_saved_residuals(f, *args)
    found = []
    for line in out.getvalue().splitlines():
        m = re.match(r"\w+\[([\d,]*)\] (.*)", line)
        found.append((tuple(int(d) for d in m.group(1).split(",") if d),
                      m.group(2)))
    return found


@pytest.mark.parametrize("fused", [False, True], ids=["chunked", "fused"])
def test_mamba2_layer_scan_saves_only_the_named_values(fused, monkeypatch,
                                                       key):
    """What the layer scan stacks for the backward, per layer: its input and
    the in_proj output ``proj`` (``mamba2.SAVED``), and nothing else (not
    the conv's shifted slices, the gate or the norms' values; not the SSD's
    output or the fused op's chunk states, which the backward recomputes),
    so a later edit of the block that adds a save shows here."""
    _take_ssd(monkeypatch, fused)
    cfg = _t_ssm()
    L, B, T = cfg.num_layers, 1, 32
    proj = 2 * cfg.ssm_inner + 2 * cfg.ssm_state + cfg.ssm_num_heads
    model = build_model(cfg)
    params = model.init(key)
    batch = make_batch(cfg, key, batch=B, seq=T)
    res = _residual_shapes(lambda p: model.loss(p, batch)[0], params)
    stacked = sorted(s for s, src in res
                     if "output of scan" in src and "forward_hidden" in src
                     and s[:2] == (L, B))
    assert stacked == [(L, B, T, cfg.d_model), (L, B, T, proj)], res
