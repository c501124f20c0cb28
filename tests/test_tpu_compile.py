"""Every Pallas kernel compiles for a described TPU v5e at real widths.

Nothing runs: the TPU compiler builds each kernel for a v5e:2x2 topology
described (not attached) in a fixture, and the HLO must carry the Mosaic
``tpu_custom_call``.  This catches what interpret mode cannot — block
shapes the TPU's (8, 128) tiling refuses, primitives Mosaic cannot lower
— at no chip time.  Shapes: a 2048 x 8192 leaf for the Parle kernels,
qwen2.5-3b attention heads (16 query / 2 kv heads of 128) for flash and
paged attention, mamba2-1.3b SSD heads (64 heads of 64, state 128) for
the scan, whose forward and backward also compile as the benchmark's
training cells run them: 2,048 tokens, vmapped over 2 Parle replicas.
A Mamba2 block's gradient compiles on both sides of its choice of SSD:
the fused op at zamba2-1.2b's widths (state 64), ``ssd_chunked`` at the
smoke widths (chunks of 32, which the kernels do not tile).  The training
cells' whole gradient (2 replicas, 4 layers at mamba2-1.3b widths, 2,048
tokens) fits its temporaries under the layer scan's save policy.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config, smoke_variant
from repro.kernels import flash_attention as fa
from repro.kernels import paged_attention as pa
from repro.kernels import parle_update as pu
from repro.kernels import ssd_scan as ssd
from repro.models import mamba2
from repro.models.model import build_model

LEAF = (2048, 8192)
R = 2                                 # local replicas
F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back from the persistent
    # cache without a chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _ssd_vjp(x, dt, A, B_mat, C_mat, dy):
    """The fused SSD's forward and backward, as a training step takes them."""
    f = lambda *a: ssd.ssd_scan(*a, chunk=128, interpret=False)[0]  # noqa: E731
    y, pull = jax.vjp(f, x, dt, A, B_mat, C_mat)
    return (y,) + pull(dy)


def _cases():
    """name -> (kernel call, [(shape, dtype)] operands)."""
    leaf, rep = [(LEAF, F32)], [((R,) + LEAF, F32)]
    s4, s3 = [((4,), F32)], [((3,), F32)]
    q_heads = [((1, 2048, 16, 128), BF16)] * 3        # GQA expanded
    pool = [((256, 16, 2, 128), BF16)] * 2             # (P, ps, KV, hd)
    # (R, B, T, nh*P), dt (R, B, T, nh), A (R, nh), B/C (R, B, T, N), dy
    ssd_rep = [((R, 1, 2048, 4096), F32), ((R, 1, 2048, 64), F32),
               ((R, 64), F32), ((R, 1, 2048, 128), F32),
               ((R, 1, 2048, 128), F32), ((R, 1, 2048, 4096), F32)]
    return {
        "parle_update_leaf": (
            lambda *a: pu.parle_update_leaf(*a, interpret=False),
            leaf * 5 + s4),
        "parle_update_leaf_bf16": (
            lambda *a: pu.parle_update_leaf(*a, interpret=False),
            [(LEAF, BF16)] + leaf * 2 + [(LEAF, BF16)] + leaf + s4),
        "parle_update_leaf_vocab_head": (     # last dim not a 128 multiple
            lambda *a: pu.parle_update_leaf(*a, interpret=False),
            [((2, 2048, 50280), F32)] * 5 + s4),
        "parle_sync_leaf": (
            lambda *a: pu.parle_sync_leaf(*a, interpret=False),
            rep * 3 + leaf + s4),
        "parle_sync_leaf_bf16_y": (
            lambda *a: pu.parle_sync_leaf(*a, interpret=False,
                                          y_dtype=BF16),
            rep * 3 + leaf + s4),
        "elastic_update_leaf": (
            lambda *a: pu.elastic_update_leaf(*a, interpret=False),
            rep * 3 + leaf + s3),
        "flash_attention": (
            lambda *a: fa.flash_attention(*a, interpret=False), q_heads),
        "paged_attention": (
            lambda *a: pa.paged_attention(*a, interpret=False),
            [((8, 16, 128), BF16)] + pool + [((8, 16), I32), ((8,), I32)]),
        "ssd_scan": (
            lambda *a: ssd.ssd_scan(*a, chunk=128, interpret=False),
            [((2, 512, 4096), F32), ((2, 512, 64), F32), ((64,), F32),
             ((2, 512, 128), F32), ((2, 512, 128), F32)]),
        "ssd_scan_replicas_fwd": (
            jax.vmap(lambda *a: ssd.ssd_scan(*a, chunk=128,
                                             interpret=False)),
            ssd_rep[:5]),
        "ssd_scan_replicas_vjp": (jax.vmap(_ssd_vjp), ssd_rep),
    }


@pytest.mark.parametrize("name", sorted(_cases()))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, operands = _cases()[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in operands]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if name == "ssd_scan_replicas_vjp":        # both directions are kernels
        assert text.count('custom_call_target="tpu_custom_call"') == 2


# config, tokens, whether the block takes the fused SSD
_SSD_BLOCKS = {
    "zamba2_fused": (lambda: get_config("zamba2-1.2b"), 2048, True),
    "smoke_chunked": (lambda: smoke_variant(get_config("mamba2-1.3b")), 512,
                      False),
}


@pytest.mark.parametrize("name", sorted(_SSD_BLOCKS))
def test_ssd_block_compiles_for_v5e(name, one_chip, monkeypatch):
    """One Mamba2 block's gradient, with the backend answering "tpu" as on
    the chip, so ``_fused_ssd`` and the kernels' interpret switch choose
    what they would there."""
    make, T, fused = _SSD_BLOCKS[name]
    cfg = dataclasses.replace(make(), num_layers=1)
    assert mamba2._fused_ssd(cfg, T, None) is False     # CPU backend here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert mamba2._fused_ssd(cfg, T, None) is fused
    lp = jax.eval_shape(lambda: mamba2.init_ssm_layer(jax.random.PRNGKey(0),
                                                      cfg))
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in jax.tree.leaves(lp)]
    x = jax.ShapeDtypeStruct((1, T, cfg.d_model), F32, sharding=one_chip)

    def loss(leaves, x):
        lp_ = jax.tree.unflatten(jax.tree.structure(lp), leaves)
        return mamba2.ssm_block_forward(lp_, cfg, x)[0].sum()

    text = jax.jit(jax.grad(loss)).lower(args, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == (
        2 if fused else 0)


def test_mamba2_training_gradient_fits_for_v5e(one_chip, monkeypatch):
    """The gradient the training cells take, vmapped over 2 Parle replicas:
    mamba2-1.3b cut to 4 layers and 4,190 vocabulary rows, 1 x 2,048
    tokens, the fused SSD on as on the chip.  The layer scan keeps only
    each layer's input and ``proj`` (``mamba2.SAVED``), so the
    temporaries stay under 4 GB (6.65 GB when it kept every intermediate,
    2.54 GB with the policy).  The backward runs the SSD's forward kernel
    again to rebuild its output and states: three kernels, ``ssd_fwd``
    twice and ``ssd_bwd`` once."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get_config("mamba2-1.3b"), num_layers=4,
                              vocab_size=4190)
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: jax.vmap(model.init)(
        jax.random.split(jax.random.PRNGKey(0), R)))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shapes)
    tokens = jax.ShapeDtypeStruct((R, 1, 2048), I32, sharding=one_chip)

    def loss(p, t):
        return model.loss(p, {"tokens": t, "labels": t})[0]

    compiled = jax.jit(jax.vmap(jax.value_and_grad(loss))).lower(
        params, tokens).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 3
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9
