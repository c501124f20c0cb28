"""Every Pallas kernel compiles for a described TPU v5e at real widths.

Nothing runs: the TPU compiler builds each kernel for a v5e:2x2 topology
described (not attached) in a fixture, and the HLO must carry the Mosaic
``tpu_custom_call``.  This catches what interpret mode cannot — block
shapes the TPU's (8, 128) tiling refuses, primitives Mosaic cannot lower
— at no chip time.  Shapes: a 2048 x 8192 leaf for the Parle kernels,
qwen2.5-3b attention heads (16 query / 2 kv heads of 128) for flash and
paged attention, mamba2-1.3b SSD heads (64 heads of 64, state 128) for
the scan.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import paged_attention as pa
from repro.kernels import parle_update as pu
from repro.kernels import ssd_scan as ssd

LEAF = (2048, 8192)
M = LEAF[0] * LEAF[1]
R, N_GLOBAL = 2, 4                    # local replicas; replicas gathered
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


def _cases():
    """name -> (kernel call, [(shape, dtype)] operands)."""
    leaf, rep = [(LEAF, F32)], [((R,) + LEAF, F32)]
    flat, rep_flat = [((M,), F32)], [((R, M), F32)]
    s4, s3 = [((4,), F32)], [((3,), F32)]
    q_heads = [((1, 2048, 16, 128), BF16)] * 3        # GQA expanded
    pool = [((256, 16, 2, 128), BF16)] * 2             # (P, ps, KV, hd)
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
        "quantize_ef_flat": (
            lambda c: pu.quantize_ef_flat(c, interpret=False), rep_flat),
        "parle_sync_dequant_flat": (
            lambda *a: pu.parle_sync_dequant_flat(*a, interpret=False),
            rep_flat * 3 + [((N_GLOBAL, M), jnp.int8),
                            ((N_GLOBAL, M // 1024), F32)] + s4),
        "parle_apply_quantize_flat": (
            lambda *a: pu.parle_apply_quantize_flat(*a, interpret=False),
            rep_flat * 3 + flat + rep_flat + s4),
        "flash_attention": (
            lambda *a: fa.flash_attention(*a, interpret=False), q_heads),
        "paged_attention": (
            lambda *a: pa.paged_attention(*a, interpret=False),
            [((8, 16, 128), BF16)] + pool + [((8, 16), I32), ((8,), I32)]),
        "ssd_scan": (
            lambda *a: ssd.ssd_scan(*a, chunk=128, interpret=False),
            [((2, 512, 64, 64), F32), ((2, 512, 64), F32), ((64,), F32),
             ((2, 512, 128), F32), ((2, 512, 128), F32)]),
    }


@pytest.mark.parametrize("name", sorted(_cases()))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, operands = _cases()[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in operands]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
