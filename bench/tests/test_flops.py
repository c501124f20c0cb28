"""bench/flops.py and the Mamba2 module's counts (bench/reference/mamba2.py)
against counts made by hand, for the ``mamba2-1.3b-s12`` configuration."""
import pytest

from bench import flops, harness
from bench.reference import mamba2

CONF = harness.load_json(harness.BENCH / "configs" / "mamba2-1.3b-s12.json")


def test_one_mamba2_layer():
    in_proj = 2 * 2048 * (2 * 4096 + 2 * 128 + 64)      # 34,865,152
    conv = 2 * 4 * (4096 + 2 * 128)                      # 34,816
    intra = 129 * (128 + 64 * 64)                        # 544,896
    inter = 4 * 128 * 64 * 64                            # 2,097,152
    out_proj = 2 * 4096 * 2048                           # 16,777,216
    assert mamba2.layer_fwd_flops(CONF["model"]) == in_proj + conv + intra \
        + inter + out_proj == 54_319_232


def test_model_and_training_counts():
    fwd = 4 * 54_319_232 + 2 * 2048 * 4190
    assert mamba2.fwd_flops_per_token(CONF["model"]) == fwd
    assert mamba2.train_flops_per_token(CONF["model"]) == 3 * fwd
    assert flops.train_per_token(CONF) == 3 * fwd == 703_317_504


def test_one_parle_kernel_call():
    # a (4, 2048) leaf, 2 replicas, f32: inner reads y, z, v, g, x and
    # writes y, z, v; sync reads x, z, v and the mean, writes x, v
    assert flops.parle_inner_bytes(4 * 2048, 2) == 8 * 2 * 8192 * 4
    assert flops.parle_sync_bytes(4 * 2048, 2) == 11 * 8192 * 4


def test_param_sizes_count_the_mamba2_share():
    per_layer = (2048 * 8512 + 4 * 4352 + 4352 + 64 * 3 + 2048 + 4096
                 + 4096 * 2048)
    assert sum(flops.param_sizes(CONF)) == 4 * per_layer + 2 * 4190 * 2048 \
        + 2048 == 120_561_408


def test_unknown_device_kind_is_an_error():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v9")
