"""The whole training step's share of the chip's bf16 peak: model FLOPs
per trained token (forward and backward, from the configuration's
architecture module through bench/flops.py) times the traced window's
tokens per second per chip, over the peak from bench/peaks.json."""
from bench import flops


def read(art):
    if art.get("kind") != "train":
        return None
    peak = flops.peaks(art["devices"][0].device_kind)["bf16_flops_per_s"]
    per_chip = (art["rounds"] * art["tokens_per_round"] / art["window_s"]
                / art["chips"])
    return 100.0 * flops.train_per_token(art["config"]) * per_chip / peak
