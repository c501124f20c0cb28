"""Operations and bytes from shapes, and the chip's peaks.

Model FLOPs come from the configuration's architecture module
(``train_flops_per_token``), which counts the matmuls and the sequence
mixer's contractions that the forward pass needs (a multiply-add is 2),
over the causal half where a product is masked; a backward pass costs
twice its forward.  The embedding lookup, norms, gates and recomputation
do not count.  Kernel bytes are what a call reads and writes, from its
operand shapes.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

from bench import harness

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's peaks from ``peaks.json``; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


def train_per_token(conf: dict) -> float:
    """Forward and backward FLOPs per trained token of configuration
    ``conf``, from its architecture module."""
    return harness.architecture(conf).train_flops_per_token(conf["model"])


def param_sizes(conf: dict) -> list[int]:
    """Element count of every parameter leaf of configuration ``conf``,
    from its architecture module's ``init``."""
    import jax
    arch = harness.architecture(conf)
    shapes = jax.eval_shape(lambda k: arch.init(k, conf["model"]),
                            jax.ShapeDtypeStruct((2,), "uint32"))
    return [math.prod(s.shape) for s in jax.tree.leaves(shapes)]


def parle_inner_bytes(elems: int, n: int, item: int = 4) -> int:
    """One inner-update call on a leaf of ``elems`` per replica: reads y,
    z, v, g, x and writes y, z, v, each (n, elems)."""
    return 8 * n * elems * item


def parle_sync_bytes(elems: int, n: int, item: int = 4) -> int:
    """One sync-update call: reads x, z, v (n, elems) and the mean xbar
    (elems), writes x and v (n, elems)."""
    return (5 * n + 1) * elems * item
