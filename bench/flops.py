"""Operations and bytes from shapes, and the chip's peaks.

Model FLOPs count the matmuls and the state space model's contractions
that the forward pass needs (a multiply-add is 2), over the causal half
where a product is masked; a backward pass costs twice its forward.  The
embedding lookup, norms, gates and recomputation do not count.
Kernel bytes are what a call reads and writes, from its operand shapes.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's peaks from ``peaks.json``; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


def ssm_layer_fwd(m: dict) -> float:
    """Forward FLOPs per token of one Mamba2 layer (chunked SSD)."""
    d, N, P, W = m["d_model"], m["ssm_state"], m["ssm_head_dim"], m["ssm_conv"]
    di = m["ssm_expand"] * d
    nh, Q = di // P, m["ssm_chunk"]
    in_proj = 2 * d * (2 * di + 2 * N + nh)
    conv = 2 * W * (di + 2 * N)
    # within a chunk each token meets (Q + 1) / 2 earlier ones on average:
    # C.B over N, then the weighted sum over P for every head
    intra = 2 * (Q + 1) / 2 * (N + nh * P)
    # the chunk's state update and its read-out: N x P per head each
    inter = 2 * 2 * N * P * nh
    out_proj = 2 * di * d
    return in_proj + conv + intra + inter + out_proj


def model_fwd(m: dict) -> float:
    """Forward FLOPs per token of the whole model: its Mamba2 layers and
    the head."""
    return (m["num_layers"] * ssm_layer_fwd(m)
            + 2 * m["d_model"] * m["vocab_size"])


def train_per_token(m: dict) -> float:
    """Forward and backward FLOPs per trained token."""
    return 3 * model_fwd(m)


def param_sizes(m: dict) -> list[int]:
    """Element count of every parameter leaf."""
    import math

    import jax

    from bench.reference import models
    shapes = jax.eval_shape(lambda k: models.init(k, m),
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
