"""A toy architecture module, found only by ``test_architecture.py``: a
language model in which each position sees its own token alone, through
an embedding, a two-layer ReLU MLP under its own ``toy_mlp`` scope, and a
head."""
import math

import jax
import jax.numpy as jnp

SCOPES = ("toy_mlp",)
CPU_SIZE = {"width": 8, "hidden": 16, "vocab_size": 16}


def init(key, m):
    d, f, V = m["width"], m["hidden"], m["vocab_size"]
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {"embed": jax.random.normal(k1, (V, d)),
            "w1": jax.random.normal(k2, (d, f)) / math.sqrt(d),
            "w2": jax.random.normal(k3, (f, d)) / math.sqrt(f),
            "head": jax.random.normal(k4, (d, V)) / math.sqrt(d)}


def loss(p, m, tokens, labels):
    x = p["embed"][tokens]
    with jax.named_scope("toy_mlp"):
        x = x + jax.nn.relu(x @ p["w1"]) @ p["w2"]
    lg = x @ p["head"]
    gold = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, -1) - gold)


def train_flops_per_token(m):
    d, f, V = m["width"], m["hidden"], m["vocab_size"]
    return 3 * (2 * 2 * d * f + 2 * d * V)
