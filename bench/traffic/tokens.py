"""The training cells' token rows, made from the seed alone.

A copy of the trainer's synthetic stream (``repro.data.synthetic``'s
``_token_batch``, unsplit): row ``(step, replica)`` of a run with seed
``s`` and ``n`` replicas is drawn from the key ``s * 100003 + step * n +
replica``, half of its next tokens follow ``(prev * 31 + 7) % V`` and the
rest are uniform.  The reference reads its rows from here, so that it
takes nothing the program made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SEED_MUL = 100003
# the trainer forms ``seed * 100003 + row`` in int32, so a cell's seed is
# folded below this bound (a run's seed may lie past 2**31)
SEED_MOD = 21000


def train_seed(seed: int) -> int:
    return seed % SEED_MOD


def rows(seed: int, step: int, replica: int, n: int, batch: int, seq: int,
         vocab: int):
    """(tokens, labels), each (batch, seq) int32."""
    key = jax.random.PRNGKey(seed * SEED_MUL + step * n + replica)
    base = jax.random.randint(key, (batch, seq + 1), 0, vocab)
    nxt = (base[:, :-1] * 31 + 7) % vocab
    coin = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5, nxt.shape)
    seq_ = jnp.concatenate([base[:, :1], jnp.where(coin, nxt, base[:, 1:])],
                           axis=-1)
    return seq_[:, :-1].astype(jnp.int32), seq_[:, 1:].astype(jnp.int32)
