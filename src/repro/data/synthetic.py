"""Synthetic data pipeline (offline container — no real datasets).

Two stream kinds:

* Token streams for the assigned LM architectures: a deterministic
  bigram-ish Markov source so that models have learnable structure
  (loss strictly below ln(V) is achievable) and runs are reproducible.
* Classification streams for the paper-faithful Table 1/2 analogues:
  a teacher-MLP labelling of Gaussian inputs — a non-convex task with a
  real generalization gap, which is what Parle's claims are about.

Replica splitting (paper §5): ``split_for_replicas`` partitions the
underlying sample index space evenly across n replicas, so replica a
only ever draws from its shard — the only cross-shard information path
is the elastic term, exactly the experiment in Table 2.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np


# ------------------------------------------------------------------
# Token streams (LM families)
# ------------------------------------------------------------------

@dataclass
class TokenStream:
    vocab_size: int
    seq_len: int
    batch_size: int
    seed: int = 0
    num_codebooks: int = 0        # audio: emit (B, K, T)
    shard: tuple[int, int] = (0, 1)   # (index, count) — replica split
    split: bool = False           # True: draw ONLY from shard's key block

    def __post_init__(self):
        rng = np.random.RandomState(self.seed)
        # sparse-ish Markov transition table over a reduced state space
        self._order = rng.permutation(self.vocab_size)

    def batch(self, step: int) -> dict:
        """Deterministic pseudo-Markov batch for ``step``."""
        idx, cnt = self.shard
        return _token_batch(step, idx, cnt, self.seed, self.batch_size,
                            self.seq_len, self.vocab_size,
                            self.num_codebooks, split=self.split)


def _token_batch(step, idx, cnt, seed, batch_size, seq_len, vocab_size,
                 num_codebooks, split=False):
    """Body of :meth:`TokenStream.batch`, traceable in ``step`` (the
    fused-round batch stager jits/vmaps it over a whole round).

    The PRNG index IS the sample identity of this synthetic stream, so
    data splitting (paper §5) is a partition of the key space:
    split=True gives shard ``idx`` its own disjoint 2^20-wide key block
    — no sample is ever drawn by two shards; split=False interleaves
    all shards through the full stream (decorrelated draws from the
    same data — every shard can see every sample)."""
    base_idx = idx * (1 << 20) + step if split else step * cnt + idx
    key = jax.random.PRNGKey(seed * 100003 + base_idx)
    shape = ((batch_size, num_codebooks, seq_len + 1) if num_codebooks
             else (batch_size, seq_len + 1))
    base = jax.random.randint(key, shape, 0, vocab_size)
    # impose structure: next token = (prev * 31 + noise) % V  half the time
    nxt = (base[..., :-1] * 31 + 7) % vocab_size
    coin = jax.random.bernoulli(jax.random.fold_in(key, 1),
                                0.5, nxt.shape)
    seq = jnp.where(coin, nxt, base[..., 1:])
    seq = jnp.concatenate([base[..., :1], seq], axis=-1)
    return {"tokens": seq[..., :-1].astype(jnp.int32),
            "labels": seq[..., 1:].astype(jnp.int32)}


# ------------------------------------------------------------------
# Classification streams (paper-faithful experiments)
# ------------------------------------------------------------------

@dataclass
class TeacherTask:
    """Fixed teacher-MLP labelled Gaussian classification task."""
    in_dim: int = 64
    hidden: int = 96
    num_classes: int = 10
    num_train: int = 4096
    num_test: int = 1024
    seed: int = 0
    label_noise: float = 0.05

    def __post_init__(self):
        rng = np.random.RandomState(self.seed)
        w1 = rng.randn(self.in_dim, self.hidden) / np.sqrt(self.in_dim)
        w2 = rng.randn(self.hidden, self.num_classes) / np.sqrt(self.hidden)
        xs = rng.randn(self.num_train + self.num_test, self.in_dim).astype(np.float32)
        logits = np.tanh(xs @ w1) @ w2
        ys = np.argmax(logits, axis=1)
        flip = rng.rand(len(ys)) < self.label_noise
        ys = np.where(flip, rng.randint(0, self.num_classes, len(ys)), ys)
        self.x_train = jnp.asarray(xs[: self.num_train])
        self.y_train = jnp.asarray(ys[: self.num_train].astype(np.int32))
        self.x_test = jnp.asarray(xs[self.num_train:])
        self.y_test = jnp.asarray(ys[self.num_train:].astype(np.int32))

    # ---- sampling -----------------------------------------------
    def train_batch(self, step: int, batch_size: int,
                    shard: tuple[int, int] = (0, 1)) -> dict:
        """Replica shard (a, n): draw only from the a-th 1/n of the data
        (paper §5 splitting).  Every sample is in exactly one shard."""
        a, n = shard
        per = self.num_train // n
        lo = a * per
        rng = np.random.RandomState((step * n + a) * 7919 + 13)
        idx = lo + rng.randint(0, per, batch_size)
        return {"x": self.x_train[idx], "y": self.y_train[idx]}

    def test_batch(self) -> dict:
        return {"x": self.x_test, "y": self.y_test}

    def batches_per_epoch(self, batch_size: int) -> int:
        return max(1, self.num_train // batch_size)


def replica_batches(task_or_stream, step: int, batch_size: int, n_replicas: int,
                    split: bool = False):
    """Stack per-replica batches along a leading replica axis.

    split=False: every replica draws from the full data (paper §4).
    split=True : replica a draws only from shard a (paper §5).
    """
    outs = []
    for a in range(n_replicas):
        shard = (a, n_replicas) if split else (0, 1)
        if isinstance(task_or_stream, TeacherTask):
            b = task_or_stream.train_batch(step * n_replicas + a
                                           if not split else step,
                                           batch_size, shard)
        else:
            s = task_or_stream
            # split=False keeps every replica's draws interleaved through
            # the full stream (shard index a decorrelates them);
            # split=True switches the key derivation to per-shard
            # disjoint blocks — the shard tuple alone does NOT split a
            # token stream (both modes walk all of it otherwise)
            s2 = TokenStream(s.vocab_size, s.seq_len, batch_size,
                             seed=s.seed, num_codebooks=s.num_codebooks,
                             shard=(a, n_replicas), split=split)
            b = s2.batch(step)
        outs.append(b)
    return jax.tree.map(lambda *xs: jnp.stack(xs), *outs)


def make_round_batch_fn(stream: TokenStream, L: int, batch_size: int,
                        n_replicas: int, split: bool = False,
                        replica_offset: int = 0,
                        n_total: Optional[int] = None):
    """Staging for fused L-step rounds: ONE jitted dispatch builds all
    L x n batches of a round — (L, n, B, T) leaves, bit-identical to
    stacking :func:`replica_batches` per step IN EITHER SPLIT MODE
    (regression-tested in tests/test_round_fused.py).  The per-step
    dispatch loop pays ~20 un-jitted host ops per step for the same
    work; the round driver double-buffers this call against the round's
    device compute.

    ``replica_offset`` / ``n_total``: an async pod worker owning
    replicas [offset, offset + n) of a fleet of n_total draws exactly
    the shard streams a single-process n_total run would hand those
    replicas (defaults leave the single-process derivation untouched).
    """
    n = n_replicas
    cnt = n if n_total is None else n_total

    def one(step, a):
        return _token_batch(step, a, cnt, stream.seed, batch_size,
                            stream.seq_len, stream.vocab_size,
                            stream.num_codebooks, split=split)

    @jax.jit
    @jax.named_scope("stage")
    def stage(start_step):
        steps = start_step + jnp.arange(L)
        return jax.vmap(lambda s: jax.vmap(lambda a: one(s, a))(
            replica_offset + jnp.arange(n)))(steps)

    return stage
