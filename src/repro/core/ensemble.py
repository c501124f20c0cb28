"""Replica-ensemble diagnostics from §1.2 of the paper.

* ``replica_overlap`` — mean pairwise cosine overlap between replicas;
  the paper's claim is that the elastic term keeps this high during
  training and scoping drives it to ~1 at the end (Fig. 1 discussion).
* ``one_shot_average`` — naive weight averaging of independent models
  (the paper shows this is catastrophic without the coupling).
* ``align_permutations`` — greedy layer-wise filter matching used in
  the paper's Fig. 1 experiment to build a permutation-invariant
  overlap for *independently trained* nets (implemented for the MLP
  family: hidden units of layer i are permuted, with the consistent
  row-permutation applied to layer i+1).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.pytree import tree_mean_axis0


@jax.jit
def replica_overlap(replica_tree) -> jnp.ndarray:
    """Mean pairwise cosine similarity across the replica axis.

    The (n, n) Gram matrix is summed leaf by leaf, so the replicas are
    never concatenated into one (n, total) copy."""
    leaves = jax.tree.leaves(replica_tree)
    n = leaves[0].shape[0]
    gram = jnp.stack([jnp.stack([
        sum(jnp.sum(l[a] * l[b]) for l in leaves) for b in range(n)])
        for a in range(n)])
    norm = jnp.sqrt(jnp.diagonal(gram)) + 1e-12
    sim = gram / (norm[:, None] * norm[None, :])
    if n == 1:
        return jnp.asarray(1.0)
    return (jnp.sum(sim) - jnp.trace(sim)) / (n * (n - 1))


@jax.jit
def replica_spread(replica_tree) -> jnp.ndarray:
    """RMS distance of replicas from their mean, normalized by the mean
    norm — goes to 0 as scoping collapses the ensemble."""
    leaves = jax.tree.leaves(replica_tree)
    means = [jnp.mean(l, axis=0, keepdims=True) for l in leaves]
    sq_dist = sum(jnp.sum((l - m) ** 2, axis=tuple(range(1, l.ndim)))
                  for l, m in zip(leaves, means))             # (n,)
    mean_norm = jnp.sqrt(sum(jnp.sum(m ** 2) for m in means))
    return jnp.sqrt(jnp.mean(sq_dist)) / (mean_norm + 1e-12)


def one_shot_average(replica_tree):
    return tree_mean_axis0(replica_tree)


# ------------------------------------------------------------------
# Permutation alignment for MLPs (Fig. 1 experiment)
# ------------------------------------------------------------------

def _greedy_match(cost: np.ndarray) -> np.ndarray:
    """Greedy assignment maximizing total similarity.  cost: (H, H)."""
    H = cost.shape[0]
    cost = cost.copy()
    perm = np.zeros(H, dtype=np.int64)
    used_r, used_c = set(), set()
    flat_order = np.argsort(-cost, axis=None)
    for idx in flat_order:
        r, c = divmod(int(idx), H)
        if r in used_r or c in used_c:
            continue
        perm[r] = c
        used_r.add(r)
        used_c.add(c)
        if len(used_r) == H:
            break
    return perm


def align_mlp(params_ref, params_other):
    """Permute hidden units of ``params_other`` (MLP layout of
    models/convnet.init_mlp) to best match ``params_ref``.  Returns the
    aligned copy."""
    ref_w1 = np.asarray(params_ref["w1"])
    oth = {k: np.asarray(v) for k, v in params_other.items()}
    # match columns of w1 (hidden units) by cosine similarity
    a = ref_w1 / (np.linalg.norm(ref_w1, axis=0, keepdims=True) + 1e-12)
    b = oth["w1"] / (np.linalg.norm(oth["w1"], axis=0, keepdims=True) + 1e-12)
    perm = _greedy_match(a.T @ b)                   # ref unit r -> other unit perm[r]
    out = dict(oth)
    out["w1"] = oth["w1"][:, perm]
    out["b1"] = oth["b1"][perm]
    out["w2"] = oth["w2"][perm][:, :]               # permute rows of next layer
    # second hidden layer
    ref_w2 = np.asarray(params_ref["w2"])
    a2 = ref_w2 / (np.linalg.norm(ref_w2, axis=0, keepdims=True) + 1e-12)
    w2p = out["w2"]
    b2 = w2p / (np.linalg.norm(w2p, axis=0, keepdims=True) + 1e-12)
    perm2 = _greedy_match(a2.T @ b2)
    out["w2"] = w2p[:, perm2]
    out["b2"] = oth["b2"][perm2]
    out["w3"] = oth["w3"][perm2][:, :]
    return {k: jnp.asarray(v) for k, v in out.items()}


def aligned_overlap(params_ref, params_other) -> float:
    """Permutation-invariant overlap between two MLPs (Fig. 1 metric)."""
    aligned = align_mlp(params_ref, params_other)
    ra = jnp.concatenate([jnp.ravel(v) for v in jax.tree.leaves(params_ref)])
    ob = jnp.concatenate([jnp.ravel(v) for v in jax.tree.leaves(aligned)])
    return float(jnp.vdot(ra, ob) /
                 (jnp.linalg.norm(ra) * jnp.linalg.norm(ob) + 1e-12))
