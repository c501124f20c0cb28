"""Plain float32 reference of Parle's first rounds (Chaudhari et al.,
2017, Eq. 8a-8d and the scoping of Eq. 9), replica by replica.

Inner step k of replica a, on that replica's rows of step k:
    g   = grad f(y) + (y - x) / gamma                          (8a)
    v   = mu v + g ;  y <- y - lr (g + mu v)                   (Nesterov)
    z   = alpha z + (1 - alpha) y                              (8b)
After L inner steps, the sync:
    xbar = mean_a x^a                                          (8d)
    g_x  = (x - z) + (x - xbar) / rho                          (8c)
    v_x  = mu v_x + g_x ;  x <- x - lr (g_x + mu v_x)
    y, z <- x ;  v <- 0 ;  gamma, rho <- max(f * ., floor), f = 1 - 1/(2B)

The model is the configuration's own architecture module (its
``reference``, bench/harness.py::architecture), its ``init`` and ``loss``.
Returns what the benchmark compares: the replica-mean loss of every inner
step, the norm of each replica's leaf of v_x after the first sync (the
gradient the outer update gets), and the norm of each replica's leaf of
the change of x over all the rounds followed.

Given several devices, replica a lives on device a mod their number, so
that the replicas' inner steps run side by side; the mean of Eq. 8d is
summed on the first device in replica order.  The arithmetic is the same
on one device or many.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness
from bench.traffic import tokens as token_rows


def leaf_norms(tree) -> dict:
    """{path: norm} over a tree without a replica axis."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(jnp.sqrt(jnp.sum(jnp.square(l))))
            for p, l in flat}


def run(conf: dict, job: dict, seed: int, rounds: int,
        devices=None) -> dict:
    """Parle's first ``rounds`` rounds of configuration ``conf`` (a
    configuration file) under the training traffic ``job``, on
    ``devices`` (default: the first device)."""
    arch, m = harness.architecture(conf), conf["model"]
    h = job["parle"]
    n, L, B, T = job["replicas"], job["L"], job["batch"], job["seq"]
    lr, mu, alpha = h["lr"], h["momentum"], h["alpha"]
    f = 1.0 - 1.0 / (2.0 * h["batches_per_epoch"])
    V = m["vocab_size"]
    devices = devices or jax.devices()[:1]
    home = [devices[a % len(devices)] for a in range(n)]
    with jax.default_matmul_precision("highest"):
        x0 = jax.jit(lambda k: arch.init(k, m))(jax.random.PRNGKey(seed))

        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def inner(y, z, v, x, toks, labs, inv_gamma):
            loss, g = jax.value_and_grad(arch.loss)(y, m, toks, labs)
            tm = jax.tree.map
            gy = tm(lambda g, y, x: g + inv_gamma * (y - x), g, y, x)
            v = tm(lambda v, gy: mu * v + gy, v, gy)
            y = tm(lambda y, gy, v: y - lr * (gy + mu * v), y, gy, v)
            z = tm(lambda z, y: alpha * z + (1 - alpha) * y, z, y)
            return loss, y, z, v

        @jax.jit
        def sync(x, z, vx, xbar, inv_rho):
            g = jax.tree.map(lambda x, z, xb: (x - z) + inv_rho * (x - xb),
                             x, z, xbar)
            vx = jax.tree.map(lambda v, g: mu * v + g, vx, g)
            x = jax.tree.map(lambda x, g, v: x - lr * (g + mu * v), x, g, vx)
            return x, vx

        xs = [jax.device_put(x0, d) for d in home]
        vxs = [jax.tree.map(jnp.zeros_like, x) for x in xs]
        gamma, rho = h["gamma0"], h["rho0"]
        losses = np.zeros((rounds * L, n))
        grad = None
        for r in range(rounds):
            ys, zs = ([jax.tree.map(jnp.copy, x) for x in xs]
                      for _ in range(2))
            vs = [jax.tree.map(jnp.zeros_like, x) for x in xs]
            for k in range(L):
                step = r * L + k
                step_loss = []
                for a in range(n):
                    toks, labs = token_rows.rows(seed, step, a, n, B, T, V)
                    loss, ys[a], zs[a], vs[a] = inner(
                        ys[a], zs[a], vs[a], xs[a], toks, labs, 1.0 / gamma)
                    step_loss.append(loss)
                losses[step] = [float(x) for x in step_loss]
            del ys, vs
            xbar = jax.tree.map(
                lambda *t: sum(jax.device_put(v, devices[0]) for v in t) / n,
                *xs)
            out = [sync(xs[a], zs[a], vxs[a], jax.device_put(xbar, home[a]),
                        1.0 / rho) for a in range(n)]
            xs, vxs = [o[0] for o in out], [o[1] for o in out]
            del zs, xbar, out
            if r == 0:
                grad = [leaf_norms(v) for v in vxs]
            gamma = max(gamma * f, h["gamma_min"])
            rho = max(rho * f, h["rho_min"])
        change = [leaf_norms(jax.tree.map(jnp.subtract, x,
                                          jax.device_put(x0, x_dev)))
                  for x, x_dev in zip(xs, home)]
    return {"losses": losses.mean(axis=1).tolist(), "grad": grad,
            "change": change}
