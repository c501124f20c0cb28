"""Faults planted under a run, in this process only, to show that the
comparison that decides ``correct`` catches them.

* ``unchanged``: every inner step and every sync returns the state it was
  given, so the round returns its state unchanged, on any mesh.
* ``half_batch``: the loss is the mean over the first half of every
  sequence's positions; the rest of the batch is left out.
* ``local_mean``: the sync's mean (Eq. 8d) is taken over the replicas a
  chip holds, with no exchange between chips.  On one chip it changes
  nothing; a cell over a mesh has to catch it.
"""
from __future__ import annotations

import contextlib
from unittest import mock

from bench import harness

FAULTS = ("unchanged", "half_batch", "local_mean")


def for_chips(chips: int) -> tuple:
    """The faults a training cell on ``chips`` chips can have:
    ``local_mean`` only where the replicas span several chips."""
    return FAULTS if chips > 1 else FAULTS[:2]


@contextlib.contextmanager
def planted(name: str):
    harness.add_src()
    if name == "unchanged":
        from repro.core import parle

        def same(state, *a, **kw):
            return state

        with mock.patch.object(parle, "inner_step", same), \
                mock.patch.object(parle, "sync_step", same):
            yield
    elif name == "half_batch":
        from repro.models import model as model_mod
        real = model_mod._lm_loss

        def lm_loss(hidden_fn, cfg):
            fn = real(hidden_fn, cfg)

            def loss(params, batch):
                half = batch["tokens"].shape[-1] // 2
                return fn(params, {k: v[..., :half] for k, v in batch.items()})
            return loss

        with mock.patch.object(model_mod, "_lm_loss", lm_loss):
            yield
    elif name == "local_mean":
        from repro.core import parle
        real = parle._sync_stats

        def sync_stats(state, cfg, axis_name, *a, **kw):
            return real(state, cfg, None, *a, **kw)

        with mock.patch.object(parle, "_sync_stats", sync_stats):
            yield
    else:
        raise ValueError(f"no fault {name!r}")
