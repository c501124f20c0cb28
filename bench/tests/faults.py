"""Faults planted under a run, in this process only, to show that the
comparison that decides ``correct`` catches them.

* ``unchanged``: the round returns the state it was given (its metrics
  are the real round's).
* ``half_batch``: the loss is the mean over the first half of every
  sequence's positions; the rest of the batch is left out.
"""
from __future__ import annotations

import contextlib
from unittest import mock

from bench import harness


@contextlib.contextmanager
def planted(name: str):
    harness.add_src()
    if name == "unchanged":
        from repro.core import parle
        real = parle._make_round_body

        def body(*a, **kw):
            fn = real(*a, **kw)

            def round_fn(state, batches):
                _, metrics = fn(state, batches)
                return state, metrics
            return round_fn

        with mock.patch.object(parle, "_make_round_body", body):
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
    else:
        raise ValueError(f"no fault {name!r}")
