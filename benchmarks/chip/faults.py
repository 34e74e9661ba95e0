"""Faults planted in the program, to show that the check catches them.

Each is a context manager that patches the program while a round step is
built (traced), so the step built inside it carries the fault:

* ``frozen`` — the round step returns the state it was given;
* ``half_batch`` — the loss leaves out the second half of each sequence's
  positions and takes the mean over the rest;
* ``no_exchange`` — the sync's average (``engine.make_sync``) takes client
  0's copy, so the clients never exchange: the round step of a cell on
  several chips, one client a chip, leaves out the exchange between them.
  (On one chip the one-pass sync kernel averages the leaves that tile
  without it, so the fault is for the cells on several chips.)

The benchmark's runs never enter these; ``control.py`` and the tests do.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax.numpy as jnp

from repro import models as repro_models
from repro.core import engine
from repro.launch import steps


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def frozen():
    build = engine.build_round_step

    def build_frozen(*a, **kw):
        step = build(*a, **kw)

        def frozen_step(state, *args):
            _, metrics = step(state, *args)
            return state, metrics
        return frozen_step

    with _patched(engine, "build_round_step", build_frozen):
        yield


@contextlib.contextmanager
def half_batch():
    build = repro_models.build

    def build_half(cfg, call=None):
        model = build(cfg, call)

        def loss(params, batch):
            labels = batch["labels"]
            S = labels.shape[-1]
            kept = jnp.where(jnp.arange(S) < S // 2, labels, -1)
            return model.loss(params, dict(batch, labels=kept))
        return dataclasses.replace(model, loss=loss)

    # launch/steps.py imported ``build`` by name
    with _patched(repro_models, "build", build_half), \
            _patched(steps, "build", build_half):
        yield


@contextlib.contextmanager
def no_exchange():
    def client_0(spec, key, n_clients):
        return lambda p: p[0]

    with _patched(engine, "make_sync", client_0):
        yield


FAULTS = {"frozen": frozen, "half_batch": half_batch,
          "no_exchange": no_exchange}
