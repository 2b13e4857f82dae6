"""Faults planted under the timed path, to show that ``correct`` catches
them: each wraps the program's training step.

    train.run(cell, ..., wrap_step=faults.TRAIN["half_batch"])
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp


def state_unchanged(step):
    """The step computes, but hands back the state it was given."""
    def f(state, batch):
        _, metrics = step(state, batch)
        return state, metrics
    return f


def half_batch(step):
    """Half of each task's queries left out; the loss is the mean over
    the rest."""
    def f(state, batch):
        t = batch["tasks"]
        m = t.query_mask.shape[-1]
        keep = (jnp.arange(m) < m // 2).astype(t.query_mask.dtype)
        tasks = dataclasses.replace(t, query_mask=t.query_mask * keep)
        return step(state, dict(batch, tasks=tasks))
    return f


TRAIN = dict(state_unchanged=state_unchanged, half_batch=half_batch)
