"""Inputs made from the seed: episodic image tasks, made on the device.

Each class of a task is a low-frequency random pattern plus pixel noise
(the synthetic class-blob family the program's own episodic stream
uses), so tasks are separable and the heads see real class structure.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

DATA_STREAM = 0xDA7A
STEP_STREAM = 0x57E9


def _class_patterns(key, way: int, size: int, channels: int,
                    class_sep: float):
    base = jax.random.normal(key, (way, size // 4, size // 4, channels))
    base = jax.image.resize(base, (way, size, size, channels), "linear")
    return class_sep * base / jnp.sqrt(jnp.mean(base ** 2) + 1e-8)


def sample_task(key, way: int, shot: int, query: int, size: int,
                channels: int = 3, class_sep: float = 0.5,
                noise: float = 1.5):
    """(support_x, support_y, query_x, query_y): ``way * shot`` support
    images in a random order and ``way * query`` query images grouped by
    class, float32 NHWC."""
    km, ks, kq, kp = jax.random.split(key, 4)
    base = _class_patterns(kp, way, size, channels, class_sep)

    def draw(k, per_class):
        x = base[:, None] + noise * jax.random.normal(
            k, (way, per_class, size, size, channels))
        y = jnp.repeat(jnp.arange(way, dtype=jnp.int32), per_class)
        return x.reshape(-1, size, size, channels), y

    sx, sy = draw(ks, shot)
    qx, qy = draw(kq, query)
    perm = jax.random.permutation(km, sx.shape[0])
    return sx[perm], sy[perm], qx, qy


def train_batch_fn(shape: Dict[str, Any], tasks_per_step: int):
    """A jitted ``(seed_key, step) -> dict of task arrays`` for the
    training cells: every step's tasks differ."""
    way, shot, query = shape["way"], shape["shot"], shape["query_per_class"]
    size = shape["image_size"]

    @jax.jit
    def batch(seed_key, step):
        k = jax.random.fold_in(jax.random.fold_in(seed_key, DATA_STREAM),
                               step)
        sx, sy, qx, qy = jax.vmap(
            lambda kk: sample_task(kk, way, shot, query, size))(
                jax.random.split(k, tasks_per_step))
        return dict(support_x=sx, support_y=sy, query_x=qx, query_y=qy,
                    support_mask=jnp.ones(sy.shape, jnp.float32),
                    query_mask=jnp.ones(qy.shape, jnp.float32))

    return batch


def step_key(seed_key, step: int):
    """The PRNG key a training step receives (its H draws fold from it)."""
    return jax.random.fold_in(jax.random.fold_in(seed_key, STEP_STREAM), step)
