"""Weight initializers (fan-in scaled, matching common LM/vision practice)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def lecun_normal(key, shape, dtype=jnp.float32, in_axis: int = -2):
    """LeCun normal: std = 1/sqrt(fan_in). Default fan-in axis is -2
    (i.e. weight laid out (in, out))."""
    fan_in = shape[in_axis] if len(shape) > 1 else shape[0]
    std = 1.0 / np.sqrt(fan_in)
    return std * jax.random.normal(key, shape, dtype)


def conv_lecun_normal(key, shape, dtype=jnp.float32):
    """LeCun normal for an HWIO conv kernel: every input tap of the
    receptive field counts, fan_in = kh * kw * c_in.  (Scaling by c_in
    alone grows activations about 3x per 3x3 layer: at four layers on
    224x224 inputs the FiLM'd features reach 1e9 and the Simple CNAPs
    covariance loses all precision.)"""
    std = 1.0 / np.sqrt(np.prod(shape[:-1]))
    return std * jax.random.normal(key, shape, dtype)


def normal_init(key, shape, std=0.02, dtype=jnp.float32):
    return std * jax.random.normal(key, shape, dtype)


def truncated_normal_init(key, shape, std=0.02, dtype=jnp.float32):
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def zeros_init(key, shape, dtype=jnp.float32):
    del key
    return jnp.zeros(shape, dtype)


def ones_init(key, shape, dtype=jnp.float32):
    del key
    return jnp.ones(shape, dtype)


def split_keys(key, n: int):
    """Split into n keys; convenience with unpacking."""
    return list(jax.random.split(key, n))
