"""Random weights from the seed, made on the device in one jitted call.

The tree is the one the program's ProtoNets learner takes (``bb``), built
here from the configuration's sizes so that neither the program's own
initialisers nor anything it made reaches the reference.  Conv kernels
and dense layers are LeCun-normal (fan-in counts every tap of a conv's
receptive field), biases zero.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def _lecun(key, shape):
    fan_in = 1
    for s in shape[:-1]:
        fan_in *= s
    return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(
        jnp.float32(fan_in))


def _conv_stack(key, in_ch: int, widths, out_dim: int) -> Dict[str, Any]:
    blocks = []
    ch = in_ch
    for i, w in enumerate(widths):
        blocks.append(dict(w=_lecun(jax.random.fold_in(key, i), (3, 3, ch, w)),
                           b=jnp.zeros((w,), jnp.float32)))
        ch = w
    head = dict(w=_lecun(jax.random.fold_in(key, len(widths)), (ch, out_dim)),
                b=jnp.zeros((out_dim,), jnp.float32))
    return dict(blocks=blocks, head=head)


def init_params(config: Dict[str, Any], key) -> Dict[str, Any]:
    bb = config["backbone"]
    params = dict(bb=_conv_stack(jax.random.fold_in(key, 0),
                                 bb["in_channels"], bb["widths"],
                                 bb["feature_dim"]))
    if config["learner"] != "protonets":
        raise NotImplementedError(config["learner"])
    return params


def make_params(config: Dict[str, Any], seed: int):
    """The weights for ``seed``, made on the default device by one jitted
    call."""
    key = jax.random.fold_in(jax.random.key(seed), 0x5EED)
    return jax.jit(lambda k: init_params(config, k))(key)
