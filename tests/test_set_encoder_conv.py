"""The conv set encoder is the conv backbone (``conv_features`` without
FiLM, and its initialiser): for the conv shapes the launchers, the chip
smoke and the tests build, its parameter tree, initial values and outputs
are those of the set encoder's own conv3x3 -> relu -> max-pool loop, kept
here as the reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.init import conv_lecun_normal, lecun_normal
from repro.core.set_encoder import (SetEncoderConfig, encode_set,
                                    init_set_encoder)


def _own_init(key, cfg):
    params = dict(blocks=[])
    ch = cfg.in_channels
    keys = jax.random.split(key, cfg.conv_blocks + 1)
    for i in range(cfg.conv_blocks):
        params["blocks"].append(
            dict(w=conv_lecun_normal(keys[i], (3, 3, ch, cfg.conv_width)),
                 b=jnp.zeros((cfg.conv_width,))))
        ch = cfg.conv_width
    params["head"] = dict(w=lecun_normal(keys[-1], (ch, cfg.task_dim)),
                          b=jnp.zeros((cfg.task_dim,)))
    return params


def _own_encode(params, x):
    h = x
    for blk in params["blocks"]:
        h = jax.lax.conv_general_dilated(
            h, blk["w"], window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + blk["b"]
        h = jax.nn.relu(h)
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
    h = jnp.mean(h, axis=(1, 2))
    return h @ params["head"]["w"] + params["head"]["b"]


@pytest.mark.parametrize("blocks,width,task_dim,size", [
    (4, 32, 64, 16),     # SetEncoderConfig's defaults
    (2, 16, 32, 24),     # the launchers' default shape
    (2, 8, 16, 12),      # the chip smoke's CPU shape
    (4, 32, 128, 32),    # the chip smoke's widths, at a CPU size
])
def test_conv_set_encoder_unchanged(blocks, width, task_dim, size):
    cfg = SetEncoderConfig(kind="conv", conv_blocks=blocks, conv_width=width,
                           task_dim=task_dim)
    key = jax.random.key(7)
    params = init_set_encoder(key, cfg)
    own = _own_init(key, cfg)
    assert jax.tree.structure(params) == jax.tree.structure(own)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(own)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    x = jax.random.normal(jax.random.key(8), (5, size, size, 3))
    np.testing.assert_array_equal(np.asarray(encode_set(params, x, cfg)),
                                  np.asarray(_own_encode(own, x)))
