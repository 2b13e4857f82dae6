"""Deep-set task encoder e_phi1 (paper Eq. 2).

Encodes a support set into a permutation-invariant task embedding by MEAN
pooling per-example encodings — the aggregation site LITE subsamples.

Three variants:
  * conv   — small conv net for image supports (paper's encoder).
  * mlp    — for pre-featurized supports (modality-stub embeddings).
  * tokens — bag-of-tokens: normalized token histogram -> MLP, for the
    episodic-LM integration (support examples are token sequences).

Both expose  init(key) -> params  and  encode(params, x) -> (B, task_dim)
per-example embeddings; pooling/LITE happens in the meta-learner.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from repro.common.init import conv_lecun_normal, lecun_normal


@dataclasses.dataclass(frozen=True)
class SetEncoderConfig:
    kind: str = "conv"            # "conv" | "mlp" | "tokens"
    in_channels: int = 3          # conv: image channels; mlp: feature dim; tokens: vocab
    task_dim: int = 64            # embedding width
    conv_blocks: int = 4
    conv_width: int = 32
    mlp_hidden: int = 128


def init_set_encoder(key: jax.Array, cfg: SetEncoderConfig) -> Dict:
    if cfg.kind == "conv":
        params = dict(blocks=[])
        ch = cfg.in_channels
        keys = jax.random.split(key, cfg.conv_blocks + 1)
        for i in range(cfg.conv_blocks):
            params["blocks"].append(
                dict(w=conv_lecun_normal(keys[i], (3, 3, ch, cfg.conv_width)),
                     b=jnp.zeros((cfg.conv_width,)))
            )
            ch = cfg.conv_width
        params["head"] = dict(w=lecun_normal(keys[-1], (ch, cfg.task_dim)),
                              b=jnp.zeros((cfg.task_dim,)))
        return params
    if cfg.kind in ("mlp", "tokens"):
        k1, k2 = jax.random.split(key)
        return dict(
            w1=lecun_normal(k1, (cfg.in_channels, cfg.mlp_hidden)),
            b1=jnp.zeros((cfg.mlp_hidden,)),
            w2=lecun_normal(k2, (cfg.mlp_hidden, cfg.task_dim)),
            b2=jnp.zeros((cfg.task_dim,)),
        )
    raise ValueError(f"unknown set encoder kind: {cfg.kind}")


def _conv(x, w, b):
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def encode_set(params: Dict, x: jnp.ndarray, cfg: SetEncoderConfig) -> jnp.ndarray:
    """Per-example encodings (B, task_dim). No pooling here — LITE pools."""
    if cfg.kind == "conv":
        h = x
        for blk in params["blocks"]:
            h = _conv(h, blk["w"], blk["b"])
            h = jax.nn.relu(h)
            h = jax.lax.reduce_window(
                h, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        h = jnp.mean(h, axis=(1, 2))  # global average pool -> (B, ch)
        return h @ params["head"]["w"] + params["head"]["b"]
    if cfg.kind in ("mlp", "tokens"):
        if cfg.kind == "tokens":
            # (B, S) int ids -> normalized histogram over the vocab
            oh = jax.nn.one_hot(x, cfg.in_channels, dtype=jnp.float32)
            x = jnp.mean(oh, axis=1)
        h = jax.nn.relu(x @ params["w1"] + params["b1"])
        return h @ params["w2"] + params["b2"]
    raise ValueError(cfg.kind)
