"""Deep-set task encoder e_phi1 (paper Eq. 2).

Encodes a support set into a permutation-invariant task embedding by MEAN
pooling per-example encodings — the aggregation site LITE subsamples.

Three variants:
  * conv   — small conv net for image supports (paper's encoder): the
    conv backbone of :mod:`repro.models.conv_backbone` at width
    ``conv_width`` x ``conv_blocks`` with a ``task_dim`` head, so the
    set encoder's image block takes the same fused kernel on TPU.
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

from repro.common.init import lecun_normal
from repro.models.conv_backbone import (ConvBackboneConfig, conv_features,
                                        init_conv_backbone)


@dataclasses.dataclass(frozen=True)
class SetEncoderConfig:
    kind: str = "conv"            # "conv" | "mlp" | "tokens"
    in_channels: int = 3          # conv: image channels; mlp: feature dim; tokens: vocab
    task_dim: int = 64            # embedding width
    conv_blocks: int = 4
    conv_width: int = 32
    mlp_hidden: int = 128


def _conv_config(cfg: SetEncoderConfig) -> ConvBackboneConfig:
    return ConvBackboneConfig(in_channels=cfg.in_channels,
                              widths=(cfg.conv_width,) * cfg.conv_blocks,
                              feature_dim=cfg.task_dim)


def init_set_encoder(key: jax.Array, cfg: SetEncoderConfig) -> Dict:
    if cfg.kind == "conv":
        return init_conv_backbone(key, _conv_config(cfg))
    if cfg.kind in ("mlp", "tokens"):
        k1, k2 = jax.random.split(key)
        return dict(
            w1=lecun_normal(k1, (cfg.in_channels, cfg.mlp_hidden)),
            b1=jnp.zeros((cfg.mlp_hidden,)),
            w2=lecun_normal(k2, (cfg.mlp_hidden, cfg.task_dim)),
            b2=jnp.zeros((cfg.task_dim,)),
        )
    raise ValueError(f"unknown set encoder kind: {cfg.kind}")


def encode_set(params: Dict, x: jnp.ndarray, cfg: SetEncoderConfig) -> jnp.ndarray:
    """Per-example encodings (B, task_dim). No pooling here — LITE pools."""
    if cfg.kind == "conv":
        return conv_features(params, x, None, _conv_config(cfg))
    if cfg.kind in ("mlp", "tokens"):
        if cfg.kind == "tokens":
            # (B, S) int ids -> normalized histogram over the vocab
            oh = jax.nn.one_hot(x, cfg.in_channels, dtype=jnp.float32)
            x = jnp.mean(oh, axis=1)
        h = jax.nn.relu(x @ params["w1"] + params["b1"])
        return h @ params["w2"] + params["b2"]
    raise ValueError(cfg.kind)
