"""The paper-faithful vision backbone: a conv feature extractor with a FiLM
site after every block (paper Fig. B.3 places FiLM after each conv /
depthwise-separable conv in EfficientNet-B0; we reproduce the structure at
configurable width/depth so the SAME code runs the paper's 224x224 regime on
TPU and an 84x84 / reduced regime on CPU tests).

Blocks: conv3x3 -> FiLM -> relu -> maxpool2 (the classic few-shot "Conv-N"
family, which the paper's small-image baselines use), plus an optional
channel-expanding stem matching EfficientNet-ish widths.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.common.init import conv_lecun_normal, lecun_normal
from repro.core.film import apply_film
from repro.kernels import dispatch
from repro.models.backbone import BackboneDef


@dataclasses.dataclass(frozen=True)
class ConvBackboneConfig:
    in_channels: int = 3
    widths: Sequence[int] = (32, 64, 128, 256)
    feature_dim: int = 256
    name: str = "convnet"


def init_conv_backbone(key: jax.Array, cfg: ConvBackboneConfig) -> Dict:
    params: Dict[str, Any] = dict(blocks=[])
    ch = cfg.in_channels
    keys = jax.random.split(key, len(cfg.widths) + 1)
    for i, w in enumerate(cfg.widths):
        params["blocks"].append(
            dict(w=conv_lecun_normal(keys[i], (3, 3, ch, w)),
                 b=jnp.zeros((w,)))
        )
        ch = w
    params["head"] = dict(w=lecun_normal(keys[-1], (ch, cfg.feature_dim)),
                          b=jnp.zeros((cfg.feature_dim,)))
    return params


def conv_features(params: Dict, x: jnp.ndarray, film: Optional[List[Dict]],
                  cfg: ConvBackboneConfig) -> jnp.ndarray:
    """x: (B, H, W, C) -> (B, feature_dim). One FiLM site per block."""
    h = x
    for i, blk in enumerate(params["blocks"]):
        h = jax.lax.conv_general_dilated(
            h, blk["w"], window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + blk["b"]
        if film is not None:
            h = apply_film(h, film[i]["gamma"], film[i]["beta"], channel_axis=-1)
        h = jax.nn.relu(h)
        if h.shape[1] >= 2 and h.shape[2] >= 2:
            h = jax.lax.reduce_window(
                h, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    h = jnp.mean(h, axis=(1, 2))
    w = params["head"]["w"]
    if isinstance(w, dict):
        # serving-time quantized head (ServingWeights leaves "head/w" in
        # the blockwise int8 form): the int8 tiles feed the MXU directly
        return dispatch.int8_matmul(h, w) + params["head"]["b"]
    return h @ w + params["head"]["b"]


def make_conv_backbone(cfg: ConvBackboneConfig) -> BackboneDef:
    return BackboneDef(
        init=lambda key: init_conv_backbone(key, cfg),
        features=lambda p, x, film: conv_features(p, x, film, cfg),
        feature_dim=cfg.feature_dim,
        film_sites=tuple(cfg.widths),
        name=cfg.name,
        quant_native_paths=("head/w",),
    )
