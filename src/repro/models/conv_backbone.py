"""The paper-faithful vision backbone: a conv feature extractor with a FiLM
site after every block (paper Fig. B.3 places FiLM after each conv /
depthwise-separable conv in EfficientNet-B0; we reproduce the structure at
configurable width/depth so the SAME code runs the paper's 224x224 regime on
TPU and an 84x84 / reduced regime on CPU tests).

Blocks: conv3x3 -> FiLM -> relu -> maxpool2 (the classic few-shot "Conv-N"
family, which the paper's small-image baselines use), plus an optional
channel-expanding stem matching EfficientNet-ish widths.

On TPU the image block (few input channels, even H and W, no FiLM, a
batch that fills at least a quarter of the kernel's 128 lanes) runs as
fused Pallas kernels (:mod:`repro.kernels.conv_pool`), forward and, for
the weights and bias, backward, so neither its full-resolution pre-pool
activation nor that activation's gradient reaches HBM.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.common.init import conv_lecun_normal, lecun_normal
from repro.core.film import apply_film
from repro.kernels import conv_pool, dispatch, ops
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


def _conv_block(h: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                film: Optional[Dict]) -> jnp.ndarray:
    """conv3x3 (SAME) + bias -> FiLM -> relu -> maxpool2, in XLA."""
    h = jax.lax.conv_general_dilated(
        h, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + b
    if film is not None:
        h = apply_film(h, film["gamma"], film["beta"], channel_axis=-1)
    h = jax.nn.relu(h)
    if h.shape[1] >= 2 and h.shape[2] >= 2:
        h = jax.lax.reduce_window(
            h, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    return h


@jax.custom_vjp
def _image_block(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray):
    """The FiLM-free image block: the fused kernel forward, and for the
    weights and bias (the H pass, the query path) the fused kernel
    backward.  A call whose image carries a tangent takes the XLA
    composite and its VJP instead, so the input gradient is exact."""
    return ops.conv3x3_relu_pool(x, w, b)


def _image_block_fwd(x, w, b):
    if x.perturbed:
        return jax.vjp(functools.partial(_conv_block, film=None),
                       x.value, w.value, b.value)
    x, w, b = x.value, w.value, b.value
    return ops.conv3x3_relu_pool(x, w, b), (x, w, b)


def _image_block_bwd(res, g):
    if not isinstance(res, tuple):    # the composite's VJP
        return res(g)
    return (None, *ops.conv3x3_relu_pool_bwd(*res, g))


_image_block.defvjp(_image_block_fwd, _image_block_bwd, symbolic_zeros=True)


def _fuses(h: jnp.ndarray, film: Optional[Dict]) -> bool:
    """The fused kernel takes an image (<= 4 channels) with even H and W
    and no FiLM (a negative FiLM scale would not commute with the max),
    on TPU only, and a batch of at least a quarter of its lanes: it pads
    the batch to whole 128-image lane blocks, so smaller calls (serving's
    query chunks) would pay for lanes they leave empty."""
    return (film is None and h.shape[-1] <= 4 and h.shape[1] % 2 == 0
            and h.shape[2] % 2 == 0
            and h.shape[0] >= conv_pool.LANES // 4
            and dispatch.pallas_native())


def conv_features(params: Dict, x: jnp.ndarray, film: Optional[List[Dict]],
                  cfg: ConvBackboneConfig) -> jnp.ndarray:
    """x: (B, H, W, C) -> (B, feature_dim). One FiLM site per block."""
    h = x
    for i, blk in enumerate(params["blocks"]):
        site = None if film is None else film[i]
        if _fuses(h, site):
            h = _image_block(h, blk["w"], blk["b"])
        else:
            h = _conv_block(h, blk["w"], blk["b"], site)
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
