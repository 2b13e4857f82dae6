"""EfficientNet-B0 (Tan & Le, ICML 2019, arXiv 1905.11946, Table 1) as a
frozen, FiLM-conditioned feature extractor: the backbone LITE meta-trains
Simple CNAPs on at 224 px (Bronskill et al., NeurIPS 2021).

Stem conv3x3 stride 2 (32) + BN + swish; 16 MBConv blocks in seven
stages; head conv1x1 to 1280 + BN + swish and a global mean pool.  Each
MBConv block:

    [1x1 expand + BN + swish]  (expand ratio != 1)
    kxk depthwise, stride s (SAME) + BN
    FiLM                        (one site per block, at the expanded width)
    swish
    squeeze-and-excitation      (reduce to max(1, 0.25 * block input))
    1x1 project + BN
    [+ identity]                (stride 1 and equal widths)

Batch norm is frozen, as LITE freezes the backbone: a per-channel affine
from stored gamma, beta, mean and var (eps 1e-3).  No drop-connect: the
backbone runs in eval mode.  1x1 convolutions are convolutions, as the
stem and the depthwise convs are, so every activation stays in XLA's
convolution layout; their weights are stored (cin, cout).  The expand
(1x1, BN, swish) is recomputed in the backward from the block input,
which is 6x narrower, rather than saved.

Weights are random (there are none pretrained here) and the statistics
identity, at which the activations shrink about threefold per block: a
caller that wants unit-scale features sets the statistics from its own
data.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.film import apply_film
from repro.models.backbone import BackboneDef

# Table 1, stages 2-8: (expand ratio, kernel, stride, out channels, repeats)
B0_STAGES: Tuple[Tuple[int, int, int, int, int], ...] = (
    (1, 3, 1, 16, 1),
    (6, 3, 2, 24, 2),
    (6, 5, 2, 40, 2),
    (6, 3, 2, 80, 3),
    (6, 5, 1, 112, 3),
    (6, 5, 2, 192, 4),
    (6, 3, 1, 320, 1),
)


@dataclasses.dataclass(frozen=True)
class EfficientNetConfig:
    in_channels: int = 3
    stem: int = 32
    stages: Tuple[Tuple[int, int, int, int, int], ...] = B0_STAGES
    head: int = 1280
    se_ratio: float = 0.25
    bn_eps: float = 1e-3
    name: str = "efficientnet_b0"


@dataclasses.dataclass(frozen=True)
class MBConvSpec:
    expand: int
    kernel: int
    stride: int
    cin: int
    cout: int
    se: int

    @property
    def mid(self) -> int:
        return self.cin * self.expand

    @property
    def residual(self) -> bool:
        return self.stride == 1 and self.cin == self.cout


def block_specs(cfg: EfficientNetConfig) -> List[MBConvSpec]:
    """The blocks in order: a stage's first block takes its stride and
    the previous width, its repeats stride 1 at the stage's width."""
    out, cin = [], cfg.stem
    for e, k, s, cout, n in cfg.stages:
        for r in range(n):
            out.append(MBConvSpec(e, k, s if r == 0 else 1, cin, cout,
                                  max(1, int(cin * cfg.se_ratio))))
            cin = cout
    return out


def _frozen_bn(c: int) -> Dict[str, jnp.ndarray]:
    """Identity statistics: mean 0, var 1, gamma 1, beta 0."""
    return dict(gamma=jnp.ones((c,)), beta=jnp.zeros((c,)),
                mean=jnp.zeros((c,)), var=jnp.ones((c,)))


def init_efficientnet(key: jax.Array, cfg: EfficientNetConfig) -> Dict:
    """LeCun-normal weights (fan-in = taps x input channels per group),
    zero biases, identity batch-norm statistics (mean 0, var 1, gamma 1,
    beta 0).  The weights are slices of one normal draw: a draw per
    tensor would compile a sampler for each of the network's shapes."""
    specs = block_specs(cfg)
    shapes = [(3, 3, cfg.in_channels, cfg.stem)]
    for spec in specs:
        if spec.expand != 1:
            shapes.append((spec.cin, spec.mid))
        shapes += [(spec.kernel, spec.kernel, 1, spec.mid),
                   (spec.mid, spec.se), (spec.se, spec.mid),
                   (spec.mid, spec.cout)]
    shapes.append((specs[-1].cout, cfg.head))
    sizes = [math.prod(sh) for sh in shapes]
    flat = jax.random.normal(key, (sum(sizes),))
    offsets = [sum(sizes[:i]) for i in range(len(sizes) + 1)]
    ws = iter(flat[a:b].reshape(sh) / math.sqrt(math.prod(sh[:-1]))
              for a, b, sh in zip(offsets, offsets[1:], shapes))

    params: Dict = dict(stem=dict(w=next(ws), bn=_frozen_bn(cfg.stem)),
                        blocks=[])
    for spec in specs:
        blk: Dict = {}
        if spec.expand != 1:
            blk["expand"] = dict(w=next(ws), bn=_frozen_bn(spec.mid))
        blk["dw"] = dict(w=next(ws), bn=_frozen_bn(spec.mid))
        blk["se"] = dict(w1=next(ws), b1=jnp.zeros((spec.se,)),
                         w2=next(ws), b2=jnp.zeros((spec.mid,)))
        blk["project"] = dict(w=next(ws), bn=_frozen_bn(spec.cout))
        params["blocks"].append(blk)
    params["head"] = dict(w=next(ws), bn=_frozen_bn(cfg.head))
    return params


def _bn(h: jnp.ndarray, bn: Dict, eps: float) -> jnp.ndarray:
    scale = bn["gamma"] * jax.lax.rsqrt(bn["var"] + eps)
    return h * scale + (bn["beta"] - bn["mean"] * scale)


def _conv(h: jnp.ndarray, w: jnp.ndarray, stride: int,
          groups: int = 1) -> jnp.ndarray:
    return jax.lax.conv_general_dilated(
        h, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)


def _conv1x1(h: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """A 1x1 convolution of a (cin, cout) weight."""
    return _conv(h, w[None, None], 1)


def _conv1x1_bn_swish(h: jnp.ndarray, p: Dict, eps: float) -> jnp.ndarray:
    return jax.nn.swish(_bn(_conv1x1(h, p["w"]), p["bn"], eps))


def _mbconv(h: jnp.ndarray, p: Dict, spec: MBConvSpec,
            film: Optional[Dict], eps: float) -> jnp.ndarray:
    x = h
    if "expand" in p:
        h = jax.checkpoint(_conv1x1_bn_swish, static_argnums=2)(
            h, p["expand"], eps)
    h = _bn(_conv(h, p["dw"]["w"], spec.stride, groups=spec.mid),
            p["dw"]["bn"], eps)
    if film is not None:
        h = apply_film(h, film["gamma"], film["beta"], channel_axis=-1)
    h = jax.nn.swish(h)
    se = p["se"]
    s = jax.nn.swish(jnp.mean(h, axis=(1, 2)) @ se["w1"] + se["b1"])
    h = h * jax.nn.sigmoid(s @ se["w2"] + se["b2"])[:, None, None, :]
    h = _bn(_conv1x1(h, p["project"]["w"]), p["project"]["bn"], eps)
    return h + x if spec.residual else h


def efficientnet_features(params: Dict, x: jnp.ndarray,
                          film: Optional[Sequence[Dict]],
                          cfg: EfficientNetConfig) -> jnp.ndarray:
    """x: (B, H, W, C) -> (B, head).  One FiLM site per MBConv block."""
    eps = cfg.bn_eps
    h = jax.nn.swish(_bn(_conv(x, params["stem"]["w"], 2),
                         params["stem"]["bn"], eps))
    for i, (spec, blk) in enumerate(zip(block_specs(cfg),
                                        params["blocks"])):
        h = _mbconv(h, blk, spec, None if film is None else film[i], eps)
    h = _conv1x1_bn_swish(h, params["head"], eps)
    return jnp.mean(h, axis=(1, 2))


def make_efficientnet_backbone(
        cfg: EfficientNetConfig = EfficientNetConfig()) -> BackboneDef:
    return BackboneDef(
        init=lambda key: init_efficientnet(key, cfg),
        features=lambda p, x, film: efficientnet_features(p, x, film, cfg),
        feature_dim=cfg.head,
        film_sites=tuple(s.mid for s in block_specs(cfg)),
        name=cfg.name,
    )
