"""The episodic workload's shape, shared by both launchers and
``chip_smoke.py``: backbone widths, task shape and LITE knobs come from
the command line, and one function builds the learner they all run.

    ap = argparse.ArgumentParser()
    add_shape_args(ap, EpisodicShape())
    shape = shape_from_args(ap.parse_args())
    learner = build_learner("simple_cnaps", shape)

The defaults are a toy size that keeps CPU runs fast.  The backbone's own
supported configuration is ``ConvBackboneConfig()`` (widths 32/64/128/256,
feature_dim 256); the paper's regime adds 224x224 images, support sets of
up to 1000 images and |H| around 40:

    --widths 32,64,128,256 --feature-dim 256 --image-size 224 \\
        --way 10 --shot 100 --query-per-class 10 --lite-h 40 --lite-chunk 100

The CNAPs-family set encoder follows the backbone: one conv block per
backbone stage at the first stage's width, task embedding half the feature
width.

``--backbone efficientnet_b0`` builds EfficientNet-B0 at Table 1's widths
(``repro.models.efficientnet``; 16 FiLM sites, 1280 features), which
``--widths`` and ``--feature-dim`` do not touch, with the CNAPs set
encoder (five conv3x3 blocks of 64 channels, task embedding 64): the
paper's LITE regime is

    --backbone efficientnet_b0 --image-size 224 --way 10 --shot 100 \\
        --query-per-class 10 --lite-h 40 --lite-chunk 100
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Optional, Tuple

from repro.core.meta_learners import MetaLearner, MetaLearnerConfig, make_learner
from repro.core.set_encoder import SetEncoderConfig
from repro.data.episodic import EpisodicImageConfig, HostEpisodicConfig
from repro.models.conv_backbone import ConvBackboneConfig, make_conv_backbone
from repro.models.efficientnet import make_efficientnet_backbone

BACKBONES = ("conv", "efficientnet_b0")


@dataclasses.dataclass(frozen=True)
class EpisodicShape:
    widths: Tuple[int, ...] = (16, 32)
    feature_dim: int = 64
    way: int = 5
    shot: int = 10
    query_per_class: int = 6
    image_size: int = 24
    lite_h: int = 8
    lite_chunk: Optional[int] = None
    backbone: str = "conv"

    def image_config(self) -> EpisodicImageConfig:
        return EpisodicImageConfig(way=self.way, shot=self.shot,
                                   query_per_class=self.query_per_class,
                                   image_size=self.image_size)

    def host_config(self) -> HostEpisodicConfig:
        return HostEpisodicConfig(way=self.way, shot=self.shot,
                                  query_per_class=self.query_per_class,
                                  image_size=self.image_size)


def build_learner(kind: str, shape: EpisodicShape) -> MetaLearner:
    if shape.backbone == "efficientnet_b0":
        backbone = make_efficientnet_backbone()
        set_cfg = SetEncoderConfig(kind="conv", conv_blocks=5, conv_width=64,
                                   task_dim=64)
    elif shape.backbone == "conv":
        backbone = make_conv_backbone(ConvBackboneConfig(
            widths=tuple(shape.widths), feature_dim=shape.feature_dim))
        set_cfg = SetEncoderConfig(kind="conv", conv_blocks=len(shape.widths),
                                   conv_width=shape.widths[0],
                                   task_dim=shape.feature_dim // 2)
    else:
        raise ValueError(f"unknown backbone {shape.backbone!r}; known: "
                         f"{BACKBONES}")
    return make_learner(MetaLearnerConfig(kind=kind, way=shape.way),
                        backbone, set_cfg)


def _widths(text: str) -> Tuple[int, ...]:
    return tuple(int(w) for w in text.split(","))


def add_shape_args(ap: argparse.ArgumentParser, defaults: EpisodicShape,
                   lite_h: bool = True) -> None:
    """Add the shape flags with ``defaults``; ``lite_h=False`` leaves out
    ``--lite-h`` (serving adapts on the exact support set)."""
    d = defaults
    ap.add_argument("--backbone", choices=BACKBONES, default=d.backbone,
                    help="feature extractor: the conv stack (--widths, "
                         "--feature-dim) or EfficientNet-B0 at its "
                         "published widths")
    ap.add_argument("--widths", type=_widths, default=tuple(d.widths),
                    help="conv backbone stage widths, comma separated")
    ap.add_argument("--feature-dim", type=int, default=d.feature_dim)
    ap.add_argument("--way", type=int, default=d.way)
    ap.add_argument("--shot", type=int, default=d.shot,
                    help="support images per class")
    ap.add_argument("--query-per-class", type=int, default=d.query_per_class)
    ap.add_argument("--image-size", type=int, default=d.image_size)
    if lite_h:
        ap.add_argument("--lite-h", type=int, default=d.lite_h,
                        help="|H|: support images back-propagated per task")
    ap.add_argument("--lite-chunk", type=int, default=d.lite_chunk,
                    help="LITE no-grad forward chunk size (images)")


def shape_from_args(args: argparse.Namespace) -> EpisodicShape:
    return EpisodicShape(
        widths=tuple(args.widths), feature_dim=args.feature_dim,
        way=args.way, shot=args.shot, query_per_class=args.query_per_class,
        image_size=args.image_size,
        lite_h=getattr(args, "lite_h", EpisodicShape.lite_h),
        lite_chunk=args.lite_chunk, backbone=args.backbone)
