"""Operations and bytes of one Simple CNAPs meta-training task on a
FiLM-conditioned EfficientNet-B0 under LITE, from shapes, by
``bench/flops.py``'s rules: what the algorithm needs, never what a
compiler emitted; a SAME convolution's taps that fall on the padding are
no work; a backward pass costs one forward per gradient it needs; bytes
are lower bounds, a layer's input read once (in float32 where it is an
image, else in bfloat16), outputs not counted.

Counted per image of the backbone: the stem, every depthwise, 1x1 expand
and projection and head convolution, FiLM (a product and a sum per
element), squeeze-and-excitation (its two dense layers and the gate's
product).  Batch norm and swish are elementwise and left out, as
``bench/flops.py`` leaves out ReLU and pooling.  Per task: the backbone
forward over the support set and the queries; its input gradients (the
backbone is frozen, so no weight gradients, and none into the images)
over |H| + queries; the set encoder (``bench/flops.py``'s conv stack and
its linear head) forward over the support set, weight and input
gradients over |H|; class sums and second moments (2F + 2F^2 per support
image); the Cholesky factors (C F^3 / 3); the Mahalanobis head (F^2 for
the triangular solve and 2F per query and class).  The covariance blend,
the FiLM generator and the backward through the head are left out: each
is under 1% of the task.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

from bench import flops as conv4


@dataclasses.dataclass(frozen=True)
class Layer:
    name: str
    flops: float
    min_bytes: float


def _taps(side: int, k: int, stride: int) -> int:
    """(output, kernel tap) pairs along one side of a SAME convolution
    whose input position lies inside the image."""
    out = -(-side // stride)
    lo = max((out - 1) * stride + k - side, 0) // 2
    return sum(1 for o in range(out) for t in range(k)
               if 0 <= o * stride + t - lo < side)


def _blocks(bb: Dict):
    cin = bb["stem"]
    for e, k, s, cout, n in bb["stages"]:
        for r in range(n):
            yield e, k, s if r == 0 else 1, cin, cout, \
                max(1, int(cin * bb["se_ratio"]))
            cin = cout


def backbone_layers(config: Dict) -> List[Layer]:
    """One image's forward through the backbone, layer by layer."""
    bb = config["backbone"]
    side, c = config["image_size"], bb["in_channels"]
    t = _taps(side, 3, 2)
    out = [Layer("stem", 2.0 * t * t * c * bb["stem"], side * side * c * 4)]
    side = -(-side // 2)
    for i, (e, k, s, cin, cout, se) in enumerate(_blocks(bb)):
        mid, a = cin * e, side * side
        if e != 1:
            out.append(Layer(f"b{i}.expand", 2.0 * a * cin * mid,
                             2.0 * a * cin))
        t = _taps(side, k, s)
        out.append(Layer(f"b{i}.dw", 2.0 * t * t * mid, 2.0 * a * mid))
        side = -(-side // s)
        a = side * side
        out.append(Layer(f"b{i}.film", 2.0 * a * mid, 0.0))
        out.append(Layer(f"b{i}.se", 4.0 * mid * se + a * mid, 0.0))
        out.append(Layer(f"b{i}.project", 2.0 * a * mid * cout,
                         2.0 * a * mid))
    last = bb["stages"][-1][3]
    out.append(Layer("head", 2.0 * side * side * last * bb["head"],
                     2.0 * side * side * last))
    return out


def set_encoder_convs(config: Dict) -> List[conv4.Conv]:
    enc = config["set_encoder"]
    return conv4._stack("enc", config["image_size"],
                        config["backbone"]["in_channels"],
                        [enc["width"]] * enc["blocks"])


def train_task(config: Dict, n_support: int, n_query: int) -> Dict[str, float]:
    """Model operations of one task; ``backbone_complement`` and
    ``backbone_complement_bytes`` are the FiLMed backbone's forward over
    the no-grad complement of the support set (N - |H| images)."""
    h = min(config["lite"]["h"], n_support)
    f, way = config["backbone"]["feature_dim"], config["way"]
    layers = backbone_layers(config)
    bb_fwd = sum(x.flops for x in layers)
    bb_bwd = sum(x.flops for x in layers if x.name != "stem")
    enc = set_encoder_convs(config)
    task_dim = config["set_encoder"]["task_dim"]
    enc_dense = 2.0 * enc[-1].cout * task_dim
    enc_fwd = sum(c.flops for c in enc) + enc_dense
    enc_bwd = conv4._bwd(enc, weights_trained=True) + 2.0 * enc_dense
    parts = dict(
        backbone=(n_support + n_query) * bb_fwd + (h + n_query) * bb_bwd,
        set_encoder=n_support * enc_fwd + h * enc_bwd,
        class_stats=n_support * (2.0 * f + 2.0 * f * f),
        cholesky=way * f ** 3 / 3.0,
        mahalanobis=n_query * way * (f * f + 2.0 * f))
    comp = n_support - h
    return dict(parts,
                total=sum(parts.values()),
                backbone_complement=comp * bb_fwd,
                backbone_complement_bytes=comp * sum(x.min_bytes
                                                     for x in layers))
