"""Operations and bytes from shapes: what the algorithm needs, never what a
compiler happened to emit.

Convolutions are 3x3, stride 1, SAME.  The zero taps of the SAME padding
are no work: along each side of length L the output positions and kernel
taps pair up 3L - 2 times inside the image, so one convolution costs
2 * (3H - 2) * (3W - 2) * Cin * Cout per image (XLA counts the same).  A backward pass costs one forward per weight gradient
and one per input gradient, counted only where the gradient is needed:
no input gradient into the images.  Work repeated by a padded chunk or a
padded task lane is not counted: it is waste, and a share of the peak
shows it as such.

Byte counts are lower bounds for the roofline: a convolution whose input
is an image reads it once in float32; one whose input is an activation
reads it at least once in bfloat16 (the precision of the matrix unit's
operands).  Outputs, which a fused consumer may never write, are not
counted.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List


@dataclasses.dataclass(frozen=True)
class Conv:
    name: str
    hw: int           # spatial side of input and output
    cin: int
    cout: int
    image_input: bool

    @property
    def flops(self) -> float:
        taps = 3 * self.hw - 2 if self.hw >= 2 else 1
        return 2.0 * taps * taps * self.cin * self.cout

    @property
    def min_bytes(self) -> float:
        return self.hw * self.hw * self.cin * (4 if self.image_input else 2)


def _stack(prefix: str, size: int, in_ch: int, widths) -> List[Conv]:
    out, hw, ch = [], size, in_ch
    for i, w in enumerate(widths):
        out.append(Conv(f"{prefix}{i}", hw, ch, w, image_input=(i == 0)))
        ch = w
        if hw >= 2:
            hw //= 2
    return out


def backbone_convs(config: Dict) -> List[Conv]:
    bb = config["backbone"]
    return _stack("bb", config["image_size"], bb["in_channels"], bb["widths"])


def _dense_backbone(config: Dict) -> float:
    bb = config["backbone"]
    return 2.0 * bb["widths"][-1] * bb["feature_dim"]


def _bwd(convs: List[Conv], weights_trained: bool) -> float:
    """One image's backward through ``convs``: input gradients into every
    layer but the first, weight gradients where the weights train."""
    total = 0.0
    for i, c in enumerate(convs):
        if i > 0:
            total += c.flops
        if weights_trained:
            total += c.flops
    return total


def head_flops(config: Dict, n_support: int, n_query: int) -> float:
    """Class sums and the prototype head for one task (forward)."""
    f, c = config["backbone"]["feature_dim"], config["way"]
    return 2.0 * n_support * f + 3.0 * n_query * c * f


def train_task(config: Dict, n_support: int, n_query: int) -> Dict[str, float]:
    """Model operations of one ProtoNets meta-training task under LITE:
    forward over the whole support set and the queries; backward over
    |H| + queries.  ``conv`` is the part in convolutions, with its byte
    lower bound."""
    if config["learner"] != "protonets":
        raise NotImplementedError(config["learner"])
    h = min(config["lite"]["h"], n_support)
    bb = backbone_convs(config)
    fwd_bb = sum(c.flops for c in bb)
    conv_fwd = (n_support + n_query) * fwd_bb
    conv_bwd = (h + n_query) * _bwd(bb, weights_trained=True)
    conv_bytes = (n_support + n_query) * sum(c.min_bytes for c in bb)
    dense = (n_support + n_query) * _dense_backbone(config)
    dense_bwd = 2.0 * (h + n_query) * _dense_backbone(config)
    head = head_flops(config, n_support, n_query)
    total = conv_fwd + conv_bwd + dense + dense_bwd + 3.0 * head
    return dict(total=total, conv=conv_fwd + conv_bwd,
                conv_fwd=conv_fwd, conv_bwd=conv_bwd, conv_bytes=conv_bytes)


def conv_min_seconds(convs_flops: float, convs_bytes: float,
                     peak_flops: float, peak_bw: float) -> float:
    """The least time the chip could spend on the convolutions: the larger
    of operations over peak FLOP/s and bytes over peak bandwidth."""
    return max(convs_flops / peak_flops, convs_bytes / peak_bw)
