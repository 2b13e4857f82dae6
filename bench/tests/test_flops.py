"""The shape-derived operation counts against XLA's own cost analysis at a
small size on the CPU.

XLA counts what it emits: the convolutions, and besides them bias adds,
relu, pooling and reductions, one operation per element each; and
in a LITE step the complement's last chunk padded up to the chunk size.
The counts in ``bench/flops.py`` are the necessary work only, so they
stay at or below XLA's, and the gap is explained by those two causes.
XLA's cost analysis counts the body of a loop once, so these tests give
LITE a chunk that covers the whole complement (no ``lax.map`` loop).

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench import flops
from bench.tests.tiny import tiny_cell

NAME = "protonets-conv4"


def _xla_flops(fn, *args) -> float:
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return float(cost["flops"])


def _elementwise(config, n_images: int) -> float:
    """Upper bound on the non-convolution operations per forward: for every
    stage, bias + relu + pooling (reads 4 per output) and a margin of 2 on
    the conv output."""
    total, hw = 0.0, config["image_size"]
    for w in config["backbone"]["widths"]:
        total += hw * hw * w * 8
        hw //= 2
    return n_images * total


def test_conv_forward_count_is_exact():
    """A convolution alone: the shape count equals XLA's."""
    cell = tiny_cell(NAME, "train-lite1000")
    cfg = cell.config
    for c in flops.backbone_convs(cfg):
        x = jnp.zeros((3, c.hw, c.hw, c.cin))
        w = jnp.zeros((3, 3, c.cin, c.cout))
        got = _xla_flops(lambda x, w: jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")), x, w)
        assert got == pytest.approx(3 * c.flops, rel=1e-6), c


def test_training_step_count_bounds_xla():
    """The program's LITE step at a small size: the count of necessary work
    is at most what XLA runs; the gap is the padded complement chunk, the
    elementwise work and its backward."""
    from repro.core.episodic import TaskBatch
    from repro.launch import train as launcher
    from bench import data
    from bench.harness.train import _argv

    cell = tiny_cell(NAME, "train-lite1000")
    cfg, tr = cell.config, cell.traffic
    cfg["lite"]["chunk"] = cfg["way"] * tr["shot"]
    job = launcher.build_episodic(launcher.build_parser().parse_args(
        _argv(cfg, tr)))
    mk = data.train_batch_fn(dict(way=cfg["way"], shot=tr["shot"],
                                  query_per_class=tr["query_per_class"],
                                  image_size=cfg["image_size"]), 1)
    key = jax.random.key(0)
    batch = dict(tasks=TaskBatch(way=cfg["way"], **mk(key, 0)),
                 key=data.step_key(key, 0))
    got = _xla_flops(job.step, job.state, batch)
    n, q = cfg["way"] * tr["shot"], cfg["way"] * tr["query_per_class"]
    mine = flops.train_task(cfg, n, q)
    assert mine["total"] <= got
    h, chunk = cfg["lite"]["h"], cfg["lite"]["chunk"]
    comp = n - h
    padded = -(-comp // chunk) * chunk - comp
    per_image = sum(c.flops for c in flops.backbone_convs(cfg))
    slack = (padded * per_image
             + 3 * _elementwise(cfg, n + q) * 2
             + mine["conv_bwd"] + 0.1 * mine["total"])
    assert got - mine["total"] <= slack
