"""Random weights from the seed for the Simple CNAPs cells, made on the
device in one jitted call.

The tree is the one the program's Simple CNAPs learner takes: ``bb`` (the
frozen EfficientNet-B0), ``enc`` (the CNAPs set encoder, a conv stack with
a linear head) and ``film_gen`` (one 2-layer MLP per FiLM site), built
here from the configuration's sizes so that neither the program's own
initialisers nor anything it made reaches the reference.  Conv kernels
and dense layers are LeCun-normal (fan-in counts every tap of a
receptive field and the input channels of one group), biases zero; the
FiLM generator's output layers are normal with the configuration's
``out_std``.  Batch norm has gamma 1 and beta 0, and its statistics are
calibrated on one seeded batch of the cell's own tasks
(``calibration_images_per_class`` support images per class), layer after
layer, at the reference's precision: with identity statistics the
features of a 224 px image come out near 1e-7.
"""
from __future__ import annotations

import itertools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench import data
from bench import reference_simple_cnaps as reference
from bench.weights import _conv_stack, _lecun

CALIBRATION_STREAM = 0xBA7C


def _bn(c: int) -> Dict[str, Any]:
    return dict(gamma=jnp.ones((c,)), beta=jnp.zeros((c,)),
                mean=jnp.zeros((c,)), var=jnp.ones((c,)))


def _efficientnet(key, bb: Dict) -> Dict[str, Any]:
    """Every weight a slice of one normal draw, scaled by its fan-in."""
    shapes = [(3, 3, bb["in_channels"], bb["stem"])]
    for e, k, _, cin, cout, se in reference.blocks(bb):
        mid = cin * e
        shapes += ([(cin, mid)] if e != 1 else []) + [
            (k, k, 1, mid), (mid, se), (se, mid), (mid, cout)]
    shapes.append((bb["stages"][-1][3], bb["head"]))
    sizes = [math.prod(sh) for sh in shapes]
    flat = jax.random.normal(key, (sum(sizes),), jnp.float32)
    ends = list(itertools.accumulate(sizes))
    ws = iter(flat[b - n:b].reshape(sh) / math.sqrt(math.prod(sh[:-1]))
              for n, b, sh in zip(sizes, ends, shapes))
    p: Dict[str, Any] = dict(stem=dict(w=next(ws), bn=_bn(bb["stem"])),
                             blocks=[])
    for e, _, _, cin, cout, se in reference.blocks(bb):
        mid = cin * e
        blk: Dict[str, Any] = {}
        if e != 1:
            blk["expand"] = dict(w=next(ws), bn=_bn(mid))
        blk["dw"] = dict(w=next(ws), bn=_bn(mid))
        blk["se"] = dict(w1=next(ws), b1=jnp.zeros((se,)),
                         w2=next(ws), b2=jnp.zeros((mid,)))
        blk["project"] = dict(w=next(ws), bn=_bn(cout))
        p["blocks"].append(blk)
    p["head"] = dict(w=next(ws), bn=_bn(bb["head"]))
    return p


def _film_generator(key, config: Dict) -> Dict[str, Any]:
    gen, task_dim = config["film_generator"], config["set_encoder"]["task_dim"]
    sites = []
    for i, c in enumerate(config["backbone"]["film_sites"]):
        k1, k2, k3 = jax.random.split(jax.random.fold_in(key, i), 3)
        std = gen["out_std"]
        sites.append(dict(
            w1=_lecun(k1, (task_dim, gen["hidden"])),
            b1=jnp.zeros((gen["hidden"],)),
            w_gamma=std * jax.random.normal(k2, (gen["hidden"], c)),
            b_gamma=jnp.zeros((c,)),
            w_beta=std * jax.random.normal(k3, (gen["hidden"], c)),
            b_beta=jnp.zeros((c,))))
    return dict(sites=sites)


def init_params(config: Dict[str, Any], key, calib_x) -> Dict[str, Any]:
    """The weights, batch norm calibrated on the images ``calib_x``."""
    bbc, enc = config["backbone"], config["set_encoder"]
    bb = _efficientnet(jax.random.fold_in(key, 0), bbc)
    with jax.default_matmul_precision("highest"):
        bb = reference.calibrate_bn(bbc, bb, calib_x)
    return dict(
        bb=bb,
        enc=_conv_stack(jax.random.fold_in(key, 1), bbc["in_channels"],
                        [enc["width"]] * enc["blocks"], enc["task_dim"]),
        film_gen=_film_generator(jax.random.fold_in(key, 2), config))


def make_params(config: Dict[str, Any], seed: int):
    """The weights for ``seed``, made on the default device by one jitted
    call; the calibration batch is a task of the cell's own family drawn
    from the seed."""
    key = jax.random.fold_in(jax.random.key(seed), 0x5EED)
    per_class = config["backbone"]["calibration_images_per_class"]

    def make(k):
        x, _, _, _ = data.sample_task(
            jax.random.fold_in(k, CALIBRATION_STREAM), config["way"],
            per_class, 1, config["image_size"])
        return init_params(config, k, x)

    return jax.jit(make)(key)
