"""The main-path Pallas kernels compile for a TPU v5e chip at real widths.

Nothing runs: each kernel is lowered and compiled for one chip of a
described (not attached) ``v5e:2x2`` topology, which is what Mosaic
refuses or accepts — block tiling, VMEM limits — and interpret mode cannot
show.  Widths are the chip smoke's: B=1000 support images, C=10 classes,
F=256 features, and a K=N=256 int8 head; the fused image block takes the
Conv-4 cell's complement chunk, 100 images of 224x224x3 into 64 filters,
alone and vmapped over two tasks, and a whole 1000-image support set; its
backward takes the query path's 100 images and the H pass's 40.

The ProtoNets Conv-4 LITE step of the benchmark cell compiles whole, with
the fused block's forward and backward, in under 7.6 GB of temporaries.

The Simple CNAPs + EfficientNet-B0 LITE step of the benchmark cell (224
px, way 10 x shot 100, 100 queries, |H| 40, chunk 100, F = 1280) compiles
whole through the launcher's ``build_episodic``, with the set encoder's
fused image block, and fits one chip's 16 GB in under 4.4 GB of
temporaries.  Neither that step nor one 100-image FiLMed EfficientNet-B0
forward chunk copies a backbone activation from one layout to another.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.  The persistent compilation cache is off around these
compiles — an entry written for a described chip cannot be read back.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import conv_pool as cp
from repro.kernels import int8_matmul as im
from repro.kernels import mahalanobis as md
from repro.kernels import segment_pool as sp

B, C, F = 1000, 10, 256
# EfficientNet-B0's activation sides at 224 px, stage by stage
STAGE_SIDES = (112, 56, 28, 14, 7)
_COPY = re.compile(r"= \w+\[(\d+),(\d+),(\d+),(\d+)\]\S* copy\(")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe it
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_hlo(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


CASES = {
    "segment_pool_weighted": (
        lambda x, w: sp.segment_pool_weighted(x, w),
        [((B, F), jnp.float32), ((B, C), jnp.float32)]),
    "class_second_moment": (
        lambda x, w: sp.class_second_moment(x, w),
        [((B, F), jnp.float32), ((B, C), jnp.float32)]),
    "mahalanobis": (
        lambda q, mu, sinv: md.mahalanobis(q, mu, sinv),
        [((B, F), jnp.float32), ((C, F), jnp.float32),
         ((C, F, F), jnp.float32)]),
    "int8_matmul": (
        lambda x, q, s: im.int8_matmul(x, q, s),
        [((B, F), jnp.float32), ((F, F), jnp.int8),
         ((F, F // 128), jnp.float32)]),
    "conv3x3_relu_pool": (
        lambda x, w, b: cp.conv3x3_relu_pool(x, w, b),
        [((100, 224, 224, 3), jnp.float32), ((3, 3, 3, 64), jnp.float32),
         ((64,), jnp.float32)]),
    # a whole 1000-image support set: 8 lane blocks, the last one partial
    "conv3x3_relu_pool_b1000": (
        lambda x, w, b: cp.conv3x3_relu_pool(x, w, b),
        [((B, 224, 224, 3), jnp.float32), ((3, 3, 3, 64), jnp.float32),
         ((64,), jnp.float32)]),
    # the train step vmaps the complement over two tasks
    "conv3x3_relu_pool_vmap": (
        jax.vmap(lambda x, w, b: cp.conv3x3_relu_pool(x, w, b),
                 in_axes=(0, None, None)),
        [((2, 100, 224, 224, 3), jnp.float32), ((3, 3, 3, 64), jnp.float32),
         ((64,), jnp.float32)]),
    # the weight and bias gradient, for the query path's 100 images and
    # the H pass's 40 (part of one lane block)
    "conv3x3_relu_pool_bwd": (
        lambda x, w, b, g: cp.conv3x3_relu_pool_bwd(x, w, b, g),
        [((100, 224, 224, 3), jnp.float32), ((3, 3, 3, 64), jnp.float32),
         ((64,), jnp.float32), ((100, 112, 112, 64), jnp.float32)]),
    "conv3x3_relu_pool_bwd_b40": (
        lambda x, w, b, g: cp.conv3x3_relu_pool_bwd(x, w, b, g),
        [((40, 224, 224, 3), jnp.float32), ((3, 3, 3, 64), jnp.float32),
         ((64,), jnp.float32), ((40, 112, 112, 64), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = CASES[name]
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
              for s, dt in specs]
    assert "tpu_custom_call" in _compile_hlo(fn, *shapes)


def _activation_copies(hlo: str):
    """The copies in compiled HLO text whose result is a backbone
    activation, (n, s, s, c) with s a stage side: a relayout of the
    activation, not the image, a weight or the covariances."""
    return [m.group(0) for m in _COPY.finditer(hlo)
            if m.group(2) == m.group(3) and int(m.group(2)) in STAGE_SIDES]


def test_efficientnet_film_chunk_copies_no_activation(one_chip):
    """One complement chunk of the Simple CNAPs cell: 100 images of 224 px
    through the FiLMed EfficientNet-B0 with no gradient.  Its 1x1s are
    convolutions, so the expanded maps stay in the layout the depthwise
    convs read."""
    from repro.models import efficientnet as en

    bb = en.make_efficientnet_backbone()
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(bb.init, jax.random.key(0)))
    film = [{k: jax.ShapeDtypeStruct((c,), jnp.float32, sharding=one_chip)
             for k in ("gamma", "beta")} for c in bb.film_sites]
    x = jax.ShapeDtypeStruct((100, 224, 224, 3), jnp.float32,
                             sharding=one_chip)
    hlo = _compile_hlo(bb.features, params, x, film)
    assert "convolution" in hlo
    assert _activation_copies(hlo) == []


def _step_on_chip(one_chip, monkeypatch, argv):
    """The launcher's episodic step for argv, compiled for one described
    chip with the TPU kernel policy."""
    from repro.kernels import dispatch, ops
    from repro.launch import train as launcher

    monkeypatch.setattr(dispatch, "pallas_native", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    job = launcher.build_episodic(launcher.build_parser().parse_args(argv))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    state = jax.tree.map(on_chip, job.state_abs)
    batch = jax.tree.map(on_chip, jax.eval_shape(job.batch_at, 0))
    return jax.jit(job.step).lower(state, batch).compile()


def test_protonets_conv4_step_compiles_for_v5e(one_chip, monkeypatch,
                                               tmp_path):
    """The ProtoNets Conv-4 cell's step (224 px, way 10 x shot 100, 100
    queries, |H| 40, chunk 100): no full-resolution block-1 activation or
    gradient is kept for the query path's backward, so its temporaries
    stay under 7.6 GB (8.37 GB with the XLA composite's backward)."""
    compiled = _step_on_chip(one_chip, monkeypatch, [
        "--episodic", "--learner", "protonets", "--tasks-per-step", "1",
        "--data-source", "device", "--widths", "64,64,64,64",
        "--feature-dim", "64", "--way", "10", "--shot", "100",
        "--query-per-class", "10", "--image-size", "224", "--lite-h", "40",
        "--lite-chunk", "100", "--ckpt-dir", str(tmp_path)])
    assert "conv3x3_relu_pool_bwd" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 7.6e9


def test_simple_cnaps_efficientnet_step_compiles_for_v5e(one_chip, monkeypatch,
                                                        tmp_path):
    """The Simple CNAPs cell's step copies no backbone activation to
    another layout, and its temporaries stay under 4.4 GB (3.95 GB; 5.39
    GB, with 23 such copies, when the MBConv 1x1s were matrix products and
    the backward kept the expanded maps)."""
    compiled = _step_on_chip(one_chip, monkeypatch, [
        "--episodic", "--learner", "simple_cnaps", "--backbone",
        "efficientnet_b0", "--tasks-per-step", "1", "--way", "10",
        "--shot", "100", "--query-per-class", "10", "--image-size", "224",
        "--lite-h", "40", "--lite-chunk", "100", "--ckpt-dir",
        str(tmp_path)])
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert _activation_copies(hlo) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4.4e9
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
