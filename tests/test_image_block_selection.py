"""Where the fused image block engages in the LITE training step.

On TPU the backbone's first block (conv3x3 + bias, ReLU, 2x2 max-pool)
runs as one Pallas kernel (``repro.kernels.conv_pool``) in every call of
at least the kernel's floor of images: the forward ``conv3x3_relu_pool``
in the complement, the H pass and the query path, and the weight and
bias gradient ``conv3x3_relu_pool_bwd`` in the last two's backward.  Here
the platform policy is forced to TPU (the kernels then run in interpret
mode on the CPU) and the launcher's episodic ProtoNets step is compiled
at a tiny size."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.episodic_train import make_batched_meta_grads
from repro.core.lite import LiteSpec
from repro.data.episodic import task_batch_at
from repro.kernels import dispatch
from repro.launch import train as launcher
from repro.launch.episodic import EpisodicShape, build_learner

_OP_NAME = re.compile(r'op_name="([^"]*)"')
# the (H, W, C) of an instruction that XLA made of a max-pool's backward
_POOL_BWD = re.compile(r'= f32\[[\d,]*?(\d+,\d+,\d+)\]\S* .*op_name="[^"]*'
                       r'select_and_scatter"')
# |H|, the complement's chunk and the queries all reach the kernel's floor
SHAPE = EpisodicShape(widths=(8, 16), feature_dim=16, way=2, shot=100,
                      query_per_class=32, image_size=8, lite_h=64,
                      lite_chunk=64)


def _on_tpu(mp):
    mp.setattr(dispatch, "pallas_native", lambda: True)


@pytest.fixture(scope="module")
def fused_step_hlo(tmp_path_factory):
    args = launcher.build_parser().parse_args(
        ["--episodic", "--learner", "protonets", "--steps", "4",
         "--tasks-per-step", "1", "--data-source", "device",
         "--widths", "8,16", "--feature-dim", "16", "--way", "2",
         "--shot", "100", "--query-per-class", "32", "--image-size", "8",
         "--lite-h", "64", "--lite-chunk", "64",
         "--ckpt-dir", str(tmp_path_factory.mktemp("ck"))])
    with pytest.MonkeyPatch.context() as mp:
        _on_tpu(mp)
        job = launcher.build_episodic(args)
        return jax.jit(job.step).lower(
            job.state, job.batch_at(0)).compile().as_text()


def test_fused_block_runs_only_in_the_complement(fused_step_hlo):
    """The forward kernel runs in all three parts of the step and never in
    a backward; the backward kernel runs only there, in the H pass's and
    the query path's; no max-pool backward is left on block 1's
    full-resolution (N, 8, 8, 8) tensor, only on block 2's."""
    names = set(_OP_NAME.findall(fused_step_hlo))
    bwd = {n for n in names if "conv3x3_relu_pool_bwd" in n}
    fwd = {n for n in names if "conv3x3_relu_pool" in n} - bwd
    scopes = ("lite.complement", "lite.h_pass", "learner.query")
    for n in fwd:
        assert "transpose(" not in n, n
    for n in bwd:
        assert "transpose(" in n and "lite.complement" not in n, n
    assert {s for s in scopes for n in fwd if s in n} == set(scopes)
    assert {s for s in scopes for n in bwd if s in n} == set(scopes[1:])
    pool_bwd = {m.group(1) for m in _POOL_BWD.finditer(fused_step_hlo)}
    assert pool_bwd == {"4,4,16"}


def _bf16_exact(tree):
    """Round float leaves to bfloat16-representable float32 values, so the
    kernel's bfloat16 operands are exact and only the summation order of
    the two paths differs."""
    return jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def test_fused_block_leaves_the_step_gradients_unchanged(monkeypatch):
    learner = build_learner("protonets", SHAPE)
    lite = LiteSpec(h=SHAPE.lite_h, chunk_size=SHAPE.lite_chunk)
    key = jax.random.PRNGKey(3)
    params = _bf16_exact(learner.init(key))
    batch = _bf16_exact(task_batch_at(jax.random.PRNGKey(4),
                                      SHAPE.image_config(), 2, 0))

    def grads():
        # a fresh closure per policy, so no trace is reused across them
        fn = make_batched_meta_grads(learner, lite)
        return (jax.make_jaxpr(fn)(params, batch, key),
                jax.jit(fn)(params, batch, key))

    plain_jaxpr, (loss0, _, g0) = grads()
    _on_tpu(monkeypatch)
    fused_jaxpr, (loss1, _, g1) = grads()
    assert "pallas_call" not in str(plain_jaxpr)
    assert "pallas_call" in str(fused_jaxpr)
    np.testing.assert_allclose(float(loss1), float(loss0), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)
