"""EfficientNet-B0 with FiLM and Simple CNAPs on it, against the plain
float32 reference (``tests/simple_cnaps_reference.py``), at the published
widths on 32 px images with seeded random weights.

The reference runs at HIGHEST with its own formulas (LU solves where the
program takes a Cholesky factor, one einsum for the second moments).
Features agree to float32 rounding, 1e-5 relative.  Meta-gradients are
compared in float64: in float32 the covariance solves at F = 1280 of a
rank-12 support set amplify rounding to 1.5e-3 of the largest gradient,
in float64 the two agree to 5e-8 (1e-6 is the tolerance).  Batch-norm statistics are set from
the support images (the reference's ``calibrated``), so features are of
unit scale and not the near-zero ones identity statistics give, and the
FiLM generator's output layers are scaled to FiLM of about 0.05: FiLM
compounds over 16 blocks, and at 0.3 the features grow sixtyfold.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import simple_cnaps_reference as ref
from repro.core.episodic import Task
from repro.core.lite import LiteSpec
from repro.launch import train as launcher
from repro.launch.episodic import EpisodicShape, build_learner
from repro.models import efficientnet as en

WAY, SHOT, QUERY, SIZE = 3, 4, 2, 32
SHAPE = EpisodicShape(backbone="efficientnet_b0", way=WAY, shot=SHOT,
                      query_per_class=QUERY, image_size=SIZE, lite_h=3,
                      lite_chunk=4)
# Table 1: each stage's output side at 224 px (the next stage's input)
TABLE1_SIDES = {1: 112, 2: 56, 3: 28, 4: 14, 5: 14, 6: 7, 7: 7}


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


@pytest.fixture(scope="module")
def setup():
    """The learner, its parameters (batch norm set from the support
    images) and one task."""
    learner = build_learner("simple_cnaps", SHAPE)
    params = learner.init(jax.random.key(0))
    k = jax.random.split(jax.random.key(1), 4)
    sx = jax.random.normal(k[0], (WAY * SHOT, SIZE, SIZE, 3))
    sy = jnp.repeat(jnp.arange(WAY), SHOT)
    qx = jax.random.normal(k[1], (WAY * QUERY, SIZE, SIZE, 3))
    qy = jnp.repeat(jnp.arange(WAY), QUERY)
    sites = [dict(s, w_gamma=0.1 * s["w_gamma"], w_beta=0.1 * s["w_beta"])
             for s in params["film_gen"]["sites"]]
    params = dict(params, bb=jax.jit(ref.calibrated)(params["bb"], sx),
                  film_gen=dict(sites=sites))
    task = Task(support_x=sx, support_y=sy, query_x=qx, query_y=qy, way=WAY)
    return learner, params, task


def _film(key, sites, scale):
    ks = jax.random.split(key, 2 * len(sites))
    return [dict(gamma=scale * jax.random.normal(ks[2 * i], (c,)),
                 beta=scale * jax.random.normal(ks[2 * i + 1], (c,)))
            for i, c in enumerate(sites)]


def test_backbone_matches_reference(setup):
    learner, params, task = setup
    bb = learner.backbone
    film = _film(jax.random.key(2), bb.film_sites, 0.1)
    x = task.query_x
    got = jax.jit(bb.features)(params["bb"], x, film)
    want = jax.jit(ref.efficientnet)(params["bb"], x, film)
    assert got.shape == (x.shape[0], 1280)
    assert 0.1 < float(jnp.std(want)) < 10.0
    assert _rel(got, want) <= 1e-5


def test_film_identity_is_the_plain_backbone(setup):
    learner, params, task = setup
    bb = learner.backbone
    feats = jax.jit(bb.features)
    zero = _film(jax.random.key(3), bb.film_sites, 0.0)
    assert _rel(feats(params["bb"], task.support_x, zero),
                feats(params["bb"], task.support_x, None)) <= 1e-6


def test_published_widths_and_strides():
    """16 FiLM sites at Table 1's expanded widths; each stage's output side
    at 224 px is Table 1's, down to 7 x 7 under the 1280-wide head."""
    bb = en.make_efficientnet_backbone()
    assert bb.film_sites == (32, 96, 144, 144, 240, 240, 480, 480, 480,
                             672, 672, 672, 1152, 1152, 1152, 1152)
    assert bb.feature_dim == 1280
    params = jax.eval_shape(bb.init, jax.random.key(0))
    x = jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda p, x: bb.features(p, x, None))(params, x)
    sides = [e.outvars[0].aval.shape[1] for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "conv_general_dilated"
             and e.params["feature_group_count"] > 1]
    stage_of = [i + 1 for i, st in enumerate(en.B0_STAGES)
                for _ in range(st[4])]
    assert len(sides) == 16
    assert sides == [TABLE1_SIDES[s] for s in stage_of]
    last = [e for e in jaxpr.jaxpr.eqns
            if e.primitive.name == "conv_general_dilated"
            and e.params["feature_group_count"] == 1]
    assert last[-1].outvars[0].aval.shape == (1, 7, 7, 1280)


def _all_eqns(jaxpr):
    """The equations of jaxpr and of every jaxpr nested in them."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _all_eqns(inner)


def test_one_by_ones_are_convolutions_and_the_expand_is_recomputed():
    """Every 1x1 (expand, project, head) is a convolution, so no
    activation leaves the convolution layout for a matrix product; only
    squeeze-and-excitation's pooled (B, C) products are dot_generals.  Each
    of the 15 expanding blocks' expand sits in one checkpoint, so the
    backward recomputes it."""
    bb = en.make_efficientnet_backbone()
    params = jax.eval_shape(bb.init, jax.random.key(0))
    film = [dict(gamma=jax.ShapeDtypeStruct((c,), jnp.float32),
                 beta=jax.ShapeDtypeStruct((c,), jnp.float32))
            for c in bb.film_sites]
    x = jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32)
    jaxpr = jax.make_jaxpr(bb.features)(params, x, film).jaxpr
    dots = [e for e in _all_eqns(jaxpr) if e.primitive.name == "dot_general"]
    assert len(dots) == 32
    assert all(v.aval.ndim == 2 for e in dots for v in e.invars)
    remat = jax.make_jaxpr(jax.checkpoint(jnp.sin))(1.0).eqns[0].primitive
    expands = [e for e in jaxpr.eqns if e.primitive is remat]
    assert len(expands) == sum(s.expand != 1 for s in en.block_specs(
        en.EfficientNetConfig())) == 15
    assert all(any(q.primitive.name == "conv_general_dilated"
                   for q in e.params["jaxpr"].eqns) for e in expands)


def _trained(tree):
    return {k: tree[k] for k in ("enc", "film_gen")}


def _grads_agree(got, want, tol):
    g, w = jax.tree.leaves(_trained(got)), jax.tree.leaves(_trained(want))
    scale = max(float(jnp.max(jnp.abs(a))) for a in w)
    assert scale > 0.0
    worst = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(g, w))
    assert worst <= tol * scale, (worst, scale)
    # the frozen backbone gets no gradient
    assert all(float(jnp.max(jnp.abs(a))) == 0.0
               for a in jax.tree.leaves(got["bb"]))


@pytest.mark.parametrize("h", [WAY * SHOT, 3], ids=["exact", "lite_h3"])
def test_meta_gradient_matches_reference(setup, h):
    """|H| = N: the program's LITE meta-gradient is the reference's full
    gradient; |H| < N: the reference's LITE estimate with the same H
    draw."""
    learner, params, task = setup
    key = jax.random.key(5)
    lite = LiteSpec(h=h, chunk_size=4)
    with jax.enable_x64(True):
        params, task = jax.tree.map(
            lambda a: a.astype(jnp.float64)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, (params, task))
        got = jax.jit(jax.grad(
            lambda p: learner.meta_loss(p, task, key, lite)[0]))(params)
        want = jax.jit(jax.grad(lambda p: ref.loss(
            p, task.support_x, task.support_y, task.query_x, task.query_y,
            WAY, key, h)))(params)
        _grads_agree(got, want, 1e-6)


def test_build_episodic_takes_the_backbone_flag(tmp_path):
    args = launcher.build_parser().parse_args(
        ["--episodic", "--learner", "simple_cnaps", "--backbone",
         "efficientnet_b0", "--steps", "2", "--tasks-per-step", "1",
         "--way", str(WAY), "--shot", str(SHOT), "--query-per-class",
         str(QUERY), "--image-size", str(SIZE), "--lite-h", "3",
         "--lite-chunk", "4", "--ckpt-dir", str(tmp_path)])
    job = launcher.build_episodic(args)
    params = job.state["params"]
    assert len(params["bb"]["blocks"]) == 16
    assert [s["w_gamma"].shape[1] for s in params["film_gen"]["sites"]] == \
        list(en.make_efficientnet_backbone().film_sites)
    assert len(params["enc"]["blocks"]) == 5
    assert params["enc"]["head"]["w"].shape == (64, 64)
    out = jax.eval_shape(job.step, job.state, job.batch_at(0))
    assert out[1]["loss"].shape == ()
    # the default stays the conv stack
    dflt = launcher.build_parser().parse_args(["--episodic"])
    assert dflt.backbone == "conv"
