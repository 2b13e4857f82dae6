"""Meta-learners + LITE: ProtoNets, CNAPs, Simple CNAPs, first-order MAML,
and the FineTuner transfer baseline (paper §3.1 / §5).

All learners are pure functions over explicit param pytrees:

    learner = make_learner(cfg, backbone)
    params  = learner.init(key)
    loss, metrics = learner.meta_loss(params, task, key, lite_spec)

Test-time adaptation speaks ONE uniform, mask-aware batched contract for
every learner kind (the episodic serving engine's API; repro.serve.episodic
dispatches thousands of personalization requests through it):

    states = learner.adapt_batch(params, task_batch, keys, lite)  # (T, ...)
    logits = learner.predict_batch(params, states, query_x)       # (T, M, way)

``adapt_batch`` vmaps over the padded task axis of a
:class:`repro.core.episodic.TaskBatch` with per-task PRNG keys and honors
the collator's support masks, so a padded batch adapts bit-exactly like
its member tasks; the returned *task-state batch* is the single-task state
pytree with a leading task axis (stack/index helpers live in
repro.core.episodic).  At serve time adaptation is forward-only, so the
aggregating learners run the LITE-chunked exact estimators
(repro.core.lite.serve_sum / serve_segment_sum): support activations stay
O(chunk) no matter how many images the support set holds, and
``LiteSpec.compute_dtype`` down-casts the chunk compute with fp32
accumulation.  Thin single-task wrappers remain for the training path:

    task_state = learner.adapt(params, support_x, support_y, key)
    logits     = learner.predict(params, task_state, query_x)

LITE enters at every support-set aggregation site (the paper's Eqs. 2-5):
the set-encoder pooling and the class-pooled feature statistics.  The
N/H backward rescale is baked into the straight-through combinator
(repro.core.lite), so the optimizer step needs no extra weighting —
mathematically identical to Algorithm 1's step(phi, N/H).

The class-statistics sites and the Simple CNAPs Mahalanobis head run
through the kernel dispatch layer (repro.kernels.dispatch; backend
naive | ref | pallas | auto selected per site at trace time): per-class
feature sums and raw second moments are kernel-fused, so the covariance
path never materializes the per-example (B, F, F) outer-product tensor
— in training (H pass), LITE-chunked serving, and the batched
adapt_batch path alike.  Only the paper's naive small-task baseline
(estimator="subsampled") keeps the literal outer-product composite: its
forward sees just the H subset, where naive is the point.

A key LITE-correctness subtlety: anything task-adapted that feeds the
support encoder (e.g. CNAPs' FiLM parameters) must be passed through the
combinator's *params* argument, not captured in a closure — otherwise the
no-grad complement pass would leak gradients through the closure and the
estimator would no longer match Eq. 8.  See ``_film_as_params`` below.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.init import lecun_normal
from repro.common.tree import tree_stop_gradient
from repro.core.episodic import Task, TaskBatch
from repro.core.film import generate_film_params, init_film_generator
from repro.core.lite import (LiteSpec, lite_class_stats, lite_segment_sum,
                             lite_sum, serve_segment_sum, serve_sum,
                             subsampled_task_sum)
from repro.kernels import dispatch
from repro.kernels.dispatch import mahalanobis_head
from repro.kernels.tpu_compat import F32_CONTRACT
from repro.core.set_encoder import (SetEncoderConfig, encode_set,
                                    init_set_encoder)
from repro.models.backbone import BackboneDef

PyTree = Any


@dataclasses.dataclass(frozen=True)
class MetaLearnerConfig:
    kind: str = "protonets"      # protonets|cnaps|simple_cnaps|fomaml|finetuner
    way: int = 5
    task_dim: int = 64
    gen_hidden: int = 64
    head_hidden: int = 64
    # fomaml / finetuner
    inner_lr: float = 0.01
    inner_steps: int = 5
    freeze_backbone: bool = False      # CNAPs-family default True via make_learner
    # simple-cnaps covariance regularization epsilon
    cov_eps: float = 1.0
    film_init_std: float = 0.1


@dataclasses.dataclass(frozen=True)
class MetaLearner:
    cfg: MetaLearnerConfig
    backbone: BackboneDef
    init: Callable[[jax.Array], PyTree]
    meta_loss: Callable[..., Tuple[jnp.ndarray, Dict]]
    adapt: Callable[..., PyTree]
    predict: Callable[[PyTree, PyTree, jnp.ndarray], jnp.ndarray]
    # uniform batched serving contract (vmapped over the padded task axis;
    # see _batched_api): every learner kind serves through these two.
    adapt_batch: Callable[..., PyTree]
    predict_batch: Callable[[PyTree, PyTree, jnp.ndarray], jnp.ndarray]


def _batched_api(adapt_one: Callable, predict: Callable
                 ) -> Tuple[Callable, Callable, Callable]:
    """Build the uniform batched contract from a mask-aware single-task
    ``adapt_one(params, sx, sy, mask, key, lite) -> task_state``.

    Returns ``(adapt, adapt_batch, predict_batch)``:

    * ``adapt(params, sx, sy, key=None, lite=..., mask=None)`` — the thin
      single-task wrapper (training/eval path; old call sites unchanged).
    * ``adapt_batch(params, batch: TaskBatch, keys, lite=...)`` — vmaps
      adaptation over the padded task axis with per-task keys, honoring the
      collator's support masks.  Returns a *task-state batch*: the
      single-task state pytree with a leading task axis on every leaf
      (stack/index via repro.core.episodic.stack_task_states /
      index_task_state).
    * ``predict_batch(params, states, qx)`` — vmaps query scoring over
      (state, query) pairs; ``qx`` is (T, M, ...) padded queries, result is
      (T, M, way) logits.

    Both batched entry points are plain vmaps of the same single-task
    functions at identical padded shapes, which is what makes batched
    serving bit-exact vs the per-task path.
    """

    def adapt(params, sx, sy, key=None, lite: LiteSpec = LiteSpec(exact=True),
              mask=None):
        key = jax.random.key(0) if key is None else key
        return adapt_one(params, sx, sy, mask, key, lite)

    def adapt_batch(params, batch: TaskBatch, keys,
                    lite: LiteSpec = LiteSpec(exact=True)):
        def one(sx, sy, sm, k):
            return adapt_one(params, sx, sy, sm, k, lite)

        return jax.vmap(one)(batch.support_x, batch.support_y,
                             batch.support_mask, keys)

    def predict_batch(params, states, qx):
        return jax.vmap(lambda st, q: predict(params, st, q))(states, qx)

    return adapt, adapt_batch, predict_batch


def _xent(logits: jnp.ndarray, labels: jnp.ndarray,
          w: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Cross-entropy; with ``w`` (validity weights) a weighted mean over the
    real examples only, so collator padding never moves the loss."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    if w is None:
        return -jnp.mean(ll)
    w = w.astype(ll.dtype)
    return -jnp.sum(ll * w) / jnp.maximum(jnp.sum(w), 1.0)


def _accuracy(logits: jnp.ndarray, labels: jnp.ndarray,
              w: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    hit = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
    if w is None:
        return jnp.mean(hit)
    return jnp.sum(hit * w) / jnp.maximum(jnp.sum(w), 1.0)


# ===========================================================================
# ProtoNets (+ LITE): metric head, all backbone params learned
# ===========================================================================

def make_protonets(cfg: MetaLearnerConfig, bb: BackboneDef) -> MetaLearner:
    def init(key):
        return dict(bb=bb.init(key))

    def _prototypes(params, sx, sy, key, lite: LiteSpec,
                    estimator=lite_segment_sum, mask=None):
        def encode(p, x):
            return bb.features(p, x, None)
        sums, counts = estimator(encode, params["bb"], sx, sy, cfg.way, key,
                                 lite, mask=mask)
        return sums / jnp.maximum(counts, 1.0)[:, None]

    def _logits(params, protos, qx):
        qf = bb.features(params["bb"], qx, None).astype(jnp.float32)
        d2 = jnp.sum((qf[:, None, :] - protos[None, :, :]) ** 2, axis=-1)
        return -d2

    def meta_loss(params, task: Task, key, lite: LiteSpec, estimator=None):
        seg = _sub_seg if estimator == "subsampled" else lite_segment_sum
        protos = _prototypes(params, task.support_x, task.support_y, key,
                             lite, seg, mask=task.support_mask)
        with jax.named_scope("learner.query"):
            logits = _logits(params, protos, task.query_x)
            loss = _xent(logits, task.query_y, task.query_mask)
        return loss, dict(
            accuracy=_accuracy(logits, task.query_y, task.query_mask))

    def adapt_one(params, sx, sy, mask, key, lite: LiteSpec):
        # forward-only serve estimator: exact prototypes, chunked, no grad
        return _prototypes(params, sx, sy, key, lite, serve_segment_sum,
                           mask=mask)

    def predict(params, task_state, qx):
        return _logits(params, task_state, qx)

    adapt, adapt_batch, predict_batch = _batched_api(adapt_one, predict)
    return MetaLearner(cfg, bb, init, meta_loss, adapt, predict,
                       adapt_batch, predict_batch)


# ===========================================================================
# CNAPs / Simple CNAPs (+ LITE): amortization; frozen backbone + FiLM
# ===========================================================================

def _film_as_params(bb: BackboneDef, bb_params, film):
    """Bundle (frozen backbone params, live FiLM tensors) into the pytree
    LITE treats as differentiable state, so the complement pass stops
    gradients through FiLM as required by Eq. 8."""
    return (tree_stop_gradient(bb_params), film)


def _make_cnaps_family(cfg: MetaLearnerConfig, bb: BackboneDef,
                       set_cfg: SetEncoderConfig, simple: bool) -> MetaLearner:
    fdim = bb.feature_dim

    def init(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        p = dict(
            bb=bb.init(k1),
            enc=init_set_encoder(k2, set_cfg),
            film_gen=init_film_generator(k3, set_cfg.task_dim,
                                         bb.film_sites, cfg.gen_hidden,
                                         out_std=cfg.film_init_std),
        )
        if not simple:   # CNAPs: classifier-weight generator MLP
            ka, kb = jax.random.split(k4)
            p["head_gen"] = dict(
                w1=lecun_normal(ka, (fdim, cfg.head_hidden)),
                b1=jnp.zeros((cfg.head_hidden,)),
                w2=lecun_normal(kb, (cfg.head_hidden, fdim + 1)),
                b2=jnp.zeros((fdim + 1,)),
            )
        return p

    def _task_embedding(params, sx, key, lite: LiteSpec, estimator=lite_sum,
                        mask=None):
        n = sx.shape[0] if mask is None else jnp.maximum(jnp.sum(mask), 1.0)

        def enc(p, x):
            return encode_set(p, x, set_cfg)

        z_sum = estimator(enc, params["enc"], sx, key, lite, mask=mask)
        return z_sum / n

    def _features(pf, x):
        # dtype-preserving: fp32 params give fp32 feats (as before); under
        # a LiteSpec.compute_dtype complement the bf16 feats stay bf16
        # (the memory win) — the estimator accumulates class stats in fp32.
        bbp, f = pf
        return bb.features(bbp, x, f)

    def _class_stats(params, film, sx, sy, key, lite: LiteSpec,
                     mode: str = "lite", mask=None):
        """Per-class feature sums (+ raw second moments for Simple CNAPs)
        through the kernel-dispatched fused estimators — the per-example
        (B, F, F) outer-product tensor is never materialized on the
        lite/serve paths (repro.core.lite.lite_class_stats).  The
        ``subsampled`` mode is the paper's naive small-task baseline: its
        forward sees only the H subset, so it keeps the literal
        outer-product composite (h is small by construction)."""
        pf = _film_as_params(bb, params["bb"], film)
        if mode == "subsampled":
            def encode(p, x):
                feat = _features(p, x)
                if simple:
                    outer = feat[:, :, None] * feat[:, None, :]
                    return dict(feat=feat, outer=outer)
                return dict(feat=feat)

            return _sub_seg(encode, pf, sx, sy, cfg.way, key, lite,
                            mask=mask)
        sum_fn = serve_sum if mode == "serve" else lite_sum
        return lite_class_stats(_features, pf, sx, sy, cfg.way, key, lite,
                                mask=mask, second_moment=simple,
                                sum_fn=sum_fn)

    def _configure(params, sx, sy, key, lite: LiteSpec,
                   sum_estimator=lite_sum, stats_mode="lite",
                   mask=None):
        """Support set -> task_state (film + head statistics).  The named
        scopes label each part in the compiled program's op names
        (profiler traces read them); they change no computation."""
        with jax.named_scope("cnaps.set_encoder"):
            z = _task_embedding(params, sx, key, lite, sum_estimator,
                                mask=mask)
        with jax.named_scope("cnaps.film_generator"):
            film = generate_film_params(params["film_gen"], z)
        with jax.named_scope("cnaps.class_stats"):
            sums, counts = _class_stats(params, film, sx, sy, key, lite,
                                        stats_mode, mask=mask)
        k_c = jnp.maximum(counts, 1.0)
        mu = sums["feat"] / k_c[:, None]                       # (C, F)
        state = dict(film=film, mu=mu)
        if simple:
            with jax.named_scope("cnaps.covariance"):
                # Simple CNAPs Mahalanobis statistics (paper Eq. in §3.1):
                # Sigma_c = l_c * S_c + (1 - l_c) * S_task + eps*I,
                # l_c = k/(k+1)
                ex2 = sums["outer"] / k_c[:, None, None]
                cov_c = ex2 - mu[:, :, None] * mu[:, None, :]
                n_tot = jnp.maximum(jnp.sum(counts), 1.0)
                mu_t = jnp.sum(sums["feat"], 0) / n_tot
                ex2_t = jnp.sum(sums["outer"], 0) / n_tot
                cov_t = ex2_t - jnp.outer(mu_t, mu_t)
                lam = (k_c / (k_c + 1.0))[:, None, None]
                sigma = lam * cov_c + (1.0 - lam) * cov_t[None]
                # scale-aware ridge: cov_eps plus a fraction of the mean
                # diagonal, so f32 cancellation in E[xx^T] - mu mu^T can
                # never push eigenvalues below the jitter (cholesky would
                # NaN).
                diag_mean = jnp.mean(jax.vmap(jnp.diag)(sigma), axis=-1)
                eps = cfg.cov_eps + 1e-3 * jnp.maximum(diag_mean, 0.0)
                sigma = sigma + eps[:, None, None] * jnp.eye(fdim)[None]
                state["chol"] = jax.vmap(jnp.linalg.cholesky)(sigma)
        else:
            h = jax.nn.relu(mu @ params["head_gen"]["w1"] + params["head_gen"]["b1"])
            wb = h @ params["head_gen"]["w2"] + params["head_gen"]["b2"]
            state["w"] = wb[:, :fdim]                          # (C, F)
            state["b"] = wb[:, fdim]
        return state

    def _logits(params, state, qx):
        qf = bb.features(tree_stop_gradient(params["bb"]), qx,
                         state["film"]).astype(jnp.float32)
        if simple:
            # Mahalanobis head through kernel dispatch: ref = the
            # cho_solve composite (bit-exact), pallas = the VMEM quadratic
            # -form kernel on the explicit inverse (custom_vjp backward);
            # serve-adapted states carry the precomputed inverse so query
            # dispatches skip the per-call O(C F^3) solves
            return -mahalanobis_head(qf, state["mu"], state["chol"],
                                     sinv=state.get("sinv"))
        return qf @ state["w"].T + state["b"]

    def meta_loss(params, task: Task, key, lite: LiteSpec, estimator=None):
        sub = estimator == "subsampled"
        sum_est = _sub_sum if sub else lite_sum
        state = _configure(params, task.support_x, task.support_y, key, lite,
                           sum_est, "subsampled" if sub else "lite",
                           mask=task.support_mask)
        with jax.named_scope("learner.query"):
            logits = _logits(params, state, task.query_x)
            loss = _xent(logits, task.query_y, task.query_mask)
        return loss, dict(
            accuracy=_accuracy(logits, task.query_y, task.query_mask))

    def adapt_one(params, sx, sy, mask, key, lite: LiteSpec):
        # forward-only serve estimators at both aggregation sites (set
        # encoder pooling + class statistics): exact, chunked, no grad
        state = _configure(params, sx, sy, key, lite,
                           sum_estimator=serve_sum,
                           stats_mode="serve", mask=mask)
        if simple and dispatch.resolve_backend() == "pallas":
            # pallas Mahalanobis head consumes the explicit inverse:
            # compute it ONCE at adaptation and carry it in the task
            # state, so every cached/repeated query dispatch skips the
            # O(C F^3) cho_solve solves (trace-time backend binding —
            # ref-backend states stay unchanged)
            state["sinv"] = dispatch.chol_inverse(state["chol"])
        return state

    def predict(params, task_state, qx):
        return _logits(params, task_state, qx)

    adapt, adapt_batch, predict_batch = _batched_api(adapt_one, predict)
    return MetaLearner(cfg, bb, init, meta_loss, adapt, predict,
                       adapt_batch, predict_batch)


# naive small-task estimators (paper's Fig-4 baseline) with matching signatures
def _sub_sum(encode_fn, params, xs, key, spec, mask=None):
    return subsampled_task_sum(encode_fn, params, xs, key, spec, mask=mask)


def _sub_seg(encode_fn, params, xs, ys, num_classes, key, spec, mask=None):
    """Naive small-task baseline with class-stratified subsampling (paper
    App. D.4 guarantees >=1 example/class so class statistics stay
    finite).  Forward AND backward see only the subset."""
    from repro.core.lite import sample_stratified_indices
    n = jax.tree.leaves(xs)[0].shape[0]
    h = spec.resolved_h(n)
    w = jnp.ones((n,), jnp.float32) if mask is None else mask
    n_real = n if mask is None else jnp.sum(mask)
    if spec.exact or h >= n:
        idx = jnp.arange(n)
        scale = 1.0
    else:
        idx = sample_stratified_indices(key, ys, num_classes, h, mask=mask)
        scale = n_real / jnp.minimum(float(h), jnp.maximum(n_real, 1.0))
    take = lambda a: jnp.take(a, idx, axis=0)
    xs_h = jax.tree.map(take, xs)
    onehot_h = jax.nn.one_hot(ys[idx], num_classes, dtype=jnp.float32) \
        * w[idx][:, None]
    enc = encode_fn(params, xs_h)
    sums = jax.tree.map(
        lambda e: scale * jnp.einsum("b...,bc->c...",
                                     e.astype(jnp.float32), onehot_h,
                                     precision=F32_CONTRACT),
        enc)
    counts = jnp.sum(jax.nn.one_hot(ys, num_classes, dtype=jnp.float32)
                     * w[:, None], axis=0)
    return sums, counts


# ===========================================================================
# First-order MAML (paper baseline; batched, no LITE needed)
# ===========================================================================

def make_fomaml(cfg: MetaLearnerConfig, bb: BackboneDef) -> MetaLearner:
    fdim = bb.feature_dim

    def init(key):
        k1, k2 = jax.random.split(key)
        return dict(bb=bb.init(k1),
                    head=dict(w=lecun_normal(k2, (fdim, cfg.way)),
                              b=jnp.zeros((cfg.way,))))

    def _logits_p(p, x):
        f = bb.features(p["bb"], x, None).astype(jnp.float32)
        return f @ p["head"]["w"] + p["head"]["b"]

    def _inner_adapt(params, sx, sy, sw=None):
        def inner_loss(p):
            return _xent(_logits_p(p, sx), sy, sw)

        p = params
        for _ in range(cfg.inner_steps):
            g = jax.grad(inner_loss)(p)
            p = jax.tree.map(lambda a, b: a - cfg.inner_lr * b, p, g)
        return p

    def meta_loss(params, task: Task, key, lite: LiteSpec, estimator=None):
        del key, lite, estimator
        adapted = _inner_adapt(params, task.support_x, task.support_y,
                               task.support_mask)
        # first-order: treat the adapted point as a constant offset
        adapted = jax.tree.map(
            lambda a, b: a + jax.lax.stop_gradient(b - a), params, adapted)
        logits = _logits_p(adapted, task.query_x)
        loss = _xent(logits, task.query_y, task.query_mask)
        return loss, dict(
            accuracy=_accuracy(logits, task.query_y, task.query_mask))

    def adapt_one(params, sx, sy, mask, key, lite: LiteSpec):
        del key, lite  # inner SGD is deterministic; no aggregation sites
        return _inner_adapt(params, sx, sy, mask)

    def predict(params, task_state, qx):
        return _logits_p(task_state, qx)

    adapt, adapt_batch, predict_batch = _batched_api(adapt_one, predict)
    return MetaLearner(cfg, bb, init, meta_loss, adapt, predict,
                       adapt_batch, predict_batch)


# ===========================================================================
# FineTuner transfer baseline (frozen backbone, linear head, K steps)
# ===========================================================================

def make_finetuner(cfg: MetaLearnerConfig, bb: BackboneDef) -> MetaLearner:
    fdim = bb.feature_dim

    def init(key):
        return dict(bb=bb.init(key))

    def adapt_one(params, sx, sy, mask, key, lite: LiteSpec):
        del key, lite
        sw = mask
        feats = bb.features(tree_stop_gradient(params["bb"]), sx, None)
        feats = jax.lax.stop_gradient(feats).astype(jnp.float32)
        head = dict(w=jnp.zeros((fdim, cfg.way)), b=jnp.zeros((cfg.way,)))

        def loss(h):
            logits = feats @ h["w"] + h["b"]
            return _xent(logits, sy, sw)

        def body(h, _):
            g = jax.grad(loss)(h)
            return jax.tree.map(lambda a, b: a - cfg.inner_lr * b, h, g), None

        head, _ = jax.lax.scan(body, head, None, length=cfg.inner_steps)
        return head

    def predict(params, head, qx):
        qf = bb.features(params["bb"], qx, None).astype(jnp.float32)
        return qf @ head["w"] + head["b"]

    def meta_loss(params, task: Task, key, lite: LiteSpec, estimator=None):
        head = adapt_one(params, task.support_x, task.support_y,
                         task.support_mask, key, lite)
        logits = predict(params, head, task.query_x)
        return _xent(logits, task.query_y, task.query_mask), dict(
            accuracy=_accuracy(logits, task.query_y, task.query_mask))

    adapt, adapt_batch, predict_batch = _batched_api(adapt_one, predict)
    return MetaLearner(cfg, bb, init, meta_loss, adapt, predict,
                       adapt_batch, predict_batch)


# ===========================================================================
# factory
# ===========================================================================

def make_learner(cfg: MetaLearnerConfig, bb: BackboneDef,
                 set_cfg: Optional[SetEncoderConfig] = None) -> MetaLearner:
    if cfg.kind == "protonets":
        return make_protonets(cfg, bb)
    if cfg.kind in ("cnaps", "simple_cnaps"):
        if set_cfg is None:
            raise ValueError("CNAPs-family learners need a SetEncoderConfig")
        return _make_cnaps_family(cfg, bb, set_cfg, simple=cfg.kind == "simple_cnaps")
    if cfg.kind == "fomaml":
        return make_fomaml(cfg, bb)
    if cfg.kind == "finetuner":
        return make_finetuner(cfg, bb)
    raise ValueError(f"unknown meta-learner kind: {cfg.kind}")
