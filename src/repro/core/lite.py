"""LITE — Large Image and Task Episodic training (Bronskill et al., NeurIPS 2021).

The paper's contribution, as a composable JAX transform.

Core observation (paper Eq. 5–8): when the loss depends on the support set
only through a permutation-invariant *sum* of per-example encodings,

    L = L( e_phi(D_S) ),   e_phi(D_S) = sum_n e_phi(x_n, y_n),

the gradient decomposes over support examples and admits the unbiased
Monte-Carlo estimator

    dL/dphi  ≈  (N/H) * L'(e_phi(D_S)) * sum_{h in H} d e^(n_h) / dphi,

where the H indices are drawn uniformly from {1..N}.  Crucially the forward
value e_phi(D_S) is EXACT (all N examples contribute); only the backward pass
is subsampled.  Memory drops from O(N) stored activations to
O(|H| + chunk_size): the complement set is forwarded in no-grad chunks whose
activations XLA never materializes for backward.

JAX realization
---------------
PyTorch toggles ``torch.grad.enabled``; in JAX the same effect is a
straight-through combinator built from ``lax.stop_gradient``:

    combined = value_full_stopped + scale * (value_H - stop_grad(value_H))

whose forward value is the exact full-set sum and whose backward is
``scale * d(value_H)``.  The complement ("H-bar") forward runs under
``stop_gradient``-ed parameters inside ``lax.map`` so that peak live
activations are bounded by one chunk — this is what makes LITE a *memory*
optimization rather than a notational one.

All public entry points operate on arbitrary pytrees of encodings so they can
aggregate anything a meta-learner pools: deep-set embeddings, backbone
features, per-class segment sums, inner-loop gradients (MAML, Eq. 3).

Every estimator is generic over a *reduction*: the default collapses the
example axis by weight-and-sum (the historical composite, bit-for-bit),
while the class-statistics entry points (:func:`lite_segment_sum`,
:func:`lite_class_stats` and their serve twins) run their chunk bodies
through :mod:`repro.kernels.dispatch`, so per-class sums and Simple
CNAPs second moments are kernel-fused on the ``ref``/``pallas`` backends
— the per-example ``(B, F, F)`` outer-product tensor the covariance path
used to materialize is gone from the H pass, the no-grad complement
chunks, and the exact path alike.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.common.tree import tree_cast, tree_stop_gradient
from repro.kernels import dispatch

PyTree = Any
EncodeFn = Callable[[PyTree, PyTree], PyTree]  # (params, batched_inputs) -> per-example encodings


@dataclasses.dataclass(frozen=True)
class LiteSpec:
    """Static configuration for one LITE aggregation site.

    Attributes:
      h: number of support examples to back-propagate (|H| in the paper).
         ``h >= n`` disables subsampling (exact gradient).
      chunk_size: batch size for the no-grad complement forward. Bounds
         activation memory of the H-bar pass. ``None`` -> one chunk.
      exact: force exact gradients (baseline / eval mode).
      compute_dtype: optional dtype name (e.g. ``"bfloat16"``) for the
         no-grad COMPLEMENT forward only: frozen params and inputs are cast
         down, per-chunk encodings are summed with float32 accumulation.
         The differentiable H pass is untouched, so gradients are
         bit-identical to the full-precision estimator; only the exact
         forward value carries low-precision rounding.  At large N the
         complement dominates the FLOPs and the live chunk activations, so
         this is the fast/low-memory path.  Ignored in exact mode (there
         is no complement pass).
    """

    h: int = 8
    chunk_size: int | None = None
    exact: bool = False
    compute_dtype: str | None = None

    def resolved_h(self, n: int) -> int:
        return n if self.exact else min(self.h, n)


def sample_stratified_indices(key: jax.Array, ys: jnp.ndarray,
                              num_classes: int, h: int,
                              mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """h indices with >= 1 example per class when h >= num_classes (the
    guarantee the paper's sub-sampled-task baseline uses, App. D.4 — a
    class with zero samples would make the naive baseline's class
    statistics singular).

    Built on the same per-index scores as ``sample_h_indices``: each
    example's within-class rank comes from ordering the class by its
    (key, index)-only uniforms, so — like the LITE draw — a task padded to
    a larger bucket selects the identical subset (padded rows contribute
    zero to every class count and rank strictly last)."""
    n = ys.shape[0]
    u = _index_scores(key, n)
    order = jnp.argsort(u)
    onehot_sorted = jax.nn.one_hot(ys[order], num_classes, dtype=jnp.float32)
    if mask is not None:
        onehot_sorted = onehot_sorted * mask[order][:, None]
    # rank of each row within its class when the class is ordered by u
    rank_sorted = jnp.sum(jnp.cumsum(onehot_sorted, axis=0) * onehot_sorted,
                          axis=1) - 1.0
    scores = jnp.zeros((n,)).at[order].set(rank_sorted + 0.5 * u[order])
    if mask is not None:
        scores = scores + 2.0 * n * (1.0 - mask)
    return jnp.argsort(scores)[:h]


def _index_scores(key: jax.Array, n: int) -> jnp.ndarray:
    """Per-index uniform scores depending only on (key, index).

    Built from ``fold_in`` per index rather than one shaped draw so the
    score of index i is invariant to n — a task padded to a larger bucket
    scores its real examples identically and therefore draws the same H
    subset (the padding-invariance the task-batch collator relies on).
    """
    return jax.vmap(
        lambda i: jax.random.uniform(jax.random.fold_in(key, i)))(jnp.arange(n))


def sample_h_indices(key: jax.Array, n: int, h: int,
                     mask: jnp.ndarray | None = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sample H distinct indices uniformly (without replacement) and return
    (h_idx[h], comp_idx[n-h]).

    Sampling *without* replacement matches the paper's Algorithm 1 line 4 in
    the regime H <= N and keeps the estimator unbiased (each index has equal
    marginal inclusion probability H/N, and the N/H rescaling corrects it).
    Ranking per-index scores yields the uniform permutation; with ``mask``
    (1 real / 0 padding) padded slots rank strictly after every real slot,
    so H fills with real examples first and the draw matches the unpadded
    task's draw index-for-index.
    """
    scores = _index_scores(key, n)
    if mask is not None:
        scores = scores + 2.0 * (1.0 - mask)
    order = jnp.argsort(scores)
    return order[:h], order[h:]


def straight_through(full_value: PyTree, grad_value: PyTree, scale) -> PyTree:
    """forward = full_value ; backward = scale * d(grad_value).

    Leaf-wise:  stop(full) + scale * (grad - stop(grad)).
    """

    def _one(f, g):
        return jax.lax.stop_gradient(f) + scale * (g - jax.lax.stop_gradient(g))

    return jax.tree.map(_one, full_value, grad_value)


def _chunked_nograd_reduce(reduce_fn: Callable, frozen_params: PyTree,
                           xs: PyTree, w: jnp.ndarray,
                           chunk_size: int | None,
                           accum_dtype: jnp.dtype | None = None) -> PyTree:
    """Weighted reduction of per-example encodings over xs, computed under
    stop-gradient'ed parameters, in sequential chunks via ``lax.map`` (so
    only one chunk's activations are ever live).

    ``reduce_fn(params, (xs_chunk, w_chunk), accum_dtype)`` collapses one
    chunk's leading example axis (default: weight rows and sum — see
    :func:`_weighted_reduce`; the segment-statistics sites pass a
    :mod:`repro.kernels.dispatch` reduction instead, which is what keeps
    fused class stats chunk-bounded too).  The chunk-pad tail folds into
    ``w`` as zero weights — 0/1 weight algebra keeps that bit-exact with
    masking the encodings after the fact.  ``accum_dtype`` upcasts each
    chunk's reduction (and the cross-chunk sum) — the fp32 accumulator
    the mixed-precision complement pass relies on."""
    leaves = jax.tree.leaves(xs)
    n = leaves[0].shape[0]
    if n == 0:
        raise ValueError("empty complement — use exact mode instead")
    xs = tree_stop_gradient(xs)

    if chunk_size is None or chunk_size >= n:
        return reduce_fn(frozen_params, (xs, w), accum_dtype)

    # Pad to a multiple of chunk_size; the padded tail carries zero weight.
    num_chunks = -(-n // chunk_size)
    pad = num_chunks * chunk_size - n

    def _pad(a):
        cfg = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, cfg)

    def _reshape(a):
        return a.reshape((num_chunks, chunk_size) + a.shape[1:])

    xs_c = jax.tree.map(lambda a: _reshape(_pad(a)), xs)
    w_c = _reshape(_pad(w))

    def _one_chunk(args):
        chunk, wc = args
        return reduce_fn(frozen_params, (chunk, wc), accum_dtype)

    partials = jax.lax.map(_one_chunk, (xs_c, w_c))
    return jax.tree.map(lambda p: jnp.sum(p, axis=0), partials)


def _masked_encode(encode_fn: EncodeFn) -> EncodeFn:
    """Wrap encode_fn to take (inputs, mask) and zero-weight masked rows."""

    def enc(params, xm):
        xs, m = xm
        e = encode_fn(params, xs)
        return jax.tree.map(
            lambda t: t * m.reshape((-1,) + (1,) * (t.ndim - 1)).astype(t.dtype),
            e)

    return enc


def _weighted_reduce(encode_fn: EncodeFn) -> Callable:
    """Default estimator reduction: encode per-example, zero-weight masked
    rows, sum the leading axis — the same composite the estimators always
    ran, bit-for-bit.  Signature: ``reduce(params, (xs, w), accum_dtype)``
    with ``w`` the (N,) 0/1 validity weights."""
    enc_w = _masked_encode(encode_fn)

    def reduce_fn(params, xm, accum_dtype=None):
        enc = enc_w(params, xm)
        return jax.tree.map(
            lambda e: jnp.sum(e, axis=0, dtype=accum_dtype), enc)

    return reduce_fn


def _ones_mask_like(xs: PyTree) -> jnp.ndarray:
    """All-real validity mask for an unmasked input set.  ``mask=None`` and
    an explicit ones mask are the SAME estimator bit-for-bit (weighting by
    1.0 is exact and padded slots simply don't exist), which is what lets
    ``lite_sum``/``subsampled_task_sum`` share one body."""
    return jnp.ones((jax.tree.leaves(xs)[0].shape[0],), jnp.float32)


def _masked_scale(mask: jnp.ndarray, h: int) -> jnp.ndarray:
    """N/H rescale over REAL examples only: when fewer than H real examples
    exist every real example lands in H and the gradient is exact
    (scale 1)."""
    n_real = jnp.sum(mask)
    return n_real / jnp.minimum(float(h), jnp.maximum(n_real, 1.0))


def lite_sum(encode_fn: EncodeFn, params: PyTree, xs: PyTree, key: jax.Array,
             spec: LiteSpec, mask: jnp.ndarray | None = None,
             reduce_fn: Callable | None = None) -> PyTree:
    """LITE estimator of ``sum_n encode_fn(params, x_n)`` (paper Eq. 8).

    Forward value: exact sum over all N examples.
    Backward: (N/H) * d/dparams [ sum over the H sampled examples ].

    Args:
      encode_fn: maps (params, batched inputs) -> per-example encodings
        (any pytree whose leaves have a leading example axis).  May be
        ``None`` when ``reduce_fn`` is given.
      params: differentiable parameters.
      xs: pytree of support inputs, leading axis N on every leaf.
      key: PRNG key for the H subset draw.
      spec: LiteSpec.  With ``spec.compute_dtype`` the complement forward
        runs under down-cast frozen params/inputs with fp32 accumulation —
        gradients are untouched (they flow only through the H pass).
      mask: optional (N,) validity weights (1 real / 0 collator padding).
        Padded rows contribute nothing to forward or backward; the N/H
        rescale uses the REAL count, so a padded task batch reproduces the
        unpadded task's estimator exactly.  ``None`` is exactly equivalent
        to an all-ones mask.
      reduce_fn: optional fused reduction replacing the default
        encode-weight-sum composite; ``reduce_fn(params, (xs_rows,
        w_rows), accum_dtype)`` must collapse the leading example axis of
        a row subset.  This is the hook the class-statistics sites use to
        run their chunk bodies through :mod:`repro.kernels.dispatch`
        (H pass, complement chunks, and exact path all go through it, so
        the estimator algebra is unchanged).

    Returns:
      Pytree of summed encodings (leading axis reduced).
    """
    n = jax.tree.leaves(xs)[0].shape[0]
    h = spec.resolved_h(n)
    if mask is None:
        mask = _ones_mask_like(xs)
    if reduce_fn is None:
        reduce_fn = _weighted_reduce(encode_fn)
    if spec.exact or h >= n:
        return reduce_fn(params, (xs, mask), None)

    # The named scopes label the three parts in the compiled program's op
    # names (profiler traces read them); they change no computation.
    with jax.named_scope("lite.split"):
        h_idx, comp_idx = sample_h_indices(key, n, h, mask)
        take = lambda a, i: jnp.take(a, i, axis=0)
        xs_h = jax.tree.map(partial(take, i=h_idx), xs)
        xs_c = jax.tree.map(partial(take, i=comp_idx), xs)
        w_c = mask[comp_idx]

    # Differentiable pass over H (single batch — |H| is small by
    # construction).
    with jax.named_scope("lite.h_pass"):
        sum_h = reduce_fn(params, (xs_h, mask[h_idx]), None)

    # No-grad pass over the complement, chunked; optionally in low
    # precision (the dominant FLOPs at large N) with fp32 accumulation.
    with jax.named_scope("lite.complement"):
        frozen = tree_stop_gradient(params)
        accum = None
        if spec.compute_dtype is not None:
            cd = jnp.dtype(spec.compute_dtype)
            frozen = tree_cast(frozen, cd)
            xs_c = tree_cast(xs_c, cd)
            accum = jnp.float32
        sum_c = _chunked_nograd_reduce(reduce_fn, frozen, xs_c, w_c,
                                       spec.chunk_size, accum_dtype=accum)

    full = jax.tree.map(lambda a, b: jax.lax.stop_gradient(a + b.astype(a.dtype)),
                        sum_h, sum_c)
    return straight_through(full, sum_h, _masked_scale(mask, h))


def serve_sum(encode_fn: EncodeFn, params: PyTree, xs: PyTree, key: jax.Array,
              spec: LiteSpec, mask: jnp.ndarray | None = None,
              reduce_fn: Callable | None = None) -> PyTree:
    """Serve-time twin of :func:`lite_sum`: the EXACT masked sum, computed
    the way LITE computes its complement — forward-only under
    ``stop_gradient``, in ``spec.chunk_size``-bounded chunks, optionally in
    low precision (``spec.compute_dtype``) with fp32 accumulation.

    Adaptation at serve time is a pure forward pass ("just a few
    optimization steps or a single forward pass" per new task — there is no
    meta-gradient to estimate), so the H-subset machinery is unnecessary:
    what LITE contributes at serve is the *memory* discipline of its
    complement pass, which lets a 1000-image support set adapt under the
    same O(chunk) activation bound as training.  ``key`` and
    ``spec.h``/``spec.exact`` are accepted (signature-compatible with
    ``lite_sum`` so learners thread it through the same estimator sites)
    but ignored.

    With ``chunk_size=None`` the value is bit-identical to exact
    ``lite_sum`` (same masked encode, same single ``jnp.sum``); chunking
    only reassociates the cross-chunk accumulation.  ``reduce_fn`` is the
    same fused-reduction hook as :func:`lite_sum`'s.
    """
    del key  # nothing is subsampled
    if mask is None:
        mask = _ones_mask_like(xs)
    if reduce_fn is None:
        reduce_fn = _weighted_reduce(encode_fn)
    frozen = tree_stop_gradient(params)
    xs = tree_stop_gradient(xs)
    accum = None
    if spec.compute_dtype is not None:
        cd = jnp.dtype(spec.compute_dtype)
        frozen = tree_cast(frozen, cd)
        xs = tree_cast(xs, cd)
        accum = jnp.float32
    return _chunked_nograd_reduce(reduce_fn, frozen, xs, mask,
                                  spec.chunk_size, accum_dtype=accum)


def _masked_onehot(ys: jnp.ndarray, num_classes: int,
                   mask: jnp.ndarray | None) -> jnp.ndarray:
    onehot_all = jax.nn.one_hot(ys, num_classes, dtype=jnp.float32)  # (N, C)
    if mask is not None:
        # padded labels are -1 (already a zero one-hot row); the explicit
        # product keeps counts exact even if a collator pads with 0..way-1
        onehot_all = onehot_all * mask[:, None]
    return onehot_all


def lite_segment_sum(encode_fn: EncodeFn, params: PyTree, xs: PyTree,
                     ys: jnp.ndarray, num_classes: int, key: jax.Array,
                     spec: LiteSpec, mask: jnp.ndarray | None = None,
                     sum_fn: Callable | None = None,
                     backend: str | None = None
                     ) -> Tuple[PyTree, jnp.ndarray]:
    """LITE estimator of per-class sums  S_c = sum_n 1(y_n = c) e(x_n).

    Needed by metric heads (ProtoNets prototypes, Simple CNAPs class
    means/covariances) and CNAPs' class-pooled classifier generator.  A single
    global N/H rescale keeps every class-sum unbiased because the H draw is
    uniform over ALL support indices:  E[sum_{h} 1(y=c) de] = (H/N) * S'_c.

    The chunk bodies (H pass, no-grad complement chunks, exact path) run
    through :func:`repro.kernels.dispatch.segment_sum` — ``backend``
    selects the implementation (None = the ambient dispatch default; the
    ``ref``/``naive`` backends reproduce the pre-dispatch expand+reduce
    composite bit-for-bit, ``pallas`` runs the one-hot MXU matmul kernel
    under a ``custom_vjp``).

    ``sum_fn`` swaps the underlying set-sum estimator (default
    :func:`lite_sum`); :func:`serve_segment_sum` passes :func:`serve_sum`
    for the forward-only serve path.

    Returns (class_sums pytree with leading axis C, counts[C] float32).
    Counts are exact (labels are not subsampled).
    """
    onehot_all = _masked_onehot(ys, num_classes, mask)
    counts = jnp.sum(onehot_all, axis=0)  # exact

    def seg_reduce(p, xm, accum_dtype=None):
        (inputs, onehot), w = xm
        # the 0/1 row weights (validity + chunk-pad tail) fold into the
        # one-hot — exact in ANY float dtype, so a low-precision
        # complement pass stays low-precision (fp32 class sums come from
        # the accum_dtype accumulation)
        oh = onehot * w.astype(onehot.dtype)[:, None]
        enc = encode_fn(p, inputs)  # leaves (B, ...)
        return jax.tree.map(
            lambda e: dispatch.segment_sum(e, oh, accum_dtype=accum_dtype,
                                           backend=backend), enc)

    sums = (sum_fn or lite_sum)(None, params, (xs, onehot_all), key,
                                spec, mask=mask, reduce_fn=seg_reduce)
    return sums, counts


def serve_segment_sum(encode_fn: EncodeFn, params: PyTree, xs: PyTree,
                      ys: jnp.ndarray, num_classes: int, key: jax.Array,
                      spec: LiteSpec, mask: jnp.ndarray | None = None,
                      backend: str | None = None
                      ) -> Tuple[PyTree, jnp.ndarray]:
    """Serve-time twin of :func:`lite_segment_sum`: exact per-class sums via
    :func:`serve_sum` — forward-only, chunked, optional low-precision
    compute with fp32 accumulation.  See ``serve_sum`` for the contract."""
    return lite_segment_sum(encode_fn, params, xs, ys, num_classes, key,
                            spec, mask=mask, sum_fn=serve_sum,
                            backend=backend)


def lite_class_stats(features_fn: Callable, params: PyTree, xs: PyTree,
                     ys: jnp.ndarray, num_classes: int, key: jax.Array,
                     spec: LiteSpec, mask: jnp.ndarray | None = None,
                     second_moment: bool = False,
                     sum_fn: Callable | None = None,
                     backend: str | None = None
                     ) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """Fused per-class feature statistics under the LITE estimator.

    ``features_fn(params, inputs) -> (B, F)`` is a single feature matrix
    (NOT a pytree).  Returns ``(stats, counts)`` with ``stats["feat"]``
    the per-class feature sums (C, F) and — when ``second_moment`` —
    ``stats["outer"]`` the per-class raw second moments
    ``sum_n 1(y_n = c) f_n f_n^T`` (C, F, F).

    The point of this entry over :func:`lite_segment_sum` with an
    outer-product encode: the chunk bodies go through
    :func:`repro.kernels.dispatch.class_second_moment`, so on the ``ref``
    and ``pallas`` backends the per-example ``(B, F, F)`` outer tensor is
    NEVER materialized — not in the H pass, not in the no-grad complement
    chunks, not on the exact path.  Live bytes per chunk drop from
    O(chunk * F^2 * way) to O(chunk * F * way + F^2 * way).  (The
    ``naive`` backend keeps the materializing composite as the bit-exact
    legacy oracle; fused contractions reassociate the example-axis sum,
    so their fp32 bits differ from naive at the last ulp.)

    Same estimator algebra as every LITE site: exact forward, H-subset
    backward with the global N/H rescale, mask/padded-lane invariance,
    ``spec.compute_dtype`` complement with fp32 accumulation.
    """
    onehot_all = _masked_onehot(ys, num_classes, mask)
    counts = jnp.sum(onehot_all, axis=0)  # exact

    def stats_reduce(p, xm, accum_dtype=None):
        (inputs, onehot), w = xm
        oh = onehot * w.astype(onehot.dtype)[:, None]
        feat = features_fn(p, inputs)                       # (B, F)
        out = dict(feat=dispatch.segment_sum(feat, oh,
                                             accum_dtype=accum_dtype,
                                             backend=backend))
        if second_moment:
            out["outer"] = dispatch.class_second_moment(
                feat, oh, accum_dtype=accum_dtype, backend=backend)
        return out

    stats = (sum_fn or lite_sum)(None, params, (xs, onehot_all), key,
                                 spec, mask=mask, reduce_fn=stats_reduce)
    return stats, counts


def lite_value_and_grad(loss_fn: Callable, argnums: int = 0):
    """Convenience: ``jax.value_and_grad`` for losses already built on
    ``lite_sum``/``lite_segment_sum`` sites.  Exists so call sites read as a
    single named concept; the estimator itself lives in the combinators."""
    return jax.value_and_grad(loss_fn, argnums=argnums)


# ---------------------------------------------------------------------------
# Naive baseline the paper compares against (Fig. 4 / Table D.8): training on
# a sub-sampled *small task* — forward AND backward see only H examples.
# ---------------------------------------------------------------------------


def subsampled_task_sum(encode_fn: EncodeFn, params: PyTree, xs: PyTree,
                        key: jax.Array, spec: LiteSpec,
                        mask: jnp.ndarray | None = None) -> PyTree:
    """Forward and backward both restricted to the H subset, rescaled by N/H
    so the *expected forward value* matches the full sum.  Unbiased in value
    but — unlike LITE — the downstream L'(e) factor is evaluated at a noisy
    encoding, which is what inflates its gradient RMSE (paper Fig. 4)."""
    n = jax.tree.leaves(xs)[0].shape[0]
    h = spec.resolved_h(n)
    if mask is None:
        mask = _ones_mask_like(xs)
    enc_w = _masked_encode(encode_fn)
    if spec.exact or h >= n:
        enc = enc_w(params, (xs, mask))
        return jax.tree.map(lambda e: jnp.sum(e, axis=0), enc)
    h_idx, _ = sample_h_indices(key, n, h, mask)
    enc = enc_w(params, (jax.tree.map(lambda a: jnp.take(a, h_idx, axis=0), xs),
                         mask[h_idx]))
    scale = _masked_scale(mask, h)
    return jax.tree.map(lambda e: scale * jnp.sum(e, axis=0), enc)
