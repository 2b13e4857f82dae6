"""Kernel dispatch for the episodic hot path: one policy, three backends.

Every support-set aggregation the meta-learners run — per-class feature
sums, the Simple CNAPs raw second moment E[x x^T], and the Mahalanobis
head — goes through the ops in this module instead of open-coded jnp.
Each op selects an implementation per *backend*:

  ``naive``   the literal pre-dispatch composite (per-example expansion,
              then a plain axis-0 reduce).  For the second moment this
              materializes the per-example ``(B, F, F)`` outer-product
              tensor — the memory bottleneck this subsystem exists to
              kill — so it survives only as the bit-exact legacy oracle
              the parity tests compare against.
  ``ref``     jnp, reassociated so XLA contracts over the example axis
              without the ``(B, F, F)`` intermediate (the second moment
              becomes ``"bc,bi,bj->cij"`` via a ``(B, C, F)`` hop —
              C = way << F).  This is the default, and the fast path on
              CPU/GPU.  For the first-order ops (plain segment sums, the
              cho_solve Mahalanobis head) there is no intermediate to
              kill, so ``ref`` keeps the ``naive`` formula and stays
              bit-exact with the pre-dispatch code; only the second
              moment is reassociated (same values to ~1e-5 fp32 — dot
              and reduce accumulate in different orders, so last-ulp
              bits legitimately differ; see the parity tests).
  ``pallas``  the Pallas kernels (repro.kernels.segment_pool one-hot MXU
              matmuls, repro.kernels.mahalanobis quadratic form), run in
              interpret mode off-TPU and lowered to Mosaic on TPU.  Each
              forward is wrapped in ``jax.custom_vjp`` with ref-math
              backwards, so the kernels are differentiable inside the
              LITE H-pass (the no-grad complement never calls the VJP).
  ``auto``    resolves to ``pallas`` on TPU, ``ref`` elsewhere.

Backend selection is *trace-time*: each op takes ``backend=None`` which
resolves against the module default (``set_default_backend`` /
``use_backend``).  Config plumbing: ``MetaTrainConfig.kernel_backend``
(bound by the episodic train-step adapter), the serving engine's
``kernel_backend`` argument (bound at engine construction), and
``--kernel-backend`` on both launchers.  Because the backend binds when
a function is lowered, a per-shape compile cache
(:class:`repro.train.pipeline.BucketedStepCache`) keyed on shapes alone
never recompiles when the ambient backend flips — switching backends on
a warm cache is a no-op by design (flat compile counters), and an engine
that wants a different backend is a new engine.

Weights everywhere are *mask-folded one-hots*: ``(B, C)`` float arrays
whose rows are zero for padded/invalid examples.  Zero-weight rows
contribute exactly nothing, which is what makes padded ``TaskBatch``
lanes work natively through every backend.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import int8_matmul as _im
from repro.kernels import mahalanobis as _md
from repro.kernels import segment_pool as _sp
from repro.kernels.tpu_compat import F32_CONTRACT
from repro.kernels.tpu_compat import interpret_mode as _interpret
from repro.optim import quant as _quant

BACKENDS = ("naive", "ref", "pallas", "auto")

# ContextVar, not a module global: engines/steps built with different
# backends may trace concurrently from different threads (the serving
# engine and the prefetching train loop live in one process) — each
# thread/context resolves its own binding, so one engine's use_backend
# scope can never leak into another's lowering.
_default_backend: contextvars.ContextVar = contextvars.ContextVar(
    "repro_kernel_backend", default="ref")


def _check(backend: str) -> str:
    if backend not in BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}; "
                         f"choose from {BACKENDS}")
    return backend


def set_default_backend(backend: str) -> None:
    """Set the current context's default backend (resolved at trace
    time).  Scoped per thread/context — prefer :func:`use_backend` for
    anything bounded."""
    _default_backend.set(_check(backend))


def get_default_backend() -> str:
    return _default_backend.get()


@contextlib.contextmanager
def use_backend(backend: Optional[str]):
    """Scoped default backend (None = leave the current default)."""
    token = None
    if backend is not None:
        token = _default_backend.set(_check(backend))
    try:
        yield
    finally:
        if token is not None:
            _default_backend.reset(token)


def pallas_native() -> bool:
    """The platform policy: True where Pallas kernels lower to Mosaic (TPU),
    False where they would run interpreted.  ``auto`` resolves by it, and
    so do the kernels chosen by platform and shape alone (the backbone's
    fused image block)."""
    return not _interpret()


def resolve_backend(backend: Optional[str] = None) -> str:
    """None -> context default; ``auto`` -> pallas on TPU else ref."""
    b = _check(_default_backend.get() if backend is None else backend)
    if b == "auto":
        return "pallas" if pallas_native() else "ref"
    return b


# ===========================================================================
# segment_sum: per-class weighted sums  S[c, ...] = sum_b w[b, c] e[b, ...]
# ===========================================================================


def _segment_sum_expand(e: jnp.ndarray, weights: jnp.ndarray,
                        accum_dtype) -> jnp.ndarray:
    """The pre-dispatch composite, bit-for-bit: expand to (B, C, ...) and
    reduce axis 0.  Weights are 0/1 (mask-folded one-hots), so any
    association of the elementwise products is exact — this formula is
    shared by ``naive`` and ``ref`` (no big intermediate to kill: the hop
    is (B, C, ...) with C = way).  The expansion is an elementwise
    product, not a dot, so no matmul precision applies to it."""
    w = weights.astype(e.dtype).reshape(weights.shape + (1,) * (e.ndim - 1))
    expanded = e[:, None] * w
    return jnp.sum(expanded, axis=0, dtype=accum_dtype)


@jax.custom_vjp
def _segment_sum_pallas(x: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """x: (B, K); weights: (B, C) -> (C, K) float32 via the one-hot-matmul
    segment_pool kernel (interpret off-TPU)."""
    return _sp.segment_pool_weighted(x, weights, interpret=_interpret())


def _segment_sum_pallas_fwd(x, weights):
    return _segment_sum_pallas(x, weights), (x, weights)


def _segment_sum_pallas_bwd(res, g):
    x, weights = res
    g = g.astype(jnp.float32)
    dx = jnp.einsum("bc,ck->bk", weights.astype(jnp.float32), g)
    dw = jnp.einsum("bk,ck->bc", x.astype(jnp.float32), g)
    return dx.astype(x.dtype), dw.astype(weights.dtype)


_segment_sum_pallas.defvjp(_segment_sum_pallas_fwd, _segment_sum_pallas_bwd)


def segment_sum(e: jnp.ndarray, weights: jnp.ndarray,
                accum_dtype=None, backend: Optional[str] = None
                ) -> jnp.ndarray:
    """Per-class weighted sum: ``out[c, ...] = sum_b weights[b, c] *
    e[b, ...]``.

    ``weights`` is a mask-folded one-hot (zero rows = padded lanes drop
    out natively).  ``accum_dtype`` upcasts the reduction (the fp32
    accumulator of the mixed-precision LITE complement).  ``naive`` and
    ``ref`` share the expand+reduce formula (bit-exact with the
    pre-dispatch code); ``pallas`` runs the MXU one-hot matmul under a
    ``custom_vjp`` with ref-math backward.
    """
    b = resolve_backend(backend)
    if b in ("naive", "ref"):
        return _segment_sum_expand(e, weights, accum_dtype)
    lead = e.shape[0]
    flat = e.reshape(lead, -1)
    out = _segment_sum_pallas(flat, weights)
    out = out.astype(accum_dtype or e.dtype)
    return out.reshape((weights.shape[1],) + e.shape[1:])


# ===========================================================================
# class_second_moment: S[c, i, j] = sum_b w[b, c] f[b, i] f[b, j]
# ===========================================================================


def _second_moment_naive(f, weights, accum_dtype):
    """Pre-dispatch composite: per-example outer products (B, F, F),
    expanded to (B, C, F, F), reduced over b.  The memory bottleneck —
    kept verbatim as the bit-exact oracle."""
    outer = f[:, :, None] * f[:, None, :]
    return _segment_sum_expand(outer, weights, accum_dtype)


def _second_moment_ref(f, weights, accum_dtype):
    """Reassociated ``"bc,bi,bj->cij"``: hop through (B, C, F) — C = way,
    so the intermediate is C/F the size of one (B, F, F) outer tensor —
    then contract the example axis on the MXU/GEMM.  Same math as naive;
    dot-vs-reduce accumulation orders differ, so bits may differ at the
    last ulp (fp32 ~1e-5 at N=1000)."""
    t = weights.astype(f.dtype)[:, :, None] * f[:, None, :]
    return jnp.einsum("bci,bj->cij", t, f, precision=F32_CONTRACT,
                      preferred_element_type=accum_dtype or f.dtype
                      ).astype(accum_dtype or f.dtype)


@jax.custom_vjp
def _second_moment_pallas(f: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    return _sp.class_second_moment(f, weights, interpret=_interpret())


def _second_moment_pallas_fwd(f, weights):
    return _second_moment_pallas(f, weights), (f, weights)


def _second_moment_pallas_bwd(res, g):
    f, weights = res
    f32, w32, g32 = (t.astype(jnp.float32) for t in (f, weights, g))
    gs = g32 + jnp.swapaxes(g32, -1, -2)
    # df[b, i] = sum_{c,j} w[b, c] (g[c, i, j] + g[c, j, i]) f[b, j]
    df = jnp.einsum("bc,cij,bj->bi", w32, gs, f32)
    # dw[b, c] = sum_{i,j} g[c, i, j] f[b, i] f[b, j]
    dw = jnp.einsum("bi,cij,bj->bc", f32, g32, f32)
    return df.astype(f.dtype), dw.astype(weights.dtype)


_second_moment_pallas.defvjp(_second_moment_pallas_fwd,
                             _second_moment_pallas_bwd)


def class_second_moment(f: jnp.ndarray, weights: jnp.ndarray,
                        accum_dtype=None, backend: Optional[str] = None
                        ) -> jnp.ndarray:
    """Per-class raw second moment ``out[c, i, j] = sum_b weights[b, c] *
    f[b, i] * f[b, j]`` — the Simple CNAPs covariance statistic — WITHOUT
    materializing the per-example ``(B, F, F)`` outer-product tensor
    (except on the ``naive`` oracle backend).

    f: (B, F); weights: (B, C) mask-folded one-hot -> (C, F, F).
    """
    b = resolve_backend(backend)
    if b == "naive":
        return _second_moment_naive(f, weights, accum_dtype)
    if b == "ref":
        return _second_moment_ref(f, weights, accum_dtype)
    out = _second_moment_pallas(f, weights)
    return out.astype(accum_dtype or f.dtype)


# ===========================================================================
# mahalanobis head: d2[b, c] = (q_b - mu_c)^T Sigma_c^{-1} (q_b - mu_c)
# ===========================================================================


def _mahalanobis_cho(qf, mu, chol):
    """Pre-dispatch composite (bit-exact): per-class triangular solves
    against the Cholesky factor."""
    diff = qf[:, None, :] - mu[None, :, :]                 # (B, C, F)
    sol = jax.vmap(
        lambda L, d: jax.scipy.linalg.cho_solve((L, True), d.T).T,
        in_axes=(0, 1), out_axes=1)(chol, diff)
    return jnp.sum(diff * sol, axis=-1)


@jax.custom_vjp
def _mahalanobis_pallas(q, mu, sinv):
    return _md.mahalanobis(q, mu, sinv, interpret=_interpret())


def _mahalanobis_pallas_fwd(q, mu, sinv):
    return _mahalanobis_pallas(q, mu, sinv), (q, mu, sinv)


def _mahalanobis_pallas_bwd(res, g):
    q, mu, sinv = res
    q32, mu32, s32, g32 = (t.astype(jnp.float32) for t in (q, mu, sinv, g))
    diff = q32[:, None, :] - mu32[None, :, :]              # (B, C, F)
    ssym = s32 + jnp.swapaxes(s32, -1, -2)
    t = jnp.einsum("cij,bcj->bci", ssym, diff)
    dq = jnp.einsum("bc,bci->bi", g32, t)
    dmu = -jnp.einsum("bc,bci->ci", g32, t)
    dsinv = jnp.einsum("bc,bci,bcj->cij", g32, diff, diff)
    return dq.astype(q.dtype), dmu.astype(mu.dtype), dsinv.astype(sinv.dtype)


_mahalanobis_pallas.defvjp(_mahalanobis_pallas_fwd, _mahalanobis_pallas_bwd)


def chol_inverse(chol: jnp.ndarray) -> jnp.ndarray:
    """Per-class covariance inverses from Cholesky factors:
    (C, F, F) lower -> (C, F, F) Sigma^{-1} via ``cho_solve(L, I)``.
    The pallas Mahalanobis head consumes this; adaptation computes it
    ONCE per task state (``state["sinv"]``) so serving's repeated query
    dispatches skip the O(C F^3) solves."""
    eye = jnp.eye(chol.shape[-1], dtype=chol.dtype)
    return jax.vmap(
        lambda L: jax.scipy.linalg.cho_solve((L, True), eye))(chol)


# ===========================================================================
# int8_matmul: out[m, n] = sum_k x[m, k] * q[k, n] * scale[k, n // BLOCK]
# ===========================================================================


def _int8_matmul_oracle(x2: jnp.ndarray, qs) -> jnp.ndarray:
    """Dequantize-then-dot: materialize the f32 weight and run a plain
    GEMM.  Shared by ``naive`` and ``ref`` (there is no cheaper
    association that avoids the f32 weight without a blocked kernel) —
    this is the bit-exact-within-reassociation oracle the pallas parity
    tests compare against."""
    w = _quant.dequantize(qs)
    return jnp.dot(x2.astype(jnp.float32), w,
                   preferred_element_type=jnp.float32)


def int8_matmul(x: jnp.ndarray, qs, backend: Optional[str] = None
                ) -> jnp.ndarray:
    """Weight-quantized matmul for the serving path: ``x @ W`` where W is
    stored in the blockwise int8 ``{q, scale, n}`` form of
    ``repro.optim.quant`` and is never materialized persistently in f32.

    x: (..., K) float; qs: quantized (K, N) weight -> (..., N) float32.

    FORWARD-ONLY contract — unlike the other sites there is no
    custom_vjp: serving runs under stop_gradient and quantized weights
    are frozen by definition, so a backward pass through this op is a
    bug, not a missing feature.  (``naive``/``ref`` remain differentiable
    as plain jnp by accident; ``pallas`` is not — do not rely on either.)

    ``naive``/``ref``: dequantize to f32, one GEMM (the oracle).
    ``pallas``: the blocked int8 kernel (``repro.kernels.int8_matmul``)
    — int8 tiles scaled in-register, fp32 accumulation, interpret mode
    off-TPU.  Leading batch dims are flattened around the 2-D kernel.
    """
    b = resolve_backend(backend)
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    if b in ("naive", "ref"):
        out = _int8_matmul_oracle(x2, qs)
    else:
        out = _im.int8_matmul(x2, qs["q"], qs["scale"],
                              interpret=_interpret())
    n = _quant.resolve_n(qs)
    return out.reshape(lead + (n,))


def mahalanobis_head(qf: jnp.ndarray, mu: jnp.ndarray, chol: jnp.ndarray,
                     backend: Optional[str] = None,
                     sinv: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Squared Mahalanobis distances of queries to class Gaussians given
    the Cholesky factors of the class covariances.

    qf: (B, F); mu: (C, F); chol: (C, F, F) lower -> (B, C).

    ``naive``/``ref``: per-class ``cho_solve`` (bit-exact with the
    pre-dispatch head; ``ref`` keeps the naive formula — there is no
    intermediate to kill, so there is no separate fused ref head).
    ``pallas``: the VMEM-resident quadratic-form kernel on the explicit
    per-class inverse, under a ``custom_vjp`` (gradients flow to
    ``chol`` through the inverse, and to q/mu/sinv through ref math).
    Pass ``sinv`` (:func:`chol_inverse`, precomputed at adaptation time
    and carried in the task state) to skip the per-call O(C F^3)
    inversion — serving's query dispatches hit this path; without it the
    inverse is recomputed here (the train path, one call per task).
    """
    b = resolve_backend(backend)
    if b in ("naive", "ref"):
        return _mahalanobis_cho(qf, mu, chol)
    if sinv is None:
        sinv = chol_inverse(chol)
    return _mahalanobis_pallas(qf, mu, sinv)
