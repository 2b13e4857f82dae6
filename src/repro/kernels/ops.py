"""Public jit'd wrappers for the Pallas kernels.

On non-TPU backends the kernels execute in interpret mode (Python
evaluation of the kernel body — bit-faithful semantics, no Mosaic); on
TPU the same code lowers to Mosaic.

How kernels reach model code — two layers:

* **The episodic hot path goes through ``repro.kernels.dispatch``**, not
  this module: the class-statistics reductions (per-class feature sums,
  Simple CNAPs second moments) and the Mahalanobis head are *dispatched*
  ops with a per-site backend policy (``naive`` legacy composite /
  ``ref`` fused jnp / ``pallas`` / ``auto``) selected via
  ``MetaTrainConfig.kernel_backend``, the serving engine's
  ``kernel_backend`` argument, or ``--kernel-backend`` on both
  launchers.  The Pallas forwards there are wrapped in ``custom_vjp``
  (ref-math backwards) so they are differentiable inside the LITE
  H-pass.  Wired sites: ProtoNets prototypes, CNAPs / Simple CNAPs class
  statistics and Mahalanobis head, through training
  (``make_batched_meta_train_step``), LITE-chunked serving
  (``repro.serve.episodic``), and the batched ``adapt_batch`` path.
  Status: ref is the default and fully validated; pallas is
  interpret-validated on CPU (parity + grad tests in
  tests/test_dispatch.py) with real-TPU Mosaic validation pending, same
  as the flash-attention sweeps.

* **This module** keeps the raw jit'd wrappers (LM-side kernels and
  direct use: flash_attention, gmm, ssd_chunk, plus the class-statistics
  kernels for tests).  The XLA/jnp path (ref semantics) is
  what the SPMD dry-run lowers, so roofline FLOPs stay visible to the
  HLO analyzer either way.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import conv_pool as _cp
from repro.kernels import flash_attention as _fa
from repro.kernels import gmm as _gmm
from repro.kernels import mahalanobis as _md
from repro.kernels import segment_pool as _sp
from repro.kernels import ssd_scan as _ssd
from repro.kernels.tpu_compat import interpret_mode as _interpret


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None):
    """q, k, v: (BH, S, D)."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, interpret=_interpret())


def flash_attention_gqa(q, k, v, **kw):
    """q: (B, S, Hq, D); k/v: (B, S, Hkv, D) — GQA via group expansion."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    o = flash_attention(fold(q), fold(k), fold(v), **kw)
    return o.reshape(b, hq, s, d).transpose(0, 2, 1, 3)


@jax.jit
def mahalanobis(q, mu, sinv):
    """q: (B, F); mu: (C, F); sinv: (C, F, F) -> (B, C)."""
    return _md.mahalanobis(q, mu, sinv, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("num_classes",))
def segment_pool(x, labels, num_classes: int):
    """x: (B, F); labels: (B,) -> (sums (C, F), counts (C,))."""
    return _sp.segment_pool(x, labels, num_classes, interpret=_interpret())


@jax.jit
def segment_pool_weighted(x, weights):
    """x: (B, F); weights: (B, C) mask-folded one-hot -> sums (C, F).
    Padded/invalid rows are zero-weight rows — the TaskBatch-native form
    the dispatch layer uses."""
    return _sp.segment_pool_weighted(x, weights, interpret=_interpret())


@jax.jit
def class_second_moment(x, weights):
    """x: (B, F); weights: (B, C) -> (C, F, F) per-class raw second
    moments sum_b w[b,c] x_b x_b^T, computed without materializing the
    per-example (B, F, F) outer tensor."""
    return _sp.class_second_moment(x, weights, interpret=_interpret())


@jax.jit
def gmm(x, w):
    """Grouped per-expert matmul: (E, C, D) @ (E, D, F) -> (E, C, F)."""
    return _gmm.gmm(x, w, interpret=_interpret())


@jax.jit
def ssd_chunk(x, dt, A, B, C):
    """Intra-chunk SSD (see repro.kernels.ssd_scan)."""
    return _ssd.ssd_chunk(x, dt, A, B, C, interpret=_interpret())


@jax.jit
def conv3x3_relu_pool(x, w, b):
    """x: (B, H, W, cin), H and W even; w: (3, 3, cin, cout); b: (cout,)
    -> maxpool2(relu(conv3x3_same(x, w) + b)), the pre-pool activation
    kept on chip (see repro.kernels.conv_pool)."""
    return _cp.conv3x3_relu_pool(x, w, b, interpret=_interpret())


@jax.jit
def conv3x3_relu_pool_bwd(x, w, b, g):
    """The weight and bias gradients of ``conv3x3_relu_pool(x, w, b)`` for
    the pooled cotangent g, the pre-pool activation and its gradient kept
    on chip (see repro.kernels.conv_pool)."""
    return _cp.conv3x3_relu_pool_bwd(x, w, b, g, interpret=_interpret())
