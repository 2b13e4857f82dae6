"""Pallas TPU kernels for the episodic class-statistics family.

``segment_pool_weighted`` — class-prototype / deep-set segment pooling:

    sums[c, f] = sum_b w[b, c] x[b, f]

On TPU a scatter is serialized; the one-hot MATMUL form keeps it on the
MXU ((C, B_t) x (B_t, F_t) per tile, accumulated over the B grid axis).
``w`` is a *weighted* one-hot — collator masks and padded ``TaskBatch``
lanes fold into it as zero rows, so padding drops out natively.  This is
the aggregation LITE subsamples (ProtoNets prototypes, CNAPs class
pooling, set-encoder sums); ``segment_pool`` keeps the original
labels-based entry point on top of it.

``class_second_moment`` — the Simple CNAPs covariance statistic:

    out[c, i, j] = sum_b w[b, c] x[b, i] x[b, j]

computed per (class, F_i-tile, F_j-tile) grid cell as one MXU matmul
((F_i, B_t) x (B_t, F_j), the class weight folded into the left operand)
accumulated over the B grid axis.  Each cell loads the full-width (B_t, C)
weight tile (a (B_t, 1) column slice breaks Mosaic's (8, 128) block
tiling) and selects its class column in-register.  The per-example (B, F, F)
outer-product tensor is never formed, which is the whole point
(repro.kernels.dispatch routes the episodic hot path here).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tpu_compat import F32_CONTRACT, CompilerParams


def _pool_kernel(w_ref, x_ref, o_ref, *, block_b: int, n_rows: int):
    bi = pl.program_id(1)

    @pl.when(bi == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = w_ref[...].astype(jnp.float32)                # (bb, C)
    x = x_ref[...].astype(jnp.float32)                # (bb, Ft)
    # zero OOB padding rows (may be NaN) — 0*NaN would poison the dot
    valid = (bi * block_b +
             jax.lax.broadcasted_iota(jnp.int32, (w.shape[0], 1), 0)) < n_rows
    w = jnp.where(valid, w, 0.0)
    x = jnp.where(valid, x, 0.0)
    o_ref[...] += jax.lax.dot_general(
        w, x, (((0,), (0,)), ((), ())), precision=F32_CONTRACT,
        preferred_element_type=jnp.float32)


def segment_pool_weighted(x: jnp.ndarray, weights: jnp.ndarray, *,
                          block_b: int = 128, block_f: int = 256,
                          interpret: bool = False) -> jnp.ndarray:
    """x: (B, F); weights: (B, C) float (mask-folded one-hot) ->
    sums (C, F) float32."""
    b, f = x.shape
    c = weights.shape[1]
    block_b = min(block_b, b)
    block_f = min(block_f, f)
    return pl.pallas_call(
        functools.partial(_pool_kernel, block_b=block_b, n_rows=b),
        grid=(pl.cdiv(f, block_f), pl.cdiv(b, block_b)),
        in_specs=[
            pl.BlockSpec((block_b, c), lambda fi, bi: (bi, 0)),
            pl.BlockSpec((block_b, block_f), lambda fi, bi: (bi, fi)),
        ],
        out_specs=pl.BlockSpec((c, block_f), lambda fi, bi: (0, fi)),
        out_shape=jax.ShapeDtypeStruct((c, f), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(weights, x)


def segment_pool(x: jnp.ndarray, labels: jnp.ndarray, num_classes: int, *,
                 block_b: int = 128, block_f: int = 256,
                 interpret: bool = False):
    """x: (B, F); labels: (B,) int32 -> (sums (C, F) f32, counts (C,) f32)."""
    onehot = jax.nn.one_hot(labels, num_classes, dtype=jnp.float32)
    sums = segment_pool_weighted(x, onehot, block_b=block_b, block_f=block_f,
                                 interpret=interpret)
    return sums, jnp.sum(onehot, axis=0)


def _second_moment_kernel(w_ref, xi_ref, xj_ref, o_ref, *, block_b: int,
                          n_rows: int):
    bi = pl.program_id(3)

    @pl.when(bi == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    ci = pl.program_id(0)
    w_all = w_ref[...].astype(jnp.float32)            # (bb, C)
    col = jax.lax.broadcasted_iota(jnp.int32, w_all.shape, 1)
    w = jnp.sum(jnp.where(col == ci, w_all, 0.0), axis=1,
                keepdims=True)                        # (bb, 1) — class ci
    xi = xi_ref[...].astype(jnp.float32)              # (bb, Ft_i)
    xj = xj_ref[...].astype(jnp.float32)              # (bb, Ft_j)
    valid = (bi * block_b +
             jax.lax.broadcasted_iota(jnp.int32, (w.shape[0], 1), 0)) < n_rows
    w = jnp.where(valid, w, 0.0)
    xi = jnp.where(valid, xi, 0.0)
    xj = jnp.where(valid, xj, 0.0)
    # (Ft_i, bb) x (bb, Ft_j) with the class weight folded into the left
    # operand: sum_b w[b] xi[b, i] xj[b, j]
    o_ref[0] += jax.lax.dot_general(
        xi * w, xj, (((0,), (0,)), ((), ())), precision=F32_CONTRACT,
        preferred_element_type=jnp.float32)


def class_second_moment(x: jnp.ndarray, weights: jnp.ndarray, *,
                        block_b: int = 128, block_f: int = 128,
                        interpret: bool = False) -> jnp.ndarray:
    """x: (B, F); weights: (B, C) float (mask-folded one-hot) ->
    out (C, F, F) float32 with out[c] = sum_b w[b, c] x[b] x[b]^T."""
    b, f = x.shape
    c = weights.shape[1]
    block_b = min(block_b, b)
    block_f = min(block_f, f)
    return pl.pallas_call(
        functools.partial(_second_moment_kernel, block_b=block_b, n_rows=b),
        grid=(c, pl.cdiv(f, block_f), pl.cdiv(f, block_f),
              pl.cdiv(b, block_b)),
        in_specs=[
            pl.BlockSpec((block_b, c), lambda ci, fi, fj, bi: (bi, 0)),
            pl.BlockSpec((block_b, block_f), lambda ci, fi, fj, bi: (bi, fi)),
            pl.BlockSpec((block_b, block_f), lambda ci, fi, fj, bi: (bi, fj)),
        ],
        out_specs=pl.BlockSpec((1, block_f, block_f),
                               lambda ci, fi, fj, bi: (ci, fi, fj)),
        out_shape=jax.ShapeDtypeStruct((c, f, f), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(weights, x, x)
