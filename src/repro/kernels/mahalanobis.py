"""Pallas TPU kernel for Simple CNAPs' Mahalanobis head (paper §3.1):

    d2[b, c] = (x_b - mu_c)^T Sinv_c (x_b - mu_c)

The per-class inverse covariance (F, F) tile and the query tile (block_b,
F) are VMEM-resident; the quadratic form runs as two MXU matmuls per
(query-block, class) grid cell, at float32 contract precision (the
distances of large, nearby features cancel).  F is the backbone feature
width (64-512 across configs) so a full (F, F) tile fits VMEM comfortably.

Block shapes follow Mosaic's tiling rule (the last two block dims divisible
by (8, 128) or equal to the array's): ``mu`` rides as (C, 1, F) so one
class row is a (1, F) tile, and the output block spans all C columns of its
query rows — it stays resident across the inner class axis and each class
writes its own column.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tpu_compat import F32_CONTRACT, CompilerParams


def _kernel(q_ref, mu_ref, sinv_ref, o_ref):
    ci = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)            # (bb, F)
    mu = mu_ref[0].astype(jnp.float32)            # (1, F)
    sinv = sinv_ref[0].astype(jnp.float32)        # (F, F)
    diff = q - mu
    t = jax.lax.dot(diff, sinv, precision=F32_CONTRACT,
                    preferred_element_type=jnp.float32)
    d2 = jnp.sum(t * diff, axis=1, keepdims=True)  # (bb, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    o_ref[...] = jnp.where(col == ci, d2, o_ref[...])


def mahalanobis(q: jnp.ndarray, mu: jnp.ndarray, sinv: jnp.ndarray, *,
                block_b: int = 128, interpret: bool = False) -> jnp.ndarray:
    """q: (B, F); mu: (C, F); sinv: (C, F, F) -> (B, C) squared distances."""
    b, f = q.shape
    c = mu.shape[0]
    block_b = min(block_b, b)
    nb = pl.cdiv(b, block_b)

    return pl.pallas_call(
        _kernel,
        grid=(nb, c),
        in_specs=[
            pl.BlockSpec((block_b, f), lambda bi, ci: (bi, 0)),
            pl.BlockSpec((1, 1, f), lambda bi, ci: (ci, 0, 0)),
            pl.BlockSpec((1, f, f), lambda bi, ci: (ci, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, c), lambda bi, ci: (bi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, c), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, mu.reshape(c, 1, f), sinv)
