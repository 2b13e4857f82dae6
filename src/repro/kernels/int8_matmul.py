"""Pallas TPU kernel: blocked int8-weight x float-activation matmul for
the weight-stationary serving path.

    out[m, n] = sum_k x[m, k] * q[k, n] * scale[k, n // BLOCK]

``q``/``scale`` are the blockwise int8 form of ``repro.optim.quant``
(absmax per 128-wide block of the trailing dim), so the weight matrix is
never materialized in f32: each grid step loads an int8 (bk, 128) weight
tile plus the full-width (bk, N/128) scale tile (a (bk, 1) column block
breaks Mosaic's (8, 128) block tiling whenever N > 128), selects its scale
column in-register, applies it, and feeds the MXU with fp32 accumulation
over the K grid axis.  The N tile is
pinned to ``BLOCK`` so one grid cell always covers exactly one scale
block — the per-block scale application the quantization scheme implies,
with no cross-block gather.

Serving-only contract: forward pass, no custom_vjp — the serve hot path
runs under stop_gradient (see ``repro.kernels.dispatch.int8_matmul``).
Padding is handled at the wrapper: M/K/N are zero-padded up to tile
multiples (zero int8 columns and zero activation rows contribute exactly
nothing), and the output is sliced back to (M, N).  Min int8 tile on TPU
is (32, 128); the padded K tile of 128 satisfies it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tpu_compat import CompilerParams
from repro.optim.quant import BLOCK


def _round_up(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


def _int8_matmul_kernel(x_ref, q_ref, s_ref, o_ref):
    ni = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)                    # (bm, bk)
    s_all = s_ref[...]                                    # (bk, N/BLOCK)
    col = jax.lax.broadcasted_iota(jnp.int32, s_all.shape, 1)
    s = jnp.sum(jnp.where(col == ni, s_all, 0.0), axis=1, keepdims=True)
    # per-block scale application: (bk, BLOCK) int8 tile * (bk, 1) scales
    w = q_ref[...].astype(jnp.float32) * s
    o_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def int8_matmul(x: jnp.ndarray, q: jnp.ndarray, scale: jnp.ndarray, *,
                block_m: int = 128, block_k: int = 128,
                interpret: bool = False) -> jnp.ndarray:
    """x: (M, K) float; q: (K, N) int8; scale: (K, ceil(N/BLOCK)) f32
    -> (M, N) float32 == x @ (q * scale-per-block)."""
    m, k = x.shape
    kq, n = q.shape
    assert kq == k, f"contraction mismatch: x K={k} vs q K={kq}"
    bm = min(block_m, _round_up(m, 8))
    bk = min(block_k, _round_up(k, BLOCK))
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, BLOCK)
    xp = jnp.pad(x.astype(jnp.float32), ((0, mp - m), (0, kp - k)))
    qp = jnp.pad(q, ((0, kp - k), (0, np_ - n)))
    sp = jnp.pad(scale, ((0, kp - k), (0, np_ // BLOCK - scale.shape[-1])))
    out = pl.pallas_call(
        _int8_matmul_kernel,
        grid=(mp // bm, np_ // BLOCK, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, BLOCK), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((bk, np_ // BLOCK), lambda mi, ni, ki: (ki, 0)),
        ],
        out_specs=pl.BlockSpec((bm, BLOCK), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp, qp, sp)
    return out[:m, :n]
