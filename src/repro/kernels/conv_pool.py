"""Pallas TPU kernel: an image block's conv3x3 (SAME) + bias, ReLU and
2x2 max-pool in one pass, writing only the pooled output.

    out[n, i, j, o] = max_{p, q in {0, 1}} relu(conv(x, w)[n, 2i+p, 2j+q, o] + b[o])

The unfused composite writes the (B, H, W, cout) pre-pool activation to
HBM and reads it back to pool it; for 224 px images and 64 filters that is
4x the pooled bytes.  Here the pre-pool values live only in vregs:

* Space-to-depth by 2 of the SAME-padded image turns every 2x2 pool window
  into one 2x2 window of the s2d image: four taps of ``4 * cin`` channels
  (padded to ``kc``).  The 3x3 kernel embedded at the four pool phases of
  a 4x4 window gives one ``(4 * cout, 4 * kc)`` weight, so one MXU matmul
  yields all four phases of a pooled pixel.
* The max over the four ``cout``-row groups, then bias and ReLU, is the
  pooled value: bias and ReLU are monotone, so they commute with the max.

Layout: the batch sits on lanes.  The s2d image is ``(H/2+1, W/2+1, kc,
Bp)`` and the output ``(H/2, W/2, cout, B)``; read as ``(B, H/2, W/2,
cout)`` that is XLA's own layout for a convolution operand on TPU
(``{0,3,2,1}``), so the transpose back is a bitcast and the next block's
convolution reads the output as it lies.  A grid step makes one output row
of one 128-image lane block; the s2d rows above and below come in as two
operands (one array under index maps one row apart), so no halo is needed.

Precision is XLA's DEFAULT on TPU: matmul operands rounded to bfloat16,
float32 accumulation, and bias, max, ReLU and the output in the input's
float dtype.

``conv3x3_relu_pool_bwd`` is the weight and bias gradient of the same
block, for a pooled cotangent ``g``.  It reads the same s2d image and
phase weight, recomputes the four phases of each pooled pixel with the
forward's matmul, and routes ``g`` to the first phase that attains the
max in window order (0,0), (0,1), (1,0), (1,1) (XLA's select-and-scatter
tie rule for a max-pool's gradient), where the pooled pre-activation is
positive.  The routed cotangent, split into two bfloat16 parts, meets
the same columns in two more matmuls, which accumulate the phase
weight's gradient in float32; folding that back to ``(3, 3, cin, cout)``
is the transpose of ``_phase_weights``.  Neither the pre-pool activation
nor its gradient leaves the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tpu_compat import CompilerParams

LANES = 128
# pooled pixels of one row per matmul, their s2d columns side by side on
# lanes: on a v5e chip 8 take 26% less time than 1 over a 100-image
# chunk of 224 px, and 16 no less than 8
PIXELS_PER_DOT = 8


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _phase_weights(w: jnp.ndarray, kc: int) -> jnp.ndarray:
    """(3, 3, cin, cout) -> (4 * cout, 4 * kc): row (p, q, o) is pool phase
    (p, q) of filter o; column (di, dj, a, b, c) is s2d tap (di, dj),
    channel (a, b, c) of the padded image's pixel (2(i+di)+a, 2(j+dj)+b)."""
    cin, cout = w.shape[2], w.shape[3]
    w4 = jnp.zeros((4, 4, cin, 2, 2, cout), w.dtype)
    for p in range(2):
        for q in range(2):
            w4 = w4.at[p:p + 3, q:q + 3, :, p, q, :].set(w)
    # (u, v) = (2 di + a, 2 dj + b) -> (di, dj, a, b, c)
    w4 = w4.reshape(2, 2, 2, 2, cin, 4 * cout).transpose(0, 2, 1, 3, 4, 5)
    w4 = jnp.pad(w4.reshape(4, 4 * cin, 4 * cout),
                 ((0, 0), (0, kc - 4 * cin), (0, 0)))
    return w4.reshape(4 * kc, 4 * cout).T


def _space_to_depth(x: jnp.ndarray, kc: int, bp: int) -> jnp.ndarray:
    """(B, H, W, cin) -> (H/2+1, W/2+1, kc, bp) bfloat16: the SAME-padded
    image by 2x2 blocks, channels (a, b, c) zero-padded to kc, batch
    zero-padded to bp and moved to lanes.  (Four strided slices in place
    of the reshape read 1.3 ms per task slower on a v5e chip: XLA makes
    them gathers.)"""
    b, h, w, cin = x.shape
    xp = jnp.pad(x, ((0, bp - b), (1, 1), (1, 1), (0, 0)))
    s = xp.reshape(bp, h // 2 + 1, 2, w // 2 + 1, 2, cin)
    s = s.transpose(1, 3, 2, 4, 5, 0).reshape(h // 2 + 1, w // 2 + 1,
                                              4 * cin, bp)
    s = jnp.pad(s, ((0, 0), (0, 0), (0, kc - 4 * cin), (0, 0)))
    return s.astype(jnp.bfloat16)


def _group(w2: int) -> int:
    """Pooled pixels per matmul: the most, up to PIXELS_PER_DOT, that
    divide the pooled row."""
    return max(d for d in range(1, min(PIXELS_PER_DOT, w2) + 1)
               if w2 % d == 0)


def _columns(top_ref, bot_ref, j0, group: int) -> jnp.ndarray:
    """(4 kc, group * LANES): pooled pixels j0 .. j0 + group - 1 of a row
    side by side, each the s2d taps (0,0), (0,1), (1,0), (1,1)."""
    return jnp.concatenate(
        [jnp.concatenate([top_ref[0, j0 + k], top_ref[0, j0 + k + 1],
                          bot_ref[0, j0 + k], bot_ref[0, j0 + k + 1]],
                         axis=0) for k in range(group)], axis=1)


def _kernel(wt_ref, b_ref, top_ref, bot_ref, o_ref, *, cout: int,
            group: int, lanes_out: int):
    wt = wt_ref[...]                                   # (4 cout, 4 kc) bf16
    bias = b_ref[...]                                  # (cout, 1) f32

    def body(g, carry):
        j0 = g * group
        y = jnp.dot(wt, _columns(top_ref, bot_ref, j0, group),
                    preferred_element_type=jnp.float32)
        m = jnp.maximum(jnp.maximum(y[:cout], y[cout:2 * cout]),
                        jnp.maximum(y[2 * cout:3 * cout], y[3 * cout:]))
        out = jnp.maximum(m + bias, 0.0).astype(o_ref.dtype)
        for k in range(group):
            o_ref[0, j0 + k] = out[:, k * LANES:k * LANES + lanes_out]
        return carry

    jax.lax.fori_loop(0, o_ref.shape[1] // group, body, 0)


def conv3x3_relu_pool(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, *,
                      interpret: bool = False) -> jnp.ndarray:
    """x: (B, H, W, cin), H and W even; w: (3, 3, cin, cout); b: (cout,)
    -> (B, H/2, W/2, cout) == maxpool2(relu(conv3x3_same(x, w) + b))."""
    bsz, h, wd, cin = x.shape
    cout = w.shape[-1]
    h2, w2 = h // 2, wd // 2
    kc = _round_up(4 * cin, 16)
    bp = _round_up(bsz, LANES)
    lanes_out = min(bsz, LANES)
    group = _group(w2)
    s = _space_to_depth(x, kc, bp)
    wt = _phase_weights(w, kc).astype(jnp.bfloat16)
    bias = b.astype(jnp.float32).reshape(cout, 1)
    row = (1, w2 + 1, kc, LANES)
    out = pl.pallas_call(
        functools.partial(_kernel, cout=cout, group=group,
                          lanes_out=lanes_out),
        grid=(bp // LANES, h2),
        in_specs=[
            pl.BlockSpec((4 * cout, 4 * kc), lambda n, i: (0, 0)),
            pl.BlockSpec((cout, 1), lambda n, i: (0, 0)),
            pl.BlockSpec(row, lambda n, i: (i, 0, 0, n)),
            pl.BlockSpec(row, lambda n, i: (i + 1, 0, 0, n)),
        ],
        out_specs=pl.BlockSpec((1, w2, cout, lanes_out),
                               lambda n, i: (i, 0, 0, n)),
        out_shape=jax.ShapeDtypeStruct((h2, w2, cout, bsz), x.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="conv3x3_relu_pool",
    )(wt, bias, s, s)
    return out.transpose(3, 0, 1, 2)


def _bwd_kernel(wt_ref, b_ref, top_ref, bot_ref, g_ref, dwt_ref, db_ref, *,
                cout: int, group: int, batch: int):
    n, i = pl.program_id(0), pl.program_id(1)

    @pl.when((n == 0) & (i == 0))
    def _():
        dwt_ref[...] = jnp.zeros_like(dwt_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    wt = wt_ref[...]                                   # (4 cout, 4 kc) bf16
    bias = b_ref[...]                                  # (cout, 1) f32
    # a partial last lane block of the cotangent reads past the batch
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, group * LANES), 1)
    real = n * LANES + lane % LANES < batch

    def body(k0, carry):
        dwt, db = carry
        j0 = k0 * group
        c = _columns(top_ref, bot_ref, j0, group)
        y = jnp.dot(wt, c, preferred_element_type=jnp.float32)
        y0, y1 = y[:cout], y[cout:2 * cout]
        y2, y3 = y[2 * cout:3 * cout], y[3 * cout:]
        m01, m23 = jnp.maximum(y0, y1), jnp.maximum(y2, y3)
        g = jnp.concatenate([g_ref[0, j0 + k] for k in range(group)], axis=1)
        live = jnp.maximum(m01, m23) + bias > 0
        g = jnp.where(live & real if batch % LANES else live, g, 0.0)
        # the first phase at the max (the bias, common to all four, left
        # out): a pair's first wins a tie within it, the first pair a tie
        # between the pairs
        left, first01, first23 = m01 >= m23, y0 >= y1, y2 >= y3
        gl = jnp.where(left, g, 0.0)
        gr = jnp.where(left, 0.0, g)
        dy = jnp.concatenate(
            [jnp.where(first01, gl, 0.0), jnp.where(first01, 0.0, gl),
             jnp.where(first23, gr, 0.0), jnp.where(first23, 0.0, gr)],
            axis=0)
        # the cotangent as the sum of two bfloat16 parts, 16 bits of its
        # mantissa: a float32 cotangent then loses little more than the
        # image's own rounding (the columns are bfloat16 already)
        hi = dy.astype(jnp.bfloat16)
        lo = (dy - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        for part in (hi, lo):
            dwt = dwt + jax.lax.dot_general(
                part, c, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        for k in range(group):
            db = db + g[:, k * LANES:(k + 1) * LANES]
        return dwt, db

    dwt, db = jax.lax.fori_loop(
        0, g_ref.shape[1] // group, body,
        (jnp.zeros(dwt_ref.shape, jnp.float32),
         jnp.zeros(db_ref.shape, jnp.float32)))
    dwt_ref[...] += dwt
    db_ref[...] += db


def conv3x3_relu_pool_bwd(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                          g: jnp.ndarray, *, interpret: bool = False):
    """The weight and bias gradients of ``conv3x3_relu_pool(x, w, b)`` for
    the pooled cotangent g: (B, H/2, W/2, cout) -> (dw (3, 3, cin, cout),
    db (cout,)), in w's and b's dtypes."""
    bsz, h, wd, cin = x.shape
    cout = w.shape[-1]
    h2, w2 = h // 2, wd // 2
    kc = _round_up(4 * cin, 16)
    bp = _round_up(bsz, LANES)
    group = _group(w2)
    s = _space_to_depth(x, kc, bp)
    wt, fold = jax.vjp(lambda w: _phase_weights(w, kc), w)
    bias = b.astype(jnp.float32).reshape(cout, 1)
    row = (1, w2 + 1, kc, LANES)
    const = lambda n, i: (0, 0)
    dwt, db = pl.pallas_call(
        functools.partial(_bwd_kernel, cout=cout, group=group, batch=bsz),
        grid=(bp // LANES, h2),
        in_specs=[
            pl.BlockSpec((4 * cout, 4 * kc), const),
            pl.BlockSpec((cout, 1), const),
            pl.BlockSpec(row, lambda n, i: (i, 0, 0, n)),
            pl.BlockSpec(row, lambda n, i: (i + 1, 0, 0, n)),
            pl.BlockSpec((1, w2, cout, LANES), lambda n, i: (i, 0, 0, n)),
        ],
        out_specs=[pl.BlockSpec((4 * cout, 4 * kc), const),
                   pl.BlockSpec((cout, LANES), const)],
        out_shape=[jax.ShapeDtypeStruct((4 * cout, 4 * kc), jnp.float32),
                   jax.ShapeDtypeStruct((cout, LANES), jnp.float32)],
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="conv3x3_relu_pool_bwd",
    )(wt.astype(jnp.bfloat16), bias, s, s, g.transpose(1, 2, 3, 0))
    dw, = fold(dwt.astype(wt.dtype))
    return dw, jnp.sum(db, axis=1).astype(b.dtype)
