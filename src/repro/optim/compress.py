"""Error-feedback int8 gradient compression for the DP all-reduce
(beyond-paper distributed trick; 1-bit-Adam/EF-SGD family).

Compression is a gradient transform around the reduction point:

    q, new_err = compress(g + err)      # int8 blockwise + residual memory
    g_hat      = decompress(q)          # the value the reduction sums

``compressed_psum`` is the shard_map reduction: the WIRE carries the int8
payload plus the per-128-block f32 scales (an ``all_gather`` of
``{q, scale}`` over the axis — ~1.03 bytes/element vs 4 for f32 psum); each
shard decodes and sums locally, which equals the psum of the per-shard
decoded values.  Error feedback (the carried residual) keeps the long-run
quantization bias at zero; numerics are covered by tests/test_optim.py
and the convergence test in tests/test_multihost.py.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.optim.quant import dequantize, quantize

PyTree = Any


def ef_compress(grads: PyTree, err: PyTree) -> Tuple[PyTree, PyTree]:
    """Compress (grads + err) to int8 per-leaf; returns (g_hat, new_err).
    g_hat is what gets all-reduced; new_err = (g+err) - g_hat is carried
    to the next step (error feedback)."""

    def one(g, e):
        tot = g.astype(jnp.float32) + e
        q = quantize(tot)
        g_hat = dequantize(q, tot.shape[-1])
        return g_hat.astype(g.dtype), tot - g_hat

    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(err)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (jax.tree.unflatten(treedef, [o[0] for o in out]),
            jax.tree.unflatten(treedef, [o[1] for o in out]))


def zeros_error(grads: PyTree) -> PyTree:
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def compressed_psum(grads: PyTree, axis_name: str, err: PyTree
                    ) -> Tuple[PyTree, PyTree]:
    """shard_map body: quantize locally, move ONLY the int8 payload +
    block scales over ``axis_name``, decode + sum locally, carry the
    quantization residual.  Returns (sum of per-shard decoded values,
    new residual) — identical in value to psum-ing the decoded f32s, at
    ~1/4 the wire bytes."""

    def one(g, e):
        tot = g.astype(jnp.float32) + e
        q = quantize(tot)
        g_hat = dequantize(q, tot.shape[-1])
        gathered = dict(q=jax.lax.all_gather(q["q"], axis_name),
                        scale=jax.lax.all_gather(q["scale"], axis_name))
        summed = jnp.sum(dequantize(gathered, tot.shape[-1]), axis=0)
        return summed.astype(g.dtype), tot - g_hat

    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(err)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (jax.tree.unflatten(treedef, [o[0] for o in out]),
            jax.tree.unflatten(treedef, [o[1] for o in out]))
