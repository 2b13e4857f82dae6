"""The plain reference: ProtoNets' mathematics under LITE in float32 at
the highest matmul precision, written from the papers' descriptions with
nothing of the program imported.

* Backbone: per block conv3x3 (SAME) + bias -> relu -> maxpool 2x2;
  global average pool; linear head.
* ProtoNets head (Snell et al. 2017): prototypes = class means of the
  support embeddings; logits = -|q - p_c|^2.
* LITE (Bronskill et al. 2021, Eq. 8): every support aggregate is the
  exact sum over all N images; its gradient is N/|H| times the gradient of
  the sum over H.  H is |H| indices drawn without replacement by ranking
  per-index uniforms u_i = U(fold_in(key, i)); the task's key is
  ``fold_in(step_key, task_index)``.
* Meta-training step: mean cross-entropy over the queries (mean over the
  tasks of a step), gradient clipped to a global norm, then AdamW.

The support sums run in blocks of rows so that the reference fits one
chip's memory beside nothing else.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
PyTree = Any


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def _conv(x, w, b):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HI) + b


def _pool(h):
    if h.shape[1] < 2 or h.shape[2] < 2:
        return h
    return jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def backbone(p, x):
    h = x
    for blk in p["blocks"]:
        h = _pool(jax.nn.relu(_conv(h, blk["w"], blk["b"])))
    h = jnp.mean(h, axis=(1, 2))
    return jnp.dot(h, p["head"]["w"], precision=HI) + p["head"]["b"]


# ---------------------------------------------------------------------------
# support sums
# ---------------------------------------------------------------------------


def block_sum(fn, xs: Tuple, block: int):
    """sum over rows of ``fn(*row_block)`` (a pytree of sums), computed
    in blocks of ``block`` rows; rows past the end weigh zero."""
    n, dt = xs[0].shape[0], xs[0].dtype
    if n <= block:
        return fn(*xs, jnp.ones((n,), dt))
    nb = -(-n // block)
    pad = nb * block - n
    w = jnp.concatenate([jnp.ones((n,), dt), jnp.zeros((pad,), dt)])
    padded = [jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])
              for a in xs]
    chunks = [a.reshape((nb, block) + a.shape[1:]) for a in padded]
    parts = jax.lax.map(lambda c: fn(*c[:-1], c[-1]),
                        tuple(chunks) + (w.reshape(nb, block),))
    return jax.tree.map(lambda a: jnp.sum(a, axis=0), parts)


def h_split(key, n: int, h: int):
    u = jax.vmap(lambda i: jax.random.uniform(jax.random.fold_in(key, i)))(
        jnp.arange(n))
    order = jnp.argsort(u)
    return order[:h], order[h:]


def lite_sum(fn, xs: Tuple, key, h: int, block: int):
    """LITE estimate of sum_n fn(x_n): the exact value, whose gradient is
    N/|H| times that of the sum over the drawn H.  ``fn(*rows, w)`` sums
    its rows weighted by ``w``."""
    n, dt = xs[0].shape[0], xs[0].dtype
    if h >= n:
        return fn(*xs, jnp.ones((n,), dt))
    hi, ci = h_split(key, n, h)
    part_h = fn(*[a[hi] for a in xs], jnp.ones((h,), dt))
    part_c = jax.lax.stop_gradient(
        block_sum(fn, tuple(a[ci] for a in xs), block))
    scale = n / h
    return jax.tree.map(
        lambda a, c: jax.lax.stop_gradient(a + c)
        + scale * (a - jax.lax.stop_gradient(a)), part_h, part_c)


def _class_sums(feat_fn, way: int):
    def fn(x, y, w):
        f = feat_fn(x)
        oh = jax.nn.one_hot(y, way, dtype=f.dtype) * w[:, None]
        return jnp.einsum("nc,nf->cf", oh, f, precision=HI)
    return fn


# ---------------------------------------------------------------------------
# heads
# ---------------------------------------------------------------------------


def proto_logits(q, protos):
    return -jnp.sum((q[:, None, :] - protos[None]) ** 2, axis=-1)


def _xent(logits, y):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def task_loss(config: Dict, params, task: Dict, key):
    """One task's query loss under LITE."""
    way = config["way"]
    h, block = config["lite"]["h"], config["lite"]["chunk"]
    sx, sy = task["support_x"], task["support_y"]
    qx, qy = task["query_x"], task["query_y"]
    counts = jnp.sum(jax.nn.one_hot(sy, way, dtype=jnp.float32), axis=0)
    if config["learner"] != "protonets":
        raise NotImplementedError(config["learner"])
    feat = lambda x: backbone(params["bb"], x)  # noqa: E731
    sums = lite_sum(_class_sums(feat, way), (sx, sy), key, h, block)
    protos = sums / jnp.maximum(counts, 1.0)[:, None]
    return _xent(proto_logits(backbone(params["bb"], qx), protos), qy)


def step_loss(config: Dict, params, batch: Dict, key):
    t = batch["support_x"].shape[0]
    losses = [task_loss(config, params,
                        {k: v[i] for k, v in batch.items()},
                        jax.random.fold_in(key, i)) for i in range(t)]
    return sum(losses) / t


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(a)) for a in jax.tree.leaves(tree)))


def make_train_step(config: Dict, dtype=None):
    """jitted ``(params, m, v, count, batch, key) -> (params, m, v, count,
    loss, clipped_grads)``.  With ``dtype`` the loss and its gradient are
    computed in that type (weights, inputs, activations; an 8-bit float
    type holds the weights and inputs, and the arithmetic runs in
    bfloat16); the optimizer stays in float32."""
    opt = config["optimizer"]

    def low(tree):
        if dtype is None:
            return tree

        def cast(a):
            if not jnp.issubdtype(a.dtype, jnp.floating):
                return a
            a = a.astype(dtype)
            # an 8-bit float holds weights and inputs; arithmetic in bf16
            return a.astype(jnp.bfloat16) if a.dtype.itemsize == 1 else a

        return jax.tree.map(cast, tree)

    @jax.jit
    def step(params, m, v, count, batch, key):
        with jax.default_matmul_precision("highest"):
            loss, g = jax.value_and_grad(
                lambda p: step_loss(config, low(p), low(batch), key))(params)
        loss = loss.astype(jnp.float32)
        g = jax.tree.map(lambda a: a.astype(jnp.float32), g)
        norm = _global_norm(g)
        scale = jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(norm,
                                                                    1e-12))
        g = jax.tree.map(lambda a: a * scale, g)
        count = count + 1
        c1 = 1.0 - opt["b1"] ** count
        c2 = 1.0 - opt["b2"] ** count
        m = jax.tree.map(lambda a, b: opt["b1"] * a + (1 - opt["b1"]) * b,
                         m, g)
        v = jax.tree.map(lambda a, b: opt["b2"] * a + (1 - opt["b2"]) * b * b,
                         v, g)

        def upd(p, mm, vv):
            s = (mm / c1) / (jnp.sqrt(vv / c2) + opt["eps"])
            if p.ndim >= 2:
                s = s + opt["weight_decay"] * p
            return p - opt["lr"] * s

        params = jax.tree.map(upd, params, m, v)
        return params, m, v, count, loss, g

    return step


def train(config: Dict, params, batches: Sequence[Tuple[Dict, Any]],
          dtype=None):
    """Run the reference over ``batches`` from ``params``: returns the
    losses, the first step's clipped gradient and the final parameters."""
    step = make_train_step(config, dtype)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.float32)
    losses, first_grad = [], None
    for batch, key in batches:
        params, m, v, count, loss, g = step(params, m, v, count, batch, key)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = jax.device_get(g)
    return losses, first_grad, jax.device_get(params)
