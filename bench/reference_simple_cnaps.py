"""The plain reference for Simple CNAPs on a FiLM-conditioned
EfficientNet-B0 under LITE: float32 at the highest matmul precision,
written from the papers' descriptions with nothing of the program
imported.  What it shares with ``bench/reference.py`` (the conv stack,
LITE's sums and H draw) it takes from there.

* Backbone, EfficientNet-B0 (Tan & Le 2019, Table 1), frozen: stem conv3x3
  stride 2, BN, swish; per MBConv block a 1x1 expand + BN + swish (where
  the ratio is not 1), a depthwise kxk conv of stride s (SAME) + BN, FiLM,
  swish, squeeze-and-excitation (mean, dense to max(1, 0.25 * block
  input), swish, dense, sigmoid, product), a 1x1 projection + BN and the
  identity where the stride is 1 and the widths agree; a 1x1 head + BN +
  swish and a global mean.  BN: (h - mean) / sqrt(var + eps) * gamma +
  beta from stored statistics.  Swish: x * sigmoid(x).
* FiLM (Perez et al. 2018): h * (1 + gamma) + beta per channel, from the
  generator: per site relu(z W1 + b1), then one linear map each to gamma
  and beta.
* Set encoder (CNAPs, Requeima et al. 2019): ``bench.reference.backbone``
  (conv3x3 + bias, relu, 2x2 max-pool per block, mean, linear head); the
  task embedding z is the mean over the support set.
* Simple CNAPs (Bateni et al. 2020): class means mu_c; Sigma_c = l_c S_c +
  (1 - l_c) S_task + eps_c I with l_c = k_c / (k_c + 1) and the program's
  ridge eps_c = cov_eps + 1e-3 * max(mean diag, 0); logits are minus the
  squared Mahalanobis distance, through the Cholesky factor and one
  triangular solve.
* LITE (Bronskill et al. 2021, Eq. 8) at both support sums, the task
  embedding and the class statistics, with the task's key at both.
* Step: mean cross-entropy over the queries (mean over the tasks of a
  step), the backbone held fixed (no gradient), clip to a global norm,
  AdamW.

Off float32 (the control) the Cholesky factorisation and the triangular
solve run in float32 on values rounded to the low type: XLA has no
bfloat16 Cholesky.  The support sums run in blocks of rows.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from bench import reference as base

HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# EfficientNet-B0 with FiLM
# ---------------------------------------------------------------------------


def blocks(bb: Dict) -> List[Tuple[int, int, int, int, int, int]]:
    """(expand, kernel, stride, cin, cout, se width) of each MBConv block
    of the configuration's stage table."""
    out, cin = [], bb["stem"]
    for e, k, s, cout, n in bb["stages"]:
        for r in range(n):
            out.append((e, k, s if r == 0 else 1, cin, cout,
                        max(1, int(cin * bb["se_ratio"]))))
            cin = cout
    return out


def _swish(x):
    return x * jax.nn.sigmoid(x)


def _dense(h, w):
    return jnp.einsum("...c,cd->...d", h, w, precision=HI)


def _conv(h, w, stride: int, groups: int = 1):
    return jax.lax.conv_general_dilated(
        h, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=HI)


def efficientnet(bb: Dict, p, x, film=None, hook=None):
    """(B, H, W, C) -> (B, head).  ``film``: one {gamma, beta} per block,
    or None; ``hook(h, bn) -> bn``, where given, picks each batch norm's
    parameters from its input, in the order the layers run."""
    eps = bb["bn_eps"]

    def bn(h, q):
        if hook is not None:
            q = hook(h, q)
        return (h - q["mean"]) / jnp.sqrt(q["var"] + eps) * q["gamma"] \
            + q["beta"]

    h = _swish(bn(_conv(x, p["stem"]["w"], 2), p["stem"]["bn"]))
    for i, ((e, _, s, cin, cout, _), q) in enumerate(zip(blocks(bb),
                                                         p["blocks"])):
        inp = h
        if e != 1:
            h = _swish(bn(_dense(h, q["expand"]["w"]), q["expand"]["bn"]))
        h = bn(_conv(h, q["dw"]["w"], s, groups=h.shape[-1]), q["dw"]["bn"])
        if film is not None:
            h = h * (1.0 + film[i]["gamma"]) + film[i]["beta"]
        h = _swish(h)
        g = _swish(_dense(jnp.mean(h, axis=(1, 2)), q["se"]["w1"])
                   + q["se"]["b1"])
        h = h * jax.nn.sigmoid(_dense(g, q["se"]["w2"])
                               + q["se"]["b2"])[:, None, None, :]
        h = bn(_dense(h, q["project"]["w"]), q["project"]["bn"])
        if s == 1 and cin == cout:
            h = h + inp
    h = _swish(bn(_dense(h, p["head"]["w"]), p["head"]["bn"]))
    return jnp.mean(h, axis=(1, 2))


def calibrate_bn(bb: Dict, p, x):
    """``p`` with each batch norm's mean and var set to those of its input
    over the batch and spatial axes of ``x``, layer after layer (no
    FiLM)."""
    p = jax.tree.map(lambda a: a, p)              # fresh containers

    def hook(h, q):
        axes = tuple(range(h.ndim - 1))
        q.update(mean=jnp.mean(h, axis=axes), var=jnp.var(h, axis=axes))
        return q

    efficientnet(bb, p, x, hook=hook)
    return p


def film_params(p, z) -> List[Dict[str, Any]]:
    out = []
    for s in p["sites"]:
        h = jax.nn.relu(_dense(z, s["w1"]) + s["b1"])
        out.append(dict(gamma=_dense(h, s["w_gamma"]) + s["b_gamma"],
                        beta=_dense(h, s["w_beta"]) + s["b_beta"]))
    return out


# ---------------------------------------------------------------------------
# Simple CNAPs under LITE
# ---------------------------------------------------------------------------


def _chol_dtype(dt):
    return jnp.promote_types(dt, jnp.float32)


def mahalanobis_logits(config: Dict, sums: Dict, counts, n: int, qf):
    """Minus the squared Mahalanobis distance of each query feature to each
    class under Simple CNAPs' blended, ridged covariances."""
    k = counts[:, None]
    mu = sums["feat"] / k
    cov_c = sums["outer"] / k[..., None] - mu[:, :, None] * mu[:, None, :]
    mu_t = jnp.sum(sums["feat"], 0) / n
    cov_t = jnp.sum(sums["outer"], 0) / n - mu_t[:, None] * mu_t[None, :]
    lam = (counts / (counts + 1.0))[:, None, None]
    sigma = lam * cov_c + (1.0 - lam) * cov_t[None]
    f = mu.shape[1]
    diag = jnp.diagonal(sigma, axis1=1, axis2=2)
    ridge = config["cov_eps"] + 1e-3 * jnp.maximum(jnp.mean(diag, -1), 0.0)
    sigma = sigma + ridge[:, None, None] * jnp.eye(f, dtype=sigma.dtype)
    dt = sigma.dtype
    chol = jnp.linalg.cholesky(sigma.astype(_chol_dtype(dt)))
    d = (qf[:, None, :] - mu[None]).astype(chol.dtype)      # (Q, C, F)
    y = jax.vmap(lambda L, dc: jax.scipy.linalg.solve_triangular(
        L, dc.T, lower=True), in_axes=(0, 1))(chol, d)      # (C, F, Q)
    return -jnp.sum(y * y, axis=1).T.astype(dt)             # (Q, C)


def task_loss(config: Dict, params, task: Dict, key):
    """One task's query loss under LITE."""
    way, bbc = config["way"], config["backbone"]
    h, block = config["lite"]["h"], config["lite"]["chunk"]
    sx, sy = task["support_x"], task["support_y"]
    qx, qy = task["query_x"], task["query_y"]
    n = sx.shape[0]

    def enc(x, w):
        return jnp.einsum("n,nd->d", w, base.backbone(params["enc"], x),
                          precision=HI)

    z = base.lite_sum(enc, (sx,), key, h, block) / n
    film = film_params(params["film_gen"], z)
    bb = jax.lax.stop_gradient(params["bb"])

    def stats(x, y, w):
        f = efficientnet(bbc, bb, x, film)
        oh = jax.nn.one_hot(y, way, dtype=f.dtype) * w[:, None]
        return dict(feat=jnp.einsum("nc,nf->cf", oh, f, precision=HI),
                    outer=jnp.einsum("nc,ni,nj->cij", oh, f, f,
                                     precision=HI))

    sums = base.lite_sum(stats, (sx, sy), key, h, block)
    counts = jnp.sum(jax.nn.one_hot(sy, way, dtype=z.dtype), axis=0)
    logits = mahalanobis_logits(config, sums, counts, n,
                                efficientnet(bbc, bb, qx, film))
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, qy[:, None], axis=-1))


def step_loss(config: Dict, params, batch: Dict, key):
    t = batch["support_x"].shape[0]
    losses = [task_loss(config, params,
                        {k: v[i] for k, v in batch.items()},
                        jax.random.fold_in(key, i)) for i in range(t)]
    return sum(losses) / t


def make_train_step(config: Dict, dtype=None):
    """jitted ``(params, m, v, count, batch, key) -> (params, m, v, count,
    loss, clipped_grads)``; with ``dtype`` the loss and its gradient are
    computed in that type (an 8-bit float holds weights and inputs, the
    arithmetic runs in bfloat16), the optimizer in float32."""
    opt = config["optimizer"]

    def low(tree):
        if dtype is None:
            return tree

        def cast(a):
            if not jnp.issubdtype(a.dtype, jnp.floating):
                return a
            a = a.astype(dtype)
            return a.astype(jnp.bfloat16) if a.dtype.itemsize == 1 else a

        return jax.tree.map(cast, tree)

    @jax.jit
    def step(params, m, v, count, batch, key):
        with jax.default_matmul_precision("highest"):
            loss, g = jax.value_and_grad(
                lambda p: step_loss(config, low(p), low(batch), key))(params)
        loss = loss.astype(jnp.float32)
        g = jax.tree.map(lambda a: a.astype(jnp.float32), g)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(a))
                            for a in jax.tree.leaves(g)))
        g = jax.tree.map(lambda a: a * jnp.minimum(
            1.0, opt["max_grad_norm"] / jnp.maximum(norm, 1e-12)), g)
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

        return jax.tree.map(upd, params, m, v), m, v, count, loss, g

    return step


def train(config: Dict, params, batches: Sequence[Tuple[Dict, Any]],
          dtype=None):
    """The reference over ``batches`` from ``params``: the losses, the
    first step's clipped gradient and the final parameters."""
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
