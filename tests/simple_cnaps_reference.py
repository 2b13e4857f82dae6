"""A plain float32 reference for Simple CNAPs on a FiLM-conditioned
EfficientNet-B0 under LITE, for the CPU tests.  Written from the papers,
with nothing of ``repro`` imported; every contraction at HIGHEST
precision.

* EfficientNet-B0 (Tan & Le 2019, Table 1): stem conv3x3 stride 2 (32),
  BN, swish; MBConv blocks (1x1 expand + BN + swish where the ratio is not
  1; depthwise kxk stride s, SAME, + BN; FiLM; swish; squeeze-and-excite
  to max(1, 0.25 * block input); 1x1 project + BN; identity where stride
  1 and equal widths); 1x1 head to 1280 + BN + swish; global mean.  BN is
  frozen: (h - mean) / sqrt(var + 1e-3) * gamma + beta.
* FiLM (Perez et al. 2018): h * (1 + gamma) + beta, per channel.
* CNAPs set encoder: conv3x3 + bias, relu, 2x2 max-pool per block (no pool
  once a side is below 2), global mean, linear head; the task embedding
  is the mean over the support set.
* FiLM generator: per site relu(z W1 + b1), then gamma and beta linear.
* Simple CNAPs (Bateni et al. 2020): class means; Sigma_c = l S_c +
  (1 - l) S_task + eps I with l = k_c / (k_c + 1), eps = 1 + 1e-3 times
  the mean diagonal (the program's ridge); logits = minus the squared
  Mahalanobis distance; mean cross-entropy.
* LITE (Bronskill et al. 2021): each support sum is exact in value; its
  gradient is N/|H| times the gradient of the sum over H, the |H| indices
  with the smallest uniforms U(fold_in(key, i)), at both aggregation sites
  with the same key.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
STAGES = ((1, 3, 1, 16, 1), (6, 3, 2, 24, 2), (6, 5, 2, 40, 2),
          (6, 3, 2, 80, 3), (6, 5, 1, 112, 3), (6, 5, 2, 192, 4),
          (6, 3, 1, 320, 1))


def blocks():
    """(expand, kernel, stride, cin, cout, se) of each MBConv block."""
    out, cin = [], 32
    for e, k, s, cout, n in STAGES:
        for r in range(n):
            out.append((e, k, s if r == 0 else 1, cin, cout,
                        max(1, int(cin * 0.25))))
            cin = cout
    return out


def _swish(x):
    return x * jax.nn.sigmoid(x)


def _dense(h, w):
    return jnp.einsum("...c,cd->...d", h, w, precision=HI)


def _conv(h, w, stride, groups=1):
    return jax.lax.conv_general_dilated(
        h, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=HI)


def _bn(h, p, hook):
    if hook is not None:
        p = hook(h, p)
    return (h - p["mean"]) / jnp.sqrt(p["var"] + 1e-3) * p["gamma"] + p["beta"]


def efficientnet(p, x, film=None, hook=None):
    """(B, H, W, 3) -> (B, 1280); ``film`` a list of {gamma, beta} per
    block or None; ``hook(h, bn) -> bn`` picks each BN's parameters."""
    h = _swish(_bn(_conv(x, p["stem"]["w"], 2), p["stem"]["bn"], hook))
    for i, ((e, k, s, cin, cout, _), bp) in enumerate(zip(blocks(),
                                                          p["blocks"])):
        inp = h
        if e != 1:
            h = _swish(_bn(_dense(h, bp["expand"]["w"]), bp["expand"]["bn"],
                           hook))
        h = _bn(_conv(h, bp["dw"]["w"], s, groups=h.shape[-1]),
                bp["dw"]["bn"], hook)
        if film is not None:
            h = h * (1.0 + film[i]["gamma"]) + film[i]["beta"]
        h = _swish(h)
        g = _swish(_dense(jnp.mean(h, axis=(1, 2)), bp["se"]["w1"])
                   + bp["se"]["b1"])
        g = jax.nn.sigmoid(_dense(g, bp["se"]["w2"]) + bp["se"]["b2"])
        h = h * g[:, None, None, :]
        h = _bn(_dense(h, bp["project"]["w"]), bp["project"]["bn"], hook)
        if s == 1 and cin == cout:
            h = h + inp
    h = _swish(_bn(_dense(h, p["head"]["w"]), p["head"]["bn"], hook))
    return jnp.mean(h, axis=(1, 2))


def calibrated(p, x):
    """``p`` with each BN's mean set to its input's per channel over
    ``x``, and its var to the mean over channels of the per-channel
    variances, layer after layer: features of unit scale for the tests.
    (A small test batch gives some channels a near-zero variance, which
    per-channel statistics would amplify without bound.)"""
    p = jax.tree.map(lambda a: a, p)

    def hook(h, bn):
        var = jnp.mean(jnp.var(h, axis=(0, 1, 2)))
        bn.update(mean=jnp.mean(h, axis=(0, 1, 2)),
                  var=jnp.full_like(bn["var"], var))
        return bn

    efficientnet(p, x, hook=hook)
    return p


def set_encoder(p, x):
    h = x
    for blk in p["blocks"]:
        h = jax.nn.relu(_conv(h, blk["w"], 1) + blk["b"])
        if h.shape[1] >= 2 and h.shape[2] >= 2:
            h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    return _dense(jnp.mean(h, axis=(1, 2)), p["head"]["w"]) + p["head"]["b"]


def film_params(p, z):
    out = []
    for s in p["sites"]:
        h = jax.nn.relu(_dense(z, s["w1"]) + s["b1"])
        out.append(dict(gamma=_dense(h, s["w_gamma"]) + s["b_gamma"],
                        beta=_dense(h, s["w_beta"]) + s["b_beta"]))
    return out


def h_indices(key, n, h):
    u = jnp.stack([jax.random.uniform(jax.random.fold_in(key, i))
                   for i in range(n)])
    return jnp.argsort(u)[:h]


def _lite(sum_fn, n, idx):
    """Exact sum over all ``n`` rows in value; N/|H| times the gradient of
    the sum over ``idx`` (every row where ``idx`` is None)."""
    full = sum_fn(jnp.arange(n))
    if idx is None:
        return full
    part = sum_fn(idx)
    scale = n / idx.shape[0]
    return jax.tree.map(
        lambda f, a: jax.lax.stop_gradient(f) + scale
        * (a - jax.lax.stop_gradient(a)), full, part)


def loss(params, sx, sy, qx, qy, way, key=None, h=None):
    """One task's query loss; with ``key`` and ``h`` < N, under LITE."""
    n = sx.shape[0]
    idx = None if h is None or h >= n else h_indices(key, n, h)
    z = _lite(lambda i: jnp.sum(set_encoder(params["enc"], sx[i]), 0),
              n, idx) / n
    film = film_params(params["film_gen"], z)
    bb = jax.lax.stop_gradient(params["bb"])

    def stats(i):
        f = efficientnet(bb, sx[i], film)
        oh = jax.nn.one_hot(sy[i], way)
        return dict(feat=jnp.einsum("nc,nf->cf", oh, f, precision=HI),
                    outer=jnp.einsum("nc,ni,nj->cij", oh, f, f,
                                     precision=HI))

    s = _lite(stats, n, idx)
    k = jnp.sum(jax.nn.one_hot(sy, way), 0)
    mu = s["feat"] / k[:, None]
    cov_c = s["outer"] / k[:, None, None] - jnp.einsum("ci,cj->cij", mu, mu)
    mu_t = jnp.sum(s["feat"], 0) / n
    cov_t = jnp.sum(s["outer"], 0) / n - jnp.outer(mu_t, mu_t)
    lam = (k / (k + 1.0))[:, None, None]
    sigma = lam * cov_c + (1.0 - lam) * cov_t
    f_dim = mu.shape[1]
    eps = 1.0 + 1e-3 * jnp.maximum(
        jnp.trace(sigma, axis1=1, axis2=2) / f_dim, 0.0)
    sigma = sigma + eps[:, None, None] * jnp.eye(f_dim)
    qf = efficientnet(bb, qx, film)
    d = qf[:, None, :] - mu[None]                          # (Q, C, F)
    sol = jnp.linalg.solve(sigma[None], d[..., None])[..., 0]
    logits = -jnp.sum(d * sol, axis=-1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, qy[:, None], axis=-1))
