"""Gradient-estimator diagnostics — the paper's §5.3 / Fig. 4 / Tables
D.7-D.8 harness, as a library (tests/test_meta_learners.py calls in).

For a fixed task and fixed params:
  * exact gradient      g*  = d meta_loss / d params at LiteSpec(exact)
  * LITE gradient       g_h = estimator with |H|=h (paper Eq. 8)
  * subsampled gradient s_h = forward AND backward on h examples (Fig. 4's
    "small task" baseline)

Reported per h over n_draws fresh index draws:
  * bias MSE:   || mean_draws(g) - g* ||^2 / dim     (Table D.7 analogue)
  * RMSE:       mean_draws ||g - g*|| / sqrt(dim)    (Fig. 4 / Table D.8)
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.episodic import Task
from repro.core.lite import LiteSpec


def _flat(tree) -> jnp.ndarray:
    return jnp.concatenate([jnp.ravel(x) for x in jax.tree.leaves(tree)])


def gradient_experiment(meta_loss: Callable, params, task: Task,
                        h_values: Sequence[int], n_draws: int,
                        key: jax.Array, subsampled_estimator=None,
                        param_filter: Optional[Callable] = None) -> Dict:
    """meta_loss(params, task, key, lite_spec, estimator=None) -> (loss, aux).

    param_filter: optional tree -> subtree selector.  The paper's Fig. 4
    measures RMSE on the FIRST Conv2D of Simple CNAPs' set encoder (the
    site where LITE's exact-forward advantage is cleanest); pass e.g.
    ``lambda p: p["enc"]["blocks"][0]["w"]`` to reproduce that.

    Returns {"exact_norm": float, "lite": {h: {bias_mse, rmse}},
             "subsampled": {h: {...}} (if subsampled_estimator given)}.
    """
    if param_filter is None:
        param_filter = lambda t: t
    grad_fn = jax.jit(
        jax.grad(lambda p, k, spec_h, exact, sub: _loss_dispatch(
            meta_loss, p, task, k, spec_h, exact, sub)[0]),
        static_argnums=(2, 3, 4))   # h determines slice shapes -> static

    g_exact = param_filter(grad_fn(params, key, 0, True, False))
    g_exact_f = _flat(g_exact)
    dim = g_exact_f.shape[0]

    out = {"exact_norm": float(jnp.linalg.norm(g_exact_f)),
           "lite": {}, "subsampled": {}}
    modes = [("lite", False)]
    if subsampled_estimator is not None:
        modes.append(("subsampled", True))

    for mode, use_sub in modes:
        for h in h_values:
            draws = []
            k = key
            for _ in range(n_draws):
                k, sub = jax.random.split(k)
                g = param_filter(grad_fn(params, sub, h, False, use_sub))
                draws.append(np.asarray(_flat(g), np.float64))
            draws = np.stack(draws)
            exact = np.asarray(g_exact_f, np.float64)
            bias_mse = float(np.mean((draws.mean(0) - exact) ** 2))
            rmse = float(np.mean(np.sqrt(np.mean((draws - exact) ** 2, axis=1))))
            out[mode][h] = dict(bias_mse=bias_mse, rmse=rmse)
    return out


def _loss_dispatch(meta_loss, params, task, key, h, exact, use_subsampled):
    spec = LiteSpec(h=h, exact=exact)
    if use_subsampled:
        return meta_loss(params, task, key, spec, estimator="subsampled")
    return meta_loss(params, task, key, spec)
