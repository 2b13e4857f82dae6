"""Expose LM-family architectures as episodic BackboneDefs:
the paper's scheme wraps ANY feature extractor — here the support/query
"examples" are token sequences and features are mean-pooled final hidden
states, with per-layer FiLM on the residual stream as the adaptation site.

Works for family='transformer' and 'mamba2' (the families with a plain
scanned trunk); the conv vision backbone remains the paper-faithful path.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mamba2, transformer
from repro.models.backbone import BackboneDef


def _film_stack(film_list, n_layers: int, d_model: int) -> Optional[dict]:
    """List of per-site {gamma, beta} (len = n_layers) -> stacked arrays."""
    if film_list is None:
        return None
    gamma = jnp.stack([f["gamma"] for f in film_list])
    beta = jnp.stack([f["beta"] for f in film_list])
    return dict(gamma=gamma, beta=beta)


def make_lm_backbone(cfg: ModelConfig) -> BackboneDef:
    if cfg.family == "transformer":
        init_fn, trunk_fn = transformer.init_transformer, transformer.trunk
    elif cfg.family == "mamba2":
        init_fn = mamba2.init_mamba2

        def trunk_fn(params, x, cfg, film=None):
            # mamba trunk has no film plumbed through scan; apply the
            # stacked film to the final states as the (documented) site.
            h = mamba2.trunk(params, x, cfg)
            return h, jnp.zeros((), jnp.float32)
    else:
        raise ValueError(f"episodic LM backbone unsupported for {cfg.family}")

    def init(key):
        return init_fn(key, cfg)

    def features(params, tokens, film):
        x = L.embed(params["embed"], tokens, jnp.dtype(cfg.compute_dtype))
        x = x * cfg.embed_scale
        fs = _film_stack(film, cfg.n_layers, cfg.d_model)
        if cfg.family == "transformer":
            h, _ = trunk_fn(params, x, cfg, fs)
        else:
            h, _ = trunk_fn(params, x, cfg)
            if film is not None:
                # final-state site for SSM (mean of per-layer film)
                from repro.core.film import apply_film
                h = apply_film(h, fs["gamma"].mean(0), fs["beta"].mean(0))
        return jnp.mean(h.astype(jnp.float32), axis=1)   # (B, d_model)

    return BackboneDef(
        init=init,
        features=features,
        feature_dim=cfg.d_model,
        film_sites=tuple([cfg.d_model] * cfg.n_layers),
        name=f"lm:{cfg.name}",
    )
