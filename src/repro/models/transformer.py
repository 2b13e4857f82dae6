"""Unified decoder-only transformer covering the dense / MoE / MLA /
local-global / softcap / QKV-bias variants in the assigned pool.

Layer stack is a ``lax.scan`` over stacked per-layer parameters (compile
time and HLO size are O(1) in depth).  Per-layer heterogeneity that the
pool needs (gemma2's local/global alternation) rides through the scan as a
per-layer window array, so one block body serves all layers.

Three entry points per model:
  loss(params, batch)          training objective (chunked cross-entropy)
  prefill(params, batch)       full-sequence forward -> (last logits, cache)
  decode_step(params, cache, tokens)  one-token KV-cache decode
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.init import lecun_normal
from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.sharding.ctx import constrain, residual_spec, P

Params = Dict
AUX_COEF = 0.01
GLOBAL_WINDOW = 1 << 30


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def moe_dispatch(lp: Params, h2d: jnp.ndarray, cfg: ModelConfig):
    """Route to the explicit expert-parallel shard_map dispatch when a
    mesh is active and divisibility allows (§Perf), else the jnp path."""
    if cfg.moe_shard_map:
        from repro.sharding.ctx import _active_mesh
        mesh = _active_mesh()
        if mesh is not None and hasattr(mesh, "devices"):
            # boundary specs must match residual_spec's layout (else GSPMD
            # reshards the full activation at every layer — see moe.py)
            layout = (getattr(cfg, "activation_layout", "hidden")
                      if cfg.shard_activations_model else "seq")
            return M.moe_ffn_sharded(lp, h2d, cfg.moe, mesh, layout=layout)
    return M.moe_ffn(lp, h2d, cfg.moe)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_block(key: jax.Array, cfg: ModelConfig) -> Params:
    k_attn, k_ffn = jax.random.split(key)
    a = cfg.attention
    p: Params = dict(
        attn_norm=jnp.zeros((cfg.d_model,)),
        ffn_norm=jnp.zeros((cfg.d_model,)),
    )
    p["attn"] = L.init_mla(k_attn, cfg) if a.kind == "mla" else L.init_gqa(k_attn, cfg)
    if cfg.moe is not None:
        p["ffn"] = M.init_moe(k_ffn, cfg.d_model, cfg.moe)
    else:
        p["ffn"] = L.init_mlp(k_ffn, cfg.d_model, cfg.d_ff)
    return p


def init_transformer(key: jax.Array, cfg: ModelConfig) -> Params:
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = jax.vmap(lambda k: init_block(k, cfg))(layer_keys)
    p = dict(
        embed=L.init_embed(k_embed, cfg.vocab_padded, cfg.d_model),
        layers=layers,
        final_norm=jnp.zeros((cfg.d_model,)),
    )
    if not cfg.tie_embeddings:
        p["lm_head"] = lecun_normal(k_head, (cfg.vocab_padded, cfg.d_model))
    return p


def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """Per-layer attention window (gemma2: even layers local)."""
    if not cfg.local_global:
        return jnp.full((cfg.n_layers,), GLOBAL_WINDOW, jnp.int32)
    idx = jnp.arange(cfg.n_layers)
    return jnp.where(idx % 2 == 0, cfg.sliding_window, GLOBAL_WINDOW).astype(jnp.int32)


# --------------------------------------------------------------------------
# block body (shared by train / prefill; decode has its own)
# --------------------------------------------------------------------------

def block(cfg: ModelConfig, lp: Params, x: jnp.ndarray, window: jnp.ndarray,
          film: Optional[Dict] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (x', aux_loss).  ``film`` (episodic adaptation):
    per-layer {gamma, beta} of width d_model applied to the residual
    stream after the block — the LM-family FiLM site."""
    a = cfg.attention
    h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    if a.kind == "mla":
        attn_out = L.mla_attention(lp["attn"], h, a, cfg.norm_eps)
    else:
        attn_out = L.gqa_attention(lp["attn"], h, a, window=window,
                                   head_constraints=cfg.attn_head_constraints)
    x = x + cfg.residual_scale * attn_out
    x = constrain(x, residual_spec(cfg))

    h = L.rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe is not None:
        b, s, d = h.shape
        y, aux = moe_dispatch(lp["ffn"], h.reshape(b * s, d), cfg)
        y = y.reshape(b, s, d)
    else:
        y = L.mlp(lp["ffn"], h)
    x = x + cfg.residual_scale * y
    if film is not None:
        from repro.core.film import apply_film
        x = apply_film(x, film["gamma"], film["beta"])
    x = constrain(x, residual_spec(cfg))
    return x, aux


def _remat(fn, cfg: ModelConfig):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)       # 'nothing' saveable


def trunk(params: Params, x: jnp.ndarray, cfg: ModelConfig,
          film: Optional[Dict] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Embedded inputs -> final hidden states. x: (B, S, D).
    film: optional {gamma (L, D), beta (L, D)} stacked per-layer FiLM."""
    windows = layer_windows(cfg)
    body = _remat(functools.partial(block, cfg), cfg)

    def step(carry, xs):
        if film is not None:
            lp, w, f = xs
        else:
            lp, w = xs
            f = None
        x, aux = carry
        x, a = body(lp, x, w, f)
        return (x, aux + a), None

    xs = (params["layers"], windows)
    if film is not None:
        xs = xs + (film,)
    (x, aux), _ = jax.lax.scan(step, (x, jnp.zeros((), jnp.float32)), xs)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux


def logits_head(params: Params, h: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    table = params["embed"] if cfg.tie_embeddings else params.get("lm_head", params["embed"])
    logits = L.unembed(table, h) * cfg.logit_scale
    logits = L.softcap(logits, cfg.final_softcap)
    if cfg.vocab_padded != cfg.vocab:
        pad_mask = jnp.arange(cfg.vocab_padded) < cfg.vocab
        logits = jnp.where(pad_mask, logits, -1e30)
    return logits


# --------------------------------------------------------------------------
# training loss (chunked cross-entropy over the sequence axis)
# --------------------------------------------------------------------------

def _xent(params: Params, h: jnp.ndarray, labels: jnp.ndarray,
          mask: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """h: (B, S, D); labels/mask: (B, S). Mean NLL over mask."""
    b, s, d = h.shape
    chunk = cfg.loss_chunk if cfg.loss_chunk > 0 else s
    n = s // chunk if s % chunk == 0 else 0
    if n <= 1:
        logits = logits_head(params, h, cfg)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - ll) * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    hc = h.reshape(b, n, chunk, d).swapaxes(0, 1)          # (n, B, chunk, D)
    lc = labels.reshape(b, n, chunk).swapaxes(0, 1)
    mc = mask.reshape(b, n, chunk).swapaxes(0, 1)

    def body(acc, xs):
        hi, li, mi = xs
        logits = logits_head(params, hi, cfg)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        return acc + jnp.sum((lse - ll) * mi), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, lc, mc))
    return total / jnp.maximum(jnp.sum(mask), 1.0)


def embed_inputs(params: Params, batch: Dict, cfg: ModelConfig) -> jnp.ndarray:
    """Token embedding (+ optional modality-stub embeddings prepended)."""
    x = L.embed(params["embed"], batch["tokens"], _dtype(cfg)) * cfg.embed_scale
    if cfg.frontend is not None and "frontend_embeds" in batch:
        fe = batch["frontend_embeds"].astype(_dtype(cfg))
        x = jnp.concatenate([fe, x], axis=1)
    return x


def loss(params: Params, batch: Dict, cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict]:
    """Next-token loss. batch: tokens (B, S) int32 [+ frontend_embeds]."""
    tokens = batch["tokens"]
    x = embed_inputs(params, batch, cfg)
    x = constrain(x, P("data", None, None))
    h, aux = trunk(params, x, cfg)
    n_front = x.shape[1] - tokens.shape[1]
    h = h[:, n_front:, :]
    labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    mask = jnp.pad(jnp.ones_like(tokens[:, 1:], jnp.float32), ((0, 0), (0, 1)))
    nll = _xent(params, h, labels, mask, cfg)
    total = nll + AUX_COEF * aux
    return total, dict(nll=nll, aux=aux)


# --------------------------------------------------------------------------
# KV-cache inference
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch_size: int, max_seq: int) -> Dict:
    a = cfg.attention
    dt = _dtype(cfg)
    lshape = (cfg.n_layers, batch_size, max_seq)
    if a.kind == "mla":
        cache = dict(
            ckv=jnp.zeros(lshape + (a.kv_lora_rank,), dt),
            krope=jnp.zeros(lshape + (a.qk_rope_dim,), dt),
        )
    else:
        cache = dict(
            k=jnp.zeros(lshape + (a.n_kv_heads, a.head_dim), dt),
            v=jnp.zeros(lshape + (a.n_kv_heads, a.head_dim), dt),
        )
    cache["len"] = jnp.zeros((), jnp.int32)
    return cache


def prefill(params: Params, batch: Dict, cfg: ModelConfig
            ) -> Tuple[jnp.ndarray, Dict]:
    """Full forward over the prompt; returns (last-token logits (B, Vp),
    populated cache).  The cache is collected as scan ys so only one
    layer's K/V is live during the sweep."""
    a = cfg.attention
    tokens = batch["tokens"]
    x = embed_inputs(params, batch, cfg)
    x = constrain(x, P("data", None, None))
    s = x.shape[1]
    positions = jnp.arange(s)
    windows = layer_windows(cfg)

    def step(carry, xs):
        lp, w = xs
        x, aux = carry
        h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        if a.kind == "mla":
            ckv, krope = L.mla_latent(lp["attn"], h, a, cfg.norm_eps, positions)
            attn_out = L.mla_attention(lp["attn"], h, a, cfg.norm_eps)
            kv = dict(ckv=ckv, krope=krope.reshape(krope.shape[0], s, -1))
        else:
            q, k, v = L.gqa_project_qkv(lp["attn"], h, a, positions,
                                        head_constraints=cfg.attn_head_constraints)
            o = L.attention_scores(q, k, v, causal=True, window=w, cap=a.attn_softcap)
            attn_out = o.reshape(h.shape[0], s, -1) @ lp["attn"]["wo"].astype(h.dtype)
            kv = dict(k=k, v=v)
        x = x + cfg.residual_scale * attn_out
        h = L.rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
        if cfg.moe is not None:
            b_, s_, d_ = h.shape
            y, aux2 = moe_dispatch(lp["ffn"], h.reshape(b_ * s_, d_), cfg)
            y = y.reshape(b_, s_, d_)
            aux = aux + aux2
        else:
            y = L.mlp(lp["ffn"], h)
        x = x + cfg.residual_scale * y
        x = constrain(x, residual_spec(cfg))
        return (x, aux), kv

    (x, _), kvs = jax.lax.scan(step, (x, jnp.zeros((), jnp.float32)),
                               (params["layers"], windows))
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_head(params, h[:, -1:, :], cfg)[:, 0, :]
    cache = dict(kvs)
    cache["len"] = jnp.asarray(s, jnp.int32)
    return logits, cache


def decode_step(params: Params, cache: Dict, tokens: jnp.ndarray,
                cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict]:
    """One decode step. tokens: (B, 1) int32; cache from init_cache/prefill.
    Returns (logits (B, Vp), updated cache)."""
    a = cfg.attention
    dt = _dtype(cfg)
    x = L.embed(params["embed"], tokens, dt) * cfg.embed_scale
    pos = cache["len"]
    windows = layer_windows(cfg)
    b = tokens.shape[0]

    def step(x, xs):
        if a.kind == "mla":
            lp, w, ckv_c, krope_c = xs
        else:
            lp, w, k_c, v_c = xs
        h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        if a.kind == "mla":
            positions = jnp.full((b, 1), pos, jnp.int32)
            ckv_new, krope_new = L.mla_latent(lp["attn"], h, a, cfg.norm_eps, positions)
            ckv_c = jax.lax.dynamic_update_slice(ckv_c, ckv_new, (0, pos, 0))
            krope_c = jax.lax.dynamic_update_slice(
                krope_c, krope_new.reshape(b, 1, -1), (0, pos, 0))
            attn_out = L.mla_decode_attention(lp["attn"], h, a, cfg.norm_eps,
                                              ckv_c, krope_c, pos)
            new_kv = (ckv_c, krope_c)
        else:
            positions = jnp.full((b, 1), pos, jnp.int32)
            q, k, v = L.gqa_project_qkv(lp["attn"], h, a, positions,
                                        head_constraints=cfg.attn_head_constraints)
            k_c = jax.lax.dynamic_update_slice(k_c, k, (0, pos, 0, 0))
            v_c = jax.lax.dynamic_update_slice(v_c, v, (0, pos, 0, 0))
            o = L.attention_scores(
                q, k_c, v_c, causal=False, window=w, cap=a.attn_softcap,
                q_positions=jnp.full((1,), pos, jnp.int32),
                k_positions=jnp.arange(k_c.shape[1]),
                k_len=pos + 1)
            attn_out = o.reshape(b, 1, -1) @ lp["attn"]["wo"].astype(h.dtype)
            new_kv = (k_c, v_c)
        x = x + cfg.residual_scale * attn_out
        h = L.rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
        if cfg.moe is not None:
            y, _ = moe_dispatch(lp["ffn"], h.reshape(b, -1), cfg)
            y = y.reshape(b, 1, -1)
        else:
            y = L.mlp(lp["ffn"], h)
        x = x + cfg.residual_scale * y
        return x, new_kv

    if a.kind == "mla":
        xs = (params["layers"], windows, cache["ckv"], cache["krope"])
    else:
        xs = (params["layers"], windows, cache["k"], cache["v"])
    x, new_kvs = jax.lax.scan(step, x, xs)
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_head(params, h, cfg)[:, 0, :]
    new_cache = dict(len=cache["len"] + 1)
    if a.kind == "mla":
        new_cache["ckv"], new_cache["krope"] = new_kvs
    else:
        new_cache["k"], new_cache["v"] = new_kvs
    return logits, new_cache
