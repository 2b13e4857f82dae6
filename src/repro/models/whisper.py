"""Whisper-style encoder-decoder transformer backbone.

Per the assignment brief, the conv/audio frontend is a STUB: the encoder
consumes precomputed frame embeddings (B, S_enc, d_model) provided by
``input_specs`` / the data pipeline.  Encoder: bidirectional self-attention;
decoder: causal self-attention + cross-attention to the encoder output.

Deviation from the original: rotary positions
instead of learned/sinusoidal tables, so sequence length is unconstrained
for the assigned 32k decode shapes.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.common.init import lecun_normal
from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.transformer import logits_head, _xent, _remat
from repro.sharding.ctx import constrain, residual_spec, P

Params = Dict


def init_cross_attn(key: jax.Array, cfg: ModelConfig) -> Params:
    a = cfg.attention
    d, h, dh = cfg.d_model, a.n_heads, a.head_dim
    ks = jax.random.split(key, 4)
    return dict(
        wq=lecun_normal(ks[0], (d, h * dh)),
        wk=lecun_normal(ks[1], (d, h * dh)),
        wv=lecun_normal(ks[2], (d, h * dh)),
        wo=lecun_normal(ks[3], (h * dh, d)),
    )


def init_enc_block(key: jax.Array, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return dict(
        attn_norm=jnp.zeros((cfg.d_model,)),
        ffn_norm=jnp.zeros((cfg.d_model,)),
        attn=L.init_gqa(k1, cfg),
        ffn=L.init_mlp(k2, cfg.d_model, cfg.d_ff),
    )


def init_dec_block(key: jax.Array, cfg: ModelConfig) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return dict(
        attn_norm=jnp.zeros((cfg.d_model,)),
        cross_norm=jnp.zeros((cfg.d_model,)),
        ffn_norm=jnp.zeros((cfg.d_model,)),
        attn=L.init_gqa(k1, cfg),
        cross=init_cross_attn(k2, cfg),
        ffn=L.init_mlp(k3, cfg.d_model, cfg.d_ff),
    )


def init_whisper(key: jax.Array, cfg: ModelConfig) -> Params:
    k_embed, k_enc, k_dec = jax.random.split(key, 3)
    enc_keys = jax.random.split(k_enc, cfg.n_encoder_layers)
    dec_keys = jax.random.split(k_dec, cfg.n_layers)
    return dict(
        embed=L.init_embed(k_embed, cfg.vocab_padded, cfg.d_model),
        encoder=jax.vmap(lambda k: init_enc_block(k, cfg))(enc_keys),
        decoder=jax.vmap(lambda k: init_dec_block(k, cfg))(dec_keys),
        enc_norm=jnp.zeros((cfg.d_model,)),
        final_norm=jnp.zeros((cfg.d_model,)),
    )


def encode(params: Params, frames: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """frames: (B, S_enc, D) stub embeddings -> encoder states."""
    a = cfg.attention
    x = frames.astype(jnp.dtype(cfg.compute_dtype))
    x = constrain(x, P("data", None, None))

    def body(lp, x):
        h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        x = x + L.gqa_attention_bidir(lp["attn"], h, a)
        h = L.rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
        x = x + L.mlp(lp["ffn"], h)
        return constrain(x, residual_spec(cfg))

    body = _remat(body, cfg)

    def step(x, lp):
        return body(lp, x), None

    x, _ = jax.lax.scan(step, x, params["encoder"])
    return L.rms_norm(x, params["enc_norm"], cfg.norm_eps)


def cross_attention(cp: Params, x: jnp.ndarray, enc: jnp.ndarray,
                    cfg: ModelConfig) -> jnp.ndarray:
    a = cfg.attention
    b, s, _ = x.shape
    se = enc.shape[1]
    q = (x @ cp["wq"].astype(x.dtype)).reshape(b, s, a.n_heads, a.head_dim)
    k = (enc @ cp["wk"].astype(x.dtype)).reshape(b, se, a.n_heads, a.head_dim)
    v = (enc @ cp["wv"].astype(x.dtype)).reshape(b, se, a.n_heads, a.head_dim)
    o = L.attention_scores(q, k, v, causal=False)
    return o.reshape(b, s, -1) @ cp["wo"].astype(x.dtype)


def cross_attention_cached(cp: Params, x: jnp.ndarray, k: jnp.ndarray,
                           v: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    a = cfg.attention
    b, s, _ = x.shape
    q = (x @ cp["wq"].astype(x.dtype)).reshape(b, s, a.n_heads, a.head_dim)
    o = L.attention_scores(q, k, v, causal=False)
    return o.reshape(b, s, -1) @ cp["wo"].astype(x.dtype)


def decode_trunk(params: Params, x: jnp.ndarray, enc: jnp.ndarray,
                 cfg: ModelConfig) -> jnp.ndarray:
    a = cfg.attention

    def body(lp, x):
        h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        x = x + L.gqa_attention(lp["attn"], h, a)
        h = L.rms_norm(x, lp["cross_norm"], cfg.norm_eps)
        x = x + cross_attention(lp["cross"], h, enc, cfg)
        h = L.rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
        x = x + L.mlp(lp["ffn"], h)
        return constrain(x, residual_spec(cfg))

    body = _remat(body, cfg)

    def step(x, lp):
        return body(lp, x), None

    x, _ = jax.lax.scan(step, x, params["decoder"])
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps)


def loss(params: Params, batch: Dict, cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict]:
    """batch: frames (B, S_enc, D) float, tokens (B, S) int32."""
    tokens = batch["tokens"]
    enc = encode(params, batch["frontend_embeds"], cfg)
    x = L.embed(params["embed"], tokens, jnp.dtype(cfg.compute_dtype))
    x = constrain(x, P("data", None, None))
    h = decode_trunk(params, x, enc, cfg)
    labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    mask = jnp.pad(jnp.ones_like(tokens[:, 1:], jnp.float32), ((0, 0), (0, 1)))
    nll = _xent(params, h, labels, mask, cfg)
    return nll, dict(nll=nll, aux=jnp.zeros((), jnp.float32))


# --------------------------------------------------------------------------
# inference: decoder self-attn KV cache + precomputed cross K/V
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch_size: int, max_seq: int) -> Dict:
    a = cfg.attention
    dt = jnp.dtype(cfg.compute_dtype)
    lb = (cfg.n_layers, batch_size)
    se = cfg.n_frontend_tokens
    return dict(
        k=jnp.zeros(lb + (max_seq, a.n_kv_heads, a.head_dim), dt),
        v=jnp.zeros(lb + (max_seq, a.n_kv_heads, a.head_dim), dt),
        cross_k=jnp.zeros(lb + (se, a.n_heads, a.head_dim), dt),
        cross_v=jnp.zeros(lb + (se, a.n_heads, a.head_dim), dt),
        len=jnp.zeros((), jnp.int32),
    )


def prefill(params: Params, batch: Dict, cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict]:
    a = cfg.attention
    tokens = batch["tokens"]
    enc = encode(params, batch["frontend_embeds"], cfg)
    b, s = tokens.shape
    se = enc.shape[1]
    x = L.embed(params["embed"], tokens, jnp.dtype(cfg.compute_dtype))
    positions = jnp.arange(s)

    def step(x, lp):
        h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = L.gqa_project_qkv(lp["attn"], h, a, positions)
        o = L.attention_scores(q, k, v, causal=True)
        x = x + o.reshape(b, s, -1) @ lp["attn"]["wo"].astype(h.dtype)
        h = L.rms_norm(x, lp["cross_norm"], cfg.norm_eps)
        ck = (enc @ lp["cross"]["wk"].astype(h.dtype)).reshape(b, se, a.n_heads, a.head_dim)
        cv = (enc @ lp["cross"]["wv"].astype(h.dtype)).reshape(b, se, a.n_heads, a.head_dim)
        x = x + cross_attention_cached(lp["cross"], h, ck, cv, cfg)
        h = L.rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
        x = x + L.mlp(lp["ffn"], h)
        return x, (k, v, ck, cv)

    x, (ks, vs, cks, cvs) = jax.lax.scan(step, x, params["decoder"])
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_head(params, h[:, -1:, :], cfg)[:, 0, :]
    return logits, dict(k=ks, v=vs, cross_k=cks, cross_v=cvs,
                        len=jnp.asarray(s, jnp.int32))


def decode_step(params: Params, cache: Dict, tokens: jnp.ndarray,
                cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict]:
    a = cfg.attention
    b = tokens.shape[0]
    pos = cache["len"]
    x = L.embed(params["embed"], tokens, jnp.dtype(cfg.compute_dtype))

    def step(x, xs):
        lp, k_c, v_c, ck, cv = xs
        h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = L.gqa_project_qkv(lp["attn"], h, a, jnp.full((b, 1), pos, jnp.int32))
        k_c = jax.lax.dynamic_update_slice(k_c, k, (0, pos, 0, 0))
        v_c = jax.lax.dynamic_update_slice(v_c, v, (0, pos, 0, 0))
        o = L.attention_scores(q, k_c, v_c, causal=False,
                               q_positions=jnp.full((1,), pos, jnp.int32),
                               k_positions=jnp.arange(k_c.shape[1]),
                               k_len=pos + 1)
        x = x + o.reshape(b, 1, -1) @ lp["attn"]["wo"].astype(h.dtype)
        h = L.rms_norm(x, lp["cross_norm"], cfg.norm_eps)
        x = x + cross_attention_cached(lp["cross"], h, ck, cv, cfg)
        h = L.rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
        x = x + L.mlp(lp["ffn"], h)
        return x, (k_c, v_c)

    x, (ks, vs) = jax.lax.scan(
        step, x, (params["decoder"], cache["k"], cache["v"],
                  cache["cross_k"], cache["cross_v"]))
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_head(params, h, cfg)[:, 0, :]
    return logits, dict(k=ks, v=vs, cross_k=cache["cross_k"],
                        cross_v=cache["cross_v"], len=pos + 1)
