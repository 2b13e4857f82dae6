"""Partition rules: map parameter/cache/batch pytrees to PartitionSpecs.

Strategy:
  * 'model' axis = tensor/expert parallel (attention heads, FFN hidden,
    MoE expert dim, vocab).
  * 'data' (+ 'pod' when present) = data parallel for activations AND the
    second param dim (FSDP / ZeRO-3 style), so no parameter is replicated
    across the data axis — required for the 236B/1T configs.
  * Rules are name+shape based; ``sanitize`` (repro.sharding.ctx) then
    drops any axis that the live mesh lacks or that does not divide the
    dim, so the same rules serve the test mesh, 16x16, and 2x16x16.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
from jax.sharding import PartitionSpec

from repro.sharding.ctx import sanitize_tree

P = PartitionSpec

FSDP = ("pod", "data")   # sanitize drops 'pod' on single-pod meshes


def _pad(spec: Tuple, ndim: int) -> PartitionSpec:
    """Left-pad a trailing-dims spec with None (layer-stack leading dims)."""
    pad = ndim - len(spec)
    return P(*([None] * pad + list(spec)))


def _param_rule(path: str, ndim: int) -> PartitionSpec:
    """Spec for the TRAILING dims implied by the leaf name."""
    name = path.split("/")[-1]

    # embeddings / unembedding: (V, D) — vocab over model, D over data
    if name in ("embed", "lm_head"):
        return _pad((("model",), FSDP), ndim)

    # MoE shared experts: small (D, F_shared) — keep off the model axis
    # (TP-sharding them costs an (B, S, D) all-reduce per layer fwd+bwd)
    if "shared" in path and name in ("w_gate", "w_up"):
        return _pad((FSDP, None), ndim)
    if "shared" in path and name == "w_down":
        return _pad((None, FSDP), ndim)

    # MoE expert banks: (E, D, F) / (E, F, D) — E over model (EP)
    if "ffn" in path and name in ("w_gate", "w_up") and ndim >= 3:
        return _pad((("model",), FSDP, None), ndim)
    if "ffn" in path and name == "w_down" and ndim >= 3:
        return _pad((("model",), None, FSDP), ndim)
    if name == "router":
        return _pad((FSDP, None), ndim)

    # dense FFN: (D, F) / (F, D)
    if name in ("w_gate", "w_up"):
        return _pad((FSDP, ("model",)), ndim)
    if name == "w_down":
        return _pad((("model",), FSDP), ndim)

    # attention projections
    if name in ("wq", "wk", "wv", "wq_b", "wk_b", "wv_b"):
        return _pad((FSDP, ("model",)), ndim)        # out dim = heads
    if name in ("wq_a", "wkv_a"):
        return _pad((FSDP, None), ndim)              # low-rank out is small
    if name == "wo":
        return _pad((("model",), FSDP), ndim)
    if name in ("bq", "bk", "bv"):
        return _pad((("model",),), ndim)

    # mamba
    if name == "in_proj":
        return _pad((FSDP, ("model",)), ndim)
    if name == "out_proj":
        return _pad((("model",), FSDP), ndim)
    if name == "conv_w":
        return _pad((("model",), None), ndim)
    if name in ("conv_b", "gate_norm"):
        return _pad((("model",),), ndim)

    # everything 1-D-ish (norm scales, dt_bias, A_log, D) replicates
    return P(*([None] * ndim))


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_specs(abstract_params: Any) -> Any:
    """Pytree of PartitionSpecs matching ``abstract_params`` (from
    jax.eval_shape on the model init)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _param_rule(_path_str(path), len(leaf.shape)),
        abstract_params)


def opt_state_specs(abstract_opt: Any, pspecs_example: Any = None) -> Any:
    """Optimizer state mirrors params (mu/nu under dicts; int8 states carry
    a trailing-dim-reduced 'scale' leaf).  Name-based rules still apply —
    the leaf names inside mu/nu are the parameter names, and 'q'/'scale'
    leaves inherit from their parent parameter name."""

    def rule(path, leaf):
        p = _path_str(path)
        name = p.split("/")[-1]
        if name in ("count", "n") or not hasattr(leaf, "shape"):
            # 'n' is the quantized dict's stored trailing dim (a plain
            # python int on the host side — no shape to shard).
            return P()
        if name in ("q", "scale"):
            parent = p.split("/")[-2]
            spec = _param_rule(parent, len(leaf.shape))
            if name == "scale":
                # scale's last dim is blocked; spec entries still apply,
                # sanitize() drops any that no longer divide.
                return spec
            return spec
        if name == "count":
            return P()
        return _param_rule(p, len(leaf.shape))

    return jax.tree_util.tree_map_with_path(rule, abstract_opt)


def batch_specs(abstract_batch: Any) -> Any:
    """Batch: leading dim over (pod, data); tokens replicate over model."""
    def rule(_path, leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        return P(*([FSDP] + [None] * (nd - 1)))
    return jax.tree_util.tree_map_with_path(rule, abstract_batch)


def cache_specs(abstract_cache: Any, batch_size: int, data_size: int,
                model_size: int = 16) -> Any:
    """Decode caches: (L, B, S, H, Dh)-style leaves.

    Placement logic (the KV cache is the decode-memory wall):
      * batch shards over (pod,)data when divisible; else the sequence
        axis takes the data axis (long-context batch=1 cells);
      * heads shard over model when divisible (no attention comm);
        otherwise the SEQUENCE axis shards over model — sequence-parallel
        decode with partial-softmax all-reduces (the gemma2 kv=4 case,
        which would otherwise replicate a 200+GB cache 16x).
    """
    big_batch = batch_size % max(data_size, 1) == 0 and batch_size >= data_size

    def rule(path, leaf):
        p = _path_str(path).split("/")[-1]
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        if p in ("k", "v", "cross_k", "cross_v"):     # (L|G, B, S, H, Dh)
            n_heads = leaf.shape[3]
            b_entry = FSDP if big_batch else None
            s_entry = None if big_batch else FSDP
            if n_heads % model_size == 0:
                return P(None, b_entry, s_entry, "model", None)
            if big_batch:
                return P(None, b_entry, "model", None, None)
            return P(None, None, (FSDP[-1], "model") if s_entry else "model",
                     None, None)
        if p in ("ckv", "krope"):       # (L, B, S, R) — latent: shard S
            b_entry = FSDP if big_batch else None
            return P(None, b_entry, "model" if big_batch else (FSDP[-1], "model"),
                     None)
        if p == "conv":                 # (L, B, C, k-1)
            return P(None, FSDP if big_batch else None, "model", None)
        if p == "ssm":                  # (L, B, H, P, N)
            return P(None, FSDP if big_batch else None, "model", None, None)
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(rule, abstract_cache)


def sanitize(specs: Any, abstract: Any, mesh) -> Any:
    return sanitize_tree(specs, abstract, mesh)


def strip_axes(specs: Any, axes=("model",)) -> Any:
    """Remove named axes from every spec (tp_enabled=False -> pure DP/FSDP)."""

    def fix(s):
        out = []
        for e in tuple(s):
            if e is None:
                out.append(None)
            elif isinstance(e, tuple):
                kept = tuple(n for n in e if n not in axes)
                out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
            else:
                out.append(None if e in axes else e)
        return P(*out)

    return jax.tree.map(fix, specs, is_leaf=lambda x: isinstance(x, P))
