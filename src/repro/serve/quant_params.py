"""Serving-time weight quantization: the frozen slice of a learner's
params in blockwise int8, dequantized lazily inside the jitted step.

The paper's serving regime adapts a task head / FiLM layers around a
FROZEN backbone — at serve time the backbone leaves are pure read-only
traffic, so they ride in the int8 ``{q, scale, n}`` form of
``repro.optim.quant`` (~4x fewer resident HBM bytes) while everything
adaptation actually writes (FiLM generators, set encoder, heads, fomaml's
fully-adapted params) stays fp32.

Which leaves freeze is a property of the learner *kind*, not a heuristic:

  protonets / cnaps / simple_cnaps / finetuner   params["bb"] — the
      backbone is stop_gradient'd (cnaps family, finetuner) or simply
      never written by adaptation (protonets); quantizing it perturbs
      support and query features THROUGH THE SAME WEIGHTS, so class
      statistics and query scores move together (argmax agreement stays
      high; see tests/test_quant_serving.py).
  fomaml   nothing — inner SGD adapts every leaf, so the frozen slice is
      empty and int8 serving is a principled no-op (bit-identical).

:class:`ServingWeights` is a registered pytree: the (mixed fp32 +
quantized-dict) param tree is the child, and the quantized/native path
sets plus the mode ride as static aux data — so it flows through jit and
the shape-bucketed AOT compile cache (``BucketedStepCache``) like any
params tree, while int8-vs-none engines can never collide on a cache
entry.  ``dequantize_params`` runs INSIDE the jitted step: XLA fuses the
int8->f32 expansion into the consumers and the f32 copy lives only for
the step (never materialized persistently); leaves on the backbone's
``quant_native_paths`` skip even that and feed
``repro.kernels.dispatch.int8_matmul`` as raw int8 tiles.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.optim.quant import BLOCK, dequantize, quantize
from repro.train.checkpoint import _path_str

PyTree = Any

SERVE_QUANT_MODES = ("none", "int8")

# learner kind -> top-level param keys that adaptation never writes
FROZEN_SLICES: Dict[str, Tuple[str, ...]] = {
    "protonets": ("bb",),
    "cnaps": ("bb",),
    "simple_cnaps": ("bb",),
    "finetuner": ("bb",),
    "fomaml": (),
}


def is_quantized_leaf(x) -> bool:
    """A blockwise-int8 quantized dict (``repro.optim.quant`` form)."""
    return isinstance(x, dict) and {"q", "scale"} <= set(x)


@dataclasses.dataclass(frozen=True)
class ServingWeights:
    """Params pytree with the frozen slice quantized (or not: mode='none').

    tree: the param tree; quantized leaves are ``{q, scale, n}`` dicts.
    quant_paths: '/'-joined paths of the quantized leaves (static aux).
    native_paths: subset consumed as int8 by the backbone's matmul sites
        (``BackboneDef.quant_native_paths``) — never dequantized at all.
    frozen_roots: the kind's frozen top-level keys (recorded even for
        mode='none' so byte accounting can name the frozen slice).
    mode: 'none' | 'int8'.
    """

    tree: PyTree
    quant_paths: Tuple[str, ...] = ()
    native_paths: Tuple[str, ...] = ()
    frozen_roots: Tuple[str, ...] = ()
    mode: str = "none"


jax.tree_util.register_pytree_node(
    ServingWeights,
    lambda sw: ((sw.tree,), (sw.quant_paths, sw.native_paths,
                             sw.frozen_roots, sw.mode)),
    lambda aux, ch: ServingWeights(ch[0], *aux),
)


def _quantizable(leaf) -> bool:
    return (hasattr(leaf, "dtype") and
            jnp.issubdtype(leaf.dtype, jnp.floating) and leaf.ndim >= 1)


def quantize_frozen(learner, params: PyTree, mode: str = "int8"
                    ) -> ServingWeights:
    """Quantize the frozen slice of ``params`` for serving.

    learner: a :class:`repro.core.meta_learners.MetaLearner` (its
    ``cfg.kind`` names the frozen slice, its ``backbone`` names the
    native int8 matmul sites).  mode='none' wraps params untouched, so
    the engine's dispatch path is identical either way.
    """
    if mode not in SERVE_QUANT_MODES:
        raise ValueError(f"unknown serve_quant mode {mode!r}; "
                         f"choose from {SERVE_QUANT_MODES}")
    kind = learner.cfg.kind
    roots = FROZEN_SLICES.get(kind, ())
    if mode == "none" or not roots:
        return ServingWeights(tree=params, frozen_roots=roots, mode="none")
    native_rel = set(getattr(learner.backbone, "quant_native_paths", ()))
    quant_paths, native_paths = [], []

    def visit(path, leaf):
        p = _path_str(path)
        root, _, rel = p.partition("/")
        if root not in roots or not _quantizable(leaf):
            return leaf
        quant_paths.append(p)
        if rel in native_rel and leaf.ndim == 2:
            native_paths.append(p)
        return quantize(leaf)

    tree = jax.tree_util.tree_map_with_path(visit, params)
    return ServingWeights(tree=tree, quant_paths=tuple(quant_paths),
                          native_paths=tuple(native_paths),
                          frozen_roots=roots, mode="int8")


def dequantize_params(sw: ServingWeights) -> PyTree:
    """Rebuild a params tree the learner can consume — called INSIDE the
    jitted adapt/predict step, so the f32 expansion is fused into the
    step and never persists.  Native-path leaves stay quantized dicts;
    the backbone's matmul site consumes them via ``dispatch.int8_matmul``.
    """
    if sw.mode == "none":
        return sw.tree
    native = set(sw.native_paths)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        sw.tree, is_leaf=is_quantized_leaf)
    out = []
    for path, leaf in flat:
        if is_quantized_leaf(leaf) and _path_str(path) not in native:
            leaf = dequantize(leaf)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def place_serving_weights(sw: ServingWeights, mesh, layout) -> ServingWeights:
    """Place serving weights in a named layout on a (sub)mesh.

    The weights become stationary on ``mesh``'s device group: in a
    multi-replica deployment each replica calls this with its OWN disjoint
    group mesh (``repro.launch.mesh.make_replica_mesh``), so weights never
    move across replica groups — the per-step wire is whatever the layout
    pays WITHIN the group (zero for ``replicated``, partial-sum
    activations for ``weight_stationary``).  ``mesh=None`` or
    ``layout in (None, 'none')`` is the identity (single-device
    placement); ``'auto'`` must be resolved to a concrete name by
    ``repro.roofline.analysis.choose_serving_layout`` before this point —
    placement applies a layout, it does not score one."""
    if mesh is None or layout in (None, "none"):
        return sw
    if layout == "auto":
        raise ValueError(
            "resolve serve_layout='auto' with "
            "repro.roofline.analysis.choose_serving_layout before placing "
            "the serving weights")
    from repro.roofline.analysis import serving_shardings
    return jax.device_put(sw, serving_shardings(sw, mesh, layout))


def param_bytes(sw: ServingWeights) -> Dict[str, int]:
    """Measured resident parameter bytes (host-side accounting over the
    ACTUAL stored arrays — not a model).  Returns totals plus the frozen
    slice alone (the ≥3x reduction guard in the tests), and the
    fp32-equivalent bytes the same leaves would occupy unquantized."""
    tot = tot_fp32 = froz = froz_fp32 = 0
    flat, _ = jax.tree_util.tree_flatten_with_path(
        sw.tree, is_leaf=is_quantized_leaf)
    for path, leaf in flat:
        p = _path_str(path)
        in_frozen = p.split("/", 1)[0] in sw.frozen_roots
        if is_quantized_leaf(leaf):
            nbytes = leaf["q"].size * leaf["q"].dtype.itemsize \
                + leaf["scale"].size * leaf["scale"].dtype.itemsize
            fp32 = 4 * leaf["q"].size
        elif hasattr(leaf, "size"):
            nbytes = leaf.size * leaf.dtype.itemsize
            fp32 = 4 * leaf.size if jnp.issubdtype(
                leaf.dtype, jnp.floating) else nbytes
        else:                                   # python scalar (e.g. 'n')
            nbytes = fp32 = 0
        tot += nbytes
        tot_fp32 += fp32
        if in_frozen:
            froz += nbytes
            froz_fp32 += fp32
    return dict(resident_bytes=tot, fp32_bytes=tot_fp32,
                frozen_resident_bytes=froz, frozen_fp32_bytes=froz_fp32)
