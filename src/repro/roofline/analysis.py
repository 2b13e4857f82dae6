"""Serving-layout scorer: compile a serving step under each candidate
weight layout and score the compiled HLO with a three-term roofline
(compute, HBM bytes, collective wire; the largest term bounds the step)."""
from __future__ import annotations

from typing import Dict

import jax

from repro.roofline.constants import HBM_BW, ICI_BW_PER_LINK, PEAK_FLOPS_BF16


# ---------------------------------------------------------------------------
# Serving layout chooser (weight-stationary int8 serving path)
#
# Serving flips training's traffic balance: batches are small (a handful of
# episodes) while the frozen backbone is the big tensor, so the training
# layout — weights sharded on their LARGEST dim and all-gathered each step
# (ZeRO-style), batch sharded on the leading dim — pays full-weight wire
# every step for activation savings it no longer needs.  The serving
# candidates below are scored on the COMPILED program (collectives_report +
# loop-aware HLO walk, same machinery as the dry-run roofline), not on a
# paper model, so the chooser's pick reflects what XLA actually emits.
# ---------------------------------------------------------------------------

SERVING_LAYOUTS = ("training", "weight_stationary", "replicated")


def _largest_divisible_dim(shape, n: int) -> int:
    """Index of the largest dim divisible by n, or -1."""
    best, best_d = -1, 0
    for i, d in enumerate(shape):
        if d % n == 0 and d > best_d:
            best, best_d = i, d
    return best


def _weight_leaf_spec(leaf, layout: str, axis: str, n: int):
    """PartitionSpec for one serving-weight leaf under a named layout.

    training: every leaf sharded on its largest divisible dim (the
        ZeRO-ish weight-gathered placement the train step uses) — weights
        are all-gathered into each step.
    weight_stationary: 2-D matmul weights sharded on the CONTRACTING dim
        (dim 0), everything else replicated — each chip keeps its weight
        shard resident and the per-step wire carries only the (small at
        serving batch sizes) partial-sum reductions of activations.
    replicated: P() everywhere — the zero-wire single-chip counterfactual.
    """
    P = jax.sharding.PartitionSpec
    shape = getattr(leaf, "shape", None)
    if shape is None or len(shape) == 0 or n <= 1:
        return P()
    if layout == "replicated":
        return P()
    if layout == "weight_stationary":
        if len(shape) == 2 and shape[0] % n == 0:
            return P(axis, None)
        return P()
    if layout == "training":
        i = _largest_divisible_dim(shape, n)
        if i < 0:
            return P()
        spec = [None] * len(shape)
        spec[i] = axis
        return P(*spec)
    raise ValueError(f"unknown serving layout {layout!r}; "
                     f"choose from {SERVING_LAYOUTS}")


def _batch_leaf_spec(leaf, layout: str, axis: str, n: int):
    """Batch operands: training shards the leading dim (data parallel);
    the serving layouts keep the batch replicated (it is small — the whole
    point of weight-stationary placement)."""
    P = jax.sharding.PartitionSpec
    shape = getattr(leaf, "shape", None)
    if (layout == "training" and shape and len(shape) >= 1
            and n > 1 and shape[0] % n == 0):
        return P(axis)
    return P()


def serving_shardings(tree, mesh, layout: str):
    """NamedSharding pytree for a serving-weights tree under ``layout``.

    Works on a raw params tree or a ``ServingWeights`` pytree — quantized
    ``{q, scale, n}`` dicts are plain subtrees, so q/scale each get a spec
    from their own shape (scale rides along replicated or sharded on its
    blocks dim as divisibility allows)."""
    axis = mesh.axis_names[0]
    n = mesh.devices.size
    return jax.tree.map(
        lambda leaf: jax.sharding.NamedSharding(
            mesh, _weight_leaf_spec(leaf, layout, axis, n)),
        tree)


def batch_shardings(tree, mesh, layout: str):
    """NamedSharding pytree for non-weight step operands (episodes, keys)."""
    axis = mesh.axis_names[0]
    n = mesh.devices.size
    return jax.tree.map(
        lambda leaf: jax.sharding.NamedSharding(
            mesh, _batch_leaf_spec(leaf, layout, axis, n)),
        tree)


def score_serving_layout(fn, weights, args, mesh, layout: str) -> Dict:
    """Compile ``fn(weights, *args)`` under ``layout`` and score it with
    the three-term roofline over the ACTUAL post-SPMD HLO."""
    from repro.roofline.hlo import analyze, collectives_report
    in_sh = (serving_shardings(weights, mesh, layout),) + tuple(
        batch_shardings(a, mesh, layout) for a in args)
    compiled = jax.jit(fn, in_shardings=in_sh).lower(weights, *args).compile()
    text = compiled.as_text()
    rep = collectives_report(text)
    hlo = analyze(text)
    terms = dict(compute=hlo["dot_flops"] / PEAK_FLOPS_BF16,
                 memory=hlo["bytes_accessed"] / HBM_BW,
                 collective=rep["total_wire_bytes"] / ICI_BW_PER_LINK)
    return dict(
        layout=layout,
        wire_bytes=rep["total_wire_bytes"],
        collective_count=rep["count"],
        dot_flops=hlo["dot_flops"],
        bytes_accessed=hlo["bytes_accessed"],
        t_compute=terms["compute"], t_memory=terms["memory"],
        t_collective=terms["collective"],
        bottleneck=max(terms, key=terms.get),
        score=max(terms.values()),
    )


def choose_serving_layout(fn, weights, args, mesh,
                          layouts=SERVING_LAYOUTS) -> Dict:
    """Pick the serving weight layout by compiling every candidate.

    fn: the jittable step, called as ``fn(weights, *args)`` (e.g. the
        engine's predict dispatch over a representative serving batch).
    Returns ``{"choice": name, "rows": {layout: score_row}}`` where each
    row is :func:`score_serving_layout`'s output.  The winner minimizes
    the max roofline term (the compiled program's time bound); ties break
    toward the earlier entry in ``layouts``.  ``replicated`` is scored as
    the zero-wire counterfactual but the wire GUARD the tests assert is
    weight_stationary-vs-training: the chosen weight-stationary layout
    must move strictly fewer wire bytes per step than the training layout
    at serving batch sizes."""
    rows = {lo: score_serving_layout(fn, weights, args, mesh, lo)
            for lo in layouts}
    choice = min(layouts, key=lambda lo: rows[lo]["score"])
    return dict(choice=choice, rows=rows)


def choose_replica_serving_layout(fn, weights, args, replica_meshes,
                                  layouts=SERVING_LAYOUTS) -> Dict:
    """Layout choice for a multi-replica deployment: score on ONE replica
    group and apply the winner to all of them.

    The replica groups from ``make_replica_mesh`` are congruent — same
    device count, same axis, same (replicated) weights — so the compiled
    program, and therefore the roofline score, is identical on every
    group; scoring ``replica_meshes[0]`` prices them all.  The scoring is
    correctly SUBGROUP-scoped by construction: the candidate is compiled
    on the group's own mesh, so every collective the score charges for is
    intra-group wire — exactly what the deployment pays per replica, with
    zero inter-group terms (there is no axis spanning two groups to
    communicate over).  Returns :func:`choose_serving_layout`'s dict plus
    ``per_replica_wire_bytes`` (== the winning row's wire bytes: the
    per-step wire EACH replica pays, not a deployment total)."""
    if not replica_meshes:
        raise ValueError("replica_meshes must be non-empty")
    out = choose_serving_layout(fn, weights, args, replica_meshes[0],
                                layouts=layouts)
    out["per_replica_wire_bytes"] = out["rows"][out["choice"]]["wire_bytes"]
    return out
