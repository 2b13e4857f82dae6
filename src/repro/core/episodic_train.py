"""Algorithm 1, complete: the per-task meta-training step with the
paper's QUERY-batch loop (lines 1-10) as a scan-accumulated gradient —
query microbatching bounds the query-side activation memory exactly as
the paper's for-loop does, while LITE (inside meta_loss) bounds the
support side.  One optimizer step per task (line 11); the N/H weighting
is already baked into the LITE combinator's backward.

    step = make_meta_train_step(learner, lite_spec, query_batch=8)
    params, opt_state, metrics = step(params, opt_state, task, key)

Beyond the paper: the TASK-BATCHED engine (``make_batched_meta_train_step``)
amortizes the per-step cost over many tasks — ``vmap`` over the task axis of
a :class:`repro.core.episodic.TaskBatch`, per-task PRNG keys (each task draws
its own H subset), task-mean gradients, ONE optimizer step — and optionally
shards the task axis across devices via ``shard_map`` (pure data parallelism:
params replicated, gradients ``pmean``-ed over the mesh axis).

    batch = collate_task_batch(tasks)            # repro.data.episodic
    step = make_batched_meta_train_step(learner, lite_spec)
    params, opt_state, metrics = step(params, opt_state, batch, key)
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.episodic import Task, TaskBatch, query_batches
from repro.core.lite import LiteSpec
from repro.core.meta_learners import MetaLearner
from repro.optim import AdamWConfig, adamw_update, clip_by_global_norm

PyTree = Any

# names the finite check and the update in the compiled step's op names,
# which profiler traces carry (``bench/layers.py`` reads them)
OPTIMIZER_SCOPE = "train.optimizer"


def make_meta_train_step(learner: MetaLearner, lite: LiteSpec,
                         query_batch: int = 0,
                         adamw: AdamWConfig = AdamWConfig(weight_decay=0.0),
                         lr: float = 1e-3,
                         max_grad_norm: float = 10.0) -> Callable:
    """query_batch=0 -> single query pass; >0 -> Algorithm 1's M_b loop
    via lax.scan gradient accumulation (repro.core.episodic.query_batches
    pads the tail batch and weights it out, so any query count works)."""

    def loss_for(params, task: Task, key):
        return learner.meta_loss(params, task, key, lite)[0]

    def grads_single(params, task: Task, key):
        return jax.value_and_grad(loss_for)(params, task, key)

    def grads_microbatched(params, task: Task, key):
        # query_batches pads the tail batch and emits per-example weights
        # (folding in any collator query_mask), so M need not divide evenly
        qx, qy, qm = query_batches(task, query_batch)

        def body(acc, xs):
            qxb, qyb, qmb = xs
            sub = Task(support_x=task.support_x, support_y=task.support_y,
                       query_x=qxb, query_y=qyb, way=task.way,
                       support_mask=task.support_mask, query_mask=qmb)
            # same key => same H subset across query batches (Alg. 1
            # draws H once per task, line 4 outside the inner use)
            l, g = jax.value_and_grad(loss_for)(params, sub, key)
            # weight each microbatch by its REAL query count so padded
            # tails don't dilute the task loss (uniform 1/nb when unmasked)
            wb = jnp.sum(qmb)
            loss_acc, g_acc = acc
            return (loss_acc + l * wb,
                    jax.tree.map(lambda a, b: a + b * wb, g_acc, g)), None

        zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, params))
        (loss_sum, grad_sum), _ = jax.lax.scan(body, zero, (qx, qy, qm))
        w_tot = jnp.maximum(jnp.sum(qm), 1.0)
        return loss_sum / w_tot, jax.tree.map(lambda a: a / w_tot, grad_sum)

    def step(params: PyTree, opt_state: Dict, task: Task, key
             ) -> Tuple[PyTree, Dict, Dict]:
        if query_batch > 0:
            loss, grads = grads_microbatched(params, task, key)
        else:
            loss, grads = grads_single(params, task, key)
        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        params, opt_state = adamw_update(params, grads, opt_state, lr, adamw)
        return params, opt_state, dict(loss=loss, grad_norm=gnorm)

    return step


# ---------------------------------------------------------------------------
# Task-batched engine: many tasks -> one optimizer step, optionally
# data-parallel over the task axis.
# ---------------------------------------------------------------------------


def task_key(key: jax.Array, task_index) -> jax.Array:
    """Per-task PRNG key convention: fold the global task index into the
    step key.  Shared by the batched engine, its sharded path, and the
    looped reference so all three draw identical H subsets for task i."""
    return jax.random.fold_in(key, task_index)


def make_batched_meta_grads(learner: MetaLearner, lite: LiteSpec) -> Callable:
    """(params, batch: TaskBatch, key) -> (loss, accuracy, grads).

    ``vmap``s ``learner.meta_loss`` over the task axis with per-task keys
    (``task_key(key, i)`` — each task draws an independent H subset) and
    returns task-MEAN loss/accuracy/gradients.  The gradient is taken of
    the task-mean loss directly (one shared-parameter backward, peak
    gradient memory O(P)) rather than stacking T per-task gradient pytrees
    and averaging them.  An optional ``ids`` argument overrides the global
    task indices, which the data-parallel path uses so shard-local slots
    keep their global key.
    """

    def grads_fn(params: PyTree, batch: TaskBatch, key,
                 ids: Optional[jnp.ndarray] = None):
        if ids is None:
            ids = jnp.arange(batch.num_tasks)

        def batch_loss(p):
            def one_task(sx, sy, sm, qx, qy, qm, i):
                task = Task(support_x=sx, support_y=sy, query_x=qx,
                            query_y=qy, way=batch.way, support_mask=sm,
                            query_mask=qm)
                loss, aux = learner.meta_loss(p, task, task_key(key, i), lite)
                return loss, aux["accuracy"]

            losses, accs = jax.vmap(one_task)(
                batch.support_x, batch.support_y, batch.support_mask,
                batch.query_x, batch.query_y, batch.query_mask, ids)
            return jnp.mean(losses), jnp.mean(accs)

        (loss, acc), grads = jax.value_and_grad(batch_loss, has_aux=True)(
            params)
        return loss, acc, grads

    return grads_fn


def _tree_all_finite(tree: PyTree) -> jnp.ndarray:
    """Scalar bool: every element of every leaf is finite.  One fused
    check inside the step's jit — the guard the fault-tolerant loop relies
    on to turn a NaN/inf gradient into a skipped step instead of silent
    parameter corruption."""
    with jax.named_scope(OPTIMIZER_SCOPE):
        ok = jnp.asarray(True)
        for leaf in jax.tree.leaves(tree):
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(leaf)))
        return ok


def init_ef_state(params: PyTree, dcn_shards: int) -> PyTree:
    """Zero error-feedback residuals for ``grad_reduce='compressed'``: one
    fp32 residual copy per DCN shard (leading axis ``dcn_shards``, sharded
    ``P('dcn')`` across the outer mesh axis).  Lives in ``opt_state['ef']``
    so checkpoints carry it and restarts stay exact."""
    return jax.tree.map(
        lambda p: jnp.zeros((dcn_shards,) + p.shape, jnp.float32), params)


def _accumulated_grads(grads_fn: Callable, params: PyTree, batch: TaskBatch,
                       key, ids, accum: int):
    """Mean loss/accuracy/grads over ``batch``, computed as ``accum``
    sequential task chunks (lax.scan) so peak activation memory is that of
    T/accum tasks.  Per-task keys ride on the GLOBAL ids, so the result is
    chunking-invariant; ``accum=1`` calls ``grads_fn`` directly and is
    bit-identical to the unaccumulated step."""
    if accum <= 1:
        return grads_fn(params, batch, key, ids)
    t = batch.num_tasks
    chunks = jax.tree.map(
        lambda a: a.reshape((accum, t // accum) + a.shape[1:]), batch)
    ids_c = ids.reshape(accum, t // accum)

    def body(carry, xs):
        chunk, cid = xs
        l, a, g = grads_fn(params, chunk, key, cid)
        cl, ca, cg = carry
        return (cl + l, ca + a, jax.tree.map(jnp.add, cg, g)), None

    zero = (jnp.zeros(()), jnp.zeros(()),
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params))
    (loss, acc, grads), _ = jax.lax.scan(body, zero, (chunks, ids_c))
    scale = 1.0 / accum       # equal chunk sizes: mean of chunk-means
    return loss * scale, acc * scale, jax.tree.map(lambda g: g * scale, grads)


def make_batched_meta_train_step(learner: MetaLearner, lite: LiteSpec,
                                 adamw: AdamWConfig = AdamWConfig(weight_decay=0.0),
                                 lr: float = 1e-3,
                                 max_grad_norm: float = 10.0,
                                 schedule: Optional[Callable] = None,
                                 mesh=None, dp_axis: str = "data",
                                 dcn_axis: str = "dcn",
                                 grad_reduce: str = "pmean",
                                 accum_steps: int = 1,
                                 skip_nonfinite: bool = True) -> Callable:
    """Task-batched meta-training step: T tasks -> ONE AdamW step.

        step(params, opt_state, batch: TaskBatch, key)
            -> (params, opt_state, metrics)

    Without a mesh the whole batch is vmapped on the local device.  With
    ``mesh`` the task axis is sharded via ``shard_map``:

    * 1-D mesh (``dp_axis`` only, today's single-host path): params/opt
      state replicated, each shard differentiates its T/S tasks, gradients
      ``pmean`` across the axis, every shard applies the identical update —
      bit-comparable to the single-device batched step.
    * two-level mesh (``make_two_level_dp_mesh``: outer ``dcn_axis`` x
      inner ``dp_axis``): the task axis shards over BOTH axes
      (``P((dcn, data))``); gradients first ``pmean`` over the fast ICI
      ``data`` axis, then reduce across hosts over ``dcn`` — exactly
      (``grad_reduce='pmean'``) or int8 error-feedback compressed
      (``'compressed'``, ``repro.optim.compress.compressed_psum``; the
      per-host residual lives in ``opt_state['ef']``, see
      :func:`init_ef_state`).  At ``dcn`` size 1 the pmean path emits no
      dcn reduction at all, so the program and its results are
      bit-identical to the 1-D path.

    ``accum_steps > 1`` scans that many sequential task chunks per shard
    before the single cross-mesh reduction (gradient accumulation), so
    ``tasks_per_step`` can exceed per-host memory; collective count per
    optimizer step is unchanged.

    ``schedule`` (step -> lr, e.g. from ``repro.optim.schedules``)
    overrides the constant ``lr``; the step index is the optimizer-state
    update count, so schedules survive checkpoint resume for free.
    Metrics report the lr actually applied.

    ``skip_nonfinite`` (default on) arms the non-finite-update guard: if
    any gradient element is NaN/inf the optimizer update is suppressed by
    a ``where``-select — params and opt state (count included) come out
    BIT-IDENTICAL to the inputs — and ``metrics['nonfinite']`` is 1.0.
    The select keeps the step a single fixed computation graph (no
    recompile, donation-safe); the fault-tolerant loop turns runs of
    skipped steps into a divergence rollback.  On a two-level mesh the
    verdict is computed on the fp32 gradients BEFORE the (possibly int8
    compressed) DCN reduction and ``pmin``-reduced across hosts, so every
    shard takes the same branch and quantized NaN garbage can never pass
    the check; the compressed path's error-feedback residual is frozen on
    skipped steps by the same select.
    """
    grads_fn = make_batched_meta_grads(learner, lite)

    @jax.named_scope(OPTIMIZER_SCOPE)
    def apply_update(params, opt_state, loss, acc, grads, ok=None):
        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        lr_t = lr if schedule is None else schedule(opt_state["count"])
        new_params, new_opt = adamw_update(params, grads, opt_state, lr_t,
                                           adamw)
        metrics = dict(loss=loss, accuracy=acc, grad_norm=gnorm,
                       lr=jnp.asarray(lr_t, jnp.float32))
        if ok is not None:
            pick = lambda n, o: jnp.where(ok, n, o)  # noqa: E731
            new_params = jax.tree.map(pick, new_params, params)
            new_opt = jax.tree.map(pick, new_opt, opt_state)
            metrics["nonfinite"] = (~ok).astype(jnp.float32)
        return new_params, new_opt, metrics

    if grad_reduce not in ("pmean", "compressed"):
        raise ValueError(f"grad_reduce={grad_reduce!r} (want 'pmean' or "
                         f"'compressed')")
    sizes = {} if mesh is None else dict(mesh.shape)
    if mesh is not None and dp_axis not in sizes:
        raise ValueError(f"mesh axes {tuple(sizes)} lack "
                         f"dp_axis={dp_axis!r}")
    dp = sizes.get(dp_axis, 1)
    two_level = dcn_axis in sizes
    dcn = sizes.get(dcn_axis, 1)
    if grad_reduce == "compressed" and not two_level:
        raise ValueError(
            "grad_reduce='compressed' compresses the cross-host DCN "
            "reduction: it needs a two-level mesh "
            "(repro.launch.mesh.make_two_level_dp_mesh) with a "
            f"{dcn_axis!r} axis")
    shards = dp * dcn
    compressed = grad_reduce == "compressed"
    reduce_dcn = two_level and (dcn > 1 or compressed)

    if mesh is None:
        def step(params: PyTree, opt_state: Dict, batch: TaskBatch, key
                 ) -> Tuple[PyTree, Dict, Dict]:
            if batch.num_tasks % accum_steps:
                raise ValueError(f"tasks_per_step={batch.num_tasks} not "
                                 f"divisible by accum_steps={accum_steps}")
            ids = jnp.arange(batch.num_tasks)
            loss, acc, grads = _accumulated_grads(grads_fn, params, batch,
                                                  key, ids, accum_steps)
            ok = _tree_all_finite(grads) if skip_nonfinite else None
            return apply_update(params, opt_state, loss, acc, grads, ok)

        return step

    from repro.optim.compress import compressed_psum
    from repro.sharding import shard_map

    task_spec = P((dcn_axis, dp_axis)) if two_level else P(dp_axis)
    in_specs = [P(), P(), task_spec, P(), task_spec]
    out_specs = [P(), P(), P()]
    if compressed:
        in_specs.append(P(dcn_axis))       # opt_state['ef'], leading axis
        out_specs.append(P(dcn_axis))

    def sharded_body(params, opt_state, local_batch, key_data, local_ids,
                     *maybe_ef):
        key = jax.random.wrap_key_data(key_data)
        loss, acc, grads = _accumulated_grads(grads_fn, params, local_batch,
                                              key, local_ids, accum_steps)
        loss = jax.lax.pmean(loss, dp_axis)
        acc = jax.lax.pmean(acc, dp_axis)
        grads = jax.lax.pmean(grads, dp_axis)
        # finite verdict on the exact fp32 grads BEFORE any dcn compression
        # (int8-quantized NaN can decode to finite garbage); pmin over dcn
        # replicates the decision so every host skips or applies together.
        ok = _tree_all_finite(grads) if skip_nonfinite else None
        # a size-1 dcn axis reduces nothing; emitting its singleton
        # all-reduces anyway changes XLA's fusion of the norm/update around
        # them and costs the bit identity with the 1-D path
        if reduce_dcn:
            loss = jax.lax.pmean(loss, dcn_axis)
            acc = jax.lax.pmean(acc, dcn_axis)
            if ok is not None:
                ok = jax.lax.pmin(ok.astype(jnp.int32), dcn_axis).astype(bool)
            if compressed:
                ef = jax.tree.map(lambda e: e[0], maybe_ef[0])
                summed, new_ef = compressed_psum(grads, dcn_axis, ef)
                grads = jax.tree.map(lambda g: g / dcn, summed)
                if ok is not None:
                    new_ef = jax.tree.map(
                        lambda n, o: jnp.where(ok, n, o), new_ef, ef)
                new_ef = jax.tree.map(lambda e: e[None], new_ef)
            else:
                grads = jax.lax.pmean(grads, dcn_axis)
        out = apply_update(params, opt_state, loss, acc, grads, ok)
        return out + ((new_ef,) if compressed else ())

    def step(params: PyTree, opt_state: Dict, batch: TaskBatch, key
             ) -> Tuple[PyTree, Dict, Dict]:
        t = batch.num_tasks
        if t % (shards * accum_steps):
            raise ValueError(
                f"tasks_per_step={t} not divisible by dp_shards*dcn_shards*"
                f"accum_steps = {dp}*{dcn}*{accum_steps}")
        ids = jnp.arange(t)
        # raw uint32 key data crosses the shard_map boundary (extended
        # key dtypes and partitioning don't mix on all jax versions)
        key_data = jax.random.key_data(key)
        sharded = functools.partial(
            shard_map, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=tuple(out_specs), check_vma=False)(sharded_body)
        if compressed:
            if "ef" not in opt_state:
                raise ValueError("grad_reduce='compressed' needs "
                                 "opt_state['ef'] — initialize it with "
                                 "init_ef_state(params, dcn_shards)")
            opt_in = {k: v for k, v in opt_state.items() if k != "ef"}
            params, opt, metrics, ef = sharded(params, opt_in, batch,
                                               key_data, ids,
                                               opt_state["ef"])
            return params, dict(opt, ef=ef), metrics
        return sharded(params, opt_state, batch, key_data, ids)

    return step


def jit_task_step(step: Callable, donate: bool = True):
    """jit a ``(params, opt_state, batch, key)`` task step, donating the
    params and optimizer-state buffers (arguments 0 and 1) so AdamW
    updates in place instead of allocating fresh copies each step.  The
    caller must thread the returned state — the donated inputs are dead
    after the call (on backends implementing donation, reuse raises)."""
    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def run_looped_baseline(learner: MetaLearner, lite: LiteSpec,
                        params: PyTree, opt_state: Dict, tasks, key,
                        adamw: AdamWConfig = AdamWConfig(weight_decay=0.0),
                        lr: float = 1e-3, max_grad_norm: float = 10.0,
                        donate: bool = False):
    """Paper Algorithm 1 verbatim: one optimizer step PER task, in a Python
    loop.  The throughput baseline ``benchmarks/task_throughput.py`` compares
    the batched engine against; uses the same per-task key convention.
    ``donate=True`` updates params/opt state in place — the caller's input
    buffers are consumed by the first step."""
    step = jit_task_step(make_meta_train_step(learner, lite, adamw=adamw,
                                              lr=lr,
                                              max_grad_norm=max_grad_norm),
                         donate=donate)
    metrics = None
    for i, task in enumerate(tasks):
        params, opt_state, metrics = step(params, opt_state, task,
                                          task_key(key, i))
    return params, opt_state, metrics
