"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-72b \
        --steps 100000 --ckpt-dir gs://.../qwen2  [--pods 2]

On a real TPU deployment each host runs this same script (jax.distributed
initializes from the TPU environment); on this container it runs the
reduced smoke config on the local device so the full control path —
sharded state init, fault-tolerant loop, checkpoint/auto-resume,
straggler monitoring — is exercised end to end.

``--episodic`` switches to the paper's workload: task-batched LITE
meta-training (repro.core.episodic_train) on the synthetic episodic image
stream, with ``--tasks-per-step`` tasks per optimizer step and the task
axis optionally sharded over ``--dp-shards`` devices within a host — and,
beyond one host, over a two-level (dcn, data) mesh with ``--dcn-shards``
outer host-level shards, ``--grad-reduce pmean|compressed`` cross-host
gradient reduction (compressed = int8 error feedback, residual
checkpointed in the optimizer state), and ``--accum-steps`` sequential
gradient-accumulation chunks so tasks_per_step can exceed per-host
memory:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
    PYTHONPATH=src python -m repro.launch.train --episodic --steps 20 \\
        --tasks-per-step 8 --dp-shards 2 --dcn-shards 2 \\
        --grad-reduce compressed --accum-steps 2

The throughput engine knobs: ``--prefetch N`` (background batch lookahead; default 2),
``--no-donate`` (disable in-place params/opt-state updates),
``--data-source host`` (host-side numpy collation the prefetcher can
overlap with device compute), ``--schedule cosine|wsd`` (per-step lr),
``--lite-dtype bfloat16`` (mixed-precision no-grad complement), and
``--kernel-backend ref|pallas|auto|naive`` (the
repro.kernels.dispatch backend for the fused class-statistics /
Mahalanobis aggregation kernels):

    PYTHONPATH=src python -m repro.launch.train --episodic \
        --steps 100 --tasks-per-step 8 --dp-shards 1 \
        --data-source host --prefetch 4 --schedule cosine

The backbone and task shape (``--widths``, ``--feature-dim``, ``--way``,
``--shot``, ``--query-per-class``, ``--image-size``, ``--lite-h``,
``--lite-chunk``) default to a toy size; ``repro.launch.episodic`` gives
the flags for the paper's full-width 224x224 regime.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import tempfile
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs.base import SHAPES_BY_NAME, MetaTrainConfig
from repro.common.compile_cache import enable_compile_cache
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.data.tokens import TokenPipeline, TokenPipelineConfig
from repro.launch.mesh import (make_dp_mesh, make_production_mesh,
                               make_test_mesh, make_two_level_dp_mesh)
from repro.optim.schedules import schedule_for
from repro.sharding import rules
from repro.sharding.ctx import P
from repro.faults import PreemptionSignal
from repro.train.checkpoint import CheckpointManager
from repro.train.loop import PreemptedError, TrainResult, train
from repro.train.step import (adamw_for, make_episodic_init_state,
                              make_episodic_train_step, make_init_state,
                              make_train_step)

# EX_TEMPFAIL: the canonical "retry me" exit — a preempted run flushed a
# checkpoint and rerunning the same command resumes bit-exactly.
EXIT_PREEMPTED = 75


def _finish_preempted(e: PreemptedError) -> None:
    print(f"preempted: {e} — rerun to resume", flush=True)
    sys.exit(EXIT_PREEMPTED)


def _fault_summary(result) -> str:
    return (f"nonfinite_skips={len(result.nonfinite_steps)} "
            f"rollbacks={result.rollbacks} "
            f"data_retries={result.data_retries} "
            f"stragglers={result.straggler_steps}")


@dataclasses.dataclass
class EpisodicJob:
    """Everything ``run_episodic`` hands the fault-tolerant loop, built
    from the launcher's arguments (``chip_smoke.py`` drives the same)."""

    meta: MetaTrainConfig
    state: Dict
    state_abs: Dict
    step: Callable
    batch_at: Callable[[int], Dict]
    batch_put: Optional[Callable]
    ckpt_dir: str


def build_episodic(args) -> EpisodicJob:
    from repro.core.lite import LiteSpec
    from repro.data.episodic import host_task_batch_at, task_batch_at
    from repro.launch.episodic import build_learner, shape_from_args
    from repro.optim import AdamWConfig

    shape = shape_from_args(args)
    meta = MetaTrainConfig(tasks_per_step=args.tasks_per_step,
                           dp_shards=args.dp_shards,
                           dcn_shards=args.dcn_shards,
                           grad_reduce=args.grad_reduce,
                           accum_steps=args.accum_steps, lr=args.peak_lr,
                           schedule=args.schedule,
                           warmup_steps=max(args.steps // 50, 1),
                           total_steps=args.steps,
                           lite_h=shape.lite_h, lite_chunk=shape.lite_chunk,
                           lite_dtype=args.lite_dtype,
                           prefetch=args.prefetch,
                           donate=not args.no_donate,
                           kernel_backend=args.kernel_backend)
    if meta.dcn_shards > 1 or meta.grad_reduce == "compressed":
        mesh = make_two_level_dp_mesh(meta.dcn_shards, meta.dp_shards)
    elif meta.dp_shards > 1:
        mesh = make_dp_mesh(meta.dp_shards)
    else:
        mesh = None
    print(f"episodic meta-training: learner={args.learner} {shape} "
          f"tasks_per_step={meta.tasks_per_step} dp_shards={meta.dp_shards} "
          f"dcn_shards={meta.dcn_shards} grad_reduce={meta.grad_reduce} "
          f"accum_steps={meta.accum_steps} "
          f"schedule={meta.schedule or 'constant'} "
          f"prefetch={meta.prefetch} donate={meta.donate} "
          f"lite_dtype={meta.lite_dtype or 'float32'} "
          f"kernel_backend={meta.kernel_backend} "
          f"devices={len(jax.devices())}")

    learner = build_learner(args.learner, shape)
    lite = LiteSpec(h=meta.lite_h, chunk_size=meta.lite_chunk,
                    compute_dtype=meta.lite_dtype)
    adamw = AdamWConfig(weight_decay=0.0)

    init = make_episodic_init_state(learner, adamw, meta_cfg=meta)
    step = make_episodic_train_step(learner, lite, meta, adamw, mesh=mesh)
    state = init(jax.random.key(0))
    state_abs = jax.eval_shape(init, jax.random.key(0))

    # land prefetched batches directly in the mesh layout the sharded
    # step consumes (task axis over (dcn, data)); key stays replicated
    batch_put = None
    if mesh is not None:
        task_sharding = NamedSharding(mesh, P(tuple(mesh.axis_names)))

        def batch_put(b):
            # the PRNG key stays host-side (extended key dtypes and
            # explicit shardings don't mix on all jax versions)
            return dict(
                tasks=jax.tree.map(
                    lambda a: jax.device_put(a, task_sharding), b["tasks"]),
                key=b["key"])

    step_key = jax.random.key(23)
    if args.data_source == "host":
        # host-side collation+augmentation — the path the prefetcher can
        # genuinely overlap with device compute
        hcfg = shape.host_config()

        def batch_at(s):
            return dict(tasks=host_task_batch_at(17, hcfg,
                                                 meta.tasks_per_step, s),
                        key=jax.random.fold_in(step_key, s))
    else:
        tcfg = shape.image_config()
        data_key = jax.random.key(17)

        def batch_at(s):
            return dict(tasks=task_batch_at(data_key, tcfg,
                                            meta.tasks_per_step, s),
                        key=jax.random.fold_in(step_key, s))

    # distinct default dir per workload AND per state template: learner,
    # plus grad_reduce/dcn_shards when compressed (opt['ef'] adds a
    # (dcn_shards, ...) leaf) — restoring a checkpoint into a different
    # template is a shape mismatch / missing-leaf KeyError
    suffix = (f"_ef{meta.dcn_shards}"
              if meta.grad_reduce == "compressed" else "")
    ckpt_dir = args.ckpt_dir or os.path.join(
        tempfile.gettempdir(),
        f"repro_train_ckpt_episodic_{args.learner}{suffix}")
    return EpisodicJob(meta=meta, state=state, state_abs=state_abs,
                       step=step, batch_at=batch_at, batch_put=batch_put,
                       ckpt_dir=ckpt_dir)


def run_episodic(args, step_hook: Optional[Callable[[int], None]] = None
                 ) -> Optional[TrainResult]:
    """Build the job from ``args`` and train it.  ``step_hook(step)`` runs
    before every step (the loop's ``preemption_hook``).  Exits non-zero
    when every step of the run was skipped as non-finite."""
    job = build_episodic(args)
    meta = job.meta
    ckpt = CheckpointManager(job.ckpt_dir, keep=3)
    preempt = PreemptionSignal().install()
    try:
        result = train(job.state, job.step, job.batch_at, args.steps,
                       ckpt=ckpt, ckpt_every=args.ckpt_every,
                       state_template=job.state_abs,
                       log_every=max(args.steps // 10, 1),
                       prefetch=meta.prefetch, donate=meta.donate,
                       batch_put=job.batch_put, preempt=preempt,
                       preemption_hook=step_hook,
                       max_nonfinite=args.max_nonfinite_skips,
                       data_retries=args.data_retries)
    except PreemptedError as e:
        _finish_preempted(e)
    if not result.metrics_history:
        print(f"nothing to do: checkpoint already at step {result.step} "
              f"(resumed_from={result.resumed_from})")
        return result
    print(f"done at step {result.step}; resumed_from={result.resumed_from}; "
          f"loss {result.metrics_history[0]['loss']:.4f} -> "
          f"{result.metrics_history[-1]['loss']:.4f}; "
          f"accuracy {result.metrics_history[-1]['accuracy']:.3f}; "
          f"throughput {result.throughput(meta.tasks_per_step):.1f} tasks/s; "
          f"{_fault_summary(result)}")
    if len(result.nonfinite_steps) == len(result.metrics_history):
        sys.exit(f"every one of the {len(result.metrics_history)} steps was "
                 f"skipped as non-finite; no update was applied")
    return result


def build_parser() -> argparse.ArgumentParser:
    from repro.launch.episodic import EpisodicShape, add_shape_args

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="minitron-4b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--schedule", choices=["cosine", "wsd"], default=None,
                    help="LR schedule (LM default cosine; --episodic "
                         "default constant --peak-lr)")
    ap.add_argument("--peak-lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None,
                    help="defaults to repro_train_ckpt (LM) or "
                         "repro_train_ckpt_episodic_<learner> (--episodic) "
                         "under the temporary directory")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--full", action="store_true",
                    help="full assigned config (pod-scale deployment)")
    ap.add_argument("--episodic", action="store_true",
                    help="task-batched LITE meta-training workload")
    ap.add_argument("--learner", default="protonets",
                    choices=["protonets", "cnaps", "simple_cnaps"])
    ap.add_argument("--tasks-per-step", type=int, default=8)
    ap.add_argument("--dp-shards", type=int, default=1,
                    help="inner ICI data-parallel shards over the task "
                         "axis (shard_map 'data' axis)")
    ap.add_argument("--dcn-shards", type=int, default=1,
                    help="outer host-level DCN shards: tasks split across "
                         "hosts on a two-level (dcn, data) mesh and "
                         "gradients reduce across hosts per --grad-reduce "
                         "(emulate hosts on CPU with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count)")
    ap.add_argument("--grad-reduce", choices=["pmean", "compressed"],
                    default="pmean",
                    help="cross-DCN gradient reduction: exact pmean, or "
                         "int8 error-feedback compression "
                         "(repro.optim.compress; residual checkpointed in "
                         "opt_state['ef'])")
    ap.add_argument("--accum-steps", type=int, default=1,
                    help="sequential gradient-accumulation chunks per "
                         "optimizer step, so --tasks-per-step can exceed "
                         "per-host memory")
    add_shape_args(ap, EpisodicShape())
    ap.add_argument("--prefetch", type=int, default=2,
                    help="background batch lookahead depth (0 = sync loop)")
    ap.add_argument("--data-source", choices=["device", "host"],
                    default="device",
                    help="episodic task stream: jitted on-device sampler, "
                         "or host-side numpy collation+augmentation (the "
                         "loader-realistic path prefetch can overlap)")
    ap.add_argument("--no-donate", action="store_true",
                    help="disable params/opt-state buffer donation")
    ap.add_argument("--lite-dtype", choices=["bfloat16", "float16"],
                    default=None,
                    help="LITE no-grad complement compute dtype "
                         "(default fp32)")
    ap.add_argument("--max-nonfinite-skips", type=int, default=8,
                    help="consecutive NaN/inf-skipped steps tolerated "
                         "before divergence rollback to the last "
                         "checkpoint (then DivergenceError)")
    ap.add_argument("--data-retries", type=int, default=2,
                    help="bounded exponential-backoff retries for a "
                         "failing batch source before the error "
                         "propagates")
    ap.add_argument("--kernel-backend",
                    choices=["ref", "pallas", "auto", "naive"],
                    default="ref",
                    help="episodic aggregation-kernel backend "
                         "(repro.kernels.dispatch): ref = fused jnp "
                         "(no (B,F,F) outer intermediate), pallas = "
                         "Pallas kernels (interpret off-TPU), auto = "
                         "pallas on TPU else ref, naive = materializing "
                         "legacy composite (bit-exact pre-dispatch "
                         "oracle)")
    return ap


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    enable_compile_cache()

    if args.episodic:
        run_episodic(args)
        return

    n_dev = len(jax.devices())
    if args.full and n_dev >= 256:
        mesh = make_production_mesh(multi_pod=args.pods > 1, pods=args.pods)
        cfg = get_config(args.arch)
    else:
        mesh = make_test_mesh()
        cfg = get_smoke_config(args.arch)
        if args.full:
            print(f"[warn] --full needs >=256 devices (have {n_dev}); "
                  f"running the smoke config on the test mesh")
    print(f"arch={cfg.name} mesh={dict(mesh.shape)} devices={n_dev}")

    init = make_init_state(cfg, adamw_for(cfg))
    sched = schedule_for(args.schedule or "cosine", args.peak_lr,
                         max(args.steps // 50, 1), args.steps)
    step = make_train_step(cfg, adamw_for(cfg), schedule=sched)

    # sharded state init
    state_abs = jax.eval_shape(init, jax.random.key(0))
    sspecs = rules.sanitize(
        dict(params=rules.param_specs(state_abs["params"]),
             opt=rules.opt_state_specs(state_abs["opt"])),
        state_abs, mesh)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                             is_leaf=lambda x: isinstance(x, P))
    with mesh:
        state = jax.jit(init, out_shardings=shardings)(jax.random.key(0))

        pipe = TokenPipeline(TokenPipelineConfig(
            vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch))

        def batch_at(s):
            return {k: jnp.asarray(v) for k, v in pipe.batch_at(s).items()}

        ckpt = CheckpointManager(
            args.ckpt_dir or os.path.join(tempfile.gettempdir(),
                                          "repro_train_ckpt"), keep=3)
        preempt = PreemptionSignal().install()
        try:
            result = train(state, step, batch_at, args.steps,
                           ckpt=ckpt, ckpt_every=args.ckpt_every,
                           state_template=state_abs, log_every=25,
                           preempt=preempt,
                           max_nonfinite=args.max_nonfinite_skips,
                           data_retries=args.data_retries)
        except PreemptedError as e:
            _finish_preempted(e)
    if not result.metrics_history:
        print(f"nothing to do: checkpoint already at step {result.step} "
              f"(resumed_from={result.resumed_from})")
        return
    print(f"done at step {result.step}; "
          f"loss {result.metrics_history[0]['loss']:.4f} -> "
          f"{result.metrics_history[-1]['loss']:.4f}; "
          f"resumed_from={result.resumed_from}; "
          f"{_fault_summary(result)}")


if __name__ == "__main__":
    main()
