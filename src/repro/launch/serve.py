"""Production serving launcher: continuous-batching decode over the
uniform cache API, and episodic adapt-many-tasks personalization serving.

LM token decode (default):

    PYTHONPATH=src python -m repro.launch.serve --arch zamba2-7b \
        --requests 8 --slots 4 --max-new 16

Episodic personalization (``--episodic``): each request is a support set
to adapt on + a query stream to answer; all four learner kinds serve
through the same batched ``adapt_batch``/``predict_batch`` contract, with
LITE-chunked forward-only adaptation, a TWO-TIER task-state store keyed
by task uid (an L1 LRU of ``--cache-capacity`` resident states over an
optional ``--warm-dir`` disk tier: evicted states spill through the
checkpoint serialization and repeat visitors rehydrate bit-exactly
instead of re-adapting; ``--repeat-frac`` controls how much of the
traffic is repeat users), continuous batching with per-request latency
accounting (p50/p99 adapt and query latency in the summary), SLO-aware
scheduling (``--query-slo-us`` lets near-deadline query chunks preempt
an adapt wave, cost-estimated from ``--adapt-cost-hint-us`` until
measured), micro-batched query dispatch, and the aggregation kernels
(class statistics, Mahalanobis head) routed through
``repro.kernels.dispatch`` (``--kernel-backend``):

    PYTHONPATH=src python -m repro.launch.serve --episodic \
        --learner protonets --requests 16 --slots 4 --shot 10 \
        --repeat-frac 0.5 --lite-chunk 32 --cache-capacity 4 \
        --warm-dir /tmp/warm_states --query-slo-us 50000

``--replicas R`` scales the same engine horizontally (R engines on
disjoint device groups, uid-hash routing, shared sharded warm tier —
``repro.serve.replica``); with ``--serve-layout auto`` the roofline
chooser scores ONE replica group and the winner applies to all:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m repro.launch.serve --episodic \
        --replicas 2 --serve-layout auto --serve-quant int8 --requests 16

The episodic backbone and task shape take the flags of
``repro.launch.episodic`` (toy defaults; ``--widths 32,64,128,256
--feature-dim 256 --image-size 224 ...`` is the paper's regime).  A run in
which any request is rejected, abandoned or failed exits non-zero.

The LM path runs the smoke config; on a TPU slice the same engines
serve the full config (params sharded by repro.sharding.rules — see
EXPERIMENTS.md §Perf cell 2 for the topology guidance: size the slice so
weights are resident, don't decode one stream set on a full pod).
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Callable, Optional, Sequence

import jax
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.configs.registry import ARCH_IDS, get_smoke_config
from repro.models.registry import get_api
from repro.serve.engine import Request, ServeEngine


def run_episodic(args, clock: Callable[[], float] = time.monotonic) -> None:
    """``clock`` is injectable (the PR6/PR7 clock-discipline contract):
    the launcher's wall-clock default is the reference monotonic clock;
    tests can pass a FakeClock and the printed throughput numbers become
    deterministic.  The engine itself receives its own injectable clock
    via ``EpisodicServeEngine(clock=...)``."""
    from repro.core.lite import LiteSpec
    from repro.data.episodic import plan_buckets, sample_image_task
    from repro.launch.episodic import build_learner, shape_from_args
    from repro.serve.episodic import EpisodicRequest, EpisodicServeEngine

    shape = shape_from_args(args)
    learner = build_learner(args.learner, shape)
    params = learner.init(jax.random.key(0))
    lite = LiteSpec(exact=True, chunk_size=shape.lite_chunk,
                    compute_dtype=args.lite_dtype)

    # synthetic personalization traffic: exactly n_users distinct users
    # visit first (cold), then the remaining repeat_frac of requests
    # revisit them (warm; supports still attached, as real clients send —
    # the engine skips adaptation on the cache hit)
    rng = np.random.default_rng(0)
    n_users = max(1, round(args.requests * (1.0 - args.repeat_frac)))
    n_users = min(n_users, args.requests)
    cfg = shape.image_config()

    def request_for(uid):
        t = sample_image_task(jax.random.key(uid), cfg)
        return EpisodicRequest(uid=uid, support_x=np.asarray(t.support_x),
                               support_y=np.asarray(t.support_y),
                               query_x=np.asarray(t.query_x), way=shape.way)

    cold = [request_for(uid) for uid in range(n_users)]
    warm = [request_for(int(rng.integers(0, n_users)))
            for _ in range(args.requests - n_users)]
    reqs = cold + warm
    buckets = plan_buckets([r.support_x.shape[0] for r in reqs],
                           max_buckets=2)

    # weight-stationary serving layout: build a 1-D mesh (with --replicas
    # R > 1: R disjoint group meshes, each over len(devices)//R devices)
    # and either honor an explicit layout name or let the roofline chooser
    # score every candidate on the compiled predict step (one replica
    # group prices them all — the groups are congruent)
    replicas = args.replicas
    serve_layout, meshes, layout_rows = args.serve_layout, None, None
    multi_dev = len(jax.devices()) >= max(2, replicas)
    if serve_layout != "none" and multi_dev:
        if replicas > 1:
            from repro.launch.mesh import make_replica_mesh
            meshes = make_replica_mesh(replicas,
                                       len(jax.devices()) // replicas)
        else:
            meshes = [jax.make_mesh((len(jax.devices()),), ("serve",))]
        if serve_layout == "auto":
            import jax.numpy as jnp
            from repro.core.episodic_train import task_key
            from repro.data.episodic import collate_task_batch
            from repro.roofline.analysis import choose_replica_serving_layout
            from repro.serve.quant_params import (dequantize_params,
                                                  quantize_frozen)
            sw = quantize_frozen(learner, params, args.serve_quant)
            probe = [sample_image_task(jax.random.key(i), cfg)
                     for i in range(2)]
            batch = collate_task_batch(
                probe, support_size=max(buckets),
                query_size=probe[0].query_x.shape[0])
            keys = jax.vmap(lambda i: task_key(jax.random.key(0), i))(
                jnp.arange(2))
            states = learner.adapt_batch(dequantize_params(sw), batch,
                                         keys, lite)
            pick = choose_replica_serving_layout(
                lambda w, st, qx: learner.predict_batch(
                    dequantize_params(w), st, qx),
                sw, (states, batch.query_x), meshes)
            serve_layout, layout_rows = pick["choice"], pick["rows"]
    elif serve_layout == "auto":
        serve_layout = "none"               # single device: nothing to place

    engine_kw = dict(lite=lite, n_slots=args.slots,
                     query_chunk=args.query_chunk,
                     support_buckets=buckets,
                     kernel_backend=args.kernel_backend,
                     cache_capacity=args.cache_capacity,
                     warm_dir=args.warm_dir,
                     query_slo_us=args.query_slo_us,
                     adapt_cost_hint_us=args.adapt_cost_hint_us,
                     max_queue=args.max_queue,
                     deadline_us=args.deadline_us,
                     serve_quant=args.serve_quant,
                     serve_layout=(None if serve_layout == "none"
                                   else serve_layout))
    if replicas > 1:
        from repro.serve.replica import ReplicatedServeEngine
        engine = ReplicatedServeEngine(learner, params, replicas=replicas,
                                       meshes=meshes,
                                       warm_shards=args.warm_shards,
                                       **engine_kw)
    else:
        engine = EpisodicServeEngine(
            learner, params, mesh=meshes[0] if meshes else None, **engine_kw)
    # cold wave first so every warm request finds its user's state cached
    # regardless of slot count — warm traffic measures the cache, not
    # admission-wave luck
    t0 = clock()
    engine.run_to_completion(cold)
    engine.run_to_completion(warm)
    dt = clock() - t0
    s = engine.stats()
    unserved = [r.uid for r in reqs
                if not r.done or r.rejected or r.abandoned or r.failed]
    print(f"episodic serve: learner={args.learner} {len(reqs)} requests "
          f"({n_users} distinct users) in {dt:.2f}s on {args.slots} slots")
    print(f"  tasks adapted {s['tasks_adapted']} "
          f"({s['tasks_adapted']/dt:.1f}/s), "
          f"queries {s['queries_served']} ({s['queries_served']/dt:.1f}/s), "
          f"cache hit-rate {s['hit_rate']:.2f}, "
          f"compiles adapt={s['adapt_compiles']} "
          f"predict={s['predict_compiles']}")
    print(f"  latency: adapt p50/p99 {s['adapt_p50_us']:.0f}/"
          f"{s['adapt_p99_us']:.0f} us, query (first logit) p50/p99 "
          f"{s['query_p50_us']:.0f}/{s['query_p99_us']:.0f} us; "
          f"store: evictions={s['evictions']} spills={s['spills']} "
          f"rehydrates={s['rehydrates']}, "
          f"slo_preemptions={s['slo_preemptions']}")
    print(f"  degradation: quarantined={s['quarantined']:.0f} "
          f"spill_errors={s['spill_errors']:.0f} "
          f"rejections={s['rejections']:.0f} "
          f"deadline_abandoned={s['deadline_abandoned']:.0f} "
          f"failed_requests={s['failed_requests']:.0f}")
    print(f"  weights: quant={args.serve_quant} layout={serve_layout} "
          f"resident {s['param_bytes_resident']} B "
          f"(fp32 {s['param_bytes_fp32']} B; frozen slice "
          f"{s['frozen_param_bytes_resident']} / "
          f"{s['frozen_param_bytes_fp32']} B)")
    if layout_rows is not None:
        for lo, r in layout_rows.items():
            print(f"    layout {lo:18s} wire={r['wire_bytes']:12.0f} B "
                  f"bottleneck={r['bottleneck']}")
    if replicas > 1:
        print(f"  replicas: {s['live_replicas']}/{s['n_replicas']} live, "
              f"failovers={s['replica_failovers']} "
              f"rerouted={s['rerouted_requests']}")
        for i, p in enumerate(s["per_replica"]):
            print(f"    replica {i}: adapted={p['tasks_adapted']:.0f} "
                  f"queries={p['queries_served']:.0f} "
                  f"hit_rate={p['hit_rate']:.2f} "
                  f"compiles adapt={p['adapt_compiles']:.0f} "
                  f"predict={p['predict_compiles']:.0f}")
    for r in reqs[:4]:
        print(f"  req uid={r.uid}: cache_hit={r.cache_hit} "
              f"preds={r.predictions()[:8].tolist()}")
    if unserved:
        # rejected, abandoned and failed requests are counted degradations,
        # not successes: the run must not exit 0 over them
        sys.exit(f"{len(unserved)} of {len(reqs)} requests were not served "
                 f"(uids {unserved[:8]})")


def main(clock: Callable[[], float] = time.monotonic,
         argv: Optional[Sequence[str]] = None) -> None:
    from repro.launch.episodic import EpisodicShape, add_shape_args

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="minitron-4b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--episodic", action="store_true",
                    help="adapt-many-tasks personalization serving")
    ap.add_argument("--learner", default="protonets",
                    choices=["protonets", "cnaps", "simple_cnaps", "fomaml",
                             "finetuner"])
    add_shape_args(ap, EpisodicShape(query_per_class=4, lite_chunk=32),
                   lite_h=False)
    ap.add_argument("--query-chunk", type=int, default=8)
    ap.add_argument("--repeat-frac", type=float, default=0.5,
                    help="fraction of requests from repeat users "
                         "(task-state cache hits)")
    ap.add_argument("--cache-capacity", type=int, default=64,
                    help="L1 task-state LRU capacity (resident adapted "
                         "states); evictions spill to --warm-dir when set")
    ap.add_argument("--warm-dir", default=None,
                    help="disk warm tier for evicted task states: spilled "
                         "via the checkpoint serialization, rehydrated "
                         "bit-exactly on a repeat uid instead of "
                         "re-adapting (default: off, evictions discard)")
    ap.add_argument("--query-slo-us", type=float, default=None,
                    help="per-request first-logit SLO in microseconds: a "
                         "pending adapt wave is deferred when it would "
                         "push a live lane's queries past this deadline")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound the admission queue: a submit over the "
                         "bound is rejected with a retry-after estimate "
                         "(EWMA adapt cost) instead of queueing unbounded "
                         "(default: unbounded)")
    ap.add_argument("--deadline-us", type=float, default=None,
                    help="per-request deadline from enqueue: a request "
                         "still logit-less past it is abandoned and its "
                         "lane/queue slot freed (default: off)")
    ap.add_argument("--adapt-cost-hint-us", type=float, default=None,
                    help="seed for the EWMA adapt-dispatch cost estimate "
                         "the SLO scheduler plans with (measured "
                         "thereafter)")
    ap.add_argument("--lite-dtype", choices=["bfloat16", "float16"],
                    default=None,
                    help="serve-time adaptation compute dtype")
    ap.add_argument("--serve-quant", choices=["none", "int8"],
                    default="none",
                    help="quantize the learner kind's FROZEN param slice "
                         "(the backbone for the CNAPs family / finetuner; "
                         "nothing for fomaml) into blockwise int8 for "
                         "serving — dequantized lazily inside the jitted "
                         "step, ~3-4x fewer resident weight bytes, logits "
                         "within quantization tolerance (fomaml "
                         "bit-identical)")
    ap.add_argument("--serve-layout",
                    choices=["auto", "none", "training",
                             "weight_stationary", "replicated"],
                    default="none",
                    help="serving weight placement on the local device "
                         "mesh: auto = compile every candidate and pick "
                         "by the three-term roofline over the actual HLO "
                         "(repro.roofline.analysis.choose_serving_layout), "
                         "weight_stationary = shard matmul weights on the "
                         "contracting dim (small-batch serving moves "
                         "activations, not gathered weights), training = "
                         "the ZeRO-ish weight-gathered train placement, "
                         "replicated = every chip holds all weights "
                         "(default: none — single-device placement)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serving replicas (repro.serve.replica): each "
                         "replica owns a full copy of the serving weights "
                         "pinned to its own disjoint device group "
                         "(len(devices)//replicas devices each, "
                         "make_replica_mesh) and its own L1 state cache; "
                         "requests route by stable uid hash, the shared "
                         "--warm-dir is partitioned into uid-hash shard "
                         "subdirs, and no predict-step collective ever "
                         "crosses a group (default: 1 — single engine)")
    ap.add_argument("--warm-shards", type=int, default=None,
                    help="uid-hash shard subdirs under --warm-dir for the "
                         "replicated path (default: 8; keep it FIXED "
                         "across deployments of the same warm root — "
                         "resizing --replicas re-routes uids but never "
                         "moves their warm files)")
    ap.add_argument("--kernel-backend",
                    choices=["ref", "pallas", "auto", "naive"],
                    default="ref",
                    help="episodic aggregation-kernel backend "
                         "(repro.kernels.dispatch), bound per engine at "
                         "construction: ref = fused jnp, pallas = Pallas "
                         "kernels (interpret off-TPU), auto = pallas on "
                         "TPU else ref, naive = materializing legacy "
                         "composite")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.episodic:
        run_episodic(args, clock=clock)
        return

    cfg = get_smoke_config(args.arch)
    api = get_api(cfg)
    params = api.init(jax.random.key(0), cfg)
    engine = ServeEngine(cfg, params, n_slots=args.slots,
                         max_seq=args.prompt_len + args.max_new + 8)

    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        size=args.prompt_len).astype(np.int32),
                    max_new_tokens=args.max_new,
                    temperature=args.temperature)
            for i in range(args.requests)]
    t0 = clock()
    engine.run_to_completion(reqs)
    dt = clock() - t0
    n_tok = sum(len(r.out_tokens) for r in reqs)
    print(f"{cfg.name} ({cfg.family} cache): {len(reqs)} requests, "
          f"{n_tok} tokens in {dt:.2f}s ({n_tok/dt:.1f} tok/s on "
          f"{len(jax.devices())} {jax.devices()[0].platform} device(s))")
    for r in reqs[:4]:
        print(f"  req {r.uid}: {r.prompt.tolist()} -> {r.out_tokens}")
    assert all(r.done for r in reqs)


if __name__ == "__main__":
    main()
