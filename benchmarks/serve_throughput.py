"""Episodic serving throughput: tasks adapted/sec, queries/sec, state-store
hit-rate, p50/p99 latency, and the compile counter over a request stream.

Four comparisons:

* ``adapt_loop`` vs ``adapt_batch`` — per-task ``learner.adapt`` dispatches
  vs ONE vmapped ``adapt_batch`` over the same T tasks (the serving
  engine's adaptation path).
* ``query_loop`` vs ``query_batch`` — per-task ``predict`` dispatches vs
  ONE micro-batched ``predict_batch`` (the engine's per-step dispatch).
* ``engine_cold`` vs ``engine_warm`` — the full EpisodicServeEngine on a
  request stream of distinct users, then the SAME users again: warm
  traffic skips adaptation via the task-state store, the compile counters
  must not grow, and both rows report nearest-rank p50/p99 adapt latency
  (enqueue -> state ready) and query latency (enqueue -> first logit)
  from the engine's clock.
* ``fomaml_readapt`` vs ``fomaml_rehydrate`` — re-adapting a task whose
  state was evicted vs rehydrating it from the disk warm tier
  (checkpoint-serialized spill): fomaml is the expensive re-adapt tail
  (see table1_adaptation_cost.csv), exactly what the two-tier store
  avoids paying again.
* ``engine_int8_cold`` vs ``engine_cold`` — the same cold request stream
  through a ``serve_quant='int8'`` engine (frozen backbone in blockwise
  int8, dequantized lazily in-jit): compile counters must match the fp32
  engine and the ``param_bytes_resident`` column carries the measured
  resident weight bytes of each engine.
* ``engine_replicas{1,2,4}_{none,int8}`` — the replica-aware router
  (``repro.serve.replica``) on 4 EMULATED devices (fresh subprocess with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4``, same pattern as
  benchmarks/dp_scaling.py): 1/2/4 replicas on disjoint
  ``make_replica_mesh`` device groups, weight-stationary per group, per
  serve_quant mode.  ``requests_per_step`` is admitted-request throughput
  per router step (each step steps every replica once — the replica-
  scaling acceptance row: ~2x from 1 -> 2 replicas) and
  ``wire_per_replica_bytes`` is ONE replica's per-step predict wire from
  ``collectives_report`` — it scales with the group's devices, not the
  deployment (4 replicas x 1 device: zero wire).

    PYTHONPATH=src python benchmarks/serve_throughput.py
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 1)[0])

REPLICA_DEVICES = 4


def _replica_worker() -> None:
    """Replica-scaling sweep, run in the 4-emulated-device subprocess:
    prints one ``REPLICA_ROWS <json>`` line the parent folds into the
    shared CSV."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.episodic_train import task_key
    from repro.core.lite import LiteSpec
    from repro.core.meta_learners import MetaLearnerConfig, make_learner
    from repro.core.set_encoder import SetEncoderConfig
    from repro.data.episodic import (EpisodicImageConfig, collate_task_batch,
                                     plan_buckets, sample_image_task)
    from repro.launch.mesh import make_replica_mesh
    from repro.models.conv_backbone import (ConvBackboneConfig,
                                            make_conv_backbone)
    from repro.roofline.analysis import score_serving_layout
    from repro.serve.episodic import EpisodicRequest
    from repro.serve.quant_params import dequantize_params, quantize_frozen
    from repro.serve.replica import ReplicatedServeEngine, uid_replica

    assert len(jax.devices()) == REPLICA_DEVICES, jax.devices()
    way, shot, query, image = 5, 4, 4, 12
    backbone = make_conv_backbone(ConvBackboneConfig(widths=(8,),
                                                     feature_dim=16))
    learner = make_learner(
        MetaLearnerConfig(kind="protonets", way=way), backbone,
        SetEncoderConfig(kind="conv", conv_blocks=1, conv_width=8,
                         task_dim=16))
    params = learner.init(jax.random.key(0))
    lite = LiteSpec(exact=True, chunk_size=32)
    cfg = EpisodicImageConfig(way=way, shot=shot, query_per_class=query,
                              image_size=image)
    buckets = plan_buckets([way * shot], max_buckets=1)
    n_req = 12

    # uids balanced across all of 1/2/4 replica homes (3 per 4-replica
    # home; 2 | 4 so the 2-replica split is even too) — the scaling rows
    # measure the router, not hash luck on a 12-request sample
    by_home = {r: [] for r in range(4)}
    u = 0
    while sum(len(v) for v in by_home.values()) < n_req:
        h = uid_replica(u, 4)
        if len(by_home[h]) < n_req // 4:
            by_home[h].append(u)
        u += 1
    uids = sorted(x for v in by_home.values() for x in v)

    def make_requests():
        return [EpisodicRequest(
            uid=u,
            support_x=np.asarray(
                (t := sample_image_task(jax.random.key(500 + u),
                                        cfg)).support_x),
            support_y=np.asarray(t.support_y),
            query_x=np.asarray(t.query_x), way=way) for u in uids]

    rows = []
    for replicas in (1, 2, 4):
        dpr = REPLICA_DEVICES // replicas
        meshes = make_replica_mesh(replicas, dpr)
        # one replica group's per-step predict wire (weight_stationary):
        # the group IS the collective domain, so this is what EACH
        # replica pays regardless of how many replicas exist
        probe = [sample_image_task(jax.random.key(i), cfg)
                 for i in range(2)]
        pbatch = collate_task_batch(probe, support_size=max(buckets),
                                    query_size=probe[0].query_x.shape[0])
        pkeys = jax.vmap(lambda i: task_key(jax.random.key(0), i))(
            jnp.arange(2))
        for quant in ("none", "int8"):
            sw = quantize_frozen(learner, params, quant)
            states = learner.adapt_batch(dequantize_params(sw), pbatch,
                                         pkeys, lite)
            wire = score_serving_layout(
                lambda w, st, qx: learner.predict_batch(
                    dequantize_params(w), st, qx),
                sw, (states, pbatch.query_x), meshes[0],
                "weight_stationary")["wire_bytes"]

            router = ReplicatedServeEngine(
                learner, params, replicas=replicas, meshes=meshes,
                serve_layout="weight_stationary", serve_quant=quant,
                lite=lite, n_slots=1, query_chunk=8,
                support_buckets=buckets, cache_capacity=n_req)
            reqs = make_requests()
            for r in reqs:
                router.submit(r)
            t0 = time.perf_counter()
            steps = 0
            while router.busy:
                router.step()
                steps += 1
            dt = time.perf_counter() - t0
            s = router.stats()
            assert s["tasks_adapted"] == n_req
            n_queries = sum(r.n_queries for r in reqs)
            rows.append(dict(
                mode=f"engine_replicas{replicas}_{quant}", tasks=n_req,
                replicas=replicas, devices_per_replica=dpr,
                requests_per_step=round(n_req / steps, 3),
                tasks_per_sec=round(n_req / dt, 1),
                queries_per_sec=round(n_queries / dt, 1),
                hit_rate=round(s["hit_rate"], 3),
                adapt_compiles=int(s["adapt_compiles"]),
                predict_compiles=int(s["predict_compiles"]),
                param_bytes_resident=int(s["param_bytes_resident"]),
                wire_per_replica_bytes=round(wire, 1),
                quarantined=0, rejections=0, deadline_abandoned=0))
    print("REPLICA_ROWS " + json.dumps(rows), flush=True)


if os.environ.get("SERVE_REPLICA_WORKER"):  # pragma: no cover - subprocess
    _replica_worker()
    sys.exit(0)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from common import emit, time_median  # noqa: E402

from repro.core.episodic import index_task_state, stack_task_states
from repro.core.episodic_train import task_key
from repro.core.lite import LiteSpec
from repro.core.meta_learners import MetaLearnerConfig, make_learner
from repro.core.set_encoder import SetEncoderConfig
from repro.data.episodic import (EpisodicImageConfig, collate_task_batch,
                                 plan_buckets, sample_image_task)
from repro.models.conv_backbone import ConvBackboneConfig, make_conv_backbone
from repro.serve.episodic import (EpisodicRequest, EpisodicServeEngine,
                                  WarmTaskStore, _pctl)


def _replica_rows() -> list:
    """Re-exec this file with 4 emulated devices (XLA_FLAGS must precede
    jax init) and collect the replica-scaling rows."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count="
                        f"{REPLICA_DEVICES}").strip()
    env["SERVE_REPLICA_WORKER"] = "1"
    env["JAX_PLATFORMS"] = "cpu"    # emulated devices; never the chip
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [str(__file__).rsplit("/", 2)[0] + "/src",
                    env.get("PYTHONPATH", "")] if p)
    r = subprocess.run([sys.executable, __file__], env=env,
                       capture_output=True, text=True)
    if r.returncode:
        raise RuntimeError(f"replica worker failed ({r.returncode}):\n"
                           f"{r.stderr[-3000:]}")
    for line in r.stdout.splitlines():
        if line.startswith("REPLICA_ROWS "):
            return json.loads(line[len("REPLICA_ROWS "):])
    raise RuntimeError("replica worker produced no REPLICA_ROWS line")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tasks", type=int, default=8)
    ap.add_argument("--way", type=int, default=5)
    ap.add_argument("--shot", type=int, default=4)
    ap.add_argument("--query", type=int, default=4)
    ap.add_argument("--image-size", type=int, default=12)
    ap.add_argument("--iters", type=int, default=9)
    ap.add_argument("--engine-requests", type=int, default=12)
    args = ap.parse_args()

    backbone = make_conv_backbone(ConvBackboneConfig(widths=(8,),
                                                     feature_dim=16))
    learner = make_learner(
        MetaLearnerConfig(kind="protonets", way=args.way), backbone,
        SetEncoderConfig(kind="conv", conv_blocks=1, conv_width=8,
                         task_dim=16))
    params = learner.init(jax.random.key(0))
    lite = LiteSpec(exact=True, chunk_size=32)
    t_count = args.tasks

    cfg = EpisodicImageConfig(way=args.way, shot=args.shot,
                              query_per_class=args.query,
                              image_size=args.image_size)
    tasks = [sample_image_task(jax.random.key(100 + i), cfg)
             for i in range(t_count)]
    batch = collate_task_batch(tasks)
    key = jax.random.key(7)
    keys = jax.vmap(lambda i: task_key(key, i))(jnp.arange(t_count))
    n_q = int(batch.query_x.shape[1])

    def blank(r):
        return dict(mode=r["mode"], tasks=r.get("tasks", ""),
                    tasks_per_sec=r.get("tasks_per_sec", ""),
                    queries_per_sec=r.get("queries_per_sec", ""),
                    speedup=r.get("speedup", ""),
                    hit_rate=r.get("hit_rate", ""),
                    wall_us=r.get("wall_us", ""),
                    adapt_p50_us=r.get("adapt_p50_us", ""),
                    adapt_p99_us=r.get("adapt_p99_us", ""),
                    query_p50_us=r.get("query_p50_us", ""),
                    query_p99_us=r.get("query_p99_us", ""),
                    adapt_compiles=r.get("adapt_compiles", ""),
                    predict_compiles=r.get("predict_compiles", ""),
                    param_bytes_resident=r.get("param_bytes_resident", ""),
                    quarantined=r.get("quarantined", ""),
                    rejections=r.get("rejections", ""),
                    deadline_abandoned=r.get("deadline_abandoned", ""),
                    replicas=r.get("replicas", ""),
                    devices_per_replica=r.get("devices_per_replica", ""),
                    requests_per_step=r.get("requests_per_step", ""),
                    wire_per_replica_bytes=r.get("wire_per_replica_bytes",
                                                 ""))

    rows = []

    # -- adaptation: per-task loop vs one vmapped dispatch -------------------
    adapt_one = jax.jit(lambda p, sx, sy, k, m: learner.adapt(
        p, sx, sy, key=k, lite=lite, mask=m))

    def run_adapt_loop():
        sts = [adapt_one(params, batch.support_x[i], batch.support_y[i],
                         keys[i], batch.support_mask[i])
               for i in range(t_count)]
        jax.block_until_ready(sts)
        return sts

    adapt_b = jax.jit(lambda p, b, k: learner.adapt_batch(p, b, k, lite))

    def run_adapt_batch():
        return jax.block_until_ready(adapt_b(params, batch, keys))

    t_loop = time_median(run_adapt_loop, args.iters)
    t_batch = time_median(run_adapt_batch, args.iters)
    rows.append(blank(dict(mode="adapt_loop", tasks=t_count,
                           tasks_per_sec=round(t_count / t_loop, 1),
                           speedup=1.0)))
    rows.append(blank(dict(mode="adapt_batch", tasks=t_count,
                           tasks_per_sec=round(t_count / t_batch, 1),
                           speedup=round(t_loop / t_batch, 2))))

    # -- query scoring: per-task loop vs one micro-batched dispatch ----------
    states = run_adapt_batch()
    per_states = [index_task_state(states, i) for i in range(t_count)]
    pred_one = jax.jit(learner.predict)
    pred_b = jax.jit(learner.predict_batch)
    states_stacked = stack_task_states(per_states)

    def run_query_loop():
        out = [pred_one(params, per_states[i], batch.query_x[i])
               for i in range(t_count)]
        jax.block_until_ready(out)

    def run_query_batch():
        jax.block_until_ready(pred_b(params, states_stacked, batch.query_x))

    t_qloop = time_median(run_query_loop, args.iters)
    t_qbatch = time_median(run_query_batch, args.iters)
    rows.append(blank(dict(mode="query_loop", tasks=t_count,
                           queries_per_sec=round(t_count * n_q / t_qloop, 1),
                           speedup=1.0)))
    rows.append(blank(dict(mode="query_batch", tasks=t_count,
                           queries_per_sec=round(t_count * n_q / t_qbatch, 1),
                           speedup=round(t_qloop / t_qbatch, 2))))

    # -- full engine: cold stream, then the same users warm ------------------
    def make_requests():
        return [EpisodicRequest(uid=i, support_x=np.asarray(t.support_x),
                                support_y=np.asarray(t.support_y),
                                query_x=np.asarray(t.query_x), way=args.way)
                for i, t in enumerate(
                    sample_image_task(jax.random.key(500 + i), cfg)
                    for i in range(args.engine_requests))]

    buckets = plan_buckets([args.way * args.shot], max_buckets=1)
    engine = EpisodicServeEngine(learner, params, lite=lite, n_slots=4,
                                 query_chunk=8, support_buckets=buckets,
                                 cache_capacity=args.engine_requests)
    cold = make_requests()
    t0 = time.perf_counter()
    engine.run_to_completion(cold)
    dt_cold = time.perf_counter() - t0
    s_cold = engine.stats()

    warm = [EpisodicRequest(uid=r.uid, query_x=np.asarray(r.query_x),
                            way=args.way)
            for r in cold]                      # repeat visitors, no support
    t0 = time.perf_counter()
    engine.run_to_completion(warm)
    dt_warm = time.perf_counter() - t0
    s_warm = engine.stats()

    n_req = args.engine_requests
    n_queries = sum(r.n_queries for r in cold)

    def wave_pctls(reqs):
        """Per-wave nearest-rank percentiles from the request timestamps
        (the engine's cumulative stats would mix the waves)."""
        alat = [(r.t_adapt - r.t_enqueue) * 1e6 for r in reqs
                if r.t_adapt is not None]
        qlat = [(r.t_first_logit - r.t_enqueue) * 1e6 for r in reqs
                if r.t_first_logit is not None]
        return dict(adapt_p50_us=round(_pctl(alat, 50)),
                    adapt_p99_us=round(_pctl(alat, 99)),
                    query_p50_us=round(_pctl(qlat, 50)),
                    query_p99_us=round(_pctl(qlat, 99)))

    rows.append(blank(dict(
        mode="engine_cold", tasks=n_req,
        tasks_per_sec=round(s_cold["tasks_adapted"] / dt_cold, 1),
        queries_per_sec=round(n_queries / dt_cold, 1),
        hit_rate=round(s_cold["hit_rate"], 3),
        adapt_compiles=s_cold["adapt_compiles"],
        predict_compiles=s_cold["predict_compiles"],
        quarantined=int(s_cold["quarantined"]),
        rejections=int(s_cold["rejections"]),
        deadline_abandoned=int(s_cold["deadline_abandoned"]),
        param_bytes_resident=s_cold["param_bytes_resident"],
        **wave_pctls(cold))))
    rows.append(blank(dict(
        mode="engine_warm", tasks=n_req,
        queries_per_sec=round(n_queries / dt_warm, 1),
        speedup=round(dt_cold / dt_warm, 2),
        hit_rate=round(
            (s_warm["cache_hits"] - s_cold["cache_hits"]) /
            max(n_req, 1), 3),
        adapt_compiles=s_warm["adapt_compiles"],
        predict_compiles=s_warm["predict_compiles"],
        quarantined=int(s_warm["quarantined"]),
        rejections=int(s_warm["rejections"]),
        deadline_abandoned=int(s_warm["deadline_abandoned"]),
        param_bytes_resident=s_warm["param_bytes_resident"],
        **wave_pctls(warm))))

    # -- int8 weight-stationary serving vs fp32, same traffic ----------------
    # quantized frozen backbone (repro.serve.quant_params): same request
    # stream, same bucket plan — the rows compare throughput, compile
    # counters (must match the fp32 engine: identical dispatch paths), and
    # the measured resident parameter bytes.
    eng_q = EpisodicServeEngine(learner, params, lite=lite, n_slots=4,
                                query_chunk=8, support_buckets=buckets,
                                cache_capacity=args.engine_requests,
                                serve_quant="int8")
    cold_q = make_requests()
    t0 = time.perf_counter()
    eng_q.run_to_completion(cold_q)
    dt_q = time.perf_counter() - t0
    s_q = eng_q.stats()
    rows.append(blank(dict(
        mode="engine_int8_cold", tasks=n_req,
        tasks_per_sec=round(s_q["tasks_adapted"] / dt_q, 1),
        queries_per_sec=round(n_queries / dt_q, 1),
        speedup=round(dt_cold / dt_q, 2),
        hit_rate=round(s_q["hit_rate"], 3),
        adapt_compiles=s_q["adapt_compiles"],
        predict_compiles=s_q["predict_compiles"],
        quarantined=int(s_q["quarantined"]),
        rejections=int(s_q["rejections"]),
        deadline_abandoned=int(s_q["deadline_abandoned"]),
        param_bytes_resident=s_q["param_bytes_resident"],
        **wave_pctls(cold_q))))

    # -- warm-tier rehydrate vs re-adaptation (fomaml: the expensive tail) ---
    import tempfile

    fomaml = make_learner(
        MetaLearnerConfig(kind="fomaml", way=args.way, inner_steps=15),
        backbone,
        SetEncoderConfig(kind="conv", conv_blocks=1, conv_width=8,
                         task_dim=16))
    f_params = fomaml.init(jax.random.key(1))
    f_task = tasks[0]
    f_key = task_key(key, 0)
    adapt_j = jax.jit(lambda p, sx, sy, k: fomaml.adapt(
        p, sx, sy, key=k, lite=lite))
    st = jax.block_until_ready(
        adapt_j(f_params, f_task.support_x, f_task.support_y, f_key))
    with tempfile.TemporaryDirectory() as warm_dir:
        warm_store = WarmTaskStore(warm_dir)
        warm_store.put(0, st)
        t_readapt = time_median(lambda: jax.block_until_ready(
            adapt_j(f_params, f_task.support_x, f_task.support_y, f_key)),
            args.iters)
        t_rehydrate = time_median(lambda: jax.block_until_ready(
            warm_store.get(0)), args.iters)
    rows.append(blank(dict(mode="fomaml_readapt", tasks=1,
                           wall_us=round(1e6 * t_readapt), speedup=1.0)))
    rows.append(blank(dict(mode="fomaml_rehydrate", tasks=1,
                           wall_us=round(1e6 * t_rehydrate),
                           speedup=round(t_readapt / t_rehydrate, 2))))

    # -- replica scaling on 4 emulated devices (fresh subprocess) ------------
    rep_rows = [blank(r) for r in _replica_rows()]
    rows.extend(rep_rows)

    emit(rows, "serve_throughput")
    by_mode = {r["mode"]: r for r in rep_rows}
    r1 = by_mode["engine_replicas1_none"]
    r2 = by_mode["engine_replicas2_none"]
    print(f"# replica scaling (4 emulated devices): requests/step "
          f"{r1['requests_per_step']} -> {r2['requests_per_step']} "
          f"(x{r2['requests_per_step'] / r1['requests_per_step']:.2f} at 2 "
          f"replicas); per-replica predict wire "
          f"{r1['wire_per_replica_bytes']} B (4 dev) -> "
          f"{r2['wire_per_replica_bytes']} B (2-dev group) -> "
          f"{by_mode['engine_replicas4_none']['wire_per_replica_bytes']} B "
          f"(1-dev group)")
    print(f"# warm-tier rehydrate vs fomaml re-adapt: "
          f"{t_readapt / t_rehydrate:.2f}x cheaper "
          f"({1e6 * t_readapt:.0f} vs {1e6 * t_rehydrate:.0f} us)")
    print(f"# adapt_batch speedup over per-task adapt loop: "
          f"{t_loop / t_batch:.2f}x")
    print(f"# predict_batch speedup over per-task query loop: "
          f"{t_qloop / t_qbatch:.2f}x")
    print(f"# warm (cached) pass speedup over cold: "
          f"{dt_cold / dt_warm:.2f}x; compile counters flat: "
          f"{s_warm['adapt_compiles'] == s_cold['adapt_compiles']}")
    print(f"# int8 serving: resident weight bytes "
          f"{s_cold['param_bytes_resident']} -> "
          f"{s_q['param_bytes_resident']} "
          f"(frozen slice {s_cold['frozen_param_bytes_resident']} -> "
          f"{s_q['frozen_param_bytes_resident']}, "
          f"{s_cold['frozen_param_bytes_resident'] / max(s_q['frozen_param_bytes_resident'], 1):.2f}x); "
          f"compile counters match fp32: "
          f"{(s_q['adapt_compiles'], s_q['predict_compiles']) == (s_cold['adapt_compiles'], s_cold['predict_compiles'])}")


if __name__ == "__main__":
    main()
