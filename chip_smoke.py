#!/usr/bin/env python3
"""Chip smoke test: LITE meta-training and episodic serving end to end on
a TPU, at the conv backbone's full width and the paper's task shape.

    python3 chip_smoke.py                # phases (a)-(d) on one chip
    python3 chip_smoke.py --four-chips   # dp4 training + 4 serving replicas

Shape: ``ConvBackboneConfig()`` widths (32, 64, 128, 256) and
feature_dim 256, 224x224x3 images, way 10 x shot 100 (a 1000-image
support set), 10 queries per class, |H| = 40, LITE chunk 100.  Weights are
random from a fixed seed; data is the synthetic episodic stream.

One chip, all in this one process:
  (a) train: a few LITE meta-training steps through the training launcher
      (``repro.launch.train``) for simple_cnaps and protonets, then restore
      the run's checkpoint;
  (b) serve: ``EpisodicServeEngine`` on support sets of 100, 500 and 1000
      images in two buckets, plus one returning user;
  (c) kernels: the Pallas kernels against their references, at op level
      and through Simple CNAPs adapt/predict;
  (d) precision: one step's loss and gradients at the default matmul
      precision against ``"highest"``.
``--four-chips`` runs only a dp_shards=4 training step against the
one-device step, and ``ReplicatedServeEngine`` with 4 one-chip replicas
against one engine.

Every phase prints its own line with ``peak_bytes_in_use`` and its wall
seconds (set-up information, not a measurement).  The last line of standard
output is ``{"ok": true, "device": {...}}`` and is printed only when every
phase passed; without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.common.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.episodic_train import make_batched_meta_grads, task_key  # noqa: E402
from repro.core.lite import LiteSpec  # noqa: E402
from repro.data.episodic import (EpisodicImageConfig, collate_task_batch,  # noqa: E402
                                 plan_buckets, sample_image_task)
from repro.kernels import dispatch, tpu_compat  # noqa: E402
from repro.launch import train as train_launcher  # noqa: E402
from repro.launch.episodic import EpisodicShape, build_learner  # noqa: E402
from repro.launch.mesh import make_replica_mesh  # noqa: E402
from repro.optim import quant  # noqa: E402
from repro.serve.episodic import EpisodicRequest, EpisodicServeEngine  # noqa: E402
from repro.serve.replica import ReplicatedServeEngine, uid_replica  # noqa: E402
from repro.train.checkpoint import CheckpointManager  # noqa: E402

CHIP_SHAPE = EpisodicShape(widths=(32, 64, 128, 256), feature_dim=256,
                           way=10, shot=100, query_per_class=10,
                           image_size=224, lite_h=40, lite_chunk=100)
TRAIN_STEPS = 4
HIGHEST = jax.lax.Precision.HIGHEST


class SmokeFailure(RuntimeError):
    pass


def _check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class CompileCounter:
    """Counts executables built in this process, compiled or loaded from
    the persistent cache (JAX times both as one backend-compile event)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.count += 1


COMPILES = CompileCounter()


def peak_bytes() -> dict:
    """``peak_bytes_in_use`` per device since the process started (None
    where the backend keeps no memory statistics)."""
    out = {}
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out[d.id] = stats.get("peak_bytes_in_use")
    return out


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def shape_argv(shape: EpisodicShape) -> list:
    argv = ["--backbone", shape.backbone,
            "--widths", ",".join(str(w) for w in shape.widths),
            "--feature-dim", str(shape.feature_dim), "--way", str(shape.way),
            "--shot", str(shape.shot),
            "--query-per-class", str(shape.query_per_class),
            "--image-size", str(shape.image_size),
            "--lite-h", str(shape.lite_h)]
    if shape.lite_chunk is not None:
        argv += ["--lite-chunk", str(shape.lite_chunk)]
    return argv


def _tree_equal(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# (a) train
# ---------------------------------------------------------------------------


def phase_train(shape: EpisodicShape, learner: str,
                steps: int = TRAIN_STEPS) -> dict:
    """LITE meta-training through the launcher (device data source,
    tasks_per_step=1, a fresh checkpoint directory), then restore the
    run's last checkpoint.  Fails on a non-finite loss or gradient norm, a
    skipped step, or an executable built after the first step."""
    marks = []
    with tempfile.TemporaryDirectory() as ckpt_dir:
        args = train_launcher.build_parser().parse_args(
            ["--episodic", "--learner", learner, "--steps", str(steps),
             "--tasks-per-step", "1", "--data-source", "device",
             "--ckpt-dir", ckpt_dir, "--ckpt-every", str(steps)]
            + shape_argv(shape))
        result = train_launcher.run_episodic(
            args, step_hook=lambda s: marks.append(COMPILES.count))
        losses = [m["loss"] for m in result.metrics_history]
        gnorms = [m["grad_norm"] for m in result.metrics_history]
        late = COMPILES.count - marks[1] if len(marks) > 1 else 0
        template = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), result.state)
        restored = CheckpointManager(ckpt_dir).restore_latest(template)
    _check(len(losses) == steps, f"{len(losses)} of {steps} steps committed")
    _check(np.all(np.isfinite(losses)), f"non-finite loss {losses}")
    _check(np.all(np.isfinite(gnorms)), f"non-finite grad norm {gnorms}")
    _check(not result.nonfinite_steps,
           f"steps skipped as non-finite: {result.nonfinite_steps}")
    _check(late == 0, f"{late} executable(s) built after the first step")
    _check(restored is not None and restored[0] == steps,
           "no checkpoint at the final step")
    _check(_tree_equal(restored[1], result.state),
           "restored checkpoint differs from the trained state")
    return dict(losses=losses, grad_norms=gnorms, late_compiles=late,
                restored_step=restored[0], state=result.state)


# ---------------------------------------------------------------------------
# (b) serve
# ---------------------------------------------------------------------------


def _request(uid: int, shape: EpisodicShape, shot: int) -> EpisodicRequest:
    cfg = EpisodicImageConfig(way=shape.way, shot=shot,
                              query_per_class=shape.query_per_class,
                              image_size=shape.image_size)
    t = sample_image_task(jax.random.key(1000 + uid), cfg)
    return EpisodicRequest(uid=uid, support_x=np.asarray(t.support_x),
                           support_y=np.asarray(t.support_y),
                           query_x=np.asarray(t.query_x), way=shape.way)


def _served(r: EpisodicRequest) -> bool:
    return r.done and not (r.rejected or r.abandoned or r.failed)


def phase_serve(shape: EpisodicShape, learner, params, n_slots: int = 2,
                query_chunk: int = 64) -> dict:
    """Support sets of shot/10, shot/2 and shot images per class (100, 500
    and 1000 images at the chip shape) in two buckets; then the first user
    returns without a support set and must get bit-identical logits."""
    shots = (max(1, shape.shot // 10), max(1, shape.shot // 2), shape.shot)
    cold = [_request(uid, shape, s) for uid, s in enumerate(shots)]
    buckets = plan_buckets([r.support_x.shape[0] for r in cold],
                           max_buckets=2)
    engine = EpisodicServeEngine(
        learner, params, lite=LiteSpec(exact=True,
                                       chunk_size=shape.lite_chunk),
        n_slots=n_slots, query_chunk=query_chunk, support_buckets=buckets)
    engine.run_to_completion(cold)
    back = EpisodicRequest(uid=cold[0].uid, query_x=cold[0].query_x,
                           way=shape.way)
    engine.run_to_completion([back])
    s = engine.stats()
    reqs = cold + [back]
    _check(len(buckets) == 2, f"support sizes fell in buckets {buckets}")
    _check(all(_served(r) for r in reqs),
           "unserved: " + str([(r.uid, r.done, r.rejected, r.abandoned,
                                r.failed) for r in reqs if not _served(r)]))
    for k in ("quarantined", "spill_errors", "rejections",
              "failed_requests"):
        _check(s[k] == 0, f"{k}={s[k]}")
    _check(s["adapt_compiles"] == len(buckets),
           f"adapt_compiles={s['adapt_compiles']} for {len(buckets)} buckets")
    _check(s["predict_compiles"] == 1,
           f"predict_compiles={s['predict_compiles']}")
    _check(back.cache_hit, "returning user missed the task-state store")
    _check(np.array_equal(back.all_logits(), cold[0].all_logits()),
           "returning user's logits differ from the first visit: max "
           f"{np.max(np.abs(back.all_logits() - cold[0].all_logits()))}")
    finite = all(np.all(np.isfinite(r.all_logits())) for r in reqs)
    _check(finite, "non-finite logits")
    return dict(buckets=buckets, support_sizes=[r.support_x.shape[0]
                                                for r in cold],
                adapt_compiles=s["adapt_compiles"],
                predict_compiles=s["predict_compiles"],
                queries_served=s["queries_served"], hit_rate=s["hit_rate"])


# ---------------------------------------------------------------------------
# (c) kernels
# ---------------------------------------------------------------------------

# tolerances of tests/test_dispatch.py (segment sums, second moments,
# Mahalanobis head) and tests/test_quant_serving.py (int8 matmul)
OP_TOL = dict(segment_sum=(1e-4, 1e-5), class_second_moment=(1e-4, 1e-5),
              mahalanobis=(1e-2, 1e-4), int8_matmul=(1e-4, 1e-5))
LEARNER_LOSS_RTOL = 5e-2
LEARNER_ARGMAX_AGREE = 0.9


def _op_pairs(b: int, f: int, c: int) -> dict:
    """(pallas result, reference) for every main-path kernel on normal
    inputs at width (b, f, c).  The class statistics and the Mahalanobis
    form are checked against float32 math at HIGHEST precision, the
    precision those kernels contract at.  The int8 serving matmul
    contracts at the default precision, like the ref backend's
    dequantize-then-dot, and is checked against that."""
    key = jax.random.key(5)
    x = jax.random.normal(key, (b, f))
    y = jax.random.randint(jax.random.fold_in(key, 1), (b,), 0, c)
    oh = jax.nn.one_hot(y, c, dtype=jnp.float32)
    mu = jax.random.normal(jax.random.fold_in(key, 2), (c, f))
    a = jax.random.normal(jax.random.fold_in(key, 3), (c, f, f)) / f ** 0.5
    sigma = jnp.einsum("cij,ckj->cik", a, a, precision=HIGHEST) + jnp.eye(f)
    with jax.default_matmul_precision("highest"):
        chol = jax.vmap(jnp.linalg.cholesky)(sigma)
        sinv = dispatch.chol_inverse(chol)
    qs = quant.quantize(jax.random.normal(jax.random.fold_in(key, 4), (f, f)))

    @jax.jit
    def pallas(x, oh, mu, chol, sinv):
        return dict(
            segment_sum=dispatch.segment_sum(x, oh, backend="pallas"),
            class_second_moment=dispatch.class_second_moment(
                x, oh, backend="pallas"),
            mahalanobis=dispatch.mahalanobis_head(x, mu, chol,
                                                  backend="pallas",
                                                  sinv=sinv),
            int8_matmul=dispatch.int8_matmul(x, qs, backend="pallas"))

    @jax.jit
    def reference(x, oh, mu, chol, sinv):
        diff = x[:, None, :] - mu[None]
        return dict(
            segment_sum=jnp.einsum("bf,bc->cf", x, oh, precision=HIGHEST),
            class_second_moment=jnp.einsum("bc,bi,bj->cij", oh, x, x,
                                           precision=HIGHEST),
            mahalanobis=jnp.einsum("bci,cij,bcj->bc", diff, sinv, diff,
                                   precision=HIGHEST),
            int8_matmul=dispatch.int8_matmul(x, qs, backend="ref"))

    got = pallas(x, oh, mu, chol, sinv)
    want = reference(x, oh, mu, chol, sinv)
    hlo = pallas.lower(x, oh, mu, chol, sinv).compile().as_text()
    return dict(got=got, want=want,
                custom_calls=hlo.count("tpu_custom_call"))


def _require_on_chip(report: dict) -> None:
    """The kernels must have lowered to Mosaic, not the interpreter."""
    _check(not report["interpret_mode"],
           "Pallas kernels ran in interpret mode")
    _check(report["adapt_custom_call"],
           "the compiled pallas adapt has no tpu_custom_call")
    _check(report["op_custom_calls"] >= 4,
           f"{report['op_custom_calls']} tpu_custom_call(s) for 4 kernels")


def phase_kernels(shape: EpisodicShape, learner, params) -> dict:
    """Op level: each kernel against its reference (see ``_op_pairs``) at
    the chip's widths (B = the support size, F = feature_dim, C = way).
    Learner level: Simple CNAPs adapt + predict with kernel_backend='pallas'
    against 'ref' on one task."""
    report = dict(interpret_mode=tpu_compat.interpret_mode())
    ops = _op_pairs(shape.way * shape.shot, shape.feature_dim, shape.way)
    report["op_custom_calls"] = ops["custom_calls"]
    bad = []
    for name, (atol, rtol) in OP_TOL.items():
        got, want = np.asarray(ops["got"][name]), np.asarray(ops["want"][name])
        report[f"{name}_max_abs"] = float(np.max(np.abs(got - want)))
        if not np.allclose(got, want, atol=atol, rtol=rtol):
            bad.append(f"{name} max |diff| {report[f'{name}_max_abs']} "
                       f"(atol {atol}, rtol {rtol})")

    task = sample_image_task(jax.random.key(7), shape.image_config())
    batch = collate_task_batch([task])
    keys = jax.vmap(lambda i: task_key(jax.random.key(0), i))(jnp.arange(1))
    lite = LiteSpec(exact=True, chunk_size=shape.lite_chunk)
    out = {}
    for backend in ("ref", "pallas"):
        def adapt(p, b, k, backend=backend):
            with dispatch.use_backend(backend):
                return learner.adapt_batch(p, b, k, lite)

        def predict(p, st, qx, backend=backend):
            with dispatch.use_backend(backend):
                return learner.predict_batch(p, st, qx)

        adapt_c = jax.jit(adapt).lower(params, batch, keys).compile()
        states = adapt_c(params, batch, keys)
        logits = jax.jit(predict)(params, states, batch.query_x)[0]
        logp = jax.nn.log_softmax(logits, axis=-1)
        loss = -float(jnp.mean(jnp.take_along_axis(
            logp, batch.query_y[0][:, None], axis=-1)))
        out[backend] = (np.asarray(logits), loss,
                        "tpu_custom_call" in adapt_c.as_text())
    (l_ref, loss_ref, _), (l_pal, loss_pal, cc) = out["ref"], out["pallas"]
    report.update(
        adapt_custom_call=cc, loss_ref=loss_ref, loss_pallas=loss_pal,
        logits_max_rel=_rel(l_pal, l_ref),
        argmax_agree=float(np.mean(np.argmax(l_pal, -1)
                                   == np.argmax(l_ref, -1))))
    if not np.isclose(loss_pal, loss_ref, rtol=LEARNER_LOSS_RTOL, atol=0):
        bad.append(f"query loss pallas {loss_pal} vs ref {loss_ref}")
    if report["argmax_agree"] < LEARNER_ARGMAX_AGREE:
        bad.append(f"argmax agreement {report['argmax_agree']}")
    if not np.all(np.isfinite(l_pal)):
        bad.append("non-finite pallas logits")
    _check(not bad, "; ".join(bad))
    _require_on_chip(report)
    return report


# ---------------------------------------------------------------------------
# (d) precision
# ---------------------------------------------------------------------------


def phase_precision(shape: EpisodicShape, learner, params) -> dict:
    """One step's loss and gradients at the default matmul precision
    against ``jax.default_matmul_precision('highest')``.  Reported, and
    gated only on being finite."""
    grads_fn = make_batched_meta_grads(
        learner, LiteSpec(h=shape.lite_h, chunk_size=shape.lite_chunk))
    batch = collate_task_batch(
        [sample_image_task(jax.random.key(11), shape.image_config())])
    key = jax.random.key(3)
    loss_d, _, g_d = jax.jit(grads_fn)(params, batch, key)

    def highest(p, b, k):
        with jax.default_matmul_precision("highest"):
            return grads_fn(p, b, k)

    loss_h, _, g_h = jax.jit(highest)(params, batch, key)
    flat_d = np.concatenate([np.ravel(x) for x in jax.tree.leaves(g_d)])
    flat_h = np.concatenate([np.ravel(x) for x in jax.tree.leaves(g_h)])
    report = dict(
        loss_default=float(loss_d), loss_highest=float(loss_h),
        loss_rel_diff=abs(float(loss_d) - float(loss_h))
        / max(abs(float(loss_h)), 1e-30),
        grad_rel_diff=float(np.linalg.norm(flat_d - flat_h)
                            / max(np.linalg.norm(flat_h), 1e-30)))
    _check(all(np.isfinite(v) for v in report.values()),
           f"non-finite precision gap {report}")
    return report


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def _first_moment(state) -> list:
    return jax.tree.leaves(state["opt"]["mu"])


def phase_dp(shape: EpisodicShape, n: int = 4) -> dict:
    """A dp_shards=n step (tasks_per_step=n) against the one-device step
    on the same batch and state (accum_steps=n: one task at a time, the
    same per-task keys).

    Tolerance: both compute the same float32 math, reduced across devices
    in another order and fused differently, and the chip's default matmul
    precision rounds dot operands to bfloat16.  Loss and gradient norm
    agree to 1e-3 relative; the gradient (AdamW's first moment, 0.1 g) to
    1e-3 of each leaf's largest entry.  AdamW's first step moves a weight
    by lr * g / (|g| + eps), which depends on the sign of g alone: where a
    gradient sits below that 1e-3 noise floor its sign may round either
    way and the weight may differ by up to 2 lr; everywhere else the
    updated weights agree to 1e-6."""
    base = ["--episodic", "--learner", "simple_cnaps", "--steps", "1",
            "--tasks-per-step", str(n), "--no-donate"] + shape_argv(shape)
    p = train_launcher.build_parser()
    dp = train_launcher.build_episodic(p.parse_args(
        base + ["--dp-shards", str(n)]))
    one = train_launcher.build_episodic(p.parse_args(
        base + ["--accum-steps", str(n)]))
    batch = dp.batch_at(0)
    sharded = dp.batch_put(batch)
    sx = sharded["tasks"].support_x
    shards = sorted((s.device.id, s.data.shape[0])
                    for s in sx.addressable_shards)
    _check(len({d for d, _ in shards}) == n and all(r == 1 for _, r in shards),
           f"support batch not split one task per device: {shards}")
    state_dp, m_dp = jax.jit(dp.step)(dp.state, sharded)
    state_one, m_one = jax.jit(one.step)(one.state, batch)
    lr = dp.meta.lr
    report = dict(task_rows_per_device=shards,
                  loss_dp=float(m_dp["loss"]), loss_one=float(m_one["loss"]),
                  loss_rel=_rel(m_dp["loss"], m_one["loss"]),
                  grad_norm_rel=_rel(m_dp["grad_norm"], m_one["grad_norm"]),
                  grad_rel=max(_rel(a, b) for a, b in zip(
                      _first_moment(state_dp), _first_moment(state_one))))
    worst = 0.0
    for p_dp, p_one, g in zip(jax.tree.leaves(state_dp["params"]),
                              jax.tree.leaves(state_one["params"]),
                              _first_moment(state_one)):
        g = np.abs(np.asarray(g))
        stable = g > 1e-3 * g.max()
        d = np.abs(np.asarray(p_dp) - np.asarray(p_one))
        _check(np.all(d <= 2 * lr * (1 + 1e-3)), "update beyond 2 lr")
        if stable.any():
            worst = max(worst, float(d[stable].max()))
    report["params_max_abs_where_stable"] = worst
    _check(report["loss_rel"] <= 1e-3 and report["grad_norm_rel"] <= 1e-3,
           f"loss/grad norm differ: {report}")
    _check(report["grad_rel"] <= 1e-3, f"gradients differ: {report}")
    _check(worst <= 1e-6, f"updated params differ: {report}")
    return report


def phase_replicas(shape: EpisodicShape, learner, params, n: int = 4,
                   query_chunk: int = 64) -> dict:
    """``ReplicatedServeEngine`` with n one-chip replicas against one
    engine on the same requests: one user per replica, per-uid logits
    bit-equal, every replica adapting its own user."""
    uids, seen = [], set()
    uid = 0
    while len(uids) < n:
        if uid_replica(uid, n) not in seen:
            seen.add(uid_replica(uid, n))
            uids.append(uid)
        uid += 1
    kw = dict(lite=LiteSpec(exact=True, chunk_size=shape.lite_chunk),
              n_slots=1, query_chunk=query_chunk,
              support_buckets=(shape.way * shape.shot,))
    router = ReplicatedServeEngine(
        learner, params, replicas=n, meshes=make_replica_mesh(n, 1),
        serve_layout="replicated", **kw)
    solo = EpisodicServeEngine(learner, params, **kw)
    reqs = [_request(u, shape, shape.shot) for u in uids]
    solo_reqs = [_request(u, shape, shape.shot) for u in uids]
    router.run_to_completion(reqs)
    solo.run_to_completion(solo_reqs)
    _check(all(_served(r) for r in reqs + solo_reqs), "unserved request")
    for a, b in zip(reqs, solo_reqs):
        _check(np.array_equal(a.all_logits(), b.all_logits()),
               f"uid {a.uid}: replica logits differ from the single engine")
    adapted = [p["tasks_adapted"] for p in router.stats()["per_replica"]]
    _check(adapted == [1] * n, f"tasks adapted per replica {adapted}")
    homes = [sorted({d.id for leaf in jax.tree.leaves(eng._weights)
                     for d in leaf.devices()}) for eng in router.replicas]
    _check(homes == [[d.id] for d in jax.devices()[:n]],
           f"replica weights not one chip each: {homes}")
    return dict(uids=uids, tasks_adapted_per_replica=adapted,
                weight_devices=homes)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def _run(name: str, fn, *args, **kwargs):
    t0 = time.perf_counter()
    try:
        out = fn(*args, **kwargs)
    except (Exception, SystemExit):  # a launcher exits on a failed run
        traceback.print_exc()
        print(f"phase {name}: FAILED after {time.perf_counter() - t0:.1f} s "
              f"peak_bytes_in_use={peak_bytes()}", flush=True)
        return None, False
    shown = {k: v for k, v in (out or {}).items() if k != "state"}
    print(f"phase {name}: ok wall_s={time.perf_counter() - t0:.1f} "
          f"peak_bytes_in_use={peak_bytes()} {shown}", flush=True)
    return out, True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the dp_shards=4 training and the "
                         "4-replica serving comparisons")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    n_dev = len(jax.devices())
    if args.four_chips and n_dev < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found {n_dev}",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    shape = CHIP_SHAPE
    print(f"chip_smoke: {n_dev} x {dev.device_kind}; {shape}", flush=True)

    learner = build_learner("simple_cnaps", shape)
    oks = []
    if args.four_chips:
        params = learner.init(jax.random.key(0))
        oks.append(_run("dp", phase_dp, shape, 4)[1])
        oks.append(_run("replicas", phase_replicas, shape, learner, params,
                        4)[1])
    else:
        trained, ok = _run("train[simple_cnaps]", phase_train, shape,
                           "simple_cnaps")
        oks.append(ok)
        oks.append(_run("train[protonets]", phase_train, shape,
                        "protonets")[1])
        params = (trained["state"]["params"] if trained is not None
                  else learner.init(jax.random.key(0)))
        oks.append(_run("serve", phase_serve, shape, learner, params)[1])
        oks.append(_run("kernels", phase_kernels, shape, learner, params)[1])
        oks.append(_run("precision", phase_precision, shape, learner,
                        params)[1])
    if not all(oks):
        print(f"chip_smoke: {oks.count(False)} phase(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
