"""LITE meta-training cells.

Set-up builds the launcher's episodic job (``repro.launch.train.
build_episodic``: the learner, the batched LITE step, AdamW, prefetch
depth and donation) from the configuration, puts the benchmark's own
weights from the seed into its state, and drives that one jitted step
through the first ``checked_steps`` steps from its prefetcher, on tasks
made on the device from the seed.  The window then runs the same step on
the same state for ``--seconds``, two steps in flight, and ends in
``block_until_ready`` on the last state.  After the window the program's
state is freed and the reference follows the first steps from the same
weights and tasks: each step's loss, the first gradient as AdamW received
it (its first moment after one step, over 1 - b1) and the parameters'
change after the checked steps are compared leaf by leaf (``compare``).
"""
from __future__ import annotations

import gc
import shutil
import statistics
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from bench import common, data, flops, reference, weights
from bench import trace as tracemod


def _argv(config: Dict, traffic: Dict) -> List[str]:
    bb = config["backbone"]
    argv = ["--episodic", "--learner", config["learner"],
            "--steps", "1000000", "--ckpt-every", "1000000",
            "--tasks-per-step", str(traffic["tasks_per_step"]),
            "--data-source", "device",
            "--prefetch", str(traffic["prefetch"]),
            "--widths", ",".join(str(w) for w in bb["widths"]),
            "--feature-dim", str(bb["feature_dim"]),
            "--way", str(config["way"]), "--shot", str(traffic["shot"]),
            "--query-per-class", str(traffic["query_per_class"]),
            "--image-size", str(config["image_size"]),
            "--lite-h", str(config["lite"]["h"]),
            "--lite-chunk", str(config["lite"]["chunk"]),
            "--peak-lr", str(config["optimizer"]["lr"])]
    return argv


def _same_tree(a, b) -> bool:
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    return ta == tb and all(x.shape == y.shape and x.dtype == y.dtype
                            for x, y in zip(la, lb))


def _leaves(tree) -> List[np.ndarray]:
    return [np.asarray(a, np.float64) for a in jax.tree.leaves(tree)]


def _gaps(prog: List[np.ndarray], ref: List[np.ndarray], keep: List[int]):
    """Per kept leaf: the gap of the norms and the norm of the difference,
    each over the larger of the reference's norm of that leaf and of the
    median kept leaf."""
    n_ref = [float(np.linalg.norm(a)) for a in ref]
    med = statistics.median([n_ref[i] for i in keep])
    norm, vec = [], []
    for i in keep:
        den = max(n_ref[i], med)
        norm.append(abs(float(np.linalg.norm(prog[i])) - n_ref[i]) / den)
        vec.append(float(np.linalg.norm(prog[i] - ref[i])) / den)
    return norm, vec


def compare(config: Dict, prog: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers ``correct`` may compare (the configuration's
    ``limits.train`` says which), with the readings beside them.

    The first gradient as AdamW received it (the program's first moment
    after one step, over 1 - b1) and the parameters' change after the
    checked steps, leaf by leaf against the reference: ``grad_gap`` and
    ``update_gap`` are the median leaf's gap of the norms, ``grad_dir`` and
    ``update_dir`` the median leaf's norm of the difference (which sees a
    gradient that points elsewhere with the same length), ``*_worst`` the
    worst leaf's; each over the larger of the reference's norm of that
    leaf and of the median leaf's.  ``loss_gap`` is the first step's
    relative loss gap; the later steps' are printed (trajectories part
    after AdamW's sign-like first steps, ``PERF.md``).  Leaves whose
    reference gradient is under a thousandth of the median leaf's are left
    out: they are nought to rounding (the head's bias, which every
    distance cancels) and move under AdamW by round-off alone."""
    b1 = config["optimizer"]["b1"]
    g_ref = _leaves(ref["grad"])
    g_prog = [a / (1.0 - b1) for a in _leaves(prog["mu"])]
    n_g = [float(np.linalg.norm(a)) for a in g_ref]
    med_g = statistics.median(n_g)
    keep = [i for i, n in enumerate(n_g) if n >= 1e-3 * med_g]
    p0 = _leaves(prog["params0"])
    d_ref = [a - b for a, b in zip(_leaves(ref["params"]), p0)]
    d_prog = [a - b for a, b in zip(_leaves(prog["params_end"]), p0)]
    grad, grad_vec = _gaps(g_prog, g_ref, keep)
    upd, upd_vec = _gaps(d_prog, d_ref, keep)
    losses = [abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                  ref["losses"])]
    return dict(loss_gap=losses[0],
                grad_gap=statistics.median(grad),
                update_gap=statistics.median(upd),
                grad_dir=statistics.median(grad_vec),
                update_dir=statistics.median(upd_vec),
                grad_gap_worst=max(grad), update_gap_worst=max(upd),
                grad_dir_worst=max(grad_vec), update_dir_worst=max(upd_vec),
                loss_gap_steps=losses, leaves_compared=len(keep))


def checks(config: Dict, nums: Dict[str, float]) -> List[common.Check]:
    """The configuration's limits, each against its number."""
    return [common.Check(k, float(nums[k]), float(v))
            for k, v in config["limits"]["train"].items()]


def reference_control(cell: common.Cell, seed: int):
    """The control: the reference put in the program's place and computed
    in the configuration's control type (``control.train.
    reference_dtype``; AdamW stays in float32), read against the float32
    reference by the same comparison."""
    config, traffic = cell.config, cell.traffic
    dtype = config["control"]["train"]["reference_dtype"]
    seed_key = jax.random.key(seed)
    make_batch = data.train_batch_fn(
        dict(way=config["way"], shot=traffic["shot"],
             query_per_class=traffic["query_per_class"],
             image_size=config["image_size"]), traffic["tasks_per_step"])
    batches = [(make_batch(seed_key, i), data.step_key(seed_key, i))
               for i in range(traffic["checked_steps"])]
    params = weights.make_params(config, seed)
    lo = reference.train(config, params, batches, dtype=dtype)
    hi = reference.train(config, params, batches)
    b1 = config["optimizer"]["b1"]
    prog = dict(losses=lo[0], params0=jax.device_get(params),
                params_end=lo[2],
                mu=jax.tree.map(lambda g: (1.0 - b1) * np.asarray(
                    g, np.float32), lo[1]))
    return compare(config, prog, dict(losses=hi[0], grad=hi[1],
                                      params=hi[2]))


def run(cell: common.Cell, *, seed: int, seconds: float, trace: bool,
        devices, t_process: float,
        wrap_step: Optional[Callable] = None, window: bool = True,
        readings: Optional[Dict[str, Any]] = None):
    """One run of a training cell.  ``wrap_step`` wraps the program's step
    (the tests plant faults with it); ``window=False`` skips the measured
    window (the correctness readings need none); ``readings``, where given,
    receives every number of ``compare``."""
    from repro.core.episodic import TaskBatch
    from repro.launch import train as launcher

    config, traffic = cell.config, cell.traffic
    compiles = common.CompileCounter()
    seed_key = jax.random.key(seed)
    args = launcher.build_parser().parse_args(_argv(config, traffic))
    job = launcher.build_episodic(args)
    opt = config["optimizer"]
    assert job.meta.max_grad_norm == opt["max_grad_norm"]
    assert job.meta.lr == opt["lr"] and job.meta.schedule is None
    params0_dev = weights.make_params(config, seed)
    if not _same_tree(params0_dev, job.state["params"]):
        raise RuntimeError("the configuration's weights do not match the "
                           "program's parameter tree")
    state = dict(params=params0_dev, opt=job.state["opt"])
    job.state = None
    params0 = jax.device_get(state["params"])

    make_batch = data.train_batch_fn(
        dict(way=config["way"], shot=traffic["shot"],
             query_per_class=traffic["query_per_class"],
             image_size=config["image_size"]),
        traffic["tasks_per_step"])

    def batch_at(s: int):
        arrays = make_batch(seed_key, s)
        return dict(tasks=TaskBatch(way=config["way"], **arrays),
                    key=data.step_key(seed_key, s))

    step = job.step if wrap_step is None else wrap_step(job.step)
    step_fn = jax.jit(step, donate_argnums=(0,) if job.meta.donate else ())
    from repro.train.pipeline import Prefetcher

    pf = Prefetcher(batch_at, 0, 1 << 40, depth=job.meta.prefetch,
                    put=job.batch_put)
    checked = traffic["checked_steps"]
    losses, mu, nonfinite = [], None, 0
    s = 0
    try:
        for s in range(checked):
            state, m = step_fn(state, pf.get(s))
            losses.append(float(m["loss"]))
            nonfinite += int(float(m.get("nonfinite", 0.0)) >= 0.5)
            if s == 0:
                mu = jax.device_get(state["opt"]["mu"])
        params_end = jax.device_get(state["params"])
        s = checked
        result: Dict[str, Any] = dict(correct=True, attempted=0, failed=0)
        obs: Dict[str, Any] = dict(config=config, traffic=traffic)
        t_setup = time.monotonic() - t_process
        c0 = compiles.count
        n_tasks = 0
        if window:
            t0 = time.monotonic()
            prev, ms = None, []
            while True:
                state, m = step_fn(state, pf.get(s))
                s += 1
                ms.append(m)
                if prev is not None:
                    jax.block_until_ready(prev)
                prev = m["loss"]
                if time.monotonic() - t0 >= seconds:
                    break
            jax.block_until_ready(state)
            t1 = time.monotonic()
            n_steps = len(ms)
            skipped = sum(int(float(m.get("nonfinite", 0.0)) >= 0.5)
                          for m in ms)
            n_tasks = n_steps * traffic["tasks_per_step"]
            result["attempted"] = n_tasks
            result["failed"] = skipped * traffic["tasks_per_step"]
            obs["tasks_per_s"] = n_tasks / (t1 - t0)
            obs["window_steps"] = n_steps
        window_compiles = compiles.count - c0
        print(f"bench: window built {window_compiles} executable(s); "
              f"{n_tasks} tasks; setup {t_setup:.3f} s", flush=True)
        if trace and window:
            box = [state]
            obs["trace"] = _traced_steps(step_fn, box, pf, s,
                                         traffic["trace_steps"])
            state = box[0]
        peak = common.peak_bytes(devices)
    finally:
        pf.close()
    per_task = flops.train_task(config, config["way"] * traffic["shot"],
                                config["way"] * traffic["query_per_class"])
    obs["model_flops_per_task"] = per_task["total"]
    obs["conv_flops_per_task"] = per_task["conv"]
    obs["conv_bytes_per_task"] = per_task["conv_bytes"]
    obs["tasks_per_step"] = traffic["tasks_per_step"]
    obs["peaks"] = common.peaks(devices[0].device_kind)
    obs["memory_peak_bytes"] = peak
    obs["setup_s"] = t_setup

    # free the program's state before the reference runs
    del state, job, step_fn
    gc.collect()
    ref_params = weights.make_params(config, seed)
    batches = []
    for i in range(checked):
        arrays = make_batch(seed_key, i)
        batches.append((arrays, data.step_key(seed_key, i)))
    r_losses, r_grad, r_params = reference.train(config, ref_params, batches)
    nums = compare(config, dict(mu=mu, params0=params0,
                                params_end=params_end, losses=losses),
                   dict(grad=r_grad, params=r_params, losses=r_losses))
    print(f"bench: losses {losses} reference {r_losses}; readings "
          f"{nums}", flush=True)
    if readings is not None:
        readings.update(nums)
    found = checks(config, nums) + [
        common.Check("skipped_steps", float(nonfinite), 0.0),
        common.Check("window_compiles", float(window_compiles), 0.0)]
    result["metrics"] = common.metrics(cell, obs, trace)
    result["device"] = common.device_info(devices, peak)
    if trace and obs.get("trace"):
        result["device"]["busy_s"] = obs["trace"]["busy_s"]
        result["device"]["window_s"] = obs["trace"]["window_s"]
        result["breakdown"] = obs["trace"]["breakdown"]
    return result, found


def _traced_steps(step_fn, state_box, pf, s, n):
    """``n`` more steps under the profiler, in the window's own pattern;
    returns the reduced trace."""
    d = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(d)
        state = state_box[0]
        with jax.profiler.TraceAnnotation(tracemod.WINDOW):
            prev = None
            for k in range(n):
                with jax.profiler.TraceAnnotation("bench.batch"):
                    b = pf.get(s + k)
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    state, m = step_fn(state, b)
                if prev is not None:
                    with jax.profiler.TraceAnnotation("bench.wait"):
                        jax.block_until_ready(prev)
                prev = m["loss"]
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.block_until_ready(state)
        jax.profiler.stop_trace()
        state_box[0] = state
        events = tracemod.load(d)
        red = tracemod.reduce(events)
        if red is not None:
            red["steps"] = n
        return red
    finally:
        shutil.rmtree(d, ignore_errors=True)
