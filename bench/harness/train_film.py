"""LITE meta-training cells of learners on a frozen FiLM-conditioned
backbone (Simple CNAPs on EfficientNet-B0).

The run is ``bench/harness/train.py``'s: set-up builds the launcher's
episodic job (``build_episodic``, with the configuration's learner and
``--backbone``), puts the benchmark's weights from the seed into its
state (``bench/weights_simple_cnaps.py``), and drives the one jitted
step through the checked steps from its prefetcher on tasks made on the
device; the window runs the same step for ``--seconds``, two steps in
flight; then the reference (``bench/reference_simple_cnaps.py``) follows
the checked steps from the same weights and tasks.  What differs:

* ``compare`` reads the trained parameters only, the set encoder and the
  FiLM generator; the backbone is frozen, and ``backbone_change`` is the
  largest change of any of its weights over the checked steps (limit 0).
* The traced steps keep the trace's op names (``bench/layers.py``
  ``load``) and add the step program's device time by the learner's
  scopes (``scope_seconds``) for the ``cnaps_*`` metrics.

Correctness readings (the program on many seeds, the float8 control and
a planted fault on a few), one JSON line per run:

    PYTHONPATH=src python3 -m bench.harness.train_film \\
        --workload <cell> --seeds 1-6 --control-seeds 101-102 \\
        --fault half_batch --fault-seeds 201-202
"""
from __future__ import annotations

import argparse
import bisect
import gc
import json
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from bench import common, data, faults, layers
from bench import flops_efficientnet as flops
from bench import reference_simple_cnaps as reference
from bench import trace as tracemod
from bench import weights_simple_cnaps as weights
from bench.harness.train import _same_tree, checks, compare
from bench.readings import _seeds

TRAINED = ("enc", "film_gen")
# the learner's scopes, in the op names of the compiled step; none nests
# in another, and LITE's scopes nest inside the first two
SCOPES = ("cnaps.set_encoder", "cnaps.film_generator", "cnaps.class_stats",
          "cnaps.covariance", "learner.query")
COMPLEMENT = "cnaps.class_stats/lite.complement"
OUTSIDE = ("lite.split", "train.optimizer")


def _argv(config: Dict, traffic: Dict) -> List[str]:
    return ["--episodic", "--learner", config["learner"],
            "--backbone", config["backbone"]["kind"],
            "--steps", "1000000", "--ckpt-every", "1000000",
            "--tasks-per-step", str(traffic["tasks_per_step"]),
            "--data-source", "device",
            "--prefetch", str(traffic["prefetch"]),
            "--way", str(config["way"]), "--shot", str(traffic["shot"]),
            "--query-per-class", str(traffic["query_per_class"]),
            "--image-size", str(config["image_size"]),
            "--lite-h", str(config["lite"]["h"]),
            "--lite-chunk", str(config["lite"]["chunk"]),
            "--peak-lr", str(config["optimizer"]["lr"])]


def scope_of(op_name: str) -> str:
    """The learner's scope an op runs under (``COMPLEMENT`` for the class
    statistics' no-grad complement), else ``lite.split`` or
    ``train.optimizer`` where the name holds one, else ``other``."""
    for scope in SCOPES:
        i = op_name.find(scope)
        if i >= 0:
            if scope == "cnaps.class_stats" and "lite.complement" in \
                    op_name[i:]:
                return COMPLEMENT
            return scope
    return next((s for s in OUTSIDE if s in op_name), "other")


def scope_seconds(events: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The step program's device seconds over the ``bench.window`` span
    by ``scope_of`` of each op's name (loop containers left out, averaged
    over devices), with ``step`` their sum; the step program is the one
    with the most device time in the window.  None where the trace holds
    no window or names no program."""
    wins = [(s, s + d) for s, d, n in events["host"] if n == tracemod.WINDOW]
    mods = events.get("modules") or []
    devices = [i for i, ops in enumerate(events["device"]) if ops]
    if not wins or not devices or len(mods) < len(events["device"]):
        return None
    w0, w1 = wins[0]
    names = events.get("op_names") or {}
    per_mod: Dict[str, Dict[str, float]] = {}
    for i in devices:
        ivs = sorted(mods[i])
        starts = [s for s, _, _ in ivs]
        for s, d, name, cat in events["device"][i]:
            s0, e0 = max(s, w0), min(s + d, w1)
            j = bisect.bisect_right(starts, s) - 1
            if e0 <= s0 or cat in tracemod.CONTAINERS or j < 0:
                continue
            ms, md, mod = ivs[j]
            if s + d > ms + md:
                continue
            key = scope_of(names.get(mod, {}).get(name, ""))
            acc = per_mod.setdefault(mod, {})
            acc[key] = acc.get(key, 0.0) + (e0 - s0)
    if not per_mod:
        return None
    step = max(per_mod, key=lambda m: sum(per_mod[m].values()))
    n = len(devices)
    out = {k: v / n / 1e9 for k, v in per_mod[step].items()}
    out["step"] = sum(per_mod[step].values()) / n / 1e9
    return out


def _traced_steps(step_fn, state_box, pf, s: int, n: int):
    """``n`` more steps under the profiler in the window's own pattern
    (``bench/harness/train.py``'s), reduced with the op names kept."""
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
        events = layers.load(d)
        red = tracemod.reduce(events)
        if red is not None:
            red["steps"] = n
            red["scopes"] = scope_seconds(events)
        return red
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _trained(tree) -> Dict[str, Any]:
    return {k: tree[k] for k in TRAINED}


def compare_trained(config: Dict, prog: Dict, ref: Dict) -> Dict[str, Any]:
    """``bench/harness/train.py``'s ``compare`` over the trained
    parameters, with ``backbone_change``: the largest change of a frozen
    backbone weight in the program over the checked steps."""
    nums = compare(
        config,
        dict(prog, mu=_trained(prog["mu"]),
             params0=_trained(prog["params0"]),
             params_end=_trained(prog["params_end"])),
        dict(ref, grad=_trained(ref["grad"]), params=_trained(ref["params"])))
    nums["backbone_change"] = max(
        float(np.max(np.abs(np.asarray(a, np.float64)
                            - np.asarray(b, np.float64))))
        for a, b in zip(jax.tree.leaves(prog["params_end"]["bb"]),
                        jax.tree.leaves(prog["params0"]["bb"])))
    return nums


def _batch_fn(config: Dict, traffic: Dict):
    return data.train_batch_fn(
        dict(way=config["way"], shot=traffic["shot"],
             query_per_class=traffic["query_per_class"],
             image_size=config["image_size"]), traffic["tasks_per_step"])


def reference_control(cell: common.Cell, seed: int) -> Dict[str, Any]:
    """The control: the reference in the configuration's control type put
    in the program's place, read against the float32 reference."""
    config, traffic = cell.config, cell.traffic
    seed_key = jax.random.key(seed)
    make_batch = _batch_fn(config, traffic)
    batches = [(make_batch(seed_key, i), data.step_key(seed_key, i))
               for i in range(traffic["checked_steps"])]
    params = weights.make_params(config, seed)
    lo = reference.train(config, params, batches,
                         dtype=config["control"]["train"]["reference_dtype"])
    hi = reference.train(config, params, batches)
    b1 = config["optimizer"]["b1"]
    prog = dict(losses=lo[0], params0=jax.device_get(params),
                params_end=lo[2],
                mu=jax.tree.map(lambda g: (1.0 - b1) * np.asarray(
                    g, np.float32), lo[1]))
    return compare_trained(config, prog, dict(losses=hi[0], grad=hi[1],
                                              params=hi[2]))


def run(cell: common.Cell, *, seed: int, seconds: float, trace: bool,
        devices, t_process: float,
        wrap_step: Optional[Callable] = None, window: bool = True,
        readings: Optional[Dict[str, Any]] = None):
    """One run of the cell; the arguments are ``bench/harness/train.py``
    ``run``'s."""
    from repro.core.episodic import TaskBatch
    from repro.launch import train as launcher
    from repro.train.pipeline import Prefetcher

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
    make_batch = _batch_fn(config, traffic)

    def batch_at(s: int):
        arrays = make_batch(seed_key, s)
        return dict(tasks=TaskBatch(way=config["way"], **arrays),
                    key=data.step_key(seed_key, s))

    step = job.step if wrap_step is None else wrap_step(job.step)
    step_fn = jax.jit(step, donate_argnums=(0,) if job.meta.donate else ())
    pf = Prefetcher(batch_at, 0, 1 << 40, depth=job.meta.prefetch,
                    put=job.batch_put)
    checked = traffic["checked_steps"]
    losses, mu, nonfinite = [], None, 0
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
            skipped = sum(int(float(m.get("nonfinite", 0.0)) >= 0.5)
                          for m in ms)
            n_tasks = len(ms) * traffic["tasks_per_step"]
            result["attempted"] = n_tasks
            result["failed"] = skipped * traffic["tasks_per_step"]
            obs["tasks_per_s"] = n_tasks / (t1 - t0)
            obs["window_steps"] = len(ms)
        window_compiles = compiles.count - c0
        print(f"bench: window built {window_compiles} executable(s); "
              f"{n_tasks} tasks; setup {t_setup:.3f} s", flush=True)
        if trace and window:
            box = [state]
            obs["trace"] = _traced_steps(step_fn, box, pf, s,
                                         traffic["trace_steps"])
            state = box[0]
            print(f"bench: device seconds by scope over "
                  f"{traffic['trace_steps']} steps "
                  f"{(obs['trace'] or {}).get('scopes')}", flush=True)
        peak = common.peak_bytes(devices)
    finally:
        pf.close()
    per_task = flops.train_task(config, config["way"] * traffic["shot"],
                                config["way"] * traffic["query_per_class"])
    obs["model_flops_per_task"] = per_task["total"]
    obs["backbone_complement_flops_per_task"] = per_task["backbone_complement"]
    obs["backbone_complement_bytes_per_task"] = \
        per_task["backbone_complement_bytes"]
    obs["tasks_per_step"] = traffic["tasks_per_step"]
    obs["peaks"] = common.peaks(devices[0].device_kind)
    obs["memory_peak_bytes"] = peak
    obs["setup_s"] = t_setup

    # free the program's state before the reference runs
    del state, job, step_fn
    gc.collect()
    batches = [(make_batch(seed_key, i), data.step_key(seed_key, i))
               for i in range(checked)]
    r_losses, r_grad, r_params = reference.train(
        config, weights.make_params(config, seed), batches)
    nums = compare_trained(
        config, dict(mu=mu, params0=params0, params_end=params_end,
                     losses=losses),
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


def collect(cell: common.Cell, devices, seeds: List[int],
            control_seeds: List[int], fault: str = "",
            fault_seeds: List[int] = ()):
    """``bench/readings.py``'s records for this harness: the program on
    ``seeds``, the control on ``control_seeds``, ``fault`` planted on
    ``fault_seeds``; a control or fault that crashes has failed."""
    runs = [("program", s, None) for s in seeds]
    runs += [("control", s, None) for s in control_seeds]
    runs += [("fault:" + fault, s, faults.TRAIN[fault]) for s in fault_seeds]
    for kind, seed, wrap in runs:
        t = time.monotonic()
        try:
            if kind == "control":
                nums = reference_control(cell, seed)
                ok = all(c.ok for c in checks(cell.config, nums))
            else:
                nums = {}
                result, found = run(cell, seed=seed, seconds=0.0,
                                    trace=False, devices=devices,
                                    t_process=t, window=False,
                                    wrap_step=wrap, readings=nums)
                ok = bool(result["correct"]) and all(c.ok for c in found)
        except Exception as e:  # noqa: BLE001  (a crash is a failed run)
            nums, ok = {"error": repr(e)}, False
        yield dict(workload=cell.name, kind=kind, seed=seed, correct=ok,
                   readings=nums, seconds=time.monotonic() - t)
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default="",
                    help="a fault of bench/faults.py to plant")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    cell = common.load_cell(args.workload)
    common.enable_compile_cache()
    devices = common.require_chips(cell.chips)
    for rec in collect(cell, devices, _seeds(args.seeds),
                       _seeds(args.control_seeds), args.fault,
                       _seeds(args.fault_seeds)):
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
