"""Device time of the program's layers, read off a profiler trace by the
op names of the program's ``jax.named_scope``s, and a traced run of a
training cell that reports it:

    python3 bench/layers.py --workload <cell> --seed <n> [--seconds 10]

The program names its training layers ``lite.split``, ``lite.h_pass``,
``lite.complement``, ``learner.query`` and ``train.optimizer``
(``SCOPES``).  XLA keeps each instruction's scope path in its op name, and
a TPU trace carries that name as the ``tf_op`` stat of each "XLA Ops"
event's metadata.  ``load`` flattens a trace as ``bench.trace.load`` does
(device ops ``[start_ns, dur_ns, name, category]``, host spans
``[start_ns, dur_ns, name]``), keeps the program's ``repro.*`` host spans
beside the benchmark's ``bench.*``, and adds two keys:

    {"modules":  [[start_ns, dur_ns, module], ...] per device,
     "op_names": {module: {instruction: op_name}}}

``modules`` are the programs on each TPU's "XLA Modules" line, named
``<name>(<program id>)``.  An op is looked up in the program whose
interval holds it: instruction names repeat across programs (the
benchmark's class-blob batch maker runs on the same chip as the step).

``bench/run.py``'s result line does not carry these numbers: its
harness (``bench/harness/train.py``) and reduction (``bench/trace.py``)
are not wired to this module.  The traced run here replaces the
harness's traced stretch with one that also times the prefetcher's
waits and keeps the trace for ``layers``.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import bisect  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

SCOPES = ("lite.split", "lite.h_pass", "lite.complement", "learner.query",
          "train.optimizer")
OTHER = "other"
_SCOPE = re.compile(r"(?<![\w.])(%s)(?![\w.])"
                    % "|".join(re.escape(sc) for sc in SCOPES))


def _event_names(ev_md: Dict[int, bytes], st_md: Dict[int, bytes]):
    """Event metadata id -> (name, hlo_category, tf_op, program_id); ""
    and None where the metadata holds no such stat."""
    stat_names = {k: next((x.decode() for g, _, x in trace._fields(v)
                           if g == 2), "") for k, v in st_md.items()}
    out = {}
    for k, v in ev_md.items():
        name, strs, pid = "", {}, None
        for g, _, x in trace._fields(v):
            if g == 2:
                name = x.decode(errors="replace")
            elif g == 5:
                st = {h: y for h, _, y in trace._fields(x)}
                key = stat_names.get(st.get(1))
                if key == "program_id":
                    pid = st.get(3, st.get(2))       # uint64 / int64
                elif key in ("hlo_category", "tf_op"):
                    if 5 in st:                      # str_value
                        strs[key] = st[5].decode(errors="replace")
                    elif 7 in st:                    # ref_value
                        strs[key] = stat_names.get(st[7], "")
        out[k] = (name, strs.get("hlo_category", ""), strs.get("tf_op", ""),
                  pid)
    return out


def load(trace_dir: str) -> Dict[str, Any]:
    """Flatten the newest ``*.xplane.pb`` under ``trace_dir``, with the
    programs' intervals and op names."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        xspace = f.read()
    device: List[List[list]] = []
    modules: List[List[list]] = []
    op_names: Dict[str, Dict[str, str]] = {}
    host: List[list] = []
    for name, lines, ev_md, st_md in trace._planes(xspace):
        is_dev = name.startswith("/device:TPU:")
        if not (is_dev or name.startswith("/host:")):
            continue
        names = _event_names(ev_md, st_md)
        ops, mods, named = [], [], []
        for line in lines:
            lname, evs = trace._line_events(line)
            if is_dev and lname not in ("XLA Ops", "XLA Modules"):
                continue
            for s, d, mid in evs:
                ename, cat, tf_op, pid = names.get(mid, ("", "", "", None))
                if lname == "XLA Modules":
                    mods.append([s, d, ename])
                elif is_dev:
                    ops.append([s, d, ename.split(" = ")[0], cat])
                    if tf_op and pid is not None:
                        named.append((str(pid), ops[-1][2], tf_op))
                elif ename.startswith(("bench.", "repro.")):
                    host.append([s, d, ename])
        if is_dev:
            device.append(ops)
            modules.append(mods)
            by_pid = {m.rsplit("(", 1)[-1].rstrip(")"): m for _, _, m in mods}
            for pid, op, tf_op in named:
                if pid in by_pid:
                    op_names.setdefault(by_pid[pid], {})[op] = tf_op
    return dict(device=device, host=host, modules=modules,
                op_names=op_names)


def scope_of(op_name: str) -> Tuple[str, str]:
    """(layer, ``fwd`` or ``bwd``) of an HLO op name: the innermost layer
    scope in it (``other`` where there is none), its backward where
    ``transpose(`` comes before that scope."""
    last = None
    for last in _SCOPE.finditer(op_name):
        pass
    layer, head = ((last.group(1), op_name[:last.start()]) if last
                   else (OTHER, op_name))
    return layer, "bwd" if "transpose(" in head else "fwd"


def layers(events: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The step program's device time by layer over the ``bench.window``
    span: ``step_module`` (the program with the most device time in the
    window), ``step_s`` (its ops' seconds, loop containers left out,
    averaged over devices) and ``layers``, ``[layer, "fwd" | "bwd",
    seconds]`` by ``scope_of`` of each op's name, ``other`` where the name
    holds no layer or is not known; they sum to ``step_s``.  None where
    the trace holds no window or names no program."""
    wins = [(s, s + d) for s, d, n in events["host"] if n == trace.WINDOW]
    devices = [i for i, ops in enumerate(events["device"]) if ops]
    mods = events.get("modules") or []
    if not wins or not devices or len(mods) < len(events["device"]):
        return None
    w0, w1 = wins[0]
    names = events.get("op_names") or {}
    per_mod: Dict[str, Dict[Tuple[str, str], float]] = {}
    for i in devices:
        ivs = sorted(mods[i])
        starts = [s for s, _, _ in ivs]
        for s, d, name, cat in events["device"][i]:
            s0, e0 = max(s, w0), min(s + d, w1)
            j = bisect.bisect_right(starts, s) - 1
            if e0 <= s0 or cat in trace.CONTAINERS or j < 0:
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
    rows = sorted(per_mod[step].items(), key=lambda kv: -kv[1])
    return dict(step_module=step,
                step_s=sum(per_mod[step].values()) / n / 1e9,
                layers=[[layer, way, v / n / 1e9] for (layer, way), v in rows])


def ms_per_task(rows: List[list], layer: str, tasks: int) -> Optional[float]:
    """A layer's device time (forward plus backward) per task in ms; None
    where the rows give it no time."""
    secs = [v for name, _, v in rows if name == layer]
    return 1e3 * sum(secs) / tasks if secs else None


def _traced_stretch(found: Dict[str, Any], untraced_steps: int):
    """A stand-in for the harness's ``_traced_steps``: ``untraced_steps``
    steps timed on the host clock, with the share of that time the loop
    spent blocked on the prefetcher (%), then the harness's traced
    pattern, whose trace ``layers`` reads before it is deleted.  What it
    finds goes into ``found``."""

    def run(step_fn, state_box, pf, s, n):
        import jax

        state = state_box[0]
        wait0, t0 = getattr(pf, "wait_s", None), time.monotonic()
        prev = None
        for k in range(untraced_steps):
            state, m = step_fn(state, pf.get(s + k))
            if prev is not None:
                jax.block_until_ready(prev)
            prev = m["loss"]
        jax.block_until_ready(state)
        plain = time.monotonic() - t0
        found["untraced_step_s"] = plain / untraced_steps
        if wait0 is not None:        # a program without the counter
            found["prefetch_wait_share"] = 100.0 * (pf.wait_s - wait0) / plain
        s += untraced_steps
        d = tempfile.mkdtemp(prefix="bench_layers_")
        try:
            jax.profiler.start_trace(d)
            with jax.profiler.TraceAnnotation(trace.WINDOW):
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
            events = load(d)
            red = trace.reduce(events)
            if red is not None:
                red["steps"] = n
                found["traced_step_s"] = red["window_s"] / n
            found["layers"] = layers(events)
            return red
        finally:
            shutil.rmtree(d, ignore_errors=True)

    return run


def main(argv=None) -> int:
    from bench import common
    from bench.harness import train

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--untraced-steps", type=int, default=40)
    args = ap.parse_args(argv)

    cell = common.load_cell(args.workload)
    common.enable_compile_cache()
    try:
        devices = common.require_chips(cell.chips)
    except common.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    found: Dict[str, Any] = {}
    train._traced_steps = _traced_stretch(found, args.untraced_steps)
    result, checks = train.run(cell, seed=args.seed, seconds=args.seconds,
                               trace=True, devices=devices,
                               t_process=T_PROCESS)
    out = dict(correct=bool(result["correct"]) and all(c.ok for c in checks),
               device=result["device"], metrics=result["metrics"], **found)
    got = found.get("layers")
    if got:
        tasks = cell.traffic["trace_steps"] * cell.traffic["tasks_per_step"]
        out["ms_per_task"] = {
            layer: ms_per_task(got["layers"], layer, tasks)
            for layer in SCOPES + (OTHER,)}
        out["ms_per_task"]["step"] = 1e3 * got["step_s"] / tasks
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
