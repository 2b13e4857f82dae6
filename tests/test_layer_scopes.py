"""The LITE step's layer names: the named scopes reach the compiled step's
op names (``bench/layers.py`` attributes device time by them), and the
prefetcher's wait counter and profiler spans.

The step is the launcher's episodic ProtoNets job (``build_episodic``)
at a tiny size, compiled on the CPU."""
import importlib.util
import math
import pathlib
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro.launch import train as launcher
from repro.train.pipeline import Prefetcher

SCOPES = ("lite.split", "lite.h_pass", "lite.complement", "learner.query",
          "train.optimizer")
# instructions that compute nothing of their own
PLUMBING = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
_INSTR = re.compile(r"^\s*(?:ROOT )?%?\S+ = (.*?) ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")


@pytest.fixture(scope="module")
def step_hlo(tmp_path_factory):
    args = launcher.build_parser().parse_args(
        ["--episodic", "--learner", "protonets", "--steps", "4",
         "--tasks-per-step", "1", "--data-source", "device",
         "--widths", "4,8", "--feature-dim", "16", "--way", "3",
         "--shot", "4", "--query-per-class", "2", "--image-size", "16",
         "--lite-h", "3", "--lite-chunk", "4",
         "--ckpt-dir", str(tmp_path_factory.mktemp("ck"))])
    job = launcher.build_episodic(args)
    fn = jax.jit(job.step)
    return fn.lower(job.state, job.batch_at(0)).compile().as_text()


def _compute_instructions(hlo: str):
    """(opcode, op name, output elements) of every instruction that
    computes something."""
    out = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(2) in PLUMBING:
            continue
        name = _OP_NAME.search(line)
        elems = sum(math.prod(int(d) for d in dims.split(",") if d)
                    for dims in _SHAPE.findall(m.group(1)))
        out.append((m.group(2), name.group(1) if name else "", elems))
    return out


def test_every_layer_scope_reaches_the_compiled_step(step_hlo):
    names = set(_OP_NAME.findall(step_hlo))
    for scope in SCOPES:
        assert any(scope in n for n in names), scope


def test_backward_runs_under_h_pass_and_query_only(step_hlo):
    names = set(_OP_NAME.findall(step_hlo))
    bwd = [n for n in names if "transpose(" in n]
    for scope in ("lite.h_pass", "learner.query"):
        assert any(scope in n[n.index("transpose("):] for n in bwd), scope
    # LITE's complement is forward only: no gradient flows through it
    assert not any("lite.complement" in n[n.index("transpose("):]
                   for n in bwd)


def test_layer_scopes_cover_the_step(step_hlo):
    """At least 95% of the compute (output elements of the instructions
    that carry the step's op names) falls under a layer scope.  What
    stays outside is glue: per-task keys, one-hot labels, the prototype
    division; instructions XLA adds itself carry no op name."""
    ins = [(op, n, e) for op, n, e in _compute_instructions(step_hlo)
           if n.startswith("jit(")]
    assert ins
    total = sum(e for _, _, e in ins)
    scoped = sum(e for _, n, e in ins if any(s in n for s in SCOPES))
    assert scoped >= 0.95 * total, (scoped, total)


def _load_bench_layers():
    """The benchmark's trace loader for layers (``bench/layers.py``)."""
    path = (pathlib.Path(__file__).resolve().parent.parent / "bench"
            / "layers.py")
    spec = importlib.util.spec_from_file_location("bench_layers", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_prefetcher_wait_counts_a_slow_source():
    def slow(s):
        time.sleep(0.05)
        return jnp.full((2,), float(s))

    pf = Prefetcher(slow, 0, 3, depth=1)
    try:
        for s in range(3):
            pf.get(s)
        assert pf.wait_s > 0.02
    finally:
        pf.close()


def test_prefetcher_wait_is_nought_with_batches_ready():
    pf = Prefetcher(lambda s: jnp.full((2,), float(s)), 0, 2, depth=2)
    try:
        deadline = time.monotonic() + 10.0
        while not pf._q.full() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pf._q.full()
        pf.get(0)
        pf.get(1)
        assert pf.wait_s < 0.05
    finally:
        pf.close()


def test_prefetcher_spans_reach_the_profiler_trace(tmp_path):
    layers = _load_bench_layers()
    jax.profiler.start_trace(str(tmp_path))
    try:
        pf = Prefetcher(lambda s: jnp.full((2,), float(s)), 0, 2, depth=1)
        try:
            pf.get(0)
            pf.get(1)
        finally:
            pf.close()
    finally:
        jax.profiler.stop_trace()
    names = {n for _, _, n in layers.load(str(tmp_path))["host"]}
    assert {"repro.prefetch.produce", "repro.prefetch.get"} <= names
