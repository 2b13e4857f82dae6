"""The layer reduction (``bench/layers.py``) on a hand-made trace whose
answer is known and on a trace recorded on one TPU v5e chip (8 steps of
``protonets-conv4.train-lite1000`` with the program's named scopes,
flattened by ``layers.load`` and kept in ``bench/tests/data``); the
reduction of ``bench/trace.py`` unchanged on the first recorded trace;
the traced stretch of ``bench/layers.py`` at a small size on the CPU.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import json
import pathlib
import time

import jax
import pytest

from bench import common, layers, trace
from bench.harness import train
from bench.tests.tiny import CPU_PEAKS, tiny_cell

DATA = pathlib.Path(__file__).resolve().parent / "data"


SCOPED = DATA / "train_scoped_events.json"


def test_reduce_unchanged_on_the_first_recorded_trace():
    """Every number ``bench/trace.py``'s reduction gives on
    ``train_events.json``: what wiring the layers into it must keep.  That
    trace names no program, so it has no layers."""
    red = trace.reduce(json.loads((DATA / "train_events.json").read_text()))
    assert red["window_s"] == 0.3
    assert red["busy_s"] == 0.29979559781400006
    assert red["conv_s"] == 0.12674616616999998
    assert red["breakdown"]["device_ops"] == [
        ["%fusion.744 [convolution fusion]", 0.026790198516000004],
        ["%fusion.769 [convolution fusion]", 0.026785408516000003],
        ["%fusion.770 [output fusion]", 0.02174617382800001],
        ["%fusion.745 [output fusion]", 0.021743965779999964],
        ["%bitcast_add_fusion [loop fusion]", 0.016464608906000017],
        ["%fusion.771 [convolution fusion]", 0.012337182889999986],
        ["%fusion.772 [output fusion]", 0.010886451795999959],
        ["%fusion.773 [convolution fusion]", 0.00700834546799998],
        ["%fusion.1 [custom fusion]", 0.00629113375],
        ["%fusion.746 [convolution fusion]", 0.006201094220000014]]
    assert red["breakdown"]["idle_gaps"] == [
        ["bench.wait@0.298099s", 1.867257800000906e-05],
        ["bench.wait@0.143230s", 1.856507800000906e-05],
        ["bench.wait@0.132004s", 1.425242199999094e-05],
        ["bench.wait@0.275563s", 1.388e-05],
        ["bench.wait@0.131942s", 1.359617199999094e-05],
        ["bench.wait@0.131957s", 1.209875e-05],
        ["bench.wait@0.275578s", 1.209757800000906e-05],
        ["bench.wait@0.286860s", 1.15825e-05],
        ["bench.wait@0.286884s", 9.295e-06],
        ["bench.wait@0.275625s", 9.24625e-06]]
    assert layers.layers(json.loads(
        (DATA / "train_events.json").read_text())) is None


def test_layers_on_hand_made_trace():
    """Two programs whose instruction names collide: an op counts under
    the op name of the program whose interval holds it, the step being
    the program with the most device time; ``transpose(`` marks the
    backward; a step op with no layer scope, or no known op name, is
    ``other``; a loop container counts nothing of its own."""
    step, batch = "jit_step(7)", "jit_batch(9)"
    ev = dict(
        host=[[0, 1000, trace.WINDOW]],
        device=[[[10, 100, "%fusion.1", "convolution fusion"],
                 [110, 50, "%fusion.2", "loop fusion"],
                 [160, 200, "%while.3", "while"],
                 [170, 60, "%fusion.4", "convolution fusion"],
                 [240, 40, "%fusion.5", "output fusion"],
                 [300, 30, "%copy.6", "copy"],
                 [400, 20, "%fusion.1", "loop fusion"],
                 [500, 90, "%fusion.7", "loop fusion"],
                 [600, 10, "%fusion.8", "loop fusion"]]],
        modules=[[[0, 380, step], [390, 40, batch], [490, 130, step]]],
        op_names={
            step: {
                "%fusion.1": "jit(step)/jvp(vmap(lite.h_pass))/conv",
                "%fusion.2": "jit(step)/transpose(jvp(vmap(lite.h_pass)))/mul",
                "%while.3": "jit(step)/jvp(vmap(lite.complement))/while",
                "%fusion.4": ("jit(step)/jvp(vmap(lite.complement))/while/"
                              "body/conv"),
                "%fusion.5": "jit(step)/jvp(vmap())/div",
                "%fusion.7": "jit(step)/train.optimizer/mul",
                "%fusion.8": ("jit(step)/transpose(jvp(vmap("
                              "learner.query)))/lite.split_like/add")},
            batch: {"%fusion.1": "jit(batch)/lite.h_pass/not_the_step"}})
    got = layers.layers(ev)
    assert got["step_module"] == step
    by = {(a, b): v for a, b, v in got["layers"]}
    assert by == pytest.approx({
        ("lite.h_pass", "fwd"): 100e-9,
        ("lite.h_pass", "bwd"): 50e-9,
        ("lite.complement", "fwd"): 60e-9,
        ("other", "fwd"): 70e-9,             # fusion.5 + copy.6 (no name)
        ("train.optimizer", "fwd"): 90e-9,
        ("learner.query", "bwd"): 10e-9})
    assert got["step_s"] == pytest.approx(380e-9)


def test_idle_gap_inside_a_program_span_takes_its_name():
    """``layers.load`` keeps the program's ``repro.*`` spans, so the
    reduction labels a gap inside one by its name."""
    ev = dict(host=[[0, 100, trace.WINDOW], [40, 30, "bench.batch"],
                    [45, 20, "repro.prefetch.get"]],
              device=[[[0, 40, "%a", ""], [70, 30, "%b", ""]]])
    gaps = dict(trace.reduce(ev)["breakdown"]["idle_gaps"])
    assert list(gaps) == ["repro.prefetch.get@0.000000s"]


def test_scope_of():
    scope_of = layers.scope_of
    assert scope_of("jit(f)/vmap(jvp(lite.complement))/while/body/"
                    "closed_call/reduce_window_max:") == ("lite.complement",
                                                          "fwd")
    assert scope_of("jit(f)/transpose(jvp(vmap(lite.h_pass)))/add") == (
        "lite.h_pass", "bwd")
    assert scope_of("jit(f)/train.optimizer/mul") == ("train.optimizer",
                                                      "fwd")
    assert scope_of("jit(f)/transpose(jvp(lite.split_like))/add") == (
        "other", "bwd")
    assert scope_of("") == ("other", "fwd")


def test_layers_cover_the_step_on_recorded_scoped_trace():
    """A trace recorded on one TPU v5e chip (8 steps of
    ``protonets-conv4.train-lite1000``): the layers plus ``other`` sum to
    the step program's device time, computed here over its intervals by
    a plainer loop; every layer scope is there, ``other`` is a small
    share, and the two costliest fusions sit under the complement."""
    ev = json.loads(SCOPED.read_text())
    got = layers.layers(ev)
    step = got["step_module"]
    assert step.startswith("jit_train_step(")
    w0, w1 = [(s, s + d) for s, d, n in ev["host"] if n == trace.WINDOW][0]
    total = 0.0
    for ms, md, mod in ev["modules"][0]:
        if mod != step:
            continue
        for s, d, _, cat in ev["device"][0]:
            if (ms <= s and s + d <= ms + md
                    and cat not in trace.CONTAINERS):
                total += max(0.0, min(s + d, w1) - max(s, w0))
    rows = got["layers"]
    assert sum(v for _, _, v in rows) == pytest.approx(total / 1e9, rel=1e-9)
    assert got["step_s"] == pytest.approx(total / 1e9, rel=1e-9)
    assert set(layers.SCOPES) <= {name for name, _, _ in rows}
    other = sum(v for name, _, v in rows if name == layers.OTHER)
    assert other < 0.05 * got["step_s"]
    names = ev["op_names"][step]
    for op in ("%fusion.272", "%fusion.273"):
        assert layers.scope_of(names[op])[0] == "lite.complement"
    assert {"repro.prefetch.get", "repro.prefetch.produce"} <= {
        n for _, _, n in ev["host"]}


def test_ms_per_task():
    rows = [["lite.h_pass", "fwd", 0.25], ["lite.h_pass", "bwd", 0.5],
            ["other", "fwd", 0.1]]
    assert layers.ms_per_task(rows, "lite.h_pass", 2) == pytest.approx(375.0)
    assert layers.ms_per_task(rows, "lite.complement", 2) is None


def test_traced_stretch_on_cpu(monkeypatch):
    """The traced run's stand-in for the harness's traced steps, at a
    small size on the CPU: it times the untraced steps and the
    prefetcher's waits, and hands the harness its reduced trace (none
    here: the CPU trace has no TPU plane, so no layers either)."""
    monkeypatch.setattr(common, "peaks", lambda kind: CPU_PEAKS)
    found = {}
    monkeypatch.setattr(train, "_traced_steps",
                        layers._traced_stretch(found, 3))
    result, checks = train.run(
        tiny_cell("protonets-conv4", "train-lite1000"), seed=2**31 + 11,
        seconds=0.5, trace=True, devices=jax.devices(),
        t_process=time.monotonic())
    assert result["correct"] and all(c.ok for c in checks)
    assert found["untraced_step_s"] > 0
    assert 0 <= found["prefetch_wait_share"] <= 100
    assert found["layers"] is None
