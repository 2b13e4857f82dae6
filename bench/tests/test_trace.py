"""The trace reduction on small traces recorded on one TPU v5e chip (a few
training steps, flattened by ``trace.load`` and
kept in ``bench/tests/data``), against a second, plainer computation, and
on a hand-made trace whose answer is known.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import json
import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
RECORDED = sorted(DATA.glob("*_events.json"))


def _busy_by_sweep(ops, w0, w1) -> float:
    """Union length by a sweep over sorted edges (+1 at a start, -1 at an
    end)."""
    edges = []
    for s, d, _, _ in ops:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            edges += [(a, 1), (b, -1)]
    edges.sort()
    depth, busy, last = 0, 0.0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_hand_made_trace():
    ev = dict(
        host=[[0, 1000, trace.WINDOW], [100, 200, "bench.dispatch"],
              [600, 300, "bench.wait"]],
        device=[[[50, 100, "convolution.1", "convolution"],
                 [120, 80, "fusion.2", "loop fusion"],
                 [400, 100, "convolution.3", "convolution fusion"],
                 [950, 200, "fusion.4", "loop fusion"]]])
    red = trace.reduce(ev)
    assert red["window_s"] == pytest.approx(1000e-9)
    # [50,200) + [400,500) + [950,1000)
    assert red["busy_s"] == pytest.approx(300e-9)
    assert red["conv_s"] == pytest.approx(200e-9)
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert sorted(gaps.values(), reverse=True)[0] == pytest.approx(450e-9)
    assert any(k.startswith("bench.wait@") for k in gaps)
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["convolution.1 [convolution]"] == pytest.approx(100e-9)


def test_no_window_or_no_device_reads_nothing():
    assert trace.reduce(dict(host=[], device=[[[0, 1, "x", ""]]])) is None
    assert trace.reduce(dict(host=[[0, 10, trace.WINDOW]], device=[])) is None


@pytest.mark.parametrize("path", RECORDED, ids=[p.name for p in RECORDED])
def test_recorded_trace(path):
    ev = json.loads(path.read_text())
    red = trace.reduce(ev)
    assert red is not None
    w0, w1 = [(s, s + d) for s, d, n in ev["host"] if n == trace.WINDOW][0]
    busy = sum(_busy_by_sweep(ops, w0, w1) for ops in ev["device"] if ops)
    busy /= len([o for o in ev["device"] if o])
    assert red["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert 0 < red["conv_s"] <= red["busy_s"] * 1.0000001
    assert red["window_s"] == pytest.approx((w1 - w0) / 1e9)
    b = red["breakdown"]
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(v > 0 for _, v in b["device_ops"] + b["idle_gaps"])
    idle = red["window_s"] - red["busy_s"]
    assert sum(v for _, v in b["idle_gaps"]) <= idle * 1.0000001
