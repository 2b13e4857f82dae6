"""``correct`` of the FiLM training harness (``bench/harness/train_film.py``)
at a small size on the CPU: Simple CNAPs on EfficientNet-B0 at its
published widths, on 64 px images, way 3 x shot 4, |H| 3.  (At 32 px the
last stages run on 1 x 1 maps, batch norm calibrated on 24 images
amplifies some channels a hundredfold, and the first loss reads 3e3.)
A sound run
passes; each fault planted under the timed path fails; the control departs
from a sound run; the trace reduction reads the learner's scopes.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import copy
import time

import jax
import pytest

from bench import common, faults
from bench.harness import train_film
from bench.tests.tiny import CPU_PEAKS

NAME = "simple_cnaps-efficientnet_b0"
SEED = 2**31 + 8191


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(common, "peaks", lambda kind: CPU_PEAKS)


def _cell() -> common.Cell:
    config = copy.deepcopy(common.load_json(
        common.BENCH / "configs" / f"{NAME}.json"))
    traffic = copy.deepcopy(common.load_json(
        common.BENCH / "traffic" / "train-film-lite1000.json"))
    config.update(image_size=64, way=3, lite={"h": 3, "chunk": 4})
    traffic.update(shot=4, query_per_class=2, trace_steps=2)
    return common.Cell(name=f"{NAME}.train-film-lite1000", chips=1,
                       config=config, traffic=traffic,
                       end_to_end=[], per_layer=[])


def _train(**kw):
    return train_film.run(_cell(), seed=SEED, seconds=0.5, trace=False,
                          devices=jax.devices(), t_process=time.monotonic(),
                          **kw)


def _correct(result, checks) -> bool:
    return bool(result["correct"]) and all(c.ok for c in checks)


def _readings(checks):
    return {c.name: c.value for c in checks}


def test_sound_run_is_correct():
    result, checks = _train()
    assert _correct(result, checks), _readings(checks)
    assert result["attempted"] > 0 and result["failed"] == 0
    names = {c.name for c in checks}
    assert set(_cell().config["limits"]["train"]) <= names
    assert {"backbone_change", "skipped_steps", "window_compiles"} <= names
    assert _readings(checks)["backbone_change"] == 0.0


@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
def test_fault_is_not_correct(fault):
    result, checks = _train(wrap_step=faults.TRAIN[fault], window=False)
    assert not _correct(result, checks), _readings(checks)


def test_control_departs_from_sound_run():
    """The float8 control reads far above a sound run on the same seed;
    whether it fails the limits is read on the chip at the cell's size."""
    got = {}
    _train(window=False, readings=got)
    ctl = train_film.reference_control(_cell(), SEED)
    assert max(ctl[k] / max(got[k], 1e-9)
               for k in ("loss_gap", "grad_gap", "grad_dir")) > 100, ctl


@pytest.mark.parametrize("name,want", [
    ("jit(train_step)/jvp(vmap(cnaps.set_encoder))/lite.h_pass/conv",
     "cnaps.set_encoder"),
    ("jit(train_step)/transpose(jvp(vmap(cnaps.class_stats)))/lite.h_pass/x",
     "cnaps.class_stats"),
    ("jit(train_step)/jvp(vmap(cnaps.class_stats))/lite.complement/while",
     "cnaps.class_stats/lite.complement"),
    ("jit(train_step)/jvp(vmap(cnaps.class_stats))/lite.split/jit(_take)",
     "cnaps.class_stats"),
    ("jit(train_step)/transpose(jvp(vmap(cnaps.covariance)))/cholesky",
     "cnaps.covariance"),
    ("jit(train_step)/jvp(vmap(cnaps.film_generator))/jit(relu)",
     "cnaps.film_generator"),
    ("jit(train_step)/transpose(jvp(vmap(learner.query)))/jit(silu)",
     "learner.query"),
    ("jit(train_step)/train.optimizer/jit(_where)", "train.optimizer"),
    ("jit(train_step)/jvp(vmap())/task_key", "other"),
])
def test_scope_of(name, want):
    assert train_film.scope_of(name) == want


def test_scope_seconds_of_the_step_program():
    """Ops count for the program whose interval holds them, inside the
    window, by their op names' scopes."""
    ms = 1_000_000
    events = dict(
        host=[[0, 100 * ms, "bench.window"]],
        device=[[[10 * ms, 5 * ms, "fusion.1", "convolution fusion"],
                 [15 * ms, 3 * ms, "fusion.2", "loop fusion"],
                 [18 * ms, 2 * ms, "while.3", "while"],
                 [40 * ms, 4 * ms, "fusion.1", "loop fusion"]]],
        modules=[[[10 * ms, 20 * ms, "jit_train_step(7)"],
                  [40 * ms, 5 * ms, "jit_batch(9)"]]],
        op_names={
            "jit_train_step(7)": {
                "fusion.1": "jit(train_step)/jvp(vmap(cnaps.class_stats))"
                            "/lite.complement/conv",
                "fusion.2": "jit(train_step)/jvp(vmap(learner.query))/x"},
            "jit_batch(9)": {"fusion.1": "jit(batch)/normal"}})
    got = train_film.scope_seconds(events)
    assert got == pytest.approx({"cnaps.class_stats/lite.complement": 0.005,
                                 "learner.query": 0.003, "step": 0.008})
