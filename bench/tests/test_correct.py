"""``correct`` at a small size on the CPU: the harness drives a whole run
(the look for a chip skipped) and compares with the reference.  A sound
run passes; each fault planted under the timed path fails; the control
departs from a sound run; ``bench/readings.py`` reads all three.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import time

import jax
import pytest

from bench import common, faults, readings
from bench.harness import train
from bench.tests.tiny import CPU_PEAKS, tiny_cell

NAME = "protonets-conv4"
SEED = 2**31 + 4099


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(common, "peaks", lambda kind: CPU_PEAKS)


def _cell():
    return tiny_cell(NAME, "train-lite1000")


def _train(**kw):
    return train.run(_cell(), seed=SEED, seconds=0.5, trace=False,
                     devices=jax.devices(), t_process=time.monotonic(), **kw)


def _correct(result, checks) -> bool:
    return bool(result["correct"]) and all(c.ok for c in checks)


def _readings(checks):
    return {c.name: c.value for c in checks}


def test_train_sound_run_is_correct():
    result, checks = _train()
    assert _correct(result, checks), _readings(checks)
    assert result["attempted"] > 0 and result["failed"] == 0
    names = {c.name for c in checks}
    assert set(_cell().config["limits"]["train"]) <= names
    assert {"skipped_steps", "window_compiles"} <= names


@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
def test_train_fault_is_not_correct(fault):
    result, checks = _train(wrap_step=faults.TRAIN[fault], window=False)
    assert not _correct(result, checks), _readings(checks)


def test_train_control_departs_from_sound_run():
    """The control (the reference in the configuration's control type, in
    the program's place) reads far above a sound run on the same seed.
    Whether it fails the limits is read on the chip at the cell's size
    (``PERF.md``); here, at a small size on the CPU, a sound run agrees
    with the reference to rounding."""
    got = {}
    _train(window=False, readings=got)
    ctl = train.reference_control(_cell(), SEED)
    assert max(ctl[k] / max(got[k], 1e-9)
               for k in ("loss_gap", "grad_gap", "grad_dir")) > 100, ctl


def test_readings_cover_program_control_and_fault():
    """``bench/readings.py`` on a tiny cell: one record per seed and kind,
    every number of ``compare`` in each, the sound run correct and the
    half-batch fault not."""
    recs = list(readings.collect(_cell(), jax.devices(), [SEED], [SEED + 1],
                                 "half_batch", [SEED + 2]))
    assert [r["kind"] for r in recs] == ["program", "control",
                                        "fault:half_batch"]
    for r in recs:
        assert "error" not in r["readings"], r
        assert {"loss_gap", "grad_gap", "grad_dir", "update_dir",
                "grad_dir_worst"} <= set(r["readings"]), r
    assert recs[0]["correct"] and not recs[2]["correct"], recs
