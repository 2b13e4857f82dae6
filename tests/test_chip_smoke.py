"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and its
phases run at a tiny size on the CPU (the tests steer them: the on-chip
lowering checks are swapped out here, not by an option of the script).
Also the compile-cache placement every entry point sets up."""
import importlib.util
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.common import compile_cache
from repro.faults import PreemptionSignal

ROOT = pathlib.Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")

_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

TINY = cs.EpisodicShape(widths=(8, 16), feature_dim=32, way=5, shot=4,
                        query_per_class=2, image_size=16, lite_h=4,
                        lite_chunk=8)


def _env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.update(extra)
    return env


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_refuses_without_tpu(argv):
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")] + argv,
                       capture_output=True, text=True, env=_env(),
                       timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr


@pytest.fixture
def learner():
    return cs.build_learner("simple_cnaps", TINY)


@pytest.mark.parametrize("kind", ["simple_cnaps", "protonets"])
def test_train_phase_tiny(monkeypatch, kind):
    monkeypatch.setattr(PreemptionSignal, "install",
                        lambda self, signals=None: self)
    out = cs.phase_train(TINY, kind, steps=3)
    assert out["late_compiles"] == 0 and out["restored_step"] == 3
    assert len(out["losses"]) == 3


def test_serve_phase_tiny(learner):
    out = cs.phase_serve(TINY, learner, learner.init(jax.random.key(0)))
    assert len(out["buckets"]) == 2
    assert out["adapt_compiles"] == 2 and out["predict_compiles"] == 1


def test_kernels_phase_tiny(monkeypatch, learner):
    checked, gate = [], cs._require_on_chip
    monkeypatch.setattr(cs, "_require_on_chip", checked.append)
    out = cs.phase_kernels(TINY, learner, learner.init(jax.random.key(0)))
    assert checked == [out] and out["interpret_mode"]
    assert out["argmax_agree"] >= cs.LEARNER_ARGMAX_AGREE
    # off the chip the kernels ran in the interpreter: the real gate fails
    with pytest.raises(cs.SmokeFailure, match="interpret mode"):
        gate(out)


def test_precision_phase_tiny(learner):
    out = cs.phase_precision(TINY, learner, learner.init(jax.random.key(0)))
    assert out["loss_rel_diff"] >= 0 and out["grad_rel_diff"] >= 0


def test_four_chip_phases_on_emulated_devices():
    code = textwrap.dedent(f"""
        import importlib.util, jax
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        tiny = cs.EpisodicShape(widths=(8, 16), feature_dim=32, way=5,
                                shot=4, query_per_class=2, image_size=16,
                                lite_h=4, lite_chunk=8)
        dp = cs.phase_dp(tiny, 4)
        assert dp["task_rows_per_device"] == [(0, 1), (1, 1), (2, 1), (3, 1)]
        learner = cs.build_learner("simple_cnaps", tiny)
        rep = cs.phase_replicas(tiny, learner,
                                learner.init(jax.random.key(0)), 4)
        assert rep["weight_devices"] == [[0], [1], [2], [3]]
        print("FOUR_OK")
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "FOUR_OK" in r.stdout


def test_cache_dir_resolution(tmp_path):
    env_dir = str(tmp_path / "cache")
    assert compile_cache.resolve_cache_dir(
        {compile_cache.ENV_VAR: env_dir}) == pathlib.Path(env_dir)
    assert compile_cache.resolve_cache_dir({}) == ROOT / ".jax_cache"
    assert compile_cache.resolve_cache_dir(
        {compile_cache.ENV_VAR: ""}) == ROOT / ".jax_cache"


_PLACE = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.common.compile_cache import enable_compile_cache
    print("DIR", enable_compile_cache(), jax.config.jax_compilation_cache_dir)
""")


def test_cache_goes_where_the_environment_says(tmp_path):
    cache = tmp_path / "cache"
    code = _PLACE + "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(3))\n"
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=_env(JAX_COMPILATION_CACHE_DIR=str(cache),
                 JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                 JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0"),
        timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert f"DIR {cache} {cache}" in r.stdout
    assert any(cache.iterdir())


def test_cache_defaults_to_the_checkout():
    env = _env()
    env.pop(compile_cache.ENV_VAR, None)
    r = subprocess.run([sys.executable, "-c", _PLACE], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    want = ROOT / ".jax_cache"
    assert f"DIR {want} {want}" in r.stdout
