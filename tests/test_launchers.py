"""Launcher entry points run end-to-end on the local device (subprocess,
so their arg parsing + mesh/sharding init paths are covered)."""
import os
import pathlib
import subprocess
import sys

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def _run(args, timeout=480):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-m"] + args, capture_output=True,
                       text=True, env=env, timeout=timeout)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout


def test_train_launcher(tmp_path):
    out = _run(["repro.launch.train", "--arch", "gemma2-2b", "--steps", "6",
                "--batch", "2", "--seq", "32", "--ckpt-dir", str(tmp_path),
                "--ckpt-every", "4"])
    assert "done at step 6" in out
    # auto-resume path: run again to a later step
    out2 = _run(["repro.launch.train", "--arch", "gemma2-2b", "--steps", "8",
                 "--batch", "2", "--seq", "32", "--ckpt-dir", str(tmp_path),
                 "--ckpt-every", "4"])
    assert "resumed_from=6" in out2


def test_serve_launcher():
    out = _run(["repro.launch.serve", "--arch", "mamba2-780m",
                "--requests", "3", "--slots", "2", "--max-new", "5",
                "--prompt-len", "4"])
    assert "3 requests" in out


TINY_SHAPE = ["--widths", "8,16", "--feature-dim", "32", "--way", "4",
              "--shot", "3", "--query-per-class", "2", "--image-size", "12"]


def test_episodic_train_launcher_takes_the_shape(tmp_path):
    out = _run(["repro.launch.train", "--episodic", "--learner",
                "simple_cnaps", "--steps", "2", "--tasks-per-step", "2",
                "--lite-h", "4", "--lite-chunk", "4",
                "--ckpt-dir", str(tmp_path)] + TINY_SHAPE)
    assert "widths=(8, 16), feature_dim=32, way=4, shot=3" in out
    assert "lite_h=4, lite_chunk=4" in out
    assert "done at step 2" in out


def test_episodic_serve_launcher_takes_the_shape():
    out = _run(["repro.launch.serve", "--episodic", "--learner",
                "simple_cnaps", "--requests", "4", "--lite-chunk", "4"]
               + TINY_SHAPE)
    assert "4 requests" in out and "failed_requests=0" in out


def test_episodic_serve_launcher_fails_on_unserved_requests():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--episodic",
         "--requests", "6", "--max-queue", "1"] + TINY_SHAPE,
        capture_output=True, text=True, env=env, timeout=480)
    assert r.returncode != 0
    assert "requests were not served" in r.stderr
