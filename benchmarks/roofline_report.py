"""Roofline table (deliverable g): reads the dry-run JSON and emits the
per-cell three-term analysis as CSV + markdown.

Also emits ``serve_layouts.csv``: the serving-layout chooser's
per-(layout x batch-regime) wire/flops/bytes table for the episodic
predict step — every candidate in ``SERVING_LAYOUTS`` compiled on a
4-device emulated mesh and scored on its actual post-SPMD HLO, plus the
chooser's pick per regime.  Emulation needs
``XLA_FLAGS=--xla_force_host_platform_device_count`` set BEFORE jax
initializes, so that section re-execs itself in a fresh subprocess (same
pattern as ``benchmarks/dp_scaling.py``)."""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys

from benchmarks.common import RESULTS_DIR, emit
from repro.roofline.analysis import format_markdown, load_table

LAYOUT_DEVICES = 4

def _dryrun_path():
    for name in ("dryrun_opt.json", "dryrun.json"):
        p = RESULTS_DIR / name
        if p.exists():
            return p
    return RESULTS_DIR / "dryrun.json"


DRYRUN = _dryrun_path()


def run() -> list:
    rows = load_table(DRYRUN, mesh="single")
    out = []
    for r in rows:
        if "skipped" in r:
            out.append(dict(arch=r["arch"], shape=r["shape"],
                            t_compute_ms="", t_memory_ms="", t_coll_ms="",
                            bottleneck="skipped", useful="", frac=""))
            continue
        out.append(dict(
            arch=r["arch"], shape=r["shape"],
            t_compute_ms=f"{1e3*r['t_compute']:.3f}",
            t_memory_ms=f"{1e3*r['t_memory']:.3f}",
            t_coll_ms=f"{1e3*r['t_collective']:.3f}",
            bottleneck=r["bottleneck"],
            useful=f"{r['useful_ratio']:.3f}",
            frac=f"{r['roofline_fraction']:.4f}",
        ))
    md = format_markdown(rows)
    (RESULTS_DIR / "roofline.md").write_text(md + "\n")
    return out


def _serve_layouts_worker() -> None:
    """Runs inside the 4-fake-device subprocess: score every serving
    layout for the episodic predict step at two serving batch regimes."""
    import jax
    import jax.numpy as jnp

    from repro.core.episodic_train import task_key
    from repro.core.lite import LiteSpec
    from repro.core.meta_learners import MetaLearnerConfig, make_learner
    from repro.core.set_encoder import SetEncoderConfig
    from repro.data.episodic import (EpisodicImageConfig, collate_task_batch,
                                     sample_image_task)
    from repro.models.conv_backbone import (ConvBackboneConfig,
                                            make_conv_backbone)
    from repro.roofline.analysis import (SERVING_LAYOUTS,
                                         choose_serving_layout)
    from repro.serve.quant_params import dequantize_params, quantize_frozen

    bb = make_conv_backbone(ConvBackboneConfig(widths=(16, 32),
                                               feature_dim=64))
    lr = make_learner(
        MetaLearnerConfig(kind="protonets", way=5), bb,
        SetEncoderConfig(kind="conv", conv_blocks=2, conv_width=16,
                         task_dim=32))
    params = lr.init(jax.random.key(0))
    sw = quantize_frozen(lr, params, "int8")
    mesh = jax.make_mesh((LAYOUT_DEVICES,), ("serve",))
    lite = LiteSpec(exact=True, chunk_size=32)

    def predict_fn(w, st, qx):
        return lr.predict_batch(dequantize_params(w), st, qx)

    rows = []
    for regime, n_tasks in (("serve_small", 2), ("serve_large", 8)):
        cfg = EpisodicImageConfig(way=5, shot=4, query_per_class=4,
                                  image_size=12)
        tasks = [sample_image_task(jax.random.key(100 + i), cfg)
                 for i in range(n_tasks)]
        batch = collate_task_batch(tasks, support_size=32,
                                   query_size=tasks[0].query_x.shape[0])
        keys = jax.vmap(lambda i: task_key(jax.random.key(0), i))(
            jnp.arange(n_tasks))
        states = lr.adapt_batch(dequantize_params(sw), batch, keys, lite)
        pick = choose_serving_layout(predict_fn, sw,
                                     (states, batch.query_x), mesh)
        for lo in SERVING_LAYOUTS:
            r = pick["rows"][lo]
            rows.append(dict(
                regime=regime, tasks=n_tasks, layout=lo,
                wire_bytes=round(r["wire_bytes"]),
                collectives=round(r["collective_count"]),
                dot_flops=round(r["dot_flops"]),
                bytes_accessed=round(r["bytes_accessed"]),
                t_compute_us=f"{1e6 * r['t_compute']:.3f}",
                t_memory_us=f"{1e6 * r['t_memory']:.3f}",
                t_coll_us=f"{1e6 * r['t_collective']:.3f}",
                bottleneck=r["bottleneck"],
                chosen=int(lo == pick["choice"])))
        ws = pick["rows"]["weight_stationary"]["wire_bytes"]
        tr = pick["rows"]["training"]["wire_bytes"]
        print(f"# {regime}: chooser picked {pick['choice']}; "
              f"weight_stationary wire {ws:.0f} B vs training {tr:.0f} B "
              f"({tr / max(ws, 1):.1f}x less)")
    emit(rows, "serve_layouts")


def serve_layouts() -> None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count"
                        f"={LAYOUT_DEVICES}").strip()
    env["SERVE_LAYOUTS_WORKER"] = "1"
    env["JAX_PLATFORMS"] = "cpu"    # emulated devices; never the chip
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in [str(pathlib.Path(__file__).resolve().parents[1] / "src"),
                     str(pathlib.Path(__file__).resolve().parents[1]),
                     env.get("PYTHONPATH", "")] if p])
    r = subprocess.run([sys.executable, __file__], env=env)
    if r.returncode:
        raise RuntimeError(f"serve_layouts worker failed ({r.returncode})")


def main() -> None:
    if os.environ.get("SERVE_LAYOUTS_WORKER"):
        _serve_layouts_worker()
        return
    if DRYRUN.exists():
        emit(run(), "roofline")
    else:
        print("no dryrun.json — run `python -m repro.launch.dryrun` first "
              "(skipping the dry-run roofline table)")
    serve_layouts()


if __name__ == "__main__":
    main()
