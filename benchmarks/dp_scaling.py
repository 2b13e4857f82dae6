"""Two-level DP scaling of the task-batched LITE engine (emulated hosts).

For each engine configuration — single-device, 1-D ``data`` mesh, and the
two-level ``(dcn, data)`` mesh with pmean / error-feedback-compressed /
gradient-accumulated cross-host reduction — this AOT-compiles the episodic
train step on 4 emulated CPU devices, accounts the per-step collective
wire bytes with :func:`repro.roofline.hlo.collectives_report` (the same
HLO walk the dry-run and the MoE wire-bytes regression guard use), and
measures steps/sec.

Emulation needs ``XLA_FLAGS=--xla_force_host_platform_device_count`` set
BEFORE jax initializes, so ``main()`` re-execs the measurement in a fresh
subprocess — the module stays registrable in ``benchmarks.run`` where jax
is already live.

    PYTHONPATH=src python benchmarks/dp_scaling.py
"""
from __future__ import annotations

import os
import subprocess
import sys

DEVICES = 4
TASKS = 8


def _worker() -> None:
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, __file__.rsplit("/", 1)[0])
    from common import emit, time_median  # noqa: E402

    from repro.core.episodic_train import (init_ef_state,
                                           make_batched_meta_train_step)
    from repro.core.lite import LiteSpec
    from repro.core.meta_learners import MetaLearnerConfig, make_learner
    from repro.core.set_encoder import SetEncoderConfig
    from repro.data.episodic import (EpisodicImageConfig,
                                     sample_image_task_batch)
    from repro.launch.mesh import make_dp_mesh, make_two_level_dp_mesh
    from repro.models.conv_backbone import (ConvBackboneConfig,
                                            make_conv_backbone)
    from repro.optim import AdamWConfig, adamw_init
    from repro.roofline.hlo import collectives_report

    assert len(jax.devices()) == DEVICES, jax.devices()
    bb = make_conv_backbone(ConvBackboneConfig(widths=(8, 16),
                                               feature_dim=32))
    learner = make_learner(
        MetaLearnerConfig(kind="protonets", way=5), bb,
        SetEncoderConfig(kind="conv", conv_blocks=1, conv_width=8,
                         task_dim=16))
    params = learner.init(jax.random.key(0))
    adamw = AdamWConfig(weight_decay=0.0)
    spec = LiteSpec(h=4)
    tcfg = EpisodicImageConfig(way=5, shot=6, query_per_class=3,
                               image_size=12)
    batch = sample_image_task_batch(jax.random.key(3), tcfg, TASKS)
    key = jax.random.key(9)
    pbytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(params))

    configs = [
        dict(engine="single", mesh=None, kw={}),
        dict(engine="dp4", mesh=make_dp_mesh(4), kw={}),
        dict(engine="dcn1xdp4", mesh=make_two_level_dp_mesh(1, 4), kw={}),
        dict(engine="dcn2xdp2_pmean", mesh=make_two_level_dp_mesh(2, 2),
             kw={}),
        dict(engine="dcn2xdp2_compressed", mesh=make_two_level_dp_mesh(2, 2),
             kw=dict(grad_reduce="compressed")),
        dict(engine="dcn2xdp2_accum2", mesh=make_two_level_dp_mesh(2, 2),
             kw=dict(accum_steps=2)),
    ]

    rows = []
    for c in configs:
        step = make_batched_meta_train_step(learner, spec, adamw=adamw,
                                            mesh=c["mesh"], **c["kw"])
        opt = adamw_init(params, adamw)
        if c["kw"].get("grad_reduce") == "compressed":
            opt["ef"] = init_ef_state(params, 2)
        compiled = jax.jit(step).lower(params, opt, batch, key).compile()
        rep = collectives_report(compiled)

        def run(compiled=compiled, opt=opt):
            jax.block_until_ready(compiled(params, opt, batch, key))

        dt = time_median(run, 5)
        rows.append(dict(
            engine=c["engine"], devices=DEVICES, tasks_per_step=TASKS,
            param_bytes=pbytes,
            wire_bytes=round(rep["total_wire_bytes"], 1),
            wire_per_param=round(rep["total_wire_bytes"] / pbytes, 3),
            collective_count=int(rep["count"]),
            step_ms=round(1e3 * dt, 2),
            tasks_per_sec=round(TASKS / dt, 1),
        ))
    emit(rows, "dp_scaling")


def main() -> None:
    if os.environ.get("DP_SCALING_WORKER"):
        _worker()
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={DEVICES}"
                        ).strip()
    env["DP_SCALING_WORKER"] = "1"
    env["JAX_PLATFORMS"] = "cpu"    # emulated devices; never the chip
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in [str(__file__).rsplit("/", 2)[0] + "/src",
                     env.get("PYTHONPATH", "")] if p])
    r = subprocess.run([sys.executable, __file__], env=env)
    if r.returncode:
        raise RuntimeError(f"dp_scaling worker failed ({r.returncode})")


if __name__ == "__main__":
    main()
