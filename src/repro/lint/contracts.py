"""Layer 2: structural contracts on compiled programs.

The AST rules catch source-level drift; these contracts catch the
failures that only exist after SPMD partitioning — a collective that
silently spans two replica groups, a layout whose wire bytes regressed
past its measured budget, a bucketed engine that
recompiles per request, a lite/chunked learner that materializes a
per-example outer-product tensor, an int8 serving path that keeps a
persistent fp32 copy of the frozen slice.  Each contract cell builds the
same miniature program an existing test builds
(so the measured ``WIRE_BUDGETS`` are directly comparable), compiles it for
real, and checks the post-SPMD HLO via :mod:`repro.roofline.hlo`.

Cells (4 emulated devices — the CLI re-execs with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``):

``replica_2x2``    weight-stationary predict on one replica group of
                   ``make_replica_mesh(2, 2)``: no collective group wider
                   than the replica's 2 devices, and per-step wire within
                   1.5x of its ``WIRE_BUDGETS`` entry.
``int8_ws``        the int8 weight-stationary layout cell from
                   tests/test_quant_serving: ws wire strictly below the
                   training layout and within 1.5x of
                   its ``WIRE_BUDGETS`` entry; the frozen slice
                   stays int8 — s8 entry parameters in the compiled
                   predict, int8 host tree, and measured frozen resident
                   bytes at least 3x below their fp32 equivalent.
``compile_flat``   a two-bucket ragged EpisodicServeEngine drained over
                   two request waves: ``adapt_compiles == len(buckets)``
                   and ``predict_compiles == 1`` — compile count must be
                   a function of the bucket plan, never the traffic.
``lite_outer``     simple_cnaps ``adapt_batch`` under a LiteSpec: no live
                   floating tensor shaped ``(..., F, F)`` with more than
                   tasks*way leading elements — the per-example
                   outer-product blowup LITE exists to avoid (the legit
                   per-class covariance is exactly ``(tasks, way, F, F)``).

The pure ``check_*`` helpers take data (HLO text / reports / stats), so
tests exercise pass AND fail paths without recompiling; the ``cell_*``
functions build the programs and need jax + 4 devices.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Sequence

from repro.lint.engine import Finding

#: budget slack over the checked-in measured numbers — generous enough to
#: absorb XLA version noise, tight enough that a layout regression (e.g.
#: weights gathered per step) blows straight through it
SLACK = 1.5

# ---------------------------------------------------------------- budgets

#: per-step compiled-HLO wire bytes as the removed serve_layouts.csv
#: (serve_small) and serve_throughput.csv (one replica of 2) recorded them
WIRE_BUDGETS = {"serve_small/training": 117888.0,
                "serve_small/weight_stationary": 15552.0,
                "replicas2_none/per_replica": 2560.0}


# ---------------------------------------------------------- pure checks

def check_inter_group(per_kind: Dict[str, Dict[str, float]],
                      group_size: int) -> List[str]:
    """No collective may span more devices than one replica group: a
    wider group means the 'disjoint replicas' claim is structurally
    false in the compiled program."""
    out = []
    for kind, rec in per_kind.items():
        if rec.get("max_group", 1) > group_size:
            out.append(
                f"{kind} spans {int(rec['max_group'])} devices but the "
                f"replica group is {group_size} wide — an inter-group "
                f"collective breaks replica isolation (weights/state "
                f"would move across groups)")
    return out


def check_wire_budget(wire_bytes: float, budget: float,
                      label: str, slack: float = SLACK) -> List[str]:
    if wire_bytes > slack * budget:
        return [f"{label}: per-step wire {wire_bytes:.0f}B exceeds "
                f"{slack}x the checked-in budget {budget:.0f}B — the "
                f"layout regressed (re-measure and update WIRE_BUDGETS if "
                f"intentional)"]
    return []


def check_compile_flat(stats: Dict, n_buckets: int) -> List[str]:
    """Compile counters must track the bucket plan, not the traffic."""
    out = []
    if stats["adapt_compiles"] != n_buckets:
        out.append(
            f"adapt_compiles={stats['adapt_compiles']} after draining "
            f"{n_buckets} bucket(s) of ragged traffic — expected exactly "
            f"{n_buckets}: one compile per planned bucket, flat across "
            f"request waves")
    if stats["predict_compiles"] != 1:
        out.append(
            f"predict_compiles={stats['predict_compiles']} — the chunked "
            f"query dispatch must compile once (chunks are padded to one "
            f"shape; task state is bucket-independent)")
    return out


_FLOAT_DTYPES = ("f64", "f32", "bf16", "f16")


def find_outer_tensors(hlo_text: str, feature_dim: int,
                       max_leading: int) -> List[str]:
    """Live floating tensors shaped ``(..., F, F)`` with more than
    ``max_leading`` leading elements, in materializing (non-fusion)
    computations.  ``max_leading = tasks * way`` admits the legit
    per-class covariance and rejects any per-example expansion."""
    from repro.roofline import hlo as hlo_mod
    comps, calls, fusion_children, _, _, _ = hlo_mod._parse(hlo_text)
    out = []
    seen = set()
    for comp, instrs in comps.items():
        if comp in fusion_children:
            continue        # fusion internals never materialize
        for ins in instrs:
            for dtype, dims in ins.result_shapes:
                if dtype not in _FLOAT_DTYPES or not dims:
                    continue
                d = [int(x) for x in dims.split(",")]
                if len(d) < 3 or d[-1] != feature_dim or d[-2] != feature_dim:
                    continue
                lead = math.prod(d[:-2])
                if lead > max_leading:
                    key = (dtype, dims)
                    if key in seen:
                        continue
                    seen.add(key)
                    out.append(
                        f"live {dtype}[{dims}] ({lead} x {feature_dim}x"
                        f"{feature_dim} outer blocks; per-class budget is "
                        f"{max_leading}) — a per-example outer-product "
                        f"tensor escaped the LITE chunking")
    return out


def entry_param_dtypes(hlo_text: str) -> List[str]:
    """Dtypes of the entry computation's parameters (what is RESIDENT
    between steps, as opposed to fused temporaries)."""
    from repro.roofline import hlo as hlo_mod
    comps, calls, _, _, _, _ = hlo_mod._parse(hlo_text)
    called = set()
    for cs in calls.values():
        called |= cs
    dtypes = []
    for comp, instrs in comps.items():
        if comp in called:
            continue
        for ins in instrs:
            if ins.opcode == "parameter":
                dtypes.extend(d for d, _ in ins.result_shapes)
    return dtypes


def check_int8_residency(hlo_text: str, sw, bytes_report: Dict) -> List[str]:
    """The int8 frozen slice must be resident AS int8: s8 entry params in
    the compiled predict, int8 leaves in the host tree, and measured
    frozen bytes >= 3x below fp32 — together these rule out a persistent
    fp32 copy (eager dequantization outside the jitted step)."""
    import jax.numpy as jnp

    out = []
    if not sw.quant_paths:
        return ["serving weights carry no quantized paths — the int8 "
                "cell was built without quantize_frozen(mode='int8')"]
    if "s8" not in entry_param_dtypes(hlo_text):
        out.append(
            "no s8 parameter reaches the compiled predict's entry "
            "computation — the program consumes an already-dequantized "
            "(persistent fp32) copy of the frozen slice")
    from repro.serve.quant_params import is_quantized_leaf
    import jax
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            sw.tree, is_leaf=is_quantized_leaf)[0]:
        if is_quantized_leaf(leaf) and leaf["q"].dtype != jnp.int8:
            out.append(f"quantized leaf {path} stores q as "
                       f"{leaf['q'].dtype}, not int8")
            break
    froz, froz32 = (bytes_report["frozen_resident_bytes"],
                    bytes_report["frozen_fp32_bytes"])
    if froz * 3 > froz32:
        out.append(
            f"frozen slice resident bytes {froz} not >=3x below fp32 "
            f"equivalent {froz32} — an fp32 copy of the frozen slice is "
            f"persisting alongside the int8 one")
    return out


# ------------------------------------------------------------- the cells

def _require_devices(n: int = 4) -> None:
    import jax
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"contract cells need {n} devices "
            f"(run via `python -m repro.lint --contracts`, which re-execs "
            f"with XLA_FLAGS=--xla_force_host_platform_device_count={n}); "
            f"got {len(jax.devices())}")


def _compile_predict(learner, sw, states, query_x, mesh, layout: str):
    """Compile the engine's predict dispatch under a named serving layout
    — same construction as roofline.analysis.score_serving_layout, but
    returning the HLO text so several contracts share one compile."""
    import jax

    from repro.roofline.analysis import batch_shardings, serving_shardings
    from repro.serve.quant_params import dequantize_params

    def predict(w, st, qx):
        return learner.predict_batch(dequantize_params(w), st, qx)

    in_sh = (serving_shardings(sw, mesh, layout),
             batch_shardings(states, mesh, layout),
             batch_shardings(query_x, mesh, layout))
    compiled = jax.jit(predict, in_shardings=in_sh).lower(
        sw, states, query_x).compile()
    return compiled.as_text()


def cell_replica_2x2() -> List[str]:
    """One group of make_replica_mesh(2, 2): intra-group-only collectives
    + wire budget (tests/test_replica.py checks group-scoped wire too)."""
    _require_devices(4)
    import jax
    import jax.numpy as jnp

    from repro.core.episodic_train import task_key
    from repro.core.lite import LiteSpec
    from repro.core.meta_learners import MetaLearnerConfig, make_learner
    from repro.core.set_encoder import SetEncoderConfig
    from repro.data.episodic import (EpisodicImageConfig, collate_task_batch,
                                     plan_buckets, sample_image_task)
    from repro.launch.mesh import make_replica_mesh
    from repro.models.conv_backbone import (ConvBackboneConfig,
                                            make_conv_backbone)
    from repro.roofline.hlo import collectives_report
    from repro.serve.quant_params import dequantize_params, quantize_frozen

    way, shot, query, image = 5, 4, 4, 12
    learner = make_learner(
        MetaLearnerConfig(kind="protonets", way=way),
        make_conv_backbone(ConvBackboneConfig(widths=(8,), feature_dim=16)),
        SetEncoderConfig(kind="conv", conv_blocks=1, conv_width=8,
                         task_dim=16))
    params = learner.init(jax.random.key(0))
    lite = LiteSpec(exact=True, chunk_size=32)
    cfg = EpisodicImageConfig(way=way, shot=shot, query_per_class=query,
                              image_size=image)
    buckets = plan_buckets([way * shot], max_buckets=1)
    probe = [sample_image_task(jax.random.key(i), cfg) for i in range(2)]
    pbatch = collate_task_batch(probe, support_size=max(buckets),
                                query_size=probe[0].query_x.shape[0])
    pkeys = jax.vmap(lambda i: task_key(jax.random.key(0), i))(jnp.arange(2))

    meshes = make_replica_mesh(2, 2)
    sw = quantize_frozen(learner, params, "none")
    states = learner.adapt_batch(dequantize_params(sw), pbatch, pkeys, lite)
    text = _compile_predict(learner, sw, states, pbatch.query_x,
                            meshes[0], "weight_stationary")
    rep = collectives_report(text)
    msgs = check_inter_group(rep["per_kind"], group_size=2)
    msgs += check_wire_budget(rep["total_wire_bytes"],
                              WIRE_BUDGETS["replicas2_none/per_replica"],
                              "replica_2x2 weight_stationary predict")
    return msgs


def cell_int8_ws() -> List[str]:
    """The int8 weight-stationary layout cell (mirrors
    tests/test_quant_serving's measured setup): wire strictly below the
    training layout and within budget, frozen slice resident as int8."""
    _require_devices(4)
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
    from repro.roofline.hlo import collectives_report
    from repro.serve.quant_params import (dequantize_params, param_bytes,
                                          quantize_frozen)

    learner = make_learner(
        MetaLearnerConfig(kind="protonets", way=3),
        make_conv_backbone(ConvBackboneConfig(widths=(16, 32),
                                              feature_dim=64)),
        SetEncoderConfig(kind="conv", conv_blocks=2, conv_width=16,
                        task_dim=32))
    params = learner.init(jax.random.key(0))
    sw = quantize_frozen(learner, params, "int8")
    mesh = jax.make_mesh((4,), ("serve",))
    tasks = [sample_image_task(
        jax.random.key(100 + i),
        EpisodicImageConfig(way=3, shot=5, query_per_class=4, image_size=8))
        for i in range(2)]
    batch = collate_task_batch(tasks, support_size=16, query_size=12)
    keys = jax.vmap(lambda i: task_key(jax.random.key(0), i))(jnp.arange(2))
    lite = LiteSpec(exact=True, chunk_size=8)
    states = learner.adapt_batch(dequantize_params(sw), batch, keys, lite)

    ws_text = _compile_predict(learner, sw, states, batch.query_x,
                               mesh, "weight_stationary")
    tr_text = _compile_predict(learner, sw, states, batch.query_x,
                               mesh, "training")
    ws = collectives_report(ws_text)["total_wire_bytes"]
    tr = collectives_report(tr_text)["total_wire_bytes"]

    msgs = check_wire_budget(ws, WIRE_BUDGETS["serve_small/weight_stationary"],
                             "int8_ws weight_stationary predict")
    if not ws < tr:
        msgs.append(
            f"weight_stationary wire {ws:.0f}B is not strictly below the "
            f"training layout's {tr:.0f}B at serving batch sizes — the "
            f"layout's reason to exist (ship activations, not gathered "
            f"weights) no longer holds")
    msgs += check_int8_residency(ws_text, sw, param_bytes(sw))
    return msgs


def cell_compile_flat() -> List[str]:
    """Two-bucket ragged engine, two waves of fresh uids: compile
    counters must equal (len(buckets), 1) and stay flat across waves."""
    import numpy as np
    import jax

    from repro.core.lite import LiteSpec
    from repro.core.meta_learners import MetaLearnerConfig, make_learner
    from repro.core.set_encoder import SetEncoderConfig
    from repro.data.episodic import (EpisodicImageConfig, plan_buckets,
                                     sample_image_task)
    from repro.models.conv_backbone import (ConvBackboneConfig,
                                            make_conv_backbone)
    from repro.serve.episodic import EpisodicRequest, EpisodicServeEngine

    way = 3
    learner = make_learner(
        MetaLearnerConfig(kind="protonets", way=way),
        make_conv_backbone(ConvBackboneConfig(widths=(8,), feature_dim=16)),
        SetEncoderConfig(kind="conv", conv_blocks=1, conv_width=8,
                         task_dim=16))
    params = learner.init(jax.random.key(0))
    shots = (2, 5)                               # ragged: supports 6 and 15
    buckets = plan_buckets([way * s for s in shots], max_buckets=2)
    engine = EpisodicServeEngine(
        learner, params, lite=LiteSpec(exact=True, chunk_size=8),
        n_slots=1, query_chunk=8, support_buckets=buckets,
        cache_capacity=16)

    uid = 0
    for _wave in range(2):
        for shot in shots:
            cfg = EpisodicImageConfig(way=way, shot=shot, query_per_class=4,
                                      image_size=8)
            t = sample_image_task(jax.random.key(uid), cfg)
            engine.submit(EpisodicRequest(
                uid=uid, support_x=np.asarray(t.support_x),
                support_y=np.asarray(t.support_y),
                query_x=np.asarray(t.query_x), way=way))
            uid += 1
        while engine.busy:
            engine.step()
    return check_compile_flat(engine.stats(), n_buckets=len(buckets))


def cell_lite_outer() -> List[str]:
    """simple_cnaps adapt under LITE: the compiled program may hold the
    per-class (tasks, way, F, F) covariance but nothing wider."""
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

    way, tasks, feature_dim = 3, 2, 16
    learner = make_learner(
        MetaLearnerConfig(kind="simple_cnaps", way=way),
        make_conv_backbone(ConvBackboneConfig(widths=(8,),
                                              feature_dim=feature_dim)),
        SetEncoderConfig(kind="conv", conv_blocks=1, conv_width=8,
                         task_dim=16))
    params = learner.init(jax.random.key(0))
    ts = [sample_image_task(
        jax.random.key(10 + i),
        EpisodicImageConfig(way=way, shot=5, query_per_class=4, image_size=8))
        for i in range(tasks)]
    batch = collate_task_batch(ts, support_size=16, query_size=12)
    keys = jax.vmap(lambda i: task_key(jax.random.key(0), i))(
        jnp.arange(tasks))
    lite = LiteSpec(exact=True, chunk_size=8)

    text = jax.jit(
        lambda p, b, k: learner.adapt_batch(p, b, k, lite)).lower(
        params, batch, keys).compile().as_text()
    # budget: per-class blocks times 2 — XLA materializes the
    # lam-weighted covariance pair (class + task) as one stacked
    # (tasks, 2, way, F, F) tensor before the sum; any per-example
    # expansion is >= shot x wider and still lands over budget
    return find_outer_tensors(text, feature_dim, max_leading=2 * tasks * way)


CELLS = {
    "replica_2x2": cell_replica_2x2,
    "int8_ws": cell_int8_ws,
    "compile_flat": cell_compile_flat,
    "lite_outer": cell_lite_outer,
}

_CELL_RULES = {
    "replica_2x2": "contract-replica",
    "int8_ws": "contract-int8",
    "compile_flat": "contract-compile-flat",
    "lite_outer": "contract-lite-outer",
}


def run_cells(names: Optional[Sequence[str]] = None) -> List[Finding]:
    names = list(names) if names else list(CELLS)
    unknown = set(names) - set(CELLS)
    if unknown:
        raise KeyError(f"unknown contract cell(s) {sorted(unknown)}; "
                       f"known: {sorted(CELLS)}")
    findings: List[Finding] = []
    for name in names:
        for msg in CELLS[name]():
            findings.append(Finding(path=f"contracts/{name}", line=0,
                                    rule=_CELL_RULES[name], message=msg))
    return findings
