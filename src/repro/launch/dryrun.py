"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell
with ShapeDtypeStruct inputs (zero allocation) on 512 placeholder devices,
and record memory / FLOPs / collective traffic for the roofline.

    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k

Output: one JSON record per cell appended to --out (default
$TMPDIR/dryrun.json), keyed "arch/shape/mesh", so interrupted
sweeps resume where they stopped.
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # placeholder devices; never the chip
os.environ["REPRO_MIXED_DOT"] = "1"   # AOT-only: bf16 dots w/ f32 accum

import argparse
import json
import pathlib
import tempfile
import time
import traceback
from typing import Callable, Dict

import jax
import numpy as np
from jax.sharding import NamedSharding

from repro.configs.base import SHAPES, SHAPES_BY_NAME
from repro.configs.registry import ARCH_IDS, cell_supported, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (abstract_cache_for, abstract_params_for,
                                batch_specs_for)
from repro.models.registry import get_api
from repro.roofline import hlo as hlo_parse
from repro.sharding import rules
from repro.sharding.ctx import P
from repro.train.step import adamw_for, make_init_state, make_train_step

DEFAULT_OUT = pathlib.Path(tempfile.gettempdir()) / "dryrun.json"


def _named(tree_specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


def _mem_analysis(compiled) -> Dict:
    try:
        ma = compiled.memory_analysis()
        keys = ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes")
        return {k: int(getattr(ma, k)) for k in keys if hasattr(ma, k)}
    except Exception as e:  # CPU backend may not implement it
        return {"error": str(e)}


def _analytic_state_bytes(abstract_state, specs, mesh) -> int:
    """Per-device parameter+optimizer bytes implied by the shardings —
    byte-exact fallback/cross-check for memory_analysis."""
    sizes = dict(mesh.shape)
    total = 0

    def shard_elems(shape, spec):
        n = int(np.prod(shape)) if shape else 1
        denom = 1
        for entry in spec:
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            for nm in names:
                denom *= sizes[nm]
        return n // max(denom, 1)

    flat_s, _ = jax.tree.flatten(abstract_state)
    flat_p = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for a, sp in zip(flat_s, flat_p):
        total += shard_elems(a.shape, sp) * a.dtype.itemsize
    return total


VARIANTS = {
    # paper-faithful GSPMD-only lowering — the §Perf baseline
    "baseline": dict(moe_shard_map=False, attn_head_constraints=False,
                     tp_enabled=True),
    # production defaults (all §Perf levers on)
    "optimized": dict(),
}


def apply_variant(cfg, variant: str):
    import dataclasses
    over = dict(VARIANTS[variant])
    if variant == "baseline":
        # baseline keeps per-arch tp choice out of the picture too
        over["tp_enabled"] = True
        over["shard_activations_model"] = True
    return dataclasses.replace(cfg, **over) if over else cfg


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             variant: str = "optimized",
             clock: Callable[[], float] = time.monotonic) -> Dict:
    """``clock`` is injectable (PR6/PR7 clock discipline): the default is
    a monotonic wall clock for the launcher path; tests may pass a
    FakeClock so the recorded lower/compile timings are deterministic."""
    cfg = apply_variant(get_config(arch), variant)
    shape = SHAPES_BY_NAME[shape_name]
    api = get_api(cfg)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = int(np.prod(list(mesh.shape.values())))

    batch_abs = batch_specs_for(cfg, shape)
    # tp_enabled=False is only a win when the batch can cover the WHOLE
    # mesh as pure DP (model axis folded into the batch); otherwise chips
    # would idle/replicate — fall back to TP for that shape.
    sizes = dict(mesh.shape)
    axes, prod = [], 1
    for ax in ("pod", "data", "model"):
        if ax in sizes and shape.global_batch % (prod * sizes[ax]) == 0:
            axes.append(ax)
            prod *= sizes[ax]
    full_dp = prod == n_chips
    tp_off = (not cfg.tp_enabled) and full_dp
    if tp_off:
        dp = tuple(axes)
        braw = jax.tree.map(
            lambda a: P(*([dp] + [None] * (len(a.shape) - 1))) if a.shape else P(),
            batch_abs)
    else:
        braw = rules.batch_specs(batch_abs)
    bspecs = rules.sanitize(braw, batch_abs, mesh)

    def tp_strip(specs):
        return rules.strip_axes(specs) if tp_off else specs

    t0 = clock()
    if shape.kind == "train":
        init = make_init_state(cfg, adamw_for(cfg))
        state_abs = jax.eval_shape(init, jax.random.key(0))
        sspecs = dict(
            params=rules.param_specs(state_abs["params"]),
            opt=rules.opt_state_specs(state_abs["opt"]),
        )
        sspecs = rules.sanitize(tp_strip(sspecs), state_abs, mesh)
        step = make_train_step(cfg, adamw_for(cfg))
        with mesh:
            lowered = jax.jit(
                step,
                in_shardings=(_named(sspecs, mesh), _named(bspecs, mesh)),
                out_shardings=(_named(sspecs, mesh), None),
            ).lower(state_abs, batch_abs)
        state_bytes = _analytic_state_bytes(state_abs, sspecs, mesh)
    elif shape.kind == "prefill":
        params_abs = abstract_params_for(cfg)
        pspecs = rules.sanitize(tp_strip(rules.param_specs(params_abs)),
                                params_abs, mesh)
        cache_abs = abstract_cache_for(cfg, shape)
        cspecs = rules.sanitize(
            tp_strip(rules.cache_specs(cache_abs, shape.global_batch,
                                       mesh.shape["data"])),
            cache_abs, mesh)

        def prefill_fn(params, batch):
            return api.prefill(params, batch, cfg)

        with mesh:
            lowered = jax.jit(
                prefill_fn,
                in_shardings=(_named(pspecs, mesh), _named(bspecs, mesh)),
                out_shardings=(None, _named(cspecs, mesh)),
            ).lower(params_abs, batch_abs)
        state_bytes = _analytic_state_bytes(params_abs, pspecs, mesh)
    else:  # decode
        params_abs = abstract_params_for(cfg)
        pspecs = rules.sanitize(tp_strip(rules.param_specs(params_abs)),
                                params_abs, mesh)
        cache_abs = abstract_cache_for(cfg, shape)
        cspecs = rules.sanitize(
            tp_strip(rules.cache_specs(cache_abs, shape.global_batch,
                                       mesh.shape["data"])),
            cache_abs, mesh)

        def decode_fn(params, cache, tokens):
            return api.decode_step(params, cache, tokens, cfg)

        with mesh:
            lowered = jax.jit(
                decode_fn,
                in_shardings=(_named(pspecs, mesh), _named(cspecs, mesh),
                              _named(bspecs, mesh)["tokens"]),
                out_shardings=(None, _named(cspecs, mesh)),
            ).lower(params_abs, cache_abs, batch_abs["tokens"])
        state_bytes = (_analytic_state_bytes(params_abs, pspecs, mesh) +
                       _analytic_state_bytes(cache_abs, cspecs, mesh))
    t_lower = clock() - t0

    t0 = clock()
    compiled = lowered.compile()
    t_compile = clock() - t0

    cost = hlo_parse.xla_cost_analysis(compiled)
    analysis = hlo_parse.analyze(compiled.as_text())
    mem = _mem_analysis(compiled)

    return dict(
        arch=arch, shape=shape_name, mesh=mesh_kind, chips=n_chips,
        variant=variant, status="ok",
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        # loop-aware (repro.roofline.hlo) — the roofline inputs
        flops_per_device=analysis["dot_flops"],
        bytes_per_device=analysis["bytes_accessed"],
        collectives=analysis["collectives"],
        # XLA's loop-naive numbers, for reference / cross-check
        xla_flops_per_device=float(cost.get("flops", 0.0)),
        xla_bytes_per_device=float(cost.get("bytes accessed", 0.0)),
        memory_analysis=mem,
        state_bytes_per_device=int(state_bytes),
    )


def load_results(path: pathlib.Path) -> Dict:
    if path.exists():
        return json.loads(path.read_text())
    return {}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS) + ["all"], default="all")
    ap.add_argument("--shape", choices=[s.name for s in SHAPES] + ["all"],
                    default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--out", type=pathlib.Path, default=DEFAULT_OUT)
    ap.add_argument("--variant", choices=list(VARIANTS), default="optimized")
    ap.add_argument("--force", action="store_true", help="recompute existing cells")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = [s.name for s in SHAPES] if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    args.out.parent.mkdir(parents=True, exist_ok=True)
    results = load_results(args.out)
    n_fail = 0
    for arch in archs:
        for shape in shapes:
            ok, reason = cell_supported(arch, shape)
            for mesh_kind in meshes:
                key = f"{arch}/{shape}/{mesh_kind}"
                if key in results and results[key].get("status") in ("ok", "skipped") \
                        and not args.force:
                    continue
                if not ok:
                    results[key] = dict(arch=arch, shape=shape, mesh=mesh_kind,
                                        status="skipped", reason=reason)
                    args.out.write_text(json.dumps(results, indent=1))
                    print(f"[skip] {key}: {reason}")
                    continue
                print(f"[run ] {key} ({args.variant}) ...", flush=True)
                try:
                    rec = run_cell(arch, shape, mesh_kind, args.variant)
                    print(f"[ ok ] {key}: compile={rec['compile_s']}s "
                          f"flops/dev={rec['flops_per_device']:.3e} "
                          f"state_bytes/dev={rec['state_bytes_per_device']:.3e}",
                          flush=True)
                except Exception as e:
                    n_fail += 1
                    rec = dict(arch=arch, shape=shape, mesh=mesh_kind,
                               status="fail", error=str(e)[-2000:],
                               tb=traceback.format_exc()[-4000:])
                    print(f"[FAIL] {key}: {e}", flush=True)
                results[key] = rec
                args.out.write_text(json.dumps(results, indent=1))
    print(f"done: {len(results)} cells, {n_fail} failures this run")


if __name__ == "__main__":
    main()
