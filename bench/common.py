"""What every cell shares: loading the cell from ``BENCHMARK.json``, the
device check, the persistent compile cache, the compile counter, the
per-layer metric readers and the result line.

A cell is found by name: its ``workloads`` entry names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the mix names the harness that drives
it (``bench/harness/<harness>.py``); each per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding a cell adds files and entries and
edits none of this.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import pathlib
import sys
from typing import Any, Callable, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = load_json(root / conf["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    per = [m for m in spec["per_layer"]
           if _applies(m, name)
           and any(e["name"] == m["moves"] for e in e2e)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, or where ``JAX_COMPILATION_CACHE_DIR`` says.  Every program
    is cached, however short its compile, so only a cell's first run in a
    checkout compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(n: int):
    """The devices to run on; raises :class:`NoChip` off a TPU or with
    fewer than ``n`` chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chip(s), JAX found {len(devs)}")
    return devs[:n]


class CompileCounter:
    """Counts executables built in this process, compiled or loaded from
    the persistent cache (JAX times both as one backend-compile event)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.count += 1


def load_reader(metric: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    """``bench/metrics/<metric>.py``'s ``read(obs)``: the metric's value
    from the run's observations, or None where it finds nothing."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_harness(name: str):
    return importlib.import_module(f"bench.harness.{name}")


@dataclasses.dataclass
class Check:
    """One number compared for ``correct``: it passes when
    ``value <= limit`` (a non-finite value fails)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def device_info(devices, memory_peak_bytes: Optional[int]) -> Dict[str, Any]:
    d = devices[0]
    return dict(platform=d.platform, kind=d.device_kind, count=len(devices),
                memory_peak_bytes=memory_peak_bytes)


def peak_bytes(devices) -> Optional[int]:
    """Peak device memory on the fullest chip since the process began: the
    allocator's peak of live buffers (``peak_bytes_in_use``) plus its peak
    reservation for the executables' temporaries (``peak_bytes_reserved``,
    which the TPU runtime keeps apart from the buffers)."""
    vals = []
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            vals.append(st["peak_bytes_in_use"]
                        + st.get("peak_bytes_reserved", 0))
    return max(vals) if vals else None


def emit(result: Dict[str, Any], checks: List[Check]) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, then the result line as the last line of standard
    output, with the checks under the key that comes last."""
    table = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    out = dict(result)
    out["checks"] = table
    print(json.dumps(out), flush=True)


def peaks(device_kind: str) -> Dict[str, Any]:
    """The chip's published peaks from ``bench/peaks.json``; a device that
    is not in the table is an error."""
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to "
                       f"bench/peaks.json with its source")
    return table[device_kind]


def metrics(cell: Cell, obs: Dict[str, Any], trace: bool) -> Dict[str, Any]:
    """The cell's end-to-end metrics (``trace`` False) or per-layer
    metrics (``trace`` True), each read from ``obs`` by its own reader;
    a metric whose reader finds nothing is left out."""
    out: Dict[str, Any] = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = load_reader(m["name"])(obs)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
