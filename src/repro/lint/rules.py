"""The repo-specific rule set.

Each rule encodes an invariant this codebase has already paid for once:
a drift shim that exists because an upstream rename broke the build, a
clock/RNG/publish discipline that exists because a test was flaky or a
crash left half-written state.  The linter's job is to make the third
occurrence impossible, not to restyle code — so every rule is scoped to
the layers where its invariant is load-bearing and stays silent
elsewhere.

Rules must not import jax (the AST scan runs in the tier-1 test suite
and must stay sub-second); the compiled-program contracts that do need
jax live in :mod:`repro.lint.contracts`.
"""
from __future__ import annotations

import ast
from typing import Iterable, List

from repro.lint.engine import Finding, LintContext, Rule


def _dotted(node: ast.AST) -> str:
    """'jax.experimental.shard_map' for nested Attribute/Name chains,
    '' for anything dynamic."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _under(rel: str, *prefixes: str) -> bool:
    return any(rel == p or rel.startswith(p.rstrip("/") + "/")
               for p in prefixes)


class JaxApiDriftRule(Rule):
    name = "jax-api-drift"
    invariant = ("shard_map and pallas CompilerParams are reached only "
                 "through the repo shims (repro.sharding / "
                 "repro.kernels.tpu_compat), and no call passes the "
                 "removed check_rep= keyword")
    recurrence = ("jax moved shard_map out of jax.experimental, renamed "
                  "TPUCompilerParams, and replaced shard_map's check_rep= "
                  "with check_vma=; every direct call site broke at once "
                  "— the shims absorb the next rename in one place")

    _SHIMS = ("src/repro/sharding/__init__.py",
              "src/repro/kernels/tpu_compat.py")
    _PARAMS = {"CompilerParams", "TPUCompilerParams"}

    def applies(self, rel: str) -> bool:
        return rel not in self._SHIMS

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.keyword) and node.arg == "check_rep":
                yield ctx.finding(
                    node.value, self.name,
                    "check_rep= — jax's shard_map takes check_vma= now "
                    "and rejects check_rep=")
            elif isinstance(node, ast.Attribute):
                dotted = _dotted(node)
                if dotted in ("jax.shard_map", "jax.experimental.shard_map",
                              "jax.experimental.shard_map.shard_map"):
                    yield ctx.finding(
                        node, self.name,
                        f"direct {dotted} — import shard_map from "
                        f"repro.sharding (the drift shim) instead")
                elif node.attr in self._PARAMS and \
                        _dotted(node.value) != "tpu_compat":
                    yield ctx.finding(
                        node, self.name,
                        f"direct pallas {node.attr} — use "
                        f"repro.kernels.tpu_compat.CompilerParams, which "
                        f"tracks the pltpu rename")
            elif isinstance(node, ast.ImportFrom) and node.module:
                mod = node.module
                names = {a.name for a in node.names}
                if (mod == "jax" and "shard_map" in names) or \
                        mod.startswith("jax.experimental.shard_map") or \
                        (mod == "jax.experimental" and "shard_map" in names):
                    yield ctx.finding(
                        node, self.name,
                        f"importing shard_map from {mod} — import it from "
                        f"repro.sharding (the drift shim) instead")
                elif "pallas" in mod and (names & self._PARAMS):
                    yield ctx.finding(
                        node, self.name,
                        f"importing {sorted(names & self._PARAMS)[0]} from "
                        f"{mod} — use repro.kernels.tpu_compat instead")


class RawCostAnalysisRule(Rule):
    name = "raw-cost-analysis"
    invariant = ("compiled.cost_analysis() is only called through "
                 "roofline.hlo.xla_cost_analysis")
    recurrence = ("cost_analysis() has returned a dict, a 1-list of dicts, "
                  "and None across jax versions; each bare call site "
                  "re-grows its own half of the normalization")

    def applies(self, rel: str) -> bool:
        return rel != "src/repro/roofline/hlo.py"

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "cost_analysis":
                yield ctx.finding(
                    node, self.name,
                    "bare compiled.cost_analysis() — call "
                    "repro.roofline.hlo.xla_cost_analysis(compiled), which "
                    "normalizes the dict/list/None drift once")


class ClockDisciplineRule(Rule):
    name = "clock-discipline"
    invariant = ("serve/train/faults/launch code reads time only through "
                 "an injectable clock parameter (default time.monotonic); "
                 "wall-clock CALLS are confined to defaults and shims")
    recurrence = ("inline time.time() made SLO accounting untestable and "
                  "non-monotonic under clock steps; PR6/PR7 moved every "
                  "component onto injected clocks — new code kept "
                  "reintroducing bare calls")

    _FNS = {"time", "monotonic", "sleep", "perf_counter"}

    def applies(self, rel: str) -> bool:
        return _under(rel, "src/repro/serve", "src/repro/train",
                      "src/repro/faults", "src/repro/launch")

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        # names bound by `from time import sleep [as z]`
        local = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom) and node.module == "time":
                for a in node.names:
                    if a.name in self._FNS:
                        local[a.asname or a.name] = a.name
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            hit = None
            if isinstance(fn, ast.Attribute) and fn.attr in self._FNS and \
                    _dotted(fn.value) == "time":
                hit = f"time.{fn.attr}"
            elif isinstance(fn, ast.Name) and fn.id in local:
                hit = f"time.{local[fn.id]}"
            if hit:
                yield ctx.finding(
                    node, self.name,
                    f"bare {hit}() call — take an injectable "
                    f"`clock: Callable[[], float] = time.monotonic` "
                    f"parameter (referencing time.monotonic as a default "
                    f"is fine; calling it inline is not) so tests can "
                    f"drive a FakeClock")


class AtomicPublishRule(Rule):
    name = "atomic-publish"
    invariant = ("durable state under serve/ and the checkpointer is "
                 "written to a tmp path and published with os.replace — "
                 "never written in place")
    recurrence = ("a crash between open('wb') and close left a torn "
                  "checkpoint/warm-tier entry that a restart then trusted; "
                  "the fault suite (ckpt.pre_*, warm.corrupt) exists "
                  "because of it")

    def applies(self, rel: str) -> bool:
        return _under(rel, "src/repro/serve") or \
            rel == "src/repro/train/checkpoint.py"

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if isinstance(fn, ast.Name) and fn.id == "open" and node.args:
                mode = node.args[1] if len(node.args) > 1 else None
                for kw in node.keywords:
                    if kw.arg == "mode":
                        mode = kw.value
                if not (isinstance(mode, ast.Constant) and
                        isinstance(mode.value, str)):
                    continue  # dynamic mode: out of static reach
                if not (set(mode.value) & set("wax")):
                    continue  # read/update modes don't create torn files
                path_src = ctx.segment(node.args[0])
                if "tmp" not in path_src.lower():
                    yield ctx.finding(
                        node, self.name,
                        f"open({path_src!r}, {mode.value!r}) writes a "
                        f"durable path in place — write to a tmp sibling "
                        f"and publish with os.replace")
            elif isinstance(fn, ast.Attribute) and \
                    fn.attr in ("write_text", "write_bytes"):
                path_src = ctx.segment(fn.value)
                if "tmp" not in path_src.lower():
                    yield ctx.finding(
                        node, self.name,
                        f"{path_src}.{fn.attr}(...) writes a durable path "
                        f"in place — write to a tmp sibling and publish "
                        f"with os.replace")


class FaultSiteRegistryRule(Rule):
    name = "fault-site-registry"
    invariant = ("every fault site named at an injection or plan call site "
                 "uses a constant from repro.faults.plan, and the registry "
                 "(FAULT_SITES) validates FaultSpec at construction")
    recurrence = ('a raw "warm.corrupt" literal at a fire() site silently '
                  "decoupled from the registry; a typo there makes an "
                  "injection point unreachable with no error anywhere")

    _SITE_CALLS = {"fire": 0, "_maybe_kill": 0, "single": 0}

    def applies(self, rel: str) -> bool:
        return rel != "src/repro/faults/plan.py"

    def _constant_for(self, value: str) -> str:
        try:
            from repro.faults import plan
            for name in dir(plan):
                if name.isupper() and getattr(plan, name, None) == value:
                    return f"repro.faults.plan.{name}"
        except Exception:
            pass
        return "a repro.faults.plan constant"

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            site_arg = None
            if isinstance(fn, ast.Attribute):
                if fn.attr in ("fire", "_maybe_kill"):
                    site_arg = node.args[0] if node.args else None
                elif fn.attr == "single" and _dotted(fn.value) == "FaultPlan":
                    site_arg = node.args[0] if node.args else None
                elif fn.attr == "seeded" and _dotted(fn.value) == "FaultPlan":
                    site_arg = node.args[1] if len(node.args) > 1 else None
            elif isinstance(fn, ast.Name) and fn.id in ("FaultSpec",
                                                        "_maybe_kill"):
                site_arg = node.args[0] if node.args else None
            for kw in node.keywords:
                if kw.arg == "site" and site_arg is None and \
                        (isinstance(fn, ast.Name) and fn.id == "FaultSpec"
                         or isinstance(fn, ast.Attribute) and
                         fn.attr in ("fire", "single", "seeded")):
                    site_arg = kw.value
            if isinstance(site_arg, ast.Constant) and \
                    isinstance(site_arg.value, str):
                yield ctx.finding(
                    node, self.name,
                    f"raw fault-site literal {site_arg.value!r} — use "
                    f"{self._constant_for(site_arg.value)} so the site "
                    f"registry and the wired injection points cannot "
                    f"drift apart")


class SeededRngRule(Rule):
    name = "seeded-rng"
    invariant = ("library code draws randomness only from explicitly "
                 "seeded np.random.default_rng / jax.random.key streams")
    recurrence = ("legacy np.random.* globals made fault soaks and "
                  "episodic samplers irreproducible across processes — "
                  "the whole harness is built on bit-exact replay")

    _CONSTRUCTORS = {"default_rng", "Generator", "PCG64", "PCG64DXSM",
                     "Philox", "SFC64", "MT19937", "SeedSequence",
                     "BitGenerator"}

    def applies(self, rel: str) -> bool:
        return _under(rel, "src/repro")

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call) and
                    isinstance(node.func, ast.Attribute)):
                continue
            fn = node.func
            if _dotted(fn.value) not in ("np.random", "numpy.random"):
                continue
            if fn.attr not in self._CONSTRUCTORS:
                yield ctx.finding(
                    node, self.name,
                    f"legacy global-state np.random.{fn.attr}(...) — "
                    f"thread an explicit np.random.default_rng(seed) "
                    f"Generator instead")
            elif fn.attr == "default_rng" and not node.args and \
                    not node.keywords:
                yield ctx.finding(
                    node, self.name,
                    "np.random.default_rng() with no seed is entropy-"
                    "seeded — pass an explicit seed so runs replay "
                    "bit-exactly")


class StaticAuxHashableRule(Rule):
    name = "static-aux-hashable"
    invariant = ("pytree aux_data (the static half of register_pytree_node "
                 "flatteners) is built from hashable literals — tuples, "
                 "strings, numbers — never list/dict/set displays")
    recurrence = ("an unhashable aux turns every jit trace into a cache "
                  "miss (or a TypeError under newer jax) the first time "
                  "the pytree crosses a jit boundary — found the hard way "
                  "with ServingWeights quant_paths")

    def applies(self, rel: str) -> bool:
        return _under(rel, "src/repro")

    _UNHASHABLE = (ast.List, ast.Dict, ast.Set,
                   ast.ListComp, ast.DictComp, ast.SetComp)

    def _aux_nodes(self, flatten: ast.AST, tree: ast.AST):
        """Yield the aux expression(s) of a flatten fn given as a lambda
        or a reference to a module-level def."""
        if isinstance(flatten, ast.Lambda):
            body = flatten.body
            if isinstance(body, ast.Tuple) and len(body.elts) == 2:
                yield body.elts[1]
        elif isinstance(flatten, ast.Name):
            for fd in ast.walk(tree):
                if isinstance(fd, ast.FunctionDef) and fd.name == flatten.id:
                    for ret in ast.walk(fd):
                        if isinstance(ret, ast.Return) and \
                                isinstance(ret.value, ast.Tuple) and \
                                len(ret.value.elts) == 2:
                            yield ret.value.elts[1]

    def check(self, ctx: LintContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            is_reg = (isinstance(fn, ast.Attribute) and
                      fn.attr == "register_pytree_node") or \
                     (isinstance(fn, ast.Name) and
                      fn.id == "register_pytree_node")
            if not is_reg or len(node.args) < 2:
                continue
            for aux in self._aux_nodes(node.args[1], ctx.tree):
                for sub in ast.walk(aux):
                    if isinstance(sub, self._UNHASHABLE):
                        kind = type(sub).__name__
                        yield ctx.finding(
                            sub, self.name,
                            f"unhashable {kind} in pytree aux_data — aux "
                            f"must hash (it keys the jit trace cache); "
                            f"use tuples/frozensets")
                        break


ALL_RULES = (
    JaxApiDriftRule(),
    RawCostAnalysisRule(),
    ClockDisciplineRule(),
    AtomicPublishRule(),
    FaultSiteRegistryRule(),
    SeededRngRule(),
    StaticAuxHashableRule(),
)

RULES_BY_NAME = {r.name: r for r in ALL_RULES}
