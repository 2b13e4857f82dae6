"""CLI: ``python -m repro.lint`` / the ``repro-lint`` entry point.

The default run is the pure-AST scan (no jax import, sub-second).
``--contracts`` additionally runs the compiled-HLO contract cells; those
need a 4-device platform, so the CLI re-execs itself in a subprocess
with ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (guarded by
``REPRO_LINT_CONTRACTS_WORKER`` so the worker doesn't recurse).
"""
from __future__ import annotations

import argparse
import os
import pathlib
import subprocess
import sys
from typing import List

from repro.lint import engine, rules

_WORKER_ENV = "REPRO_LINT_CONTRACTS_WORKER"


def _list_rules() -> None:
    for r in rules.ALL_RULES:
        print(f"{r.name}")
        print(f"    invariant:  {r.invariant}")
        print(f"    recurrence: {r.recurrence}")


def _run_contracts(cells: List[str], as_json: bool) -> int:
    """Re-exec into a 4-device worker (or run directly if we are it)."""
    if os.environ.get(_WORKER_ENV) == "1":
        from repro.lint import contracts
        findings = contracts.run_cells(cells or None)
        return _emit(findings, as_json)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    env[_WORKER_ENV] = "1"
    env["JAX_PLATFORMS"] = "cpu"    # emulated devices; never the chip
    env.setdefault("PYTHONPATH", str(engine.repo_root() / "src"))
    cmd = [sys.executable, "-m", "repro.lint", "--contracts", "--no-ast"]
    if as_json:
        cmd.append("--json")
    for c in cells:
        cmd += ["--cells", c]
    return subprocess.run(cmd, env=env).returncode


def _emit(findings, as_json: bool) -> int:
    if as_json:
        print(engine.findings_json(findings))
    else:
        for f in findings:
            print(f.format())
        if findings:
            print(f"{len(findings)} finding(s)", file=sys.stderr)
    return 1 if findings else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro-lint",
        description="repo-native static analysis: AST rules + compiled-HLO "
                    "contracts (see repro.lint.__doc__ for the catalog)")
    ap.add_argument("paths", nargs="*", type=pathlib.Path,
                    help="files/dirs to scan (default: src/ and tests/)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit findings as JSON")
    ap.add_argument("--rules", action="append", default=[],
                    metavar="RULE", help="run only these rule ids")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    ap.add_argument("--contracts", action="store_true",
                    help="also run the compiled-HLO contract cells "
                         "(spawns a 4-device worker)")
    ap.add_argument("--cells", action="append", default=[], metavar="CELL",
                    help="restrict --contracts to these cell names")
    ap.add_argument("--no-ast", action="store_true",
                    help="skip the AST scan (contracts only)")
    args = ap.parse_args(argv)

    if args.list_rules:
        _list_rules()
        return 0

    rc = 0
    if not args.no_ast:
        active = list(rules.ALL_RULES)
        if args.rules:
            unknown = set(args.rules) - set(rules.RULES_BY_NAME)
            if unknown:
                ap.error(f"unknown rule(s): {sorted(unknown)} — "
                         f"see --list-rules")
            active = [rules.RULES_BY_NAME[r] for r in args.rules]
        root = engine.repo_root()
        targets = args.paths or engine.default_targets(root)
        findings = engine.lint_paths(targets, root, active)
        rc = _emit(findings, args.as_json)

    if args.contracts:
        rc = max(rc, _run_contracts(args.cells, args.as_json))
    return rc


if __name__ == "__main__":
    sys.exit(main())
