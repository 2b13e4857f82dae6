"""Benchmark orchestrator: one module per paper table/figure + kernel and
roofline reports.  ``PYTHONPATH=src python -m benchmarks.run``

Every module runs in this one process; the ones that emulate devices
re-exec a child with ``JAX_PLATFORMS=cpu``, so a child never reaches for
a chip this process already holds."""
from __future__ import annotations

import sys
import time
import traceback

MODULES = (
    "benchmarks.fig4_rmse",            # paper Fig. 4 / Tables D.7-D.8
    "benchmarks.table2_vary_h",        # paper Table 2 / D.4-D.6
    "benchmarks.table1_adaptation_cost",  # paper Table 1 adaptation cost
    "benchmarks.memory_vs_h",          # paper §D.4 memory-vs-|H| claim
    "benchmarks.serve_throughput",     # episodic serving engine throughput
    "benchmarks.kernel_bench",         # Pallas kernels vs jnp reference
    "benchmarks.dp_scaling",           # two-level DP engine wire bytes + rate
    "benchmarks.roofline_report",      # dry-run roofline table (§Roofline)
)


def main() -> None:
    failures = []
    for mod_name in MODULES:
        print(f"\n=== {mod_name} ===", flush=True)
        t0 = time.time()
        try:
            mod = __import__(mod_name, fromlist=["main"])
            mod.main()
            print(f"# {mod_name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures.append(mod_name)
            traceback.print_exc()
    if failures:
        print(f"\nFAILED benchmarks: {failures}")
        sys.exit(1)
    print("\nall benchmarks complete")


if __name__ == "__main__":
    main()
