"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs only on a TPU with at least as many chips as the cell asks for; it
exits non-zero and prints no result elsewhere.  Weights and inputs come
from ``--seed``; set-up warms every shape the cell uses and loads programs
from the persistent compile cache inside the checkout; the measured window
lasts ``--seconds``; then what the window produced is compared with the
plain reference in ``bench/reference.py``.  ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics and the
device's busy time from a profiler trace.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = common.load_cell(args.workload)
    common.enable_compile_cache()
    try:
        devices = common.require_chips(cell.chips)
    except common.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import repro  # noqa: F401  (the system under test must be present)

    harness = common.load_harness(cell.traffic["harness"])
    result, checks = harness.run(cell, seed=args.seed, seconds=args.seconds,
                                 trace=bool(args.trace), devices=devices,
                                 t_process=T_PROCESS)
    result["correct"] = bool(result["correct"]) and all(c.ok for c in checks)
    common.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
