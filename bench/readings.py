"""The readings that a training cell's limits are set from, in one
process: every number ``compare`` gives (``bench/harness/train.py``) for
the program on many seeds, for the control (the reference in the
configuration's control type, put in the program's place) on a few, and
for a fault planted under the timed path (``bench/faults.py``) on a few,
each printed as a JSON line with whether it passes the configuration's
limits.  No measured window: training's readings need none.

    python3 bench/readings.py --workload <cell> --seeds 1-12 \\
        --control-seeds 101-103 [--fault half_batch --fault-seeds 201-203]
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
from typing import Dict, Iterator, List  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import common  # noqa: E402


def _seeds(text: str) -> List[int]:
    out = []
    for part in filter(None, text.split(",")):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b or a) + 1))
    return out


def collect(cell: common.Cell, devices, seeds: List[int],
            control_seeds: List[int], fault: str = "",
            fault_seeds: List[int] = ()) -> Iterator[Dict]:
    """One record per seed: ``kind`` (program, control or fault:<name>),
    ``readings`` (every number of ``compare``, or the error a crash
    raised) and ``correct`` against the configuration's limits.  A control
    or fault that crashes has failed."""
    from bench import faults
    from bench.harness import train

    runs = [("program", s, None) for s in seeds]
    runs += [("control", s, None) for s in control_seeds]
    runs += [("fault:" + fault, s, faults.TRAIN[fault]) for s in fault_seeds]
    for kind, seed, wrap in runs:
        t = time.monotonic()
        try:
            if kind == "control":
                nums = train.reference_control(cell, seed)
                ok = all(c.ok for c in train.checks(cell.config, nums))
            else:
                captured = {}
                result, found = train.run(
                    cell, seed=seed, seconds=0.0, trace=False,
                    devices=devices, t_process=t, window=False,
                    wrap_step=wrap, readings=captured)
                nums = captured
                ok = bool(result["correct"]) and all(c.ok for c in found)
        except Exception as e:  # noqa: BLE001  (a crash is a failed run)
            nums, ok = {"error": repr(e)}, False
        yield dict(workload=cell.name, kind=kind, seed=seed, correct=ok,
                   readings=nums, seconds=time.monotonic() - t)
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default="",
                    help="a fault of bench/faults.py to plant")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    cell = common.load_cell(args.workload)
    common.enable_compile_cache()
    devices = common.require_chips(cell.chips)
    for rec in collect(cell, devices, _seeds(args.seeds),
                       _seeds(args.control_seeds), args.fault,
                       _seeds(args.fault_seeds)):
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
