"""Readings of the compared numbers, for setting a cell's limits.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --control-seeds 4,5,6 [--seconds 2]

In one process (set-up is paid once), runs the cell's window and
comparison on each seed as the benchmark does, first with the library
(``program``), then with the control put in its place: the cell's call
module's ``control``, the reference computed in the nearest precision
below the configuration's (for osu_allreduce, bfloat16 for float32).
Prints one JSON line per run and exits 0; a chip is needed as for the
benchmark.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", required=True)
    p.add_argument("--seconds", type=float, default=2.0)
    args = p.parse_args(argv)
    from benchmark import harness

    cell = harness.load_cell(args.workload)
    runs = [("program", int(s), None) for s in args.seeds.split(",")]
    runs += [("control", int(s), cell.call.control)
             for s in args.control_seeds.split(",")]
    for who, seed, call in runs:
        t0 = time.perf_counter()
        res = harness.run(cell, seed, args.seconds, False, t0, call=call)
        print(json.dumps({"workload": args.workload, "who": who,
                          "seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "check": res["check"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
