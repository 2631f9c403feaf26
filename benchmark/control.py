"""Readings of the compared numbers, for setting a cell's limits.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --control-seeds 4,5,6 [--seconds 2]

In one process (set-up is paid once), runs the cell's window and
comparison on each seed as the benchmark does, first with the library
(``program``), then with the control put in its place: the reference
computed in bfloat16, the nearest precision below the configuration's
float32.  Prints one JSON line per run and exits 0; a chip is needed
as for the benchmark.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def bf16_sum(x):
    """The reference in bfloat16: every rank's buffer rounded to bfloat16,
    summed in bfloat16, returned as float32 to every rank, in the form
    the caller passed (host numpy or a device array)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    low = jnp.asarray(x).astype(jnp.bfloat16)
    out = jnp.broadcast_to(low.sum(0, dtype=jnp.bfloat16), low.shape)
    out = out.astype(jnp.float32)
    if isinstance(x, np.ndarray):
        return np.asarray(out)
    return jax.device_put(out, x.sharding)


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
    runs += [("control", int(s), bf16_sum)
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
