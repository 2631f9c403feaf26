"""The one traffic generator: a closed loop of blocking calls whose
message sizes a mix file (``traffic/<name>.json``) lists.

A mix gives ``sizes_bytes`` (bytes per rank per call), ``repeats_per_cycle``
(how often each size comes in one cycle) and ``inputs_per_size`` (how
many distinct send buffers each size has).  One cycle is
``repeats_per_cycle`` rounds, each round every size once in an order
drawn from the seed: every seed does the same work in another order,
and no seed's order bunches the large messages together.  The window
runs cycles back to back.  ``checked_per_size`` positions of each size
are kept for the comparison with the reference.  What a size must be
(a whole number of the call's elements), and any key of its own, the
cell's call module checks.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

MIX_KEYS = ("loop", "callers", "sizes_bytes", "repeats_per_cycle",
            "inputs_per_size", "checked_per_size", "trace_seconds")


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    missing = [k for k in MIX_KEYS if k not in mix]
    if missing:
        raise ValueError(f"traffic mix {path} lacks {missing}")
    if mix["loop"] != "closed" or mix["callers"] != 1:
        raise ValueError(f"{path}: this generator drives one closed-loop caller")
    return mix


def cycle(mix: dict, seed: int) -> list[tuple[int, int]]:
    """One cycle of ``(size index, input slot)`` pairs: rounds of every
    size once, each round shuffled by ``seed``; a size's occurrences take
    its input slots in turn."""
    n_sizes, reps = len(mix["sizes_bytes"]), mix["repeats_per_cycle"]
    rng = np.random.default_rng([seed, 0])
    order = np.concatenate([rng.permutation(n_sizes) for _ in range(reps)])
    seen = [0] * n_sizes
    out = []
    for i in order.tolist():
        out.append((i, seen[i] % mix["inputs_per_size"]))
        seen[i] += 1
    return out


def checked_positions(mix: dict, sched: list[tuple[int, int]],
                      seed: int) -> list[int]:
    """Positions in the cycle whose latest result is compared with the
    reference: ``checked_per_size`` of each size, drawn from ``seed``."""
    rng = np.random.default_rng([seed, 1])
    picks = []
    for i in range(len(mix["sizes_bytes"])):
        where = [p for p, (s, _) in enumerate(sched) if s == i]
        k = min(mix["checked_per_size"], len(where))
        picks += rng.choice(where, size=k, replace=False).tolist()
    return sorted(picks)
