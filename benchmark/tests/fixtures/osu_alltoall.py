"""osu_alltoall: ``Comm.alltoall(x)`` of rank-major ``(n, n, blk)``
float32 blocks, as OSU Micro-Benchmarks' ``osu_alltoall`` calls
``MPI_Alltoall``: rank r's block j goes to rank j as its block r.

A call module that the tests add to a copy of the benchmark as a file,
to show that a collective other than allreduce comes without an edit
to the harness, the generator or the readers.  A size is the bytes each
rank sends in one call: ``n`` blocks of ``blk`` float32.  The reference
is the block transpose ``out[j, r] = x[r, j]``, compared bit for bit;
the bus bytes are nccl-tests' ``(n-1)/n * S``.
"""

from __future__ import annotations

import jax
import numpy as np

DEVICE_OPS = "all-to-all"


def validate(mix: dict, cfg: dict) -> None:
    if any(s <= 0 or s % (4 * cfg["ranks"]) for s in mix["sizes_bytes"]):
        raise ValueError("osu_alltoall: sizes must be whole float32 blocks "
                         "for every rank")


def inputs(cfg: dict, mix: dict, seed: int, n: int, sharding, on_host: bool):
    rng = np.random.default_rng(seed)
    out = []
    for s in mix["sizes_bytes"]:
        slots = [rng.standard_normal((n, n, s // (4 * n)), dtype=np.float32)
                 for _ in range(mix["inputs_per_size"])]
        out.append(slots if on_host
                   else [jax.device_put(a, sharding) for a in slots])
    return out


def bind(world, cfg: dict):
    return world.alltoall


def error(x: np.ndarray, out: np.ndarray, cfg: dict) -> float:
    """How many elements differ, bit for bit, from the block transpose."""
    ref = x.swapaxes(0, 1)
    if out.shape != ref.shape or out.dtype != ref.dtype:
        return float(x.size)
    return float(np.count_nonzero(out.view(np.uint32) != ref.view(np.uint32)))


def bus_bytes(nbytes: int, n: int) -> float:
    return (n - 1) / n * nbytes


def floor_s(nbytes: int, n: int, peaks: dict) -> None:
    return None  # no floor model for an all-to-all here


def control(x):
    """The exchange done right on blocks rounded to bfloat16."""
    import jax.numpy as jnp

    low = jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32)
    out = jnp.swapaxes(low, 0, 1)
    if isinstance(x, np.ndarray):
        return np.asarray(out)
    return jax.device_put(out, x.sharding)
