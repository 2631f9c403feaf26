"""hbm_to_host: one rank's device buffer read back into a numpy array,
the copy that a checkpoint's save makes of each shard.

A call module that the tests add to a copy of the benchmark as a file,
to show that a one-chip cell, which no collective can make honestly,
comes without an edit to the harness and reports ``busbw_GBps`` on one
rank.  Inputs are standard normal ``(n, count)`` float32 buffers made
on the device from the seed.  A call reads its buffer back through a
fresh view of the same device memory: JAX keeps an array's host copy
once it has made one, so reading the input itself again would copy
nothing.  The result is compared bit for bit with the input.  The bus
bytes are the bytes that cross the rank's host link, ``S`` per call;
there is no floor model and no library span.
"""

from __future__ import annotations

import jax
import numpy as np

#: copies are not operations on the chip; ``benchmark/trace.py`` names
#: a copy from the device ``d2h``
DEVICE_OPS = "d2h"


def validate(mix: dict, cfg: dict) -> None:
    if any(s % 4 or s <= 0 for s in mix["sizes_bytes"]):
        raise ValueError("hbm_to_host: sizes must be whole float32 counts")


def _normals(key_data, shapes):
    keys = jax.random.split(jax.random.wrap_key_data(key_data), len(shapes))
    return tuple(jax.random.normal(k, s, np.float32)
                 for k, s in zip(keys, shapes))


def inputs(cfg: dict, mix: dict, seed: int, n: int, sharding, on_host: bool):
    k = mix["inputs_per_size"]
    shapes = tuple((n, s // 4) for s in mix["sizes_bytes"] for _ in range(k))
    key = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    flat = jax.jit(_normals, static_argnums=1,
                   out_shardings=(sharding,) * len(shapes))(key, shapes)
    jax.block_until_ready(flat)
    return [list(flat[i * k:(i + 1) * k])
            for i in range(len(mix["sizes_bytes"]))]


def _to_host(x) -> np.ndarray:
    view = jax.make_array_from_single_device_arrays(
        x.shape, x.sharding, [x.addressable_data(i)
                              for i in range(len(x.addressable_shards))])
    return np.asarray(view)


def bind(world, cfg: dict):
    return _to_host


def error(x: np.ndarray, out: np.ndarray, cfg: dict) -> float:
    """How many elements differ, bit for bit, from the input."""
    if out.shape != x.shape or out.dtype != x.dtype:
        return float(x.size)
    return float(np.count_nonzero(out.view(np.uint32) != x.view(np.uint32)))


def bus_bytes(nbytes: int, n: int) -> float:
    return float(nbytes)


def floor_s(nbytes: int, n: int, peaks: dict) -> None:
    return None


def control(x) -> np.ndarray:
    """The buffer read back rounded to bfloat16."""
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
