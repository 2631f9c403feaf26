"""osu_allreduce: ``Comm.allreduce(x, op)`` of rank-major float32
buffers, as OSU Micro-Benchmarks' ``osu_allreduce`` calls
``MPI_Allreduce``.

A call module is what the harness knows of one collective; a
configuration names it by its ``benchmark`` key.  Inputs are standard
normal ``(n, count)`` float32 buffers made from the seed; a result is
compared with ``reference.max_err_eps``, the float64 fold over ranks;
the bus bytes and the least time per call are ``arith``'s allreduce
model, which gives no bus bytes to one rank; the control is the
reference computed in bfloat16.
"""

from __future__ import annotations

import jax
import numpy as np

from benchmark import arith, reference

#: the HLO opcode prefix of the operation that does the work on the chips
DEVICE_OPS = "all-reduce"
#: the library's span around one call (``benchmark/libspans.py``)
API_SPAN = "ompi.api.allreduce"


def validate(mix: dict, cfg: dict) -> None:
    if any(s % 4 or s <= 0 for s in mix["sizes_bytes"]):
        raise ValueError("osu_allreduce: sizes must be whole float32 counts")


def _normals(key_data, shapes):
    keys = jax.random.split(jax.random.wrap_key_data(key_data), len(shapes))
    return tuple(jax.random.normal(k, s, np.float32)
                 for k, s in zip(keys, shapes))


def inputs(cfg: dict, mix: dict, seed: int, n: int, sharding, on_host: bool):
    """``inputs[size index][slot]``: standard normal float32 rank-major
    (n, count) buffers made on the device in one jitted call from
    ``seed``; host buffers are copied to numpy before the window."""
    k = mix["inputs_per_size"]
    shapes = tuple((n, s // 4) for s in mix["sizes_bytes"] for _ in range(k))
    key = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    make = jax.jit(_normals, static_argnums=1,
                   out_shardings=(sharding,) * len(shapes))
    flat = list(make(key, shapes))
    jax.block_until_ready(flat)
    if on_host:
        for i, a in enumerate(flat):
            flat[i] = np.asarray(a)
            a.delete()
    return [flat[i * k:(i + 1) * k] for i in range(len(mix["sizes_bytes"]))]


def bind(world, cfg: dict):
    from ompi_tpu import op as ops

    op = getattr(ops, cfg["op"])
    return lambda x: world.allreduce(x, op)


def error(x: np.ndarray, out: np.ndarray, cfg: dict) -> float:
    return reference.max_err_eps(x, out)


def bus_bytes(nbytes: int, n: int) -> float | None:
    """``2(n-1)/n * S``; None on one rank, where the model gives 0 and
    an allreduce moves nothing across a link."""
    return arith.bus_bytes(nbytes, n) if n >= 2 else None


def floor_s(nbytes: int, n: int, peaks: dict) -> float:
    return arith.allreduce_floor_s(nbytes, n, peaks)[0]


def control(x):
    """The reference in bfloat16: every rank's buffer rounded to bfloat16,
    summed in bfloat16, returned as float32 to every rank, in the form
    the caller passed (host numpy or a device array)."""
    import jax.numpy as jnp

    low = jnp.asarray(x).astype(jnp.bfloat16)
    out = jnp.broadcast_to(low.sum(0, dtype=jnp.bfloat16), low.shape)
    out = out.astype(jnp.float32)
    if isinstance(x, np.ndarray):
        return np.asarray(out)
    return jax.device_put(out, x.sharding)
