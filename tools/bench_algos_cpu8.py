"""coll/base algorithm family on the 8-device virtual CPU mesh —
RELATIVE timings (VERDICT r3 next #4).

Every ICI perf number the driver sees is n_ranks=1 on the one real
chip, where ring/bruck/rabenseifner degenerate to identity; this leg
runs the actual multi-device schedules (n=8) so algorithm-level
regressions are visible as relative movement even though CPU-mesh
emulation says nothing absolute about TPU.  Matches SURVEY §4's
oversubscribed-emulation technique.

Prints ONE line ``ALGOS8 {json}`` with per-algorithm µs at a small
(latency-regime) and large (bandwidth-regime) payload.
"""

import json
import os
import time

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import numpy as np
from jax import shard_map

from ompi_tpu.coll import base
from ompi_tpu.mesh import AXIS
from ompi_tpu.op import SUM

N = 8
MESH = jax.sharding.Mesh(np.array(jax.devices()[:N]), (AXIS,))

ALLREDUCE = {
    "psum": base.allreduce_psum,
    "ordered_linear": base.allreduce_ordered_linear,
    "ring": base.allreduce_ring,
    "ring_segmented": base.allreduce_ring_segmented,
    "recursive_doubling": base.allreduce_recursive_doubling,
    "rabenseifner": base.allreduce_rabenseifner,
}
ALLGATHER = {
    "direct": base.allgather_direct,
    "ring": base.allgather_ring,
    "bruck": base.allgather_bruck,
}
BCAST = {
    "direct": base.bcast_direct,
    "binomial": base.bcast_binomial,
    "pipeline": base.bcast_pipeline,
}
REDUCE = {
    "binomial": base.reduce_binomial,
    "ordered": base.reduce_ordered,
}
REDUCE_SCATTER = {
    "direct": base.reduce_scatter_direct,
    "ring": base.reduce_scatter_ring,
    "ordered": base.reduce_scatter_ordered,
}
ALLTOALL = {
    "direct": base.alltoall_direct,
    "pairwise": base.alltoall_pairwise,
}
BARRIER = {
    "allreduce": base.barrier_allreduce,
    "dissemination": base.barrier_dissemination,
}


def timed(fn, x, iters, out_specs=None):
    # the replication check must be OFF: the schedules return
    # rank-varying intermediates on purpose
    f = jax.jit(
        shard_map(
            fn, mesh=MESH,
            in_specs=jax.sharding.PartitionSpec(AXIS),
            out_specs=(jax.sharding.PartitionSpec(AXIS)
                       if out_specs is None else out_specs),
            check_vma=False,
        )
    )
    jax.block_until_ready(f(x))  # compile
    # best-of-3 batches: emulation jitter is multiplicative, the min
    # is the honest estimate of the schedule's cost
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = f(x)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e6


def main() -> None:
    """All SEVEN coll/base algorithm families (VERDICT r4 next #5):
    allreduce, allgather, bcast, reduce, reduce_scatter, alltoall,
    barrier — each variant timed at a latency-regime and a
    bandwidth-regime payload on the n=8 virtual mesh."""
    P = jax.sharding.PartitionSpec
    out = {"n_devices": N, "allreduce": {}, "allgather": {}, "bcast": {},
           "reduce": {}, "reduce_scatter": {}, "alltoall": {},
           "barrier": {}}
    for regime, elems, iters in (("small_us", 256, 30),
                                 ("large_us", 1 << 20, 5)):
        x = np.ones((N, elems), np.float32)
        # (N, N, blk) layout for the block-distributed families
        xb = np.ones((N, N, max(1, elems // N)), np.float32)
        for name, fn in ALLREDUCE.items():
            wrapped = (lambda f: lambda v: f(v, SUM, N))(fn)
            out["allreduce"].setdefault(name, {})[regime] = round(
                timed(wrapped, x, iters), 1)
        for name, fn in ALLGATHER.items():
            g = (lambda f: lambda v: f(v, N))(fn)
            out["allgather"].setdefault(name, {})[regime] = round(
                timed(g, x, iters, out_specs=P()), 1)
        for name, fn in BCAST.items():
            b = (lambda f: lambda v: f(v, N, 0))(fn)
            out["bcast"].setdefault(name, {})[regime] = round(
                timed(b, x, iters), 1)
        for name, fn in REDUCE.items():
            r = (lambda f: lambda v: f(v, SUM, N, 0))(fn)
            out["reduce"].setdefault(name, {})[regime] = round(
                timed(r, x, iters), 1)
        for name, fn in REDUCE_SCATTER.items():
            rs = (lambda f: lambda v: f(v[0], SUM, N))(fn)
            out["reduce_scatter"].setdefault(name, {})[regime] = round(
                timed(rs, xb, iters), 1)
        for name, fn in ALLTOALL.items():
            a2a = (lambda f: lambda v: f(v[0], N))(fn)
            out["alltoall"].setdefault(name, {})[regime] = round(
                timed(a2a, xb, iters), 1)
        if regime == "small_us":  # barriers carry no payload
            for name, fn in BARRIER.items():
                bar = (lambda f: lambda v: v[0, :1] + f(N).astype(
                    np.float32))(fn)
                out["barrier"].setdefault(name, {})[regime] = round(
                    timed(bar, x, iters), 1)
    print("ALGOS8 " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
