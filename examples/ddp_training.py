"""Data-parallel training on the framework — the MPI user's workflow.

The pattern every reference user runs (gradient allreduce under a
training loop), expressed two ways:

* :func:`train_step_host` — the MPI-API form: compute local gradients,
  ``comm.allreduce`` them (host in/out), apply — how a C/Fortran MPI
  code does DDP;
* :func:`make_fused_step` — the TPU-native form: ONE jitted program
  over the mesh where the gradient sync is the framework's ring
  allreduce schedule from ``coll/base``, fused by XLA with the
  backward pass (no host round-trip per step).

Model: a small MLP regression (enough to prove loss descent and
bit-identical replicas).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ompi_tpu.coll import base as algos
from ompi_tpu.mesh import AXIS
from ompi_tpu.op import SUM


def init_params(rng: np.random.RandomState, din=8, dh=32, dout=1):
    return {
        "w1": rng.randn(din, dh).astype(np.float32) * 0.3,
        "b1": np.zeros(dh, np.float32),
        "w2": rng.randn(dh, dout).astype(np.float32) * 0.3,
        "b2": np.zeros(dout, np.float32),
    }


def _forward(params, x):
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


def _loss(params, x, y):
    return jnp.mean((_forward(params, x) - y) ** 2)


def train_step_host(comm, params, x_local, y_local, lr=0.05):
    """One DDP step through the MPI API: local grads → allreduce → SGD.
    ``x_local``/``y_local``: rank-major (n, batch/n, ...) shards."""
    n = comm.size
    grads = [
        jax.grad(_loss)(params, jnp.asarray(x_local[r]),
                        jnp.asarray(y_local[r]))
        for r in range(n)
    ]
    new = {}
    for key in params:
        stacked = np.stack([np.asarray(g[key]) for g in grads])
        summed = np.asarray(comm.allreduce(stacked, SUM))[0]
        new[key] = params[key] - lr * summed / n
    return new


def make_fused_step(mesh, n: int, lr=0.05):
    """The TPU-native step: grad + ring-allreduce + SGD in ONE compiled
    program (the sync rides coll/base's ppermute ring inside the jit,
    so XLA overlaps it with the backward)."""

    def per_device(params, x, y):
        x, y = x[0], y[0]
        g = jax.grad(_loss)(jax.tree.map(lambda p: p[0], params), x, y)
        g = jax.tree.map(lambda t: algos.allreduce_ring(t, SUM, n), g)
        return jax.tree.map(
            lambda p, gr: (p[0] - lr * gr / n)[None], params, g
        )

    f = shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS)),
        out_specs=P(AXIS),
    )
    return jax.jit(f)


def replicate(params, n: int):
    """Rank-major replication of the parameter pytree."""
    return jax.tree.map(lambda p: np.broadcast_to(p, (n,) + p.shape).copy(),
                        params)
