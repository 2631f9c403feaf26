"""Ring attention over the framework's mesh — long-context sequence
parallelism on the collective substrate.

The sequence axis is sharded over the communicator's mesh (one block of
queries/keys/values per rank).  Each rank computes blockwise attention
against its local K/V, then the K/V blocks rotate around the ring with
``lax.ppermute`` — the SAME neighbor-exchange schedule the framework's
``coll/base`` ring collectives use — while softmax statistics (running
max + normalizer) accumulate online.  After n-1 rotations every query
block has attended to the FULL sequence with per-rank memory O(seq/n):
the long-context recipe (Ring Attention; blockwise online softmax).

Run on any ompi_tpu communicator::

    comm = api.init()
    out = ring_attention(comm, q, k, v)   # q,k,v: (n, block, heads, dh)

The math is exact (not an approximation): results match full attention
up to float tolerance, which ``tests/test_examples.py`` asserts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax import shard_map
from jax.sharding import PartitionSpec as P

from ompi_tpu.mesh import AXIS


def _block_attend(q, k, v, m_prev, l_prev, o_prev, scale):
    """One blockwise-attention accumulation step (online softmax).

    q: (B, H, D); k/v: (Bk, H, D); running stats m (B, H), l (B, H),
    o (B, H, D).  Einsums pin HIGHEST precision: the TPU MXU's default
    bf16-input mode costs ~1e-2 absolute error vs the dense oracle."""
    prec = lax.Precision.HIGHEST
    s = jnp.einsum("bhd,khd->bhk", q, k, precision=prec) * scale
    m_cur = jnp.max(s, axis=-1)  # (B, H)
    m_new = jnp.maximum(m_prev, m_cur)
    # rescale previous accumulators to the new max
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])  # (B, H, Bk)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    o_new = o_prev * alpha[..., None] + jnp.einsum(
        "bhk,khd->bhd", p, v, precision=prec
    )
    return m_new, l_new, o_new


def ring_attention_program(n: int):
    """The per-device ring-attention program for an n-rank mesh
    (use under ``shard_map`` with the framework's mesh AXIS)."""

    def per_device(q, k, v):
        # leading mesh axis of size 1 per device (rank-major convention)
        q, k, v = q[0], k[0], v[0]
        scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
        # fresh accumulators are device-varying state under shard_map's
        # manual-axes tracking (they'll differ per rank after step 1)
        # jax < 0.6 has no pcast and treats shard_map values as
        # device-varying already — identity there, pcast where it exists
        pcast = getattr(lax, "pcast", None)
        to_varying = ((lambda a: pcast(a, AXIS, to="varying"))
                      if pcast is not None else (lambda a: a))
        m0 = to_varying(jnp.full(q.shape[:-1], -jnp.inf, q.dtype))
        l0 = to_varying(jnp.zeros(q.shape[:-1], q.dtype))
        o0 = jnp.zeros_like(q)
        perm = [(i, (i + 1) % n) for i in range(n)]  # the ring

        # local block first, then n-1 rotate-and-attend steps — exactly
        # n-1 ppermutes (a final rotation would be dead communication)
        m, l, o = _block_attend(q, k, v, m0, l0, o0, scale)

        def step(carry, _):
            kb, vb, m, l, o = carry
            kb = lax.ppermute(kb, AXIS, perm)
            vb = lax.ppermute(vb, AXIS, perm)
            m, l, o = _block_attend(q, kb, vb, m, l, o, scale)
            return (kb, vb, m, l, o), None

        if n > 1:
            (_, _, m, l, o), _ = lax.scan(
                step, (k, v, m, l, o), None, length=n - 1
            )
        return (o / l[..., None])[None]

    return per_device


#: compiled-program cache: mesh → jitted ring program (jit's own cache
#: then keys on shapes/dtypes — repeat calls dispatch, not retrace);
#: bounded like coll/xla's cache so comm churn can't pin meshes forever
_compiled: dict = {}


def ring_attention(comm, q, k, v):
    """Full-sequence attention with the sequence axis sharded over the
    communicator's ranks.  q/k/v: rank-major (n, block, heads, dh)."""
    n = comm.size
    mesh = comm.mesh.mesh
    # n is NOT derivable from the mesh: a MultiProcComm's local mesh
    # can serve comms of different global sizes — key on both
    key = (mesh, n)
    fn = _compiled.get(key)
    if fn is None:
        if len(_compiled) > 64:
            _compiled.clear()
        fn = jax.jit(shard_map(
            ring_attention_program(n),
            mesh=mesh,
            in_specs=(P(AXIS), P(AXIS), P(AXIS)),
            out_specs=P(AXIS),
        ))
        _compiled[key] = fn
    qd = comm.mesh.stage_in(np.asarray(q))
    kd = comm.mesh.stage_in(np.asarray(k))
    vd = comm.mesh.stage_in(np.asarray(v))
    return np.asarray(fn(qd, kd, vd))


def reference_attention(q, k, v):
    """Dense full-sequence attention (the parity oracle)."""
    n, b, h, d = q.shape
    qf = np.asarray(q).reshape(n * b, h, d)
    kf = np.asarray(k).reshape(n * b, h, d)
    vf = np.asarray(v).reshape(n * b, h, d)
    s = np.einsum("bhd,khd->bhk", qf, kf) / np.sqrt(d)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    return np.einsum("bhk,khd->bhd", p, vf).reshape(n, b, h, d)
