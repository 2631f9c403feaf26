"""Pallas ring-collective kernels — the device-DMA schedule family.

The coll/xla algorithm families built from ``lax`` collectives leave
the per-hop data movement to XLA's collective lowering.  This module
supplies the third family: **ring schedules whose hop primitive is an
explicit Pallas kernel** issuing an RDMA-style HBM→HBM DMA between
neighboring devices with send/recv semaphores
(``pltpu.make_async_remote_copy`` under ``shard_map`` — SNIPPETS.md
[1]; the snippet's right-permute kernel is exactly one hop of these
rings).  On TPU the kernel keeps every hop's bytes device-resident
with explicit semaphore ordering; the ring structure (chunk rotation,
fold bracketing) is IDENTICAL to ``coll.base``'s ring family, so
``MPI_SUM`` results are bit-exact against the host-plane schedules.

Degradation ladder (tier-1 runs under ``JAX_PLATFORMS=cpu``):

* **dma** — a TPU backend is present: the hop is a
  ``pl.pallas_call`` around ``make_async_remote_copy`` (start → wait
  on both semaphores — the send/recv semaphore pair the DCN device
  plane maps RTS/CTS onto).
* **interpret** — ``--mca dcn_device_interpret 1``: the hop's kernel
  BODY (the copy semantics) executes under the Pallas interpreter
  (``interpret=True``) after a ``lax.ppermute`` carries the bytes
  between the virtual devices — the same kernel code path, CPU-
  debuggable, deterministic.
* **emulate** (default off-TPU) — the hop is a plain
  ``lax.ppermute``: the structured ring-permute emulation with the
  exact schedule shape, so tests exercise chunk rotation, fold
  order, and the decision tables without Pallas in the loop.

Every public function here is a **per-device function** meant to run
inside ``coll/xla``'s ``shard_map`` wrapper (the ``_spmd`` factory),
exactly like the ``coll.base`` algorithms.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ompi_tpu.mesh import AXIS
from ompi_tpu.op.op import Op

__all__ = [
    "mode", "dma_available", "ring_hop",
    "ring_allreduce", "ring_allgather", "ring_reduce_scatter",
]


@functools.lru_cache(maxsize=1)
def dma_available() -> bool:
    """True when the default backend is a TPU — the only platform the
    async-remote-copy DMA leg lowers on.  A backend that fails to
    initialize raises here: on the chip, a swallowed error would
    quietly swap the DMA kernel for a ``ppermute``."""
    return jax.devices()[0].platform == "tpu"


def _interpret_forced() -> bool:
    try:
        from ompi_tpu.core import mca

        return bool(mca.default_context().store.get(
            "dcn_device_interpret", False))
    except Exception:  # noqa: BLE001 — pre-init: default off
        return False


def mode() -> str:
    """Which hop implementation this process compiles: ``dma`` |
    ``interpret`` | ``emulate``.  The forced-interpret knob wins even
    when a TPU is attached — that is the one platform where an
    operator debugging a miscompiling DMA kernel needs it."""
    if _interpret_forced():
        return "interpret"
    return "dma" if dma_available() else "emulate"


def _ring_perm(n: int) -> list[tuple[int, int]]:
    return [(i, (i + 1) % n) for i in range(n)]


# -- the hop kernel ------------------------------------------------------

def _copy_kernel(src_ref, dst_ref):
    """The hop body under the interpreter: what lands on the receiving
    device (the DMA's effect, minus the wire)."""
    dst_ref[...] = src_ref[...]


def _remote_hop_kernel(x_ref, o_ref, send_sem, recv_sem, *, n: int):
    """One right-rotation hop as an explicit remote DMA: start the
    HBM→HBM copy toward the right neighbor, then wait BOTH semaphores
    — send (our buffer is reusable) and recv (the left neighbor's
    bytes have landed).  The send/recv semaphore pair is the exact
    protocol the DCN device plane maps RTS/CTS onto.

    The copy lands in the right neighbor's ``o_ref``, whose HBM XLA
    may have assigned from a buffer an earlier op on that device still
    reads.  So the hop opens with a one-way neighbor barrier: each
    device tells its LEFT neighbor (the one that writes into it) that
    it has entered this hop, and waits for the same word from its
    RIGHT neighbor before it starts the copy.  One signal per device
    per hop keeps the count exact across the hops of a schedule: the
    right neighbor can enter hop k+1 only after our hop-k copy into it
    completed, so our hop-k wait never consumes a hop-k+1 signal, and
    a signal that arrives early is consumed by the next hop's wait
    (every device runs the same sequence of hop kernels)."""
    from jax.experimental.pallas import tpu as pltpu

    my_id = lax.axis_index(AXIS)  # int32, also under jax_enable_x64
    n32 = jnp.int32(n)
    right = lax.rem(my_id + 1, n32)
    left = lax.rem(my_id + n32 - 1, n32)
    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(barrier, 1, device_id=(left,),
                           device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(barrier, 1)
    copy = pltpu.make_async_remote_copy(
        src_ref=x_ref,
        dst_ref=o_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=(right,),
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    copy.start()
    copy.wait()


def _dma_hop(x, n: int):
    """TPU leg: the pallas_call wrapping one remote-copy hop.  Every
    hop kernel shares ``collective_id`` 0: they all synchronize with
    the same ring neighbors on the one mesh axis."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA] * 2,
    )
    return pl.pallas_call(
        functools.partial(_remote_hop_kernel, n=n),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(collective_id=0),
    )(x)


def ring_hop(x, n: int, _mode: str | None = None):
    """One ring hop (right rotation): device r's ``x`` arrives on
    device ``(r+1) % n``.  The single communication primitive every
    schedule below is built from."""
    m = _mode or mode()
    if m == "dma":
        return _dma_hop(x, n)
    y = lax.ppermute(x, AXIS, _ring_perm(n))
    if m == "interpret":
        from jax.experimental import pallas as pl

        y = pl.pallas_call(
            _copy_kernel,
            out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
            interpret=True,
        )(y)
    return y


# -- ring schedules (chunk rotation identical to coll.base's rings) -----

def _pad_chunks(x, n: int):
    """Flatten + pad so the payload splits into n equal chunks —
    the same chunking coll.base's ring uses."""
    flat = x.reshape(-1)
    size = flat.shape[0]
    pad = (-size) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(n, -1), size


def _unpad(flat, size: int, shape):
    return flat.reshape(-1)[:size].reshape(shape)


def ring_allreduce(x, op: Op, n: int, _mode: str | None = None):
    """Ring reduce-scatter + ring allgather with the Pallas hop:
    2(n-1)/n · size bytes per device per direction, every hop an
    explicit DMA.  Chunk rotation and fold bracketing mirror
    ``coll.base.allreduce_ring`` exactly (bit-exact MPI_SUM against
    it); commutative ops only, like every ring."""
    if n == 1:
        return x
    m = _mode or mode()
    idx = lax.axis_index(AXIS)
    chunks, size = _pad_chunks(x, n)
    # reduce-scatter: at step s device r DMAs chunk (r - s) right and
    # folds the left neighbor's arrival into chunk (r - s - 1)
    for s in range(n - 1):
        send_idx = (idx - s) % n
        recv_idx = (idx - s - 1) % n
        send = jnp.take(chunks, send_idx, axis=0)
        recv = ring_hop(send, n, m)
        mine = jnp.take(chunks, recv_idx, axis=0)
        chunks = lax.dynamic_update_index_in_dim(
            chunks, op.jax_fn(mine, recv), recv_idx, 0)
    # allgather: rotate the owned fully-reduced chunk around the ring
    own_idx = (idx + 1) % n
    cur = jnp.take(chunks, own_idx, axis=0)
    for s in range(n - 1):
        cur = ring_hop(cur, n, m)
        write_idx = (idx - s) % n
        chunks = lax.dynamic_update_index_in_dim(chunks, cur, write_idx, 0)
    return _unpad(chunks, size, x.shape)


def ring_allgather(x, n: int, _mode: str | None = None):
    """(…)-shaped per-device block → (n, …) gathered rows, n-1 DMA
    hops each forwarding the newest block (coll.base.allgather_ring's
    schedule on the Pallas hop)."""
    if n == 1:
        return x[None]
    m = _mode or mode()
    idx = lax.axis_index(AXIS)
    out = jnp.zeros((n,) + x.shape, x.dtype)
    out = lax.dynamic_update_index_in_dim(out, x, idx, 0)
    cur = x
    for s in range(n - 1):
        cur = ring_hop(cur, n, m)
        src = (idx - s - 1) % n
        out = lax.dynamic_update_index_in_dim(out, cur, src, 0)
    return out


def ring_reduce_scatter(x, op: Op, n: int, _mode: str | None = None):
    """(n, …) rank-major contributions → this device's reduced row:
    the partial for block b starts at rank (b+1)%n and accumulates
    while traveling the ring until it reaches its owner — the exact
    schedule (and fold bracketing, so bit-exact MPI_SUM) of
    ``coll.base.reduce_scatter_ring``, on the Pallas hop.
    Commutative ops only, like every ring."""
    if n == 1:
        return x[0]
    m = _mode or mode()
    idx = lax.axis_index(AXIS)
    cur = jnp.take(x, (idx - 1) % n, axis=0)
    for s in range(n - 1):
        cur = ring_hop(cur, n, m)
        # received: partial for block b = idx - s - 2; add our own
        b = (idx - s - 2) % n
        cur = op.jax_fn(cur, jnp.take(x, b, axis=0))
    return cur
