"""HBM arena — device-staging management for host-sourced buffers.

≈ ``opal/mca/mpool`` + ``opal/mca/rcache`` (SURVEY.md §2.3): the
reference preallocates registered host memory so NIC DMA never pays
per-call registration; the TPU analog is HBM staging for buffers that
enter through the host (numpy) API.

**Why there is no literal "H2D into a pooled buffer" path.**  Under
PJRT/IFRT a host→device transfer *always* materializes a new logical
buffer — there is no public API to overwrite an existing device
allocation with host bytes).  The mpool free-list therefore lives at
three levels, all of which this class owns or accounts:

* **runtime allocator recycling** — successive ``stage_in`` calls of
  the same signature land on XLA's BFC free list, so steady-state
  staging reuses the same HBM *addresses* (the allocator's doing; not
  counted here).
* **buffer donation** — compiled collectives for shape-preserving ops
  are built with ``donate_argnums`` when their input is the
  framework-owned staged buffer, so XLA writes the result into the
  SAME HBM allocation: steady state is ONE buffer per in-flight
  collective instead of two.  User jax arrays are NEVER donated
  (MPI semantics: sendbuf is preserved).
* **device-buffer free list** — ``acquire``/``release`` pool
  framework-internal device temporaries (barrier tokens, schedule
  scratch) keyed by (shape, dtype): after warm-up every acquisition
  is a pool hit, no allocation, no H2D.  The zero-per-call-alloc path
  for *user* payloads is the persistent-request family
  (``allreduce_init`` …): buffer staged once, program compiled once,
  each ``start()`` re-dispatches on the same allocation.

Donation is controlled by ``--mca accelerator_tpu_donate_staged`` (the
compiled-callable caches key on the var-store version, so toggling it
takes effect on the next resolution).
"""

from __future__ import annotations

import threading

import jax
import numpy as np

from ompi_tpu.tool import spc

#: free-list depth per (shape, dtype) signature — temporaries are tiny
#: (tokens/scratch); deeper lists would just pin HBM
_POOL_CAP = 4


class HbmArena:
    """Per-mesh staging manager: free-lists device temporaries, counts
    H2D traffic and donation resolutions.  Cheap by construction — the
    per-call cost is one attribute test plus integer adds; everything
    signature-level (donation) is accounted at resolution time, not per
    call."""

    __slots__ = (
        "stage_calls", "stage_bytes", "donate_signatures",
        "pool_hits", "pool_allocs", "_lock", "_free",
    )

    def __init__(self):
        self.stage_calls = 0
        self.stage_bytes = 0
        #: call signatures resolved to a donating compiled program
        self.donate_signatures = 0
        self.pool_hits = 0
        self.pool_allocs = 0
        self._lock = threading.Lock()
        #: (shape, dtype str) → free device buffers
        self._free: dict[tuple, list] = {}

    # -- staging accounting --------------------------------------------

    def stage_in(self, host_array: np.ndarray, sharding) -> jax.Array:
        with self._lock:
            self.stage_calls += 1
            self.stage_bytes += host_array.nbytes
        if spc.attached():
            spc.inc("arena_stage_in")
            spc.inc("arena_stage_bytes", host_array.nbytes)
        return jax.device_put(host_array, sharding)

    def note_donation(self) -> None:
        """A collective signature resolved to a donating program."""
        with self._lock:
            self.donate_signatures += 1
        if spc.attached():
            spc.inc("arena_donations")

    # -- device-temporary free list (mpool free list proper) -----------

    def acquire(self, shape: tuple, dtype, sharding) -> jax.Array:
        """A pooled device buffer of the given signature: pool hit when
        one is free, fresh allocation otherwise.  Contents are
        **unspecified** (pool hits return stale bytes — callers use
        these strictly as tokens/scratch whose values are never read;
        there is deliberately no fill parameter so value-dependent use
        cannot be expressed).  The sharding is part of the pool key — a
        replicated token is never served where a rank-sharded one was
        asked for."""
        key = (tuple(shape), np.dtype(dtype).str, sharding)
        with self._lock:
            lst = self._free.get(key)
            while lst:
                buf = lst.pop()
                if not buf.is_deleted():
                    self.pool_hits += 1
                    return buf
            self.pool_allocs += 1
        if spc.attached():
            spc.inc("arena_pool_alloc")
        return jax.device_put(
            np.zeros(shape, np.dtype(dtype)), sharding)

    def release(self, buf: jax.Array) -> None:
        """Return a buffer to the free list (drops it when full or when
        XLA already consumed it through donation)."""
        if buf is None or buf.is_deleted():
            return
        key = (tuple(buf.shape), buf.dtype.str, buf.sharding)
        with self._lock:
            if len(self._free) > 256:  # unbounded-signature backstop:
                self._free.clear()     # drop pooled HBM, keep counters
            lst = self._free.setdefault(key, [])
            if len(lst) < _POOL_CAP:
                lst.append(buf)

    def stats(self) -> dict:
        with self._lock:
            return {
                "stage_calls": self.stage_calls,
                "stage_bytes": self.stage_bytes,
                "donate_signatures": self.donate_signatures,
                "pool_hits": self.pool_hits,
                "pool_allocs": self.pool_allocs,
            }
