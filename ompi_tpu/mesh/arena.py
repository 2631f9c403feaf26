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
  is a pool hit, no allocation, no H2D.
* **dropped results** — a blocking collective on device buffers whose
  output has its input's shape, dtype and sharding (allreduce, bcast,
  alltoall) keeps its latest result per call signature as a *spare*
  (``run_recycled``).  The next call of that signature
  passes the spare as a donated receive buffer, so XLA writes the new
  result into it and allocates nothing.  Ownership rule: a spare is
  reused only when the pool holds the sole reference to the array and
  to every per-chip shard object it has handed out
  (``sys.getrefcount``), and it is not deleted; anything the caller
  still holds, in a variable, a container or a shard view, is left
  alone.  Only results the communicator's device path made enter the
  pool: never a user input, a host-buffer result, or a non-blocking or
  persistent result.  The pool is LRU over signatures, at most
  ``_SPARE_CAP`` per communicator, and pins at most an eighth of a
  chip's ``bytes_limit`` per mesh.

Donation of staged inputs is controlled by ``--mca
accelerator_tpu_donate_staged`` (the
compiled-callable caches key on the var-store version, so toggling it
takes effect on the next resolution).
"""

from __future__ import annotations

import sys
import threading

import jax
import numpy as np

from ompi_tpu.tool import spc

#: free-list depth per (shape, dtype) signature — temporaries are tiny
#: (tokens/scratch); deeper lists would just pin HBM
_POOL_CAP = 4

#: most call signatures whose latest result a communicator keeps as a
#: spare; the bytes bound (an eighth of ``bytes_limit``) applies on top
#: where the backend reports a limit
_SPARE_CAP = 64

#: ``sys.getrefcount`` of a spare inside ``run_recycled`` when the pool's
#: entry is its only owner: the entry tuple, the local name, the
#: argument of ``getrefcount``
_SOLE_OWNER = 3


def _shard_refs(arr) -> tuple:
    """Reference counts of the per-chip shard objects the array caches
    (``addressable_data``/``addressable_shards`` hand these out).  The
    same call when a result is kept and when it is reused gives the same
    tuple only when nothing outside took a reference in between."""
    return tuple([sys.getrefcount(s) for s in arr._arrays])


class HbmArena:
    """Per-mesh staging manager: free-lists device temporaries and the
    device path's dropped results, counts H2D traffic and donation
    resolutions.  Cheap by construction — the per-call cost is one
    attribute test plus integer adds, and for a kept result a dict
    update and a few reference counts; everything signature-level
    (donation) is accounted at resolution time, not per call."""

    __slots__ = (
        "stage_calls", "stage_bytes", "donate_signatures",
        "pool_hits", "pool_allocs", "recycle_hits", "recycle_misses",
        "spare_limit", "_devices", "_spare_bytes", "_lock", "_free",
    )

    def __init__(self, devices):
        self.stage_calls = 0
        self.stage_bytes = 0
        #: call signatures resolved to a donating compiled program
        self.donate_signatures = 0
        self.pool_hits = 0
        self.pool_allocs = 0
        #: device-buffer calls that wrote into a spare / that allocated
        self.recycle_hits = 0
        self.recycle_misses = 0
        #: most bytes per chip that spares may pin: None until the
        #: first keep reads the devices' ``bytes_limit``, 0 where none
        #: is reported (``_SPARE_CAP`` alone bounds the pool then)
        self.spare_limit: int | None = None
        self._devices = tuple(devices)
        self._spare_bytes = 0
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

    # -- dropped results of the device path (spares) --------------------
    # A pool is a communicator's dict: signature -> (result, _shard_refs
    # of it when kept, bytes per chip), oldest use first.

    def run_recycled(self, pool: dict, sig, fn, rfn, x):
        """One blocking device-path call of signature ``sig``: into the
        signature's kept result, donated to ``rfn(x, recv)``, when the
        pool owned it alone and it is live, else into a fresh allocation
        (``fn(x)``).  The result becomes the signature's spare, evicting
        the least recently used signatures past the bounds.  Returns
        (result, whether it was recycled).  One method, and the bytes
        read off the old entry, because this runs on every call."""
        ent = pool.pop(sig, None)  # atomic: a concurrent call finds none
        out = None
        if ent is not None:
            spare = ent[0]
            if (sys.getrefcount(spare) == _SOLE_OWNER
                    and not spare.is_deleted()
                    and _shard_refs(spare) == ent[1]):
                try:
                    out = rfn(x, spare)
                except RuntimeError:  # the runtime refused the donation
                    out = None
            del spare
        recycled = out is not None
        if not recycled:
            out = fn(x)
        if spc._attached:
            spc.inc("recycle_hits" if recycled else "recycle_misses")
        refs = _shard_refs(out)
        nbytes = ent[2] if ent is not None else out.nbytes // len(refs)
        if self.spare_limit is None:
            self.spare_limit = self._read_limit()
        limit = self.spare_limit
        with self._lock:
            if recycled:
                self.recycle_hits += 1
            else:
                self.recycle_misses += 1
            if ent is not None:
                self._spare_bytes -= ent[2]
            if out is x:  # never keep a caller's own array
                return out, recycled
            old = pool.pop(sig, None)  # a concurrent call's, if any
            if old is not None:
                self._spare_bytes -= old[2]
            pool[sig] = (out, refs, nbytes)
            self._spare_bytes += nbytes
            while pool and (len(pool) > _SPARE_CAP
                            or (limit and self._spare_bytes > limit)):
                self._spare_bytes -= pool.pop(next(iter(pool)))[2]
        return out, recycled

    def _read_limit(self) -> int:
        """An eighth of the smallest ``bytes_limit`` of the mesh's
        devices; 0 (no bytes bound) where one reports none."""
        limits = [(d.memory_stats() or {}).get("bytes_limit")
                  for d in self._devices]
        return min(limits) // 8 if limits and all(limits) else 0

    def drop_spares(self, pool: dict) -> None:
        """Release every spare of one communicator (``Comm.free``)."""
        with self._lock:
            self._spare_bytes -= sum(ent[2] for ent in pool.values())
            pool.clear()

    def stats(self) -> dict:
        with self._lock:
            return {
                "stage_calls": self.stage_calls,
                "stage_bytes": self.stage_bytes,
                "donate_signatures": self.donate_signatures,
                "pool_hits": self.pool_hits,
                "pool_allocs": self.pool_allocs,
                "recycle_hits": self.recycle_hits,
                "recycle_misses": self.recycle_misses,
                "spare_bytes": self._spare_bytes,
            }
